"""Finds everything that belongs to one cell by the names in
``BENCHMARK.json``: the cell's entry, its configuration file, its traffic
file (``perfbench/traffic/<traffic>.json``, which names the driver kind),
the driver module (``perfbench/drivers/<kind>.py``) and one reader module
per per-layer metric (``perfbench/metrics/<metric>.py``). A later cell,
configuration or metric is added as new files and entries alone."""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
PERFBENCH = ROOT / "perfbench"


class SpecError(Exception):
    """The benchmark's files do not describe the cell asked for."""


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: dict, name: str, root: pathlib.Path = ROOT):
    """(workload entry, configuration dict, traffic dict) of a cell."""
    work = _by_name(bench["workloads"], name, "workload")
    cfg_entry = _by_name(bench["configs"], work["config"], "config")
    config = json.loads((root / cfg_entry["file"]).read_text())
    path = root / "perfbench" / "traffic" / f"{work['traffic']}.json"
    if not path.is_file():
        raise SpecError(f"traffic {work['traffic']!r} has no file {path}")
    return work, config, json.loads(path.read_text())


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bench: dict, cell_name: str):
    return [m for m in bench["end_to_end"] if _applies(m, cell_name)]


def per_layer(bench: dict, cell_name: str):
    """Per-layer metrics read in this cell: those that list it, and those
    without a list whose moved end-to-end metric the cell reports."""
    reported = {m["name"] for m in end_to_end(bench, cell_name)}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in reported)]


def driver(kind: str):
    return importlib.import_module(f"perfbench.drivers.{kind}")


def reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read(ctx)`` function of ``perfbench/metrics/<metric>.py``, or,
    where that file is absent and the name is ``<quantity>.<suffix>``, of
    ``perfbench/metrics/<quantity>.py``: one quantity split by the
    end-to-end metric it moves keeps one reader."""
    base = root / "perfbench" / "metrics"
    path = base / f"{metric}.py"
    if not path.is_file() and "." in metric:
        path = base / f"{metric.rsplit('.', 1)[0]}.py"
    if not path.is_file():
        raise SpecError(f"per-layer metric {metric!r} has no reader "
                        f"{base / (metric + '.py')}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
