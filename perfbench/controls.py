"""Readings from which the limits of ``correct`` are set: every compared
number for sound runs of the program on many seeds, and for the control
(the reference computed in bfloat16, put in the program's place) on some
of them, at the cell's own size, all in one process.

    python3 perfbench/controls.py --workload fig4-grid --seeds 1 2 3 \
        --control-seeds 1 2 3

Each seed sets the cell up and runs one unit of its traffic, as a run of
``perfbench/run.py`` does before its window closes; one JSON line per
seed, with ``program`` and (for control seeds) ``control`` readings of
every number the check can compare (a cell's traffic holds some of them
to limits), and
a last line with the largest program reading and the smallest control
reading of each number. Needs a TPU, as the benchmark does.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench.core import spec  # noqa: E402


def readings(name: str, seeds, control_seeds, *, need_chip=True,
             traffic_override=None, config_override=None,
             emit=print):
    import jax
    from perfbench import run
    run.use_compile_cache(jax)
    bench = spec.load_benchmark()
    work, config, traffic = spec.cell(bench, name)
    traffic = {**traffic, **(traffic_override or {})}
    config = {**config, **(config_override or {})}
    if need_chip:
        run.device_info(jax, work["chips"])
    prog, ctrl = {}, {}
    for seed in seeds:
        t0 = time.perf_counter()
        drv = spec.driver(traffic["driver"]).Driver(config, traffic, seed)
        drv.setup()
        units = [drv.unit()]
        drv.release()
        line = {"seed": seed, "program": drv.numbers(units)}
        if seed in control_seeds:
            line["control"] = drv.numbers(units, control=True)
        line["seconds"] = time.perf_counter() - t0
        emit(json.dumps(line))
        for k, v in line["program"].items():
            prog[k] = max(prog.get(k, v), v)
        for k, v in line.get("control", {}).items():
            ctrl[k] = min(ctrl.get(k, v), v)
    emit(json.dumps({"lower": prog, "upper": ctrl}))
    return prog, ctrl


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    readings(args.workload, args.seeds, set(args.control_seeds),
             emit=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
