"""The benchmark's copies of the traffic generators equal the program's
own at this commit, and the harness finds a new cell, configuration and
per-layer metric from new files alone.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""
import json
import pathlib
import shutil
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench.core import spec  # noqa: E402
from perfbench.traffic import synthetic  # noqa: E402


def _equal(a: dict, b: dict):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


@pytest.mark.parametrize("seeds,n,m", [((0,), 3, 50), ((2**31 + 5, 7), 5, 40)])
def test_batched_streams_equal_program(seeds, n, m):
    from repro.sim import synthetic as prog
    _equal(synthetic.batched_device_streams(seeds, n, m, 0.7185, [0.7829]),
           prog.batched_device_streams(seeds, n, m, 0.7185, [0.7829]))


@pytest.mark.parametrize("chunk", [1, 3, 64])
def test_chunked_streams_equal_program(chunk):
    from repro.sim import synthetic as prog
    ours = synthetic.chunked_device_streams((4, 9), 10, 6, 0.7185, [0.7829],
                                            chunk_devices=chunk)
    theirs = prog.chunked_device_streams((4, 9), 10, 6, 0.7185, [0.7829],
                                         chunk_devices=chunk).materialize()
    _equal(ours, theirs)
    _equal(ours, synthetic.batched_device_streams((4, 9), 10, 6, 0.7185,
                                                  [0.7829]))


def test_calibration_and_static_threshold_equal_program():
    from benchmarks import common
    from repro.configs.cascade_tiers import DEVICE_PROFILES, SERVER_PROFILES
    from repro.sim import synthetic as prog
    conf, cl, ch = synthetic.calibration_set(0.7185, 0.7829)
    cal = prog.calibration_set(0.7185, 0.7829)
    np.testing.assert_array_equal(conf, cal.confidence)
    np.testing.assert_array_equal(cl, cal.correct_light)
    np.testing.assert_array_equal(ch, cal.correct_heavy[:, 0])
    dev, srv = DEVICE_PROFILES["low"], SERVER_PROFILES["inceptionv3"]
    assert synthetic.static_threshold(dev.accuracy, srv.accuracy) == \
        common.static_threshold_for(dev, srv)


def test_tier_latencies_equal_fig_scale():
    from benchmarks import fig_scale
    got = synthetic.tier_latencies(300, 0.031, fig_scale.TIER_LAT_MULT,
                                   (0.9, 1.1), seed=1)
    want, _ = fig_scale._latencies(300, 0.031)
    np.testing.assert_array_equal(got, want)


def _tree(tmp_path: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark's files, as a checkout holds them."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def test_new_cell_config_and_metric_from_files_alone(tmp_path):
    root = _tree(tmp_path)
    bench = spec.load_benchmark(root)
    cfg = json.loads((root / "perfbench/configs/paper-t4-inceptionv3.json")
                     .read_text())
    cfg["name"] = "paper-t4-mid"
    cfg["device"]["accuracy"] = 0.7502
    (root / "perfbench/configs/paper-t4-mid.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((root / "perfbench/traffic/fig4-grid.json")
                         .read_text())
    traffic["fleets"] = [7]
    (root / "perfbench/traffic/tiny-grid.json").write_text(
        json.dumps(traffic))
    (root / "perfbench/metrics/sim.lanes.py").write_text(
        "def read(ctx):\n    return float(len(ctx.driver.lanes))\n")
    (root / "perfbench/metrics/lanes_seen.py").write_text(
        "def read(ctx):\n    return -1.0\n")
    bench["configs"].append({
        "name": "paper-t4-mid", "source": "https://arxiv.org/abs/2412.04147",
        "file": "perfbench/configs/paper-t4-mid.json", "reduced": [],
        "why": "mid tier"})
    bench["workloads"].append({
        "name": "tiny", "config": "paper-t4-mid", "traffic": "tiny-grid",
        "chips": 1, "why": "a test cell"})
    bench["end_to_end"][0]["workloads"].append("tiny")
    bench["per_layer"].append({
        "name": "sim.lanes", "unit": "lanes", "better": "higher",
        "source": "program_counter", "layer": "sim entry",
        "moves": "sim_samples_per_s", "workloads": ["tiny"]})
    # a quantity split by the rate it moves keeps one reader
    bench["per_layer"].append({
        "name": "lanes_seen.tiny", "unit": "lanes", "better": "higher",
        "source": "program_counter", "layer": "sim entry",
        "moves": "sim_samples_per_s", "workloads": ["tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = spec.load_benchmark(root)
    work, config, tr = spec.cell(bench, "tiny", root)
    assert config["device"]["accuracy"] == 0.7502 and tr["fleets"] == [7]
    assert spec.driver(tr["driver"]).Driver is not None
    names = [m["name"] for m in spec.per_layer(bench, "tiny")]
    assert names == ["sim.lanes", "lanes_seen.tiny"]
    ctx = type("Ctx", (), {"driver": type("D", (), {"lanes": [1, 2]})()})
    assert spec.reader("sim.lanes", root)(ctx) == 2.0
    assert spec.reader("lanes_seen.tiny", root)(ctx) == -1.0
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_reader.tiny", root)
    assert [m["name"] for m in spec.end_to_end(bench, "tiny")] == \
        ["sim_samples_per_s", "setup_s"]


def test_every_named_file_exists():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        spec.cell(bench, w["name"])
        for m in spec.per_layer(bench, w["name"]):
            assert callable(spec.reader(m["name"]))
        assert spec.end_to_end(bench, w["name"])
