"""The sharded fleet cell (``fleet-100k-4chip``): the harness finds its
files by name, its driver refuses to start without the chips it shards
over, runs the sharded core and holds it to the reference on four CPU
devices (in a child process), and its exchange reader reads collectives
from a hand-made four-plane trace."""
import json
import os
import pathlib
import subprocess
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench.core import spec, trace  # noqa: E402
from perfbench.drivers import sim_sweep  # noqa: E402

CELL = "fleet-100k-4chip"
PLANES = [f"{trace.DEVICE_PREFIX}{i}" for i in range(4)]
TRIPS, PERIOD = 4, 100


def _planes_tr(collectives=("pmin.56", "all-reduce.13"), bare_plane=None):
    """Four planes, each running a loop of ``TRIPS`` trips of ``PERIOD``
    ns: ``fusion.1`` [0, 20], the collectives one after the other from 20
    (the first lasting 10 + the plane's index, the rest 15 each) and
    ``fusion.2`` [80, 90]; ``bare_plane`` runs no collective."""
    ops = {}
    for i, plane in enumerate(PLANES):
        evs = [(5, 395, "while.1")]
        for t in range(0, TRIPS * PERIOD, PERIOD):
            evs += [(t, t + 20, "fusion.1"), (t + 80, t + 90, "fusion.2")]
            if i == bare_plane:
                continue
            start = t + 20
            for k, name in enumerate(collectives):
                end = start + (10 + i if k == 0 else 15)
                evs.append((start, end, name))
                start = end
        ops[plane] = evs
    return trace.from_events(ops, {}, [(0, TRIPS * PERIOD, "bench.window")])


def _read(tr, count=21):
    ctx = types.SimpleNamespace(
        trace=tr, units=[{"out": {"exchanges_per_trip": count}}])
    return spec.reader("exchange_us_per_iter.sharded")(ctx)


def test_exchange_reader_by_hand():
    # per trip: (10 + i) + 15 ns on plane i, 26.5 ns averaged over planes
    assert _read(_planes_tr()) == pytest.approx(26.5e-3)
    # the halves of an asynchronous collective count once, their
    # intervals are its time
    halves = _planes_tr(("pmin.56", "all-reduce-start.2",
                         "all-reduce-done.2"))
    assert _read(halves, count=2) == pytest.approx(41.5e-3)


@pytest.mark.parametrize("tr,count", [
    (_planes_tr(bare_plane=2), 21),   # a plane with no collective
    (_planes_tr(), 1),                # more a trip than the program issues
    (_planes_tr(), None),             # a program that keeps no count
])
def test_exchange_reader_reads_nothing(tr, count):
    assert _read(tr, count) is None


def test_cell_found_by_name():
    bench = spec.load_benchmark()
    work, config, traffic = spec.cell(bench, CELL)
    assert work["chips"] == config["device_shards"] == 4
    assert config["devices"] == max(traffic["fleets"]) == 100_000
    drv = spec.driver(traffic["driver"]).Driver
    assert issubclass(drv, sim_sweep.Driver)
    assert [m["name"] for m in spec.per_layer(bench, CELL)] == [
        "core_us_per_iter.sharded", "device_idle.sharded",
        "exchange_us_per_iter.sharded"]
    assert [m["name"] for m in spec.end_to_end(bench, CELL)] == [
        "fleet_samples_per_s", "setup_s"]


def test_driver_refuses_without_its_chips(monkeypatch):
    import jax
    assert jax.device_count() < 4
    _, config, traffic = spec.cell(spec.load_benchmark(), CELL)

    def no_work(*_a, **_k):
        raise AssertionError("streams made before the chips were checked")
    monkeypatch.setattr(sim_sweep.synthetic, "chunked_device_streams",
                        no_work)
    monkeypatch.setattr(sim_sweep.synthetic, "batched_device_streams",
                        no_work)
    drv = spec.driver(traffic["driver"]).Driver(config, traffic, 7)
    with pytest.raises(RuntimeError, match="4 chips"):
        drv.setup()
    assert not hasattr(drv, "streams")


CHILD = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from perfbench import run
res = run.run_cell({cell!r}, 2**31 + 77, 0.5, False, need_chip=False,
                   traffic_override={{"fleets": [500]}},
                   config_override={{"samples_per_device": 24}})
print(json.dumps(res))
"""


def test_sharded_cell_runs_correct_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=str(ROOT), src=str(ROOT / "src"), cell=CELL)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    bad = {k: c for k, c in res["checks"].items()
           if not c["value"] <= c["limit"]}
    assert res["correct"] and not bad, bad
    assert res["failed"] == 0 and res["counters"]["window_compiles"] == 0
