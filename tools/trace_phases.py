"""Splits a sim cell's event-loop trip by phase, and its device idle time
by host phase, from a profiler trace of one sweep unit.

    python3 tools/trace_phases.py --workload fig4-grid --seed 7 \\
        [--samples 40 --fleets 2,5] [--keep PATH]

The cell comes from ``BENCHMARK.json`` as ``perfbench/run.py`` builds it
(``--samples`` and ``--fleets`` cut it). The tool warms the cell up,
times one unit untraced, then traces one unit (its first
``trace_seconds`` where the traffic sets them; the unit runs on untraced)
and reduces the trace with ``perfbench/core/scopes.py``. It prints one
JSON object: the device time per trip of each phase scope of the sim
core (``jaxsim.exchange`` holds the device-sharded core's collectives)
and of the ops in none, the cell's per-layer metrics as the benchmark's
readers read them from the same trace, device idle time by host span,
the unit walls traced and untraced, and the reductions' own times.
``--keep`` copies the trace's ``.xplane.pb`` there. The device numbers
need a TPU: elsewhere the trace has no device plane and they read null.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import tempfile
import threading
import time
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench.core import scopes, spec, trace  # noqa: E402

PHASES = ("jaxsim.devices", "jaxsim.queue", "jaxsim.frontier",
          "jaxsim.boundary")
# the device-sharded core's collectives (none in the lane cores)
EXCHANGE = "jaxsim.exchange"
SPANS = ("jaxsim.prepare", "jaxsim.transfer", "jaxsim.execute")
# readers of the benchmark's per-layer metrics that read the trace alone
EXISTING = ("core_us_per_iter.sim", "sim.host_ms_per_sweep",
            "device_idle.sim")


def traced_unit(jax, drv, seconds, logdir):
    """One unit under the profiler, in a ``bench.window`` span that closes
    when the unit ends or after ``seconds``; returns the unit's wall and
    the unit."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    wall, units, failure = [], [], []

    def work():
        try:
            t0 = time.perf_counter()
            units.append(drv.unit())
            wall.append(time.perf_counter() - t0)
        except BaseException as e:  # re-raised by the caller's thread
            failure.append(e)
    worker = threading.Thread(target=work)
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            worker.start()
            worker.join(seconds)
    finally:
        jax.profiler.stop_trace()
    worker.join()
    if failure:
        raise failure[0]
    return wall[0], units[0]


def reduce(tr, metrics=EXISTING, units=()) -> dict:
    """The phase split of trace ``tr``, with the per-layer metrics
    ``metrics`` read from it by the benchmark's own readers (``units``:
    the traced units, for readers that read them)."""
    trips = scopes.event_trips(tr)
    per_trip = {s: scopes.scope_us_per_trip(tr, s)
                for s in PHASES + (EXCHANGE, scopes.EVENT_SCOPE,
                                   scopes.UNSCOPED)}
    busy = trace.busy_s(tr)
    phases = [per_trip[s] for s in PHASES + (EXCHANGE,)]
    ctx = types.SimpleNamespace(trace=tr, units=list(units))
    return {
        "trips": trips,
        "us_per_trip": per_trip,
        "phases_us_per_trip": (sum(phases) if None not in phases
                               else None),
        "busy_us_per_trip": busy * 1e6 / trips if trips else None,
        # the benchmark's per-layer metrics, read by its own readers
        "metrics": {m: spec.reader(m)(ctx) for m in metrics},
        "busy_s": busy, "window_s": tr.window_s,
        "idle_s_by_span": scopes.idle_s_by_span(tr),
        "idle_ms": {s: scopes.idle_ms_by_span(tr, s) for s in SPANS},
        "spans_s": {n: (b - a) * 1e-9 for a, b, n in tr.program_spans},
        "top_ops_by_scope": scopes.top_ops_by_scope(tr, 12),
        "n_ops": tr.n_ops,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--samples", type=int, help="samples per device")
    ap.add_argument("--fleets", help="devices per lane, comma-separated")
    ap.add_argument("--keep", help="copy the trace's .xplane.pb here")
    args = ap.parse_args(argv)

    import jax
    from perfbench import run
    run.use_compile_cache(jax)
    bench = spec.load_benchmark()
    _, config, traffic = spec.cell(bench, args.workload)
    if args.samples:
        config = dict(config, samples_per_device=args.samples)
    if args.fleets:
        traffic = dict(traffic,
                       fleets=[int(n) for n in args.fleets.split(",")])
    drv = spec.driver(traffic["driver"]).Driver(config, traffic, args.seed)
    t0 = time.perf_counter()
    drv.setup()
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    drv.unit()
    unit_s = time.perf_counter() - t0

    logdir = tempfile.mkdtemp(prefix="trace-phases-")
    try:
        traced_wall, unit = traced_unit(
            jax, drv, traffic.get("trace_seconds"), logdir)
        path = trace.find_xplane(logdir)
        if args.keep:
            shutil.copyfile(path, args.keep)
        t0 = time.perf_counter()
        trace.load(path)
        trace_load_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        tr = scopes.load(path)
        scopes_load_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        result = reduce(tr, [m["name"] for m in spec.per_layer(
            bench, args.workload)], [unit])
        result["reduce_s"] = time.perf_counter() - t0
        result["xplane_bytes"] = os.path.getsize(path)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    dev = jax.devices()[0]
    result.update(
        workload=args.workload, seed=args.seed,
        device={"platform": dev.platform, "kind": dev.device_kind},
        setup_s=setup_s, unit_s=unit_s, traced_unit_s=traced_wall,
        trace_load_s=trace_load_s, scopes_load_s=scopes_load_s)
    drv.release()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
