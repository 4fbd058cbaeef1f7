"""Runs one cell of the benchmark once and prints its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by
name from ``BENCHMARK.json`` (see ``perfbench/core/spec.py``). A run:

1. refuses to go on without a TPU, or with fewer chips than the cell asks
   for (exit code 3, no result line);
2. set-up: the driver makes its inputs and weights from ``--seed`` and
   warms up every shape the cell uses. ``setup_s`` is the time from
   process start to the first timed unit;
3. ``--trace 0``: runs whole units of work back to back until
   ``--seconds`` have passed and reports the cell's end-to-end metrics;
   ``--trace 1``: runs one unit under the profiler and reports the cell's
   per-layer metrics, read from the trace by ``perfbench/metrics/``. Where
   the traffic sets ``trace_seconds``, the profiler records only that
   many seconds from the unit's start, and the unit runs on untraced to
   its end (a whole unit would hold more device events than the profiler
   keeps);
4. reads the device's peak memory, frees the program's state, and
   checks what the timed units produced against the plain reference
   (``perfbench/reference/``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), ``counters`` (compiles inside the window, units), and,
last, ``checks``: every number compared, with its limit. The checks are
also the last lines of standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench.core import spec  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
NO_READING = 1e30


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell needs."""


class Compiles:
    """Counts the programs XLA compiles in this process, and those it
    loads from the persistent cache instead (JAX reports a backend
    compile for both)."""

    def __init__(self, jax):
        self.programs = 0
        self.loads = 0

        def on_duration(event, duration, **_):
            if event == COMPILE_EVENT:
                self.programs += 1

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self.loads += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def n(self) -> int:
        return self.programs - self.loads


def use_compile_cache(jax) -> None:
    """JAX's persistent compilation cache, for every program however
    short its compile: where ``JAX_COMPILATION_CACHE_DIR`` says if it is
    set (JAX reads it itself), else at a fixed path inside the checkout
    (the path is part of what a later run must find again)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        CACHE_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(jax, chips: int):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def timed_window(drv, seconds: float, compiles: Compiles):
    """Whole units back to back until ``seconds`` have passed."""
    c0 = compiles.programs
    units = []
    start = time.perf_counter()
    while True:
        u0 = time.perf_counter()
        units.append(drv.unit())
        units[-1]["wall_s"] = time.perf_counter() - u0
        if time.perf_counter() - start >= seconds:
            break
    wall = time.perf_counter() - start
    return units, wall, compiles.programs - c0, start


def traced_window(jax, drv, compiles: Compiles, seconds=None):
    """One unit under the profiler, bounded by a ``bench.window`` span
    that closes when the unit ends or, if ``seconds`` is given, after
    that many seconds; the unit then runs on, untraced, to its end."""
    from perfbench.core import trace
    logdir = tempfile.mkdtemp(prefix="perfbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    c0 = compiles.programs
    units, failure = [], []

    def work():
        try:
            units.append(drv.unit())
        except BaseException as e:  # re-raised by the caller's thread
            failure.append(e)
    worker = threading.Thread(target=work)
    try:
        start = time.perf_counter()
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                worker.start()
                worker.join(seconds)
        finally:
            jax.profiler.stop_trace()
        worker.join()
        wall = time.perf_counter() - start
        if failure:
            raise failure[0]
        tr = trace.load(trace.find_xplane(logdir))
        if tr.n_ops >= trace.MAX_OPS:
            raise trace.Truncated(
                f"{tr.n_ops} device operations traced: the profiler keeps "
                f"about {trace.MAX_OPS} and drops the rest")
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    return units, wall, compiles.programs - c0, start, tr


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             root: pathlib.Path = ROOT, t0: float = T0,
             need_chip: bool = True, traffic_override=None,
             config_override=None) -> dict:
    """One run of cell ``name``; returns the result dict."""
    import jax
    use_compile_cache(jax)
    compiles = Compiles(jax)
    bench = spec.load_benchmark(root)
    work, config, traffic = spec.cell(bench, name, root)
    traffic = {**traffic, **(traffic_override or {})}
    config = {**config, **(config_override or {})}
    if need_chip:
        device = device_info(jax, work["chips"])
    else:
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": 1}
    jax_ready = time.perf_counter() - t0
    drv = spec.driver(traffic["driver"]).Driver(config, traffic, seed)
    drv.setup()
    setup_compiles, setup_loads = compiles.n, compiles.loads
    # set-up leaves millions of objects (traced programs, caches) that
    # every full collection would walk again, as a pause of a second or
    # more at a random point of the window: collect once and freeze them
    gc.collect()
    gc.freeze()
    if traced:
        units, wall, n_comp, start, tr = traced_window(
            jax, drv, compiles, traffic.get("trace_seconds"))
    else:
        units, wall, n_comp, start = timed_window(drv, seconds, compiles)
    setup_s = start - t0
    device["memory_peak_bytes"] = memory_peak(jax, work["chips"])
    result = {"correct": None,
              "attempted": sum(u["attempted"] for u in units),
              "failed": sum(u["failed"] for u in units)}
    if traced:
        from perfbench.core import trace
        ctx = Context(tr, drv, units, config, traffic)
        metrics = {}
        for m in spec.per_layer(bench, name):
            value = spec.reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(tr),
                               "idle_gaps": trace.idle_gaps(tr)}
    else:
        values = dict(drv.rates(units, wall), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end(bench, name)}
    drv.release()
    # a check with no reading (no window closed) fails as
    # a large finite number, so the line stays plain JSON
    checks = {k: (v if math.isfinite(v) else NO_READING, lim)
              for k, (v, lim) in drv.check(units).items()}
    result.update(
        correct=all(v <= lim for v, lim in checks.values()),
        metrics=metrics, device=device,
        counters={"window_compiles": n_comp, "units": len(units),
                  "unit_s": [u.get("wall_s") for u in units],
                  "unit_trips": [u.get("trips") for u in units],
                  "window_wall_s": wall, "setup_s": setup_s,
                  "setup_jax_ready_s": jax_ready,
                  "setup_compiles": setup_compiles,
                  "setup_cache_loads": setup_loads,
                  "trace_device_ops": tr.n_ops if traced else None},
        checks={k: {"value": v, "limit": lim}
                for k, (v, lim) in checks.items()})
    return result


class Context:
    """What a per-layer metric's reader may read: the reduced trace, the
    driver (shapes and counts of the work it ran), the traced units, and
    the configuration and traffic."""

    def __init__(self, trace, driver, units, config, traffic):
        self.trace, self.driver, self.units = trace, driver, units
        self.config, self.traffic = config, traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except (NoChip, spec.SpecError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r}) {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
