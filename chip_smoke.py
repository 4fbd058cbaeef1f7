"""Smoke run of the cascade on a TPU, through the entry points users call.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the multi-chip paths, on four

One chip runs four phases, in order:

1. device — the first device must be a TPU and kernel dispatch must
   resolve to ``pallas``; nothing here ever falls back to the CPU;
2. sim at paper scale — one ``benchmarks.common.sweep`` call at Fig. 4's
   largest point (100 devices, 600 samples each, 3 seeds x 3
   schedulers), held to the float64 reference sim (``sim/events.py``),
   then every behavioural figure held to ``tests/golden/figures.json``,
   then a re-sweep with other thresholds that must compile nothing;
3. segmented frontier — a 4096-device point with the segmented frontier
   (the size rule's default) and with the flat argmin, which must agree;
4. live served cascade — ``run_transport`` with 8 ``tier-low`` clients
   and a ``tier-server-heavy`` engine at its configured widths, equal to
   ``run_cascade`` on the same inputs, then every ladder bucket of the
   served classify under ``pallas`` against ``ref`` dispatch.

``--four-chips`` runs only the paths that exist across chips: the
device-axis-sharded core against the one-chip segmented run, and the
sweep-sharded core against the one-chip sweep.

Each phase prints its own lines; a failed check raises and the process
exits non-zero. Only when every phase passed is the last line of stdout
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Wall times printed here include compiles and come from one smoke run:
they are not benchmark numbers. Parameters and data are made from fixed
seeds.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import common, fig4_homogeneous, fig_scale  # noqa: E402
from benchmarks.kernels_bench import NUMERIC_ATOL  # noqa: E402
from repro.configs.cascade_tiers import (BATCH_LADDER,  # noqa: E402
                                         DEVICE_PROFILES, SERVER_PROFILES)
from repro.kernels import ops as kops  # noqa: E402
from repro.sim import events, jaxsim, synthetic  # noqa: E402


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@contextlib.contextmanager
def phase(name: str):
    print(f"[{name}] start", flush=True)
    t0 = time.perf_counter()
    yield
    print(f"[{name}] passed in {time.perf_counter() - t0:.3f} s "
          f"(smoke run, compiles included)", flush=True)


@contextlib.contextmanager
def placements():
    """Record the sharding of every array placed by ``jax.device_put``
    while the block runs (the sim's dispatch paths place their inputs
    themselves)."""
    shardings = []
    real = jax.device_put

    def spy(x, *args, **kwargs):
        out = real(x, *args, **kwargs)
        shardings.extend(leaf.sharding for leaf in jax.tree.leaves(out))
        return out

    jax.device_put = spy
    try:
        yield shardings
    finally:
        jax.device_put = real


def require_spread(shardings, label: str, n: int = 4) -> None:
    """Every placed input spans all ``n`` chips and some are split over
    them, so nothing is piled onto the first chip."""
    spans = sorted({len(s.device_set) for s in shardings})
    split = sum(not s.is_fully_replicated for s in shardings)
    print(f"[{label}] {len(shardings)} placed inputs span {spans} devices, "
          f"{split} split over them", flush=True)
    require(spans == [n] and split > 0,
            f"inputs not spread over all {n} chips: spans {spans}, "
            f"{split} split")


def compiles() -> int:
    return jaxsim.stats_snapshot()["backend_compiles"]


# ---------------------------------------------------------------------------
# 1. device
# ---------------------------------------------------------------------------
def check_device(n_chips: int):
    devs = jax.devices()
    dev = devs[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devs)} jax={jax.__version__} "
          f"dispatch={kops.dispatch_mode()} bvsb_tiles={kops.bvsb_tiles()}",
          flush=True)
    require(dev.platform == "tpu",
            f"JAX found no TPU (platform {dev.platform!r}); this smoke run "
            f"never falls back to the CPU")
    require(len(devs) >= n_chips,
            f"{n_chips} chips needed, JAX sees {len(devs)}")
    require(kops.dispatch_mode() == "pallas",
            f"kernel dispatch is {kops.dispatch_mode()!r}, not 'pallas'")
    return dev


# ---------------------------------------------------------------------------
# 2. sim at paper scale
# ---------------------------------------------------------------------------
SCHEDULERS = ("multitasc++", "multitasc", "static")


def _fig4_point():
    """Fig. 4's largest point: its specs for every (scheduler, seed),
    the streams tiled to match, and the shared device vectors."""
    dev, srv = DEVICE_PROFILES["low"], SERVER_PROFILES["inceptionv3"]
    n, samples, seeds = max(common.DEVICE_COUNTS), common.SAMPLES, \
        common.SEEDS
    streams = common.cached_streams(seeds, n, samples, dev.accuracy,
                                    [srv.accuracy])
    tiled = {k: np.concatenate([v] * len(SCHEDULERS))
             for k, v in streams.items()}
    lat = np.full(n, dev.latency, np.float32)
    slo = np.full(n, fig4_homogeneous.SLO, np.float32)
    return dev, srv, n, samples, seeds, streams, tiled, lat, slo


def _specs(n, samples, seeds, init_threshold, static_threshold):
    return [jaxsim.JaxSimSpec(scheduler=s, n_devices=n,
                              samples_per_device=samples,
                              init_threshold=init_threshold,
                              static_threshold=static_threshold)
            for s in SCHEDULERS for _ in seeds]


def _reference(dev, srv, streams, lat, slo, seed_idx):
    """``sim/events.py`` (float64) on lane ``seed_idx``'s streams under
    multitasc++, with the core's float32 latencies and SLO."""
    n = lat.shape[0]
    runtimes = []
    for i in range(n):
        stream = synthetic.SampleStream(
            confidence=streams["confidence"][seed_idx, i],
            correct_light=streams["correct_light"][seed_idx, i],
            correct_heavy=streams["correct_heavy"][seed_idx, i])
        prof = dataclasses.replace(dev, latency=float(lat[i]))
        runtimes.append(events.DeviceRuntime(prof, stream, float(slo[i]),
                                             0.5))
    sched = events.make_scheduler("multitasc++", n, server_profile=srv,
                                  slo=float(slo.min()), init_threshold=0.5)
    return events.run(runtimes, (srv,), sched, window=1.5)


def sim_paper_scale():
    dev, srv, n, samples, seeds, streams, tiled, lat, slo = _fig4_point()
    static_t = common.static_threshold_for(dev, srv)
    specs = _specs(n, samples, seeds, 0.5, static_t)
    before = jaxsim.stats_snapshot()
    t0 = time.perf_counter()
    out = common.sweep(specs, tiled, lat, slo, (srv,))
    wall = time.perf_counter() - t0
    after = jaxsim.stats_snapshot()
    for i, sp in enumerate(specs):
        print(f"[sim] {sp.scheduler} seed={seeds[i % len(seeds)]} "
              f"n={n} samples={samples}: sr={float(out['sr'][i]):.4f} "
              f"acc={float(out['accuracy'][i]):.6f} "
              f"thr={float(out['throughput'][i]):.4f} "
              f"completed={int(out['completed'][i])} "
              f"events={int(out['n_events'][i])}")
    print(f"[sim] smoke run, not a benchmark: {len(specs)} points in one "
          f"sweep call, wall {wall:.3f} s (compile included), "
          f"{after['events'] - before['events']} events, "
          f"{after['backend_compiles'] - before['backend_compiles']} "
          f"backend compiles", flush=True)
    require(all(int(c) == n * samples for c in out["completed"]),
            f"a point did not complete all {n * samples} samples: "
            f"{out['completed']}")

    ref = _reference(dev, srv, streams, lat, slo, 0)
    tol = events.SIM_TOL["multitasc++"]
    d_sr = abs(float(out["sr"][0]) - ref.sr)
    d_acc = abs(float(out["accuracy"][0]) - ref.accuracy)
    print(f"[sim] vs sim/events.py (multitasc++, seed {seeds[0]}): "
          f"completed {int(out['completed'][0])} vs {ref.completed}, "
          f"|d_sr|={d_sr:.4f} (tol {tol['sr']}), "
          f"|d_acc|={d_acc:.6f} (tol {tol['acc']})", flush=True)
    require(int(out["completed"][0]) == ref.completed,
            "completed count differs from the reference sim")
    require(d_sr <= tol["sr"] and d_acc <= tol["acc"],
            "sr/accuracy outside the differential tolerance")

    golden = json.loads(common.GOLDEN_FIGURES.read_text())
    t0 = time.perf_counter()
    rows = common.capture_figure_rows(golden["_settings"])
    drift = common.golden_drift(rows, golden["rows"])
    print(f"[sim] golden figures: {len(rows)} rows at "
          f"{golden['_settings']}, {len(drift)} outside GOLDEN_TOL, "
          f"wall {time.perf_counter() - t0:.3f} s", flush=True)
    require(not drift, "golden drift:\n" + "\n".join(drift))

    c0 = compiles()
    out2 = common.sweep(_specs(n, samples, seeds, 0.6, static_t + 0.05),
                        tiled, lat, slo, (srv,))
    added = compiles() - c0
    print(f"[sim] re-sweep with other thresholds: {added} backend "
          f"compiles, sr[0]={float(out2['sr'][0]):.4f}", flush=True)
    require(added == 0, f"re-sweep compiled {added} programs")


# ---------------------------------------------------------------------------
# 3. segmented frontier
# ---------------------------------------------------------------------------
def _outputs_equal(a, b, keys, traces):
    bad = [k for k in keys
           if not np.array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                 equal_nan=True)]
    bad += [f"traces[{k}]" for k in traces
            if not np.array_equal(np.asarray(a["traces"][k]),
                                  np.asarray(b["traces"][k]),
                                  equal_nan=True)]
    return bad


def segmented_frontier():
    dev, srv = DEVICE_PROFILES["low"], SERVER_PROFILES["inceptionv3"]
    n, samples = 4096, fig_scale.SAMPLES
    require(n >= jaxsim.SEG_AUTO_MIN, "point below the segmented size")
    lat, _ = fig_scale._latencies(n, dev.latency)
    slo = np.full(n, fig_scale.SLO, np.float32)
    streams = synthetic.device_streams(n, samples, dev.accuracy,
                                       [srv.accuracy], seed=fig_scale.SEED)
    spec = jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=n,
                             samples_per_device=samples)
    runs = {}
    for label, seg in (("segmented", None), ("flat", False)):
        t0 = time.perf_counter()
        runs[label] = jaxsim.run(spec, streams, lat, slo, (srv,),
                                 frontier_seg=seg)
        print(f"[seg] {label}: sr={float(runs[label]['sr']):.4f} "
              f"completed={int(runs[label]['completed'])} "
              f"events={int(runs[label]['n_events'])} "
              f"wall {time.perf_counter() - t0:.3f} s (smoke run, "
              f"compile included)", flush=True)
    # tests/test_scale.py's contract: every result and trace row bitwise
    # equal; only n_events may grow, as a simultaneous-completion tie
    # drains over several pops (one segment per event)
    seg, flat = runs["segmented"], runs["flat"]
    keys = [k for k in seg if k not in ("traces", "n_events")]
    bad = _outputs_equal(seg, flat, keys, jaxsim.TRACE_KEYS)
    print(f"[seg] {len(keys)} results and {len(jaxsim.TRACE_KEYS)} traces: "
          f"{'bitwise equal' if not bad else 'DIFFER ' + str(bad)}; "
          f"n_events segmented {int(seg['n_events'])} >= flat "
          f"{int(flat['n_events'])}", flush=True)
    require(not bad, f"segmented vs flat differ in {bad}")
    require(int(seg["n_events"]) >= int(flat["n_events"]),
            "the segmented run took fewer events than the flat one")
    require(int(seg["completed"]) == n * samples, "samples lost")


# ---------------------------------------------------------------------------
# 4. live served cascade
# ---------------------------------------------------------------------------
def served_cascade():
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serving import executables
    from repro.serving.cascade import run_cascade
    from repro.serving.client import DeviceClient
    from repro.serving.engine import ServedModel, ServerEngine
    from repro.serving.transport import run_transport

    n, samples, seq = 8, 20, 16
    dev, srv = DEVICE_PROFILES["low"], SERVER_PROFILES["inceptionv3"]
    light_cfg, heavy_cfg = get_config("tier-low"), \
        get_config("tier-server-heavy")
    light, heavy = build_model(light_cfg), build_model(heavy_cfg)
    lp, hp = light.init(jax.random.key(1)), heavy.init(jax.random.key(2))
    print(f"[served] server model {heavy_cfg.name}: "
          f"{heavy_cfg.num_layers} layers, d_model {heavy_cfg.d_model}, "
          f"vocab {heavy_cfg.vocab_size}; {n} {light_cfg.name} clients x "
          f"{samples} samples of {seq} tokens", flush=True)
    rng = np.random.default_rng(0)
    vocab = light_cfg.vocab_size
    datasets = [[rng.integers(0, vocab, seq).astype(np.int32)
                 for _ in range(samples)] for _ in range(n)]
    labels = [[int(x) for x in rng.integers(0, vocab, samples)]
              for _ in range(n)]

    def cascade(run):
        clients = [DeviceClient(i, light, lp, dev, slo=0.15, window=1.5,
                                threshold=0.5) for i in range(n)]
        engine = ServerEngine([ServedModel("heavy", heavy, hp, srv)])
        sched = events.make_scheduler("multitasc++", n, server_profile=srv,
                                      slo=0.15)
        t0 = time.perf_counter()
        res = run(clients, engine, sched, datasets, labels)
        print(f"[served] {run.__name__}: sr={res.sr:.4f} "
              f"acc={res.accuracy:.4f} completed={res.completed} "
              f"forwarded={res.forwarded_frac:.4f} "
              f"batches={engine.batch_history} wall "
              f"{time.perf_counter() - t0:.3f} s (smoke run, compiles "
              f"included)", flush=True)
        return res

    live = cascade(run_transport)
    ref = cascade(run_cascade)
    require(live.completed == n * samples, "the cascade lost samples")
    np.testing.assert_equal(dataclasses.asdict(live),
                            dataclasses.asdict(ref))
    print("[served] run_transport == run_cascade: bitwise equal",
          flush=True)

    for bucket in BATCH_LADDER:
        tokens = rng.integers(0, vocab, (bucket, seq)).astype(np.int32)
        c0 = compiles()
        fn = executables.classify_fn(heavy, hp, bucket)
        conf, pred = fn(hp, tokens)
        c_pallas = compiles() - c0
        hlo = fn.lower(hp, tokens).compile().as_text()
        require("tpu_custom_call" in hlo,
                f"bucket {bucket}: no Mosaic kernel in the classify HLO")
        prev = kops.set_dispatch("ref")
        try:
            c0 = compiles()
            rconf, rpred = executables.classify_fn(heavy, hp, bucket)(
                hp, tokens)
            c_ref = compiles() - c0
        finally:
            kops.set_dispatch(prev)
        err = float(np.max(np.abs(np.asarray(conf) - np.asarray(rconf))))
        mismatch = int(np.sum(np.asarray(pred) != np.asarray(rpred)))
        print(f"[served] bucket {bucket}: compiles pallas={c_pallas} "
              f"ref={c_ref}, tpu_custom_call present, max |d_conf| "
              f"{err:.3e} (tol {NUMERIC_ATOL}), top-1 mismatches "
              f"{mismatch}", flush=True)
        require(err <= NUMERIC_ATOL and mismatch == 0,
                f"bucket {bucket}: pallas and ref classify disagree")
    print(f"[served] executable cache: {executables.cache_stats()}",
          flush=True)


# ---------------------------------------------------------------------------
# --four-chips
# ---------------------------------------------------------------------------
def device_sharded(mesh):
    servers = (SERVER_PROFILES["inceptionv3"],
               SERVER_PROFILES["efficientnetb3"])
    n, samples, seed = 8192, 40, 2
    rng = np.random.default_rng(seed)
    lat = rng.uniform(0.04, 0.2, n).astype(np.float32)
    slo = (lat * 2.0).astype(np.float32)
    streams = synthetic.device_streams(n, samples, 0.72,
                                       [p.accuracy for p in servers], seed)
    spec = jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=n,
                             samples_per_device=samples,
                             model_switching=True)
    t0 = time.perf_counter()
    local = jaxsim.run(spec, streams, lat, slo, servers, frontier_seg=True)
    print(f"[device-sharded] one-chip segmented run: "
          f"events={int(local['n_events'])} wall "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    before = jaxsim.stats_snapshot()
    t0 = time.perf_counter()
    with placements() as placed:
        shard = jaxsim.run_device_sharded(spec, streams, lat, slo, servers,
                                          mesh=mesh)
    wall = time.perf_counter() - t0
    added = (jaxsim.stats_snapshot()["device_sharded_points"]
             - before["device_sharded_points"])
    print(f"[device-sharded] 4-chip run: events={int(shard['n_events'])} "
          f"wall {wall:.3f} s; device_sharded_points +{added}", flush=True)
    require_spread(placed, "device-sharded")
    require(added == 1, "the run was not counted as device-sharded")
    bad = _outputs_equal(shard, local, jaxsim.SHARDED_EXACT_KEYS,
                         jaxsim.SHARDED_EXACT_TRACES)
    require(not bad, f"device-sharded differs from local in {bad}")
    for k in jaxsim.SHARDED_ULP_KEYS:
        np.testing.assert_allclose(np.asarray(shard[k]),
                                   np.asarray(local[k]), rtol=1e-6,
                                   err_msg=k)
    for k in jaxsim.SHARDED_ULP_TRACES:
        np.testing.assert_allclose(np.asarray(shard["traces"][k]),
                                   np.asarray(local["traces"][k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)
    print("[device-sharded] dynamics bitwise equal, float aggregates "
          "within the last ulp", flush=True)


def sweep_sharded(mesh):
    dev, srv, n, samples, _, _, _, lat, slo = _fig4_point()
    seeds = (0, 1, 2, 3)
    streams = common.cached_streams(seeds, n, samples, dev.accuracy,
                                    [srv.accuracy])
    scheds = ("multitasc++", "static")
    tiled = {k: np.concatenate([v] * len(scheds)) for k, v in streams.items()}
    specs = [jaxsim.JaxSimSpec(
        scheduler=s, n_devices=n, samples_per_device=samples,
        static_threshold=common.static_threshold_for(dev, srv))
        for s in scheds for _ in seeds]
    t0 = time.perf_counter()
    local = jaxsim.run_sweep(specs, tiled, lat, slo, (srv,))
    print(f"[sweep-sharded] one-chip sweep of {len(specs)} points: wall "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    before = jaxsim.stats_snapshot()
    t0 = time.perf_counter()
    with placements() as placed:
        shard = jaxsim.run_sweep_sharded(specs, tiled, lat, slo, (srv,),
                                         mesh=mesh)
    wall = time.perf_counter() - t0
    added = (jaxsim.stats_snapshot()["sharded_points"]
             - before["sharded_points"])
    print(f"[sweep-sharded] 4-chip sweep: wall {wall:.3f} s; "
          f"sharded_points +{added}", flush=True)
    require_spread(placed, "sweep-sharded")
    require(added == len(specs), "not every point ran sharded")
    bad = [k for k in ("sr", "accuracy", "throughput")
           if not np.array_equal(np.asarray(shard[k]), np.asarray(local[k]))]
    require(not bad, f"sweep-sharded differs from one chip in {bad}")
    print("[sweep-sharded] sr/accuracy/throughput bitwise equal",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths, on four chips")
    args = ap.parse_args()
    common.use_compile_cache()
    n_chips = 4 if args.four_chips else 1
    try:
        with phase("device"):
            dev = check_device(n_chips)
        if args.four_chips:
            from repro.launch.mesh import make_sweep_mesh
            mesh = make_sweep_mesh((4,))
            with phase("device-sharded"):
                device_sharded(mesh)
            with phase("sweep-sharded"):
                sweep_sharded(mesh)
        else:
            with phase("sim"):
                sim_paper_scale()
            with phase("seg"):
                segmented_frontier()
            with phase("served"):
                served_cascade()
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
