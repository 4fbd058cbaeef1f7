"""Fleet-scale engine tests: chunked lazy streams, the segmented
frontier, device-axis sharding, queue caps, and the fig_scale bench
gates.

The load-bearing contracts:

* ``synthetic.chunked_device_streams`` is bitwise-identical to the dense
  ``batched_device_streams`` at ANY chunk size — both implement stream
  fixture v2, and the golden figures pin that fixture, so a chunking
  drift would silently re-baseline every figure.
* the segmented frontier (``frontier_seg``) is an exact refactor of the
  flat argmin: every metric, per-device vector and trace row bitwise
  equal, including simultaneous-completion tie storms. Only
  ``n_events`` may differ (ties drain over several pops).
* ``run_device_sharded`` reproduces the local segmented engine's fleet
  DYNAMICS bitwise (integer totals, per-device vectors); float
  aggregates that psum per-shard partials (``accuracy``, trace
  thresh/sr/acc means) may differ in the last ulp — the documented
  reduction-order contract.
"""
import importlib.util
import json
import pathlib
from dataclasses import replace as dataclasses_replace

import jax
import numpy as np
import pytest

from repro.configs.cascade_tiers import SERVER_PROFILES
from repro.sim import jaxsim, synthetic

SERVERS = (SERVER_PROFILES["inceptionv3"], SERVER_PROFILES["efficientnetb3"])


# ---------------------------------------------------------------------------
# chunked lazy streams vs the dense fixture-v2 generator
# ---------------------------------------------------------------------------
def test_stream_fixture_version_pinned():
    """The chunked generator reproduces fixture v2; a version bump means
    the chunk-position bookkeeping must be re-derived and this suite's
    bitwise assertions re-validated."""
    assert synthetic.STREAM_FIXTURE_VERSION == 2


@pytest.mark.parametrize("chunk", [64, 128, 4096])
def test_chunked_streams_bitwise_equal_dense(chunk):
    seeds, n, s = (0, 1), 300, 17
    light = np.linspace(0.6, 0.85, n)
    heavy = [p.accuracy for p in SERVERS]
    dense = synthetic.batched_device_streams(seeds, n, s, light, heavy)
    lazy = synthetic.chunked_device_streams(seeds, n, s, light, heavy,
                                            chunk_devices=chunk)
    mat = lazy.materialize()
    assert set(mat) == set(dense)
    for k in dense:
        assert mat[k].dtype == dense[k].dtype, k
        np.testing.assert_array_equal(mat[k], dense[k], err_msg=k)


def test_chunked_streams_chunk_slices_match_dense():
    """chunks() itself (the path fig_scale iterates) yields exactly the
    dense tensors' device-axis slices, in order, covering [0, N)."""
    seeds, n, s = (3,), 150, 9
    dense = synthetic.batched_device_streams(seeds, n, s, 0.72, [0.9])
    lazy = synthetic.chunked_device_streams(seeds, n, s, 0.72, [0.9],
                                            chunk_devices=64)
    hi_prev = 0
    for lo, hi, block in lazy.chunks():
        assert lo == hi_prev and hi > lo
        hi_prev = hi
        for k in dense:
            np.testing.assert_array_equal(
                block[k], dense[k][:, lo:hi], err_msg=f"{k}[{lo}:{hi}]")
    assert hi_prev == n


def test_run_accepts_stream_chunks_handle():
    """jaxsim materializes a StreamChunks handle itself — the lazy
    object is a drop-in for the dense dict, bitwise."""
    n, s = 40, 12
    lazy = synthetic.chunked_device_streams((0,), n, s, 0.72,
                                            [SERVERS[0].accuracy])
    dense = {k: v[0] for k, v in lazy.materialize().items()}
    spec = jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=n,
                             samples_per_device=s)
    lat = np.full(n, 0.1, np.float32)
    slo = np.full(n, 0.25, np.float32)
    a = jaxsim.run(spec, lazy, lat, slo, SERVERS[:1])
    b = jaxsim.run(spec, dense, lat, slo, SERVERS[:1])
    _assert_outputs_equal(a, b)


# ---------------------------------------------------------------------------
# segmented frontier vs flat argmin: bitwise refactor
# ---------------------------------------------------------------------------
def _point(n, s, scheduler, frontier_seg, latencies, seed=0, slo_mult=2.0,
           **kw):
    streams = synthetic.device_streams(n, s, 0.72,
                                       [p.accuracy for p in SERVERS], seed)
    spec = jaxsim.JaxSimSpec(scheduler=scheduler, n_devices=n,
                             samples_per_device=s, model_switching=True)
    slo = (latencies * slo_mult).astype(np.float32)
    return jaxsim.run(spec, streams, latencies, slo, SERVERS,
                      frontier_seg=frontier_seg, **kw)


def _assert_outputs_equal(a, b, skip=(), err=""):
    assert set(a) == set(b)
    for k in a:
        if k in skip:
            continue
        if k == "traces":
            for tk in a[k]:
                np.testing.assert_array_equal(
                    np.asarray(a[k][tk]), np.asarray(b[k][tk]),
                    err_msg=f"{err}traces[{tk}]")
        else:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]),
                                          err_msg=err + k)


@pytest.mark.parametrize("scheduler", ["multitasc++", "static"])
@pytest.mark.parametrize("seed", range(3))
def test_seg_frontier_bitwise_heterogeneous(seed, scheduler):
    """Raw-uniform latencies (ties have measure zero): the segmented
    engine must be an exact drop-in for the flat argmin."""
    n, s = 200, 25
    rng = np.random.default_rng(seed)
    lat = rng.uniform(0.04, 0.2, n).astype(np.float32)
    flat = _point(n, s, scheduler, False, lat, seed)
    seg = _point(n, s, scheduler, True, lat, seed)
    # ties are absent, so even the event count must agree
    _assert_outputs_equal(seg, flat)


@pytest.mark.parametrize("scheduler", ["multitasc++", "static"])
def test_seg_frontier_bitwise_tie_storm(scheduler):
    """np.full latencies: ALL devices complete at the same instants
    (every benchmark figure's regime). The segmented engine drains a
    cross-segment tie over several pops — one segment per event — so
    n_events legitimately differs, but the tie must fully drain before
    any server launch: every metric and trace row stays bitwise equal."""
    n, s = 200, 25
    lat = np.full(n, 0.125, np.float32)
    flat = _point(n, s, scheduler, False, lat)
    seg = _point(n, s, scheduler, True, lat)
    assert int(seg["n_events"]) >= int(flat["n_events"])
    _assert_outputs_equal(seg, flat, skip=("n_events",))


@pytest.mark.parametrize("seg_size", [128, 256])
def test_seg_frontier_bitwise_explicit_sizes(seg_size):
    n, s = 200, 20
    rng = np.random.default_rng(7)
    lat = rng.uniform(0.05, 0.18, n).astype(np.float32)
    flat = _point(n, s, "multitasc++", False, lat, 7)
    seg = _point(n, s, "multitasc++", seg_size, lat, 7)
    _assert_outputs_equal(seg, flat)


def test_seg_frontier_bitwise_with_scenarios():
    """Churn + offline windows + tiered switching through the segmented
    path: the seg engine reuses the flat completion maths on a slice, so
    scenario state (join/leave, offline deferral) must survive the
    base-offset indexing bitwise."""
    n, s = 150, 20
    rng = np.random.default_rng(11)
    lat = rng.uniform(0.05, 0.2, n).astype(np.float32)
    total_t = float(lat.max()) * s
    kw = dict(
        tier_ids=rng.integers(0, 3, n).astype(np.int32),
        c_upper=np.asarray([0.85, 0.8, 0.75], np.float32),
        offline_start=np.where(rng.random(n) < 0.3,
                               rng.uniform(0.2, 0.6, n) * total_t,
                               np.inf).astype(np.float32),
        offline_for=rng.uniform(1.0, 3.0, n).astype(np.float32),
        join_t=np.where(rng.random(n) < 0.3,
                        rng.uniform(0.1, 0.4, n) * total_t,
                        0.0).astype(np.float32),
        leave_t=np.where(rng.random(n) < 0.3,
                         rng.uniform(0.5, 0.9, n) * total_t,
                         np.inf).astype(np.float32))
    flat = _point(n, s, "multitasc++", False, lat, 11, **kw)
    seg = _point(n, s, "multitasc++", True, lat, 11, **kw)
    _assert_outputs_equal(seg, flat)


def test_seg_auto_threshold_keeps_small_fleets_flat():
    """frontier_seg=None (the default everywhere) must leave fleets
    below SEG_AUTO_MIN on the flat path — the compiled cores and golden
    figures of every existing caller are captured against it."""
    assert jaxsim._seg_layout(1024, None) == (0, 1024)
    seg, n_pad = jaxsim._seg_layout(jaxsim.SEG_AUTO_MIN, None)
    assert seg > 0 and n_pad % seg == 0
    # explicit True opts in regardless of size
    seg, _ = jaxsim._seg_layout(256, True)
    assert seg == jaxsim.N_BUCKET
    # segment count ~sqrt: G doubles until G*G >= n_pad
    seg, n_pad = jaxsim._seg_layout(200_000, None)
    assert seg * seg >= n_pad and (seg // 2) ** 2 < n_pad


def test_seg_layout_validation():
    with pytest.raises(ValueError):
        jaxsim._seg_layout(4096, 64)          # not a N_BUCKET multiple
    with pytest.raises(ValueError):
        jaxsim._seg_layout(4096, -128)
    with pytest.raises(ValueError):          # sharding needs segments
        jaxsim._seg_layout(4096, False, device_shards=2)


# ---------------------------------------------------------------------------
# queue capacity override + peak occupancy metric
# ---------------------------------------------------------------------------
def test_queue_cap_override_and_peak_metric():
    n, s = 64, 20
    rng = np.random.default_rng(5)
    lat = rng.uniform(0.04, 0.15, n).astype(np.float32)
    base = _point(n, s, "multitasc++", None, lat, 5, slo_mult=1.3)
    peak = int(base["queue_peak"])
    assert 0 < peak <= n * s
    # a cap comfortably above the observed peak cannot change dynamics
    streams = synthetic.device_streams(n, s, 0.72,
                                       [p.accuracy for p in SERVERS], 5)
    spec = jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=n,
                             samples_per_device=s, model_switching=True,
                             queue_cap=max(peak + jaxsim.MAX_POP + 8, 128))
    capped = jaxsim.run(spec, streams, lat,
                        (lat * 1.3).astype(np.float32), SERVERS)
    _assert_outputs_equal(capped, base)
    # regression: a cap that makes tail wrap the ring many times — the
    # old in-ring dummy write slot (cap-1) collided with real appends
    # there and corrupted queued entries (order-dependent scatter)
    tight = dataclasses_replace(spec, queue_cap=jaxsim.MAX_POP + 24)
    wrapped = jaxsim.run(tight, streams, lat,
                         (lat * 1.3).astype(np.float32), SERVERS)
    _assert_outputs_equal(wrapped, base)


def test_queue_cap_must_exceed_max_pop():
    n = 8
    streams = synthetic.device_streams(n, 4, 0.72, [0.9], 0)
    spec = jaxsim.JaxSimSpec(scheduler="static", n_devices=n,
                             samples_per_device=4,
                             queue_cap=jaxsim.MAX_POP)
    with pytest.raises(ValueError):
        jaxsim.run(spec, streams, np.full(n, 0.1, np.float32),
                   np.full(n, 0.3, np.float32), SERVERS[:1])


# ---------------------------------------------------------------------------
# device-axis sharding vs the local segmented engine
# ---------------------------------------------------------------------------
needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 jax devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=4)")

# fleet dynamics: must be bitwise identical between sharded and local
# (integer totals, elementwise per-device floats, exact int trace rows);
# psum-of-partials float aggregates: reduction order differs from the
# flat sum -> last-ulp wiggle allowed, nothing more
EXACT_KEYS = jaxsim.SHARDED_EXACT_KEYS
EXACT_TRACES = jaxsim.SHARDED_EXACT_TRACES
ULP_KEYS = jaxsim.SHARDED_ULP_KEYS
ULP_TRACES = jaxsim.SHARDED_ULP_TRACES


def _sharded_vs_local(n, s, scheduler, seed, **kw):
    from repro.launch.mesh import make_sweep_mesh
    rng = np.random.default_rng(seed)
    lat = rng.uniform(0.04, 0.2, n).astype(np.float32)
    slo = (lat * 2.0).astype(np.float32)
    streams = synthetic.device_streams(n, s, 0.72,
                                       [p.accuracy for p in SERVERS], seed)
    spec = jaxsim.JaxSimSpec(scheduler=scheduler, n_devices=n,
                             samples_per_device=s, model_switching=True)
    local = jaxsim.run(spec, streams, lat, slo, SERVERS,
                       frontier_seg=True, **kw)
    mesh = make_sweep_mesh((4,))
    shard = jaxsim.run_device_sharded(spec, streams, lat, slo, SERVERS,
                                      mesh=mesh, **kw)
    for k in EXACT_KEYS:
        np.testing.assert_array_equal(np.asarray(shard[k]),
                                      np.asarray(local[k]), err_msg=k)
    for k in ULP_KEYS:
        np.testing.assert_allclose(np.asarray(shard[k]),
                                   np.asarray(local[k]), rtol=1e-6,
                                   err_msg=k)
    for tk in EXACT_TRACES:
        np.testing.assert_array_equal(np.asarray(shard["traces"][tk]),
                                      np.asarray(local["traces"][tk]),
                                      err_msg=f"traces[{tk}]")
    for tk in ULP_TRACES:
        np.testing.assert_allclose(np.asarray(shard["traces"][tk]),
                                   np.asarray(local["traces"][tk]),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"traces[{tk}]")


@needs_mesh
@pytest.mark.parametrize("scheduler", ["multitasc++", "static"])
def test_device_sharded_matches_local_seg(scheduler):
    _sharded_vs_local(300, 12, scheduler, seed=2)


@needs_mesh
def test_device_sharded_with_tiers_and_churn():
    n = 256
    rng = np.random.default_rng(9)
    total_t = 0.2 * 14
    _sharded_vs_local(
        n, 14, "multitasc++", seed=9,
        tier_ids=rng.integers(0, 3, n).astype(np.int32),
        c_upper=np.asarray([0.85, 0.8, 0.75], np.float32),
        join_t=np.where(rng.random(n) < 0.3,
                        rng.uniform(0.1, 0.4, n) * total_t,
                        0.0).astype(np.float32),
        leave_t=np.where(rng.random(n) < 0.3,
                         rng.uniform(0.5, 0.9, n) * total_t,
                         np.inf).astype(np.float32))


def test_device_sharded_meshless_fallback_is_local_run():
    """mesh=None (or a 1-lane mesh) must route to the ordinary local
    path, segmented by default — bitwise, so callers can use one entry
    point unconditionally."""
    n, s = 96, 10
    rng = np.random.default_rng(3)
    lat = rng.uniform(0.05, 0.2, n).astype(np.float32)
    slo = (lat * 2.0).astype(np.float32)
    streams = synthetic.device_streams(n, s, 0.72,
                                       [p.accuracy for p in SERVERS], 3)
    spec = jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=n,
                             samples_per_device=s)
    a = jaxsim.run_device_sharded(spec, streams, lat, slo, SERVERS,
                                  mesh=None)
    b = jaxsim.run(spec, streams, lat, slo, SERVERS, frontier_seg=True)
    _assert_outputs_equal(a, b)


def test_device_axis_of_rejects_multi_axis_mesh():
    from repro.launch.mesh import device_axis_of, make_sweep_mesh
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices for a 2x2 mesh")
    with pytest.raises(ValueError):
        device_axis_of(make_sweep_mesh((2, 2)))


# ---------------------------------------------------------------------------
# differential vs the float64 reference sim at fleet width
# ---------------------------------------------------------------------------
def test_differential_fleet_width_seg_engine():
    """N=1000 devices, short streams, through BOTH the reference heap
    simulator and the segmented jax engine — the fleet-scale path obeys
    the same differential tolerances the small-N harness pins (conserved
    completions exactly; totals within the multitasc++ TOL)."""
    from test_differential import (TOL, WINDOW, run_reference,
                                   random_config)
    cfg = random_config(0, "multitasc++")
    n, s = 1000, 6
    rng = np.random.default_rng(1234)
    cfg.n, cfg.samples = n, s
    cfg.latencies = rng.uniform(0.04, 0.2, n).astype(np.float32)
    cfg.slos = (cfg.latencies * rng.uniform(1.4, 2.4, n)).astype(np.float32)
    cfg.tier_ids = rng.integers(0, 3, n).astype(np.int32)
    streams = synthetic.device_streams(
        n, s, 0.72, [p.accuracy for p in cfg.servers], 99)
    per_dev = [synthetic.SampleStream(
        confidence=streams["confidence"][i],
        correct_light=streams["correct_light"][i],
        correct_heavy=streams["correct_heavy"][i]) for i in range(n)]
    ref = run_reference(cfg, per_dev)
    spec = jaxsim.JaxSimSpec(
        scheduler="multitasc++", n_devices=n, samples_per_device=s,
        window=WINDOW, init_threshold=cfg.init_threshold,
        static_threshold=cfg.static_threshold)
    out = jaxsim.run(spec, streams, cfg.latencies, cfg.slos, cfg.servers,
                     tier_ids=cfg.tier_ids, c_upper=cfg.c_upper,
                     frontier_seg=True)
    assert int(out["completed"]) == n * s
    assert int(out["queue_left"]) == 0
    tol = TOL["multitasc++"]
    assert abs(float(out["sr"]) - ref.sr) <= tol["sr"]
    assert abs(float(out["accuracy"]) - ref.accuracy) <= tol["acc"]
    assert abs(float(out["forwarded_frac"]) - ref.forwarded_frac) \
        <= tol["fwd"]


@pytest.mark.slow
def test_hundred_k_devices_seg_engine():
    """The headline point: a 100k-device fleet through chunked streams +
    the segmented frontier. One server genuinely cannot drain a 100k
    fleet's forwards inside the simulated duration, so the exact
    invariant is conservation — every sample either completed or is
    still queued at exit — plus per-device outputs at full width and a
    bounded compile count."""
    n, s = 100_000, 4
    rng = np.random.default_rng(0)
    lat = rng.uniform(0.04, 0.2, n).astype(np.float32)
    slo = (lat * 2.0).astype(np.float32)
    chunks = synthetic.chunked_device_streams(
        (0,), n, s, 0.72, [SERVERS[0].accuracy])
    spec = jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=n,
                             samples_per_device=s)
    before = jaxsim.stats_snapshot()["backend_compiles"]
    out = jaxsim.run(spec, chunks, lat, slo, SERVERS[:1],
                     frontier_seg=True)
    assert int(out["completed"]) + int(out["queue_left"]) == n * s
    assert int(out["completed"]) > 0.9 * n * s
    assert int(out["queue_peak"]) >= int(out["queue_left"])
    assert np.asarray(out["per_device_sr"]).shape == (n,)
    # one event-core executable (plus nothing that scales with N)
    assert jaxsim.stats_snapshot()["backend_compiles"] - before <= 12


# ---------------------------------------------------------------------------
# check_bench: the fig_scale gates actually reject regressions
# ---------------------------------------------------------------------------
def _check_bench(tmp_path, new_extra, base_extra, argv_extra=()):
    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "check_bench_gate_probe", root / "tools/check_bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    row = {"wall_s": 1.0, "n_points": 2, "n_compiles": 1, "n_events": 10,
           "n_shards": 1, "n_points_sharded": 0}
    new = {"_schema": mod.BENCH_SCHEMA, "fig_scale": {**row, **new_extra}}
    base = {"_schema": mod.BENCH_SCHEMA,
            "fig_scale": {**row, **base_extra}}
    pn, pb = tmp_path / "new.json", tmp_path / "base.json"
    pn.write_text(json.dumps(new))
    pb.write_text(json.dumps(base))
    import sys
    old = sys.argv
    sys.argv = ["check_bench", str(pn), str(pb), *argv_extra]
    try:
        return mod.main()
    finally:
        sys.argv = old


GOOD = {"wall_per_event_ratio": 1.1, "max_compiles_per_n": 1}


def test_check_bench_passes_healthy_fig_scale(tmp_path):
    assert _check_bench(tmp_path, GOOD, GOOD) == 0


def test_check_bench_rejects_wpe_ratio_regression(tmp_path):
    assert _check_bench(tmp_path,
                        {**GOOD, "wall_per_event_ratio": 9.7}, GOOD) == 1


def test_check_bench_rejects_per_n_recompiles(tmp_path):
    assert _check_bench(tmp_path,
                        {**GOOD, "max_compiles_per_n": 3}, GOOD) == 1


def test_check_bench_rejects_missing_gated_metrics(tmp_path):
    # a refactor that silently drops the metric must fail, not pass
    assert _check_bench(tmp_path, {"max_compiles_per_n": 1}, GOOD) == 1
    assert _check_bench(tmp_path, {"wall_per_event_ratio": 1.0}, GOOD) == 1


def test_check_bench_require_flag_fails_on_missing_figure(tmp_path):
    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "check_bench_require_probe", root / "tools/check_bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    pn, pb = tmp_path / "new.json", tmp_path / "base.json"
    pn.write_text(json.dumps({"_schema": mod.BENCH_SCHEMA}))
    pb.write_text(json.dumps({"_schema": mod.BENCH_SCHEMA}))
    import sys
    old = sys.argv
    sys.argv = ["check_bench", str(pn), str(pb), "--require", "fig_scale"]
    try:
        assert mod.main() == 1
    finally:
        sys.argv = old
