"""Tier-1 numerics suite for the kernel dispatch layer (kernels/ops.py).

Every kernel is pinned against its pure-jnp oracle in ``kernels/ref.py``
THROUGH the dispatch wrappers — the same jitted executables the serving
hot path runs — across ragged batches, vocab sizes that are not a
multiple of the BV tile, duplicate-max tie rows, extreme logits and
f32/bf16 inputs. On the CPU tier this exercises Pallas interpret mode,
i.e. the exact TPU kernel body (tiling, scratch accumulators, online
rescale) executing as traced jnp ops.

Also covered here:

* property tests for the BvSB invariants (0 <= bvsb <= 1; top-1 is the
  first-index argmax, ties included) via hypothesis or the conftest
  mini-engine;
* the dispatch-state contract (``set_dispatch`` / ``use_kernels`` /
  ``cache_token``) and the serving-executable cache splitting on it —
  the staleness bug the token exists to prevent;
* the blocked-timing floor (``kernels/timing.py``) and a full
  ``benchmarks/kernels_bench.py`` run: every published row's timed
  block must clear the measured resolution floor;
* a poisoned-kernel negative test: an off-by-one-tile BvSB must make
  the bench RAISE before publishing, not skip or pass vacuously;
* the ``kernels`` gates of tools/check_bench.py, negative-tested the
  same way tests/test_serving_differential.py covers the serving gates.
"""
import importlib.util
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.timing import MIN_RES_MULT, time_blocked, \
    timer_resolution

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic mini engine from conftest
    from conftest import given, settings, st  # noqa: F401

BB, BV = ops.bvsb_tiles()


@pytest.fixture
def restore_dispatch():
    prev = ops.dispatch_mode()
    yield
    ops.set_dispatch(prev)


def _bvsb(x, mode):
    if mode == "ref":
        return ops._bvsb_dispatch(x, mode="ref", bb=0, bv=0)
    return ops._bvsb_dispatch(x, mode=mode, bb=BB, bv=BV)


# ---------------------------------------------------------------------------
# BvSB pinned vs oracle: shapes, dtypes, ties, extremes
# ---------------------------------------------------------------------------
# ragged batches (not a multiple of BB) x vocabs not a multiple of BV,
# plus the serving shape (ladder-max batch x tier vocab)
SHAPES = [(1, 2048), (3, 2048), (20, 2048), (8, 1000), (5, 700),
          (64, 130)]


@pytest.mark.parametrize("b,v", SHAPES)
def test_bvsb_dispatch_pinned_vs_ref(b, v):
    rng = np.random.default_rng(b * 4096 + v)
    x = (rng.standard_normal((b, v)) * 4).astype(np.float32)
    conf, top1 = _bvsb(x, "interpret")
    rconf, rtop1 = _bvsb(x, "ref")
    np.testing.assert_allclose(np.asarray(conf), np.asarray(rconf),
                               atol=1e-5)
    assert np.array_equal(np.asarray(top1), np.asarray(rtop1))


def test_bvsb_dispatch_pinned_bf16():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((20, 1000)) * 4, jnp.bfloat16)
    conf, top1 = _bvsb(x, "interpret")
    rconf, rtop1 = _bvsb(x, "ref")
    # both paths compute in f32 after the cast; the tolerance covers the
    # bf16 input rounding, not implementation drift
    np.testing.assert_allclose(np.asarray(conf), np.asarray(rconf),
                               atol=2e-3)
    assert np.array_equal(np.asarray(top1), np.asarray(rtop1))


def test_bvsb_tie_rows_first_index_zero_margin():
    x = np.full((4, 300), -1.0, np.float32)
    x[0, [7, 199]] = 3.0     # duplicate max straddling a BV tile
    x[1, [0, 1]] = 2.5       # adjacent duplicate max
    x[2, :] = 0.0            # fully tied row
    x[3, 299] = 5.0          # unique max in the last (ragged) column
    for mode in ("interpret", "ref"):
        conf, top1 = map(np.asarray, _bvsb(x, mode))
        np.testing.assert_allclose(conf[:3], 0.0, atol=1e-6,
                                   err_msg=mode)
        assert list(top1) == [7, 0, 0, 299], mode


def test_bvsb_extreme_finite_and_neg_inf_logits():
    # -1e38 is the kernel's own vocab-padding value: rows full of it
    # with one real logit are exactly the padded-tile configuration
    x = np.full((3, 600), -1e38, np.float32)
    x[0, 5] = 1e4
    x[1, 7] = 0.0
    x[2, :10] = -np.inf
    x[2, 11] = 2.0
    conf, top1 = map(np.asarray, _bvsb(x, "interpret"))
    rconf, rtop1 = map(np.asarray, _bvsb(x, "ref"))
    np.testing.assert_allclose(conf, rconf, atol=1e-5)
    assert np.array_equal(top1, rtop1)
    # a single dominant logit saturates the margin
    np.testing.assert_allclose(conf[:2], 1.0, atol=1e-6)
    assert list(top1) == [5, 7, 11]


def test_bvsb_pos_inf_logits_nan_in_both_modes():
    """+inf logits are out of the cascade's input contract; the pinned
    behavior is that BOTH modes surface NaN confidence (softmax of +inf)
    rather than a confident decision. top-1 is unspecified on NaN rows
    (top_k orders NaNs arbitrarily), so only the margin is asserted."""
    x = np.zeros((2, 64), np.float32)
    x[0, 3] = np.inf
    x[1, 5] = np.inf
    x[1, 9] = np.inf
    for mode in ("interpret", "ref"):
        conf, _ = _bvsb(x, mode)
        assert np.all(np.isnan(np.asarray(conf))), mode


@settings(max_examples=15, deadline=None)
@given(b=st.integers(min_value=1, max_value=8),
       v=st.integers(min_value=2, max_value=200),
       seed=st.integers(min_value=0, max_value=10000),
       quantize=st.booleans())
def test_bvsb_margin_and_top1_invariants(b, v, seed, quantize):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((b, v)) * 3).astype(np.float32)
    if quantize:  # integer-valued logits force duplicate maxima
        x = np.round(x)
    conf, top1 = map(np.asarray, _bvsb(x, "interpret"))
    assert conf.shape == (b,) and top1.shape == (b,)
    assert np.all(conf >= -1e-6) and np.all(conf <= 1.0 + 1e-6)
    # numpy argmax is the first-index tie rule the kernel must preserve
    assert np.array_equal(top1, np.argmax(x, axis=1))


# ---------------------------------------------------------------------------
# the other kernels, pinned through the same dispatch wrappers
# ---------------------------------------------------------------------------
def test_flash_attention_dispatch_pinned():
    rng = np.random.default_rng(1)
    q = rng.standard_normal((2, 16, 4, 32)).astype(np.float32)
    k = rng.standard_normal((2, 16, 2, 32)).astype(np.float32)
    v = rng.standard_normal((2, 16, 2, 32)).astype(np.float32)
    for window in (None, 8):
        out = ops._flash_dispatch(q, k, v, mode="interpret",
                                  causal=True, window=window)
        ref = ops._flash_dispatch(q, k, v, mode="ref",
                                  causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, err_msg=f"window={window}")


def test_decode_attention_dispatch_pinned():
    rng = np.random.default_rng(2)
    q = rng.standard_normal((2, 4, 32)).astype(np.float32)
    kc = rng.standard_normal((2, 16, 2, 32)).astype(np.float32)
    vc = rng.standard_normal((2, 16, 2, 32)).astype(np.float32)
    lens = np.array([16, 9], np.int32)  # full + ragged cache
    out = ops._decode_dispatch(q, kc, vc, lens, mode="interpret")
    ref = ops._decode_dispatch(q, kc, vc, lens, mode="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4)


def test_rglru_dispatch_pinned():
    rng = np.random.default_rng(3)
    a = (1.0 / (1.0 + np.exp(-rng.standard_normal((2, 16, 32))))) \
        .astype(np.float32)
    u = rng.standard_normal((2, 16, 32)).astype(np.float32)
    out = ops._rglru_dispatch(a, u, None, mode="interpret")
    ref = ops._rglru_dispatch(a, u, None, mode="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4)


# ---------------------------------------------------------------------------
# dispatch state, cache token, executable-cache splitting
# ---------------------------------------------------------------------------
def test_set_dispatch_contract(restore_dispatch):
    ops.set_dispatch("ref")
    assert ops.dispatch_mode() == "ref"
    assert not ops.kernels_enabled()
    assert ops.cache_token() == ("ref", 0, 0)
    # 'auto' resolves from the backend: interpret on the CPU tier
    ops.set_dispatch("auto")
    assert ops.dispatch_mode() == "interpret"
    assert ops.kernels_enabled()
    assert ops.cache_token() == ("interpret",) + ops.bvsb_tiles()
    with pytest.raises(ValueError):
        ops.set_dispatch("mosaic2")
    assert ops.set_dispatch("ref") == "interpret"  # returns prev


def test_use_kernels_back_compat(restore_dispatch):
    ops.use_kernels(False)
    assert ops.dispatch_mode() == "ref" and not ops.kernels_enabled()
    ops.use_kernels(True)
    assert ops.dispatch_mode() == "interpret" and ops.kernels_enabled()


def test_public_bvsb_follows_dispatch_state(restore_dispatch):
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((5, 257)) * 2).astype(np.float32)
    ops.set_dispatch("interpret")
    ci, ti = map(np.asarray, ops.bvsb(x))
    ops.set_dispatch("ref")
    cr, tr = map(np.asarray, ops.bvsb(x))
    np.testing.assert_allclose(ci, cr, atol=1e-5)
    assert np.array_equal(ti, tr)


def test_executable_cache_splits_on_dispatch_mode(restore_dispatch):
    """The staleness bug cache_token() fixes: flipping dispatch must
    yield a DIFFERENT serving executable (the mode is read at trace
    time), and flipping back must hit the warm one, not rebuild."""
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serving import executables

    model = build_model(get_config("tier-low"))
    params = model.init(jax.random.key(0))
    executables.clear_cache()
    try:
        ops.set_dispatch("interpret")
        f_on = executables.classify_fn(model, params, 1)
        ops.set_dispatch("ref")
        f_off = executables.classify_fn(model, params, 1)
        assert f_on is not f_off
        ops.set_dispatch("interpret")
        assert executables.classify_fn(model, params, 1) is f_on
        assert executables.cache_stats()["executables"] == 2
        # and the two executables agree numerically
        tok = np.zeros((1, 8), np.int32)
        c_on, p_on = f_on(params, tok)
        c_off, p_off = f_off(params, tok)
        np.testing.assert_allclose(np.asarray(c_on), np.asarray(c_off),
                                   atol=1e-5)
        assert np.array_equal(np.asarray(p_on), np.asarray(p_off))
    finally:
        executables.clear_cache()


# ---------------------------------------------------------------------------
# blocked timing: sub-millisecond rows must clear the resolution floor
# ---------------------------------------------------------------------------
def test_timer_resolution_positive_and_cached():
    r = timer_resolution()
    assert r > 0
    assert timer_resolution() == r  # lru_cached: one measurement/process


def test_time_blocked_clears_floor():
    per_call, wall, reps = time_blocked(lambda: None)
    assert wall >= MIN_RES_MULT * timer_resolution()
    assert reps >= 1
    assert per_call * reps == pytest.approx(wall, rel=1e-9)


# ---------------------------------------------------------------------------
# benchmarks/kernels_bench.py: rows, gate metrics, poisoned kernel
# ---------------------------------------------------------------------------
def _bench():
    from benchmarks import kernels_bench
    return kernels_bench


def test_kernels_bench_rows_and_gate_metrics(restore_dispatch):
    kb = _bench()
    ops.set_dispatch("interpret")
    rows = kb.run()
    assert rows, "interpret-mode bench must produce rows"
    # satellite contract: every published row's timed block cleared the
    # measured timer-resolution floor (>= MIN_RES_MULT x resolution)
    for name, t in kb.LAST_TIMINGS.items():
        assert t["block_wall_s"] >= t["floor_s"], (name, t)
        assert t["reps"] >= 1, name
    for key in ("kernel_bvsb_us_per_sample",
                "kernel_bvsb_ref_us_per_sample",
                "kernel_numerics_max_err", "kernel_top1_mismatch",
                "kernel_warm_compiles", "kernel_timer_floor_ok"):
        assert key in kb.EXTRA_JSON, key
    assert kb.EXTRA_JSON["kernel_numerics_max_err"] <= kb.NUMERIC_ATOL
    assert kb.EXTRA_JSON["kernel_top1_mismatch"] == 0
    assert kb.EXTRA_JSON["kernel_warm_compiles"] == 0
    assert kb.EXTRA_JSON["kernel_timer_floor_ok"] == 1


def test_kernels_bench_ref_mode_publishes_nothing(restore_dispatch):
    ops.set_dispatch("ref")
    assert _bench().run() == []


def test_poisoned_kernel_fails_numerics_gate_loudly(restore_dispatch,
                                                    monkeypatch):
    """An off-by-one-tile BvSB (grid drops the last vocab tile) must make
    the bench RAISE before timing/publishing anything — the gate must be
    loud, never a vacuous skip."""
    kb = _bench()
    ops.set_dispatch("interpret")
    real = ops._bvsb_dispatch

    def poisoned(x, *, mode, bb, bv):
        if mode == "ref":
            return real(x, mode="ref", bb=0, bv=0)
        return real(x[:, :x.shape[1] - bv], mode=mode, bb=bb, bv=bv)

    monkeypatch.setattr(ops, "_bvsb_dispatch", poisoned)
    with pytest.raises(AssertionError, match="numerics gate"):
        kb.run()


# ---------------------------------------------------------------------------
# check_bench: the kernels gates actually reject regressions
# ---------------------------------------------------------------------------
def _check_bench_kernels(tmp_path, new_extra, base_extra):
    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "check_bench_kernels_probe", root / "tools/check_bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    row = {"wall_s": 1.0, "n_points": 8, "n_compiles": 8}
    new = {"_schema": mod.BENCH_SCHEMA, "kernels": {**row, **new_extra}}
    base = {"_schema": mod.BENCH_SCHEMA, "kernels": {**row, **base_extra}}
    pn, pb = tmp_path / "new.json", tmp_path / "base.json"
    pn.write_text(json.dumps(new))
    pb.write_text(json.dumps(base))
    old = sys.argv
    sys.argv = ["check_bench", str(pn), str(pb)]
    try:
        return mod.main()
    finally:
        sys.argv = old


KGOOD = {"kernel_bvsb_us_per_sample": 25.0,
         "kernel_bvsb_ref_us_per_sample": 450.0,
         "kernel_numerics_max_err": 1e-6, "kernel_top1_mismatch": 0,
         "kernel_warm_compiles": 0, "kernel_timer_floor_ok": 1}


def test_check_bench_passes_healthy_kernels(tmp_path):
    assert _check_bench_kernels(tmp_path, KGOOD, KGOOD) == 0


def test_check_bench_rejects_kernel_regressions(tmp_path):
    bad = {"kernel_numerics_max_err": 0.5,  # mistiled kernel magnitude
           "kernel_top1_mismatch": 1,       # one wrong forwarding index
           "kernel_warm_compiles": 1,       # unstable static arg
           "kernel_timer_floor_ok": 0}      # noise published as perf
    for key, val in bad.items():
        assert _check_bench_kernels(
            tmp_path, {**KGOOD, key: val}, KGOOD) == 1, key


def test_check_bench_rejects_missing_kernel_metrics(tmp_path):
    # a bench edit that silently drops a gated key must fail, not pass
    for key in ("kernel_numerics_max_err", "kernel_top1_mismatch",
                "kernel_warm_compiles", "kernel_timer_floor_ok"):
        crippled = {k: v for k, v in KGOOD.items() if k != key}
        assert _check_bench_kernels(tmp_path, crippled, KGOOD) == 1, key


def test_check_bench_require_kernels_fails_when_missing(tmp_path):
    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "check_bench_kernels_req_probe", root / "tools/check_bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    pn, pb = tmp_path / "new.json", tmp_path / "base.json"
    pn.write_text(json.dumps({"_schema": mod.BENCH_SCHEMA}))
    pb.write_text(json.dumps({"_schema": mod.BENCH_SCHEMA}))
    old = sys.argv
    sys.argv = ["check_bench", str(pn), str(pb), "--require", "kernels"]
    try:
        assert mod.main() == 1
    finally:
        sys.argv = old


def test_gate_atol_in_lockstep_with_bench(tmp_path):
    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "check_bench_atol_probe", root / "tools/check_bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.KERNEL_NUMERIC_ATOL == _bench().NUMERIC_ATOL
