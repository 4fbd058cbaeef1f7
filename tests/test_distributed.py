"""Distributed-layer tests runnable on one device: the vocab-parallel CE
and BvSB shard_map paths (model axis of size 1 — psum/pmax become
identities, so equality against the local reference validates the math),
sharding-rule unit tests, and the HLO roofline parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import distributed, shardings
from repro.models.model import build_model, cross_entropy
from repro.roofline import hlo as rhlo
from repro.roofline.analysis import compute_roofline, model_flops
from repro.configs.base import INPUT_SHAPES


@pytest.fixture(scope="module")
def tiny_mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_vocab_parallel_ce_matches_local(tiny_mesh):
    b, s, d, v = 2, 6, 32, 128
    hidden = jax.random.normal(jax.random.key(0), (b, s, d))
    table = jax.random.normal(jax.random.key(1), (v, d)) * 0.1
    labels = jax.random.randint(jax.random.key(2), (b, s), 0, 100)
    labels = labels.at[0, 0].set(-100)
    with tiny_mesh:
        ce_vp = distributed.vocab_parallel_ce(hidden, table, labels,
                                              tiny_mesh, ("data",), 100)
    logits = hidden @ table.T
    logits = jnp.where(jnp.arange(v) < 100, logits, -1e30)
    ce_ref = cross_entropy(logits, labels, 100)
    assert float(ce_vp) == pytest.approx(float(ce_ref), rel=1e-5)


def test_vocab_parallel_ce_grads_match(tiny_mesh):
    b, s, d, v = 2, 4, 16, 64
    hidden = jax.random.normal(jax.random.key(0), (b, s, d))
    table = jax.random.normal(jax.random.key(1), (v, d)) * 0.1
    labels = jax.random.randint(jax.random.key(2), (b, s), 0, v)

    def f_vp(h, t):
        with tiny_mesh:
            return distributed.vocab_parallel_ce(h, t, labels, tiny_mesh,
                                                 ("data",), v)

    def f_ref(h, t):
        return cross_entropy(h @ t.T, labels, v)

    g_vp = jax.grad(f_vp, argnums=(0, 1))(hidden, table)
    g_ref = jax.grad(f_ref, argnums=(0, 1))(hidden, table)
    for a, b_ in zip(g_vp, g_ref):
        np.testing.assert_allclose(a, b_, atol=1e-5)


def test_vocab_parallel_bvsb_matches_kernel_ref(tiny_mesh):
    from repro.kernels.ref import bvsb_ref
    b, d, v = 4, 32, 256
    hidden = jax.random.normal(jax.random.key(3), (b, 1, d))
    table = jax.random.normal(jax.random.key(4), (v, d)) * 0.2
    with tiny_mesh:
        conf, top1 = distributed.vocab_parallel_bvsb(hidden, table,
                                                     tiny_mesh, ("data",), v)
    ref_conf, ref_top1 = bvsb_ref(hidden[:, 0, :] @ table.T)
    np.testing.assert_allclose(conf, ref_conf, atol=1e-5)
    np.testing.assert_array_equal(top1, ref_top1)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
def test_param_specs_divisible_for_all_archs():
    """Every parameter of every assigned arch gets a spec whose sharded
    dims divide the production mesh (the dry-run would fail otherwise —
    this is the fast pre-check)."""
    from repro.configs import list_archs
    for arch in list_archs():
        cfg = get_config(arch)
        model = build_model(cfg)
        params_shape = jax.eval_shape(
            lambda m=model: m.init(jax.random.key(0), jnp.bfloat16))
        flat = jax.tree_util.tree_flatten_with_path(params_shape)[0]
        for path, leaf in flat:
            spec = shardings.param_spec(path, leaf,
                                        fsdp_axes=("pod", "data"),
                                        fsdp_size=32, model_size=16)
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                n = 16 if ax == "model" else 32
                assert leaf.shape[dim] % n == 0, (arch, path, leaf.shape,
                                                  spec)


def test_accum_steps_heuristic():
    assert distributed.default_accum_steps(32e9, 256, 16) == 8
    assert distributed.default_accum_steps(16e9, 256, 16) == 4
    assert distributed.default_accum_steps(0.4e9, 256, 16) == 1
    assert distributed.default_accum_steps(32e9, 1, 16) == 1
    # must divide the global batch
    assert 256 % (distributed.default_accum_steps(32e9, 256, 16) * 16) == 0


# ---------------------------------------------------------------------------
# roofline HLO parser
# ---------------------------------------------------------------------------
# Version-keyed format fixture: (major, minor) jax releases whose HLO
# text dumps the regex parser is KNOWN to handle, with the quirks each
# introduced. An unknown version or an unrecognized dump skips the trip-
# count assertions with a loud, actionable message instead of failing on
# cosmetic text drift (ROADMAP: "the text format drifts between
# releases") — while a silent *mis*-parse on a known version still
# fails hard.
HLO_FORMAT_FIXTURES = {
    # add a version ONLY after vetting rhlo.diagnose() against its real
    # dumps (the canary test below then guards it); pre-registering
    # future versions would defeat the vet-before-trust design
    (0, 9): dict(inline_operand_types=True),   # operand types inline
                                               # since 0.4.37; vetted on
                                               # 0.9.0's CPU dumps
}


def _jax_format_key():
    return tuple(int(x) for x in jax.__version__.split(".")[:2])


def _analyze_checked(compiled):
    """rhlo.analyze, or a loud skip when the dump isn't recognized."""
    text = compiled.as_text()
    diag = rhlo.diagnose(text)
    key = _jax_format_key()
    if key not in HLO_FORMAT_FIXTURES or not diag.recognized:
        pytest.skip(
            f"*** HLO text format of jax {jax.__version__} is not "
            f"recognized by the roofline parser (known versions: "
            f"{sorted(HLO_FORMAT_FIXTURES)}; diagnostics: {diag}). "
            f"Update the tolerant regexes in src/repro/roofline/hlo.py "
            f"and add the version to HLO_FORMAT_FIXTURES in "
            f"tests/test_distributed.py ***")
    return rhlo.analyze(text)


def test_hlo_format_recognized_on_this_jax():
    """The canary: a trivial jitted matmul-in-scan must diagnose as
    recognized on a fixture-listed jax — if this skips, the pins above
    need updating BEFORE the roofline numbers can be trusted."""
    key = _jax_format_key()
    if key not in HLO_FORMAT_FIXTURES:
        pytest.skip(
            f"*** jax {jax.__version__} is not in HLO_FORMAT_FIXTURES — "
            f"vet rhlo.diagnose() on this version's dumps and add it ***")
    w = jnp.ones((16, 16), jnp.float32)
    c = jax.jit(lambda x: (x @ w).sum()).lower(jnp.ones((4, 16))).compile()
    diag = rhlo.diagnose(c.as_text())
    assert diag.recognized, diag
    assert diag.n_dot_parsed >= 1


def test_hlo_parser_tolerates_sigil_free_dumps():
    """The %-optional hardening end to end: stripping every % sigil (a
    render-mode drift) must leave dot FLOPs exact — and diagnose() must
    notice when it instead degrades (an unresolved lhs operand type
    silently contributes k=1, a 128x undercount on this program)."""
    w = jnp.ones((128, 128), jnp.float32)

    def f(x):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y.sum()

    text = jax.jit(f).lower(jnp.ones((32, 128))).compile().as_text()
    ref = rhlo.analyze(text)
    stripped = text.replace("%", "")
    diag = rhlo.diagnose(stripped)
    st = rhlo.analyze(stripped)
    # either the parser fully understands the dump (then the numbers
    # must be exact) or it must say so — never recognized-but-wrong
    if diag.recognized:
        assert st.dot_flops == pytest.approx(ref.dot_flops)
        assert sorted(st.while_trips) == sorted(ref.while_trips)
    else:  # pragma: no cover - parser regressed; keep the gate honest
        pytest.fail(f"sigil-free dump no longer recognized: {diag}")


def test_hlo_diagnose_flags_unparseable_dump():
    """A dump whose instructions stop matching must flip recognized to
    False (the loud-skip path) instead of analyzing to zeros."""
    w = jnp.ones((16, 16), jnp.float32)
    c = jax.jit(lambda x: (x @ w).sum()).lower(jnp.ones((4, 16))).compile()
    mangled = c.as_text().replace(" = ", " := ")
    assert not rhlo.diagnose(mangled).recognized


def test_hlo_parser_counts_scan_flops():
    w = jnp.ones((128, 128), jnp.float32)

    def f(x):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y.sum()

    c = jax.jit(f).lower(jnp.ones((32, 128))).compile()
    st = _analyze_checked(c)
    assert st.dot_flops == pytest.approx(2 * 32 * 128 * 128 * 7)
    assert st.while_trips == [7]


def test_hlo_parser_nested_scans():
    w = jnp.ones((64, 64))

    def f(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None
        z, _ = jax.lax.scan(outer, x, None, length=5)
        return z.sum()

    c = jax.jit(f).lower(jnp.ones((8, 64))).compile()
    st = _analyze_checked(c)
    assert st.dot_flops == pytest.approx(2 * 8 * 64 * 64 * 15)
    assert sorted(st.while_trips) == [3, 5]


def test_roofline_terms_and_dominance():
    cfg = get_config("qwen3-32b")
    shape = INPUT_SHAPES["train_4k"]
    stats = rhlo.HloStats(dot_flops=1e15, dot_bytes=1e12,
                          collective_bytes=1e11)
    r = compute_roofline(cfg, shape, stats, 256)
    assert r.compute_s == pytest.approx(1e15 / 197e12)
    assert r.memory_s == pytest.approx(1e12 / 819e9)
    assert r.collective_s == pytest.approx(1e11 / 50e9)
    assert r.dominant == "compute"
    assert r.model_flops == pytest.approx(
        6 * cfg.active_param_count() * 256 * 4096)


def test_peaks_keyed_by_device_kind(monkeypatch):
    """v5e's published peaks are in the table; a TPU kind that is not
    raises — in the table lookup and in the autotuner's roofline floor —
    instead of being timed against v5e's numbers."""
    from repro.kernels import autotune
    from repro.roofline import analysis

    v5e = analysis.peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        analysis.peaks("TPU v9 imaginary")

    class _Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])
    with pytest.raises(ValueError, match="no published peaks"):
        autotune.roofline_floor_s(64, 2048)


def test_model_flops_moe_uses_active_params():
    cfg = get_config("deepseek-moe-16b")
    shape = INPUT_SHAPES["train_4k"]
    assert cfg.active_param_count() < cfg.param_count()
    assert model_flops(cfg, shape) == pytest.approx(
        6 * cfg.active_param_count() * 256 * 4096)
