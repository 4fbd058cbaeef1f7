"""End-to-end behaviour tests for the paper's system: the full closed loop
(real models + scheduler) and integration across substrate layers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.cascade_tiers import DEVICE_PROFILES, SERVER_PROFILES
from repro.core import decision
from repro.models.model import build_model
from repro.serving.cascade import run_cascade
from repro.serving.client import DeviceClient
from repro.serving.engine import ServedModel, ServerEngine
from repro.sim.events import make_scheduler


def test_decision_function_eq3():
    conf = jnp.array([0.1, 0.5, 0.9])
    fwd = decision.decide(conf, 0.5)
    np.testing.assert_array_equal(fwd, [1, 0, 0])


def test_confidence_metrics_agree_on_top1():
    logits = jax.random.normal(jax.random.key(0), (8, 128)) * 3
    for name, fn in decision.METRICS.items():
        conf, top1 = fn(logits)
        np.testing.assert_array_equal(top1, logits.argmax(-1), err_msg=name)
        assert float(conf.min()) >= 0.0 and float(conf.max()) <= 1.0, name


def test_bvsb_orders_confidence_sensibly():
    sharp = jnp.zeros((1, 64)).at[0, 3].set(10.0)
    flat = jnp.zeros((1, 64))
    cs, _ = decision.bvsb_confidence(sharp)
    cf, _ = decision.bvsb_confidence(flat)
    assert float(cs[0]) > float(cf[0])


def test_full_system_scheduler_adapts_threshold():
    """Live loop: with an untrained light model (all low confidence) the
    scheduler must cut thresholds to protect the SLO."""
    lcfg = get_config("tier-low")
    hcfg = get_config("tier-server-fast")
    lm, hm = build_model(lcfg), build_model(hcfg)
    lp, hp = lm.init(jax.random.key(0)), hm.init(jax.random.key(1))
    n = 8
    srv = SERVER_PROFILES["efficientnetb3"]  # slow server -> congestion
    clients = [DeviceClient(i, lm, lp, DEVICE_PROFILES["low"], 0.1, 1.0,
                            0.9) for i in range(n)]
    engine = ServerEngine([ServedModel("heavy", hm, hp, srv)])
    sched = make_scheduler("multitasc++", n, server_profile=srv, slo=0.1,
                           init_threshold=0.9)
    rng = np.random.default_rng(2)
    datasets = [[jnp.asarray(rng.integers(0, lcfg.vocab_size, 8), jnp.int32)
                 for _ in range(60)] for _ in range(n)]
    res = run_cascade(clients, engine, sched, datasets)
    final_thresh = np.asarray(res.timeline["thresholds"][-1])
    # untrained confidence ~0 -> must have cut thresholds below init
    assert final_thresh.mean() < 0.9
    assert res.sr > 50.0  # scheduler recovered some SLO headroom


def test_bvsb_kernel_used_in_decision_path():
    from repro.kernels import ops as kops
    logits = jax.random.normal(jax.random.key(1), (8, 512))
    kops.use_kernels(True)
    c1, t1 = decision.bvsb_confidence(logits)
    kops.use_kernels(False)
    c2, t2 = decision.bvsb_confidence(logits)
    kops.use_kernels(True)
    np.testing.assert_allclose(c1, c2, atol=1e-5)
    np.testing.assert_array_equal(t1, t2)


def test_bench_schema_constants_in_lockstep():
    """benchmarks/run.py stamps the bench json with BENCH_SCHEMA and
    tools/check_bench.py refuses a json whose _schema differs from its
    own copy — the two constants (and the committed baseline) must
    move together or every CI bench gate fails closed."""
    import json
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent
    import importlib.util

    def load(name, rel):
        spec = importlib.util.spec_from_file_location(name, root / rel)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    run_mod = load("bench_run_schema_probe", "benchmarks/run.py")
    check_mod = load("check_bench_schema_probe", "tools/check_bench.py")
    assert run_mod.BENCH_SCHEMA == check_mod.BENCH_SCHEMA
    baseline = json.loads((root / "BENCH_jaxsim.json").read_text())
    assert baseline.get("_schema") == run_mod.BENCH_SCHEMA, (
        "committed BENCH_jaxsim.json was captured under a different "
        "schema; re-run benchmarks/run.py --quick --json")


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir_rule(monkeypatch, tmp_path, env_dir):
    """Entry points leave JAX_COMPILATION_CACHE_DIR to JAX when it is set
    and otherwise pin the cache to the fixed <repo>/.jax_cache — never a
    temporary or per-process path, which a later run could not find."""
    from benchmarks import common

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        common.use_compile_cache()
        got = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    if env_dir is None:
        assert got == str(common.REPO_ROOT / ".jax_cache")
    else:
        assert got is None
