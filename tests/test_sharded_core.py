"""The device-axis-sharded event core (``jaxsim.run_device_sharded``) on
four CPU devices, so that a one-device test run runs it instead of
skipping it.

Each test starts this file as a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` and
``JAX_PLATFORMS=cpu`` (JAX fixes its device count when it starts, so the
parent, with one device, cannot), under its own timeout. The child runs
one case, asserts what it can see alone, and prints one JSON line for the
parent's assertions:

* ``parity <scheduler>``: a 300-device fleet of 12 samples each in three
  latency tiers, sharded over four devices, against the float64 event
  simulator (``sim/events.py``, the differential tolerances) and against
  one device's segmented core (``jaxsim.run(frontier_seg=True)``, the
  ``SHARDED_EXACT_*`` / ``SHARDED_ULP_*`` contract);
* ``exchange``: every collective of the compiled sharded core carries
  the ``jaxsim.exchange`` scope, the program's count of the collectives
  a trip issues equals the all-reduces of the lowered loop body, and the
  scope changes no op;
* ``stats``: ``SweepStats`` counts the sharded core's trips.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")
TIMEOUT_S = 110
N, SAMPLES = 300, 12
TIER_MULT = (0.8, 1.0, 1.25)


def _child(*case) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [SRC, HERE] + [p for p in [os.environ.get("PYTHONPATH")]
                                  if p]),
               XLA_FLAGS=" ".join(
                   [os.environ.get("XLA_FLAGS", ""),
                    "--xla_force_host_platform_device_count=4"]).strip())
    proc = subprocess.run([sys.executable, os.path.abspath(__file__),
                           *case], capture_output=True, text=True,
                          timeout=TIMEOUT_S, env=env, cwd=HERE)
    assert proc.returncode == 0, proc.stderr[-6000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("scheduler", ["multitasc++", "static"])
def test_sharded_core_matches_events_and_local_seg(scheduler):
    out = _child("parity", scheduler)
    assert out["devices"] == 4
    assert out["completed"] == N * SAMPLES


@pytest.fixture(scope="module")
def exchange():
    return _child("exchange")


def test_every_collective_carries_exchange_scope(exchange):
    assert exchange["compiled_collectives"] > 0
    assert exchange["body_collectives"] > 0
    assert exchange["unscoped"] == []


def test_exchange_counter_counts_body_collectives(exchange):
    assert exchange["counter"] == exchange["lowered_body_all_reduces"] > 0
    # the compiler combines independent all-reduces, never splits them
    assert exchange["body_collectives"] <= exchange["counter"]


def test_exchange_scope_changes_no_op(exchange):
    assert exchange["same_without_scopes"]


def test_sweep_stats_count_sharded_trips():
    out = _child("stats")
    assert out["trips"] == sum(out["n_events"]) > 0
    assert out["exchanges_per_trip"] == out["counter"] > 0


# ---------------------------------------------------------------------------
# the child's cases (run under four CPU devices)
# ---------------------------------------------------------------------------
def _fleet(scheduler):
    """A DiffConfig of the differential harness: N devices in three
    latency tiers (0.8/1.0/1.25 x 31 ms, +-10%), the paper's servers."""
    import test_differential as td
    rng = np.random.default_rng(15)
    tier = (np.arange(N) * len(TIER_MULT)) // N
    lat = (0.031 * np.asarray(TIER_MULT)[tier]
           * rng.uniform(0.9, 1.1, N)).astype(np.float32)
    return td.DiffConfig(
        seed=15, scheduler=scheduler, n=N, samples=SAMPLES, latencies=lat,
        slos=np.full(N, 0.15, np.float32), tier_ids=tier.astype(np.int32),
        c_upper=np.asarray([0.85, 0.8, 0.75], np.float32),
        servers=td.SERVERS, model_switching=False, init_threshold=0.5,
        static_threshold=0.6)


def _spec(cfg):
    from repro.sim import jaxsim
    return jaxsim.JaxSimSpec(
        scheduler=cfg.scheduler, n_devices=cfg.n,
        samples_per_device=cfg.samples, window=1.5,
        init_threshold=cfg.init_threshold,
        static_threshold=cfg.static_threshold)


def _case_parity(scheduler):
    import jax
    import test_differential as td
    from repro.launch.mesh import make_sweep_mesh
    from repro.sim import jaxsim
    cfg = _fleet(scheduler)
    per_dev, streams = td._streams_of(cfg)
    args = (_spec(cfg), streams, cfg.latencies, cfg.slos, cfg.servers)
    kw = dict(tier_ids=cfg.tier_ids, c_upper=cfg.c_upper)
    shard = jaxsim.run_device_sharded(*args, mesh=make_sweep_mesh((4,)),
                                      **kw)
    local = jaxsim.run(*args, frontier_seg=True, **kw)
    for k in jaxsim.SHARDED_EXACT_KEYS:
        np.testing.assert_array_equal(np.asarray(shard[k]),
                                      np.asarray(local[k]), err_msg=k)
    for k in jaxsim.SHARDED_ULP_KEYS:
        np.testing.assert_allclose(np.asarray(shard[k]),
                                   np.asarray(local[k]), rtol=1e-6,
                                   err_msg=k)
    for k in jaxsim.SHARDED_EXACT_TRACES:
        np.testing.assert_array_equal(np.asarray(shard["traces"][k]),
                                      np.asarray(local["traces"][k]),
                                      err_msg=k)
    for k in jaxsim.SHARDED_ULP_TRACES:
        np.testing.assert_allclose(np.asarray(shard["traces"][k]),
                                   np.asarray(local["traces"][k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)
    ref = td.run_reference(cfg, per_dev)
    assert int(shard["completed"]) == ref.completed == N * SAMPLES
    assert int(shard["queue_left"]) == 0
    tol = td.TOL[scheduler]
    gaps = {"sr": abs(float(shard["sr"]) - ref.sr),
            "acc": abs(float(shard["accuracy"]) - ref.accuracy),
            "fwd": abs(float(shard["forwarded_frac"])
                       - ref.forwarded_frac)}
    for k, v in gaps.items():
        assert v <= tol[k], (scheduler, k, v, tol[k])
    return {"devices": jax.device_count(),
            "completed": int(shard["completed"]), "gaps": gaps}


def _lowered(cfg):
    """The sharded core of ``cfg`` on a four-device mesh, and its
    arguments."""
    import test_differential as td
    from repro.launch.mesh import make_sweep_mesh
    from repro.sim import jaxsim
    _, streams = td._streams_of(cfg)
    static, params, srv, arrays, _, _ = jaxsim._prepare(
        _spec(cfg), streams, cfg.latencies, cfg.slos, cfg.servers,
        cfg.tier_ids, cfg.c_upper, None, None, device_shards=4)
    core = jaxsim._make_core_device(static, make_sweep_mesh((4,)))
    args = ({k: v[0] for k, v in params.items()}, srv,
            *(a[0] for a in arrays))
    return core, args


def _body_all_reduces(module) -> int:
    """``stablehlo.all_reduce`` ops inside the body of the module's
    while loops, in the functions the body calls too."""
    funcs = {}
    for op in module.body.operations:
        if op.operation.name == "func.func":
            funcs[op.attributes["sym_name"].value] = op

    def walk(op, in_body):
        name = op.operation.name
        n = int(in_body and name == "stablehlo.all_reduce")
        if name == "func.call":
            n += walk(funcs[op.attributes["callee"].value], in_body)
        for i, region in enumerate(op.regions):
            inner = in_body or (name == "stablehlo.while" and i == 1)
            for block in region.blocks:
                for child in block.operations:
                    n += walk(child, inner)
        return n

    return walk(funcs["main"], False)


def _case_exchange():
    import contextlib
    import re

    import jax
    from repro.sim import jaxsim
    cfg = _fleet("multitasc++")
    core, args = _lowered(cfg)
    lowered = core.lower(*args)
    text = lowered.compile().as_text()
    coll = [line for line in text.splitlines()
            if re.search(r"[])}] (all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)(-start)?\(", line)]
    paths = [re.search(r'op_name="([^"]*)"', line) for line in coll]
    unscoped = [line.strip()[:160] for line, p in zip(coll, paths)
                if p is None or "jaxsim.exchange" not in p.group(1)]
    body = [p for p in paths if p and "/while/body/" in p.group(1)]
    counter = jaxsim._exchanges_per_trip(core.trace(*args).jaxpr)
    n_body = _body_all_reduces(lowered.compiler_ir("stablehlo"))

    @contextlib.contextmanager
    def no_scope(_name):
        yield

    scoped = lowered.as_text()
    jaxsim._make_core_device.cache_clear()
    real = jax.named_scope
    jax.named_scope = no_scope
    try:
        core2, args2 = _lowered(cfg)
        plain = core2.lower(*args2).as_text()
    finally:
        jax.named_scope = real
    return {"compiled_collectives": len(coll), "body_collectives":
            len(body), "unscoped": unscoped, "counter": counter,
            "lowered_body_all_reduces": n_body,
            "same_without_scopes": "jaxsim." not in plain
            and scoped == plain}


def _case_stats():
    import test_differential as td
    from repro.launch.mesh import make_sweep_mesh
    from repro.sim import jaxsim
    cfg = _fleet("multitasc++")
    _, streams = td._streams_of(cfg)
    before = jaxsim.stats_snapshot()
    n_events = []
    for _ in range(2):
        out = jaxsim.run_device_sharded(
            _spec(cfg), streams, cfg.latencies, cfg.slos, cfg.servers,
            mesh=make_sweep_mesh((4,)))
        n_events.append(int(out["n_events"]))
    after = jaxsim.stats_snapshot()
    core, args = _lowered(cfg)
    return {"n_events": n_events,
            "trips": after["device_sharded_trips"]
            - before["device_sharded_trips"],
            "exchanges_per_trip": after["device_sharded_exchanges_per_trip"],
            "counter": jaxsim._exchanges_per_trip(core.trace(*args).jaxpr)}


if __name__ == "__main__":
    case, *rest = sys.argv[1:]
    result = {"parity": _case_parity, "exchange": _case_exchange,
              "stats": _case_stats}[case](*rest)
    print(json.dumps(result))
