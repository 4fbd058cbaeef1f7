"""What the sim core leaves in a profiler trace: a named scope per phase
of an event-loop trip on the device ops, a host span per phase of a sweep
call, and a compile count that leaves persistent-cache loads out."""
import contextlib
import functools
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.configs.cascade_tiers import DEVICE_PROFILES, SERVER_PROFILES
from repro.sim import jaxsim, synthetic

SCOPES = ("jaxsim.event", "jaxsim.devices", "jaxsim.queue",
          "jaxsim.frontier", "jaxsim.boundary")
SPANS = ("jaxsim.prepare", "jaxsim.transfer", "jaxsim.execute")
DEV, SRV = DEVICE_PROFILES["low"], SERVER_PROFILES["inceptionv3"]


def _inputs(seeds, n, samples):
    streams = synthetic.batched_device_streams(seeds, n, samples,
                                               DEV.accuracy, [SRV.accuracy])
    spec = jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=n,
                             samples_per_device=samples)
    return spec, streams, np.full(n, DEV.latency), np.full(n, 0.15)


CORES = [((0, 1, 2), 10, 6, None),     # flat frontier, three lanes
         ((0,), 4096, 2, True)]         # segmented frontier


def _lowered(seeds, n, samples, frontier_seg):
    spec, streams, lat, slo = _inputs(seeds, n, samples)
    static, params, srv, arrays, _, _ = jaxsim._prepare(
        spec, streams, lat, slo, (SRV,), None, None, None, None,
        frontier_seg=frontier_seg)
    assert (static.seg > 0) == bool(frontier_seg)
    core = jax.jit(functools.partial(jaxsim._run_core_lanes, static))
    return core.lower(params, srv, *arrays)


@pytest.mark.parametrize("seeds,n,samples,frontier_seg", CORES)
def test_loop_ops_carry_phase_scopes(seeds, n, samples, frontier_seg):
    text = _lowered(seeds, n, samples, frontier_seg).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    for scope in SCOPES:
        assert any(scope in p for p in paths), scope
    # every op of the loop's body is in some phase
    body = [p for p in paths if "/while/body/" in p]
    assert body
    assert [p for p in body if "jaxsim." not in p] == []


@pytest.mark.parametrize("seeds,n,samples,frontier_seg", CORES)
def test_scopes_change_no_op(monkeypatch, seeds, n, samples, frontier_seg):
    # the program lowered with every named scope a no-op is the same
    # program, op for op; only the ops' locations differ
    @contextlib.contextmanager
    def no_scope(_name):
        yield

    scoped = _lowered(seeds, n, samples, frontier_seg).as_text()
    monkeypatch.setattr(jax, "named_scope", no_scope)
    plain = _lowered(seeds, n, samples, frontier_seg).as_text()
    assert "jaxsim." not in plain
    assert scoped == plain


def test_sweep_host_spans_in_order(tmp_path):
    from jax.profiler import ProfileData
    spec, streams, lat, slo = _inputs((5, 6), 4, 3)
    jaxsim.run_sweep(spec, streams, lat, slo, (SRV,))     # compile first
    with jax.profiler.trace(str(tmp_path)):
        jaxsim.run_sweep(spec, streams, lat, slo, (SRV,))
    [path] = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                    "*", "*.xplane.pb"))
    found = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for plane in ProfileData.from_file(path).planes
                   if not plane.name.startswith("/device:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("jaxsim."))
    assert [n for _, _, n in found] == list(SPANS)
    for (_, end, _), (start, _, _) in zip(found, found[1:]):
        assert end <= start


def test_backend_compiles_leaves_cache_loads_out():
    before = jaxsim.stats.backend_compiles
    try:
        # a load from the persistent cache: JAX records the hit inside
        # the timing it reports as a backend compile
        jaxsim._on_jax_cache_hit(jaxsim._CACHE_HIT_EVENT)
        jaxsim._on_jax_event(jaxsim._COMPILE_EVENT, 0.5)
        assert jaxsim.stats.backend_compiles == before
        # a compile
        jaxsim._on_jax_event(jaxsim._COMPILE_EVENT, 0.5)
        assert jaxsim.stats.backend_compiles == before + 1
        # other events count nothing
        jaxsim._on_jax_cache_hit("/jax/compilation_cache/"
                                 "compile_requests_use_cache")
        jaxsim._on_jax_event("/jax/compilation_cache/"
                             "cache_retrieval_time_sec", 0.1)
        assert jaxsim.stats.backend_compiles == before + 1
    finally:
        jaxsim.stats.backend_compiles = before


def test_trace_phases_tool_on_a_recorded_trace():
    # tools/trace_phases.py's reduction of the small scoped trace recorded
    # on a TPU v5e (perfbench/tests/data/sim_scoped.xplane.pb)
    import importlib.util
    import pathlib
    root = pathlib.Path(__file__).resolve().parents[1]
    spec = importlib.util.spec_from_file_location(
        "trace_phases_probe", root / "tools" / "trace_phases.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    from perfbench.core import scopes
    tr = scopes.load(str(root / "perfbench" / "tests" / "data"
                         / "sim_scoped.xplane.pb"))
    out = tool.reduce(tr)
    assert out["trips"] == 61
    parts = out["us_per_trip"]
    assert all(parts[s] > 0 for s in tool.PHASES)
    # the scopes split the trip's busy time: nothing counted twice
    assert sum(parts.values()) == pytest.approx(out["busy_us_per_trip"],
                                                rel=0.01)
    assert out["metrics"]["core_us_per_iter.sim"] == pytest.approx(
        218.9495, rel=1e-6)
    assert sum(out["idle_s_by_span"].values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-9)
    assert set(out["idle_ms"]) == set(SPANS)
