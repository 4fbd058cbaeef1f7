"""The program's names in a trace (``core/scopes.py``): the op-path
decoder and the scope and span reductions, on hand-made events and on
small traces recorded on a TPU v5e: ``data/sim_small.xplane.pb`` from a
program without scopes or spans, ``data/sim_scoped.xplane.pb`` from one
with them (both one sweep unit of a fig4-grid cut to fleets of 2 and 5
devices and 40 samples, 18 lanes)."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from perfbench.core import scopes, trace  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"
UNSCOPED_TRACE = DATA / "sim_small.xplane.pb"
SCOPED_TRACE = DATA / "sim_scoped.xplane.pb"
DEV = "/device:TPU:0"
PHASES = ("jaxsim.devices", "jaxsim.queue", "jaxsim.frontier",
          "jaxsim.boundary")
SPANS = ("jaxsim.prepare", "jaxsim.transfer", "jaxsim.execute")


@pytest.mark.parametrize("path,scope", [
    ("jit(<unknown>)/while/body/jaxsim.event/vmap(jaxsim.queue)/"
     "jaxsim.frontier/reduce_min", "jaxsim.frontier"),
    ("jit(<unknown>)/while/body/jaxsim.event/vmap(jaxsim.devices)/mul",
     "jaxsim.devices"),
    ("jit(<unknown>)/while/body/jaxsim.boundary/cond/branch_1_fun/add",
     "jaxsim.boundary"),
    ("jit(<unknown>)/vmap()/reduce_min", scopes.UNSCOPED),
    (None, scopes.UNSCOPED),
])
def test_scope_is_the_innermost_name(path, scope):
    assert scopes.scope_of(path) == scope


def test_strip_type():
    assert scopes.strip_type("jit(<unknown>)/while:") == "jit(<unknown>)/while"
    assert scopes.strip_type("a/b/Add:Add") == "a/b/Add"
    assert scopes.strip_type("a/b") == "a/b"


def _loop_tr():
    # window [0, 1000]; four trips (starts 100, 200, 300, 400) of an event
    # step of two ops in jaxsim.devices that overlap ([t, t+20] and
    # [t+10, t+30]), one in jaxsim.queue ([t+40, t+50]) and one in
    # jaxsim.event alone ([t+55, t+60]); a boundary op on two trips, in a
    # branch op; an unscoped op once; the loop and the branch are control
    # flow, not work
    ev = "jit(f)/while/body/jaxsim.event/"
    paths = {"fusion.1": ev + "vmap(jaxsim.devices)/mul",
             "gather.2": ev + "vmap(jaxsim.devices)/gather",
             "scatter.3": ev + "vmap(jaxsim.queue)/scatter",
             "and.4": ev + "and",
             "fusion.9": "jit(f)/while/body/jaxsim.boundary/cond/add",
             "copy.1": "jit(f)/copy", "cond.5": scopes.CONTROL}
    ops = [(t + d0, t + d1, n) for t in (100, 200, 300, 400)
           for d0, d1, n in ((0, 20, "fusion.1"), (10, 30, "gather.2"),
                             (40, 50, "scatter.3"), (55, 60, "and.4"))]
    ops += [(170, 180, "fusion.9"), (370, 390, "fusion.9"),
            (165, 185, "cond.5"), (10, 40, "copy.1"), (50, 450, "while.1")]
    return scopes.from_events({DEV: ops}, {}, [(0, 1000, "bench.window")],
                              op_paths={DEV: paths})


@pytest.mark.parametrize("scope,ns_per_trip", [
    ("jaxsim.devices", 30), ("jaxsim.queue", 10), ("jaxsim.event", 5),
    ("jaxsim.boundary", 7.5), (scopes.UNSCOPED, 7.5),
    ("jaxsim.frontier", 0)])
def test_scope_time_per_trip_by_hand(scope, ns_per_trip):
    tr = _loop_tr()
    assert scopes.scope_us_per_trip(tr, scope) == pytest.approx(
        ns_per_trip * 1e-3)


def test_scope_time_needs_an_event_step():
    tr = _loop_tr()
    assert scopes.scope_us_per_trip(tr, "jaxsim.devices", min_trips=5) \
        is None
    bare = scopes.from_events(tr.ops, {}, [(0, 1000, "bench.window")])
    assert scopes.scope_us_per_trip(bare, "jaxsim.devices") is None


def _spans_tr():
    # window [0, 200]; bench.unit [10, 190] holds jaxsim.prepare [10, 50],
    # jaxsim.transfer [50, 80] and jaxsim.execute [80, 185]; the device is
    # busy over [60, 70], [90, 150] and [160, 170]
    ops = {DEV: [(60, 70, "copy.1"), (90, 150, "fusion.1"),
                 (160, 170, "fusion.2"), (85, 175, "while.1")]}
    return scopes.from_events(
        ops, {}, [(0, 200, "bench.window"), (10, 190, "bench.unit")],
        program_spans=[(10, 50, "jaxsim.prepare"),
                       (50, 80, "jaxsim.transfer"),
                       (80, 185, "jaxsim.execute")])


def test_idle_split_by_span_by_hand():
    tr = _spans_tr()
    idle = scopes.idle_s_by_span(tr)
    # the gap [70, 90] is split by overlap: 10 to transfer, 10 to execute
    assert idle == pytest.approx({
        scopes.UNSPANNED: 20e-9, "jaxsim.prepare": 40e-9,
        "jaxsim.transfer": 20e-9, "jaxsim.execute": 35e-9,
        "bench.unit": 5e-9})
    assert sum(idle.values()) == pytest.approx(
        tr.window_s - trace.busy_s(tr))
    assert scopes.idle_ms_by_span(tr, "jaxsim.execute") == \
        pytest.approx(35e-6)
    assert scopes.idle_ms_by_span(tr, "jaxsim.nothing") is None


def test_idle_by_span_per_run():
    # two runs of a span share its idle time
    tr = scopes.from_events(
        {DEV: [(40, 60, "fusion.1")]}, {}, [(0, 100, "bench.window")],
        program_spans=[(0, 50, "jaxsim.prepare"),
                       (50, 100, "jaxsim.prepare")])
    assert scopes.idle_ms_by_span(tr, "jaxsim.prepare") == \
        pytest.approx(80e-6 / 2)


def test_decoder_on_a_recorded_trace():
    paths = scopes.read_op_paths(str(UNSCOPED_TRACE))
    assert list(paths) == [DEV]
    control = {op for op, p in paths[DEV].items() if p == scopes.CONTROL}
    assert len(paths[DEV]) - len(control) == 208     # ops with a tf_op
    assert {op.split(".")[0] for op in control} == {"while", "cond"}
    assert paths[DEV]["fusion.99"] == "jit(<unknown>)/vmap()/reduce_min"
    assert paths[DEV]["reduce_or.22"] == "jit(<unknown>)/while/cond/reduce_or"
    assert not any(p.endswith(":") for p in paths[DEV].values())


def test_program_without_names_reads_nothing():
    # the program before it named its phases: every reading is None,
    # and the benchmark's own reduction is what it was
    tr = scopes.load(str(UNSCOPED_TRACE))
    assert tr.program_spans == []
    for scope in PHASES + (scopes.UNSCOPED,):
        assert scopes.scope_us_per_trip(tr, scope) is None
    for span in SPANS:
        assert scopes.idle_ms_by_span(tr, span) is None
    idle = scopes.idle_s_by_span(tr)
    assert set(idle) == {scopes.UNSPANNED, "bench.unit"}
    assert sum(idle.values()) == pytest.approx(
        tr.window_s - trace.busy_s(tr), rel=1e-9)


def test_trace_reduction_unchanged_on_a_recorded_trace():
    tr = trace.load(str(UNSCOPED_TRACE))
    assert tr.n_ops == 10509
    assert trace.busy_s(tr) == pytest.approx(0.012684968, rel=1e-12)
    assert tr.window_s == pytest.approx(0.02600086, rel=1e-12)
    assert trace.loop_trips(tr) == 58
    assert trace.loop_period_s(tr) == pytest.approx(
        0.00021907152631578947, rel=1e-12)
    top = trace.top_ops(tr, 3)
    assert [n for n, _ in top] == ["fusion.141", "fusion.145", "fusion.151"]
    assert [s for _, s in top] == pytest.approx(
        [0.001308816, 0.001308633, 0.00067074], rel=1e-9)
    [[name, idle]] = trace.idle_gaps(tr)
    assert name == "bench.unit"
    assert idle == pytest.approx(0.013315892, rel=1e-9)
    assert trace.module_s_by_span(
        tr, "jit__unknown", trace.spans(tr, "bench.unit")) == \
        pytest.approx([0.012740922], rel=1e-9)


def test_scoped_recorded_trace():
    tr = scopes.load(str(SCOPED_TRACE))
    assert [n for _, _, n in tr.program_spans] == list(SPANS)
    paths = tr.op_paths[DEV]
    for scope in (scopes.EVENT_SCOPE,) + PHASES:
        assert any(scope in (p or "") for p in paths.values()), scope
    # the event step's run count is the loop's trips
    assert scopes.event_trips(tr) == trace.loop_trips(tr) == 61
    phases = [scopes.scope_us_per_trip(tr, s) for s in PHASES]
    assert all(v > 0 for v in phases)
    # every op of the event step is in a phase
    assert scopes.scope_us_per_trip(tr, scopes.EVENT_SCOPE) == 0
    # the ops the compiler made without metadata (the ring's flattened
    # scatters) carry the loop's own path
    assert paths["fusion.147"] == "jit(<unknown>)/while"
    idle = scopes.idle_s_by_span(tr)
    assert sum(idle.values()) == pytest.approx(
        tr.window_s - trace.busy_s(tr), rel=1e-9)
    for span in SPANS:
        assert scopes.idle_ms_by_span(tr, span) > 0
