"""The trace reduction, on hand-made events and on a small trace recorded
on a TPU v5e (``data/sim_small.xplane.pb``: one sweep unit of a fig4-grid
cut to fleets of 2 and 5 devices and 40 samples, 18 lanes, with its
``bench.*`` spans; the sweep reported 58 events)."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from perfbench.core import trace  # noqa: E402

RECORDED = pathlib.Path(__file__).parent / "data" / "sim_small.xplane.pb"
RECORDED_TRIPS = 58
DEV = "/device:TPU:0"


def _tr():
    # window [0, 100]; device ops: [10, 30] and an overlapping [20, 40],
    # [60, 70], and [90, 120] cut at the window's end, all inside a loop
    # op over [5, 95] that is not counted as work; host spans:
    # bench.unit over [0, 50], bench.execute over [55, 80]
    ops = {DEV: [(10, 30, "fusion.1"), (20, 40, "bvsb.1"),
                 (60, 70, "fusion.1"), (90, 120, "copy"),
                 (5, 95, "while.2")]}
    modules = {DEV: [(10, 40, "jit_fn(7)"), (60, 70, "jit_fn(7)"),
                     (90, 120, "jit_other(2)")]}
    spans = [(0, 100, "bench.window"), (0, 50, "bench.unit"),
             (55, 80, "bench.execute")]
    return trace.from_events(ops, modules, spans)


def test_union_merges_overlaps():
    assert trace.union([(0, 2, "a"), (1, 3, "b"), (5, 6, "c")]) == \
        [(0, 3), (5, 6)]


def test_busy_and_window_by_hand():
    tr = _tr()
    assert tr.window_s == pytest.approx(100e-9)
    # busy: [10, 40] + [60, 70] + [90, 100] = 30 + 10 + 10
    assert trace.busy_s(tr) == pytest.approx(50e-9)


def test_module_time_by_span_by_hand():
    tr = _tr()
    assert trace.op_name("%bvsb.1 = (f32[1,1]) custom-call(f32[1,2048] %a)"
                         ) == "bvsb.1"
    assert trace.spans(tr, "bench.execute") == [(55, 80)]
    # runs of jit_fn pair with spans in time order
    assert trace.module_s_by_span(tr, "jit_fn", [(0, 50), (55, 80)]) == \
        pytest.approx([30e-9, 10e-9])
    assert trace.module_s_by_span(tr, "jit_fn", [(0, 50)]) is None


def test_top_ops_and_idle_gaps_by_hand():
    tr = _tr()
    top = dict(trace.top_ops(tr))
    assert top == pytest.approx({"fusion.1": 30e-9, "bvsb.1": 20e-9,
                                 "copy": 10e-9})
    # idle gaps: [0, 10] (midpoint 5) and [40, 60] (midpoint 50) go to
    # bench.unit, [70, 90] (midpoint 80) to bench.execute
    gaps = dict(trace.idle_gaps(tr))
    assert gaps == pytest.approx({"bench.unit": 30e-9,
                                  "bench.execute": 20e-9})
    assert sum(gaps.values()) == pytest.approx(
        tr.window_s - trace.busy_s(tr))


def _loop_tr(hi):
    # window [0, hi]; a loop over [50, 450] whose body runs fusion.1 and
    # fusion.2 once a trip, every 100 (trips start at 100, 200, 300,
    # 400), a boundary op every other trip, and a prologue op once
    body = [(t + d, t + d + 20, n) for t in (100, 200, 300, 400)
            for d, n in ((0, "fusion.1"), (30, "fusion.2"))]
    ops = {DEV: body + [(160, 170, "fusion.9"), (360, 370, "fusion.9"),
                        (10, 40, "copy.1"), (50, 450, "while.1")]}
    spans = [(0, hi, "bench.window")]
    return trace.from_events(ops, {DEV: [(10, 460, "jit__unknown(1)")]},
                             spans)


@pytest.mark.parametrize("hi,trips", [(1000, 4), (340, 3)])
def test_loop_period_by_hand(hi, trips):
    # a window that closes with the loop running reads the same period
    tr = _loop_tr(hi)
    assert trace.loop_trips(tr) == trips
    assert trace.loop_period_s(tr) == pytest.approx(100e-9)
    assert trace.loop_period_s(tr, min_trips=5) is None


def test_one_window_span_required():
    with pytest.raises(ValueError):
        trace.from_events({}, {}, [(0, 1, "bench.unit")])


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace")
def test_recorded_trace():
    tr = trace.load(str(RECORDED))
    assert trace.planes(tr) == [DEV]
    busy = trace.busy_s(tr)
    assert 0 < busy < tr.window_s
    gaps = trace.idle_gaps(tr, k=1000)
    assert sum(s for _, s in gaps) == pytest.approx(tr.window_s - busy,
                                                    rel=1e-9)
    units = trace.spans(tr, "bench.unit")
    assert len(units) == 1
    # the core ran once, inside the unit span that waited for it, and
    # every operation ran inside it
    [core] = trace.module_s_by_span(tr, "jit__unknown", units)
    assert 0 < busy <= core < tr.window_s
    assert trace.module_s_by_span(tr, "", units) == pytest.approx([core])
    # the sweep reported 58 events; trips times the period is the run
    assert trace.loop_trips(tr) == RECORDED_TRIPS
    assert trace.loop_period_s(tr) * RECORDED_TRIPS == pytest.approx(
        core, rel=0.01)
