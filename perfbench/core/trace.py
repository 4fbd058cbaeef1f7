"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to the numbers the per-layer metrics use.

What it reads:

* device planes ``/device:TPU:<i>``: the ``XLA Ops`` line (one event per
  operation run on the chip, named by its HLO text, of which the
  instruction's name is kept: ``%bvsb.1 = (...) custom-call(...)`` is
  ``bvsb.1``) and the ``XLA Modules`` line (one event per run of a
  compiled program, named after the jitted function: ``jit_fn(<id>)``);
* host planes: the benchmark's own spans, ``jax.profiler.TraceAnnotation``
  events whose names start with ``bench.``; ``bench.window`` bounds the
  traced window.

All times are nanoseconds on the profiler's clock, which puts host and
device events on one axis.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# operations that only hold others (loops, branches, calls): their
# children are on the same line, and the time between the children is
# not work
CONTROL_FLOW = ("while", "conditional", "call")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

# the TPU profiler keeps about 6.3M device events of one trace (a traced
# unit with ~8.6M and one with ~17M operations both came back with
# ~6.3M) and drops the rest, end of program runs included
MAX_OPS = 6_000_000

Interval = Tuple[float, float, str]


class Truncated(Exception):
    """The trace holds as many operations as the profiler keeps: it has
    dropped some, and no number read from it is whole."""


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]        # per device plane
    modules: Dict[str, List[Interval]]    # per device plane
    spans: List[Interval]                 # the benchmark's host spans
    window: Tuple[float, float]

    @property
    def n_ops(self) -> int:
        return sum(len(v) for v in self.ops.values())

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = defaultdict(list), defaultdict(list), []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                ops[plane.name].extend(
                    (e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                    for e in line.events)
            elif device and line.name == MODULES_LINE:
                modules[plane.name].extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events)
            elif not device:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return from_events(dict(ops), dict(modules), spans)


def op_name(text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def from_events(ops, modules, spans) -> Trace:
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, got "
                         f"{len(windows)}")
    w = windows[0]
    return Trace({k: sorted(v) for k, v in ops.items()},
                 {k: sorted(v) for k, v in modules.items()},
                 sorted(spans), (w[0], w[1]))


def _clip(events, lo, hi):
    return [(max(a, lo), min(b, hi), n) for a, b, n in events
            if b > lo and a < hi]


def union(events) -> List[Tuple[float, float]]:
    """Merged busy intervals of (start, end, name) events."""
    out: List[List[float]] = []
    for a, b, _ in sorted(events):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _device_events(tr: Trace, plane: str):
    """Ops of a plane other than control flow, or its modules where the
    plane has no op line."""
    ops = [e for e in tr.ops.get(plane, ())
           if e[2].split(".", 1)[0] not in CONTROL_FLOW]
    return ops or tr.modules.get(plane, [])


def planes(tr: Trace) -> List[str]:
    return sorted(set(tr.ops) | set(tr.modules))


def busy_s(tr: Trace) -> float:
    """Seconds in the window in which an operation ran on the device,
    averaged over the device planes."""
    ps = planes(tr)
    if not ps:
        return 0.0
    lo, hi = tr.window
    tot = sum(b - a for p in ps
              for a, b in union(_clip(_device_events(tr, p), lo, hi)))
    return tot / len(ps) * 1e-9


def module_s_by_span(tr: Trace, prefix: str, spans):
    """Device seconds of the run of the program whose name starts with
    ``prefix`` that each host span waited for, pairing runs and spans in
    time order; None unless there is one run for each span. (The
    profiler aligns the device's clock with the host's to about 0.1 ms,
    so a run can stick out of the span that waited for it.)"""
    runs = sorted((a, b) for p in planes(tr)
                  for a, b, n in tr.modules.get(p, [])
                  if n.startswith(prefix) and b > tr.window[0]
                  and a < tr.window[1])
    if len(runs) != len(spans):
        return None
    return [(b - a) * 1e-9 for a, b in runs]


def _loop_runs(tr: Trace, plane: str, min_trips: int):
    """(trips, start times of each body operation) of the loop that ran
    most on ``plane`` in the window; (0, {}) where none ran ``min_trips``
    times. Each operation of a loop's body runs once a trip, so among the
    operations that ran ``min_trips`` times or more, the number of runs
    that most names share is the trips traced."""
    runs: Dict[str, List[float]] = defaultdict(list)
    lo, hi = tr.window
    for a, _, n in _clip(_device_events(tr, plane), lo, hi):
        runs[n].append(a)
    by_count: Dict[int, int] = defaultdict(int)
    for starts in runs.values():
        if len(starts) >= min_trips:
            by_count[len(starts)] += 1
    if not by_count:
        return 0, {}
    trips = max(by_count, key=lambda c: (by_count[c], c))
    return trips, {n: v for n, v in runs.items() if len(v) == trips}


def loop_trips(tr: Trace, min_trips: int = 3) -> int:
    """Trips traced of the loop that ran most, on the first plane."""
    ps = planes(tr)
    return _loop_runs(tr, ps[0], min_trips)[0] if ps else 0


def loop_period_s(tr: Trace, min_trips: int = 3):
    """Device seconds per trip of the loop that ran most in the window:
    the time from the first to the last start of one of its body's
    operations, over the runs between, gaps between the body's
    operations included. Read from starts of body operations alone, it
    holds for a window that closes with the loop still running. Median
    over the body's operations, mean over planes; None where no loop ran
    ``min_trips`` times."""
    periods = []
    for p in planes(tr):
        trips, body = _loop_runs(tr, p, min_trips)
        if trips:
            per = sorted((max(v) - min(v)) / (trips - 1)
                         for v in body.values())
            periods.append(per[len(per) // 2])
    if not periods:
        return None
    return sum(periods) / len(periods) * 1e-9


def spans(tr: Trace, name: str) -> List[Tuple[float, float]]:
    lo, hi = tr.window
    return [(a, b) for a, b, n in tr.spans
            if n == name and a >= lo and b <= hi]


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    """The ``k`` device operations that took most time in the window,
    as [name, seconds] (seconds averaged over planes)."""
    lo, hi = tr.window
    tot: Dict[str, float] = defaultdict(float)
    ps = planes(tr)
    for p in ps:
        for a, b, n in _clip(_device_events(tr, p), lo, hi):
            tot[n] += (b - a) * 1e-9 / len(ps)
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(tr: Trace, k: int = 10) -> List[List]:
    """Device idle time in the window, by what the host was doing: each
    gap between busy intervals goes to the innermost benchmark span
    around its midpoint (``(none)`` outside them). Returns the ``k``
    largest totals as [span name, seconds], averaged over planes."""
    lo, hi = tr.window
    inner = [s for s in tr.spans if s[2] != WINDOW_SPAN]
    tot: Dict[str, float] = defaultdict(float)
    ps = planes(tr)
    for p in ps:
        edge = lo
        for a, b in union(_clip(_device_events(tr, p), lo, hi)) + [(hi, hi)]:
            if a > edge:
                mid = 0.5 * (a + edge)
                around = [s for s in inner if s[0] <= mid <= s[1]]
                name = (min(around, key=lambda s: s[1] - s[0])[2]
                        if around else "(none)")
                tot[name] += (a - edge) * 1e-9 / len(ps)
            edge = max(edge, b)
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]
