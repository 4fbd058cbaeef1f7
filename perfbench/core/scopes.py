"""The program's own names in a profiler trace, beside ``trace.py``'s
reduction (whose numbers this module leaves as they are):

* each device op's JAX name path, the ``tf_op`` stat of its event
  metadata (``jit(<unknown>)/while/body/jaxsim.event/vmap(jaxsim.queue)/
  scatter-add``). ``jax.profiler.ProfileData`` does not expose event
  metadata, so it is read from the ``.xplane.pb`` with a protobuf
  wire-format reader: plane names, ``stat_metadata`` and
  ``event_metadata`` with its stats, skipping the lines (schema:
  ``tsl/profiler/protobuf/xplane.proto``). An op's scope is the innermost
  ``jaxsim.*`` name in its path, ``vmap(...)`` wrapped or not;
* the program's host spans, ``jax.profiler.TraceAnnotation`` events whose
  names start with ``jaxsim.``, kept apart from the benchmark's
  ``bench.*`` spans.

The sim core (``repro.sim.jaxsim``) names the phases of an event-loop
trip ``jaxsim.devices``, ``jaxsim.queue``, ``jaxsim.frontier`` and
``jaxsim.boundary``, and wraps the event step in ``jaxsim.event``; its
sweep calls run ``jaxsim.prepare``, ``jaxsim.transfer`` and
``jaxsim.execute`` on the host (docs/ARCHITECTURE.md, "Tracing").
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional

from perfbench.core import trace

PROGRAM_PREFIX = "jaxsim."
EVENT_SCOPE = "jaxsim.event"
UNSCOPED = "unscoped"
UNSPANNED = "(none)"
TF_OP = "tf_op"
# the path given to control-flow ops (the profiler gives them no tf_op):
# a loop's or a branch's interval holds its ops, so it is not work of its
# own (``trace.CONTROL_FLOW`` misses ``cond.*``, JAX's name for them)
CONTROL = "(control flow)"
_SCOPE = re.compile(r"jaxsim\.\w+")
# the opcode of an op's HLO text: the word after its shape
_OPCODE = re.compile(r"[\]})] ([a-z][a-z-]*)\(")

# field numbers of xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_EVENT_MD_NAME, _EVENT_MD_STATS = 2, 5
_STAT_MD_NAME = 2
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7


@dataclasses.dataclass
class ScopedTrace(trace.Trace):
    program_spans: List[trace.Interval]            # the program's host spans
    op_paths: Dict[str, Dict[str, Optional[str]]]  # plane -> op -> path
    # reductions already made of this trace, by what they were of
    _memo: dict = dataclasses.field(default_factory=dict, repr=False,
                                    compare=False)


# ----------------------------------------------------------------------
# protobuf wire format


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of a message in ``buf[lo:hi]``: a varint's
    value, or the (start, end) of a length-delimited field's payload,
    which is not read."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 1:
            value, i = None, i + 8
        elif kind == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span):
    key = value = None
    for f, v in _fields(buf, *span):
        if f == _MAP_KEY:
            key = v
        elif f == _MAP_VALUE:
            value = v
    return key, value


def _tf_op(buf: bytes, md, stat_names: Dict[int, str]):
    """(op text, path) of one event metadata; path None without tf_op."""
    text, path = "", None
    for f, v in _fields(buf, *md):
        if f == _EVENT_MD_NAME:
            text = _text(buf, v)
        elif f == _EVENT_MD_STATS:
            stat, value = None, None
            for g, w in _fields(buf, *v):
                if g == _STAT_MD_ID:
                    stat = w
                elif g == _STAT_STR:
                    value = _text(buf, w)
                elif g == _STAT_REF:
                    value = stat_names.get(w)
            if stat_names.get(stat) == TF_OP and value is not None:
                path = value
    return text, path


def strip_type(path: str) -> str:
    """``jit(f)/while/add:`` (or ``...:Add``) -> ``jit(f)/while/add``."""
    return path.rsplit(":", 1)[0] if ":" in path else path


def read_op_paths(path: str) -> Dict[str, Dict[str, Optional[str]]]:
    """Per device plane, the JAX name path of each op (named as
    ``trace.op_name`` names it), ``None`` where one op maps to two
    different paths, ``CONTROL`` for a loop, branch or call. Other ops
    without a ``tf_op`` are left out."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[str, Dict[str, Optional[str]]] = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != _SPACE_PLANES:
            continue
        name, event_md, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == _PLANE_NAME:
                name = _text(buf, v)
            elif f == _PLANE_EVENT_MD:
                event_md.append(v)
            elif f == _PLANE_STAT_MD:
                key, md = _map_entry(buf, v)
                for g, w in _fields(buf, *md):
                    if g == _STAT_MD_NAME:
                        stat_names[key] = _text(buf, w)
        if not name.startswith(trace.DEVICE_PREFIX):
            continue
        paths: Dict[str, Optional[str]] = {}
        for entry in event_md:
            _, md = _map_entry(buf, entry)
            text, op_path = _tf_op(buf, md, stat_names)
            opcode = _OPCODE.search(text)
            if opcode and opcode.group(1) in ("while", "conditional",
                                              "call"):
                op_path = CONTROL
            elif op_path is None:
                continue
            op, op_path = trace.op_name(text), strip_type(op_path)
            paths[op] = op_path if paths.get(op, op_path) == op_path \
                else None
        out[name] = paths
    return out


# ----------------------------------------------------------------------
# loading


def load(path: str) -> ScopedTrace:
    """``trace.load``'s reduction, with the program's host spans and the
    ops' name paths."""
    from jax.profiler import ProfileData
    base = trace.load(path)
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for plane in ProfileData.from_file(path).planes
             if not plane.name.startswith(trace.DEVICE_PREFIX)
             for line in plane.lines for e in line.events
             if e.name.startswith(PROGRAM_PREFIX)]
    return ScopedTrace(**vars(base), program_spans=sorted(spans),
                       op_paths=read_op_paths(path))


def from_events(ops, modules, spans, program_spans=(), op_paths=None):
    base = trace.from_events(ops, modules, spans)
    return ScopedTrace(**vars(base), program_spans=sorted(program_spans),
                       op_paths=op_paths or {})


# ----------------------------------------------------------------------
# device time by scope


def scope_of(path: Optional[str]) -> str:
    """The innermost ``jaxsim.*`` name of an op's path; ``UNSCOPED`` for
    an op in none, or with no single path."""
    found = _SCOPE.findall(path or "")
    return found[-1] if found else UNSCOPED


def _event_step(tr: ScopedTrace, plane: str, min_trips: int):
    """(trips, the plane's ops in the window but control flow, op ->
    scope): the trips are the run count most ops of ``jaxsim.event``
    share, since the event step runs once a trip; 0 where none ran
    ``min_trips`` times."""
    key = ("event_step", plane, min_trips)
    if key not in tr._memo:
        tr._memo[key] = _event_step_of(tr, plane, min_trips)
    return tr._memo[key]


def _event_step_of(tr: ScopedTrace, plane: str, min_trips: int):
    lo, hi = tr.window
    paths = tr.op_paths.get(plane, {})
    events = [e for e in trace._clip(trace._device_events(tr, plane), lo,
                                     hi) if paths.get(e[2]) != CONTROL]
    runs: Dict[str, int] = defaultdict(int)
    for _, _, op in events:
        runs[op] += 1
    by_count: Dict[int, int] = defaultdict(int)
    for op, n in runs.items():
        if n >= min_trips and EVENT_SCOPE in (paths.get(op) or ""):
            by_count[n] += 1
    trips = (max(by_count, key=lambda c: (by_count[c], c))
             if by_count else 0)
    return trips, events, {op: scope_of(paths.get(op)) for op in runs}


def event_trips(tr: ScopedTrace, min_trips: int = 3) -> int:
    """Trips of the event loop traced, on the first plane."""
    ps = trace.planes(tr)
    return _event_step(tr, ps[0], min_trips)[0] if ps else 0


def scope_us_per_trip(tr: ScopedTrace, scope: str, min_trips: int = 3):
    """Device busy time (the union of their intervals) of the ops whose
    scope is ``scope``, in the window, per trip of the event loop, in
    microseconds; mean over planes. None where no event step ran
    ``min_trips`` times."""
    per = []
    for p in trace.planes(tr):
        trips, events, scope_by_op = _event_step(tr, p, min_trips)
        if trips:
            mine = [e for e in events if scope_by_op[e[2]] == scope]
            per.append(sum(b - a for a, b in trace.union(mine)) / trips)
    return sum(per) / len(per) * 1e-3 if per else None


def top_ops_by_scope(tr: ScopedTrace, k: int = 5) -> Dict[str, List]:
    """Per scope, the ``k`` ops that took most device time in the
    window, as [name, seconds] (first plane)."""
    ps = trace.planes(tr)
    if not ps:
        return {}
    _, events, scope_by_op = _event_step(tr, ps[0], 1)
    tot: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    for a, b, op in events:
        tot[scope_by_op[op]][op] += (b - a) * 1e-9
    return {s: sorted(([n, v] for n, v in ops.items()),
                      key=lambda x: -x[1])[:k]
            for s, ops in tot.items()}


# ----------------------------------------------------------------------
# device idle time by host span


def idle_s_by_span(tr: ScopedTrace) -> Dict[str, float]:
    """Device idle time in the window, split over the host spans
    (``bench.*`` but the window, and ``jaxsim.*``): each part of an idle
    gap goes to the innermost span that covers it (``UNSPANNED`` where
    none does), by overlap. Seconds, averaged over planes; they add up
    to the window less ``trace.busy_s``."""
    if "idle" not in tr._memo:
        tr._memo["idle"] = _idle_s_by_span(tr)
    return tr._memo["idle"]


def _idle_s_by_span(tr: ScopedTrace) -> Dict[str, float]:
    lo, hi = tr.window
    spans = [(max(a, lo), min(b, hi), n)
             for a, b, n in list(tr.spans) + list(tr.program_spans)
             if n != trace.WINDOW_SPAN and b > lo and a < hi]
    edges = sorted({lo, hi} | {x for a, b, _ in spans for x in (a, b)})
    pieces = []
    for x0, x1 in zip(edges, edges[1:]):
        around = [s for s in spans if s[0] <= x0 and x1 <= s[1]]
        pieces.append((x0, x1, min(around, key=lambda s: s[1] - s[0])[2]
                       if around else UNSPANNED))
    tot: Dict[str, float] = defaultdict(float)
    ps = trace.planes(tr)
    for p in ps:
        busy = trace.union(trace._clip(trace._device_events(tr, p), lo, hi))
        i = 0
        for x0, x1, name in pieces:
            while i < len(busy) and busy[i][1] <= x0:
                i += 1
            covered, j = 0.0, i
            while j < len(busy) and busy[j][0] < x1:
                covered += min(busy[j][1], x1) - max(busy[j][0], x0)
                j += 1
            tot[name] += (x1 - x0 - covered) * 1e-9 / len(ps)
    return dict(tot)


def idle_ms_by_span(tr: ScopedTrace, name: str):
    """Device idle time inside span ``name`` (where it is the innermost
    span), per run of that span in the window, in milliseconds; None
    where the span did not run or the trace has no device plane."""
    lo, hi = tr.window
    runs = sum(1 for a, b, n in list(tr.spans) + list(tr.program_spans)
               if n == name and b > lo and a < hi)
    if not runs or not trace.planes(tr):
        return None
    return idle_s_by_span(tr).get(name, 0.0) / runs * 1e3
