"""Lane-masking invariant rules (LM*): the machine form of the
"Lane-masking invariants" section of docs/ARCHITECTURE.md.

The checked object is the *real* engine body — ``jaxsim.lane_stepper``
returns the exact ``body`` the compiled core loops over — so the
invariants can't drift from the code the way prose can:

* LM001 — every carry-field write is gated on the active-lane
  predicate: each output leaf of the body is either the untouched
  identity of its own input leaf, or its (conservative) backward slice
  reaches the ``active`` carry input. A write like ``out["t"] =
  st["frontier"]`` — real data, wrong gating — depends on *neither*
  and fails.
* LM002 — the window-boundary ``lax.cond`` touches only
  ``BOUNDARY_FIELDS`` and the per-window trace rows: the forward taint
  of every top-level ``cond``'s outputs must land only on allowed
  output leaves. A body with no top-level ``cond`` at all also fails
  (the invariant would otherwise pass vacuously on a rewritten
  engine).

Both checks run on the *unrolled single-iteration* body jaxpr; the
``lax.while_loop`` wrapper adds nothing to either property.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import jax

from repro.analysis.findings import Finding, Severity
from repro.analysis import jaxpr_tools as jt

from jax.extend.core import Literal, Var

FAMILY = "lane-mask"


@dataclasses.dataclass
class LaneEntry:
    name: str
    body: Callable      # carry -> carry (the loop body)
    st0: object         # example carry (pytree of arrays)
    boundary_fields: Sequence[str]
    active_key: str = "active"
    trace_key: str = "traces"


def default_lane_entries() -> List[LaneEntry]:
    import numpy as np
    from repro.sim import jaxsim, synthetic
    from repro.configs.cascade_tiers import ServerProfile
    n, s = 3, 6
    spec = jaxsim.JaxSimSpec("multitasc++", n, s, model_switching=True)
    streams = synthetic.device_streams(n, s, 0.7, [0.9], 0)
    lat = np.full(n, 0.05, np.float32)
    slo = np.full(n, 0.2, np.float32)
    srv = (ServerProfile("lint", "synthetic", 0.9, 0.05, 16),)
    st0, step, _ = jaxsim.lane_stepper(spec, streams, lat, slo, srv)
    return [LaneEntry("lane-stepper", step, st0,
                      boundary_fields=jaxsim.BOUNDARY_FIELDS)]


def check_lane_entry(entry: LaneEntry) -> List[Finding]:
    """Run LM001 + LM002 on one body; shared by the rule runners and
    the tier-1 mutated-copy pins in tests/test_lint.py."""
    return (_check_masking(entry) + _check_boundary(entry))


def _body_jaxpr(entry: LaneEntry):
    closed = jax.make_jaxpr(entry.body)(entry.st0)
    jaxpr = jt.unwrap_jit(closed.jaxpr)
    paths = jt.leaf_paths(entry.st0)
    if len(jaxpr.invars) != len(paths) or len(jaxpr.outvars) != len(paths):
        raise ValueError(
            f"lane entry {entry.name}: body must map the carry to a "
            f"carry of identical structure ({len(paths)} leaves, got "
            f"{len(jaxpr.invars)} invars / {len(jaxpr.outvars)} outvars)")
    return jaxpr, paths


def _entry_path(entry: LaneEntry) -> str:
    return f"<entry:{entry.name}>"


def _check_masking(entry: LaneEntry) -> List[Finding]:
    out: List[Finding] = []
    jaxpr, paths = _body_jaxpr(entry)
    active_leaf = f"['{entry.active_key}']"
    if active_leaf not in paths:
        return [Finding(
            "LM001", FAMILY, Severity.ERROR, _entry_path(entry), 0,
            entry.active_key,
            f"carry has no {entry.active_key!r} leaf — the active-lane "
            f"predicate the masking invariant gates on is missing")]
    active_idx = paths.index(active_leaf)
    dep = jt.backward_deps(jaxpr)
    for i, (path, ov) in enumerate(zip(paths, jaxpr.outvars)):
        if isinstance(ov, Literal):
            out.append(Finding(
                "LM001", FAMILY, Severity.ERROR, _entry_path(entry), 0,
                path,
                "carry leaf is overwritten with a constant — the write "
                "is not gated on the active-lane predicate"))
            continue
        if ov is jaxpr.invars[i]:
            continue  # untouched pass-through
        if active_idx not in dep.get(ov, set()):
            out.append(Finding(
                "LM001", FAMILY, Severity.ERROR, _entry_path(entry), 0,
                path,
                f"carry write does not depend on the "
                f"{entry.active_key!r} predicate: an inactive lane "
                f"would keep stepping (unmasked write)"))
    return out


def _check_boundary(entry: LaneEntry) -> List[Finding]:
    out: List[Finding] = []
    jaxpr, paths = _body_jaxpr(entry)
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    if not conds:
        return [Finding(
            "LM002", FAMILY, Severity.ERROR, _entry_path(entry), 0,
            "boundary",
            "no top-level lax.cond in the body — the window-boundary "
            "exchange the invariant constrains is gone (or was inlined "
            "into the per-event path)")]
    allowed = set(entry.boundary_fields) | {entry.trace_key}
    for eqn in conds:
        tainted = jt.forward_taint(jaxpr, list(eqn.outvars))
        for path, ov in zip(paths, jaxpr.outvars):
            if isinstance(ov, Var) and ov in tainted \
                    and jt.top_level_key(path) not in allowed:
                out.append(Finding(
                    "LM002", FAMILY, Severity.ERROR, _entry_path(entry),
                    0, path,
                    f"boundary cond reaches carry leaf {path} — only "
                    f"BOUNDARY_FIELDS {tuple(entry.boundary_fields)} "
                    f"and {entry.trace_key!r} rows may be touched by "
                    f"the window boundary"))
    return out


def rule_lm001(ctx) -> List[Finding]:
    out: List[Finding] = []
    for entry in ctx.lane_entries:
        out.extend(_check_masking(entry))
    return out


def rule_lm002(ctx) -> List[Finding]:
    out: List[Finding] = []
    for entry in ctx.lane_entries:
        out.extend(_check_boundary(entry))
    return out
