"""Jaxpr walking/slicing primitives shared by the trace-discipline and
lane-masking rules.

Dependence is *conservative*: every eqn's outputs are taken to depend
on every input (control-flow sub-jaxprs included — a ``cond``'s outputs
depend on its predicate and both branches' operands). That is exactly
the right polarity for the invariants here: "output X is gated on the
active predicate" may only produce false *passes* if the engine wired
the predicate in somewhere (which is the property being checked), and
"the boundary cond reaches only BOUNDARY_FIELDS" may only produce
false *failures* — never a silent miss.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Set, Tuple

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, Var


def unwrap_jit(jaxpr: Jaxpr) -> Jaxpr:
    """``make_jaxpr`` of a jitted function yields one ``jit`` eqn
    wrapping the real program; descend to it (repeatedly, for nested
    wrappers with matching arity).

    Any other primitive in that wrapper position (a ``remat2``, a
    ``shard_map``, a renamed ``jit``) raises: the rules would otherwise
    see the whole program as one opaque eqn whose outputs depend on
    every input, and pass vacuously.
    """
    while len(jaxpr.eqns) == 1:
        eqn = jaxpr.eqns[0]
        inner = eqn.params.get("jaxpr")
        inner = inner.jaxpr if isinstance(inner, ClosedJaxpr) else inner
        if not (isinstance(inner, Jaxpr)
                and list(eqn.invars) == list(jaxpr.invars)
                and list(eqn.outvars) == list(jaxpr.outvars)
                and len(inner.invars) == len(eqn.invars)
                and len(inner.outvars) == len(eqn.outvars)):
            break
        if eqn.primitive.name != "jit":
            raise ValueError(
                f"unknown wrapper primitive {eqn.primitive.name!r} around "
                f"the traced program; jaxpr_tools.unwrap_jit must learn "
                f"to descend through it")
        jaxpr = inner
    return jaxpr


def sub_jaxprs(eqn) -> Iterator[Jaxpr]:
    """All jaxprs referenced by an eqn's params (cond/while/scan/jit
    branches, bodies, ...)."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if isinstance(v, ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, Jaxpr):
                yield v


def walk_eqns(jaxpr: Jaxpr) -> Iterator[Tuple[Jaxpr, object]]:
    """Depth-first (jaxpr, eqn) pairs over the whole program."""
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for sub in sub_jaxprs(eqn):
            yield from walk_eqns(sub)


def all_avals(jaxpr: Jaxpr) -> Iterator[Tuple[str, object]]:
    """(where, aval) for every var the program mentions: entry invars,
    constvars, and each eqn's outputs, recursively."""
    for v in jaxpr.invars:
        yield "invar", v.aval
    for v in jaxpr.constvars:
        yield "constvar", v.aval
    for sub, eqn in walk_eqns(jaxpr):
        for v in eqn.outvars:
            yield eqn.primitive.name, v.aval


def used_vars(jaxpr: Jaxpr) -> Set[Var]:
    """Every Var consumed as an input by some eqn or returned as an
    output, recursively (a var not in this set is dead)."""
    used: Set[Var] = set()
    def visit(jx: Jaxpr):
        for v in jx.outvars:
            if isinstance(v, Var):
                used.add(v)
        for eqn in jx.eqns:
            for a in eqn.invars:
                if isinstance(a, Var):
                    used.add(a)
            for sub in sub_jaxprs(eqn):
                visit(sub)
    visit(jaxpr)
    return used


def backward_deps(jaxpr: Jaxpr) -> Dict[Var, Set[int]]:
    """var -> set of entry-invar indices it transitively depends on
    (conservative per-eqn closure; constvars contribute nothing — they
    are baked into the executable, not cache-key inputs)."""
    dep: Dict[Var, Set[int]] = {v: {i} for i, v in enumerate(jaxpr.invars)}
    for eqn in jaxpr.eqns:
        s: Set[int] = set()
        for a in eqn.invars:
            if isinstance(a, Var):
                s |= dep.get(a, set())
        for o in eqn.outvars:
            dep[o] = s
    return dep


def forward_taint(jaxpr: Jaxpr, roots: List[Var]) -> Set[Var]:
    """All vars transitively computed from ``roots`` by the top-level
    eqn sequence (single forward pass suffices: a jaxpr is in
    topological order)."""
    tainted: Set[Var] = set(roots)
    for eqn in jaxpr.eqns:
        if any(isinstance(a, Var) and a in tainted for a in eqn.invars):
            tainted |= set(eqn.outvars)
    return tainted


def leaf_paths(tree) -> List[str]:
    """Flattened pytree key paths, aligned with the jaxpr invar/outvar
    order of a function taking/returning that tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(path) for path, _ in flat]


def top_level_key(path: str) -> str:
    """``"['traces']['sr']"`` -> ``"traces"``."""
    return path.split("]")[0].lstrip("[").strip("'\"")
