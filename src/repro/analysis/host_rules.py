"""Host-dispatch rules (HD*): AST lint over the host-loop surfaces.

Each rule is the mechanized form of a recompile leak this repo has
actually shipped and then fixed by hand:

* HD001 — eager ``jnp.*`` construction in host context. Host code
  holds numpy and crosses to the device once, via ``jax.device_put``
  or a jit boundary; ``jnp.asarray``/``jnp.full``/... on host
  dispatches a throwaway ``jit(convert_element_type)`` executable per
  call site x shape (the fig4/fig17 leak, the kernels_bench compile
  storm, ``Static``'s jnp state).
* HD002 — integer indexing of a device array in host code
  (``thresh[device_id]``): an eager ``dynamic_slice`` compiled per
  fleet size. Transfer once with ``np.asarray`` and index that.
* HD003 — ``jax.jit`` created inside a function/method: per-object
  closures compile per client (the seed serving engine's bug; fixed by
  the process-wide executable cache). Factories decorated with
  ``functools.lru_cache``/``cache`` are exempt — the decorator *is*
  the discipline; anything else needs an allowlist entry naming its
  cache.
* HD004 — host call into a traced scheduler kernel
  (``multitascpp.update``/``switching.decide``/...): op-soup eager
  dispatch of the whole kernel. Call the module's jitted wrapper
  (``switching.decide_jit``) or go through the compiled core.

Traced contexts are exempt from all four: a function is traced if it
is (a) named in ``TRACED_FUNCTIONS`` for its file (the sim-engine
builders whose bodies execute at trace time), (b) decorated with a
jit/vmap-family transform, (c) lexically nested in a traced function,
or (d) passed to / defined inline in a call to a traced consumer
(``jax.jit``, ``lax.while_loop``, ``shard_map``, ...).
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.findings import Finding, Severity

FAMILY = "host-dispatch"

EAGER_CONSTRUCTORS = {
    "asarray", "array", "full", "zeros", "ones", "arange", "linspace",
    "stack", "concatenate", "broadcast_to", "eye", "tile", "full_like",
    "zeros_like", "ones_like", "where", "nonzero", "repeat",
}

# call basenames whose argument subtrees are traced (or jit-boundary)
# contexts, not host code
TRACED_CONSUMERS = {
    "jit", "vmap", "pmap", "grad", "value_and_grad", "shard_map",
    "while_loop", "fori_loop", "scan", "cond", "switch", "checkpoint",
    "remat", "custom_jvp", "custom_vjp", "named_call", "make_jaxpr",
}

# decorator basenames that make the decorated def traced
TRACED_DECORATORS = {"jit", "vmap", "pmap", "shard_map", "custom_jvp",
                     "custom_vjp"}

# decorator basenames that exempt an enclosing def from HD003: a
# memoized factory compiles once per key by construction
CACHED_FACTORY_DECORATORS = {"lru_cache", "cache"}

# repo files whose listed module-level functions are trace-time code
# (their bodies run under make_jaxpr/jit even though nothing marks them
# syntactically): the sim-engine builders and the pure jnp kernels that
# both the compiled core and the jitted host wrappers close over
TRACED_FUNCTIONS: Dict[str, Set[str]] = {
    "src/repro/sim/jaxsim.py": {
        "_ring_slots", "_ring_write", "_ring_read", "_pack_stream_words",
        "_unpack_stream_words", "_pop_calc",
        "_seg_phases", "_engine_fns", "_batched_engine",
        "_run_core_lanes", "_device_engine", "_run_core_device",
    },
    "src/repro/core/multitascpp.py": {"update", "init_state"},
    "src/repro/core/multitasc.py": {"update", "init_state"},
    "src/repro/core/switching.py": {"decide", "decide_partials",
                                    "decide_from_partials"},
    "src/repro/core/decision.py": {"bvsb_confidence", "top1_confidence",
                                   "entropy_confidence", "decide"},
}

# traced kernels HD004 polices at host call sites: the scheduler
# kernels (call the module's jitted wrapper), and the raw Pallas
# kernels + their pure-jnp oracles (all hot-path traffic goes through
# the dispatch layer ``repro.kernels.ops`` — its jitted ``_*_dispatch``
# wrappers are the only sanctioned jit boundaries, and they carry the
# mode/tile static args that ``cache_token()`` pins into the serving
# executable cache)
KERNEL_MODULES: Dict[str, Set[str]] = {
    "repro.core.multitascpp": {"update", "init_state"},
    "repro.core.multitasc": {"update", "init_state"},
    "repro.core.switching": {"decide", "decide_partials",
                             "decide_from_partials"},
    "repro.kernels.bvsb": {"bvsb"},
    "repro.kernels.flash_attention": {"flash_attention"},
    "repro.kernels.decode_attention": {"decode_attention"},
    "repro.kernels.rglru_scan": {"rglru_scan"},
    "repro.kernels.ref": {"bvsb_ref", "flash_attention_ref",
                          "decode_attention_ref", "rglru_scan_ref"},
}


def _basename(func: ast.expr) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


@dataclasses.dataclass
class _Imports:
    jnp_aliases: Set[str]
    jit_names: Set[str]          # bare names that mean jax.jit
    jax_aliases: Set[str]
    kernel_bare: Dict[str, str]  # bare name -> kernel module
    kernel_alias: Dict[str, str]  # module alias -> kernel module


def _scan_imports(tree: ast.Module) -> _Imports:
    imp = _Imports(set(), set(), set(), {}, {})
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                name = a.asname or a.name
                if a.name == "jax.numpy":
                    imp.jnp_aliases.add(name)
                elif a.name == "jax":
                    imp.jax_aliases.add(name)
                elif a.name in KERNEL_MODULES:
                    imp.kernel_alias[name.split(".")[0]
                                     if a.asname is None else name] = a.name
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            for a in node.names:
                name = a.asname or a.name
                if mod == "jax" and a.name == "numpy":
                    imp.jnp_aliases.add(name)
                elif mod == "jax" and a.name == "jit":
                    imp.jit_names.add(name)
                elif f"{mod}.{a.name}" in KERNEL_MODULES:
                    imp.kernel_alias[name] = f"{mod}.{a.name}"
                elif mod in KERNEL_MODULES \
                        and a.name in KERNEL_MODULES[mod]:
                    imp.kernel_bare[name] = mod
    return imp


class _Scanner(ast.NodeVisitor):
    def __init__(self, rel_path: str, imports: _Imports,
                 traced_names: Set[str]):
        self.rel = rel_path
        self.imp = imports
        self.traced_names = traced_names
        self.findings: List[Finding] = []
        self.traced_depth = 0
        self.def_stack: List[Tuple[str, bool]] = []  # (name, cached)
        self.jnp_locals: List[Set[str]] = []

    # -- context helpers ---------------------------------------------------
    def _in_traced(self) -> bool:
        return self.traced_depth > 0

    def _symbol(self) -> str:
        return ".".join(n for n, _ in self.def_stack) or "<module>"

    def _emit(self, rule: str, node: ast.AST, message: str):
        self.findings.append(Finding(
            rule, FAMILY, Severity.WARN, self.rel,
            getattr(node, "lineno", 0), self._symbol(), message))

    def _dec_names(self, node) -> Set[str]:
        names = set()
        for dec in node.decorator_list:
            for sub in ast.walk(dec):
                b = None
                if isinstance(sub, ast.Name):
                    b = sub.id
                elif isinstance(sub, ast.Attribute):
                    b = sub.attr
                if b:
                    names.add(b)
        return names

    # -- defs --------------------------------------------------------------
    def _visit_def(self, node):
        decs = self._dec_names(node)
        traced = (self._in_traced()
                  or node.name in self.traced_names
                  or bool(decs & TRACED_DECORATORS))
        cached = bool(decs & CACHED_FACTORY_DECORATORS)
        self.def_stack.append((node.name, cached))
        self.jnp_locals.append(set())
        self.traced_depth += 1 if traced else 0
        for child in ast.iter_child_nodes(node):
            self.visit(child)
        self.traced_depth -= 1 if traced else 0
        self.jnp_locals.pop()
        self.def_stack.pop()

    visit_FunctionDef = _visit_def
    visit_AsyncFunctionDef = _visit_def

    def visit_Lambda(self, node):
        # classified by enclosing context (inline-traced lambdas are
        # handled at the consumer Call site)
        self.generic_visit(node)

    # -- statements feeding HD002's local dataflow -------------------------
    def _track_assign(self, target, value):
        if not self.jnp_locals or not isinstance(target, ast.Name):
            return
        if isinstance(value, ast.Call):
            base = value.func
            if isinstance(base, ast.Attribute):
                root = base.value
                if isinstance(root, ast.Name) \
                        and root.id in self.imp.jnp_aliases:
                    self.jnp_locals[-1].add(target.id)
                if isinstance(root, ast.Name) \
                        and root.id in self.imp.jax_aliases \
                        and base.attr == "device_put":
                    self.jnp_locals[-1].add(target.id)

    def visit_Assign(self, node):
        for t in node.targets:
            self._track_assign(t, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        if node.value is not None:
            self._track_assign(node.target, node.value)
        self.generic_visit(node)

    # -- the rules ---------------------------------------------------------
    def visit_Call(self, node):
        base = _basename(node.func)

        # a traced-consumer call: its argument subtree is not host code
        if base in TRACED_CONSUMERS:
            self._check_hd003(node, base)
            self.visit(node.func)
            self.traced_depth += 1
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                self.visit(a)
            self.traced_depth -= 1
            return

        if not self._in_traced():
            self._check_hd001(node)
            self._check_hd004(node)
        self.generic_visit(node)

    def _check_hd001(self, node):
        f = node.func
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                and f.value.id in self.imp.jnp_aliases \
                and f.attr in EAGER_CONSTRUCTORS:
            self._emit(
                "HD001", node,
                f"eager jnp.{f.attr} in host context dispatches a "
                f"throwaway executable per call site; build numpy and "
                f"cross the boundary once (device_put / jit argument)")

    def _check_hd003(self, node, base):
        if base != "jit":
            return
        f = node.func
        is_jit = (isinstance(f, ast.Name) and f.id in self.imp.jit_names) \
            or (isinstance(f, ast.Attribute)
                and isinstance(f.value, ast.Name)
                and f.value.id in self.imp.jax_aliases)
        if not is_jit or not self.def_stack:
            return
        if any(cached for _, cached in self.def_stack):
            return  # memoized factory: compiles once per key
        self._emit(
            "HD003", node,
            "jax.jit created inside a function compiles per enclosing "
            "object/call (the per-client executable leak); hoist to "
            "module level or memoize the factory (functools.lru_cache "
            "/ the serving executable cache)")

    def _check_hd004(self, node):
        f = node.func
        mod = kernel = None
        if isinstance(f, ast.Name) and f.id in self.imp.kernel_bare:
            mod, kernel = self.imp.kernel_bare[f.id], f.id
        elif isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            m = self.imp.kernel_alias.get(f.value.id)
            if m and f.attr in KERNEL_MODULES[m]:
                mod, kernel = m, f.attr
        if kernel:
            self._emit(
                "HD004", node,
                f"host call into traced kernel {mod}.{kernel} dispatches "
                f"its op graph eagerly; call the module's jitted wrapper "
                f"(e.g. switching.decide_jit) or keep it inside the "
                f"compiled core")

    def visit_Subscript(self, node):
        if isinstance(node.ctx, ast.Load) and not self._in_traced() \
                and isinstance(node.value, ast.Name) and self.jnp_locals \
                and node.value.id in self.jnp_locals[-1]:
            self._emit(
                "HD002", node,
                f"indexing device array {node.value.id!r} in host code "
                f"is an eager dynamic_slice compiled per shape; "
                f"np.asarray once and index the host copy")
        self.generic_visit(node)


def _collect_traced_names(tree: ast.Module, rel_path: str) -> Set[str]:
    names = set(TRACED_FUNCTIONS.get(rel_path, set()))
    # any name referenced inside a traced-consumer call's arguments is
    # trace-time code (jax.jit(body), while_loop(cond_fn, body_fn, ...))
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and _basename(node.func) in TRACED_CONSUMERS:
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                for sub in ast.walk(a):
                    if isinstance(sub, ast.Name):
                        names.add(sub.id)
    return names


def scan_source(rel_path: str, source: str) -> List[Finding]:
    tree = ast.parse(source, filename=rel_path)
    imports = _scan_imports(tree)
    scanner = _Scanner(rel_path, imports,
                       _collect_traced_names(tree, rel_path))
    scanner.visit(tree)
    return scanner.findings


def _scan_files(ctx) -> List[Finding]:
    cache = getattr(ctx, "_hd_cache", None)
    if cache is None:
        cache = []
        for abs_path, rel_path in ctx.files:
            with open(abs_path, encoding="utf-8") as f:
                cache.extend(scan_source(rel_path, f.read()))
        ctx._hd_cache = cache
    return cache


def _make_rule(rule_id: str):
    def run(ctx) -> List[Finding]:
        return [f for f in _scan_files(ctx) if f.rule == rule_id]
    return run


rule_hd001 = _make_rule("HD001")
rule_hd002 = _make_rule("HD002")
rule_hd003 = _make_rule("HD003")
rule_hd004 = _make_rule("HD004")
