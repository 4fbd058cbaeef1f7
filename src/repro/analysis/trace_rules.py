"""Trace-discipline rules (TD*): properties of the real entry points'
ClosedJaxprs, not of source text.

Entries are traced with ``jax.make_jaxpr`` — no compilation, no device
execution — under the session's standard dtype config, and (for entries
declaring ``x64=True``) additionally under ``jax.experimental
.enable_x64()``. The x64 pass is the teeth of TD001: with x64 disabled
JAX *canonicalizes* every float64 away at trace time, so code that
relies on that canonicalization instead of explicit ``float32`` dtypes
looks clean until someone flips ``JAX_ENABLE_X64`` — tracing under x64
surfaces exactly those sites. The big lane core, the scheduler kernels
and the serving classify forward all trace under the x64 pass: the
core's boundary-cond branch dtypes and scatter indices are explicit
(``_ratio32`` / ``dtype=jnp.int32``), so enable_x64 changes nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.analysis.findings import Finding, Severity
from repro.analysis import jaxpr_tools as jt

FAMILY = "trace-discipline"

BAD_DTYPES = ("float64", "complex128")


@dataclasses.dataclass
class TraceEntry:
    """One traced entry point.

    ``build()`` -> (fn, args, kwargs); building may be expensive (it
    can assemble a whole sim core), tracing happens once per dtype
    config. ``donate``: positional indices of donated args (mirroring
    the entry's real ``donate_argnums``) for the dead-donation check.
    """
    name: str
    build: Callable[[], Tuple[Callable, tuple, dict]]
    donate: Tuple[int, ...] = ()
    x64: bool = False


@dataclasses.dataclass
class StaticKeyEntry:
    """A recompile-key audit: ``static_of(spec)`` must be *invariant*
    under any change of the declared traced fields. ``spec_a``/
    ``spec_b`` differ in every traced field; identical static keys mean
    no traced value leaked into the key."""
    name: str
    static_of: Callable
    spec_a: object
    spec_b: object
    traced_fields: Sequence[str]


def _trace(entry: TraceEntry, x64: bool):
    fn, args, kwargs = entry.build()
    if x64:
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(fn)(*args, **kwargs)
    else:
        closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return jt.unwrap_jit(closed.jaxpr), args, kwargs


def _entry_path(entry: TraceEntry) -> str:
    return f"<entry:{entry.name}>"


def rule_td001(ctx) -> List[Finding]:
    """TD001: no float64/complex128 aval anywhere in the program."""
    out: List[Finding] = []
    for entry in ctx.trace_entries:
        configs = [False] + ([True] if entry.x64 else [])
        for x64 in configs:
            jaxpr, _, _ = _trace(entry, x64)
            seen = set()
            for where, aval in jt.all_avals(jaxpr):
                dt = str(getattr(aval, "dtype", ""))
                if dt in BAD_DTYPES and (where, str(aval)) not in seen:
                    seen.add((where, str(aval)))
                    out.append(Finding(
                        "TD001", FAMILY, Severity.ERROR,
                        _entry_path(entry), 0, where,
                        f"{dt} aval {aval} in the traced program"
                        f"{' (x64 trace)' if x64 else ''} — the core is "
                        f"float32; give the producing site an explicit "
                        f"dtype"))
    return out


def rule_td002(ctx) -> List[Finding]:
    """TD002: no weak-typed entry aval — weak vs strong is a jit-cache
    key split, so a weak scalar argument recompiles against its
    strongly-typed twin (pass np.float32/np.int32, not python
    scalars)."""
    out: List[Finding] = []
    for entry in ctx.trace_entries:
        jaxpr, args, kwargs = _trace(entry, False)
        paths = jt.leaf_paths((args, kwargs))
        for i, v in enumerate(jaxpr.invars):
            if getattr(v.aval, "weak_type", False):
                sym = paths[i] if i < len(paths) else f"arg{i}"
                out.append(Finding(
                    "TD002", FAMILY, Severity.ERROR,
                    _entry_path(entry), 0, sym,
                    f"weak-typed entry aval {v.aval} (python scalar "
                    f"reached the jit boundary; pass a numpy scalar so "
                    f"the cache key is stable)"))
    return out


def rule_td003(ctx) -> List[Finding]:
    """TD003: the recompile key is structure-only — no traced per-point
    value may leak into it."""
    out: List[Finding] = []
    for entry in ctx.static_key_entries:
        sa = entry.static_of(entry.spec_a)
        sb = entry.static_of(entry.spec_b)
        if sa != sb:
            diff = []
            if dataclasses.is_dataclass(sa) and dataclasses.is_dataclass(sb):
                for f in dataclasses.fields(sa):
                    va, vb = getattr(sa, f.name), getattr(sb, f.name)
                    if va != vb:
                        diff.append(f"{f.name}: {va!r} != {vb!r}")
            out.append(Finding(
                "TD003", FAMILY, Severity.ERROR,
                f"<entry:{entry.name}>", 0, "static-key",
                f"static key changed under a traced-fields-only spec "
                f"change ({', '.join(diff) or f'{sa!r} != {sb!r}'}) — a "
                f"traced value leaked into the recompile key; every "
                f"sweep point would compile its own core"))
    return out


def rule_td004(ctx) -> List[Finding]:
    """TD004: every donated buffer is consumed. A donated-but-dead
    buffer is donation theater: the caller loses the buffer and the
    core never reads it (zero-size placeholders — e.g. the (B, n, 0)
    ``arrive`` tensor of a saturated sweep — are exempt: they carry no
    bytes to lose)."""
    out: List[Finding] = []
    for entry in ctx.trace_entries:
        if not entry.donate:
            continue
        jaxpr, args, kwargs = _trace(entry, False)
        if kwargs:
            raise ValueError(
                f"entry {entry.name}: donate with kwargs is ambiguous; "
                f"pass donated buffers positionally")
        used = jt.used_vars(jaxpr)
        # map positional args to their flattened invar ranges
        offsets, k = [], 0
        for a in args:
            width = len(jax.tree_util.tree_leaves(a))
            offsets.append((k, k + width))
            k += width
        for pos in entry.donate:
            lo, hi = offsets[pos]
            for v in jaxpr.invars[lo:hi]:
                size = int(np.prod(v.aval.shape)) if v.aval.shape else 1
                if size == 0:
                    continue
                if v not in used:
                    out.append(Finding(
                        "TD004", FAMILY, Severity.ERROR,
                        _entry_path(entry), 0, f"arg{pos}",
                        f"donated buffer {v.aval} (positional arg {pos})"
                        f" is never consumed by the traced program"))
    return out


# ---------------------------------------------------------------------------
# default entries: the repo's real jit boundaries
# ---------------------------------------------------------------------------
def _lane_core_entry(with_arrive: bool) -> TraceEntry:
    def build():
        import functools
        from repro.sim import jaxsim, synthetic
        from repro.configs.cascade_tiers import ServerProfile
        n, s = 3, 6
        spec = jaxsim.JaxSimSpec("multitasc++", n, s, model_switching=True)
        streams = dict(synthetic.device_streams(n, s, 0.7, [0.9], 0))
        if with_arrive:
            streams["arrive"] = np.zeros((n, s), np.float32)
        lat = np.full(n, 0.05, np.float32)
        slo = np.full(n, 0.2, np.float32)
        srv = (ServerProfile("lint", "synthetic", 0.9, 0.05, 16),)
        static, params, srvt, arrays, _, _ = jaxsim._prepare(
            spec, streams, lat, slo, srv, None, None, None, None)
        fn = functools.partial(jaxsim._run_core_lanes, static)
        return fn, (params, srvt) + tuple(arrays), {}
    # donate indices mirror _make_core's donate_argnums=(2, 3, 4, 5):
    # the conf/cl/ch/arrive stream buffers
    return TraceEntry(
        name="lane-core-arrive" if with_arrive else "lane-core",
        build=build, donate=(2, 3, 4, 5), x64=True)


def _scheduler_entries() -> List[TraceEntry]:
    def build_mtpp():
        from repro.core import multitascpp as mtpp
        st = {"thresh": np.full(4, 0.5, np.float32),
              "mult": np.ones(4, np.float32)}
        fn = lambda s, sr, tgt, na, act: mtpp.update(  # noqa: E731
            s, sr, mtpp.MultiTASCPPConfig(), sr_target=tgt,
            n_active=na, active=act)
        return fn, (st, np.full(4, 90.0, np.float32),
                    np.full(4, 95.0, np.float32), np.float32(4),
                    np.ones(4, bool)), {}

    def build_mt():
        from repro.core import multitasc as mt
        st = {"thresh": np.full(4, 0.5, np.float32)}
        fn = lambda s, ob, act: mt.update(  # noqa: E731
            s, ob, 8, mt.MultiTASCConfig(), active=act)
        return fn, (st, np.int32(4), np.ones(4, bool)), {}

    def build_decide():
        from repro.core import switching
        fn = lambda th, ti, cl, cu, act: switching.decide(  # noqa: E731
            th, ti, 3, cl, cu, active=act)
        return fn, (np.full(6, 0.5, np.float32),
                    np.zeros(6, np.int32), np.float32(0.05),
                    np.full(3, 0.8, np.float32), np.ones(6, bool)), {}

    return [TraceEntry("mtpp-update", build_mtpp, x64=True),
            TraceEntry("mt-update", build_mt, x64=True),
            TraceEntry("switching-decide", build_decide, x64=True)]


def _kernel_entries() -> List[TraceEntry]:
    """The jitted kernel dispatch wrappers in ``kernels/ops.py``, traced
    in every CPU-reachable mode (interpret = the kernel body as jnp ops,
    ref = the pure-jnp oracle). x64=True: the kernel bodies and oracles
    pin every constant/iota to f32/i32, so enable_x64 must change
    nothing (the tie-mask ``-inf`` and masking ``-1e30`` scalars have
    regressed to weak f64 before)."""
    import functools

    from repro.kernels import ops

    def build_bvsb(mode):
        def build():
            bb, bv = (0, 0) if mode == "ref" else ops.bvsb_tiles()
            fn = functools.partial(ops._bvsb_dispatch, mode=mode,
                                   bb=bb, bv=bv)
            return fn, (np.zeros((8, 256), np.float32),), {}
        return build

    def build_flash(mode):
        def build():
            fn = functools.partial(ops._flash_dispatch, mode=mode,
                                   causal=True, window=None)
            q = np.zeros((2, 16, 4, 32), np.float32)
            kv = np.zeros((2, 16, 2, 32), np.float32)
            return fn, (q, kv, kv), {}
        return build

    def build_decode(mode):
        def build():
            fn = functools.partial(ops._decode_dispatch, mode=mode)
            q = np.zeros((2, 4, 32), np.float32)
            kc = np.zeros((2, 16, 2, 32), np.float32)
            return fn, (q, kc, kc, np.full(2, 9, np.int32)), {}
        return build

    def build_rglru(mode):
        def build():
            def fn(a, u):
                return ops._rglru_dispatch(a, u, None, mode=mode)
            a = np.zeros((2, 16, 32), np.float32)
            return fn, (a, a), {}
        return build

    out = []
    for mode in ("interpret", "ref"):
        out += [
            TraceEntry(f"kernel-bvsb-{mode}", build_bvsb(mode), x64=True),
            TraceEntry(f"kernel-flash-{mode}", build_flash(mode),
                       x64=True),
            TraceEntry(f"kernel-decode-{mode}", build_decode(mode),
                       x64=True),
            TraceEntry(f"kernel-rglru-{mode}", build_rglru(mode),
                       x64=True),
        ]
    return out


def _serving_classify_entry() -> TraceEntry:
    def build():
        from repro.configs import get_config
        from repro.models.model import build_model
        from repro.serving import executables
        cfg = get_config("tier-low")
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        fn = executables.classify_fn(model, params, 1)
        return fn, (params, np.zeros((1, 8), np.int32)), {}
    return TraceEntry("serving-classify", build)


def default_trace_entries() -> List[TraceEntry]:
    return ([_lane_core_entry(False), _lane_core_entry(True)]
            + _scheduler_entries() + [_serving_classify_entry()]
            + _kernel_entries())


def default_static_key_entries() -> List[StaticKeyEntry]:
    from repro.sim import jaxsim
    base = dict(n_devices=3, samples_per_device=6)
    # flip every traced per-point scalar plus the scheduler code and the
    # (also traced) real device count: none of it may move the key
    spec_a = jaxsim.JaxSimSpec("multitasc++", **base)
    spec_b = jaxsim.JaxSimSpec(
        "static", n_devices=5, samples_per_device=6,
        **{f: getattr(spec_a, f) * 0.5 + 0.01
           for f in jaxsim.TRACED_FIELDS})
    return [StaticKeyEntry(
        name="jaxsim-static",
        static_of=lambda sp: jaxsim._static_of(sp, n_servers=1,
                                               max_lat=0.05),
        spec_a=spec_a, spec_b=spec_b,
        traced_fields=jaxsim.TRACED_FIELDS)]
