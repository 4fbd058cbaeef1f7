"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler installed with JAX lowers and compiles for a described
``v5e:2x2`` topology without a chip, so what Mosaic or XLA:TPU would
refuse (a block shape the tiling rules forbid, a program that does not
fit the chip's 16 GiB) fails here, at no chip time. Nothing runs: these
tests say nothing about results or speed.

Covered:

* the fused BvSB kernel at every ``BATCH_LADDER`` bucket of the served
  vocabulary, plus an off-tile vocabulary — ``tpu_custom_call`` must be
  in the HLO, so the kernel (not a fallback) is what compiles;
* ``classify_fn``'s forward for ``tier-server-heavy`` at the top bucket
  under ``pallas`` dispatch;
* the lane-aligned sim core at fig11_lanes' B=64, and at N=4096, where
  the segmented frontier is on — and that its event loop never copies
  the server queue ring (no ring-sized relayout in the while body), and
  with the flat frontier reads its streams from one flat packed buffer
  (no 3-D stream gather, no stream-sized relayout);
* the device-axis-sharded sim core on the four-chip mesh;
* the sim cores' phase scopes (``jaxsim.*``), which must reach the ops'
  metadata the TPU profiler records.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and several test workers
import this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.configs import get_config
from repro.configs.cascade_tiers import (BATCH_LADDER, DEVICE_PROFILES,
                                         SERVER_PROFILES)
from repro.kernels import ops
from repro.models.model import build_model
from repro.serving import executables
from repro.sim import jaxsim, synthetic

HBM_BYTES = 16 * 2**30          # one v5e chip
SERVED_VOCAB = get_config("tier-server-heavy").vocab_size


@pytest.fixture(scope="module")
def topo():
    """The described topology, with the persistent compile cache off:
    entries written for a described chip cannot be read back here."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU library otherwise writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


SIM_SCOPES = ("jaxsim.event", "jaxsim.devices", "jaxsim.queue",
              "jaxsim.frontier", "jaxsim.boundary")


def _assert_scoped(compiled):
    text = compiled.as_text()
    for scope in SIM_SCOPES:
        assert scope in text, scope


# ops that move a whole buffer to a new shape or layout; the TPU
# compiler lowers a one-element dimension's removal as a ``reduce``
RELAYOUT_OPS = ("reshape", "copy", "transpose", "reduce")


def _while_body(text):
    """The instruction lines of the compiled while loop's body and of
    every computation it calls."""
    comps, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            lines = comps.setdefault(head.group(1), [])
        elif line == "}":
            lines = None
        elif lines is not None:
            lines.append(line)
    todo = re.findall(r"\bbody=%([^\s,]+)", text)
    assert todo, "no while loop in the compiled core"
    seen = set()
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo += [ref for line in comps[name]
                     for ref in re.findall(r"%([\w.\-]+)", line)
                     if ref in comps]
    return [line for name in seen for line in comps[name]]


def _assert_ring_not_copied(compiled, b, cap):
    """The event loop updates the queue ring in place: no op in its
    body relayouts a ring-sized buffer (``b * cap`` elements, or
    ``[b, cap]``), as the copies in and out of a flat layout around a
    vmapped ring write did on every trip."""
    ring = re.compile(rf"\[(?:{b * cap}|{b},{cap})\]")
    op = re.compile(r"= \S+ ({})\(".format("|".join(RELAYOUT_OPS)))
    copies = [line.strip()[:160] for line in _while_body(compiled.as_text())
              if ring.search(line.split(" = ", 1)[-1].split("(", 1)[0])
              and op.search(line)]
    assert not copies, copies


# an instruction line: its name, result shape (not a tuple), opcode and
# operands
HLO_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                       r"([\w\-]+)\(([^)]*)\)")


def _assert_streams_read_flat(compiled, b, n_pad, s):
    """The flat lane event reads each completing device's sample with
    one gather from a flat buffer of packed words: no gather in the loop
    body has a ``[b, n_pad, s]`` stream operand, and no op in it
    relayouts a stream-sized buffer (``b * n_pad * s`` elements)."""
    instrs = [m.groups() for m in map(HLO_INSTR.match,
                                      _while_body(compiled.as_text()))
              if m]
    shapes = {name: tuple(int(d) for d in dims.split(",") if d)
              for name, dims, _, _ in instrs}
    size = b * n_pad * s
    gathers, copies, flat_reads = [], [], 0
    for name, _, op, args in instrs:
        args = re.findall(r"%([\w.\-]+)", args)
        touched = [shapes[name]] + [shapes[a] for a in args if a in shapes]
        if op == "gather" and args:
            operand = shapes.get(args[0])
            if operand == (b, n_pad, s):
                gathers.append(name)
            flat_reads += operand == (size,)
        if op in RELAYOUT_OPS and any(
                int(np.prod(shape)) == size for shape in touched):
            copies.append(name)
    assert not gathers, gathers
    assert not copies, copies
    assert flat_reads == 1, flat_reads


def _assert_fits(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, total


@pytest.mark.parametrize("b,v", [(b, SERVED_VOCAB) for b in BATCH_LADDER]
                         + [(20, 1000)])
def test_bvsb_kernel_compiles(one_chip, b, v):
    bb, bv = ops._tuned_tiles("tpu")
    fn = functools.partial(ops._bvsb_dispatch, mode="pallas", bb=bb, bv=bv)
    x = jax.ShapeDtypeStruct((b, v), jnp.float32, sharding=one_chip)
    compiled = jax.jit(fn).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)


def test_classify_forward_compiles(one_chip):
    bucket = max(BATCH_LADDER)
    model = build_model(get_config("tier-server-heavy"))
    params = _shapes(jax.eval_shape(model.init, jax.random.key(0)),
                     one_chip)
    tokens = jax.ShapeDtypeStruct((bucket, 16), jnp.int32, sharding=one_chip)
    prev = ops.set_dispatch("pallas")
    try:
        fn = executables.classify_fn(model, params, bucket)
        compiled = fn.lower(params, tokens).compile()
    finally:
        ops.set_dispatch(prev)
        executables.clear_cache()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)


def _prepared(seeds, n, samples, **kw):
    dev, srv = DEVICE_PROFILES["low"], SERVER_PROFILES["inceptionv3"]
    streams = synthetic.batched_device_streams(seeds, n, samples,
                                               dev.accuracy, [srv.accuracy])
    spec = jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=n,
                             samples_per_device=samples)
    return jaxsim._prepare(spec, streams, np.full(n, dev.latency),
                           np.full(n, 0.15), (srv,), None, None, None,
                           None, **kw)


@pytest.mark.parametrize("seeds,n,samples,segmented", [
    (tuple(s % 8 for s in range(64)), 25, 600, False),   # fig11_lanes B=64
    ((0,), 4096, 40, True),                              # fig_scale budget
])
def test_lane_core_compiles(one_chip, seeds, n, samples, segmented):
    static, params, srv, arrays, _, _ = _prepared(seeds, n, samples)
    assert (static.seg > 0) == segmented
    core = jax.jit(functools.partial(jaxsim._run_core_lanes, static))
    compiled = core.lower(_shapes(params, one_chip), _shapes(srv, one_chip),
                          *_shapes(arrays, one_chip)).compile()
    _assert_fits(compiled)
    _assert_scoped(compiled)
    _assert_ring_not_copied(compiled, len(seeds), static.cap)
    if not segmented:
        _assert_streams_read_flat(compiled, len(seeds), static.n_pad,
                                  samples)


def test_device_sharded_core_compiles(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    static, params, srv, arrays, _, _ = _prepared((0,), 16384, 40,
                                                  device_shards=4)
    rep = NamedSharding(mesh, PartitionSpec())
    dev = NamedSharding(mesh, PartitionSpec("data"))
    args = (_shapes({k: v[0] for k, v in params.items()}, rep),
            _shapes(srv, rep),
            *(_shapes(a[0], rep if i == 7 else dev)
              for i, a in enumerate(arrays)))
    compiled = jaxsim._make_core_device(static, mesh).lower(*args).compile()
    assert "all-reduce" in compiled.as_text()
    _assert_fits(compiled)
    _assert_scoped(compiled)
