"""Fused BvSB (Best-versus-Second-Best) confidence kernel — paper Eq. 2.

The forwarding decision function evaluates BvSB = P1 - P2 over the softmax
of every sample's logits, on every device and for every server batch. The
naive implementation materializes the full softmax and top-k sorts; this
kernel streams vocab tiles through VMEM once, tracking a running
(max1, max2, sum-exp, argmax) tuple with online rescaling:

    BvSB = (1 - exp(m2 - m1)) / sum_j exp(l_j - m1)

TPU mapping: grid = (B/BB, V/BV); the vocab (reduction) axis is the
minormost grid dim so the VMEM scratch accumulators stay resident across
vocab tiles; tiles are 128-lane aligned. The top-1 class index is tracked
alongside for the cascade's prediction reuse.

The (BB, BV) tile shape is a tunable: ``repro.kernels.autotune`` sweeps
the grid against the roofline memory bound and persists the winner, and
``repro.kernels.ops`` passes the persisted tiles in. Defaults below are
the hand-picked fallback when no tuned tiles exist.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BB = 8      # batch rows per tile (default; autotune may override)
BV = 512    # vocab lanes per tile (multiple of 128; autotune may override)

# finite column-pad value: exp(_NEG - m1) underflows to exactly 0 for any
# finite row max, and unlike -inf it cannot produce (-inf) - (-inf) = nan
# in the online rescale when a whole tile is padding
_NEG = -1e38


def _bvsb_kernel(logits_ref, bvsb_ref, top1_ref, m1_s, m2_s, z_s, idx_s):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)
    bv = logits_ref.shape[1]

    @pl.when(vi == 0)
    def _init():
        m1_s[...] = jnp.full_like(m1_s, -jnp.inf)
        m2_s[...] = jnp.full_like(m2_s, -jnp.inf)
        z_s[...] = jnp.zeros_like(z_s)
        idx_s[...] = jnp.zeros_like(idx_s)

    # every per-row quantity is a (BB, 1) column: Mosaic only takes a
    # rank-1 block that spans the whole array or is a multiple of 128
    x = logits_ref[...].astype(jnp.float32)            # (BB, BV)
    tile_m1 = jnp.max(x, axis=1, keepdims=True)
    tile_arg = jnp.argmax(x, axis=1, keepdims=True).astype(jnp.int32)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    masked = jnp.where(cols == tile_arg, jnp.float32(-jnp.inf), x)
    tile_m2 = jnp.max(masked, axis=1, keepdims=True)
    tile_z = jnp.sum(jnp.exp(x - tile_m1), axis=1, keepdims=True)

    m1_old, m2_old = m1_s[...], m2_s[...]
    z_old, idx_old = z_s[...], idx_s[...]

    m1_new = jnp.maximum(m1_old, tile_m1)
    loser = jnp.minimum(m1_old, tile_m1)  # runner-up candidate across tiles
    m2_new = jnp.maximum(jnp.maximum(m2_old, tile_m2), loser)
    z_new = (z_old * jnp.exp(m1_old - m1_new)
             + tile_z * jnp.exp(tile_m1 - m1_new))
    idx_new = jnp.where(tile_m1 > m1_old, tile_arg + vi * bv, idx_old)

    m1_s[...] = m1_new
    m2_s[...] = m2_new
    z_s[...] = z_new
    idx_s[...] = idx_new

    @pl.when(vi == nv - 1)
    def _fin():
        bvsb_ref[...] = (1.0 - jnp.exp(m2_s[...] - m1_s[...])) / z_s[...]
        top1_ref[...] = idx_s[...]


@functools.partial(jax.jit, static_argnames=("interpret", "bb", "bv"))
def bvsb(logits, *, interpret=False, bb=None, bv=None):
    """logits: (B, V) -> (bvsb (B,) fp32, top1 (B,) int32).

    ``bb``/``bv`` override the (BB, BV) tile shape (autotuned callers);
    both are clamped to the actual array extent. Ragged batches (a
    12-row pop off an unsorted ladder, a drained queue tail) round up to
    the next row-tile multiple with zero rows, and a vocab that is not a
    multiple of the lane tile rounds up with ``_NEG`` columns — the pads
    are inert to the online max/sum (exp underflows to exactly 0), cost
    at most one extra grid row/column, and are sliced off before
    returning.
    """
    b, v = logits.shape
    bb = min(bb or BB, b)
    bv = min(bv or BV, v)
    padv = -v % bv
    x = logits
    if padv:
        x = jnp.pad(x, ((0, 0), (0, padv)), constant_values=_NEG)
    padb = -b % bb
    if padb:
        x = jnp.pad(x, ((0, padb), (0, 0)))
    bp, vp = b + padb, v + padv
    out, top1 = pl.pallas_call(
        _bvsb_kernel,
        grid=(bp // bb, vp // bv),
        in_specs=[pl.BlockSpec((bb, bv), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((bb, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((bp, 1), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((bb, 1), jnp.float32),
            pltpu.VMEM((bb, 1), jnp.float32),
            pltpu.VMEM((bb, 1), jnp.float32),
            pltpu.VMEM((bb, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x)
    return out[:b, 0], top1[:b, 0]
