"""Mixture-of-Experts layer with expert-parallel (EP) sharding.

Scheme (production): activations are sharded over the batch ("data"/"pod")
axes and replicated over the "model" axis; expert weights are sharded over
the "model" axis (E_local = E / model_size experts per shard). Each shard:

  1. computes the router for its local tokens,
  2. packs tokens routed to its *local* experts into a static-capacity
     buffer (capacity-factor token dropping, GShard-style),
  3. runs the expert FFNs as one batched einsum,
  4. scatters gate-weighted outputs back to token order,
  5. psums partial outputs over the "model" axis.

This avoids all-to-all buffers entirely — the only collective is one
d_model-sized all-reduce per MoE layer (same as tensor-parallel MLP), at the
cost of router recompute per model shard (negligible). Shared experts
(DeepSeekMoE / Moonlight) run as a tensor-parallel dense MLP outside the
shard_map. On a single device (smoke tests / CPU) the same code runs with
E_local = E and the psum elided — one code path, no stubs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import KeyGen, MeshContext, dense_init

CAPACITY_FACTOR = 1.25


def moe_init(rng: KeyGen, cfg, dtype):
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    e = cfg.num_experts
    p = {
        "router": dense_init(rng(), (d, e), cfg.init_scale, jnp.float32),
        "w_gate": dense_init(rng(), (e, d, f), cfg.init_scale, dtype),
        "w_up": dense_init(rng(), (e, d, f), cfg.init_scale, dtype),
        "w_down": dense_init(rng(), (e, f, d), cfg.init_scale, dtype),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        p["shared"] = {
            "w_gate": dense_init(rng(), (d, fs), cfg.init_scale, dtype),
            "w_up": dense_init(rng(), (d, fs), cfg.init_scale, dtype),
            "w_down": dense_init(rng(), (fs, d), cfg.init_scale, dtype),
        }
    return p


def _route(x_flat, router_w, cfg):
    """Top-k routing. Returns (gates (N,k) fp32, ids (N,k) int32, probs)."""
    logits = x_flat.astype(jnp.float32) @ router_w  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, ids, probs


def _local_expert_compute(x_flat, wg, wu, wd, gates, ids, cfg, e0, e_local,
                          act, capacity):
    """Steps 2-4 above for experts [e0, e0+e_local)."""
    n, d = x_flat.shape
    k = cfg.num_experts_per_tok
    flat_ids = ids.reshape(-1)                      # (N*k,)
    flat_gates = gates.reshape(-1)
    tok = jnp.repeat(jnp.arange(n), k)

    local = (flat_ids >= e0) & (flat_ids < e0 + e_local)
    le = jnp.where(local, flat_ids - e0, e_local)   # dummy bucket = e_local
    oh = jax.nn.one_hot(le, e_local + 1, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1, le[:, None], 1)[:, 0]
    keep = local & (pos < capacity)
    le_c = jnp.where(keep, le, e_local)             # dropped -> dummy
    pos_c = jnp.where(keep, pos, 0)

    # dispatch into (e_local+1, C, d); dummy row absorbs drops/non-local
    buf = jnp.zeros((e_local + 1, capacity, d), x_flat.dtype)
    buf = buf.at[le_c, pos_c].add(jnp.where(keep[:, None], x_flat[tok], 0))
    buf = buf[:e_local]

    h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
        "ecd,edf->ecf", buf, wu)
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd)     # (e_local, C, d)

    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((1, capacity, d), out_buf.dtype)], axis=0)
    contrib = out_buf[le_c, pos_c] * (flat_gates * keep)[:, None].astype(
        out_buf.dtype)
    y = jnp.zeros((n, d), out_buf.dtype).at[tok].add(contrib)
    return y


def _shared_expert(params, x, act):
    g = act(x @ params["w_gate"])
    return (g * (x @ params["w_up"])) @ params["w_down"]


def aux_load_balance_loss(probs, ids, cfg):
    """Switch-style load-balance loss from router probs and assignments."""
    e = cfg.num_experts
    me = probs.mean(axis=0)                                    # (E,)
    counts = jnp.zeros((e,)).at[ids.reshape(-1)].add(1.0)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    return e * jnp.sum(me * frac)


def moe_apply(params, x, cfg, mctx: MeshContext, *, act=jax.nn.silu,
              return_aux: bool = False):
    """x: (B, S, d) -> (B, S, d) [, aux_loss]."""
    b, s, d = x.shape
    msize = mctx.model_size
    e = cfg.num_experts
    assert e % msize == 0, (e, msize)
    e_local = e // msize
    k = cfg.num_experts_per_tok

    def local_fn(x_blk, router_w, wg, wu, wd):
        # x_blk: (b_loc, s, d), replicated over the model axis
        nl = x_blk.shape[0] * x_blk.shape[1]
        cap = max(int(CAPACITY_FACTOR * nl * k / e), 8)
        xf = x_blk.reshape(nl, d)
        gates, ids, probs = _route(xf, router_w, cfg)
        if mctx.model_axis is not None:
            e0 = jax.lax.axis_index(mctx.model_axis) * e_local
        else:
            e0 = 0
        y = _local_expert_compute(xf, wg, wu, wd, gates, ids, cfg, e0,
                                  e_local, act, cap)
        if mctx.model_axis is not None:
            y = jax.lax.psum(y, mctx.model_axis)
        aux = aux_load_balance_loss(probs, ids, cfg)
        if mctx.batch_axes:
            aux = jax.lax.pmean(aux, mctx.batch_axes)
        return y.reshape(x_blk.shape).astype(x.dtype), aux

    if mctx.mesh is None or mctx.model_axis is None:
        y, aux = local_fn(x, params["router"], params["w_gate"],
                          params["w_up"], params["w_down"])
    else:
        ma = mctx.model_axis
        ba = mctx.batch_axes if mctx.batch_axes else None
        x_spec = P(ba, None, None)
        fn = jax.shard_map(
            local_fn, mesh=mctx.mesh,
            in_specs=(x_spec, P(None, None), P(ma, None, None),
                      P(ma, None, None), P(ma, None, None)),
            out_specs=(x_spec, P()),
            check_vma=False)
        y, aux = fn(x, params["router"], params["w_gate"], params["w_up"],
                    params["w_down"])

    if cfg.num_shared_experts:
        # tensor-parallel dense shared expert (pjit auto-sharded)
        y = y + _shared_expert(params["shared"], x, act).astype(y.dtype)

    if return_aux:
        return y, aux * cfg.router_aux_loss_coef
    return y
