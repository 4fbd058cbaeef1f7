"""Distributed step factories: sharded train / prefill / decode.

Key pieces:
  * vocab-parallel cross-entropy — the lm head stays sharded on the vocab
    axis; loss needs only (B,S)-sized pmax/psum collectives instead of an
    all-gather of (B,S,V) logits (637 GB for qwen3-32b train_4k!).
  * vocab-parallel BvSB — the paper's forwarding decision function (Eq. 2)
    evaluated on-accelerator directly from sharded decode logits; the
    cascade's confidence comes out of serve_step with no logits
    materialization at all.
  * serve_step = ONE decode token over a KV cache (the brief's decode
    shapes); train_step = full fwd/bwd + AdamW update.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch import shardings as sh
from repro.launch.mesh import batch_axes_of
from repro.models.common import MeshContext
from repro.models.model import IGNORE, Model
from repro.training import optimizer as opt

MODEL = "model"


# ---------------------------------------------------------------------------
# vocab-parallel head ops
# ---------------------------------------------------------------------------
def vocab_parallel_ce(hidden, table, labels, mesh, batch_axes, vocab_size):
    """hidden: (B,S,d); table: (PV,d) sharded on PV; labels: (B,S)."""
    ba = batch_axes if batch_axes else None

    def local(h, tb, lbl):
        vloc = tb.shape[0]
        v0 = jax.lax.axis_index(MODEL) * vloc
        logits = h.astype(jnp.float32) @ tb.astype(jnp.float32).T
        gidx = v0 + jnp.arange(vloc)
        logits = jnp.where(gidx < vocab_size, logits, -1e30)
        # stabilizer only -> constant wrt grads (pmax has no JVP rule)
        m = jax.lax.stop_gradient(
            jax.lax.pmax(jax.lax.stop_gradient(logits).max(-1), MODEL))
        z = jax.lax.psum(jnp.exp(logits - m[..., None]).sum(-1), MODEL)
        mask = lbl != IGNORE
        safe = jnp.where(mask, lbl, 0)
        inrange = (safe >= v0) & (safe < v0 + vloc)
        loc = jnp.clip(safe - v0, 0, vloc - 1)
        gold_l = jnp.take_along_axis(logits, loc[..., None], -1)[..., 0]
        gold = jax.lax.psum(jnp.where(inrange, gold_l, 0.0), MODEL)
        nll = (m + jnp.log(z) - gold) * mask
        num = nll.sum()
        den = mask.sum().astype(jnp.float32)
        if batch_axes:
            num = jax.lax.psum(num, batch_axes)
            den = jax.lax.psum(den, batch_axes)
        return num / jnp.maximum(den, 1.0)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(ba, None, None), P(MODEL, None), P(ba, None)),
        out_specs=P(), check_vma=False)(hidden, table, labels)


def vocab_parallel_bvsb(hidden, table, mesh, batch_axes, vocab_size):
    """hidden: (B,1,d) -> (bvsb (B,), top1 (B,)). Eq. 2 on-accelerator."""
    ba = batch_axes if batch_axes else None

    def local(h, tb):
        vloc = tb.shape[0]
        v0 = jax.lax.axis_index(MODEL) * vloc
        logits = (h[:, 0, :].astype(jnp.float32)
                  @ tb.astype(jnp.float32).T)                    # (B, vloc)
        gidx = v0 + jnp.arange(vloc)
        logits = jnp.where(gidx < vocab_size, logits, -1e30)
        m1l = logits.max(-1)
        argl = logits.argmax(-1).astype(jnp.int32) + v0
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + v0
        m2l = jnp.where(cols == argl[:, None], -jnp.inf, logits).max(-1)
        zl = jnp.exp(logits - m1l[:, None]).sum(-1)

        m1 = jax.lax.pmax(m1l, MODEL)
        # global runner-up: best of (local m2 where local max is global max,
        # local m1 otherwise)
        m2 = jax.lax.pmax(jnp.where(m1l == m1, m2l, m1l), MODEL)
        z = jax.lax.psum(zl * jnp.exp(m1l - m1), MODEL)
        top1 = jax.lax.pmax(jnp.where(m1l == m1, argl, -1), MODEL)
        bvsb = (1.0 - jnp.exp(m2 - m1)) / z
        return bvsb, top1

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(ba, None, None), P(MODEL, None)),
        out_specs=(P(ba), P(ba)), check_vma=False)(hidden, table)


# ---------------------------------------------------------------------------
# step factories
# ---------------------------------------------------------------------------
def _head_table(params, cfg):
    return params["embed"]["table"] if cfg.tie_embeddings \
        else params["lm_head"]["table"]


def default_accum_steps(n_params: float, global_batch: int,
                        data_shards: int) -> int:
    """Gradient-accumulation depth: keeps per-device live activations of
    the layer-remat carry within HBM for the big dense configs."""
    if global_batch < 2 * data_shards:
        return 1
    per = 8 if n_params > 2e10 else (4 if n_params > 4e9 else 1)
    while global_batch % (per * data_shards) != 0 and per > 1:
        per //= 2
    return per


def make_train_step(model: Model, mesh, *, remat=True, accum_steps=1,
                    adamw: opt.AdamWConfig = opt.AdamWConfig()):
    cfg = model.cfg
    batch_axes = batch_axes_of(mesh)
    mctx = MeshContext(batch_axes=batch_axes, model_axis=MODEL, mesh=mesh)

    def loss_fn(params, batch):
        labels = batch["labels"]
        hidden, _, aux = model.forward(params, batch, mctx, remat=remat,
                                       return_hidden=True)
        if hidden.shape[1] != labels.shape[1]:  # vlm: vision prefix
            hidden = hidden[:, -labels.shape[1]:]
        ce = vocab_parallel_ce(hidden, _head_table(params, cfg), labels,
                               mesh, batch_axes, cfg.vocab_size)
        return ce + aux, {"ce": ce, "aux": aux}

    def grads_of(params, batch):
        if accum_steps <= 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        # microbatch accumulation as a scan: bounded activation memory,
        # trip-count visible to the HLO cost analysis
        b = batch["tokens"].shape[0]
        assert b % accum_steps == 0, (b, accum_steps)
        mb = b // accum_steps
        chunked = jax.tree.map(
            lambda x: x.reshape((accum_steps, mb) + x.shape[1:]), batch)

        def body(carry, chunk):
            g_acc, l_acc, m_acc = carry
            (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, chunk)
            g_acc = jax.tree.map(
                lambda a, x: a + x.astype(jnp.float32) / accum_steps,
                g_acc, g)
            m_acc = {k: m_acc[k] + m[k] / accum_steps for k in m_acc}
            return (g_acc, l_acc + l / accum_steps, m_acc), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        m0 = {"ce": jnp.zeros(()), "aux": jnp.zeros(())}
        (grads, loss, metrics), _ = jax.lax.scan(
            body, (g0, jnp.zeros(()), m0), chunked)
        return (loss, metrics), grads

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = grads_of(params, batch)
        params, opt_state, om = opt.update(params, grads, opt_state, adamw)
        return params, opt_state, {"loss": loss, **metrics, **om}

    return train_step


def make_prefill_step(model: Model, mesh):
    cfg = model.cfg
    batch_axes = batch_axes_of(mesh)
    mctx = MeshContext(batch_axes=batch_axes, model_axis=MODEL, mesh=mesh)

    def prefill_step(params, batch):
        hidden, cache, _ = model.forward(params, batch, mctx,
                                         collect_cache=True,
                                         return_hidden=True)
        conf, top1 = vocab_parallel_bvsb(hidden[:, -1:, :],
                                         _head_table(params, cfg), mesh,
                                         batch_axes, cfg.vocab_size)
        return conf, top1, cache

    return prefill_step


def make_serve_step(model: Model, mesh, global_batch: int):
    """ONE new token with a KV cache (decode shapes). Returns the paper's
    forwarding-decision inputs (BvSB confidence + top-1) on-device."""
    cfg = model.cfg
    batch_axes = batch_axes_of(mesh)
    import numpy as np
    nb = int(np.prod([mesh.shape[a] for a in batch_axes]))
    eff_batch_axes = batch_axes if global_batch % nb == 0 and \
        global_batch >= nb else ()
    mctx = MeshContext(batch_axes=eff_batch_axes, model_axis=MODEL, mesh=mesh)

    def serve_step(params, tokens1, cache, pos):
        hidden, new_cache = model.decode_step(params, tokens1, cache, pos,
                                              mctx, return_hidden=True)
        conf, top1 = vocab_parallel_bvsb(hidden, _head_table(params, cfg),
                                         mesh, eff_batch_axes,
                                         cfg.vocab_size)
        return conf, top1, new_cache

    return serve_step
