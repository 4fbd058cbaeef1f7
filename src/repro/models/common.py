"""Shared building blocks: init, norms, RoPE (incl. M-RoPE), MLPs, embeddings.

Pure-functional modules: params are nested dicts of jnp arrays; every block
exposes ``init(rng, cfg, ...) -> params`` and an apply function.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# mesh / sharding context threaded through the model
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MeshContext:
    """How the model should see the device mesh.

    batch_axes: mesh axis names the batch dim is sharded over (may be empty).
    model_axis: mesh axis name for tensor/expert parallelism (None on 1 device).
    mesh: the jax Mesh (None on single device).
    """
    batch_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = None
    mesh: Optional[jax.sharding.Mesh] = None

    @property
    def model_size(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]


LOCAL = MeshContext()


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(rng, shape, scale, dtype):
    # truncated-normal fan-in style init
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale / np.sqrt(max(fan_in / 1024.0, 1e-9)) if False else scale
    return (jax.random.truncated_normal(rng, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


class KeyGen:
    """Deterministic per-path PRNG splitting."""

    def __init__(self, rng):
        self._rng = rng

    def __call__(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps=1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def groupnorm(x, num_groups, eps=1e-6):
    """Headwise group norm used by xLSTM cells. x: (..., H, hd)."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE (standard + multimodal M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections: Sequence[int]):
    """Qwen2-VL multimodal RoPE [arXiv:2409.12191].

    x: (B, S, H, hd); positions3: (3, B, S) int32 giving (t, h, w) position
    ids; sections: per-axis frequency-block sizes summing to hd/2.
    """
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(hd, theta)  # (half,)
    # angle per axis, then select section-wise (static slicing)
    ang_axes = positions3.astype(jnp.float32)[..., None] * freqs  # (3, B, S, half)
    parts, off = [], 0
    for ax, sec in enumerate(sections):
        parts.append(ang_axes[ax, :, :, off:off + sec])
        off += sec
    ang = jnp.concatenate(parts, axis=-1)  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------
def mlp_init(rng: KeyGen, d, f, scale, dtype):
    return {
        "w_gate": dense_init(rng(), (d, f), scale, dtype),
        "w_up": dense_init(rng(), (d, f), scale, dtype),
        "w_down": dense_init(rng(), (f, d), scale, dtype),
    }


def mlp_apply(params, x, act: str = "silu"):
    a = jax.nn.silu if act == "silu" else jax.nn.gelu
    g = a(x @ params["w_gate"])
    return (g * (x @ params["w_up"])) @ params["w_down"]


# ---------------------------------------------------------------------------
# embedding / lm head with internal vocab padding (sharding-friendly)
# ---------------------------------------------------------------------------
def padded_vocab(vocab_size: int, multiple: int = 128) -> int:
    return ((vocab_size + multiple - 1) // multiple) * multiple


def embed_init(rng: KeyGen, vocab, d, scale, dtype):
    pv = padded_vocab(vocab)
    return {"table": dense_init(rng(), (pv, d), scale, dtype)}


def embed_apply(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def lm_head_apply(params, x, vocab_size: int):
    """Returns logits over the PADDED vocab, padding entries masked to -inf-ish.

    Keeping the padded width preserves clean sharding; the mask keeps
    padded classes out of softmax/BvSB/losses.
    """
    logits = x @ params["table"].T
    pv = params["table"].shape[0]
    if pv != vocab_size:
        mask = jnp.arange(pv) < vocab_size
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    return logits
