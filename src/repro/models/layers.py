"""Shared model layers: norms, RoPE, embeddings, init helpers.

All models are functional: params are nested dicts of arrays, apply functions
are pure.  Leaf names follow the sharding conventions consumed by
repro.distributed.sharding (wq/wk/wv/w_in/w_gate = column-parallel,
wo/w_out = row-parallel, embed = vocab-parallel, …).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "rmsnorm", "init_rmsnorm", "rope", "yarn_inv_freq", "apply_rope",
    "softcap",
    "dense_init", "embed_init", "take_embed", "logits_from_embed",
    "ACT",
]

ACT = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


def init_rmsnorm(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.zeros((d,), dtype)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(dt)


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def rope(positions: jax.Array, head_dim: int, theta: float,
         yarn=None) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for absolute positions.  positions: (...,) int.
    ``yarn`` (a configs.base.YarnConfig) scales the frequencies and the
    tables as YaRN does."""
    half = head_dim // 2
    if yarn is None:
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        mscale = 1.0
    else:
        freq = jnp.asarray(yarn_inv_freq(head_dim, theta, yarn), jnp.float32)
        mscale = yarn.attention_factor
    ang = positions.astype(jnp.float32)[..., None] * freq  # (..., half)
    return jnp.cos(ang) * mscale, jnp.sin(ang) * mscale


def yarn_inv_freq(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies (head_dim/2,), as HF transformers'
    ``_compute_yarn_parameters`` with ``truncate``: index i keeps its
    original frequency theta^(-2i/d) up to the floor of the correction
    dimension of ``beta_fast`` rotations, takes it / ``factor`` from the
    ceiling of that of ``beta_slow`` rotations, and is ramped linearly
    between the two."""
    half = head_dim // 2

    def corr_dim(rotations):
        return (head_dim * math.log(yarn.original_max
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = theta ** (-np.arange(half, dtype=np.float64) / half)
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return extra * (1.0 - ramp) + extra / yarn.factor * ramp


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., L, H, D); cos/sin: (..., L, D/2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def dense_init(key: jax.Array, d_in: int, d_out: int, dtype=jnp.float32,
               scale: float | None = None) -> jax.Array:
    s = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * s).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def take_embed(embed: jax.Array, tokens: jax.Array, *, scale: bool = False) -> jax.Array:
    x = jnp.take(embed, tokens, axis=0)
    if scale:  # gemma-style sqrt(d) input scaling
        x = x * jnp.asarray(x.shape[-1] ** 0.5, x.dtype)
    return x


def logits_from_embed(embed: jax.Array, x: jax.Array,
                      cap: float | None = None) -> jax.Array:
    lg = jnp.einsum("...d,vd->...v", x, embed.astype(x.dtype))
    return softcap(lg.astype(jnp.float32), cap)
