"""Pallas kernel: LPSA sink+window flash attention (paper Sec. IV-B).

Single flash-style pass with the StreamingLLM mask (attention sink + local
window): per (head, q-block) the kernel sweeps key blocks with an online
softmax; scores and softmax statistics never leave VMEM — the TPU version of
the paper's claim that LPSA keeps attention intermediates off DRAM.

Supports GQA (q heads index kv heads via h // n_rep) and Gemma-style logit
soft-capping.  Positions are explicit arrays so the same kernel serves
prefill packs (contiguous positions) and the ring-buffer decode cache
(arbitrary slot->position maps, -1 = empty slot).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, out_ref,
                 m_scr, l_scr, acc_scr, *, n_kb: int, sink: int, window: int,
                 softcap: float | None, scale: float):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)               # (bq, D)
    k = k_ref[0].astype(jnp.float32)               # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    qp = qpos_ref[...]                             # (bq, 1) int32
    kp = kpos_ref[...]                             # (1, bk) int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    mask = (kp <= qp) & ((kp < sink) | (qp - kp < window)) & (kp >= 0)
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked running max so exp() stays finite
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
    p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = l_scr[...]
        out_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            out_ref.dtype)


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_pos: jax.Array, k_pos: jax.Array, *, sink: int,
                     window: int, softcap: float | None = None,
                     block_q: int = 128, block_k: int = 128,
                     interpret: bool = False) -> jax.Array:
    """q: (Hq, Lq, D); k, v: (Hkv, Lk, D); q_pos: (Lq,); k_pos: (Lk,).

    Returns (Hq, Lq, D) in q.dtype.  Batch is vmapped by the wrapper.
    """
    hq, lq, d = q.shape
    hkv, lk, _ = k.shape
    if hq % hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq}, {hkv}")
    n_rep = hq // hkv
    # tile shapes are autotuner parameters — degrade to divisors so every
    # candidate is runnable on awkward ring-cache lengths
    bq = min(block_q, lq)
    while lq % bq:
        bq -= 1
    bk = min(block_k, lk)
    while lk % bk:
        bk -= 1
    n_kb = lk // bk

    kernel = functools.partial(
        _attn_kernel, n_kb=n_kb, sink=sink, window=window, softcap=softcap,
        scale=1.0 / (d ** 0.5))
    return pl.pallas_call(
        kernel,
        grid=(hq, lq // bq, n_kb),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h // n_rep, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h // n_rep, j, 0)),
            pl.BlockSpec((bq, 1), lambda h, i, j: (i, 0)),
            pl.BlockSpec((1, bk), lambda h, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, lq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        # kept free of "sparse_attention" (see kernels/das_gemm.py's name)
        name="sparse_attn",
    )(q, k, v, q_pos.astype(jnp.int32)[:, None],
      k_pos.astype(jnp.int32)[None, :])
