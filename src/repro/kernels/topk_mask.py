"""Pallas kernel: DAS Top-K-per-block bitmask (the paper's ASM generator).

Rank-per-lane formulation: inside every 32-lane block, lane i survives iff

    #{ |x_j| > |x_i| }  +  #{ j < i : |x_j| == |x_i| }  <  keep

i.e. strict-rank with lane-order tie-breaking — identical semantics to
core.das.das_mask (proved by tests).  The O(B^2)=32x32 broadcast compare per
block is pure VPU work, fully parallel across the (rows x blocks) grid — no
sort, no data-dependent control flow, which is exactly what the TPU vector
unit wants (the SFU of the paper computes the same TopK in hardware).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 32


def _topk_mask_kernel(x_ref, out_ref, *, keep: int, block: int):
    x = jnp.abs(x_ref[...].astype(jnp.float32))    # (bm, bk)
    bm, bk = x.shape
    nb = bk // block
    a = x.reshape(bm, nb, block)
    ai = a[:, :, :, None]                          # lane i
    aj = a[:, :, None, :]                          # lane j
    gt = jnp.sum((aj > ai), axis=-1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)  # i index
    jlt = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1) < lane
    eq_before = jnp.sum((aj == ai) & jlt[None, None], axis=-1)
    rank = gt + eq_before
    out_ref[...] = (rank < keep).reshape(bm, bk).astype(jnp.int8)


def topk_mask(x: jax.Array, *, keep: int = BLOCK // 2, block: int = BLOCK,
              block_m: int = 128, block_k: int = 512,
              interpret: bool = False) -> jax.Array:
    """(M, K) -> int8 {0,1} mask with `keep` survivors per `block` lanes."""
    m, kdim = x.shape
    if kdim % block:
        raise ValueError(f"K={kdim} not divisible by DAS block {block}")
    bm = min(block_m, m)
    bk = min(block_k, kdim)
    if m % bm or kdim % bk or bk % block:
        raise ValueError(f"bad tiling ({bm},{bk}) for ({m},{kdim})")
    kernel = functools.partial(_topk_mask_kernel, keep=keep, block=block)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, kdim // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, kdim), jnp.int8),
        interpret=interpret,
        name="topk_mask",
    )(x)
