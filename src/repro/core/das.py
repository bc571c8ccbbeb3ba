"""DAS — Dynamic Activation N:M Sparsity (paper Sec. III-C, Fig. 6).

Per token, the hidden dimension is split into blocks of size ``B_s`` (=32 in
the paper); inside each block the Top-K largest-|x| activations survive
(K = S_a * B_s, S_a = 1/2 by default).  The resulting bitmask M both zeroes
the dropped activations and — in hardware — steers a butterfly router that
skips the matching weight channels, shrinking the effective GEMM K-dim by S_a.

     Y = (Q_int8(X) .* M) @ Q_1.58(W)^T ,   M = TopK_block(|X|)      (Eq. 1)

TPU realization: the mask is a per-block rank comparison (no sort); the
"butterfly" becomes a per-block one-hot select that *compacts* the kept
activations and their lane ids to dense (S_a*K)-long rows, which the fused
kernel scatters back against the ternary weight slab before the MXU matmul
(kernels/das_gemm.py).  No top-k, sort or gather: all elementwise compares
and small reductions, which fuse.  This module holds the pure-JAX semantics:

  * ``das_mask``      — the N:M bitmask (ASM in the paper),
  * ``das_apply``     — masked activations (training / QAT path),
  * ``das_compact``   — mask -> compacted activations + absolute lane indices
                        (the serving path the kernels consume),
  * ``das_gemm_ref``  — compacted sparse GEMM oracle.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "DEFAULT_BLOCK",
    "das_mask",
    "das_apply",
    "das_compact",
    "das_gemm_ref",
    "CompactActivation",
]

DEFAULT_BLOCK = 32


class CompactActivation(NamedTuple):
    """Block-compacted activation: values + absolute K-lane indices."""

    values: jax.Array   # (..., K*S_a)
    indices: jax.Array  # (..., K*S_a) int32 lane ids into the original K
    keep_per_block: int
    block_size: int


def _check(k: int, block_size: int, keep: int) -> None:
    if k % block_size != 0:
        raise ValueError(f"hidden dim {k} not divisible by DAS block {block_size}")
    if not (0 < keep <= block_size):
        raise ValueError(f"keep={keep} out of range for block {block_size}")


@jax.named_scope("das")
def das_mask(x: jax.Array, *, block_size: int = DEFAULT_BLOCK,
             keep: int | None = None, sparsity: float = 0.5) -> jax.Array:
    """Top-K-per-block bitmask over |x| along the last axis (the paper's ASM).

    ``keep`` lanes per ``block_size`` survive; default keep = S_a * B_s with
    S_a = 1 - ``sparsity``... nb: the paper calls S_a the *valid* proportion,
    so S_a = keep/block_size and ``sparsity`` = 1 - S_a.
    """
    k = x.shape[-1]
    if keep is None:
        keep = max(1, int(round(block_size * (1.0 - sparsity))))
    if not (0 < keep <= block_size):
        raise ValueError(f"keep={keep} out of range for block {block_size}")
    rem = k % block_size
    if rem:  # non-divisible hidden dims (e.g. bitnet-1.3b d_ff=5460):
        # sparsify the divisible prefix, keep the tail lanes dense
        main = das_mask(x[..., :k - rem], block_size=block_size, keep=keep)
        tail = jnp.ones_like(x[..., k - rem:], dtype=bool)
        return jnp.concatenate([main, tail], axis=-1)
    nb = k // block_size
    xb = jnp.abs(x).reshape(x.shape[:-1] + (nb, block_size))
    return _block_keep(xb, keep).reshape(x.shape)


def _block_keep(ab: jax.Array, keep: int) -> jax.Array:
    """Kept lanes of each block of ``ab`` = |x| of shape (..., nb, B).

    Rank-comparison form (no sort): lane survives iff
      #{|x_j| > |x_i|} + #{j < i : |x_j| == |x_i|} < keep,
    so ties go to the lower lane, as ``lax.top_k`` breaks them.
    O(B^2)=32x32 compares — pure elementwise/reduce ops, which GSPMD
    partitions cleanly (lax.top_k lowers to sort, which XLA SPMD
    *fully replicates*: a 22 GiB all-gather per mask at pod scale).
    """
    ai = ab[..., :, None]
    aj = ab[..., None, :]
    gt = jnp.sum((aj > ai), axis=-1)
    lane = jnp.arange(ab.shape[-1])
    jlt = (lane[None, :] < lane[:, None])
    eq_before = jnp.sum((aj == ai) & jlt, axis=-1)
    return (gt + eq_before) < keep


@jax.named_scope("das")
def das_apply(x: jax.Array, mask: jax.Array) -> jax.Array:
    """Masked activations.  Gradient flows through surviving lanes only
    (mask treated as constant — the paper's sparsify-then-quantize QAT)."""
    return x * mask.astype(x.dtype)


@partial(jax.jit, static_argnames=("block_size", "keep"))
@jax.named_scope("das")
def das_compact(x: jax.Array, *, block_size: int = DEFAULT_BLOCK,
                keep: int = DEFAULT_BLOCK // 2) -> CompactActivation:
    """Compact the Top-K lanes of every block (the butterfly-router output).

    Returns values (..., nb*keep) in ``x.dtype`` and absolute int32 lane
    indices; indices within a block are ascending, so the downstream weight
    gather is quasi-contiguous.  The kept lanes are `das_mask`'s; a kept
    lane's slot is the count of kept lanes before it in its block, and each
    slot takes its lane by a one-hot select over the block — no top-k, sort
    or gather.  The select picks the value's bits, not a float sum, so the
    output is bit-exact (a summed -0.0 would come out +0.0).
    """
    k = x.shape[-1]
    _check(k, block_size, keep)
    nb = k // block_size
    xb = x.reshape(x.shape[:-1] + (nb, block_size))
    kept = _block_keep(jnp.abs(xb), keep)                   # (..., nb, B)
    lane = jnp.arange(block_size, dtype=jnp.int32)
    # slot of a kept lane = kept lanes at lower index (exclusive cumsum)
    before = lane[None, :] < lane[:, None]                  # (B_i, B_j)
    slot = jnp.sum(kept[..., None, :] & before, axis=-1, dtype=jnp.int32)
    slots = jnp.arange(keep, dtype=jnp.int32)[:, None]      # (keep, 1)
    onehot = kept[..., None, :] & (slot[..., None, :] == slots)
    uint = jnp.dtype(f"uint{8 * x.dtype.itemsize}")
    bits = jax.lax.bitcast_convert_type(xb, uint)[..., None, :]
    vals = jax.lax.bitcast_convert_type(
        jnp.sum(jnp.where(onehot, bits, 0), axis=-1, dtype=uint), x.dtype)
    idx = jnp.sum(jnp.where(onehot, lane, 0), axis=-1)    # (..., nb, keep)
    base = (jnp.arange(nb, dtype=jnp.int32) * block_size)[:, None]
    newshape = x.shape[:-1] + (nb * keep,)
    return CompactActivation(values=vals.reshape(newshape),
                             indices=(idx + base).reshape(newshape),
                             keep_per_block=keep, block_size=block_size)


def das_gemm_ref(ca: CompactActivation, w: jax.Array) -> jax.Array:
    """Oracle sparse GEMM: gather W rows at the kept lanes, dense matmul.

    ``w`` is (K, N).  For batched activations the gather is per token —
    exactly what the butterfly router materializes per cycle in the paper.
    """
    gathered = jnp.take(w, ca.indices, axis=0)       # (..., Kc, N)
    return jnp.einsum("...k,...kn->...n", ca.values.astype(jnp.float32),
                      gathered.astype(jnp.float32))
