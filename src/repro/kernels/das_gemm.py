"""Pallas kernels: DAS block-sparse ternary GEMV/GEMM (paper Sec. III-C/D/E).

The STL core consumes *compacted* activations — per 32-lane block only the
Top-K survive — and a butterfly router steers the matching weight channels.
On TPU the router becomes a one-hot **scatter**: the compacted values are
expanded back to their dense lane positions inside VMEM (a VPU
compare-select per compacted column over the 320-lane slab, done once per M
tile and reused across the N tiles), then a dense slab dot runs on the MXU.
HBM sees only the compacted activations (S_a x fewer bytes) — the bandwidth
side of DAS — while the FLOP saving of the butterfly does not transfer to a
dense systolic array (DESIGN.md §2).

Two kernels:

  * ``das_gemv``         — single-token GEMV against *unpacked* int8 trits
    (the paper's "STL core is optimized for GEMV" decode shape; batch rows
    vmapped by the caller).
  * ``das_ternary_gemm`` — the fused serving path: batched compacted
    activations routed straight against weights that *stay base-3 packed in
    HBM*.  Each K tile is the paper's 64B:80B slab (320 trits = 64 packed
    bytes): the VPU scatters the compacted values and decodes the packed
    slab while the MXU consumes the previous one.  This is the
    composition of DAS and TWD in one datapath — dense activations never
    round-trip through HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ternary_gemm import (K_SLAB, KP_SLAB, LANES, SUBLANES,
                                        TRITS_PER_BYTE, _decode_block,
                                        dot_dtype, out_tile, slab_major,
                                        slabs_per_step)

K_TILE = 512          # dense lanes per K tile (das_gemv)
BLOCK = 32            # DAS block size B_s


def _das_gemv_kernel(vals_ref, idx_ref, w_ref, wscale_ref, out_ref, *,
                     n_k: int, keep: int):
    """grid = (N/bn, K/K_TILE); one token.

    vals/idx: (1, bkc) compacted activation slab (bkc = K_TILE*keep/BLOCK),
    w: (K_TILE, bn) int8 trits, out: (1, bn) f32.
    """
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = vals_ref[...].astype(jnp.float32)       # (1, bkc)
    local = idx_ref[...] - k * K_TILE              # absolute -> tile-local
    # scatter to dense lanes: onehot (bkc, K_TILE) — the "butterfly router"
    lanes = jax.lax.broadcasted_iota(jnp.int32, (local.shape[1], K_TILE), 1)
    onehot = (local[0, :, None] == lanes).astype(jnp.float32)
    dense = jax.lax.dot(vals, onehot,
                        preferred_element_type=jnp.float32)   # (1, K_TILE)
    w = w_ref[...].astype(jnp.float32)
    out_ref[...] += jax.lax.dot(dense, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finalize():
        out_ref[...] = out_ref[...] * wscale_ref[0, 0]


def das_gemv(values: jax.Array, indices: jax.Array, w_trits: jax.Array,
             w_scale: jax.Array, *, keep: int = BLOCK // 2,
             block_n: int = 256, interpret: bool = False) -> jax.Array:
    """(Kc,) compacted values/indices  x  (K, N) trits  ->  (N,) f32.

    Kc = K * keep / BLOCK; indices must be block-sorted ascending (the
    output of core.das.das_compact).
    """
    (kc,) = values.shape
    kdim, n = w_trits.shape
    if kc * BLOCK != kdim * keep:
        raise ValueError(f"Kc={kc} inconsistent with K={kdim}, keep={keep}")
    if kdim % K_TILE:
        raise ValueError(f"K={kdim} must be a multiple of {K_TILE}")
    bkc = K_TILE * keep // BLOCK
    bn = min(block_n, n)
    if n % bn:
        raise ValueError(f"N={n} not tileable by {bn}")
    n_k = kdim // K_TILE

    kernel = functools.partial(_das_gemv_kernel, n_k=n_k, keep=keep)
    out = pl.pallas_call(
        kernel,
        grid=(n // bn, n_k),
        in_specs=[
            pl.BlockSpec((1, bkc), lambda j, k: (0, k)),
            pl.BlockSpec((1, bkc), lambda j, k: (0, k)),
            pl.BlockSpec((K_TILE, bn), lambda j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
        name="das_gemv",
    )(values[None, :], indices[None, :].astype(jnp.int32), w_trits,
      jnp.asarray(w_scale, jnp.float32).reshape(1, 1))
    return out[0]


# ---------------------------------------------------------------------------
# das_ternary_gemm: fused DAS scatter + TWD decode + matmul (serving path)
# ---------------------------------------------------------------------------

def _das_ternary_gemm_kernel(vals_ref, idx_ref, p_ref, wscale_ref, out_ref,
                             dense_scr, *, n_k: int, bk: int):
    """grid = (M tiles, N tiles, K steps of `bk` slabs), iterated in order.

    vals/idx: (bk, bm, cs) slab-major compacted activations (cs compacted
    lanes per 320-trit slab), p: (bk*64, bn) uint8 base-3 packed weights,
    out: (bm, bn) f32, dense_scr: (K/320, bm, 320) the scattered activations
    of this M tile, written on the first N tile and reused by the others.
    """
    j, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j == 0)
    def _scatter():
        # the butterfly router: every compacted column lands on the one dense
        # lane its index names (indices are unique within a token's slab, so
        # a chain of selects is an exact scatter).  Columns are read as
        # static lane slices, so no reshape splits the lane axis; rows go
        # in sublane groups to bound the live (rows, 320) intermediates.
        bm = vals_ref.shape[1]
        rows = SUBLANES if bm % SUBLANES == 0 else bm
        groups = bm // rows
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, K_SLAB), 1)

        def one_group(t, carry):
            s, g = t // groups, t % groups              # slab, row group
            r0 = pl.multiple_of(g * rows, rows)
            vals = vals_ref[s, pl.ds(r0, rows), :].astype(jnp.float32)
            loc = idx_ref[s, pl.ds(r0, rows), :] - (k * bk + s) * K_SLAB
            dense = jnp.zeros((rows, K_SLAB), jnp.float32)
            for c in range(vals.shape[1]):
                dense = jnp.where(loc[:, c:c + 1] == lanes, vals[:, c:c + 1],
                                  dense)
            dense_scr[k * bk + s, pl.ds(r0, rows), :] = dense.astype(
                dense_scr.dtype)
            return carry

        jax.lax.fori_loop(0, bk * groups, one_group, 0)

    # TWD decode of the 64B:80B slab(s) on the VPU, then the MXU slab dot
    def slab_dot(s, carry):
        w = _decode_block(p_ref[pl.ds(pl.multiple_of(s * KP_SLAB, KP_SLAB),
                                      KP_SLAB), :])
        out_ref[...] += jax.lax.dot(
            dense_scr[k * bk + s], w.astype(dense_scr.dtype),
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, bk, slab_dot, 0)

    @pl.when(k == n_k - 1)
    def _finalize():
        out_ref[...] = out_ref[...] * wscale_ref[0, 0]


def das_ternary_gemm(values: jax.Array, indices: jax.Array,
                     packed: jax.Array, w_scale: jax.Array, *, keep: int,
                     block: int = BLOCK, block_m: int = 128,
                     block_n: int = 640, block_k: int = 4,
                     interpret: bool = False) -> jax.Array:
    """(M, Kc) compacted values/indices  x  base-3 packed (K/5, N) -> (M, N).

    Kc = K * keep / block; indices are absolute K-lane ids, block-sorted
    ascending (core.das.das_compact output).  K must tile by the 320-trit
    (64-byte) TWD slab and `block` must divide the slab.  Weights stay
    packed in HBM; activations enter compacted — the fused DAS+TWD datapath.
    Tile shapes are autotuner parameters: ``block_m``/``block_n`` bound the
    output tile (see `ternary_gemm.out_tile`), ``block_k`` is the number of
    320-trit slabs scattered + decoded per K step (degraded to a divisor of
    K/320).
    """
    m, kc = values.shape
    kp, n = packed.shape
    kdim = kp * TRITS_PER_BYTE
    if kc * block != kdim * keep:
        raise ValueError(f"Kc={kc} inconsistent with K={kdim}, keep={keep}, "
                         f"block={block}")
    if kdim % K_SLAB:
        raise ValueError(f"K={kdim} must be a multiple of the {K_SLAB}-trit slab")
    if K_SLAB % block:
        raise ValueError(f"DAS block {block} must divide the {K_SLAB}-trit slab")
    if not (0 < keep <= block):
        raise ValueError(f"keep={keep} out of range for block {block}")
    n_slab = kdim // K_SLAB
    bk = slabs_per_step(n_slab, block_k)
    cs = K_SLAB // block * keep                 # compacted lanes per slab
    bm = out_tile(m, block_m, SUBLANES)
    bn = out_tile(n, block_n, LANES)
    n_k = n_slab // bk

    kernel = functools.partial(_das_ternary_gemm_kernel, n_k=n_k, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn), n_k),
        in_specs=[
            pl.BlockSpec((bk, bm, cs), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((bk, bm, cs), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((bk * KP_SLAB, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_slab, bm, K_SLAB),
                                   dot_dtype(values.dtype))],
        # the scatter on the first N tile feeds the later ones: only the
        # M axis may be split across cores
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        # the HLO instruction's name in a trace, which also appears in the
        # operand list of every op that reads the output: kept free of
        # "das_ternary_gemm", which bench/lib/kernels.json matches anywhere
        # in an instruction's text
        name="das_gemm",
    )(slab_major(values, cs), slab_major(indices.astype(jnp.int32), cs),
      packed, jnp.asarray(w_scale, jnp.float32).reshape(1, 1))
