"""Pallas TPU kernels: TWD decode + fused ternary mpGEMM (STL-core analogue).

Two kernels:

  * ``twd_decode``   — the paper's 64B:80B decompressor: uint8 base-3 bytes
    (5 trits each) expand to int8 {-1,0,1} in VMEM.  The arithmetic div/mod
    decode replaces the dual-port-ROM lookup (cheaper than a 256-gather on
    the VPU; identical output).
  * ``ternary_gemm`` — fused decode + matmul: activations (int8 or float)
    stream through the MXU against weights that *stay base-3 packed in HBM*
    (1.6 bits/weight).  K is tiled in 320-trit slabs = 64 packed bytes —
    literally the paper's 64B:80B block.  Decode happens on the VPU while the
    MXU consumes the previous slab, so the memory win costs no MXU time.

Weight layout: packed (K/5, N) uint8, packing along K (axis 0) so a TP shard
of the N axis never splits a byte.  Accumulation: f32 (exact for int8
activations up to |K| ~ 1e5 — asserted in the wrapper).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TRITS_PER_BYTE = 5
K_SLAB = 320          # trits per K tile  (= 64 packed bytes : 80 int2 bytes)
KP_SLAB = K_SLAB // TRITS_PER_BYTE


def _decode_block(packed_u8: jax.Array) -> jax.Array:
    """(Kp, N) uint8 -> (5*Kp, N) trit values in int32, pack order preserved."""
    p = packed_u8.astype(jnp.int32)
    digits = []
    for _ in range(TRITS_PER_BYTE):
        digits.append(p % 3 - 1)
        p = p // 3
    w = jnp.stack(digits, axis=1)                  # (Kp, 5, N)
    return w.reshape(w.shape[0] * TRITS_PER_BYTE, w.shape[2])


# ---------------------------------------------------------------------------
# twd_decode: standalone decompressor (weight prefetch stage)
# ---------------------------------------------------------------------------

def _twd_decode_kernel(p_ref, out_ref):
    out_ref[...] = _decode_block(p_ref[...]).astype(jnp.int8)


def twd_decode(packed: jax.Array, *, block_n: int = 256,
               interpret: bool = False) -> jax.Array:
    """(Kp, N) uint8 -> (5*Kp, N) int8 trits."""
    kp, n = packed.shape
    bkp = min(kp, 512)
    bn = min(n, block_n)
    if kp % bkp or n % bn:
        raise ValueError(f"packed shape {packed.shape} not tileable by ({bkp},{bn})")
    return pl.pallas_call(
        _twd_decode_kernel,
        grid=(kp // bkp, n // bn),
        in_specs=[pl.BlockSpec((bkp, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bkp * TRITS_PER_BYTE, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((kp * TRITS_PER_BYTE, n), jnp.int8),
        interpret=interpret,
        name="twd_decode",
    )(packed)


# ---------------------------------------------------------------------------
# ternary_gemm: fused decode + matmul
# ---------------------------------------------------------------------------

# Mosaic lays a 32-bit array out in (8, 128) tiles: a block's last two dims
# must be multiples of these or span the whole array axis.
SUBLANES, LANES = 8, 128


def out_tile(dim: int, want: int, align: int) -> int:
    """Block length along one output axis: the whole axis when it fits in
    `want`, else `want` rounded down to the layout alignment.  The grid then
    takes ``cdiv(dim, tile)`` steps; a partial last block only computes
    rows/columns that are dropped on write (output rows and columns are
    independent in a matmul, so the padding never reaches a kept value)."""
    if dim <= want:
        return dim
    return max(align, want // align * align)


def slabs_per_step(n_slab: int, want: int) -> int:
    """320-trit slabs per K step: `want` degraded to a divisor of the slab
    count (the contraction must tile exactly)."""
    bk = max(1, min(want, n_slab))
    while n_slab % bk:
        bk -= 1
    return bk


def slab_major(x: jax.Array, width: int) -> jax.Array:
    """(M, n*width) -> (n, M, width).  A slab then sits on the leading axis,
    so its block spans the whole (M-tile, width) plane and meets the (8, 128)
    rule for any slab width (160 compacted or 320 dense lanes)."""
    m = x.shape[0]
    return x.reshape(m, -1, width).transpose(1, 0, 2)


def dot_dtype(dtype) -> jnp.dtype:
    """MXU operand type.  bf16 and int8 activations are exact in bf16 and the
    trits are exact in any float, so a bf16 dot with f32 accumulation gives
    the same sums as the f32 reference; f32 activations keep an f32 dot."""
    exact_in_bf16 = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.int8))
    return jnp.dtype(jnp.bfloat16 if jnp.dtype(dtype) in exact_in_bf16
                     else jnp.float32)


def _ternary_gemm_kernel(x_ref, p_ref, wscale_ref, xscale_ref, out_ref, *,
                         n_k: int, bk: int, mxu_dtype):
    """grid = (M tiles, N tiles, K steps of `bk` slabs).

    x: (bk, bm, 320) slab-major activations, p: (bk*64, bn) uint8,
    out: (bm, bn) f32, resident across the K steps.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def slab(s, carry):
        w = _decode_block(p_ref[pl.ds(pl.multiple_of(s * KP_SLAB, KP_SLAB),
                                      KP_SLAB), :])
        out_ref[...] += jax.lax.dot(
            x_ref[s].astype(mxu_dtype), w.astype(mxu_dtype),
            preferred_element_type=jnp.float32)
        return carry

    # a loop, not an unrolled sequence: Mosaic's compile time grows with the
    # unrolled decode, and one slab's decode already fills the VPU
    jax.lax.fori_loop(0, bk, slab, 0)

    @pl.when(k == n_k - 1)
    def _finalize():
        out_ref[...] = out_ref[...] * wscale_ref[0, 0] * xscale_ref[...]


def ternary_gemm(x: jax.Array, packed: jax.Array, w_scale: jax.Array,
                 x_scale: jax.Array | None = None, *, block_m: int = 128,
                 block_n: int = 640, block_k: int = 4,
                 interpret: bool = False) -> jax.Array:
    """Y[f32] = (x ⊙ rowscale) @ dequant(packed) — weights never unpacked in HBM.

    x: (M, K) int8 | bf16 | f32;  packed: (K/5, N) uint8;  w_scale: scalar;
    x_scale: (M, 1) f32 per-row activation scale (int8 path) or None.
    Tile shapes are autotuner parameters: ``block_m``/``block_n`` bound the
    output tile (see `out_tile`), ``block_k`` is the number of 320-trit
    slabs decoded per K step (degraded to a divisor of K/320).
    """
    m, kdim = x.shape
    kp, n = packed.shape
    if kp * TRITS_PER_BYTE != kdim:
        raise ValueError(f"K mismatch: x K={kdim}, packed holds {kp * TRITS_PER_BYTE}")
    if kdim % K_SLAB:
        raise ValueError(f"K={kdim} must be a multiple of the {K_SLAB}-trit slab")
    if kdim > 100_000 and x.dtype == jnp.int8:
        raise ValueError("f32 accumulation no longer exact for int8 at this K")
    bm = out_tile(m, block_m, SUBLANES)
    bn = out_tile(n, block_n, LANES)
    n_slab = kdim // K_SLAB
    bk = slabs_per_step(n_slab, block_k)
    n_k = n_slab // bk
    if x_scale is None:
        x_scale = jnp.ones((m, 1), jnp.float32)
    w_scale = jnp.asarray(w_scale, jnp.float32).reshape(1, 1)

    kernel = functools.partial(_ternary_gemm_kernel, n_k=n_k, bk=bk,
                               mxu_dtype=dot_dtype(x.dtype))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn), n_k),
        in_specs=[
            pl.BlockSpec((bk, bm, K_SLAB), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((bk * KP_SLAB, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ternary_gemm",
    )(slab_major(x, K_SLAB), packed, w_scale, x_scale)
