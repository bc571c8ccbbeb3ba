"""TWD — LUT-based 64B:80B Ternary Weight Decompression (paper Sec. III-E).

Each ternary value carries log2(3) = 1.585 bits of information; five trits fit
in one byte (3^5 = 243 <= 256), i.e. 1.6 bits/weight.  The paper stores weights
in this base-3 packed form in DRAM and decompresses them with a LUT ROM inside
the memory interface: 64 compressed bytes expand to 80 bytes of 2-bit-packed
weights (320 trits).

On TPU the "ROM" is a VMEM-resident (256, 5) int8 decode table and the
"decompressor" is a vectorized gather executed next to the MXU (see
kernels/ternary_gemm.py for the fused version).  This module provides:

  * offline packing (numpy/JAX) used when exporting checkpoints for serving,
  * the decode LUT constant,
  * pure-JAX decode (the oracle for the Pallas kernels),
  * helpers mapping between logical weight shapes and packed shapes.

Packing is along the *first* (input/K) axis so that a TP-sharded output axis
never splits a packed byte, and K stays contiguous for decode-then-matmul.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

__all__ = [
    "TRITS_PER_BYTE",
    "decode_lut",
    "pack_ternary",
    "unpack_ternary",
    "packed_dim",
    "packed_nbytes",
    "compression_ratio_vs_int2",
]

TRITS_PER_BYTE = 5
_POW3 = np.array([1, 3, 9, 27, 81], dtype=np.int32)  # 3^0 .. 3^4


def _build_decode_lut() -> np.ndarray:
    """(256, 5) int8 table: byte value -> 5 trits in {-1, 0, +1}.

    Entries >= 243 are invalid encodings; they decode to all-zeros (a packed
    stream produced by pack_ternary never contains them).
    """
    lut = np.zeros((256, TRITS_PER_BYTE), dtype=np.int8)
    for byte in range(3 ** TRITS_PER_BYTE):
        v = byte
        for i in range(TRITS_PER_BYTE):
            lut[byte, i] = (v % 3) - 1  # digit in {0,1,2} -> {-1,0,+1}
            v //= 3
    return lut


_DECODE_LUT_NP = _build_decode_lut()


def decode_lut() -> jax.Array:
    """The (256, 5) int8 decode table (paper's dual-port ROM contents)."""
    return jnp.asarray(_DECODE_LUT_NP)


def packed_dim(k: int) -> int:
    """Packed length of a K-sized axis (ceil division by 5)."""
    return (k + TRITS_PER_BYTE - 1) // TRITS_PER_BYTE


def packed_nbytes(shape: tuple[int, ...]) -> int:
    """Total bytes of the packed representation of a (K, ...) weight."""
    k = shape[0]
    rest = int(np.prod(shape[1:])) if len(shape) > 1 else 1
    return packed_dim(k) * rest


def pack_ternary(values: jax.Array | np.ndarray,
                 row_align: int = 1) -> jax.Array:
    """Pack int8 trits in {-1,0,1} along axis 0 into uint8, 5 per byte.

    (K, ...) -> (ceil(K/5), ...) rounded up so the packed row count is a
    multiple of `row_align` (16 at export => packed rows shard 16-way).
    K is zero-padded.
    """
    v = jnp.asarray(values, dtype=jnp.int32)
    k = v.shape[0]
    rows = -(-packed_dim(k) // row_align) * row_align
    kp = rows * TRITS_PER_BYTE
    if kp != k:
        pad = [(0, kp - k)] + [(0, 0)] * (v.ndim - 1)
        v = jnp.pad(v, pad)
    digits = v + 1  # {-1,0,1} -> {0,1,2}
    d = digits.reshape((kp // TRITS_PER_BYTE, TRITS_PER_BYTE) + v.shape[1:])
    pow3 = jnp.asarray(_POW3).reshape((1, TRITS_PER_BYTE) + (1,) * (v.ndim - 1))
    packed = jnp.sum(d * pow3, axis=1)
    return packed.astype(jnp.uint8)


@jax.named_scope("twd_unpack")
def unpack_ternary(packed: jax.Array, k: int) -> jax.Array:
    """Decode uint8 base-3 bytes back to int8 trits along axis 0.

    (P, ...) -> (k, ...) with k <= 5*P.  Pure-JAX oracle for the Pallas decode;
    implemented as the same LUT gather the hardware ROM performs.
    """
    lut = decode_lut()  # (256, 5)
    trits = lut[packed.astype(jnp.int32)]  # (P, ..., 5)
    # Move the trit digit axis next to P and flatten: (P, 5, ...) -> (5P, ...)
    trits = jnp.moveaxis(trits, -1, 1)
    flat = trits.reshape((packed.shape[0] * TRITS_PER_BYTE,) + packed.shape[1:])
    return flat[:k].astype(jnp.int8)


@jax.named_scope("twd_unpack")
def unpack_ternary_arith(packed: jax.Array, k: int) -> jax.Array:
    """Arithmetic (gather-free) decode: repeated div/mod by 3.

    Identical output to :func:`unpack_ternary`; preferred inside Pallas TPU
    kernels where a 256-entry gather is slower than 5 cheap integer ops.
    """
    p = packed.astype(jnp.int32)
    outs = []
    for _ in range(TRITS_PER_BYTE):
        outs.append((p % 3) - 1)
        p = p // 3
    trits = jnp.stack(outs, axis=1)  # (P, 5, ...)
    flat = trits.reshape((packed.shape[0] * TRITS_PER_BYTE,) + packed.shape[1:])
    return flat[:k].astype(jnp.int8)


def compression_ratio_vs_int2(k: int) -> float:
    """Bytes(base-3 packed) / Bytes(2-bit packed) for a K-length column.

    The paper's headline: 64B:80B = 0.8 (Sec. III-E).
    """
    b_base3 = packed_dim(k)
    b_int2 = (k + 3) // 4
    return b_base3 / b_int2
