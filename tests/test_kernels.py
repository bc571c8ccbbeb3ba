"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import das, twd
from repro.kernels import ops, ref


@pytest.mark.parametrize("k,n", [(320, 128), (640, 256), (1600, 512)])
def test_twd_decode_kernel(rng, k, n):
    trits = rng.integers(-1, 2, size=(k, n)).astype(np.int8)
    packed = jnp.asarray(twd.pack_ternary(trits))
    out = np.asarray(ops.twd_decode(packed, k, mode="interpret"))
    assert np.array_equal(out, trits)


@pytest.mark.parametrize("m,k,n,dtype", [
    (8, 320, 128, "float32"), (16, 640, 256, "bfloat16"),
    (128, 960, 512, "float32"), (1, 320, 256, "float32"),
])
def test_ternary_gemm_kernel(rng, m, k, n, dtype):
    trits = rng.integers(-1, 2, size=(k, n)).astype(np.int8)
    packed = jnp.asarray(twd.pack_ternary(trits))
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.dtype(dtype))
    y = np.asarray(ops.ternary_gemm(x, packed, 0.5, mode="interpret"))
    yr = np.asarray(ref.ternary_gemm_packed_ref(x, packed, 0.5, k))
    tol = 2e-2 if dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(y, yr, rtol=tol, atol=tol)


def test_ternary_gemm_int8_exact(rng):
    k, n, m = 640, 256, 8
    trits = rng.integers(-1, 2, size=(k, n)).astype(np.int8)
    packed = jnp.asarray(twd.pack_ternary(trits))
    xi = jnp.asarray(rng.integers(-127, 128, size=(m, k)), jnp.int8)
    xsc = jnp.asarray(rng.random((m, 1)) + 0.5, jnp.float32)
    y = np.asarray(ops.ternary_gemm(xi, packed, 0.37, xsc, mode="interpret"))
    yr = np.asarray(ref.ternary_gemm_packed_ref(xi, packed, 0.37, k, xsc))
    np.testing.assert_allclose(y, yr, rtol=1e-6, atol=1e-6)  # exact int path


@pytest.mark.parametrize("m,k,keep", [(64, 512, 16), (128, 1024, 8),
                                      (32, 2048, 24)])
def test_topk_mask_kernel(rng, m, k, keep):
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    mk = np.asarray(ops.topk_mask(x, keep=keep, mode="interpret"))
    mr = np.asarray(ref.das_topk_mask_ref(x, block_size=32, keep=keep))
    mc = np.asarray(das.das_mask(x, block_size=32, keep=keep))
    assert np.array_equal(mk.astype(bool), mr)
    assert np.array_equal(mr, mc)  # three formulations agree


@pytest.mark.parametrize("k,n", [(512, 256), (1024, 512), (2048, 256)])
def test_das_gemv_kernel(rng, k, n):
    xv = jnp.asarray(rng.standard_normal((k,)), jnp.float32)
    ca = das.das_compact(xv[None], block_size=32, keep=16)
    w = jnp.asarray(rng.integers(-1, 2, size=(k, n)), jnp.int8)
    g = np.asarray(ops.das_gemv(ca.values[0], ca.indices[0], w, 0.5,
                                keep=16, mode="interpret"))
    gr = np.asarray(ref.das_gemv_ref(ca.values[0], ca.indices[0], w, 0.5))
    np.testing.assert_allclose(g, gr, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("hq,hkv,lq,lk,cap", [
    (4, 2, 256, 256, None), (4, 4, 128, 256, 30.0), (8, 1, 256, 128, None),
])
def test_sparse_attention_kernel(rng, hq, hkv, lq, lk, cap):
    B, D = 2, 64
    q = jnp.asarray(rng.standard_normal((B, hq, lq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, hkv, lk, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, hkv, lk, D)), jnp.float32)
    qp = jnp.broadcast_to(jnp.arange(lq) + (lk - lq), (B, lq)).astype(jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(lk), (B, lk)).astype(jnp.int32)
    a = np.asarray(ops.sparse_attention(q, k, v, qp, kp, sink=16, window=64,
                                        softcap=cap, mode="interpret"))
    b = np.asarray(ops.sparse_attention(q, k, v, qp, kp, sink=16, window=64,
                                        softcap=cap, mode="ref"))
    np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4)


def test_sparse_attention_ring_positions(rng):
    """Scrambled slot->position maps with empties (decode ring layout)."""
    B, Hq, Hkv, D, Lk = 2, 4, 2, 64, 128
    kp = np.concatenate([np.arange(8), 64 + (np.arange(56) + 7) % 56,
                         -np.ones(64)]).astype(np.int32)
    kp = jnp.asarray(np.broadcast_to(kp, (B, Lk)).copy())
    qp = jnp.full((B, 1), 120, jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, Hq, 1, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, Lk, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, Lk, D)), jnp.float32)
    a = np.asarray(ops.sparse_attention(q, k, v, qp, kp, sink=8, window=56,
                                        mode="interpret"))
    b = np.asarray(ops.sparse_attention(q, k, v, qp, kp, sink=8, window=56,
                                        mode="ref"))
    np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gemm_kernels_partial_tiles(rng, dtype):
    """M and N that the tiles do not divide (the grid's last blocks are
    partial), several K steps, and several N tiles reusing one M tile's
    scattered activations: both kernels still match their oracles."""
    m, k, n, keep = 12, 960, 200, 16
    tiles = dict(block_m=8, block_n=128, block_k=1)
    trits = rng.integers(-1, 2, size=(k, n)).astype(np.int8)
    packed = jnp.asarray(twd.pack_ternary(trits))
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.dtype(dtype))
    tol = 2e-2 if dtype == "bfloat16" else 1e-4
    y = ops.ternary_gemm(x, packed, 0.5, mode="interpret", **tiles)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.ternary_gemm_packed_ref(x, packed, 0.5,
                                                               k)),
        rtol=tol, atol=tol)
    ca = das.das_compact(x, block_size=32, keep=keep)
    y = ops.das_ternary_gemm(ca.values, ca.indices, packed, 0.5, keep=keep,
                             mode="interpret", **tiles)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.das_ternary_gemm_ref(
            ca.values, ca.indices, packed, 0.5, k)), rtol=tol, atol=tol)
