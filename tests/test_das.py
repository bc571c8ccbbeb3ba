"""DAS block Top-K sparsity (Sec. III-C): exactness + optimality properties.

Property tests skip (via the hypothesis_compat shim) when hypothesis is
not installed; the deterministic exactness tests always run so tier-1
stays green in a bare environment.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import das


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([16, 32, 64]),
       st.integers(1, 16))
def test_mask_counts(seed, block, keep):
    keep = min(keep, block)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((3, block * 4)), jnp.float32)
    m = np.asarray(das.das_mask(x, block_size=block, keep=keep))
    counts = m.reshape(3, 4, block).sum(-1)
    assert np.all(counts == keep)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_mask_keeps_largest(seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((2, 64)).astype(np.float32)
    m = np.asarray(das.das_mask(jnp.asarray(x), block_size=32, keep=16))
    for r in range(2):
        for b in range(2):
            blk = np.abs(x[r, b * 32:(b + 1) * 32])
            mb = m[r, b * 32:(b + 1) * 32]
            # kept magnitude sum == top-16 magnitude sum (optimality)
            assert np.isclose(blk[mb].sum(), np.sort(blk)[-16:].sum(),
                              rtol=1e-6)


def test_compact_matches_masked_dense(rng):
    x = jnp.asarray(rng.standard_normal((3, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((128, 16)), jnp.float32)
    m = das.das_mask(x, block_size=32, keep=16)
    ca = das.das_compact(x, block_size=32, keep=16)
    ref = np.asarray(das.das_apply(x, m)) @ np.asarray(w)
    out = np.asarray(das.das_gemm_ref(ca, w))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_compact_indices_sorted_and_valid(rng):
    x = jnp.asarray(rng.standard_normal((2, 96)), jnp.float32)
    ca = das.das_compact(x, block_size=32, keep=8)
    idx = np.asarray(ca.indices).reshape(2, 3, 8)
    for b in range(3):
        blk = idx[:, b]
        assert np.all((blk >= b * 32) & (blk < (b + 1) * 32))
        assert np.all(np.diff(blk, axis=-1) > 0)


def test_gradient_flows_through_kept_only(rng):
    import jax
    x = jnp.asarray(rng.standard_normal((1, 64)), jnp.float32)
    m = das.das_mask(x, block_size=32, keep=16)
    g = jax.grad(lambda x_: jnp.sum(das.das_apply(x_, m)))(x)
    assert np.array_equal(np.asarray(g) != 0, np.asarray(m))


def _compact_oracle(x, block, keep):
    """The per-block top-k + sort + take_along_axis compaction, written out
    as the oracle for the gather-free `das_compact`."""
    import jax
    nb = x.shape[-1] // block
    xb = x.reshape(x.shape[:-1] + (nb, block))
    _, idx = jax.lax.top_k(jnp.abs(xb), keep)
    idx = jnp.sort(idx, axis=-1)
    vals = jnp.take_along_axis(xb, idx, axis=-1)
    abs_idx = idx.astype(jnp.int32) + (jnp.arange(nb, dtype=jnp.int32)
                                       * block)[:, None]
    shape = x.shape[:-1] + (nb * keep,)
    return vals.reshape(shape), abs_idx.reshape(shape)


def _compact_input(kind, lead, rng):
    """(*lead, 128) activations: random, forced ties, whole blocks equal,
    or signed zeros among ties."""
    shape = lead + (128,)
    if kind == "random":
        return rng.standard_normal(shape)
    if kind == "ties":  # few distinct magnitudes, both signs
        return rng.integers(-3, 4, shape) * 0.5
    if kind == "equal_blocks":  # every lane of a block the same |x|
        x = rng.standard_normal(shape)
        x[..., :32] = 1.25
        x[..., 32:64] = -1.25 * np.sign(rng.standard_normal(lead + (32,)))
        x[..., 64:96] = 0.0
        return x
    x = rng.integers(-1, 2, shape) * 0.0  # +-0.0 among small ties
    x[..., ::3] = np.copysign(0.0, rng.standard_normal(x[..., ::3].shape))
    x[..., ::5] = rng.integers(-2, 3, x[..., ::5].shape) * 0.75
    return x


@pytest.mark.parametrize("kind", ["random", "ties", "equal_blocks", "zeros"])
@pytest.mark.parametrize("lead", [(5,), (2, 3)])
@pytest.mark.parametrize("keep", [8, 16, 32])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_compact_bit_exact_vs_topk_gather(kind, lead, keep, dtype, rng):
    x = jnp.asarray(_compact_input(kind, lead, rng), dtype)
    vals, idx = _compact_oracle(x, 32, keep)
    ca = das.das_compact(x, block_size=32, keep=keep)
    assert ca.values.dtype == x.dtype and ca.indices.dtype == jnp.int32
    assert ca.values.shape == vals.shape and ca.indices.shape == idx.shape
    np.testing.assert_array_equal(np.asarray(ca.indices), np.asarray(idx))
    uint = np.dtype(f"uint{8 * x.dtype.itemsize}")
    np.testing.assert_array_equal(np.asarray(ca.values).view(uint),
                                  np.asarray(vals).view(uint))
