"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU v5e.

Interpret mode cannot see what the chip's compiler refuses: a block that
breaks the (8, 128) tiling rule, a kernel that needs more VMEM than its
scoped limit, a layout Mosaic cannot lower.  These tests compile the kernels
at bitnet-3b's published widths (d_model 3200, d_ff 8640, head_dim 100, an
LPSA ring of 1024) for a *described* v5e chip — no chip is needed — and
assert that the Pallas call survives into the compiled program.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library at a time, so a module-level
call would make the test workers collect different tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.das_gemm import das_ternary_gemm
from repro.kernels.sparse_attn import sparse_attention
from repro.kernels.ternary_gemm import ternary_gemm

D_MODEL, D_FF, HEADS, HEAD_DIM, RING = 3200, 8640, 32, 100, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the program"


@pytest.mark.parametrize("m,k,n", [
    (4, D_MODEL, D_FF),      # decode: gate/up projection
    (256, D_FF, D_MODEL),    # one LPSA pack of prefill: down projection
])
def test_das_ternary_gemm_compiles(one_chip, m, k, n):
    kc = k // 2              # DAS keeps 16 of every 32 lanes
    _compile(lambda v, i, p: das_ternary_gemm(v, i, p, 0.5, keep=16,
                                              block=32),
             one_chip, ((m, kc), jnp.bfloat16), ((m, kc), jnp.int32),
             ((k // 5, n), jnp.uint8))


@pytest.mark.parametrize("m,k,n,dtype", [
    (4, D_FF, D_MODEL, jnp.bfloat16),    # DAS-off route, down projection
    (4, D_MODEL, D_FF, jnp.int8),        # int8 activations
])
def test_ternary_gemm_compiles(one_chip, m, k, n, dtype):
    _compile(lambda x, p: ternary_gemm(x, p, 0.5), one_chip,
             ((m, k), dtype), ((k // 5, n), jnp.uint8))


def test_lpsa_decode_attention_compiles(one_chip):
    b = 4

    def attend(q, k, v, qp, kp):
        return jax.vmap(lambda *a: sparse_attention(
            *a, sink=128, window=RING - 128))(q, k, v, qp, kp)

    _compile(attend, one_chip,
             ((b, HEADS, 1, HEAD_DIM), jnp.bfloat16),
             ((b, HEADS, RING, HEAD_DIM), jnp.bfloat16),
             ((b, HEADS, RING, HEAD_DIM), jnp.bfloat16),
             ((b, 1), jnp.int32), ((b, RING), jnp.int32))
