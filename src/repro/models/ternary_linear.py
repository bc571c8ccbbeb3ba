"""TernaryLinear — the paper's Ternary Linear module as a first-class layer.

One logical layer, three physical representations:

  * **master**  {"w": f32/bf16}          — training / QAT: STE ternary
    fake-quant + A8 activation fake-quant + DAS mask (Eq. 1 end-to-end).
  * **packed**  {"packed": u8, "scale"}  — serving: base-3 TWD bytes; the
    matmul goes through kernels/ops (Pallas fused decode on TPU, jnp
    reference elsewhere).
  * **trits**   {"trits": i8, "scale"}   — the paper's "naive INT8/INT2"
    ablation points (weights resident unpacked).

`export_serving` converts master -> packed/trits/bf16 offline, exactly like
the paper's offline weight encoder feeding the TWD ROM.

Serving dispatch for the packed representation (the paper's Sec. III-C/D/E
composition):

  * DAS on + kernel mode + slab-aligned shapes  ->  `ops.das_ternary_gemm`:
    activations are block-compacted once (`tlin_compact`, shareable across
    sibling projections of the same input) and routed *compacted* against
    the base-3 packed weights — dense activations never round-trip HBM.
  * kernel mode but DAS off / unaligned shapes  ->  `ops.ternary_gemm`
    (fused TWD decode, dense activations).
  * otherwise (or shapes incompatible with any kernel) -> pure-jnp
    reference: densified DAS mask + unpack + einsum.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import TernaryConfig
from repro.core import das as das_lib
from repro.core import ternary as tq
from repro.core import twd
from repro.kernels import ops

__all__ = ["tlin_init", "tlin_apply", "tlin_compact", "export_tlin",
           "MaskedActivation"]


class MaskedActivation(NamedTuple):
    """Densified DAS-masked activations — the tuned-mode shared prep when the
    autotuned impl is one of the ``xla_dense_*`` decode-GEMMs (a rank-compare
    mask is ~5x cheaper than the top-k compaction on XLA-CPU).  Produced by
    `tlin_compact`, consumed by `tlin_apply` via ``ca=`` like its compacted
    sibling `core.das.CompactActivation`."""

    x: jax.Array   # (..., K) f32, dropped lanes zeroed


def tlin_init(key: jax.Array, d_in: int, d_out: int, dtype=jnp.float32,
              scale: float | None = None) -> dict:
    s = scale if scale is not None else d_in ** -0.5
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * s
    return {"w": w.astype(dtype)}


def _das_maybe(x: jax.Array, tc: TernaryConfig) -> jax.Array:
    if tc.das is None:
        return x
    mask = das_lib.das_mask(x, block_size=tc.das.block, keep=tc.das.keep)
    return das_lib.das_apply(x, mask)


def tlin_compact(x: jax.Array, tc: TernaryConfig,
                 p: dict | None = None, *, kernel_mode: str = "ref"):
    """Block-compact `x` for the fused DAS serving path, or None.

    Returns a `CompactActivation` only when a layer with params `p` (any
    sibling sharing the same input works — pass one of them) would actually
    take the fused path; callers projecting the same `x` through several
    packed linears (q/k/v, gate/in) compute this once and pass it to each
    `tlin_apply` via ``ca=``.
    """
    if tc.das is None or not tc.enabled:
        return None
    if not ops.kernel_wanted(kernel_mode):
        return None
    if kernel_mode == "tuned":
        # the prep representation follows the tuned impl: xla_dense_* wants a
        # densified mask (shared across siblings), everything else compacted
        if p is None or "packed" not in p:
            return None
        from repro.kernels import autotune, xla_gemm
        k = x.shape[-1]
        m = 1
        for s in x.shape[:-1]:
            m *= s
        cfg = autotune.lookup("das_ternary_gemm", m=m, k=k,
                              n=p["packed"].shape[1], keep=tc.das.keep,
                              block=tc.das.block)
        if cfg.impl.startswith("xla_dense"):
            return MaskedActivation(
                xla_gemm.masked_dense(x, keep=tc.das.keep,
                                      block=tc.das.block))
        if cfg.impl == "ref" or k % tc.das.block:
            return None
        return das_lib.das_compact(x, block_size=tc.das.block,
                                   keep=tc.das.keep)
    if p is not None:
        if "packed" not in p:
            return None
        if not ops.fused_das_ok(x.shape[-1], p["packed"].shape[0], tc.das):
            return None
    return das_lib.das_compact(x, block_size=tc.das.block, keep=tc.das.keep)


def _apply_packed_tuned(p: dict, x2: jax.Array, tc: TernaryConfig,
                        ca) -> jax.Array:
    """Tuned-mode serving matmul: per-shape impl from the autotune cache.

    Trace-safe — `autotune.lookup` only reads the cache (perfmodel ranking on
    a miss); tuning happened eagerly in the ServeEngine warmup.  Unlike the
    Pallas modes this covers *any* K: the ``xla_dense_*`` impls mask with a
    dense tail, so e.g. bitnet's d_ff=5460 stays on a tuned path.
    """
    from repro.kernels import autotune, xla_gemm
    m, k = x2.shape
    scale = p["scale"]
    n = p["packed"].shape[1]
    if tc.das is None:
        return ops.ternary_gemm(x2, p["packed"], scale, mode="tuned")
    cfg = autotune.lookup("das_ternary_gemm", m=m, k=k, n=n,
                          keep=tc.das.keep, block=tc.das.block)
    if cfg.impl.startswith("xla_dense"):
        xs = ca.x.reshape(-1, k) if isinstance(ca, MaskedActivation) \
            else xla_gemm.masked_dense(x2, keep=tc.das.keep,
                                       block=tc.das.block)
        return xla_gemm.decode_matmul(xs, p["packed"], scale, impl=cfg.impl)
    if cfg.impl == "ref" or k % tc.das.block:
        ops.note_fallback("das_ternary_gemm", (m, k, n),
                          "no tuned candidate for this shape")
        xs = _das_maybe(x2, tc)
        w = twd.unpack_ternary_arith(p["packed"], k)
        return (xs.astype(jnp.float32) @ w.astype(jnp.float32)) * scale
    if not isinstance(ca, das_lib.CompactActivation):
        ca = das_lib.das_compact(x2, block_size=tc.das.block,
                                 keep=tc.das.keep)
    kc = ca.values.shape[-1]
    return autotune.run_das_gemm(
        ca.values.reshape(-1, kc), ca.indices.reshape(-1, kc), p["packed"],
        scale, keep=tc.das.keep, block=tc.das.block, cfg=cfg)


@jax.named_scope("twd_unpack")
def _unpack5(packed: jax.Array) -> jax.Array:
    """Slice-free base-3 decode: (Kp, N) u8 -> (5*Kp, N) i8 trits.

    ``twd.unpack_ternary_arith`` ends with a ``flat[:k]`` slice to drop the
    pack padding, which forces GSPMD to gather a K-sharded slab before
    slicing.  The sharded path instead decodes the *full* padded slab —
    padding bytes decode to 0-trits, so zero-padding the activations to
    5*Kp (see `_apply_packed_sharded`) makes the padded contraction exact.
    Pure reshape/arithmetic, so a "model"-sharded dim stays sharded.
    """
    digits = [(packed // jnp.uint8(3 ** i)) % 3 for i in range(twd.TRITS_PER_BYTE)]
    stacked = jnp.stack(digits, axis=1)            # (Kp, 5, N)
    flat = stacked.reshape(-1, packed.shape[-1])   # (5*Kp, N)
    return flat.astype(jnp.int8) - 1


def _apply_packed_sharded(p: dict, x2: jax.Array,
                          tc: TernaryConfig) -> jax.Array:
    """GSPMD-friendly packed matmul for the "sharded" kernel mode.

    Column-parallel layers shard N ("model" on packed dim 1) with no
    communication; row-parallel layers shard packed K (dim 0), and the
    zero-padded contraction below reduces with exactly one all-reduce —
    the Megatron one-collective-per-block-half pattern.  No Pallas, no
    dynamic slicing: every op here propagates a NamedSharding.
    """
    k = x2.shape[-1]
    xs = _das_maybe(x2, tc).astype(jnp.float32)
    w = _unpack5(p["packed"]).astype(jnp.float32)  # (5*Kp, N), zeros past k
    xp = jnp.pad(xs, ((0, 0), (0, w.shape[0] - k)))
    return (xp @ w) * p["scale"]


def _apply_packed(p: dict, x: jax.Array, tc: TernaryConfig,
                  kernel_mode: str, ca) -> jax.Array:
    """Serving matmul against base-3 packed weights (see module docstring)."""
    k = x.shape[-1]
    lead = x.shape[:-1]
    scale = p["scale"]
    kp = p["packed"].shape[0]
    if kernel_mode == "sharded":
        y = _apply_packed_sharded(p, x.reshape(-1, k), tc)
    elif kernel_mode == "tuned":
        y = _apply_packed_tuned(p, x.reshape(-1, k), tc, ca)
    elif ops.kernel_wanted(kernel_mode) and ops.fused_das_ok(k, kp, tc.das):
        # fused path: compacted activations straight into the kernel
        if ca is None:
            ca = das_lib.das_compact(x, block_size=tc.das.block,
                                     keep=tc.das.keep)
        kc = ca.values.shape[-1]
        y = ops.das_ternary_gemm(
            ca.values.reshape(-1, kc), ca.indices.reshape(-1, kc),
            p["packed"], scale, keep=tc.das.keep, block=tc.das.block,
            mode=kernel_mode)
    elif ops.kernel_wanted(kernel_mode) and ops.packed_gemm_ok(k, kp):
        xs = _das_maybe(x, tc)
        y = ops.ternary_gemm(xs.reshape(-1, k), p["packed"], scale,
                             mode=kernel_mode)
    else:  # shapes a kernel can't tile (or ref mode): pure-jnp reference
        if ops.kernel_wanted(kernel_mode):
            ops.note_fallback("ternary_gemm", (k, p["packed"].shape[1]),
                              f"K={k} not tileable by the {ops.K_SLAB}-trit "
                              f"slab (packed rows {kp})")
        xs = _das_maybe(x, tc)
        w = twd.unpack_ternary_arith(p["packed"], k)
        y = jnp.einsum("mk,kn->mn", xs.reshape(-1, k).astype(jnp.float32),
                       w.astype(jnp.float32)) * scale
    return y.reshape(*lead, y.shape[-1]).astype(x.dtype)


def tlin_apply(p: dict, x: jax.Array, tc: TernaryConfig, *,
               kernel_mode: str = "ref", ca=None) -> jax.Array:
    """Apply the ternary linear in whatever representation `p` carries.

    ``ca`` optionally supplies a precomputed `CompactActivation` of `x`
    (from `tlin_compact`) so sibling projections of one input don't repeat
    the per-block top-k; it is consulted only on the fused packed path.

    ``kernel_mode`` accepts anything ``ops.KernelMode.parse`` does (members,
    canonical names, aliases); unknown modes raise ValueError here, at the
    API edge, instead of silently selecting the reference path downstream.
    """
    kernel_mode = ops.KernelMode.parse(kernel_mode).value
    if not tc.enabled:
        w = p["w"] if "w" in p else p["w_hp"]
        return jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))

    if "w" in p:  # --- training / QAT path (differentiable) ----------------
        xs = _das_maybe(x, tc)
        xq = tq.int8_fake_quant(xs)
        wq = tq.ternary_fake_quant(p["w"])
        return jnp.einsum("...k,kn->...n", xq, wq.astype(xq.dtype))

    # --- serving paths ------------------------------------------------------
    if "packed" in p:
        return _apply_packed(p, x, tc, kernel_mode, ca)
    if "trits" in p:
        xs = _das_maybe(x, tc)
        w = p["trits"].astype(x.dtype) * p["scale"].astype(x.dtype)
        return jnp.einsum("...k,kn->...n", xs, w)
    raise KeyError(f"unrecognized ternary-linear params: {sorted(p)}")


def export_tlin(p: dict, tc: TernaryConfig) -> dict:
    """Master -> serving representation (offline encoder for the TWD path)."""
    if "w" not in p:
        return p
    if not tc.enabled:
        return {"w_hp": p["w"]}
    tw = tq.ternary_quantize(p["w"])
    if tc.serve_format == "packed":
        return {"packed": twd.pack_ternary(tw.values, row_align=16),
                "scale": tw.scale}
    if tc.serve_format == "int8":
        return {"trits": tw.values, "scale": tw.scale}
    if tc.serve_format == "bf16":
        return {"trits": tw.values.astype(jnp.bfloat16).astype(jnp.int8),
                "scale": tw.scale}
    raise ValueError(tc.serve_format)
