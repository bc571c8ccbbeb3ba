"""jit'd public wrappers around the kernels with backend-aware dispatch.

Kernel modes (``Runtime.kernel_mode`` / ``--kernel-mode``):

  * ``ref``        — pure-jnp reference paths everywhere.
  * ``interpret``  — Pallas kernels under ``interpret=True`` (emulated; slow
    but exercises the real grid/BlockSpec code on any backend).
  * ``pallas``     — compiled Pallas kernels, unconditionally.
  * ``compiled``   — backend-capability probe: compiled Pallas where the
    backend supports it (TPU/GPU), ``interpret=True`` otherwise, so one mode
    runs the same kernel code everywhere.
  * ``tuned``      — per-shape dispatch from the autotune cache
    (kernels/autotune.py): Pallas tile configs on TPU/GPU, the XLA-native
    decode-GEMMs (kernels/xla_gemm.py) on CPU.  Tune eagerly (ServeEngine
    warmup / ``python -m repro.kernels.autotune``) BEFORE tracing: inside a
    jit trace the lookup is cache-read-only and falls back to the perfmodel
    ranking on a miss.
  * ``auto``       — ``pallas`` on TPU, reference elsewhere (legacy default).

All model code calls through these so the kernel layer is swappable per
backend without touching the models.  When a kernel mode is requested but a
shape is inadmissible (``packed_gemm_ok`` / ``fused_das_ok``), the caller
falls back to the reference path and reports it via :func:`note_fallback` —
once per shape (the warning fires at trace time, and XLA traces each shape
once), with counters surfaced in ``ServeEngine`` stats.
"""

from __future__ import annotations

import enum
import warnings
from collections import Counter
from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .das_gemm import das_gemv as _das_gemv_pallas
from .das_gemm import das_ternary_gemm as _das_ternary_gemm_pallas
from .sparse_attn import sparse_attention as _sparse_attn_pallas
from .ternary_gemm import K_SLAB, TRITS_PER_BYTE
from .ternary_gemm import ternary_gemm as _ternary_gemm_pallas
from .ternary_gemm import twd_decode as _twd_decode_pallas
from .topk_mask import topk_mask as _topk_mask_pallas

__all__ = [
    "KernelMode", "KERNEL_MODES",
    "backend_kind", "pallas_compiled_ok", "use_pallas",
    "kernel_wanted", "attn_kernel_wanted", "attn_kernel_fits",
    "packed_gemm_ok", "fused_das_ok",
    "note_fallback", "fallback_counts", "reset_fallbacks",
    "twd_decode", "ternary_gemm", "das_gemv", "das_ternary_gemm",
    "topk_mask", "sparse_attention", "K_SLAB",
]

class KernelMode(str, enum.Enum):
    """Typed kernel-mode selector replacing the stringly-typed mode kwarg.

    A ``str`` subclass, so every existing ``mode == "ref"`` /
    ``mode in ("pallas", ...)`` comparison keeps working on members.  Code
    that stores or hashes modes should normalise through
    ``KernelMode.parse(x).value`` (enum members hash by name, not by the
    mixed-in string value, so a raw member is a poor dict key next to
    plain strings).
    """
    REF = "ref"
    INTERPRET = "interpret"
    PALLAS = "pallas"
    COMPILED = "compiled"
    TUNED = "tuned"
    AUTO = "auto"
    # GSPMD-safe serving path: reference-style math with slice-free packed
    # decode, so XLA can shard the contraction over the "model" mesh axis
    # (see models/ternary_linear._apply_packed_sharded).  Excluded from
    # kernel_wanted/attn_kernel_wanted — Pallas kernels stay single-device.
    SHARDED = "sharded"

    def __str__(self) -> str:           # str(KernelMode.REF) == "ref" on 3.10+
        return self.value

    @classmethod
    def parse(cls, value) -> "KernelMode":
        """Accept a member, canonical name, or alias; reject anything else
        with a ValueError that lists the valid modes."""
        if isinstance(value, cls):
            return value
        if isinstance(value, str):
            v = _KERNEL_MODE_ALIASES.get(value.strip().lower(),
                                         value.strip().lower())
            try:
                return cls(v)
            except ValueError:
                pass
        raise ValueError(
            f"unknown kernel mode {value!r}: valid modes are "
            f"{', '.join(m.value for m in cls)} (aliases: "
            f"{', '.join(f'{a}->{b}' for a, b in sorted(_KERNEL_MODE_ALIASES.items()))})")


_KERNEL_MODE_ALIASES = {
    "reference": "ref", "jnp": "ref", "xla": "ref",
    "interp": "interpret", "emulate": "interpret", "emulated": "interpret",
    "mosaic": "pallas",
    "autotune": "tuned", "autotuned": "tuned",
    "spmd": "sharded", "gspmd": "sharded",
}

KERNEL_MODES = tuple(m.value for m in KernelMode)


def backend_kind() -> str:
    """The active JAX backend: "cpu" | "gpu" | "tpu"."""
    return jax.default_backend()


def pallas_compiled_ok() -> bool:
    """Can Pallas kernels compile natively on this backend?"""
    return backend_kind() in ("tpu", "gpu")


def use_pallas() -> bool:
    return backend_kind() == "tpu"


def kernel_wanted(mode: str) -> bool:
    """True when `mode` selects a non-reference execution path for the
    ternary linears (Pallas compiled/emulated, or the tuned dispatch)."""
    return mode in ("pallas", "interpret", "compiled", "tuned") \
        or (mode == "auto" and use_pallas())


def attn_kernel_wanted(mode: str) -> bool:
    """True when decode attention should route through the Pallas
    ``sparse_attn`` kernel.  Narrower than :func:`kernel_wanted`:
    ``interpret`` keeps the XLA flash path (emulated attention per decode
    step is pathological) and ``tuned`` picks per-shape in the caller."""
    return mode in ("pallas", "compiled") or (mode == "auto" and use_pallas())


def attn_kernel_fits(hq: int, hkv: int, lk: int, block_k: int = 128) -> bool:
    """True when the Pallas ``sparse_attn`` kernel tiles a decode over a
    cache of `lk` positions: whole groups of query heads per kv head, and
    key blocks the TPU's (8, 128) tiling accepts (the cache in one block,
    or in whole blocks of 128 lanes)."""
    return hq % hkv == 0 and (lk <= block_k or lk % 128 == 0)


def _pallas_opts(mode: str) -> dict | None:
    """kwargs for a Pallas call under `mode`, or None for the reference."""
    if mode == "pallas":
        return {}
    if mode == "interpret":
        return {"interpret": True}
    if mode == "compiled":
        return {} if pallas_compiled_ok() else {"interpret": True}
    if mode == "auto" and use_pallas():
        return {}
    return None


def packed_gemm_ok(k: int, packed_rows: int) -> bool:
    """Shapes admissible for the fused-decode `ternary_gemm` kernel: the
    packed rows must cover K exactly (no export padding beyond K) and K must
    tile by the 320-trit (64-byte) TWD slab."""
    return packed_rows * TRITS_PER_BYTE == k and k % K_SLAB == 0


def fused_das_ok(k: int, packed_rows: int, das) -> bool:
    """Shapes admissible for the fused `das_ternary_gemm` serving path:
    packed-GEMM-compatible AND the DAS block tiles the TWD slab (so a slab
    holds whole blocks and the compacted stream splits per K tile)."""
    return (das is not None and packed_gemm_ok(k, packed_rows)
            and K_SLAB % das.block == 0 and 0 < das.keep <= das.block)


# ---------------------------------------------------------------------------
# silent-fallback accounting (once-per-shape warnings + counters)
# ---------------------------------------------------------------------------

_fallbacks: Counter = Counter()
_fallback_warned: set = set()


def note_fallback(op: str, key: tuple, reason: str) -> None:
    """Record that a kernel mode fell back to the jnp reference for `key`
    (a hashable shape signature).  Warns once per (op, key); counts every
    occurrence.  Called at trace time from the dispatchers, so a jitted
    serving step reports each distinct shape exactly once."""
    _fallbacks[(op, key)] += 1
    if (op, key) not in _fallback_warned:
        _fallback_warned.add((op, key))
        warnings.warn(
            f"kernel fallback: {op}{key} -> jnp reference ({reason}); "
            f"perf-sensitive paths should use slab-aligned shapes",
            RuntimeWarning, stacklevel=3)


def fallback_counts() -> dict:
    """{(op, shape_key): count} of reference fallbacks since last reset."""
    return dict(_fallbacks)


def reset_fallbacks() -> None:
    _fallbacks.clear()
    _fallback_warned.clear()


# ---------------------------------------------------------------------------
# op wrappers
# ---------------------------------------------------------------------------

def twd_decode(packed: jax.Array, k: int, *, mode: str = "auto") -> jax.Array:
    """uint8 (Kp, N) -> int8 trits (k, N)."""
    opts = _pallas_opts("compiled" if mode == "tuned" else mode)
    if opts is not None:
        return _twd_decode_pallas(packed, **opts)[:k]
    return ref.twd_decode_ref(packed, k)


def ternary_gemm(x: jax.Array, packed: jax.Array, w_scale: jax.Array,
                 x_scale: jax.Array | None = None, *, mode: str = "auto",
                 **kw) -> jax.Array:
    """(M, K) x base-3-packed (K/5, N) -> (M, N) f32."""
    if mode == "tuned":
        from . import autotune
        return autotune.run_gemm(x, packed, w_scale, x_scale=x_scale, **kw)
    opts = _pallas_opts(mode)
    if opts is not None:
        return _ternary_gemm_pallas(x, packed, w_scale, x_scale, **opts, **kw)
    k = x.shape[-1]
    return ref.ternary_gemm_packed_ref(x, packed, w_scale, k, x_scale)


def das_gemv(values: jax.Array, indices: jax.Array, w_trits: jax.Array,
             w_scale: jax.Array, *, keep: int, mode: str = "auto",
             **kw) -> jax.Array:
    opts = _pallas_opts("compiled" if mode == "tuned" else mode)
    if opts is not None:
        return _das_gemv_pallas(values, indices, w_trits, w_scale, keep=keep,
                                **opts, **kw)
    return ref.das_gemv_ref(values, indices, w_trits, w_scale)


def das_ternary_gemm(values: jax.Array, indices: jax.Array,
                     packed: jax.Array, w_scale: jax.Array, *, keep: int,
                     block: int = 32, mode: str = "auto", **kw) -> jax.Array:
    """Fused serving path: (M, Kc) compacted activations x base-3 packed
    (K/5, N) -> (M, N) f32 — DAS scatter + TWD decode + matmul in one pass."""
    if mode == "tuned":
        from . import autotune
        return autotune.run_das_gemm(values, indices, packed, w_scale,
                                     keep=keep, block=block, **kw)
    opts = _pallas_opts(mode)
    if opts is not None:
        return _das_ternary_gemm_pallas(values, indices, packed, w_scale,
                                        keep=keep, block=block, **opts, **kw)
    k = packed.shape[0] * TRITS_PER_BYTE
    return ref.das_ternary_gemm_ref(values, indices, packed, w_scale, k)


def topk_mask(x: jax.Array, *, keep: int, block: int = 32,
              mode: str = "auto", **kw) -> jax.Array:
    """(…, K) -> int8 mask; leading dims flattened into rows."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    opts = _pallas_opts("compiled" if mode == "tuned" else mode)
    if opts is not None:
        m = _topk_mask_pallas(x2, keep=keep, block=block, **opts, **kw)
    else:
        m = ref.das_topk_mask_ref(x2, block_size=block, keep=keep).astype(jnp.int8)
    return m.reshape(*lead, x.shape[-1])


def sparse_attention(q, k, v, q_pos, k_pos, *, sink: int, window: int,
                     softcap: float | None = None, mode: str = "auto",
                     **kw) -> jax.Array:
    """Batched LPSA attention.  q: (B, Hq, Lq, D); k, v: (B, Hkv, Lk, D);
    q_pos: (B, Lq); k_pos: (B, Lk).  Returns (B, Hq, Lq, D).  Tile kwargs
    (``block_q``/``block_k``) pass through to the Pallas kernel.  ``tuned``
    resolves per-shape in models/attention.py; here it means ``compiled``."""
    opts = _pallas_opts("compiled" if mode == "tuned" else mode)
    if opts is not None:
        f = partial(_sparse_attn_pallas, sink=sink, window=window,
                    softcap=softcap, **opts, **kw)
        return jax.vmap(f)(q, k, v, q_pos, k_pos)

    def one(qb, kb, vb, qp, kp):
        hq, hkv = qb.shape[0], kb.shape[0]
        n_rep = hq // hkv
        kr = jnp.repeat(kb, n_rep, axis=0)
        vr = jnp.repeat(vb, n_rep, axis=0)
        return jax.vmap(lambda a, b, c: ref.sparse_attn_ref(
            a, b, c, qp, kp, sink=sink, window=window, softcap=softcap))(
                qb, kr, vr)
    return jax.vmap(one)(q, k, v, q_pos, k_pos)
