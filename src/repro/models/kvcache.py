"""KV caches and recurrent states for serving, behind a tagged CacheSpec API.

Three attention cache layouts (``CacheSpec.layout``):
  * full  — (B, S_max, Hkv, Dh) with a write cursor: the conventional cache
    (the paper's "naive" baseline whose DRAM traffic LPSA removes).
  * ring  — (B, sink+window, Hkv, Dh) + slot->position map: O(TL_SA) memory
    at ANY context length (the LPSA decode cache; core.lpsa.decode_slot).
  * paged — one (num_pages, page_size, Hkv, Dh) K/V arena shared by every
    sequence, addressed through per-sequence int32 page tables
    (B, pages_per_seq).  Memory scales with *live tokens*, not
    B x S_max, and pages holding a common prompt prefix can be shared
    between sequences by refcount (repro.serve.kvpool).  Page 0 is a
    reserved null page: unmapped page-table entries point at it and its
    positions stay -1, so gathers through unmapped entries are masked.

Recurrent states for SSM/linear-attention families (mamba / rwkv / gla) are
fixed-size per token — the "native sub-quadratic" path of the zoo — and get
their own CacheSpec layouts so one factory covers the whole zoo.

The legacy per-layout constructors (``init_attn_full`` / ``init_attn_ring`` /
``init_mamba_state`` / ``init_rwkv_state`` / ``init_gla_state``) remain as
thin deprecated shims over :func:`init_cache`.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SsmConfig
from repro.core.lpsa import decode_slot

__all__ = [
    "CacheSpec", "CACHE_LAYOUTS", "init_cache", "is_paged",
    "attn_write", "attn_read", "ring_from_stream",
    # deprecated shims
    "init_attn_full", "init_attn_ring", "init_mamba_state",
    "init_rwkv_state", "init_gla_state",
]

CACHE_LAYOUTS = ("full", "ring", "paged", "mamba", "rwkv", "gla")


@dataclass(frozen=True)
class CacheSpec:
    """Tagged description of one layer's serving cache.

    ``layout`` selects the variant; only the fields that variant reads are
    meaningful (full: max_len; ring: sink+window; paged: page_size +
    num_pages; recurrent layouts: batch only).  ``batch`` is the number of
    sequences for the per-sequence layouts — the paged arena itself is
    batch-free (sequences address it through page tables).
    """
    layout: str
    batch: int
    max_len: int = 0
    sink: int = 0
    window: int = 0
    page_size: int = 0
    num_pages: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.layout not in CACHE_LAYOUTS:
            raise ValueError(
                f"unknown cache layout {self.layout!r}: valid layouts are "
                f"{', '.join(CACHE_LAYOUTS)}")
        if self.layout == "paged" and (self.page_size < 1 or self.num_pages < 2):
            raise ValueError(
                "paged cache needs page_size >= 1 and num_pages >= 2 "
                f"(page 0 is the reserved null page); got page_size="
                f"{self.page_size}, num_pages={self.num_pages}")


def init_cache(cfg: ModelConfig, spec: CacheSpec) -> dict:
    """One layer's cache pytree for ``spec`` — the single factory replacing
    the per-layout ``init_attn_*`` / ``init_*_state`` constructors."""
    if spec.layout == "full":
        shp = (spec.batch, spec.max_len, cfg.n_kv_heads, cfg.head_dim_)
        return {"k": jnp.zeros(shp, spec.dtype),
                "v": jnp.zeros(shp, spec.dtype),
                "pos": jnp.full((spec.batch, spec.max_len), -1, jnp.int32)}
    if spec.layout == "ring":
        s = spec.sink + spec.window
        shp = (spec.batch, s, cfg.n_kv_heads, cfg.head_dim_)
        return {"k": jnp.zeros(shp, spec.dtype),
                "v": jnp.zeros(shp, spec.dtype),
                "pos": jnp.full((spec.batch, s), -1, jnp.int32)}
    if spec.layout == "paged":
        shp = (spec.num_pages, spec.page_size, cfg.n_kv_heads, cfg.head_dim_)
        return {"k_pages": jnp.zeros(shp, spec.dtype),
                "v_pages": jnp.zeros(shp, spec.dtype),
                "pos_pages": jnp.full((spec.num_pages, spec.page_size), -1,
                                      jnp.int32)}
    if spec.layout == "mamba":
        from repro.models.mamba2 import init_ssd_buffers
        s: SsmConfig = cfg.ssm or SsmConfig()
        d_inner = s.expand * cfg.d_model
        n_heads = d_inner // s.head_dim
        return {
            "conv": jnp.zeros((spec.batch, s.conv_width - 1, d_inner),
                              jnp.float32),
            "ssm": jnp.zeros((spec.batch, n_heads, s.head_dim, s.state_dim),
                             jnp.float32),
            # partial-chunk token buffers: decode replays the prefill chunk
            # grid row-by-row (mamba2.mamba_decode), so the state carries the
            # last full-chunk boundary plus the buffered remainder tokens.
            **init_ssd_buffers(cfg, spec.batch),
        }
    if spec.layout == "rwkv":
        hd = cfg.head_dim_
        return {
            "wkv": jnp.zeros((spec.batch, cfg.n_heads, hd, hd), jnp.float32),
            "shift_t": jnp.zeros((spec.batch, 1, cfg.d_model), jnp.float32),
            "shift_c": jnp.zeros((spec.batch, 1, cfg.d_model), jnp.float32),
        }
    if spec.layout == "gla":
        hd = cfg.head_dim_
        return {"s": jnp.zeros((spec.batch, cfg.n_heads, hd, hd), jnp.float32)}
    raise ValueError(spec.layout)  # unreachable (CacheSpec validates)


def is_paged(cache: dict) -> bool:
    return isinstance(cache, dict) and "k_pages" in cache


# --------------------------------------------------------------------------
# attention cache write / read
# --------------------------------------------------------------------------

@jax.named_scope("kv_write")
def attn_write(cache: dict, k_new: jax.Array, v_new: jax.Array, t: jax.Array,
               *, sink: int, window: int, ring: bool,
               page_table: jax.Array | None = None) -> dict:
    """Insert one token's K/V per sequence at absolute positions t.

    t: (B,) int32 — each sequence's own absolute position (a scalar t
    broadcasts, preserving the old lock-step behaviour).  Slots are computed
    per sequence (core.lpsa.decode_slot is elementwise over t), so sequences
    at different decode depths coexist in one batched cache.  A full-cache
    write past max_len is dropped (its slot keeps pos = -1 and stays
    masked) rather than clobbering the last slot.

    Paged caches additionally take ``page_table`` (B, pages_per_seq) int32:
    the write lands in page ``page_table[b, t // page_size]`` at offset
    ``t % page_size``.  Rows with t < 0 (inactive slots) are routed to the
    reserved null page 0 with pos = -1, so they never corrupt shared pages.
    """
    if is_paged(cache):
        return _paged_write(cache, k_new, v_new, t, page_table)
    b = cache["k"].shape[0]
    t = jnp.asarray(t, jnp.int32)
    if t.ndim == 0:
        t = jnp.broadcast_to(t, (b,))
    slot = decode_slot(t, sink, window) if ring else t          # (B,)
    bidx = jnp.arange(b)
    k = cache["k"].at[bidx, slot].set(k_new[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[bidx, slot].set(v_new[:, 0].astype(cache["v"].dtype))
    pos = cache["pos"].at[bidx, slot].set(t)
    return {"k": k, "v": v, "pos": pos}


def _paged_write(cache: dict, k_new: jax.Array, v_new: jax.Array,
                 t: jax.Array, page_table: jax.Array) -> dict:
    if page_table is None:
        raise ValueError("paged cache write requires a page_table")
    b = k_new.shape[0]
    ps = cache["k_pages"].shape[1]
    t = jnp.asarray(t, jnp.int32)
    if t.ndim == 0:
        t = jnp.broadcast_to(t, (b,))
    valid = t >= 0
    pi = jnp.where(valid, t // ps, 0)
    off = jnp.where(valid, t % ps, 0)
    phys = jnp.where(valid, page_table[jnp.arange(b), pi], 0)   # (B,)
    k = cache["k_pages"].at[phys, off].set(
        k_new[:, 0].astype(cache["k_pages"].dtype))
    v = cache["v_pages"].at[phys, off].set(
        v_new[:, 0].astype(cache["v_pages"].dtype))
    pos = cache["pos_pages"].at[phys, off].set(jnp.where(valid, t, -1))
    return {"k_pages": k, "v_pages": v, "pos_pages": pos}


def attn_read(cache: dict, page_table: jax.Array | None = None):
    """-> (k (B,S,Hkv,Dh), v, k_pos (B,S)); invalid slots have pos = -1.

    For paged caches the per-sequence view is gathered through
    ``page_table``: S = pages_per_seq * page_size, and gathered index
    ``i == absolute position i`` (page tables map logical page j to
    positions [j*page_size, (j+1)*page_size)), so the view is laid out
    exactly like a full cache — downstream attention (flash_masked, the
    LPSA decode kernels) is layout-oblivious.
    """
    if is_paged(cache):
        if page_table is None:
            raise ValueError("paged cache read requires a page_table")
        kp, vp, pp = cache["k_pages"], cache["v_pages"], cache["pos_pages"]
        b, n = page_table.shape
        ps = kp.shape[1]
        k = kp[page_table].reshape(b, n * ps, *kp.shape[2:])
        v = vp[page_table].reshape(b, n * ps, *vp.shape[2:])
        pos = pp[page_table].reshape(b, n * ps)
        return k, v, pos
    return cache["k"], cache["v"], cache["pos"]


@jax.named_scope("kv_write")
def ring_from_stream(cfg: ModelConfig, state, *, sink: int, window: int) -> dict:
    """Convert a core.lpsa.lpsa_prefill scan carry into a decode ring cache.

    state = (k_sink, v_sink, k_win, v_win, t_end): sink slots land in ring
    slots [0, sink); window tokens (positions t_end-window..t_end-1, oldest
    first in the stream buffer) land at their decode_slot positions.
    """
    k_sink, v_sink, k_win, v_win, t_end = state
    dtype = k_sink.dtype
    b = k_sink.shape[0]
    # sink slots [0, sink): valid while position < t_end
    sink_pos = jnp.arange(sink)
    sink_valid = sink_pos < t_end
    # each ring slot j in [sink, sink+window) pulls the unique stream-buffer
    # position p with p ≡ (j - sink) (mod window) inside [t_end-window, t_end)
    j = jnp.arange(window)                       # slot offset = j
    base = t_end - window                        # stream buffer start position
    p = base + (j - (base - sink)) % window
    ring_valid = (p >= sink) & (p >= 0)
    idx = jnp.clip(p - base, 0, window - 1)      # index into the stream buffer
    k_ring = jnp.take(k_win, idx, axis=1).astype(dtype)
    v_ring = jnp.take(v_win, idx, axis=1).astype(dtype)
    k = jnp.concatenate([k_sink.astype(dtype), k_ring], axis=1)
    v = jnp.concatenate([v_sink.astype(dtype), v_ring], axis=1)
    pos = jnp.concatenate([jnp.where(sink_valid, sink_pos, -1),
                           jnp.where(ring_valid, p, -1)]).astype(jnp.int32)
    # per-sequence position map: prefill runs the whole batch in lock-step,
    # so every sequence starts from the same slot->position assignment
    pos = jnp.broadcast_to(pos[None], (b, pos.shape[0]))
    return {"k": k, "v": v, "pos": pos}


# --------------------------------------------------------------------------
# deprecated per-layout constructors (shims over init_cache)
# --------------------------------------------------------------------------

_DEPRECATION_WARNED: set = set()


def _warn_deprecated(old: str, new: str) -> None:
    if old not in _DEPRECATION_WARNED:   # once per process, not per trace
        _DEPRECATION_WARNED.add(old)
        warnings.warn(
            f"{old} is deprecated; use init_cache(cfg, CacheSpec({new})) "
            f"(models/kvcache.py)", DeprecationWarning, stacklevel=3)


def init_attn_full(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> dict:
    _warn_deprecated("init_attn_full", "layout='full', ...")
    return init_cache(cfg, CacheSpec("full", batch, max_len=max_len,
                                     dtype=dtype))


def init_attn_ring(cfg: ModelConfig, batch: int, sink: int, window: int,
                   dtype=jnp.bfloat16) -> dict:
    _warn_deprecated("init_attn_ring", "layout='ring', ...")
    return init_cache(cfg, CacheSpec("ring", batch, sink=sink, window=window,
                                     dtype=dtype))


def init_mamba_state(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> dict:
    _warn_deprecated("init_mamba_state", "layout='mamba', ...")
    return init_cache(cfg, CacheSpec("mamba", batch))


def init_rwkv_state(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> dict:
    _warn_deprecated("init_rwkv_state", "layout='rwkv', ...")
    return init_cache(cfg, CacheSpec("rwkv", batch))


def init_gla_state(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> dict:
    _warn_deprecated("init_gla_state", "layout='gla', ...")
    return init_cache(cfg, CacheSpec("gla", batch))
