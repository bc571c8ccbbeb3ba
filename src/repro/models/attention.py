"""GQA attention with TENET ternary projections + LPSA / local / full modes.

Layer kinds (configs.base.layer_pattern):
  "attn"  — global attention: full causal, or sink+window when cfg.lpsa set
            and cfg.lpsa_on_globals; RoPE with cfg.rope_yarn's scaling
  "local" — sliding-window attention (window = cfg.window, no sink), plain
            RoPE

Three execution paths share one set of (ternary) projection weights:
  * train / full-prefill: chunked flash attention in pure JAX (differentiable,
    O(L·bk) live memory — scores never materialize globally),
  * streaming prefill: core.lpsa.lpsa_prefill (pack-fused, Algorithm 1),
  * decode: one-token attention against a full or ring KV cache.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import lpsa as lpsa_lib
from repro.kernels import ops
from repro.models import layers as L
from repro.models.ternary_linear import tlin_apply, tlin_compact, tlin_init

__all__ = [
    "attn_init", "qkv_project", "flash_masked", "attn_train",
    "attn_prefill_streaming", "attn_decode", "kind_sink_window",
    "attn_scope",
]

NEG_INF = -1e30
FULL_SINK = 1 << 30   # sink larger than any position == full causal


def kind_sink_window(cfg: ModelConfig, kind: str, serve_sparse: bool) -> tuple[int, int]:
    """(sink, window) for a layer kind.  serve_sparse toggles LPSA on the
    globals of a config that allows it (cfg.lpsa_on_globals)."""
    if kind == "local":
        return 0, cfg.window
    if cfg.lpsa is not None and cfg.lpsa_on_globals and serve_sparse:
        return cfg.lpsa.sink, cfg.lpsa.window
    return FULL_SINK, 0


def attn_scope(sink: int) -> str:
    """Named scope of a layer's kind of attention, inside ``attn``:
    ``attn_window`` over a ring (sink+window or sliding), ``attn_full``
    over every earlier position."""
    return "attn_full" if sink >= FULL_SINK else "attn_window"


def attn_init(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    return {
        "wq": tlin_init(ks[0], d, qd, dtype),
        "wk": tlin_init(ks[1], d, kvd, dtype),
        "wv": tlin_init(ks[2], d, kvd, dtype),
        "wo": tlin_init(ks[3], qd, d, dtype, scale=(qd * 2 * cfg.n_layers) ** -0.5),
    }


def qkv_project(p: dict, cfg: ModelConfig, x: jax.Array, *,
                kernel_mode: str = "ref"):
    """(B, L, D) -> q (B,L,Hq,Dh), k/v (B,L,Hkv,Dh) through ternary linears.

    On the fused DAS serving path the block top-k (the paper's ASM) runs
    once per token and the compacted stream feeds all three projections."""
    b, l, _ = x.shape
    tc = cfg.ternary
    ca = tlin_compact(x, tc, p["wq"], kernel_mode=kernel_mode)
    q = tlin_apply(p["wq"], x, tc, kernel_mode=kernel_mode, ca=ca)
    k = tlin_apply(p["wk"], x, tc, kernel_mode=kernel_mode, ca=ca)
    v = tlin_apply(p["wv"], x, tc, kernel_mode=kernel_mode, ca=ca)
    hd = cfg.head_dim_
    return (q.reshape(b, l, cfg.n_heads, hd),
            k.reshape(b, l, cfg.n_kv_heads, hd),
            v.reshape(b, l, cfg.n_kv_heads, hd))


def _rope_fn(cfg: ModelConfig, kind: str):
    """RoPE of a layer kind: global layers take cfg.rope_yarn's scaling,
    local (sliding) layers plain RoPE."""
    yarn = cfg.rope_yarn if kind == "attn" else None

    def f(x, pos):
        cos, sin = L.rope(pos, cfg.head_dim_, cfg.rope_theta, yarn)
        return L.apply_rope(x, cos, sin)
    return f


def flash_masked(q, k, v, q_pos, k_pos, *, sink: int, window: int,
                 softcap: float | None = None, kv_chunk: int = 512) -> jax.Array:
    """Differentiable chunked flash attention with the LPSA mask family.

    q: (B, Lq, Hq, D); k, v: (B, Lk, Hkv, D); q_pos (Lq,) or per-sequence
    (B, Lq); k_pos (Lk,) or (B, Lk).  Per-sequence positions let each batch
    row sit at its own decode depth (continuous batching); 1-D positions
    broadcast to the whole batch (lock-step).  Scans KV chunks with an
    online softmax; per-step live memory is O(Lq * kv_chunk) — the XLA
    analogue of the Pallas kernel.
    """
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    n_rep = hq // hkv
    c = min(kv_chunk, lk)
    if lk % c:
        c = lk  # fall back to a single chunk for awkward cache sizes
    scale = d ** -0.5
    q_pos = jnp.broadcast_to(jnp.atleast_2d(q_pos), (b, lq))
    k_pos = jnp.broadcast_to(jnp.atleast_2d(k_pos), (b, lk))
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)       # (B,Hq,Lq,D)
    kc = k.reshape(b, lk // c, c, hkv, d).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, lk // c, c, hkv, d).transpose(1, 0, 3, 2, 4)
    kpc = k_pos.reshape(b, lk // c, c).swapaxes(0, 1)    # (N, B, c)

    def step(carry, blk):
        m, l, acc = carry
        kb, vb, kp = blk                                  # (B,Hkv,c,D), (B,c)
        kb = jnp.repeat(kb, n_rep, axis=1).astype(jnp.float32)
        vb = jnp.repeat(vb, n_rep, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kb) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        mask = lpsa_lib.lpsa_allowed(q_pos[:, :, None], kp[:, None, :],
                                     sink, window)
        mask = mask & (kp >= 0)[:, None, :]               # (B,Lq,c)
        s = jnp.where(mask[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        p = jnp.where(mask[:, None], jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(m <= NEG_INF, 0.0, jnp.exp(m - m_safe))
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, hq, lq, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, hq, lq, 1), jnp.float32),
            jnp.zeros((b, hq, lq, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, (kc, vc, kpc))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)        # (B,Lq,Hq,D)


def attn_train(p: dict, cfg: ModelConfig, x: jax.Array, kind: str, *,
               serve_sparse: bool = True, kernel_mode: str = "ref") -> jax.Array:
    """Training / full-prefill attention over a whole sequence."""
    b, l, _ = x.shape
    sink, window = kind_sink_window(cfg, kind, serve_sparse)
    q, k, v = qkv_project(p, cfg, x, kernel_mode=kernel_mode)
    pos = jnp.arange(l)
    rp = _rope_fn(cfg, kind)
    q, k = rp(q, pos), rp(k, pos)
    o = flash_masked(q, k, v, pos, pos, sink=sink, window=window,
                     softcap=cfg.attn_softcap)
    o = o.reshape(b, l, cfg.q_dim)
    return tlin_apply(p["wo"], o, cfg.ternary, kernel_mode=kernel_mode)


def attn_prefill_streaming(p: dict, cfg: ModelConfig, x: jax.Array, kind: str,
                           *, kernel_mode: str = "ref"):
    """LPSA Algorithm-1 prefill: fused pack-chunked projection + attention.

    Returns (y, stream_state) — the scan carry becomes the decode ring cache
    (models.kvcache.ring_from_stream).
    """
    sink, window = kind_sink_window(cfg, kind, True)
    if sink >= FULL_SINK:
        raise ValueError("streaming prefill needs a sparse pattern (lpsa/local)")
    spec = lpsa_lib.LpsaSpec(sink=sink, window=window,
                             chunk=cfg.lpsa.chunk if cfg.lpsa else 256)
    proj = partial(_stream_proj, p, cfg, kernel_mode)
    o, state = lpsa_lib.lpsa_prefill(
        x, proj, spec=spec, num_q_heads=cfg.n_heads,
        num_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim_,
        rope=_rope_fn(cfg, kind), softcap=cfg.attn_softcap,
        return_state=True)
    b, l = x.shape[0], x.shape[1]
    y = tlin_apply(p["wo"], o.reshape(b, l, cfg.q_dim), cfg.ternary,
                   kernel_mode=kernel_mode)
    return y, state


def _stream_proj(p, cfg, kernel_mode, pack):
    return qkv_project(p, cfg, pack, kernel_mode=kernel_mode)


def attn_decode(p: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
                t: jax.Array, kind: str, *, serve_sparse: bool = True,
                kernel_mode: str = "ref",
                page_table: jax.Array | None = None):
    """One-token decode.  x: (B, 1, D); cache from models.kvcache.

    t: scalar (lock-step: all sequences at the same position) or (B,)
    per-sequence positions (continuous batching: each slot at its own
    decode depth).  Paged caches (kvcache.CacheSpec layout="paged") take
    ``page_table`` (B, pages_per_seq) int32 mapping each sequence's logical
    pages to arena pages; the gathered view is laid out exactly like a full
    cache, so the attention math below is layout-oblivious.  For paged
    caches rows with t < 0 are inactive (their write is routed to the null
    page and masked).  Returns (y (B,1,D), new_cache).
    """
    from repro.models import kvcache  # local import to avoid cycle

    b = x.shape[0]
    sink, window = kind_sink_window(cfg, kind, serve_sparse)
    q, k, v = qkv_project(p, cfg, x, kernel_mode=kernel_mode)
    t = jnp.asarray(t, jnp.int32)
    if t.ndim == 0:
        t = jnp.broadcast_to(t, (b,))
    pos = t[:, None]                                     # (B, 1)
    rp = _rope_fn(cfg, kind)
    q, k = rp(q, pos), rp(k, pos)
    ring = sink < FULL_SINK
    cache = kvcache.attn_write(cache, k, v, t, sink=sink, window=window,
                               ring=ring, page_table=page_table)
    k_all, v_all, k_pos = kvcache.attn_read(cache, page_table)  # k_pos (B, S)
    o = _decode_attention(cfg, q, k_all, v_all, pos, k_pos, sink=sink,
                          window=window, kernel_mode=kernel_mode)
    o = o.reshape(b, 1, cfg.q_dim)
    return tlin_apply(p["wo"], o, cfg.ternary, kernel_mode=kernel_mode), cache


def _decode_attention(cfg: ModelConfig, q, k, v, q_pos, k_pos, *, sink: int,
                      window: int, kernel_mode: str) -> jax.Array:
    """Route the one-token attention step by kernel mode.

    q: (B, Lq, Hq, D); k, v: (B, Lk, Hkv, D); q_pos (B, Lq); k_pos (B, Lk).
    ``pallas``/``compiled`` go through the Pallas LPSA kernel where its
    tiles fit the shape (``ops.attn_kernel_fits``: GQA heads, a cache whose
    length the key blocks tile) and note a fallback to `flash_masked`
    where they do not; ``tuned`` resolves the per-shape winner from the
    autotune cache — Pallas tiles where they compile, the chunked XLA flash
    (with the tuned kv-chunk) otherwise; everything else keeps
    `flash_masked`, which shares the decode step's per-token compaction
    budget with the ternary linears (one fused LPSA+DAS decode trace).
    """
    b, lq, hq, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    kv_chunk = min(512, lk)
    tiles: dict = {}
    route_pallas = ops.attn_kernel_wanted(kernel_mode)
    if route_pallas and not ops.attn_kernel_fits(hq, hkv, lk):
        ops.note_fallback("sparse_attn", (hq, hkv, lk, d),
                          f"{hq} q heads over {hkv} kv heads and a cache of "
                          f"{lk} do not tile the kernel's key blocks")
        route_pallas = False
    if kernel_mode == "tuned":
        from repro.kernels import autotune
        tcfg = autotune.lookup(
            "sparse_attn", **autotune.attn_dims(hq=hq, hkv=hkv, lq=lq, lk=lk,
                                                d=d, sink=sink, window=window))
        if tcfg.impl == "pallas":
            route_pallas = True
            tiles = {"block_q": tcfg.block_m or 128,
                     "block_k": tcfg.block_k or 128}
        else:   # xla_flash winner (or interpret/ref: emulated per-token
            # attention is pathological — keep the XLA flash path)
            kv_chunk = tcfg.block_k or kv_chunk
    if route_pallas:
        o = ops.sparse_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), q_pos,
            k_pos, sink=sink, window=window, softcap=cfg.attn_softcap,
            mode="pallas" if kernel_mode == "tuned" else kernel_mode, **tiles)
        return o.swapaxes(1, 2)
    return flash_masked(q, k, v, q_pos, k_pos, sink=sink, window=window,
                        softcap=cfg.attn_softcap, kv_chunk=kv_chunk)
