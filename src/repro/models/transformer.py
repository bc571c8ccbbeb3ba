"""Composable decoder stack: per-layer mixers, scan-over-groups, remat.

The repeating `layer_pattern` of a config becomes one scan *group*: the
group body unrolls the pattern's blocks; `lax.scan` iterates groups with
per-position parameter stacks (keeps the lowered HLO O(pattern), not
O(layers) — essential for compiling 61-layer trillion-param configs against
512 partitions).  Layers left over when the pattern doesn't divide n_layers
(gemma3's 26 = 4*6 + 2) run as an unrolled tail.  Zamba2's weight-shared
attention block is threaded through as non-scanned `shared` params.

Three phases share the same parameters:
  train    — full-sequence differentiable pass (fake-quant ternary, DAS)
  prefill  — serving: streaming LPSA/local (ring caches) or full attention
  decode   — one token against the caches/states
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import gla as G
from repro.models import kvcache as KV
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MOE
from repro.models import rwkv6 as R
from repro.models.ternary_linear import tlin_apply, tlin_compact, tlin_init

__all__ = ["Runtime", "stack_init", "stack_train", "stack_prefill",
           "stack_decode", "layer_cache_spec", "init_layer_cache",
           "ffn_init", "ffn_apply"]


@dataclass(frozen=True)
class Runtime:
    """Execution context threaded through the model functions."""
    mesh: Any = None
    dp_axes: tuple = ("data",)
    ep_axis: str = "model"
    kernel_mode: str = "ref"
    serve_sparse: bool = True      # LPSA on global-attention layers at serve


# --------------------------------------------------------------------------
# FFN
# --------------------------------------------------------------------------

def ffn_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    out_scale = (f * 2 * cfg.n_layers) ** -0.5
    if cfg.ffn_kind == "mlp":
        return {"w_in": tlin_init(ks[0], d, f, dtype),
                "w_out": tlin_init(ks[1], f, d, dtype, scale=out_scale)}
    return {"w_gate": tlin_init(ks[0], d, f, dtype),
            "w_in": tlin_init(ks[1], d, f, dtype),
            "w_out": tlin_init(ks[2], f, d, dtype, scale=out_scale)}


def ffn_apply(p: dict, cfg: ModelConfig, x: jax.Array, *, kernel_mode="ref"):
    act = L.ACT[cfg.act]
    tc = cfg.ternary
    if "w_gate" in p:
        # gate and up share the input: compact once for the fused DAS path
        ca = tlin_compact(x, tc, p["w_gate"], kernel_mode=kernel_mode)
        h = act(tlin_apply(p["w_gate"], x, tc, kernel_mode=kernel_mode,
                           ca=ca)) * \
            tlin_apply(p["w_in"], x, tc, kernel_mode=kernel_mode, ca=ca)
    else:
        h = act(tlin_apply(p["w_in"], x, tc, kernel_mode=kernel_mode))
    return tlin_apply(p["w_out"], h, tc, kernel_mode=kernel_mode)


def _mixer_ffn(p: dict, cfg: ModelConfig, x: jax.Array, rt: Runtime,
               serve: bool = False):
    """The FFN/MoE half of an attention/gla block -> (y, the MoE routing
    counters or None).  ``serve`` (prefill and decode) switches MoE to the
    no-drop capacity (a hot expert must never drop a request's token; see
    moe.serve_capacity)."""
    if cfg.moe is not None:
        cap = (MOE.serve_capacity(cfg, x.shape[0] * x.shape[1])
               if serve else None)
        out = MOE.moe_apply(p["moe"], cfg, x, mesh=rt.mesh,
                            dp_axes=rt.dp_axes, ep_axis=rt.ep_axis,
                            kernel_mode=rt.kernel_mode, capacity=cap,
                            with_stats=rt.mesh is None)
        return out if rt.mesh is None else (out, None)
    return ffn_apply(p["ffn"], cfg, x, kernel_mode=rt.kernel_mode), None


def _ffn_scope(cfg: ModelConfig) -> str:
    return "moe" if cfg.moe is not None else "ffn"


# --------------------------------------------------------------------------
# per-block init
# --------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, kind: str, dtype=jnp.float32,
               shared_attn: bool = False) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    p: dict = {"norm1": L.init_rmsnorm(d, dtype)}
    if kind in ("attn", "local"):
        if not shared_attn:
            p["attn"] = A.attn_init(ks[0], cfg, dtype)
        p["norm2"] = L.init_rmsnorm(d, dtype)
        if cfg.moe is not None:
            p["moe"] = MOE.moe_init(ks[1], cfg, dtype)
        else:
            p["ffn"] = ffn_init(ks[1], cfg, dtype)
    elif kind == "mamba":
        p["mamba"] = M.mamba_init(ks[0], cfg, dtype)
    elif kind == "rwkv":
        p["rwkv"] = R.rwkv_init(ks[0], cfg, dtype)
        p["norm2"] = L.init_rmsnorm(d, dtype)
    elif kind == "gla":
        p["gla"] = G.gla_init(ks[0], cfg, dtype)
        p["norm2"] = L.init_rmsnorm(d, dtype)
        p["ffn"] = ffn_init(ks[1], cfg, dtype)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return p


def stack_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    kinds = cfg.layer_kinds()
    pat = cfg.layer_pattern
    plen = len(pat)
    n_groups, tail = (divmod(cfg.n_layers, plen) if cfg.scan_layers
                      else (0, cfg.n_layers))
    keys = jax.random.split(key, cfg.n_layers + 1)
    shared = (A.attn_init(keys[-1], cfg, dtype)
              if cfg.shared_attn and any(k in ("attn", "local") for k in kinds)
              else None)

    def one(key, kind):
        shared_attn = cfg.shared_attn and kind in ("attn", "local")
        return block_init(key, cfg, kind, dtype, shared_attn=shared_attn)

    stacked = None
    if n_groups:
        # one batched init per pattern position, straight into the stacked
        # layout: stacking per-layer trees would hold every weight twice
        # (a 3B master in bf16 is 6.6 GB of a 16 GB chip)
        stacked = tuple(
            jax.vmap(lambda k, kind=kind: one(k, kind))(
                keys[j:n_groups * plen:plen])
            for j, kind in enumerate(pat))
    first = n_groups * plen
    tail_params = tuple(one(keys[i], kinds[i])
                        for i in range(first, first + tail))
    return {"stacked": stacked, "tail": tail_params, "shared": shared}


# --------------------------------------------------------------------------
# phase bodies
# --------------------------------------------------------------------------

def _attn_params(bp: dict, shared):
    return bp["attn"] if "attn" in bp else shared


def block_train(bp: dict, cfg: ModelConfig, x: jax.Array, kind: str,
                shared, rt: Runtime) -> jax.Array:
    km = rt.kernel_mode
    if kind in ("attn", "local"):
        x = x + A.attn_train(_attn_params(bp, shared), cfg,
                             L.rmsnorm(bp["norm1"], x), kind,
                             serve_sparse=rt.serve_sparse, kernel_mode=km)
        x = x + _mixer_ffn(bp, cfg, L.rmsnorm(bp["norm2"], x), rt)[0]
    elif kind == "mamba":
        y, _ = M.mamba_train(bp["mamba"], cfg, L.rmsnorm(bp["norm1"], x),
                             kernel_mode=km)
        x = x + y
    elif kind == "rwkv":
        x, _ = _rwkv_block_seq(bp, cfg, x, km, None)
    elif kind == "gla":
        y, _ = G.gla_train(bp["gla"], cfg, L.rmsnorm(bp["norm1"], x),
                           kernel_mode=km)
        x = x + y
        x = x + _mixer_ffn(bp, cfg, L.rmsnorm(bp["norm2"], x), rt)[0]
    return x


def _rwkv_block_seq(bp, cfg, x, km, state):
    """RWKV block over a sequence: time-mix then channel-mix (pre-norms)."""
    xt = L.rmsnorm(bp["norm1"], x)
    y_t, st_t = R.rwkv_time_mix(bp["rwkv"], cfg, xt, kernel_mode=km,
                                wkv0=state["wkv"] if state else None,
                                prev=state["shift_t"] if state else None)
    x1 = x + y_t
    xc = L.rmsnorm(bp["norm2"], x1)
    y_c, shift_c = R.rwkv_channel_mix(bp["rwkv"], cfg, xc, kernel_mode=km,
                                      prev=state["shift_c"] if state else None)
    return x1 + y_c, {**st_t, "shift_c": shift_c}


def block_prefill(bp: dict, cfg: ModelConfig, x: jax.Array, kind: str,
                  shared, rt: Runtime, batch: int, max_len: int):
    """-> (x, cache_entry) with caches ready for decode at position L."""
    km = rt.kernel_mode
    if kind in ("attn", "local"):
        sink, _ = A.kind_sink_window(cfg, kind, rt.serve_sparse)
        with jax.named_scope("attn"), jax.named_scope(A.attn_scope(sink)):
            x, cache = _attn_prefill(bp, cfg, x, kind, shared, rt, batch,
                                     max_len)
        with jax.named_scope(_ffn_scope(cfg)):
            x = x + _mixer_ffn(bp, cfg, L.rmsnorm(bp["norm2"], x), rt,
                               serve=True)[0]
        return x, cache
    if kind == "mamba":
        y, state = M.mamba_train(
            bp["mamba"], cfg, L.rmsnorm(bp["norm1"], x), kernel_mode=km,
            return_state=True)
        return x + y, state
    if kind == "rwkv":
        return _rwkv_block_seq(bp, cfg, x, km, None)
    if kind == "gla":
        y, s_fin = G.gla_train(bp["gla"], cfg, L.rmsnorm(bp["norm1"], x),
                               kernel_mode=km)
        x = x + y
        with jax.named_scope(_ffn_scope(cfg)):
            x = x + _mixer_ffn(bp, cfg, L.rmsnorm(bp["norm2"], x), rt,
                               serve=True)[0]
        return x, {"s": s_fin}
    raise ValueError(kind)


def _attn_prefill(bp: dict, cfg: ModelConfig, x: jax.Array, kind: str,
                  shared, rt: Runtime, batch: int, max_len: int):
    """The attention half of a block's prefill: -> (x + attention, the
    layer's decode cache)."""
    km = rt.kernel_mode
    dt = x.dtype
    ap = _attn_params(bp, shared)
    xin = L.rmsnorm(bp["norm1"], x)
    sink, window = A.kind_sink_window(cfg, kind, rt.serve_sparse)
    if sink < A.FULL_SINK:   # sparse: streaming prefill -> ring cache
        y, state = A.attn_prefill_streaming(ap, cfg, xin, kind,
                                            kernel_mode=km)
        cache = KV.ring_from_stream(cfg, state, sink=sink, window=window)
    else:                    # full attention -> full cache
        q, k, v = A.qkv_project(ap, cfg, xin, kernel_mode=km)
        pos = jnp.arange(x.shape[1])
        rp = A._rope_fn(cfg, kind)
        q, k = rp(q, pos), rp(k, pos)
        o = A.flash_masked(q, k, v, pos, pos, sink=A.FULL_SINK, window=0,
                           softcap=cfg.attn_softcap)
        y = tlin_apply(ap["wo"], o.reshape(x.shape[0], x.shape[1], -1),
                       cfg.ternary, kernel_mode=km)
        full = KV.init_cache(cfg, KV.CacheSpec("full", batch,
                                               max_len=max_len, dtype=dt))
        with jax.named_scope("kv_write"):
            kpad = full["k"].at[:, :k.shape[1]].set(k.astype(dt))
            vpad = full["v"].at[:, :v.shape[1]].set(v.astype(dt))
            ppad = full["pos"].at[:, :k.shape[1]].set(pos.astype(jnp.int32))
        cache = {"k": kpad, "v": vpad, "pos": ppad}
    return x + y, cache


def block_decode(bp: dict, cfg: ModelConfig, x: jax.Array, kind: str,
                 cache, t, shared, rt: Runtime,
                 page_table: jax.Array | None = None):
    """One-token decode -> (x, cache, MoE routing counters or None); t is
    scalar (lock-step) or (B,) per-sequence positions (continuous batching)
    — recurrent mixers are position-free.  ``page_table`` addresses paged
    attention caches (ignored by every other layout)."""
    km = rt.kernel_mode
    if kind in ("attn", "local"):
        sink, _ = A.kind_sink_window(cfg, kind, rt.serve_sparse)
        with jax.named_scope("attn"), jax.named_scope(A.attn_scope(sink)):
            y, cache = A.attn_decode(_attn_params(bp, shared), cfg,
                                     L.rmsnorm(bp["norm1"], x), cache, t,
                                     kind, serve_sparse=rt.serve_sparse,
                                     kernel_mode=km, page_table=page_table)
            x = x + y
        with jax.named_scope(_ffn_scope(cfg)):
            y, st = _mixer_ffn(bp, cfg, L.rmsnorm(bp["norm2"], x), rt,
                               serve=True)
            x = x + y
        return x, cache, st
    if kind == "mamba":
        y, cache = M.mamba_decode(bp["mamba"], cfg,
                                  L.rmsnorm(bp["norm1"], x), cache, t,
                                  kernel_mode=km)
        return x + y, cache, None
    if kind == "rwkv":
        xt = L.rmsnorm(bp["norm1"], x)
        y_t, st = R.rwkv_time_mix_step(bp["rwkv"], cfg, xt, cache,
                                       kernel_mode=km)
        x1 = x + y_t
        xc = L.rmsnorm(bp["norm2"], x1)
        y_c, shift_c = R.rwkv_channel_mix_step(bp["rwkv"], cfg, xc,
                                               cache["shift_c"],
                                               kernel_mode=km)
        return x1 + y_c, {**st, "shift_c": shift_c}, None
    if kind == "gla":
        y, cache = G.gla_decode(bp["gla"], cfg, L.rmsnorm(bp["norm1"], x),
                                cache, kernel_mode=km)
        x = x + y
        with jax.named_scope(_ffn_scope(cfg)):
            y, st = _mixer_ffn(bp, cfg, L.rmsnorm(bp["norm2"], x), rt,
                               serve=True)
            x = x + y
        return x, cache, st
    raise ValueError(kind)


# --------------------------------------------------------------------------
# cache init (decode entry point without a prefill pass)
# --------------------------------------------------------------------------

def layer_cache_spec(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     rt: Runtime, dtype=jnp.bfloat16, *, page_size: int = 0,
                     num_pages: int = 0) -> KV.CacheSpec:
    """Resolve a layer kind to its serving CacheSpec.  ``page_size > 0``
    turns would-be full caches into views over a shared paged arena
    (ring/recurrent layouts are already O(1) per slot and stay per-slot)."""
    if kind in ("attn", "local"):
        sink, window = A.kind_sink_window(cfg, kind, rt.serve_sparse)
        if sink < A.FULL_SINK:
            return KV.CacheSpec("ring", batch, sink=sink, window=window,
                                dtype=dtype)
        if page_size > 0:
            return KV.CacheSpec("paged", batch, max_len=max_len,
                                page_size=page_size, num_pages=num_pages,
                                dtype=dtype)
        return KV.CacheSpec("full", batch, max_len=max_len, dtype=dtype)
    if kind in ("mamba", "rwkv", "gla"):
        return KV.CacheSpec(kind, batch)
    raise ValueError(kind)


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     rt: Runtime, dtype=jnp.bfloat16, *, page_size: int = 0,
                     num_pages: int = 0):
    return KV.init_cache(cfg, layer_cache_spec(
        cfg, kind, batch, max_len, rt, dtype, page_size=page_size,
        num_pages=num_pages))


# --------------------------------------------------------------------------
# stack drivers (scan over groups + unrolled tail)
# --------------------------------------------------------------------------

def _maybe_remat(f, cfg):
    return jax.checkpoint(f) if cfg.remat else f


def stack_train(params: dict, cfg: ModelConfig, x: jax.Array, rt: Runtime):
    pat = cfg.layer_pattern
    shared = params["shared"]

    if params["stacked"] is not None:
        def group(x, gp):
            for j, kind in enumerate(pat):
                x = block_train(gp[j], cfg, x, kind, shared, rt)
            return x, None
        x, _ = jax.lax.scan(_maybe_remat(group, cfg), x, params["stacked"])
    start = cfg.n_layers - len(params["tail"])
    for i, bp in enumerate(params["tail"]):
        kind = cfg.layer_kinds()[start + i]
        f = (lambda bp_, x_, kind_=kind:
             block_train(bp_, cfg, x_, kind_, shared, rt))
        x = (jax.checkpoint(f) if cfg.remat else f)(bp, x)
    return x


def stack_prefill(params: dict, cfg: ModelConfig, x: jax.Array, rt: Runtime,
                  max_len: int):
    pat = cfg.layer_pattern
    shared = params["shared"]
    b = x.shape[0]

    caches_stacked = None
    if params["stacked"] is not None:
        def group(x, gp):
            caches = []
            for j, kind in enumerate(pat):
                x, c = block_prefill(gp[j], cfg, x, kind, shared, rt, b, max_len)
                caches.append(c)
            return x, tuple(caches)
        x, caches_stacked = jax.lax.scan(_maybe_remat(group, cfg), x,
                                         params["stacked"])
    tail_caches = []
    start = cfg.n_layers - len(params["tail"])
    for i, bp in enumerate(params["tail"]):
        kind = cfg.layer_kinds()[start + i]
        x, c = block_prefill(bp, cfg, x, kind, shared, rt, b, max_len)
        tail_caches.append(c)
    return x, {"stacked": caches_stacked, "tail": tuple(tail_caches)}


def stack_decode(params: dict, cfg: ModelConfig, x: jax.Array, caches: dict,
                 t, rt: Runtime, page_table: jax.Array | None = None):
    pat = cfg.layer_pattern
    shared = params["shared"]

    new_stacked = stats_stacked = None
    if params["stacked"] is not None:
        def group(x, xs):
            gp, gc = xs
            ncs, stats = [], []
            for j, kind in enumerate(pat):
                # page_table is closure-captured: one shared (B, pages) table
                # is loop-invariant across scan groups (each group's paged
                # arena is a distinct leaf of gc)
                x, nc, st = block_decode(gp[j], cfg, x, kind, gc[j], t,
                                         shared, rt, page_table)
                ncs.append(nc)
                stats.append(st)
            return x, (tuple(ncs), tuple(stats))
        x, (new_stacked, stats_stacked) = jax.lax.scan(
            group, x, (params["stacked"], caches["stacked"]))
    new_tail, tail_stats = [], []
    start = cfg.n_layers - len(params["tail"])
    for i, bp in enumerate(params["tail"]):
        kind = cfg.layer_kinds()[start + i]
        x, nc, st = block_decode(bp, cfg, x, kind, caches["tail"][i], t,
                                 shared, rt, page_table)
        new_tail.append(nc)
        tail_stats.append(st)
    return (x, {"stacked": new_stacked, "tail": tuple(new_tail)},
            _layer_counters(stats_stacked, tail_stats))


def _layer_counters(stacked, tail) -> dict | None:
    """The MoE layers' routing counters in layer order, {name: (n,) int32}
    (None without MoE layers).  ``stacked`` holds one entry per pattern
    position, each counter (groups,) from the scan; ``tail`` one per
    unrolled layer."""
    rows = []
    if stacked is not None:
        mine = [st for st in stacked if st is not None]
        if mine:
            rows.append({k: jnp.stack([st[k] for st in mine], 1).reshape(-1)
                         for k in mine[0]})
    rows += [{k: v[None] for k, v in st.items()} for st in tail
             if st is not None]
    if not rows:
        return None
    return {k: jnp.concatenate([r[k] for r in rows]) for k in rows[0]}
