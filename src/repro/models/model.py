"""Public model API: init / train forward / loss / prefill / decode / export.

`build(cfg)` returns a Model namespace of pure functions for one config.
Inputs:  tokens (B, S) int32, or precomputed embeddings (B, S, D) for the
stub-frontend families (audio/vlm, per the brief).  Training targets are
next-token labels (B, S) with -1 = masked.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import kvcache as KV  # noqa: F401  (re-export convenience)
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import transformer as T
from repro.models.ternary_linear import export_tlin
from repro.models.transformer import Runtime

__all__ = ["Runtime", "init_params", "forward", "loss_fn", "prefill",
           "decode_step", "init_caches", "export_serving", "uses_embeds"]


def uses_embeds(cfg: ModelConfig) -> bool:
    return cfg.frontend != "none"


def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    k_e, k_h, k_s = jax.random.split(key, 3)
    p = {
        "embed": L.embed_init(k_e, cfg.vocab_padded, cfg.d_model, dtype),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
        "layers": T.stack_init(k_s, cfg, dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = L.dense_init(k_h, cfg.d_model, cfg.vocab_padded, dtype,
                                 scale=0.02)
    return p


def _inputs_to_x(p: dict, cfg: ModelConfig, batch_in: jax.Array) -> jax.Array:
    if batch_in.dtype in (jnp.int32, jnp.int64):
        scale = cfg.family == "dense" and cfg.name.startswith("gemma")
        return L.take_embed(p["embed"], batch_in, scale=scale)
    return batch_in  # stub frontend supplies embeddings directly


def _logits(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = L.rmsnorm(p["final_norm"], x)
    if cfg.tie_embeddings:
        lg = L.logits_from_embed(p["embed"], x, cfg.logit_softcap)
    else:
        lg = L.softcap(jnp.einsum("...d,dv->...v", x,
                                  p["head"].astype(x.dtype)).astype(jnp.float32),
                       cfg.logit_softcap)
    if cfg.vocab_padded > cfg.vocab:  # mask padded vocab rows
        lg = lg + jnp.where(jnp.arange(cfg.vocab_padded) < cfg.vocab,
                            0.0, -1e30)
    return lg


def forward(p: dict, cfg: ModelConfig, batch_in: jax.Array,
            rt: Runtime = Runtime()) -> jax.Array:
    """Full-sequence forward -> logits (B, S, V) f32."""
    x = _inputs_to_x(p, cfg, batch_in)
    x = T.stack_train(p["layers"], cfg, x, rt)
    return _logits(p, cfg, x)


def loss_fn(p: dict, cfg: ModelConfig, batch: dict,
            rt: Runtime = Runtime()) -> tuple[jax.Array, dict]:
    """Next-token cross entropy.  batch: {"inputs", "labels"}; labels -1 = pad."""
    logits = forward(p, cfg, batch["inputs"], rt)
    labels = batch["labels"]
    mask = labels >= 0
    lab = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    denom = jnp.maximum(mask.sum(), 1)
    loss = nll.sum() / denom
    return loss, {"loss": loss, "tokens": denom}


def prefill(p: dict, cfg: ModelConfig, batch_in: jax.Array,
            rt: Runtime = Runtime(), *, max_len: int | None = None):
    """Serving prefill: -> (last-position logits (B, V), caches)."""
    s = batch_in.shape[1]
    max_len = max_len if max_len is not None else s + 1
    with jax.named_scope("embed"):
        x = _inputs_to_x(p, cfg, batch_in)
    x, caches = T.stack_prefill(p["layers"], cfg, x, rt, max_len)
    with jax.named_scope("lm_head"):
        return _logits(p, cfg, x[:, -1:])[:, 0], caches


def init_caches(p_or_none, cfg: ModelConfig, batch: int, max_len: int,
                rt: Runtime = Runtime(), dtype=jnp.bfloat16, *,
                page_size: int = 0, num_pages: int = 0) -> dict:
    """Decode caches without a prefill pass (dry-run entry point).

    ``page_size > 0`` allocates would-be full attention caches as one shared
    paged arena per layer (kvcache.CacheSpec layout="paged"); decode_step
    then needs a ``page_table``.  Other layouts are unaffected.
    """
    kinds = cfg.layer_kinds()
    plen = len(cfg.layer_pattern)
    n_groups, tail = (divmod(cfg.n_layers, plen) if cfg.scan_layers
                      else (0, cfg.n_layers))
    stacked = None
    if n_groups:
        per_pos = []
        for j, kind in enumerate(cfg.layer_pattern):
            one = T.init_layer_cache(cfg, kind, batch, max_len, rt, dtype,
                                     page_size=page_size, num_pages=num_pages)
            per_pos.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape), one))
        stacked = tuple(per_pos)
    tail_caches = tuple(
        T.init_layer_cache(cfg, kinds[n_groups * plen + i], batch, max_len,
                           rt, dtype, page_size=page_size,
                           num_pages=num_pages)
        for i in range(tail))
    return {"stacked": stacked, "tail": tail_caches}


def decode_step(p: dict, cfg: ModelConfig, caches: dict, token_or_embed,
                t, rt: Runtime = Runtime(), page_table=None):
    """One decode step.  t: scalar position (lock-step batch) or (B,)
    per-sequence positions (continuous batching); for paged caches inactive
    rows pass t = -1 and ``page_table`` (B, pages_per_seq) int32 addresses
    the shared arenas.  -> (logits (B, V), new caches)."""
    if token_or_embed.ndim == 1:
        token_or_embed = token_or_embed[:, None]
    with jax.named_scope("embed"):
        x = _inputs_to_x(p, cfg, token_or_embed)
    x, caches = T.stack_decode(p["layers"], cfg, x, caches, t, rt, page_table)
    with jax.named_scope("lm_head"):
        return _logits(p, cfg, x)[:, 0], caches


def _per_group(f, stacked: jax.Array):
    """vmap `f` over the leading group axis one group at a time: the export
    intermediates (f32/int32 copies of a weight) then exist for one layer at
    a time, not for the whole stack."""
    outs = [jax.vmap(f)(stacked[g:g + 1]) for g in range(stacked.shape[0])]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)


def export_serving(p: dict, cfg: ModelConfig) -> dict:
    """Master weights -> serving representation (TWD packing, Sec. III-E).

    Scan-stacked leaves (leading group axis) are exported per group, so
    per-tensor scales stay per-layer and the export's working memory is one
    layer's, not the stack's."""
    def conv(tree: Any) -> Any:
        if isinstance(tree, dict):
            if "w" in tree and hasattr(tree["w"], "ndim"):
                if tree["w"].ndim == 2:
                    return export_tlin(tree, cfg.ternary)
                if tree["w"].ndim == 3:      # stacked (G, K, N)
                    return _per_group(lambda w: export_tlin({"w": w},
                                                            cfg.ternary),
                                      tree["w"])
            if "experts_gate" in tree:
                if tree["experts_gate"]["w"].ndim == 4:  # stacked (G,E,D,F)
                    return jax.vmap(lambda t: MOE.export_moe(t, cfg))(tree)
                return MOE.export_moe(tree, cfg)
            return {k: conv(v) for k, v in tree.items()}
        if isinstance(tree, tuple):
            return tuple(conv(v) for v in tree)
        return tree
    out = dict(p)
    out["layers"] = conv(p["layers"])
    return out
