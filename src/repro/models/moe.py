"""Mixture-of-Experts FFN with ternary experts and expert parallelism.

Routing: top-k softmax router (fp32) with capacity-bounded, sort-based
dispatch (position-in-expert from a stable argsort — the GShard/Switch
recipe without the O(T·E·C) one-hot dispatch tensor).

Expert parallelism (EP): experts shard on the "model" mesh axis.  Under
`shard_map` each device dispatches its local tokens to *its own* experts
only (out-of-range scatter indices drop the rest), runs the expert FFNs,
and a `psum` over the model axis re-assembles every token's mixture — the
TPU rendition of the all-to-all exchange: tokens never move, only D-wide
partial outputs reduce, which beats a2a whenever top_k ≥ 1 destinations
span shards (see EXPERIMENTS.md §Perf for the measured collective terms).

The same dispatch code runs without a mesh (single-device smoke tests) by
treating the full expert range as local.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoeConfig
from repro.core import ternary as tq
from repro.core import twd
from repro.models.ternary_linear import tlin_apply, tlin_compact

__all__ = ["moe_init", "moe_apply", "export_moe", "decode_capacity"]


def moe_init(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    e: MoeConfig = cfg.moe
    d, f = cfg.d_model, e.d_expert
    ks = jax.random.split(key, 5)

    def w(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    p = {
        "router": w(ks[0], (d, e.n_experts), d ** -0.5),
        "experts_gate": {"w": w(ks[1], (e.n_experts, d, f), d ** -0.5)},
        "experts_in": {"w": w(ks[2], (e.n_experts, d, f), d ** -0.5)},
        "experts_out": {"w": w(ks[3], (e.n_experts, f, d),
                               (f * 2 * cfg.n_layers) ** -0.5)},
    }
    if e.n_shared:
        ks2 = jax.random.split(ks[4], 3)
        fs = e.d_expert * e.n_shared
        p["shared_gate"] = {"w": w(ks2[0], (d, fs), d ** -0.5)}
        p["shared_in"] = {"w": w(ks2[1], (d, fs), d ** -0.5)}
        p["shared_out"] = {"w": w(ks2[2], (fs, d),
                                  (fs * 2 * cfg.n_layers) ** -0.5)}
    return p


def export_moe(p: dict, cfg: ModelConfig) -> dict:
    """Master experts -> serving format (per-expert scale, packed base-3).

    vmap-safe: operates on array leaves only (no python branching on values).
    """
    out = dict(p)
    for name in ("experts_gate", "experts_in", "experts_out"):
        w = p[name]["w"]
        gamma = jnp.mean(jnp.abs(w), axis=(1, 2), keepdims=True) + 1e-6
        trits = jnp.clip(jnp.round(w / gamma), -1, 1).astype(jnp.int8)
        if cfg.ternary.serve_format == "packed":
            packed = jax.vmap(lambda t: twd.pack_ternary(t, row_align=16))(trits)
            out[name] = {"packed": packed,
                         "scale": gamma.astype(jnp.float32)}
        else:
            out[name] = {"trits": trits, "scale": gamma.astype(jnp.float32)}
    from repro.models.ternary_linear import export_tlin
    for name in ("shared_gate", "shared_in", "shared_out"):
        if name in p:
            out[name] = export_tlin(p[name], cfg.ternary)
    return out


def _expert_weights(p: dict, cfg: ModelConfig, x_dtype):
    """-> (wg, wi, wo) dequantized/fake-quantized expert stacks."""
    e = cfg.moe
    kdims = {"experts_gate": cfg.d_model, "experts_in": cfg.d_model,
             "experts_out": e.d_expert}
    out = []
    for name in ("experts_gate", "experts_in", "experts_out"):
        sub = p[name]
        if "w" in sub:
            w = (tq.ternary_fake_quant_stacked(sub["w"])
                 if cfg.ternary.enabled else sub["w"])  # per-expert scale:
            out.append(w.astype(x_dtype))               # EP-shard invariant
        elif "trits" in sub:
            out.append(sub["trits"].astype(x_dtype) * sub["scale"].astype(x_dtype))
        else:
            k = kdims[name]
            w = jax.vmap(lambda pk: twd.unpack_ternary_arith(pk, k))(sub["packed"])
            out.append(w.astype(x_dtype) * sub["scale"].astype(x_dtype))
    return out


def _dispatch_compute(x_tok, weights, router, cfg: ModelConfig,
                      e_start, e_local: int, capacity: int):
    """Route (T, D) tokens, run experts [e_start, e_start+e_local), return
    the partial combine (T, D) (zeros for tokens routed elsewhere)."""
    e: MoeConfig = cfg.moe
    t, d = x_tok.shape
    wg, wi, wo = weights                                         # local stacks
    logits = x_tok.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    gate, expert = jax.lax.top_k(probs, e.top_k)                 # (T, K)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    flat_e = expert.reshape(-1)                                  # (T*K,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e.n_experts)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(flat_e.shape[0]) - starts[sorted_e]
    pos = jnp.zeros_like(flat_e).at[order].set(pos_sorted)       # rank in expert

    local_e = flat_e - e_start
    ok = (local_e >= 0) & (local_e < e_local) & (pos < capacity)
    slot = jnp.where(ok, local_e * capacity + pos, e_local * capacity)

    tok_idx = jnp.repeat(jnp.arange(t), e.top_k)
    x_in = x_tok
    if cfg.ternary.enabled and cfg.ternary.das is not None:
        from repro.core import das as das_lib
        m = das_lib.das_mask(x_in, block_size=cfg.ternary.das.block,
                             keep=cfg.ternary.das.keep)
        x_in = das_lib.das_apply(x_in, m)
    if cfg.ternary.enabled:
        x_in = tq.int8_fake_quant(x_in)
    buf = jnp.zeros((e_local * capacity + 1, d), x_tok.dtype)
    buf = buf.at[slot].set(x_in[tok_idx], mode="drop")
    buf = buf[:-1].reshape(e_local, capacity, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
        "ecd,edf->ecf", buf, wi)
    y = jnp.einsum("ecf,efd->ecd", h, wo)                        # (E_l, C, D)

    y_flat = jnp.concatenate([y.reshape(e_local * capacity, d),
                              jnp.zeros((1, d), y.dtype)], axis=0)
    g = jnp.where(ok, gate.reshape(-1), 0.0).astype(y.dtype)
    contrib = y_flat[jnp.minimum(slot, e_local * capacity)] * g[:, None]
    return jnp.zeros((t, d), y.dtype).at[tok_idx].add(contrib)


def _shared_ffn(p: dict, cfg: ModelConfig, x: jax.Array, kernel_mode: str):
    # shared gate/up see the same tokens: compact once on the fused DAS path
    ca = tlin_compact(x, cfg.ternary, p["shared_gate"],
                      kernel_mode=kernel_mode)
    g = tlin_apply(p["shared_gate"], x, cfg.ternary, kernel_mode=kernel_mode,
                   ca=ca)
    u = tlin_apply(p["shared_in"], x, cfg.ternary, kernel_mode=kernel_mode,
                   ca=ca)
    return tlin_apply(p["shared_out"], jax.nn.silu(g) * u, cfg.ternary,
                      kernel_mode=kernel_mode)


def _ep_spec(sub: dict, ep_axis: str):
    """EP PartitionSpec tree for one expert param dict (axis 0 = experts)."""
    return {k: P(ep_axis) for k in sub}


def decode_capacity(cfg: ModelConfig, batch: int) -> int:
    """No-drop per-expert capacity for a decode tick of ``batch`` tokens.

    The training-time capacity ``t * top_k / E * cf`` models a balanced
    router over thousands of tokens; at decode t is the live batch (a
    handful of rows), so a momentarily hot expert overflows the bound and
    the overflow tokens are SILENTLY dropped from its mixture — making a
    request's tokens depend on its batch-mates (batch-variant serving).
    A single expert can receive at most one routed copy of each token, so
    capacity == batch makes drops impossible at decode.
    """
    del cfg
    return max(1, batch)


def moe_apply(p: dict, cfg: ModelConfig, x: jax.Array, *, mesh=None,
              dp_axes=("data",), ep_axis: str = "model",
              kernel_mode: str = "ref", capacity: int | None = None
              ) -> jax.Array:
    """x: (B, S, D) -> (B, S, D).  EP via shard_map when a mesh is given.

    ``capacity`` overrides the per-expert token capacity (serving decode
    passes :func:`decode_capacity` so hot experts never drop tokens; None
    keeps the capacity-factor formula used in training)."""
    e: MoeConfig = cfg.moe
    b, s, d = x.shape

    if mesh is None:
        t = b * s
        cap = capacity if capacity is not None else max(
            1, min(t, int(t * e.top_k / e.n_experts * e.capacity_factor) + 1))
        weights = _expert_weights(p, cfg, x.dtype)
        y = _dispatch_compute(x.reshape(t, d), weights, p["router"], cfg,
                              0, e.n_experts, cap).reshape(b, s, d)
    else:
        ep = mesh.shape[ep_axis]
        dp = 1
        for a in dp_axes:
            dp *= mesh.shape[a]
        if e.n_experts % ep:
            raise ValueError(f"{e.n_experts} experts not divisible by EP={ep}")
        e_local = e.n_experts // ep
        t_local = max(1, (b // dp)) * s
        cap = capacity if capacity is not None else max(
            1, min(t_local, int(t_local * e.top_k / e.n_experts
                                * e.capacity_factor) + 1))

        expert_names = ("experts_gate", "experts_in", "experts_out")
        p_experts = {k: p[k] for k in expert_names}
        specs = {k: _ep_spec(p[k], ep_axis) for k in expert_names}

        def local_fn(x_blk, pe, router):
            ei = jax.lax.axis_index(ep_axis)
            tl = x_blk.shape[0] * x_blk.shape[1]
            weights = _expert_weights(pe, cfg, x_blk.dtype)
            y = _dispatch_compute(x_blk.reshape(tl, d), weights, router, cfg,
                                  ei * e_local, e_local, cap)
            return jax.lax.psum(y, ep_axis).reshape(x_blk.shape)

        y = jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(dp_axes, None, None), specs, P()),
            out_specs=P(dp_axes, None, None),
            check_vma=False,
        )(x, p_experts, p["router"])

    if e.n_shared:
        y = y + _shared_ffn(p, cfg, x, kernel_mode)
    return y
