"""MoE routing/dispatch == dense loop: the capacity-bounded sort dispatch
(training) with its rank cut, and the every-expert form that runs where the
capacity holds every token (serving, and reduced configs at cf = E/top_k)."""
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import moe as MOE


def _dense_reference(p, cfg, x, capacity=None):
    """Loop-over-experts oracle.  With ``capacity``, an expert keeps the
    first ``capacity`` picks routed to it in (token, rank) order and drops
    the rest, as the capacity-bounded dispatch does; None drops nothing."""
    e = cfg.moe
    t = x.shape[0] * x.shape[1]
    xt = x.reshape(t, -1)
    logits = xt @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, e.top_k)
    gate = gate / gate.sum(-1, keepdims=True)
    if capacity is not None:
        seen = np.zeros(e.n_experts, int)
        keep = np.zeros((t, e.top_k), bool)
        for i, ei in enumerate(np.asarray(expert).reshape(-1)):
            keep[i // e.top_k, i % e.top_k] = seen[ei] < capacity
            seen[ei] += 1
        assert not keep.all()           # the case drops some picks
        gate = jnp.where(keep, gate, 0.0)
    wg, wi, wo = MOE._expert_weights(p, cfg, xt.dtype)
    from repro.core import ternary as tq
    xin = tq.int8_fake_quant(xt) if cfg.ternary.enabled else xt
    y = jnp.zeros_like(xt)
    for k in range(e.top_k):
        for ei in range(e.n_experts):
            sel = (expert[:, k] == ei)
            h = jax.nn.silu(xin @ wg[ei]) * (xin @ wi[ei])
            if cfg.ternary.enabled:
                h = tq.int8_fake_quant(h)
            ye = h @ wo[ei]
            y = y + jnp.where(sel[:, None], ye * gate[:, k:k+1], 0.0)
    return y.reshape(x.shape)


def test_dispatch_matches_dense_loop():
    cfg = reduced(get_config("qwen3-moe-30b-a3b"))
    cfg = dataclasses.replace(cfg, ternary=dataclasses.replace(
        cfg.ternary, das=None))
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)) * 0.5
    got = MOE.moe_apply(p, cfg, x)
    want = _dense_reference(p, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("factor", [0.5, 1.0, 1.25])
def test_sort_dispatch_drops_by_rank(factor):
    """Below E / top_k the capacity is under the call's tokens, so the
    sort/scatter/gather dispatch runs (training's path) and drops the picks
    past each expert's capacity: exactly the dense loop with that cut."""
    cfg = reduced(get_config("qwen3-moe-30b-a3b"))
    cfg = dataclasses.replace(cfg, ternary=dataclasses.replace(
        cfg.ternary, das=None), moe=dataclasses.replace(
        cfg.moe, capacity_factor=factor))
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)) * 0.5
    t, e = 32, cfg.moe
    cap = max(1, min(t, int(t * e.top_k / e.n_experts * factor) + 1))
    assert cap < t
    got = MOE.moe_apply(p, cfg, x)
    want = _dense_reference(p, cfg, x, capacity=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_capacity_drops_tokens():
    cfg = reduced(get_config("qwen3-moe-30b-a3b"))
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=0.05))
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y = MOE.moe_apply(p, cfg, x)
    assert bool(jnp.isfinite(y).all())
    # with tiny capacity most tokens drop -> much smaller output norm
    cfg_full = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=4.0))
    y_full = MOE.moe_apply(p, cfg_full, x)
    assert float(jnp.linalg.norm(y)) < float(jnp.linalg.norm(y_full))


def test_shared_expert_added():
    cfg = reduced(get_config("kimi-k2-1t-a32b"))
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    assert "shared_gate" in p
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model))
    y = MOE.moe_apply(p, cfg, x)
    assert bool(jnp.isfinite(y).all())
