"""Mellum 2's mechanisms in the program: YaRN RoPE on the full layers, the
sliding window's boundary in streaming prefill and in the sink-0 decode
ring, dropless MoE in the serving prefill, the per-kind resolution of sink,
window and RoPE (BitNet's unchanged), and the decode attention's routing
between the Pallas kernel and the XLA flash path by shape.

The full model against its plain reference is bench/tests/test_mellum.py.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.configs.base import LpsaConfig, MoeConfig
from repro.core import lpsa
from repro.kernels import ops
from repro.models import attention as A
from repro.models import kvcache as KV
from repro.models import layers as L
from repro.models import model as MD
from repro.models import moe as MOE
from repro.models.transformer import Runtime, layer_cache_spec
from repro.serve import Request, ServeConfig, ServeEngine
from repro.serve.metrics import Telemetry

MELLUM = get_config("mellum2-12b-a2.5b")


# -- YaRN ----------------------------------------------------------------------

def test_yarn_frequencies_and_attention_factor():
    """dim 128, base 5e5, factor 16, original 8192: the correction range
    over the 64 frequency indices is [18, 35]; up to 18 the original
    frequency, from 35 it / 16, linear between.  cos and sin carry the
    attention factor, so q.k scales by its square."""
    yarn = MELLUM.rope_yarn
    f = L.yarn_inv_freq(128, 5e5, yarn)
    base = 5e5 ** (-np.arange(64) / 64)
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(5e5)))
    high = math.ceil(128 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(5e5)))
    assert (low, high) == (18, 35)
    ramp = np.clip((np.arange(64) - 18) / 17, 0, 1)
    np.testing.assert_allclose(f, base * (1 - ramp) + base / 16 * ramp,
                               rtol=1e-12)
    np.testing.assert_allclose(f[:19], base[:19], rtol=1e-12)
    np.testing.assert_allclose(f[35:], base[35:] / 16, rtol=1e-12)

    pos = jnp.arange(0, 4096, 7)
    cos, sin = L.rope(pos, 128, 5e5, yarn)
    ang = (np.asarray(pos, np.float32)[:, None]
           * f.astype(np.float32)).astype(np.float64)
    np.testing.assert_allclose(cos, 1.2772588722239782 * np.cos(ang),
                               atol=2e-5)
    np.testing.assert_allclose(sin, 1.2772588722239782 * np.sin(ang),
                               atol=2e-5)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 1, 128))
    plain = L.apply_rope(x, *L.rope(pos[:1] * 0 + 3, 128, 5e5))
    scaled = L.apply_rope(x, *L.rope(pos[:1] * 0 + 3, 128, 5e5,
                                     dataclasses.replace(yarn, factor=1.0)))
    np.testing.assert_allclose(jnp.vdot(scaled[0, 0], scaled[0, 1]),
                               1.2772588722239782 ** 2
                               * jnp.vdot(plain[0, 0], plain[0, 1]),
                               rtol=1e-4)


def test_kinds_resolve_sink_window_and_rope():
    """BitNet's global layers resolve to the LPSA sink 128 + window 896 and
    plain RoPE, as before; Mellum's sliding layers to a sink-0 ring of 1024
    with plain RoPE, its full layers to a full cache with YaRN, whatever
    Runtime.serve_sparse says."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 4, 100))
    pos = jnp.arange(1000, 1005)
    for arch in ("bitnet-3b", "bitnet-1.3b"):
        cfg = get_config(arch)
        assert A.kind_sink_window(cfg, "attn", True) == (128, 896)
        assert A.kind_sink_window(cfg, "attn", False) == (A.FULL_SINK, 0)
        assert layer_cache_spec(cfg, "attn", 4, 2048, Runtime()) == \
            KV.CacheSpec("ring", 4, sink=128, window=896)
        xb = x[..., :cfg.head_dim_]
        np.testing.assert_array_equal(
            A._rope_fn(cfg, "attn")(xb, pos),
            L.apply_rope(xb, *L.rope(pos, cfg.head_dim_, 10_000.0)))
    for sparse in (True, False):
        assert A.kind_sink_window(MELLUM, "attn", sparse) == (A.FULL_SINK, 0)
        assert A.kind_sink_window(MELLUM, "local", sparse) == (0, 1024)
    rt = Runtime()
    assert layer_cache_spec(MELLUM, "local", 4, 2048, rt) == \
        KV.CacheSpec("ring", 4, sink=0, window=1024)
    assert layer_cache_spec(MELLUM, "attn", 4, 2048, rt) == \
        KV.CacheSpec("full", 4, max_len=2048)
    x = jnp.tile(x, (1, 1, 1, 2))[..., :128]
    np.testing.assert_array_equal(
        A._rope_fn(MELLUM, "local")(x, pos),
        L.apply_rope(x, *L.rope(pos, 128, 5e5)))
    np.testing.assert_array_equal(
        A._rope_fn(MELLUM, "attn")(x, pos),
        L.apply_rope(x, *L.rope(pos, 128, 5e5, MELLUM.rope_yarn)))


# -- the sliding window's boundary -------------------------------------------

W, T = 1024, 1280


def _attend(q, k, v, q_pos, k_pos, keep):
    """Plain softmax attention of one head: q (Lq, D) at q_pos over keys
    at k_pos that keep(q, k) admits."""
    s = (q @ k.T) / np.sqrt(q.shape[-1])
    ok = keep(q_pos[:, None], k_pos[None, :]) & (k_pos[None, :] <= q_pos[:, None])
    s = np.where(ok, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ v


def _stream(rng, q_next):
    """Streaming prefill (sink 0, window 1024, packs of 256) of T random
    positions of one head, each key at distance 1024 from a query (and,
    at T - 1024, from `q_next` at T) aligned with that query, so that
    admitting it would change the output."""
    q, k, v = (rng.standard_normal((1, T, 1, 8)).astype(np.float32)
               for _ in range(3))
    def aligned(qs):     # a key that scores 30 / sqrt(8) against qs
        return 30 * qs / (qs * qs).sum(-1, keepdims=True)
    k[:, :T - W] = aligned(q[:, W:])
    k[:, T - W] = aligned(q_next)
    qkv = {"q": q, "k": k, "v": v}

    def proj(pack):      # the "hidden state" carries q | k | v
        return pack[..., None, :8], pack[..., None, 8:16], pack[..., None, 16:]
    out, state = lpsa.lpsa_prefill(
        jnp.concatenate([q, k, v], -1)[:, :, 0], proj,
        spec=lpsa.LpsaSpec(0, W, 256), num_q_heads=1, num_kv_heads=1,
        head_dim=8, return_state=True)
    return qkv, np.asarray(out)[0, :, 0], state


def test_sliding_window_boundary_in_streaming_prefill():
    """HF's sliding mask: query q sees key k iff 0 <= q - k < 1024, so a
    distance of 1023 is kept and 1024 dropped."""
    rng = np.random.default_rng(0)
    qkv, out, _ = _stream(rng, rng.standard_normal((1, 8)))
    q, k, v = (qkv[n][0, :, 0] for n in ("q", "k", "v"))
    p = np.arange(T)
    want = _attend(q, k, v, p, p, lambda a, b: a - b < W)
    one_more = _attend(q, k, v, p, p, lambda a, b: a - b <= W)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    assert np.abs(out[W:] - one_more[W:]).max() > 0.5


def test_sliding_window_boundary_in_the_decode_ring():
    """The ring the streaming prefill leaves holds positions 256..1279;
    writing position 1280 evicts 256, and the decode query at 1280 sees
    257 (distance 1023) but not 256 (1024), in the XLA flash path and in
    the Pallas kernel (interpret mode)."""
    rng = np.random.default_rng(1)
    kn, vn, qn = (rng.standard_normal((1, 1, 1, 8)).astype(np.float32)
                  for _ in range(3))
    qkv, _, state = _stream(rng, qn[0, 0])
    ring = KV.ring_from_stream(MELLUM, state, sink=0, window=W)
    assert sorted(np.asarray(ring["pos"][0])) == list(range(256, T))
    t = jnp.array([T])
    ring = KV.attn_write(ring, kn, vn, t, sink=0, window=W, ring=True)
    kp = np.asarray(ring["pos"][0])
    assert kp.min() == 257 and kp.max() == T
    k_all = np.concatenate([qkv["k"][0, :, 0], kn[0, :, 0]])
    v_all = np.concatenate([qkv["v"][0, :, 0], vn[0, :, 0]])
    p = np.arange(T + 1)
    want = _attend(qn[0, :, 0], k_all, v_all, p[-1:], p,
                   lambda a, b: a - b < W)
    one_more = _attend(qn[0, :, 0], k_all, v_all, p[-1:], p,
                       lambda a, b: a - b <= W)
    assert np.abs(want - one_more).max() > 0.1
    xla = A.flash_masked(jnp.asarray(qn), ring["k"], ring["v"], t[:, None],
                         ring["pos"], sink=0, window=W)
    np.testing.assert_allclose(np.asarray(xla)[0, :, 0], want, rtol=2e-5,
                               atol=2e-5)
    pallas = ops.sparse_attention(
        jnp.asarray(qn).swapaxes(1, 2), ring["k"].swapaxes(1, 2),
        ring["v"].swapaxes(1, 2), t[:, None], ring["pos"], sink=0, window=W,
        mode="interpret")
    np.testing.assert_allclose(np.asarray(pallas)[0, 0], want, rtol=2e-5,
                               atol=2e-5)


# -- dropless MoE in the serving prefill --------------------------------------

def _hot_config():
    cfg = reduced(MELLUM)
    return dataclasses.replace(
        cfg, ternary=dataclasses.replace(cfg.ternary, enabled=False,
                                         das=None),
        lpsa=LpsaConfig(sink=0, window=32, chunk=256),
        moe=MoeConfig(n_experts=8, top_k=2, d_expert=128,
                      capacity_factor=1.25))


def test_a_hot_pack_prefills_as_it_decodes_and_drops_nothing():
    """A 256-token pack whose tokens are mostly one id sends most of its
    tokens to the same experts: the training capacity (256 * 2 / 8 * 1.25
    + 1 = 81 a expert) drops most of them, the serving prefill drops none
    and gives the logits of token-by-token decode."""
    cfg = _hot_config()
    rt = Runtime()
    p = MD.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    toks = np.where(rng.random(256) < 0.85, 7,
                    rng.integers(0, cfg.vocab, 256)).astype(np.int32)
    lg, _ = MD.prefill(p, cfg, jnp.asarray(toks)[None], rt, max_len=257)
    # one layer's MoE on a pack whose first 200 rows are one token: the
    # serving capacity keeps all of them, the training capacity cuts them
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 256, cfg.d_model))
    x = x.at[:, :200].set(x[:, :1])
    moe = p["layers"]["tail"][0]["moe"]
    train = MOE.moe_apply(moe, cfg, x)
    serve, st = MOE.moe_apply(moe, cfg, x, with_stats=True,
                              capacity=MOE.serve_capacity(cfg, 256))
    assert int(st["max_load"]) >= 200 > 81
    np.testing.assert_allclose(np.asarray(serve[0, 1:200]),
                               np.asarray(serve[0, :1]).repeat(199, 0),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(train[0, 150:200]),
                           np.asarray(serve[0, 150:200]), atol=1e-3)

    step = jax.jit(lambda c, tok, t: MD.decode_step(p, cfg, c, tok, t, rt))
    caches = MD.init_caches(None, cfg, 1, 257, rt, jnp.float32)
    for t in range(256):
        dl, caches = step(caches, jnp.asarray(toks[t:t + 1]), jnp.array(t))
    np.testing.assert_allclose(np.asarray(dl), np.asarray(lg), rtol=1e-4,
                               atol=1e-4)


def test_engine_counts_routing():
    """The engine reads the MoE counters of every tick into its stats and
    the telemetry: a tick's experts per layer and its largest per-expert
    load."""
    cfg = dataclasses.replace(reduced(MELLUM), moe=MoeConfig(
        n_experts=8, top_k=2, d_expert=128, capacity_factor=1.0))
    sp = MD.export_serving(MD.init_params(jax.random.PRNGKey(0), cfg), cfg)
    eng = ServeEngine(cfg, sp, Runtime(), ServeConfig(max_slots=2,
                                                      max_len=96))
    tele = Telemetry(engine=eng)
    rng = np.random.default_rng(0)
    for uid, n in enumerate((40, 70)):
        eng.submit(Request(uid=uid, max_new_tokens=6,
                           prompt=rng.integers(0, cfg.vocab, n)
                           .astype(np.int32)))
    eng.run()
    assert [r["layout"] for r in eng.layout_summary()] == \
        ["ring", "ring", "ring", "full"]
    st = eng.stats
    assert set(eng.moe_tick) == {"experts_hit", "max_load"}
    assert eng.moe_tick["experts_hit"].shape == (cfg.n_layers,)
    assert 1 <= st.moe_max_load <= 2
    assert st.decode_steps * cfg.n_layers <= st.moe_experts_hit \
        <= st.decode_steps * cfg.n_layers * 4
    snap = tele.snapshot(eng)
    assert 1.0 <= snap["moe"]["experts_hit_per_layer"] <= 4.0
    assert snap["moe"]["max_expert_load"] == st.moe_max_load
    assert snap["engine"]["moe_experts_hit"] == st.moe_experts_hit

    # the compiled step and prefill name the kinds of attention and the
    # MoE's stages in their ops' scopes
    _, args = eng._tick_inputs()
    step = eng._step.lower(eng.sparams, eng.caches, *args).compile()
    prefill = eng._prefill.lower(eng.sparams,
                                 jnp.zeros((1, 16), jnp.int32)).compile()
    for text in (step.as_text(), prefill.as_text()):
        scopes = {s for op in re.findall(r'op_name="([^"]*)"', text)
                  for s in op.split("/")}
        assert {"attn", "attn_window", "attn_full", "moe", "route",
                "dispatch", "experts", "combine"} <= scopes
        assert "ffn" not in scopes


# -- decode attention: kernel or XLA flash, by shape --------------------------

def test_attention_kernel_fits():
    assert ops.attn_kernel_fits(32, 4, 2048)       # Mellum's full caches
    assert ops.attn_kernel_fits(32, 4, 1024)       # and its rings
    assert ops.attn_kernel_fits(32, 32, 1024)      # BitNet's LPSA rings
    assert ops.attn_kernel_fits(4, 2, 32)          # one block
    assert not ops.attn_kernel_fits(32, 4, 1000)   # no 128-lane blocks
    assert not ops.attn_kernel_fits(6, 4, 1024)    # heads not grouped


def test_gqa_full_cache_decode_in_the_kernel():
    """GQA 8:1 at head dim 128 over a full cache with empty slots: the
    kernel (interpret mode) against the reference."""
    rng = np.random.default_rng(4)
    b, lk = 2, 256
    q = jnp.asarray(rng.standard_normal((b, 8, 1, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((b, 1, lk, 128)), jnp.float32)
            for _ in range(2))
    kp = jnp.asarray(np.where(np.arange(lk) < 200, np.arange(lk), -1)
                     [None].repeat(b, 0), jnp.int32)
    qp = jnp.full((b, 1), 199, jnp.int32)
    got = ops.sparse_attention(q, k, v, qp, kp, sink=A.FULL_SINK, window=0,
                               mode="interpret")
    want = ops.sparse_attention(q, k, v, qp, kp, sink=A.FULL_SINK, window=0,
                                mode="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-4,
                               atol=3e-4)


def test_a_cache_the_kernel_cannot_tile_takes_the_xla_path():
    """Under the pallas mode a full cache of 200 positions (no whole
    128-lane key blocks) decodes through `flash_masked`, noted as a
    fallback, with the same result."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((2, 1, 8, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 200, 2, 16)), jnp.float32)
            for _ in range(2))
    kp = jnp.broadcast_to(jnp.arange(200), (2, 200)).astype(jnp.int32)
    qp = jnp.full((2, 1), 199, jnp.int32)
    before = ops.fallback_counts().get(("sparse_attn", (8, 2, 200, 16)), 0)
    got = A._decode_attention(MELLUM, q, k, v, qp, kp, sink=A.FULL_SINK,
                              window=0, kernel_mode="pallas")
    assert ops.fallback_counts()[("sparse_attn", (8, 2, 200, 16))] == \
        before + 1
    want = A.flash_masked(q, k, v, qp, kp, sink=A.FULL_SINK, window=0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
