"""DSE-driven kernel autotuner (kernels/autotune) + tuned dispatch.

(a) cache: TileConfig round-trips through the on-disk JSON; a populated
    cache answers `tune` with ZERO timed candidate runs;
(b) ranking: `perfmodel.kernel_cost` orders the XLA-CPU implementations the
    way they actually measure (dense-mask decode-GEMMs beat the gather
    path; f32dec beats plain decode), and `tune`'s timed winner is one of
    the perfmodel's top-ranked candidates;
(c) parity: the tuned/compiled/interpret dispatches agree with the jnp
    reference over a hypothesis sweep of shapes and seeds;
(d) fallback accounting: shape-inadmissible layers under a kernel mode warn
    exactly once per shape and count every occurrence;
(e) engine: `kernel_mode="tuned"` produces token streams bitwise identical
    to "ref", and a second engine over the same shapes warms up from the
    cache without re-timing anything (`stats.autotune_timed_runs == 0`).
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs.base import DasConfig, LpsaConfig, ModelConfig, TernaryConfig
from repro.core import das, twd
from repro.core.perfmodel import CPU_HOST, kernel_cost
from repro.kernels import autotune, ops, ref
from repro.models import model as MD
from repro.models.ternary_linear import export_tlin, tlin_apply, tlin_init
from repro.serve import Request, ServeEngine

SCALE = 0.37


@pytest.fixture()
def cache(tmp_path):
    return autotune.AutotuneCache(str(tmp_path / "autotune.json"))


# -------------------------------------------------------------------------
# (a) cache round-trip + zero re-timing
# -------------------------------------------------------------------------

def test_cache_round_trip(cache):
    cfg = autotune.TileConfig("xla_dense_f32dec", block_m=8, block_n=256,
                              block_k=2)
    key = autotune.shape_key("das_ternary_gemm", "cpu", m=4, k=1280, n=512,
                             keep=16, block=32)
    cache.put(key, cfg, 123.4)
    reloaded = autotune.AutotuneCache(cache.path)
    assert reloaded.get(key) == cfg
    assert reloaded.entries[key]["us"] == 123.4
    with open(cache.path) as f:
        assert json.load(f)["version"] == 1


def test_cache_corrupt_file_is_empty(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    assert autotune.AutotuneCache(str(p)).entries == {}


def test_tune_hit_does_zero_timed_runs(cache):
    dims = dict(m=2, k=320, n=128, keep=16, block=32)
    cfg = autotune.tune("das_ternary_gemm", backend="cpu", cache=cache,
                        budget=2, iters=1, **dims)
    assert cache.timed_runs > 0
    fresh = autotune.AutotuneCache(cache.path)     # reload from disk
    cfg2 = autotune.tune("das_ternary_gemm", backend="cpu", cache=fresh,
                         budget=2, iters=1, **dims)
    assert cfg2 == cfg
    assert fresh.timed_runs == 0


def test_lookup_is_pure_and_deterministic(cache):
    dims = dict(m=4, k=640, n=256, keep=16, block=32)
    a = autotune.lookup("das_ternary_gemm", backend="cpu", cache=cache, **dims)
    b = autotune.lookup("das_ternary_gemm", backend="cpu", cache=cache, **dims)
    assert a == b
    assert cache.timed_runs == 0 and cache.entries == {}   # never persists


def test_shape_key_order_independent():
    assert autotune.shape_key("op", "cpu", m=1, k=2) == \
        autotune.shape_key("op", "cpu", k=2, m=1)


# -------------------------------------------------------------------------
# (b) perfmodel ranking vs reality
# -------------------------------------------------------------------------

def test_perfmodel_orders_cpu_impls():
    """The documented XLA-CPU facts, as the model must rank them:
    masked-dense decode-GEMMs beat the gather path (gathers run ~15x below
    streaming bandwidth), and the f32dec strided decode beats the plain
    int unpack (no materialized digit stack)."""
    dims = dict(m=4, k=1280, n=512, keep=16, block=32)
    c = {impl: kernel_cost(CPU_HOST, "das_ternary_gemm", impl, **dims)
         for impl in ("xla_dense_f32dec", "xla_dense_plain", "xla_gather")}
    assert c["xla_dense_f32dec"] < c["xla_dense_plain"] < c["xla_gather"]
    d = {impl: kernel_cost(CPU_HOST, "ternary_gemm", impl, m=4, k=1280,
                           n=512, keep=0, block=0)
         for impl in ("xla_f32dec", "xla_plain")}
    assert d["xla_f32dec"] < d["xla_plain"]


def test_tuned_winner_among_model_top_ranked(cache):
    """Timed confirmation picks from the perfmodel's top `budget` — i.e. the
    analytic ranking and the measurement agree on the winner's bracket."""
    dims = dict(m=4, k=640, n=256, keep=16, block=32)
    budget = 2
    ranked = sorted(
        autotune.candidates("das_ternary_gemm", "cpu", **dims),
        key=lambda c: kernel_cost(CPU_HOST, "das_ternary_gemm", c.impl,
                                  block_m=c.block_m, block_n=c.block_n,
                                  block_k=c.block_k, **dims))
    won = autotune.tune("das_ternary_gemm", backend="cpu", cache=cache,
                        budget=budget, iters=2, **dims)
    assert won in ranked[:budget]


def test_candidates_feasibility():
    # unaligned K: no pallas tiles, no gather, but masked-dense still covers
    cands = autotune.candidates("das_ternary_gemm", "cpu", m=2, k=5460,
                                n=128, keep=16, block=32)
    impls = {c.impl for c in cands}
    assert "xla_dense_f32dec" in impls and "xla_dense_plain" in impls
    assert "xla_gather" not in impls and "pallas" not in impls
    # interpret backend enumerates only emulated Pallas tiles
    cands = autotune.candidates("das_ternary_gemm", "interpret", m=2, k=320,
                                n=128, keep=16, block=32)
    assert cands and all(c.impl == "interpret" for c in cands)
    # infeasible everywhere -> empty -> lookup returns the ref sentinel
    assert autotune.candidates("ternary_gemm", "interpret", m=2, k=321,
                               n=128, keep=0, block=0) == []
    cfg = autotune.lookup("ternary_gemm", backend="interpret",
                          cache=autotune.AutotuneCache("/nonexistent/x.json"),
                          m=2, k=321, n=128, keep=0, block=0)
    assert cfg.impl == "ref"


def test_failed_candidates_are_recorded(cache, monkeypatch):
    """A candidate that raises while timed is kept, with its error, in the
    cache entry; tuning goes on with the others."""
    real = autotune._time_gemm

    def flaky(op, cfg, dims, **kw):
        if cfg.impl == "xla_dense_plain":
            raise ValueError("refused by the compiler")
        return real(op, cfg, dims, **kw)

    monkeypatch.setattr(autotune, "_time_gemm", flaky)
    dims = dict(m=2, k=320, n=128, keep=16, block=32)
    won = autotune.tune("das_ternary_gemm", backend="cpu", cache=cache,
                        budget=10, iters=1, **dims)
    entry = cache.entries[autotune.shape_key("das_ternary_gemm", "cpu",
                                             **dims)]
    assert won.impl != "xla_dense_plain"
    assert [f["impl"] for f in entry["failed"]] == ["xla_dense_plain"]
    assert "refused by the compiler" in entry["failed"][0]["error"]


def test_tpu_raises_when_every_pallas_candidate_fails(cache, monkeypatch):
    """On a TPU backend a Pallas refusal is a defect, not a cue to serve
    through the next implementation."""
    def refuse_pallas(op, cfg, dims, **kw):
        if cfg.impl == "pallas":
            raise RuntimeError("Mosaic: unsupported shape cast")
        return 1.0

    monkeypatch.setattr(autotune, "_time_gemm", refuse_pallas)
    monkeypatch.setattr(autotune, "_hw", lambda backend: CPU_HOST)
    with pytest.raises(RuntimeError, match="every Pallas candidate failed"):
        autotune.tune("das_ternary_gemm", backend="tpu", cache=cache,
                      budget=100, iters=1, m=4, k=640, n=256, keep=16,
                      block=32)


def test_backend_hw_is_keyed_by_device_kind():
    from repro.core import perfmodel
    assert perfmodel.backend_hw("TPU v5 lite") is perfmodel.TPU_V5E
    assert perfmodel.backend_hw("cpu") is CPU_HOST
    with pytest.raises(KeyError, match="no peaks known"):
        perfmodel.backend_hw("TPU v99")


# -------------------------------------------------------------------------
# (c) compiled / tuned / interpret / ref parity
# -------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 5),
       st.sampled_from([320, 640]), st.sampled_from([128, 256]),
       st.sampled_from([8, 16, 32]))
def test_gemm_impl_parity_hypothesis(seed, m, k, n, keep):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    trits = rng.integers(-1, 2, size=(k, n)).astype(np.int8)
    packed = jnp.asarray(twd.pack_ternary(trits))
    want = np.asarray(ref.ternary_gemm_packed_ref(x, packed, SCALE, k))
    for impl in ("xla_f32dec", "xla_plain", "interpret"):
        got = np.asarray(autotune.run_gemm(
            x, packed, SCALE, cfg=autotune.TileConfig(impl, 4, 128, 1)))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4,
                                   err_msg=impl)
    ca = das.das_compact(x, block_size=32, keep=keep)
    want = np.asarray(ref.das_ternary_gemm_ref(ca.values, ca.indices, packed,
                                               SCALE, k))
    for impl in ("xla_dense_f32dec", "xla_dense_plain", "xla_gather",
                 "interpret"):
        got = np.asarray(autotune.run_das_gemm(
            ca.values, ca.indices, packed, SCALE, keep=keep, block=32,
            cfg=autotune.TileConfig(impl, 2, 128, 1)))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4,
                                   err_msg=impl)


def test_compiled_mode_matches_ref(rng):
    """`compiled` probes the backend: on CPU it must transparently run the
    Pallas kernels under interpret=True and agree with the reference."""
    m, k, n = 3, 640, 256
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    trits = rng.integers(-1, 2, size=(k, n)).astype(np.int8)
    packed = jnp.asarray(twd.pack_ternary(trits))
    want = np.asarray(ops.ternary_gemm(x, packed, SCALE, mode="ref"))
    got = np.asarray(ops.ternary_gemm(x, packed, SCALE, mode="compiled"))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


def test_tuned_tlin_matches_ref_any_k(rng, tmp_path, monkeypatch):
    """Tuned dispatch covers K the Pallas modes cannot tile (5460 = bitnet
    d_ff: not slab-aligned, not block-divisible) without falling back."""
    monkeypatch.setenv("TENET_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune.reset_default_cache()
    try:
        tc = TernaryConfig(das=DasConfig(32, 16))
        for k in (320, 5460):
            p = export_tlin(tlin_init(jax.random.PRNGKey(0), k, 128), tc)
            x = jnp.asarray(rng.standard_normal((2, k)), jnp.float32)
            a = np.asarray(tlin_apply(p, x, tc, kernel_mode="tuned"))
            b = np.asarray(tlin_apply(p, x, tc, kernel_mode="ref"))
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-4)
    finally:
        autotune.reset_default_cache()


# -------------------------------------------------------------------------
# (d) fallback accounting
# -------------------------------------------------------------------------

def test_fallback_warns_once_counts_every_time(rng):
    tc = TernaryConfig(das=DasConfig(32, 16))
    p = export_tlin(tlin_init(jax.random.PRNGKey(0), 64, 48), tc)
    x = jnp.asarray(rng.standard_normal((2, 64)), jnp.float32)
    ops.reset_fallbacks()
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            tlin_apply(p, x, tc, kernel_mode="interpret")
            tlin_apply(p, x, tc, kernel_mode="interpret")
        relevant = [m for m in w if "kernel fallback" in str(m.message)]
        assert len(relevant) == 1                      # once per shape
        counts = ops.fallback_counts()
        assert sum(c for (op, _), c in counts.items()
                   if op == "ternary_gemm") == 2       # every occurrence
        # ref mode is an intentional choice, never a counted fallback
        ops.reset_fallbacks()
        tlin_apply(p, x, tc, kernel_mode="ref")
        assert ops.fallback_counts() == {}
    finally:
        ops.reset_fallbacks()


# -------------------------------------------------------------------------
# (e) serve engine: tuned == ref tokens, second warmup is free
# -------------------------------------------------------------------------

TUNED_CFG = ModelConfig(
    name="tiny-tuned", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
    ternary=TernaryConfig(das=DasConfig(16, 8)),
    lpsa=LpsaConfig(sink=4, window=12, chunk=8),
    dtype="float32", remat=False, scan_layers=False,
)


@pytest.mark.slow
def test_serve_engine_tuned_matches_ref_and_warmup_cached(tmp_path,
                                                          monkeypatch):
    monkeypatch.setenv("TENET_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune.reset_default_cache()
    try:
        params = MD.init_params(jax.random.PRNGKey(0), TUNED_CFG)
        sparams = MD.export_serving(params, TUNED_CFG)
        rng = np.random.default_rng(0)
        trace = [Request(uid=i, prompt=np.asarray(
                             rng.integers(0, TUNED_CFG.vocab, pl), np.int32),
                         max_new_tokens=4, arrival=0)
                 for i, pl in enumerate((9, 16))]
        outs, engines = {}, {}
        for mode in ("ref", "tuned"):
            eng = ServeEngine(TUNED_CFG, sparams, max_slots=2, max_len=64,
                              seed=0, kernel_mode=mode)
            for r in trace:
                eng.submit(r)
            outs[mode] = eng.run()
            engines[mode] = eng
        for uid in outs["ref"]:
            np.testing.assert_array_equal(outs["ref"][uid].tokens,
                                          outs["tuned"][uid].tokens)
        assert engines["tuned"].stats.autotune_timed_runs > 0
        # second engine over identical shapes: warm cache, ZERO timed runs
        autotune.reset_default_cache()     # fresh object, same on-disk file
        eng2 = ServeEngine(TUNED_CFG, sparams, max_slots=2, max_len=64,
                           seed=0, kernel_mode="tuned")
        assert eng2.stats.autotune_timed_runs == 0
        for r in trace:
            eng2.submit(r)
        outs2 = eng2.run()
        for uid in outs["ref"]:
            np.testing.assert_array_equal(outs["ref"][uid].tokens,
                                          outs2[uid].tokens)
    finally:
        autotune.reset_default_cache()
