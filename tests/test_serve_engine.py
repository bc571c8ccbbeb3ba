"""Continuous-batching serving: per-sequence caches + engine invariance.

(a) per-sequence attn_write/attn_read reduces to the old shared-t
    behaviour when all sequences are in lock-step;
(b) engine integration: staggered requests with different prompt lengths
    produce tokens bitwise identical to running each request alone
    (batch invariance), in both `full` and `ring` (LPSA) cache modes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DasConfig, LpsaConfig, ModelConfig, TernaryConfig
from repro.core import lpsa
from repro.models import attention as A
from repro.models import kvcache as KV
from repro.models import model as MD
from repro.models.transformer import Runtime
from repro.serve import FifoScheduler, Request, ServeEngine, sample_token

CFG = ModelConfig(
    name="tiny-serve", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
    ternary=TernaryConfig(das=DasConfig(16, 8)),
    lpsa=LpsaConfig(sink=4, window=12, chunk=8),
    dtype="float32", remat=False, scan_layers=False,
)


@pytest.fixture(scope="module")
def sparams():
    params = MD.init_params(jax.random.PRNGKey(0), CFG)
    return MD.export_serving(params, CFG)


# -------------------------------------------------------------------------
# (a) cache layer: per-sequence t == shared t in lock-step
# -------------------------------------------------------------------------

@pytest.mark.parametrize("ring", [False, True])
def test_attn_write_lockstep_matches_shared_t(rng, ring):
    B, S, Hkv, D = 3, 20, CFG.n_kv_heads, CFG.head_dim_
    sink, window = 4, 12
    init = (KV.init_attn_ring(CFG, B, sink, window, jnp.float32) if ring
            else KV.init_attn_full(CFG, B, S, jnp.float32))
    shared, perseq = init, init
    for t in range(16):
        k = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), jnp.float32)
        shared = KV.attn_write(shared, k, v, jnp.array(t), sink=sink,
                               window=window, ring=ring)
        perseq = KV.attn_write(perseq, k, v, jnp.full((B,), t), sink=sink,
                               window=window, ring=ring)
    for name in ("k", "v", "pos"):
        np.testing.assert_array_equal(np.asarray(shared[name]),
                                      np.asarray(perseq[name]))
    assert shared["pos"].shape[0] == B  # position map is per-sequence


def test_attn_write_per_sequence_positions(rng):
    """Sequences at different depths land in their own ring slots."""
    B, Hkv, D = 2, CFG.n_kv_heads, CFG.head_dim_
    sink, window = 4, 12
    cache = KV.init_attn_ring(CFG, B, sink, window, jnp.float32)
    t = jnp.asarray([2, 30])          # row 0 in sink range, row 1 deep decode
    k = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), jnp.float32)
    cache = KV.attn_write(cache, k, k, t, sink=sink, window=window, ring=True)
    pos = np.asarray(cache["pos"])
    slot0 = int(lpsa.decode_slot(jnp.array(2), sink, window))
    slot1 = int(lpsa.decode_slot(jnp.array(30), sink, window))
    assert pos[0, slot0] == 2 and pos[1, slot1] == 30
    assert pos[1, slot0] == -1        # row 1 untouched at row 0's slot


@pytest.mark.parametrize("serve_sparse", [True, False])
def test_attn_decode_vector_t_matches_scalar(rng, serve_sparse):
    B = 2
    rt = Runtime(serve_sparse=serve_sparse)
    ap = A.attn_init(jax.random.PRNGKey(3), CFG)
    sink, window = A.kind_sink_window(CFG, "attn", serve_sparse)
    cache_s = (KV.init_attn_ring(CFG, B, sink, window, jnp.float32)
               if sink < A.FULL_SINK
               else KV.init_attn_full(CFG, B, 24, jnp.float32))
    cache_v = cache_s
    for t in range(8):
        x = jnp.asarray(rng.standard_normal((B, 1, CFG.d_model)), jnp.float32)
        y_s, cache_s = A.attn_decode(ap, CFG, x, cache_s, jnp.array(t), "attn",
                                     serve_sparse=serve_sparse)
        y_v, cache_v = A.attn_decode(ap, CFG, x, cache_v, jnp.full((B,), t),
                                     "attn", serve_sparse=serve_sparse)
        np.testing.assert_array_equal(np.asarray(y_s), np.asarray(y_v))
    _ = rt


# -------------------------------------------------------------------------
# (b) engine integration: batch invariance under staggered admission
# -------------------------------------------------------------------------

def _trace(seed=0):
    rng = np.random.default_rng(seed)
    # prompt 11: shorter than one pack (pure tail feed); 19: pack + tail;
    # 16: exactly pack-aligned (first token from prefill logits)
    spec = [(11, 6, 0, 0.0), (19, 5, 3, 0.9), (16, 4, 4, 0.0)]
    return [Request(uid=i,
                    prompt=np.asarray(rng.integers(0, CFG.vocab, p), np.int32),
                    max_new_tokens=g, arrival=a, temperature=tmp)
            for i, (p, g, a, tmp) in enumerate(spec)]


@pytest.mark.parametrize("serve_sparse", [True, False],
                         ids=["ring", "full"])
def test_engine_batch_invariance(sparams, serve_sparse):
    rt = Runtime(serve_sparse=serve_sparse)
    trace = _trace()
    eng = ServeEngine(CFG, sparams, rt, max_slots=2, max_len=64, seed=0)
    for r in trace:
        eng.submit(r)
    joint = eng.run()
    assert set(joint) == {r.uid for r in trace}
    for r in trace:
        solo_eng = ServeEngine(CFG, sparams, rt, max_slots=2, max_len=64,
                               seed=0)
        solo_eng.submit(r)
        solo = solo_eng.run()[r.uid]
        np.testing.assert_array_equal(solo.tokens, joint[r.uid].tokens)
        assert len(joint[r.uid].tokens) == r.max_new_tokens


def test_engine_admits_mid_decode(sparams):
    """A request arriving later joins while earlier slots keep decoding."""
    trace = _trace()
    eng = ServeEngine(CFG, sparams, Runtime(), max_slots=2, max_len=64)
    for r in trace:
        eng.submit(r)
    results = eng.run()
    late = results[2]
    assert late.admit_vtime >= trace[2].arrival > 0
    assert late.admitted_with_active > 0   # other slots were mid-generation
    # overlap: it was admitted strictly before the last earlier request done
    assert late.admit_vtime < max(results[0].finish_vtime,
                                  results[1].finish_vtime)
    assert eng.stats.slot_utilization > 0.5


def test_engine_eos_frees_slot(sparams):
    """EOS termination frees the slot early (fewer tokens than max)."""
    rng = np.random.default_rng(1)
    prompt = np.asarray(rng.integers(0, CFG.vocab, 11), np.int32)
    eng = ServeEngine(CFG, sparams, Runtime(), max_slots=1, max_len=64)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=50))
    free_run = eng.run()[0]
    # pretend the first sampled id that did not occur earlier in the output
    # is EOS: generation must stop exactly there
    toks = free_run.tokens.tolist()
    stop = next(i for i, t in enumerate(toks) if i > 0 and t not in toks[:i])
    eos = toks[stop]
    eng2 = ServeEngine(CFG, sparams, Runtime(), max_slots=1, max_len=64)
    eng2.submit(Request(uid=0, prompt=prompt, max_new_tokens=50, eos_id=eos))
    stopped = eng2.run()[0]
    assert stopped.tokens.tolist() == toks[:stop + 1]


def test_engine_rejects_bad_requests_and_resets(sparams):
    eng = ServeEngine(CFG, sparams, Runtime(), max_slots=1, max_len=64)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(uid=0, prompt=np.zeros((0,), np.int32),
                           max_new_tokens=4))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(uid=0, prompt=np.zeros((4,), np.int32),
                           max_new_tokens=0))
    eng.submit(Request(uid=7, prompt=np.zeros((4,), np.int32),
                       max_new_tokens=1))
    with pytest.raises(ValueError, match="already in flight"):
        eng.submit(Request(uid=7, prompt=np.zeros((6,), np.int32),
                           max_new_tokens=1))
    eng.run()
    rt_full = Runtime(serve_sparse=False)
    eng_full = ServeEngine(CFG, sparams, rt_full, max_slots=1, max_len=16)
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng_full.submit(Request(uid=0, prompt=np.zeros((12,), np.int32),
                                max_new_tokens=8))
    # reset_clock: only valid drained; zeroes vtime/stats, keeps jit caches
    req = _trace()[0]
    eng.submit(req)
    eng.run()
    assert eng.vtime > 0
    eng.reset_clock()
    assert eng.vtime == 0 and eng.stats.decode_steps == 0
    eng.submit(req)
    with pytest.raises(RuntimeError, match="non-drained"):
        eng.reset_clock()
    assert len(eng.run()[req.uid].tokens) == req.max_new_tokens


def test_wave_policy_matches_tokens_but_serializes(sparams):
    """Lock-step baseline: same per-request tokens, later finish times."""
    trace = _trace()
    cont = ServeEngine(CFG, sparams, Runtime(), max_slots=2, max_len=64)
    wave = ServeEngine(CFG, sparams, Runtime(), max_slots=2, max_len=64,
                       policy="wave")
    for r in trace:
        cont.submit(r)
        wave.submit(r)
    rc, rw = cont.run(), wave.run()
    for r in trace:
        np.testing.assert_array_equal(rc[r.uid].tokens, rw[r.uid].tokens)
    assert wave.stats.decode_steps >= cont.stats.decode_steps


# -------------------------------------------------------------------------
# scheduler + sampler units
# -------------------------------------------------------------------------

def test_scheduler_priority_then_arrival():
    s = FifoScheduler()
    mk = lambda uid, arr, pri=0: Request(uid=uid, prompt=np.zeros(1, np.int32),
                                         max_new_tokens=1, arrival=arr,
                                         priority=pri)
    s.add(mk(0, 5))        # future-dated
    s.add(mk(1, 0))
    s.add(mk(2, 0, pri=-1))
    assert s.pop_ready(0).uid == 2    # best priority first
    assert s.pop_ready(0).uid == 1    # future-dated uid=0 never blocks
    assert s.pop_ready(0) is None
    assert s.next_arrival() == 5
    assert s.pop_ready(5).uid == 0
    assert len(s) == 0


def test_sampler_modes(rng):
    logits = jnp.asarray(rng.standard_normal(64), jnp.float32)
    key = jax.random.PRNGKey(0)
    greedy = sample_token(logits, key, jnp.float32(0.0))
    assert int(greedy) == int(jnp.argmax(logits))
    # top-k restricts support to the k best ids
    top4 = set(np.asarray(jax.lax.top_k(logits, 4)[1]).tolist())
    draws = {int(sample_token(logits, jax.random.PRNGKey(i),
                              jnp.float32(5.0), top_k=4)) for i in range(32)}
    assert draws <= top4 and len(draws) > 1
    # deterministic per key
    a = sample_token(logits, jax.random.PRNGKey(7), jnp.float32(1.0))
    b = sample_token(logits, jax.random.PRNGKey(7), jnp.float32(1.0))
    assert int(a) == int(b)
