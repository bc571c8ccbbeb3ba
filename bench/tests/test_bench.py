"""Tests of the benchmark's own code, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The trace reduction, the cost functions and the window arithmetic against
hand counts; the command's refusals; and the decision on ``correct``: a
sound tiny run passes, the control (the reference one precision lower, in
the program's place) fails, and so does each fault a served cell can have
(a decode step that returns its state unchanged, a token altered where it
is produced).  They are not among the repository's tier-1 tests
(``testpaths`` is ``tests/``).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
import numpy as np  # noqa: E402
from lib import check, costs, spec, trace as TR, traffic, window  # noqa: E402

ROOT = os.path.dirname(tiny.BENCH)
V5E = costs.peaks("TPU v5 lite")


# -- trace reduction ------------------------------------------------------

def _trace():
    # window [0, 10]; one line of properly nested ops: a loop [1,4] holding
    # a GEMM [1,3] and an attention call [3,4]; a fusion [6,7]; a GEMM
    # [9,12] clipped to [9,10].  Modules: step [1,4], prefill [6,7],
    # step [9,12].
    gemm = "%closed_call.5 = f32[16,3200] custom-call(...), das_ternary_gemm"
    ops = [[("%while.3 = (s32[]) while(...)", 1.0, 4.0), (gemm, 1.0, 3.0),
            ("%vmap.8 = bf16[16] custom-call(...), sparse_attention",
             3.0, 4.0),
            ("%fusion.1 = bf16[8] fusion(...)", 6.0, 7.0), (gemm, 9.0, 12.0)]]
    mods = [[("jit__step_fn(1)", 1.0, 4.0), ("jit__lambda(2)", 6.0, 7.0),
             ("jit__step_fn(1)", 9.0, 12.0)]]
    spans = [("bench.traced", 0.0, 10.0), ("bench.tick", 0.6, 4.5),
             ("bench.admission", 4.5, 8.0), ("bench.client", 8.0, 8.5)]
    return TR.Trace(ops, mods, spans, (0.0, 10.0))


def test_busy_union_and_idle_share():
    tr = _trace()
    assert tr.busy_s() == pytest.approx(3.0 + 1.0 + 1.0)
    assert tr.window_s() == 10.0
    assert TR.union([(2, 4), (1, 3), (6, 7)]) == [(1, 4), (6, 7)]


def test_kernel_and_module_matching():
    tr, pats = _trace(), TR.patterns()
    assert tr.time_of(pats["ops"]["das_ternary_gemm"]) == (3.0, 2)
    assert tr.time_of(pats["ops"]["sparse_attention"]) == (1.0, 1)
    step = pats["modules"]["decode_step"]
    assert tr.time_of(step, modules=True) == (4.0, 2)
    assert tr.module_time_except(step) == 1.0


def test_gaps_are_named_by_the_host_span():
    tr = _trace()
    # gaps: [0,1] 1s (host in no bench span but the window), [4,6] 2s
    # (middle 5.0: admission), [7,9] 2s (middle 8.0: admission and client,
    # the innermost wins)
    got = tr.idle_gaps()
    assert sorted(got, key=lambda g: (-g[1], g[0])) == [
        ["admission", 2.0], ["client", 2.0], ["other", 1.0]]
    top = tr.top_ops()
    assert top[0] == ["%closed_call.5", 3.0]
    assert dict(top)["%while.3"] == 0.0     # all of its time is its body


def test_self_times_of_nested_events():
    ev = [("loop", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 4.0, 6.0),
          ("c", 4.5, 5.0), ("d", 11.0, 12.0)]
    assert sorted(TR.self_times(ev)) == [("a", 2.0), ("b", 1.5),
                                         ("c", 0.5), ("d", 1.0),
                                         ("loop", 6.0)]


# -- operations and bytes at bitnet-3b's shapes ----------------------------

CFG3B = json.load(open(os.path.join(tiny.BENCH, "configs", "bitnet-3b.json")))


def test_ternary_gemm_counts():
    w = costs.ternary_gemm(16, 3200, 8640, 16, 32)
    assert w["flops"] == 2 * 16 * 1600 * 8640 == 442_368_000
    assert w["bytes"] == 5_529_600 + 4 + 102_400 + 276_480
    t, bound = costs.roofline_s(w, V5E)
    assert bound == "bandwidth" and t == pytest.approx(w["bytes"] / 819e9)
    # K = 5460 keeps 16 of each of its 170 whole blocks and its last 20
    assert costs.das_k(5460, 16, 32) == 170 * 16 + 20


def test_attention_counts():
    assert costs.live_keys(99, 128, 896) == 100
    assert costs.live_keys(5000, 128, 896) == 1024
    w = costs.lpsa_attention(1024, 32, 32, 100)
    assert w["flops"] == 4 * 32 * 100 * 1024
    assert w["bytes"] == 2 * 2 * 32 * 100 * 1024 + 2 * 2 * 32 * 100


def test_model_counts():
    per_layer_lin = 40_960_000 + 55_296_000 + 27_648_000
    assert costs.token_flops(CFG3B, 99) == (
        26 * (per_layer_lin + 4 * 32 * 100 * 100) + 2 * 3200 * 32002)
    assert costs.weight_bytes(CFG3B) == (
        26 * (24_780_800 + 28 + 12_800) + 204_812_800 + 6_400)
    assert costs.kv_bytes(1024, CFG3B) == 1024 * 26 * 2 * 3200 * 2


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        costs.peaks("TPU v9 imaginary")


# -- window arithmetic ------------------------------------------------------

def _log():
    # window [10, 20]
    return {
        0: {"submit_t": 2.0, "times": [9.0, 11.0, 12.0, 21.0]},
        1: {"submit_t": 8.0, "times": [13.0, 15.0, 20.0]},
        2: {"submit_t": 19.0, "times": [22.0]},
    }


def test_window_metrics():
    log = _log()
    assert window.out_tokens(log, 10.0, 20.0) == 5
    assert window.out_tok_s(log, 10.0, 20.0) == 0.5
    # gaps whose later token lands in (10, 20]: 2, 1 (req 0), 2, 5 (req 1)
    assert sorted(window.gaps_ending_in(log, 10.0, 20.0)) == [1, 2, 2, 5]
    # first tokens in the window: request 1 only (13 - 8)
    assert window.first_token_waits(log, 10.0, 20.0) == [5.0]
    m = window.metrics(log, 10.0, 20.0)
    assert m["ttft_p50_s"] == (5.0, "s", 1)
    assert m["itl_p99_ms"][2] == 4
    assert m["itl_p99_ms"][0] == pytest.approx(1e3 * (2 + 0.97 * 3))


def test_lengths_are_seed_free_and_within_the_law():
    mix = tiny.TRAFFIC
    ps = [traffic.lengths(mix, i, 128) for i in range(240)]
    assert all(4 <= p <= 40 and 4 <= o <= 24 for p, o in ps)
    assert ps == [traffic.lengths(mix, i, 128) for i in range(240)]
    # an output never runs past the context its prompt leaves
    assert all(p + o <= 44 for p, o in (traffic.lengths(mix, i, 44)
                                        for i in range(240)))
    a = traffic.prompt_tokens(2 ** 31 + 5, 3, 10, 512)
    assert (a == traffic.prompt_tokens(2 ** 31 + 5, 3, 10, 512)).all()
    assert not (a == traffic.prompt_tokens(2 ** 31 + 6, 3, 10, 512)).all()


# -- the command's refusals -------------------------------------------------

def _command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bitnet-1.3b.chat.c16",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    r = _command(ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_benchmark_alone_means_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _command(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


# -- correct: a sound run, the control and the faults ----------------------

def test_a_das_tie_broken_the_other_way_is_not_a_fault():
    model = spec.model_module(tiny.CONFIG)
    cfg = dict(tiny.CONFIG, dtype="bfloat16")
    key = spec.seed_key(2 ** 31 + 5)
    toks, pos = np.arange(500), np.arange(500) % 128
    k, v = model.first_layer_kv(key, cfg, toks, pos)
    das = model._das

    def das_high(x, block, keep):
        # ties at the cut to the higher lane: the lanes of each block
        # reversed, DAS, reversed back
        main = x.shape[-1] // block * block
        flip = x[..., :main].reshape(x.shape[:-1] + (-1, block))[..., ::-1]
        out = das(flip.reshape(x.shape[:-1] + (main,)), block, keep)
        out = out.reshape(flip.shape)[..., ::-1].reshape(x.shape[:-1]
                                                         + (main,))
        return np.concatenate([out, x[..., main:]], -1) if main < x.shape[
            -1] else out
    model._das = das_high
    model._cached.cache_clear()
    k2, v2 = model.first_layer_kv(key, cfg, toks, pos)
    model._das = das
    model._cached.cache_clear()
    plain = np.linalg.norm((k2 - k).reshape(500, -1), axis=1) / \
        np.linalg.norm(k.reshape(500, -1), axis=1)
    assert (plain > 0.02).sum() > 10          # ties do flip
    err, free = model.first_layer_error(key, cfg, toks, pos, k2, v2, 0)
    assert err.max() < 0.01 and free.max() >= 2
    kl, vl = model.first_layer_kv(key, cfg, toks, pos, "float8_e4m3fn")
    low, _ = model.first_layer_error(key, cfg, toks, pos, kl, vl, 0)
    assert np.median(low) > 3 * err.max()


@pytest.fixture(scope="module")
def sound():
    return tiny.run(seconds=1.5)


def test_a_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["checks"]["max_token_rank"]["value"] == 1
    assert sound["checks"]["kv0_rel_err"]["value"] <= 1e-5
    assert list(sound)[-1] == "checks"


def test_the_control_is_not_correct():
    import time
    from lib import harness
    low = check.CONTROL[tiny.CONFIG["dtype"]]
    r = harness.run_cell(tiny.cell(), 3, 1.5, False, time.perf_counter(),
                         kernel_mode="interpret", cfg=tiny.program_config(),
                         control=low)
    assert r["correct"] is True
    # judged by the same function as the program, on the same sample
    assert r["control"]["correct"] is False
    assert r["control"]["checks"]["kv0_rel_err"]["value"] > 1e-5
    assert r["fault_altered_token"]["correct"] is False
    assert r["fault_altered_token"]["checks"]["max_token_rank"]["value"] > 1


def _break_state(engine):
    import jax
    import jax.numpy as jnp
    step = engine._step

    def stale(sparams, caches, *a):
        kept = jax.tree.map(jnp.copy, caches)
        tok, _ = step(sparams, caches, *a)
        return tok, kept
    engine._step = stale


def _alter_token(engine):
    step = engine._step

    def altered(*a):
        tok, caches = step(*a)
        return (tok + 1) % engine.cfg.vocab, caches
    engine._step = altered


def test_a_traced_run_reads_its_trace():
    import time
    from lib import harness
    r = harness.run_cell(tiny.cell(), 4, 1.5, True, time.perf_counter(),
                         kernel_mode="interpret", cfg=tiny.program_config())
    assert r["correct"] is True
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not os.path.exists(harness.TRACE_DIR)


def _drop_half(engine):
    step = engine._step

    def half(*a):
        tok, caches = step(*a)
        return tok.at[tok.shape[0] // 2:].set(0), caches
    engine._step = half


@pytest.mark.parametrize("fault", [_break_state, _alter_token, _drop_half])
def test_a_fault_is_not_correct(fault):
    assert tiny.run(seconds=1.5, fault=fault)["correct"] is False
