"""The Mellum configuration against its plain reference, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_mellum.py

A tiny Mellum-shaped model (one whole period of three sliding layers and a
full one, a window shorter than its prompts so the rings wrap, YaRN on the
full layer, 8 experts at top 2 with the training capacity factor 1.25)
served through the engine in f32: its prefill and decode logits against
``bench/models/mellum.py``'s, then as a cell of the harness, where a sound
run is ``correct`` and the bf16 control and a fault are not.  Also the
configuration file against the program, and the operation counts that
``metrics/moe_step_mfu.py`` reads.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402  (puts bench/ and src/ on the path)
from lib import check, spec  # noqa: E402

MODEL = spec.model_module({"model": "mellum"})
FILE = json.load(open(os.path.join(tiny.BENCH, "configs",
                                   "mellum2-12b-a2.5b.json")))
WINDOW = 16

CONFIG = dict(
    FILE, name="mellum-tiny", hidden_size=64, intermediate_size=128,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, vocab_size=500, max_position_embeddings=128,
    moe_intermediate_size=128, num_experts=8, num_experts_per_tok=2,
    sliding_window=WINDOW, dtype="float32", lpsa={"pack": 16},
    layer_types=FILE["layer_types"][:4], mlp_layer_types=["sparse"] * 4)

# 4 clients, as the chip cell: rings and full caches of 4 slots through
# prefill, tail feed, insert, scrub (every retirement) and decode
TRAFFIC = dict(tiny.TRAFFIC, clients=4, open_after_finished=4,
               check={"requests": 3, "min_tokens": 20})
LIMITS = {"max_token_rank": 1, "kv0_rel_err": 1e-5,
          "wrong_length_requests": 0}


def program_config():
    import dataclasses
    from repro.configs import get_config, reduced
    from repro.configs.base import MoeConfig
    cfg = reduced(get_config("mellum2-12b-a2.5b"), n_layers=4, vocab=500)
    return dataclasses.replace(
        cfg, window=WINDOW, scan_layers=True,
        moe=MoeConfig(n_experts=8, top_k=2, d_expert=128, n_shared=0,
                      capacity_factor=1.25))


def cell() -> dict:
    return {"name": "mellum-tiny.chat", "chips": 1, "config": CONFIG,
            "traffic": TRAFFIC, "limits": LIMITS,
            "end_to_end": [{"name": n} for n in ("out_tok_s", "setup_s")],
            "per_layer": []}


def run(seed=3, control=None, fault=None):
    from lib import harness
    return harness.run_cell(cell(), seed, 1.5, False, time.perf_counter(),
                            kernel_mode="interpret", cfg=program_config(),
                            control=control, fault=fault)


# -- the configuration file ------------------------------------------------

def test_the_file_is_what_the_program_runs():
    cfg = MODEL.program_config(FILE)
    assert (cfg.n_layers, cfg.d_model, cfg.moe.n_experts) == (28, 2304, 64)
    for key, value in (("sliding_window", 512), ("num_experts_per_tok", 4),
                       ("program_config", "no-such-model")):
        with pytest.raises(SystemExit):
            MODEL.program_config(dict(FILE, **{key: value}))
    rope = json.loads(json.dumps(FILE["rope_parameters"]))
    rope["full_attention"]["factor"] = 8
    with pytest.raises(SystemExit):
        MODEL.program_config(dict(FILE, rope_parameters=rope))


def test_token_counts():
    """Operations of one token at Mellum's shapes, counted by hand: per
    layer q/k/v/o at half their input lanes, the router, 8 experts at half
    their input lanes, attention over the layer's keys; the head once."""
    proj = 2 * 1152 * 4096 + 2 * 2 * 1152 * 512 + 2 * 2048 * 2304
    router = 2 * 2304 * 64
    experts = 8 * (2 * 2 * 1152 * 896 + 2 * 448 * 2304)
    head = 2 * 2304 * 98304
    att = 4 * 32 * 128
    assert MODEL.layer_flops(FILE, 99, False) == proj + router + experts \
        + att * 100
    assert MODEL.layer_flops(FILE, 1999, False) == proj + router + experts \
        + att * 1024
    assert MODEL.layer_flops(FILE, 1999, True) == proj + router + experts \
        + att * 2000
    assert MODEL.token_flops(FILE, 1999) == 28 * (proj + router + experts) \
        + 21 * att * 1024 + 7 * att * 2000 + head


def test_yarn_frequencies():
    rp = FILE["rope_parameters"]["full_attention"]
    f = MODEL.yarn_inv_freq(128, 5e5, rp)
    base = 5e5 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(f[:19], base[:19])
    np.testing.assert_allclose(f[35:], base[35:] / 16)
    mid = (np.arange(19, 35) - 18) / 17
    np.testing.assert_allclose(f[19:35], base[19:35] * (1 - mid)
                               + base[19:35] / 16 * mid)


# -- the engine against the reference -----------------------------------------

def test_prefill_and_decode_match_the_reference():
    """Two prompts served together through the engine, every tick's logits
    against the reference's (``logits_check.py``, as on the chip)."""
    import jax
    import logits_check
    from repro.models import model as MD
    from repro.models.transformer import Runtime
    from repro.serve import ServeConfig, ServeEngine
    cfg = program_config()
    key = spec.seed_key(5)
    abstract = jax.eval_shape(
        lambda k: MD.export_serving(MD.init_params(k, cfg), cfg),
        jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, MODEL.bind(key, CONFIG, abstract),
                      Runtime(kernel_mode="interpret"),
                      config=ServeConfig(kernel_mode="interpret",
                                         max_slots=2, max_len=128))
    rng = np.random.default_rng(0)
    # 37 = two packs and a tail of 5, 22 = one pack and a tail of 6: both
    # longer than the window, so every sliding layer's ring has wrapped
    prompts = [rng.integers(0, 500, n).astype(np.int32) for n in (37, 22)]
    out, rows = logits_check.serve_rows(eng, prompts, 12)
    assert sorted(rows) == [(u, p) for u, n in ((0, 37), (1, 22))
                            for p in range(n // 16 * 16, n + 11)]
    got = logits_check.compare(MODEL, key, CONFIG, prompts, out, rows,
                               check.CONTROL["float32"])
    assert got["rows"] == len(rows) and got["served_tokens_are_greedy"]
    # f32 on both sides, only the order of sums differs (6e-7 measured);
    # the bf16 control and a wrong window, RoPE or top-k read 1e-2 or more
    assert got["program_rel_err"]["max"] < 1e-4, got
    assert got["control_rel_err"]["min"] > 1e-4, got
    assert eng.stats.moe_max_load >= 1


# -- as a cell of the harness ----------------------------------------------

@pytest.fixture(scope="module")
def calibrated():
    return run(control=check.CONTROL[CONFIG["dtype"]])


def test_a_sound_run_is_correct_and_the_control_is_not(calibrated):
    r = calibrated
    assert r["correct"] is True
    assert r["checks"]["max_token_rank"]["value"] == 1
    assert r["checks"]["kv0_rel_err"]["value"] <= 1e-5
    assert r["control"]["correct"] is False
    assert r["control"]["checks"]["kv0_rel_err"]["value"] > 1e-5
    assert r["fault_altered_token"]["correct"] is False


def _stale_state(engine):
    import jax
    import jax.numpy as jnp
    step = engine._step

    def stale(sparams, caches, *a):
        kept = jax.tree.map(jnp.copy, caches)
        tok, _, moe = step(sparams, caches, *a)
        return tok, kept, moe
    engine._step = stale


def test_a_fault_is_not_correct():
    assert run(fault=_stale_state)["correct"] is False


# -- moe_step_mfu ---------------------------------------------------------------

def _mfu_ctx(cfg):
    import types
    from lib import trace as TR
    # window [0, 2] s; ticks 0 and 1, one slot active in each at positions
    # 1500 and 1501; one admission of a 600-token prompt (512 prefilled)
    tr = TR.Trace([[("%fusion.1 = f32[8] fusion(...)", 0.5, 1.0)]], [[]],
                  [("bench.traced", 0.0, 2.0)], (0.0, 2.0))
    loop = types.SimpleNamespace(
        log={4: {"prompt_len": 600}},
        active_positions=lambda tick: [1500 + tick])
    return {"trace": tr, "loop": loop, "cfg": cfg, "uids": range(4, 5),
            "ticks": (0, 2), "peaks": {"bf16_flops": 197e12}}


def test_moe_step_mfu_reads_every_token_processed():
    from lib import harness
    read = harness.load_reader("moe_step_mfu")
    flops = (MODEL.token_flops(FILE, 1500) + MODEL.token_flops(FILE, 1501)
             + sum(MODEL.token_flops(FILE, p) for p in range(512)))
    assert read(_mfu_ctx(FILE)) == pytest.approx(100 * flops / 2.0 / 197e12)
    # a model module without the counts (bitnet's) reads nothing
    bitnet = json.load(open(os.path.join(tiny.BENCH, "configs",
                                         "bitnet-3b.json")))
    assert read(_mfu_ctx(bitnet)) is None


# -- moe_attn_roofline and moe_step_hbm_share --------------------------------

def test_attention_work_and_step_bytes():
    kv_row = 2 * 2 * 4 * 128            # bf16 K and V of one key, 4 kv heads
    q_io = 2 * 2 * 32 * 128             # bf16 q in and out, 32 heads
    got = MODEL.attention_work(FILE, [99, 1999])
    live = 21 * (100 + 1024) + 7 * (100 + 2000)
    assert got == {"flops": 4 * 32 * 128 * live,
                   "bytes": kv_row * live + 28 * 2 * q_io}
    # one token reaches its 8 experts; four reach 64 (1 - (56/64)^4)
    assert MODEL.experts_reached(FILE, 1) == pytest.approx(8)
    assert MODEL.experts_reached(FILE, 4) == pytest.approx(
        64 * (1 - (7 / 8) ** 4))
    attn = (2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304) / 5 + 4 * 4
    expert = 3 * 2304 * 896 / 5 + 3 * 4
    layer = attn + 2 * 2304 * 64 + 2 * 2 * 2304 + 8 * expert
    head = 2 * 98304 * 2304 + 2 * 2304
    assert MODEL.step_bytes(FILE, [1999]) == pytest.approx(
        28 * layer + kv_row * (21 * 1024 + 7 * 2000) + head)


def _attn_ctx(cfg):
    ctx = _mfu_ctx(cfg)
    from lib import costs, trace as TR
    op = ("%custom-call.3 = bf16[4,32,1,128]{3,2,1,0} custom-call("
          "bf16[4,32,1,128]{3,2,1,0} %q, bf16[4,4,1024,128]{3,2,1,0} %k), "
          'custom_call_target="tpu_custom_call"')
    ctx["trace"] = TR.Trace([[(op, 0.5, 0.6), (op, 1.0, 1.1)]], [[]],
                            [("bench.traced", 0.0, 2.0)], (0.0, 2.0))
    ctx.update(patterns=TR.patterns(), costs=costs,
               peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    return ctx


def test_moe_attn_roofline_reads_the_kernel_against_its_least_time():
    from lib import harness
    read = harness.load_reader("moe_attn_roofline")
    least = sum(MODEL.attention_work(FILE, [p])["bytes"]
                for p in (1500, 1501)) / 819e9
    assert read(_attn_ctx(FILE)) == pytest.approx(100 * least / 0.2)
    bitnet = json.load(open(os.path.join(tiny.BENCH, "configs",
                                         "bitnet-3b.json")))
    assert read(_attn_ctx(bitnet)) is None


def test_moe_step_hbm_share_reads_every_tick():
    from lib import harness
    read = harness.load_reader("moe_step_hbm_share")
    need = MODEL.step_bytes(FILE, [1500]) + MODEL.step_bytes(FILE, [1501])
    assert read(_attn_ctx(FILE)) == pytest.approx(100 * need / 2.0 / 819e9)
    bitnet = json.load(open(os.path.join(tiny.BENCH, "configs",
                                         "bitnet-3b.json")))
    assert read(_attn_ctx(bitnet)) is None
