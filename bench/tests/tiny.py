"""A cell small enough for the CPU: the benchmark's own code paths, with a
cut-down bitnet config (the program's ``reduced``) and a short closed loop.

Only the tests use it; the command never runs at this size."""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

CONFIG = {
    "name": "bitnet-tiny", "model": "bitnet", "program_config": "bitnet-3b",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 500, "tie_word_embeddings": False,
    "max_position_embeddings": 128, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06,
    "dtype": "float32", "lpsa": {"sink": 8, "window": 24, "pack": 16},
    "das": {"block": 32, "keep": 16},
}

TRAFFIC = {
    "loop": "closed", "clients": 3, "stagger_ticks": 3,
    "open_after_finished": 3,
    "prompt_tokens": {"law": "lognormal", "median": 24, "sigma": 0.6,
                      "min": 4, "max": 40},
    "output_tokens": {"law": "lognormal", "median": 10, "sigma": 0.5,
                      "min": 4, "max": 24},
    "greedy": True, "trace_seconds": 1, "check": {"requests": 3,
                                                  "min_tokens": 20},
}


def program_config():
    import dataclasses
    from repro.configs import get_config, reduced
    return dataclasses.replace(reduced(get_config("bitnet-3b"), n_layers=2,
                                       vocab=500), tie_embeddings=False)


LIMITS = {"max_token_rank": 1, "kv0_rel_err": 1e-5, "wrong_length_requests": 0}


def cell() -> dict:
    # the tiny config is f32 throughout, so a sound run matches the
    # reference to f32 rounding; the control (bf16) and the faults do not
    return {"name": "bitnet-tiny.chat", "chips": 1, "config": CONFIG,
            "traffic": TRAFFIC,
            "limits": LIMITS,
            "end_to_end": [{"name": n} for n in
                           ("out_tok_s", "itl_p99_ms", "ttft_p50_s",
                            "setup_s")],
            "per_layer": []}


def run(seconds: float = 2.0, fault=None, seed: int = 3) -> dict:
    import time
    from lib import harness
    return harness.run_cell(cell(), seed, seconds, False, time.perf_counter(),
                            kernel_mode="interpret", cfg=program_config(),
                            fault=fault)
