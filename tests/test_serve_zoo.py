"""Serving the model zoo: batch invariance for every slot-state family.

A request's tokens must be bitwise independent of its batch-mates for
EVERY layout in the engine's slot-state union — not just the attention
KV caches test_serve_engine.py covers, but mamba chunk-replay state
(hybrid), rwkv wkv/shift state, the gla state matrix, and MoE routing.
MoE is the sharpest case: the training-time expert capacity
``t * top_k / E * cf`` would let a momentarily hot expert drop whichever
request happened to share the decode tick, so the configs here force a
production-tight ``capacity_factor=1.0`` and rely on the engine's
no-drop serving capacity (models/moe.serve_capacity).

Same joint-vs-solo assertion style as test_serve_engine.py: replay a
staggered-admission trace, then each request alone, and require exact
token equality.
"""
import dataclasses
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import model as MD
from repro.models.transformer import Runtime
from repro.serve import Request, ServeConfig, ServeEngine

pytestmark = pytest.mark.slow

# one config per slot-state family (attn-only is test_serve_engine.py's job)
FAMILIES = ["zamba2-2.7b",        # mamba/attn hybrid, shared attention
            "rwkv6-3b",           # pure rwkv recurrent
            "gla-1.3b",           # pure gla recurrent
            "qwen3-moe-30b-a3b",  # MoE FFN over LPSA attention
            "mellum2-12b-a2.5b"]  # MoE FFN over sliding rings + full caches


@functools.lru_cache(maxsize=None)
def _setup(arch):
    cfg = reduced(get_config(arch))
    if cfg.moe is not None:
        # reduced() relaxes capacity to "no drops anywhere"; restore a
        # production-tight factor so this test would FAIL if decode ever
        # fell back to the capacity-factor formula
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.0))
    p = MD.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, MD.export_serving(p, cfg)


def _trace(cfg):
    # prompt lengths straddle the ssm/lpsa chunk (16 under reduced()): the
    # hybrid config exercises prefill state handoff at a non-boundary AND
    # decode-side chunk folds; generation crosses a fold for every slot
    rng = np.random.default_rng(0)
    spec = [(18, 8, 0, 0.0), (23, 6, 2, 0.9), (10, 7, 4, 0.7)]
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, p).astype(np.int32),
                    max_new_tokens=g, arrival=a, temperature=tp)
            for i, (p, g, a, tp) in enumerate(spec)]


@pytest.mark.parametrize("arch", FAMILIES)
def test_zoo_batch_invariance(arch):
    cfg, sparams = _setup(arch)
    rt = Runtime()
    sc = ServeConfig(max_slots=2, max_len=64)
    trace = _trace(cfg)
    eng = ServeEngine(cfg, sparams, rt, sc)
    for r in trace:
        eng.submit(r)
    joint = eng.run()
    assert set(joint) == {r.uid for r in trace}
    for r in trace:
        solo_eng = ServeEngine(cfg, sparams, rt, sc)
        solo_eng.submit(r)
        solo = solo_eng.run()[r.uid]
        np.testing.assert_array_equal(solo.tokens, joint[r.uid].tokens)
        assert len(joint[r.uid].tokens) == r.max_new_tokens


def test_moe_expert_capacity_admission_control():
    """moe_expert_capacity throttles ADMISSION, never tokens: with the
    bound at 1 the engine serializes requests (each admitted into an empty
    batch), defers the rest, and still produces the exact tokens of the
    unbounded run."""
    cfg, sparams = _setup("qwen3-moe-30b-a3b")
    rt = Runtime()
    trace = _trace(cfg)

    free = ServeEngine(cfg, sparams, rt, ServeConfig(max_slots=2, max_len=64))
    for r in trace:
        free.submit(r)
    unbounded = free.run()
    assert free.stats.moe_capacity_deferrals == 0

    capped = ServeEngine(cfg, sparams, rt,
                         ServeConfig(max_slots=2, max_len=64,
                                     moe_expert_capacity=1))
    for r in trace:
        capped.submit(r)
    serial = capped.run()
    assert capped.stats.moe_capacity_deferrals > 0
    for uid, res in serial.items():
        assert res.admitted_with_active == 0      # never co-resident
        np.testing.assert_array_equal(res.tokens, unbounded[uid].tokens)


def test_layout_summary_matches_layer_kinds():
    cfg, sparams = _setup("zamba2-2.7b")
    eng = ServeEngine(cfg, sparams, Runtime(),
                      ServeConfig(max_slots=2, max_len=64))
    rows = eng.layout_summary()
    assert [r["kind"] for r in rows] == list(cfg.layer_kinds())
    assert all(r["layout"] == "mamba" for r in rows if r["kind"] == "mamba")
    # shared-attn layers ride the LPSA ring under serve_sparse
    assert all(r["layout"] == "ring" for r in rows if r["kind"] == "attn")
