"""The serving engine's host spans and the model's named scopes, read back
from a profiler trace and from the compiled programs.

A tiny engine serves two requests under ``jax.profiler``: one whose prompt
is a whole number of packs (its first token is sampled at admission) and
one with a prompt tail fed through the decode ticks.  The trace is read
with ``jax.profiler.ProfileData``: every ``serve.*`` span is there with its
arguments, children nest inside their parents, and the CPU's op events
name the executables.  The compiled step and prefill carry the model's
scopes in their ops' ``op_name`` metadata, and no scope or executable is
named like a kernel the trace reduction searches for by substring.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import DasConfig, LpsaConfig, ModelConfig, TernaryConfig
from repro.models import model as MD
from repro.models.transformer import Runtime
from repro.serve import Request, ServeConfig, ServeEngine

CFG = ModelConfig(
    name="tiny-trace", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
    ternary=TernaryConfig(das=DasConfig(16, 8)),
    lpsa=LpsaConfig(sink=4, window=12, chunk=8),
    dtype="float32", remat=False, scan_layers=False,
)
# uid -> prompt length: 16 is two whole packs (first token at admission),
# 12 is one pack and a 4-token tail (first token from a decode tick)
PROMPTS = {3: 16, 5: 12}
GEN = 3

SPANS = ["serve.tick", "serve.tick.prepare", "serve.tick.dispatch",
         "serve.tick.wait", "serve.tick.deliver", "serve.admit",
         "serve.admit.prefill", "serve.admit.insert", "serve.admit.sample",
         "serve.admit.wait", "serve.poll", "serve.retire", "serve.recover"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    sparams = MD.export_serving(MD.init_params(jax.random.PRNGKey(0), CFG),
                                CFG)
    eng = ServeEngine(CFG, sparams, Runtime(),
                      config=ServeConfig(max_slots=2, max_len=64))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=u, prompt=np.asarray(rng.integers(0, CFG.vocab, p),
                                             np.int32), max_new_tokens=GEN)
            for u, p in PROMPTS.items()]
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        for r in reqs:
            eng.submit(r)
        eng.run_forever(poll=lambda: None)
        eng.recover(lost_devices=0)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)[0]
    spans, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if e.name.startswith("serve."):
                    spans.append((e.name, e.start_ns, e.end_ns, stats))
                if "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
    return eng, spans, modules


@pytest.fixture(scope="module")
def hlo(served):
    """Compiled HLO text of the decode step and of a two-pack prefill."""
    eng = served[0]
    _, args = eng._tick_inputs()
    step = eng._step.lower(eng.sparams, eng.caches, *args).compile()
    prefill = eng._prefill.lower(eng.sparams,
                                 jnp.zeros((1, 16), jnp.int32)).compile()
    return {"step": step.as_text(), "prefill": prefill.as_text()}


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("name", SPANS)
def test_every_span_is_in_the_trace(served, name):
    assert _named(served[1], name), f"no {name} span in the trace"


def test_tick_spans_carry_the_step_number(served):
    eng, spans, _ = served
    steps = sorted(s[3]["step_num"] for s in _named(spans, "serve.tick"))
    assert steps == list(range(eng.stats.decode_steps))
    for child in ("prepare", "dispatch", "wait", "deliver"):
        got = sorted(s[3]["step_num"]
                     for s in _named(spans, f"serve.tick.{child}"))
        assert got == steps, child


def test_admit_spans_carry_the_request(served):
    spans = served[1]
    admits = {s[3]["uid"]: s[3] for s in _named(spans, "serve.admit")}
    assert {u: (a["prompt_len"], a["packs"]) for u, a in admits.items()} \
        == {u: (p, p // CFG.lpsa.chunk) for u, p in PROMPTS.items()}
    for name in ("serve.admit.prefill", "serve.admit.insert",
                 "serve.retire"):
        assert sorted(s[3]["uid"] for s in _named(spans, name)) \
            == sorted(PROMPTS), name
    # only the whole-pack prompt samples its first token at admission
    for name in ("serve.admit.sample", "serve.admit.wait"):
        assert [s[3]["uid"] for s in _named(spans, name)] == [3], name


@pytest.mark.parametrize("child,parent,key", [
    ("serve.tick.prepare", "serve.tick", "step_num"),
    ("serve.tick.dispatch", "serve.tick", "step_num"),
    ("serve.tick.wait", "serve.tick", "step_num"),
    ("serve.tick.deliver", "serve.tick", "step_num"),
    ("serve.admit.prefill", "serve.admit", "uid"),
    ("serve.admit.insert", "serve.admit", "uid"),
    ("serve.admit.sample", "serve.admit", "uid"),
    ("serve.admit.wait", "serve.admit", "uid"),
    ("serve.retire", "serve.tick.deliver", None),
])
def test_children_nest_in_their_parent(served, child, parent, key):
    spans = served[1]
    for _, a, b, st in _named(spans, child):
        assert any(pa <= a and b <= pb
                   and (key is None or pst[key] == st[key])
                   for _, pa, pb, pst in _named(spans, parent)), \
            (child, st)


@pytest.mark.parametrize("module", ["jit__step_fn", "jit_prefill",
                                    "jit_sample_first"])
def test_executables_are_named(served, module):
    assert module in served[2]


@pytest.mark.parametrize("program,scope", [
    ("step", "embed"), ("step", "attn"), ("step", "ffn"),
    ("step", "lm_head"), ("step", "sample"), ("step", "das"),
    ("step", "twd_unpack"), ("step", "kv_write"), ("step", "attn_window"),
    ("prefill", "embed"), ("prefill", "attn"), ("prefill", "ffn"),
    ("prefill", "lm_head"), ("prefill", "das"), ("prefill", "kv_write"),
    ("prefill", "attn_window"),
])
def test_compiled_programs_carry_the_scopes(hlo, program, scope):
    assert any(scope in op.split("/") for op in _op_names(hlo[program])), \
        f"no op of the {program} is under the {scope!r} scope"


@pytest.mark.parametrize("kernel", ["das_ternary_gemm", "sparse_attention"])
@pytest.mark.parametrize("program", ["step", "prefill"])
def test_no_scope_or_executable_is_named_like_a_kernel(hlo, program,
                                                       kernel):
    text = hlo[program]
    module = re.match(r"HloModule (\S+),", text).group(1)
    assert kernel not in module
    assert not [op for op in _op_names(text) if kernel in op]
