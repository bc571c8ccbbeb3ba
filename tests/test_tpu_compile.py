"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU v5e.

Interpret mode cannot see what the chip's compiler refuses: a block that
breaks the (8, 128) tiling rule, a kernel that needs more VMEM than its
scoped limit, a layout Mosaic cannot lower.  These tests compile the kernels
at bitnet-3b's published widths (d_model 3200, d_ff 8640, head_dim 100, an
LPSA ring of 1024) for a *described* v5e chip — no chip is needed — and
assert that the Pallas call survives into the compiled program, under the
name a device trace gives it.

The Mellum MoE configuration's whole decode step (4 slots) and one packed
prefill compile the same way at its published widths: they must fit the
chip's memory and keep the Pallas attention kernel for GQA 32:4 at head dim
128 over both its sliding rings and its full caches.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library at a time, so a module-level
call would make the test workers collect different tests.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.das import das_compact
from repro.kernels.das_gemm import das_ternary_gemm
from repro.kernels.sparse_attn import sparse_attention
from repro.kernels.ternary_gemm import ternary_gemm

D_MODEL, D_FF, HEADS, HEAD_DIM, RING = 3200, 8640, 32, 100, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the program"
    return text


@pytest.mark.parametrize("m,k,n", [
    (4, D_MODEL, D_FF),      # decode: gate/up projection
    (256, D_FF, D_MODEL),    # one LPSA pack of prefill: down projection
])
def test_das_ternary_gemm_compiles(one_chip, m, k, n):
    kc = k // 2              # DAS keeps 16 of every 32 lanes
    _compile(lambda v, i, p: das_ternary_gemm(v, i, p, 0.5, keep=16,
                                              block=32),
             one_chip, ((m, kc), jnp.bfloat16), ((m, kc), jnp.int32),
             ((k // 5, n), jnp.uint8))


@pytest.mark.parametrize("m,k,n,dtype", [
    (4, D_FF, D_MODEL, jnp.bfloat16),    # DAS-off route, down projection
    (4, D_MODEL, D_FF, jnp.int8),        # int8 activations
])
def test_ternary_gemm_compiles(one_chip, m, k, n, dtype):
    _compile(lambda x, p: ternary_gemm(x, p, 0.5), one_chip,
             ((m, k), dtype), ((k // 5, n), jnp.uint8))


def test_lpsa_decode_attention_compiles(one_chip):
    b = 4

    def attend(q, k, v, qp, kp):
        return jax.vmap(lambda *a: sparse_attention(
            *a, sink=128, window=RING - 128))(q, k, v, qp, kp)

    _compile(attend, one_chip,
             ((b, HEADS, 1, HEAD_DIM), jnp.bfloat16),
             ((b, HEADS, RING, HEAD_DIM), jnp.bfloat16),
             ((b, HEADS, RING, HEAD_DIM), jnp.bfloat16),
             ((b, 1), jnp.int32), ((b, RING), jnp.int32))


def _instructions(text):
    """The compiled program's instructions without their metadata: the text
    a device trace names each operation by."""
    return [re.sub(r", metadata=\{.*$", "", line).strip().removeprefix(
        "ROOT ") for line in text.splitlines() if " = " in line]


@pytest.mark.parametrize("kernel,name", [("das_ternary_gemm", "das_gemm"),
                                         ("sparse_attention",
                                          "vmap_sparse_attn_")])
def test_kernel_instruction_names(one_chip, kernel, name):
    """A kernel's instruction carries its Pallas name, and no instruction
    holds the kernel's function name: an op that reads the kernel's output
    lists it by name among its operands, and the benchmark's trace reduction
    finds kernels by that substring (bench/lib/kernels.json)."""
    if kernel == "das_ternary_gemm":
        text = _compile(lambda v, i, p: 2.0 * das_ternary_gemm(
            v, i, p, 0.5, keep=16, block=32), one_chip,
            ((4, D_MODEL // 2), jnp.bfloat16), ((4, D_MODEL // 2), jnp.int32),
            ((D_MODEL // 5, D_FF), jnp.uint8))
    else:
        b = 4
        text = _compile(lambda *a: 2.0 * jax.vmap(lambda *x: sparse_attention(
            *x, sink=128, window=RING - 128))(*a), one_chip,
            ((b, HEADS, 1, HEAD_DIM), jnp.bfloat16),
            ((b, HEADS, RING, HEAD_DIM), jnp.bfloat16),
            ((b, HEADS, RING, HEAD_DIM), jnp.bfloat16),
            ((b, 1), jnp.int32), ((b, RING), jnp.int32))
    ins = _instructions(text)
    calls = [i for i in ins if 'custom_call_target="tpu_custom_call"' in i]
    assert len(calls) == 1 and calls[0].startswith(f"%{name}.")
    assert any(f"%{name}." in i.split(" = ", 1)[1] for i in ins), \
        "nothing reads the kernel's output"
    assert not [i for i in ins if kernel in i]


@pytest.mark.parametrize("m,k", [
    (256, D_FF),     # one LPSA pack of prefill: down projection's input
    (4, D_MODEL),    # decode: attention / gate-up input
])
def test_das_compact_has_no_gather_or_sort(one_chip, m, k):
    """DAS compaction compiles to compares, reductions and selects: no
    gather and no sort instruction (the op_name metadata may still name
    its jnp calls)."""
    arg = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda x: das_compact(x, block_size=32, keep=16)) \
        .lower(arg).compile().as_text()
    # an instruction reads "<shape> <opcode>(<operands>), <attributes>"
    ops = [re.search(r"(?:^|\s)([a-z][\w-]*)\(", i.split(" = ", 1)[1])
           for i in _instructions(text)]
    opcodes = {o.group(1) for o in ops if o}
    assert "fusion" in opcodes or "reduce" in opcodes
    assert not opcodes & {"gather", "sort"}, sorted(opcodes)


@pytest.fixture(scope="module")
def mellum_programs(one_chip):
    """Compiled decode step (4 slots, context 2048) and one-pack prefill of
    the Mellum configuration at full width, from shapes alone."""
    import functools
    import types

    from repro.configs import get_config
    from repro.models import model as MD
    from repro.models.transformer import Runtime
    from repro.serve.engine import ServeEngine
    from repro.serve.sampler import make_sampler

    cfg, rt, slots, context = get_config("mellum2-12b-a2.5b"), \
        Runtime(kernel_mode="pallas"), 4, 2048

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    sp = shaped(jax.eval_shape(
        lambda k: MD.export_serving(MD.init_params(k, cfg), cfg),
        jax.random.PRNGKey(0)))
    caches = shaped(jax.eval_shape(lambda: MD.init_caches(
        None, cfg, slots, context, rt, jnp.bfloat16)))
    ns = types.SimpleNamespace(cfg=cfg, rt=rt, _uses_embeds=False,
                               _base_key=jax.random.PRNGKey(0),
                               _sampler=make_sampler(0))
    ns._fold_key = functools.partial(ServeEngine._fold_key, ns)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (slots,), dtype,
                                    sharding=one_chip)
    step = jax.jit(functools.partial(ServeEngine._step_fn, ns),
                   donate_argnums=(1,)).lower(
        sp, caches, None, vec(jnp.int32), vec(jnp.int32), vec(jnp.float32),
        vec(jnp.int32), vec(jnp.int32), vec(bool), vec(bool),
        vec(jnp.float32, slots, cfg.d_model)).compile()
    prefill = jax.jit(lambda p, x: MD.prefill(
        p, cfg, x, rt, max_len=context)).lower(
        sp, vec(jnp.int32, 1, cfg.lpsa.chunk)).compile()
    return {"step": step, "prefill": prefill}


def test_mellum_step_runs_the_attention_kernel_on_both_kinds(
        mellum_programs):
    """The decode step of every layer of the scanned pattern (3 sliding
    rings of 1024, 1 full cache of 2048) calls the Pallas attention kernel
    with GQA's 4 kv heads, and the benchmark's trace reduction, which finds
    the kernel by the operand types a trace event's name lists
    (bench/lib/kernels.json, read by attn_roofline and moe_attn_roofline),
    finds each call."""
    import json
    import os
    import re
    path = os.path.join(os.path.dirname(__file__), "..", "bench", "lib",
                        "kernels.json")
    pats = json.load(open(path))["ops"]["sparse_attention"]
    ins = _instructions(mellum_programs["step"].as_text())
    calls = [i for i in ins if 'custom_call_target="tpu_custom_call"' in i]
    assert len(calls) == 4 and all(
        i.startswith("%vmap_sparse_attn_.") for i in calls), calls
    lens = []
    for i in calls:
        types = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}",
                           i.split("operand_layout_constraints={", 1)[1])
        # the event name as a trace lists it: each operand with its type
        event = (i.split(" custom-call(", 1)[0] + " custom-call(" + ", ".join(
            f"{t} %x{j}" for j, t in enumerate(types))
            + '), custom_call_target="tpu_custom_call"')
        assert any(re.search(p, event) for p in pats), event
        lens.append(int(re.match(r"bf16\[4,4,(\d+),128\]", types[1])[1]))
    assert types[0].startswith("bf16[4,32,1,128]")
    assert sorted(lens) == [1024, 1024, 1024, 2048], lens


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_mellum_fits_one_chip(mellum_programs, program):
    m = mellum_programs[program].memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used < 10e9, used        # of the chip's 16 GB
    assert m.argument_size_in_bytes > 3e9   # every expert is held


def test_mellum_decode_keeps_the_attention_kernel(mellum_programs):
    """Both kinds of attention (the 1024-slot sliding rings and the
    2048-position full caches) decode through the Pallas kernel."""
    calls = [i for i in _instructions(mellum_programs["step"].as_text())
             if 'custom_call_target="tpu_custom_call"' in i]
    assert len({re.search(r"bf16\[4,4,(\d+),128\]", c).group(1)
                for c in calls}) == 2, calls
