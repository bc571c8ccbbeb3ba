"""Compile a cell's serving programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python bench/aot.py bitnet-3b.chat.c4 [...]

For each named cell this lowers and compiles, for one chip of a described
``v5e:2x2`` topology, the two programs the cell's window drives at their
timed shapes: the engine's decode step at the cell's slot count (the
engine's own ``_step_fn``) and the batch-1 packed prefill at the longest
pack-aligned prefix the cell's traffic can produce.  It prints each
program's ``memory_analysis()``.  Nothing runs: a compile that passes here
says the chip's compiler accepts the programs and how much memory they
declare, not how fast they are.
"""

from __future__ import annotations

import functools
import os
import sys
import time
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import spec as S  # noqa: E402


def compile_cell(cell: dict) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models import model as MD
    from repro.models.transformer import Runtime
    from repro.serve.engine import ServeEngine
    from repro.serve.sampler import make_sampler

    cfg = S.model_module(cell["config"]).program_config(cell["config"])
    context = S.max_len(cell["config"])
    mix = cell["traffic"]
    slots = mix["clients"]
    rt = Runtime(kernel_mode="pallas")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    sp = shaped(jax.eval_shape(
        lambda k: MD.export_serving(MD.init_params(k, cfg), cfg),
        jax.random.PRNGKey(0)))
    caches = shaped(jax.eval_shape(
        lambda: MD.init_caches(None, cfg, slots, context, rt,
                               jnp.dtype(cfg.dtype))))
    ns = types.SimpleNamespace(cfg=cfg, rt=rt, _uses_embeds=False,
                               _base_key=jax.random.PRNGKey(0),
                               _sampler=make_sampler(0))
    ns._fold_key = functools.partial(ServeEngine._fold_key, ns)
    step = jax.jit(functools.partial(ServeEngine._step_fn, ns),
                   donate_argnums=(1,))

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (slots,), dtype, sharding=one)

    args = (sp, caches, None, vec(jnp.int32), vec(jnp.int32),
            vec(jnp.float32), vec(jnp.int32), vec(jnp.int32), vec(bool),
            vec(bool), vec(jnp.float32, slots, cfg.d_model))
    t0 = time.perf_counter()
    c = step.lower(*args).compile()
    print(f"[aot] {cell['name']}: decode step at {slots} slots compiled in "
          f"{time.perf_counter() - t0:.1f}s: {c.memory_analysis()}",
          flush=True)

    longest = max(S.prefill_lengths(mix, cfg.lpsa.chunk), default=0)
    if longest:
        pre = jax.jit(lambda s, x: MD.prefill(s, cfg, x, rt,
                                              max_len=context))
        t0 = time.perf_counter()
        c = pre.lower(sp, vec(jnp.int32, 1, longest)).compile()
        print(f"[aot] {cell['name']}: prefill of {longest} tokens compiled "
              f"in {time.perf_counter() - t0:.1f}s: {c.memory_analysis()}",
              flush=True)
    else:
        print(f"[aot] {cell['name']}: the traffic has no packed prefill",
              flush=True)


def main(argv: list[str]) -> None:
    S.add_program_path()
    bench = S.load_benchmark()
    for name in argv or [w["name"] for w in bench["workloads"]]:
        compile_cell(S.resolve_cell(bench, name))


if __name__ == "__main__":
    main(sys.argv[1:])
