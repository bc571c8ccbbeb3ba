"""Serve a configuration through the engine and compare every logit row it
computes with the plain reference's, at full width on the chip.

    python bench/logits_check.py --config mellum2-12b-a2.5b \
        --dtype float32 --prompts 1290 1300 1310 1320 --gen 16 --seed 7

The configuration file's model at the given dtype (every matmul at
``highest``), weights drawn from the seed as the benchmark draws them, the
prompts' tokens from the seed; one engine (``pallas`` kernels, layout
``auto``, a slot per prompt, the file's context) serves every prompt at
once: packed prefill of each prompt's whole packs, tail feed, decode.  Each
decode tick's logits are read back per active slot.  The reference (the
model module's ``logits``) then runs over each prompt and its served
tokens, and so does the control (the reference one precision below).  Per
row: max |program - reference| / max |reference|; the same for the control
against the reference.  The last line of standard output is one JSON
object: both distributions, whether every served token was the reference's
first choice, the engine's MoE routing counters, and the device.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import check, spec, traffic  # noqa: E402


def serve_rows(engine, prompts: list, gen: int):
    """Serve `prompts` (uids 0..) greedily through `engine`; -> (results,
    {(uid, position): the logits (V,) of the tick that read that
    position}), read from each tick's sampler."""
    import jax
    import numpy as np
    from repro.serve import Request
    from repro.serve.engine import FREE
    seen, rows = [], {}
    sample, tick = engine._sampler, engine.step_decode
    vocab = engine.cfg.vocab

    def sampler(logits, keys, temps):
        jax.debug.callback(lambda lg: seen.append(np.asarray(lg)), logits)
        return sample(logits, keys, temps)

    def step():
        at = [(s.req.uid, s.input_pos) if s.state != FREE else None
              for s in engine._slots]
        tick()
        for i, a in enumerate(at):
            if a is not None:
                rows[a] = seen[-1][i, :vocab]
    engine._sampler, engine.step_decode = sampler, step
    for uid, p in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=p, max_new_tokens=gen,
                              temperature=0.0))
    return engine.run(), rows


def compare(model, key, cfg_file: dict, prompts: list, results: dict,
            rows: dict, control: str | None = None) -> dict:
    """Per served row, max |program - reference| / max |reference| (and,
    with `control`, the same for the reference at that precision); whether
    every served token was the reference's first choice."""
    import numpy as np
    pad = -(-max(len(p) + len(results[u].tokens)
                 for u, p in enumerate(prompts)) // 64) * 64
    errs, ctrl, greedy = [], [], True
    for uid, p in enumerate(prompts):
        seq = np.concatenate([p, results[uid].tokens[:-1]])
        ref = model.logits(key, cfg_file, seq, pad)
        lo = model.logits(key, cfg_file, seq, pad, control) if control \
            else None
        greedy &= bool((ref[len(p) - 1:].argmax(-1)
                        == results[uid].tokens).all())
        for (u, pos), lg in rows.items():
            if u == uid:
                scale = np.abs(ref[pos]).max()
                errs.append(float(np.abs(lg - ref[pos]).max() / scale))
                if lo is not None:
                    ctrl.append(float(np.abs(lo[pos] - ref[pos]).max()
                                      / scale))

    def dist(v):
        return {"max": max(v), "q50": float(np.median(v)),
                "q90": float(np.quantile(v, 0.9)), "min": min(v)} \
            if v else None
    return {"rows": len(errs), "program_rel_err": dist(errs),
            "control_rel_err": dist(ctrl), "served_tokens_are_greedy": greedy}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--prompts", type=int, nargs="+", required=True)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    spec.add_program_path()

    import dataclasses

    import jax
    from repro.models import model as MD
    from repro.models.transformer import Runtime
    from repro.serve import ServeConfig, ServeEngine

    jax.config.update("jax_default_matmul_precision", "highest")
    t0 = time.perf_counter()
    cfg_file = dict(spec.config_file(args.config), dtype=args.dtype)
    model = spec.model_module(cfg_file)
    cfg = dataclasses.replace(
        model.program_config(spec.config_file(args.config)),
        dtype=args.dtype)
    key = spec.seed_key(args.seed)
    abstract = jax.eval_shape(
        lambda k: MD.export_serving(MD.init_params(k, cfg), cfg),
        jax.random.PRNGKey(0))
    engine = ServeEngine(
        cfg, model.bind(key, cfg_file, abstract), Runtime(kernel_mode="pallas"),
        config=ServeConfig(kernel_mode="pallas", layout="auto",
                           max_slots=len(args.prompts),
                           max_len=spec.max_len(cfg_file),
                           seed=args.seed % 2 ** 31))
    prompts = [traffic.prompt_tokens(args.seed, i, n, cfg.vocab)
               for i, n in enumerate(args.prompts)]
    results, rows = serve_rows(engine, prompts, args.gen)
    dev = jax.devices()[0]
    st = engine.stats
    counters = {"moe_max_load": st.moe_max_load,
                "moe_experts_hit_per_layer_tick": st.moe_experts_hit / max(
                    1, st.decode_steps * cfg.n_layers),
                "decode_steps": st.decode_steps,
                "kernel_fallbacks": engine.kernel_fallback_deltas()}
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    del engine              # its jitted steps hold it in a cycle
    gc.collect()
    print(f"[check] served in {time.perf_counter() - t0:.1f}s, "
          f"{len(rows)} rows", file=sys.stderr, flush=True)
    got = compare(model, key, cfg_file, prompts, results, rows,
                  check.CONTROL[args.dtype])
    print(json.dumps({
        "config": args.config, "dtype": args.dtype, "seed": args.seed,
        "prompts": args.prompts, "gen": args.gen, **got,
        "control": check.CONTROL[args.dtype], "counters": counters,
        "seconds": time.perf_counter() - t0,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "memory_peak_bytes": peak}}), flush=True)


if __name__ == "__main__":
    main()
