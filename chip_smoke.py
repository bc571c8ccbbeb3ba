"""Chip smoke test: serve bitnet-3b at full width on one TPU through Pallas.

    python chip_smoke.py               # one chip: pallas engine vs ref engine
    python chip_smoke.py --four-chips  # Topology(tp=4) vs one-device sharded

The one-chip run builds the serving engine through ``launch/serve.py``'s
``build_engine`` at bitnet-3b's published widths (random weights from a
seed), serves a few greedy requests whose prompts are longer than one LPSA
pack, so both the packed prefill and the token-by-token tail feed run, with
``kernel_mode="pallas"``, then serves the same requests with
``kernel_mode="ref"`` on the same chip.  It fails unless the logits agree
within LOGIT_TOL, the first tokens agree wherever the reference's choice is
clear, and no layer fell back to the jnp reference.

``--four-chips`` runs only the tensor-parallel path: the same requests under
``Topology(tp=4)`` against the one-device ``sharded``-mode engine.

Lines before the last are informative, not claims.  The last line is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Without a TPU, or without the repo's ``src/`` next to this file, the script
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ARCH = "bitnet-3b"
SEED = 0
SLOTS = 4
PROMPT_LEN = 300     # one 256-token LPSA pack + a 44-token tail fed by decode
GEN = 6
# Logit agreement between two paths, as a share of the reference logits'
# spread (max - min over the vocabulary, per request).  Both paths compute
# the same bf16 model and the ternary products are exact in either (bf16
# activations times {-1, 0, +1}): at the end of the packed prefill the
# pallas and ref logits agreed exactly on a v5e.  Decode differs: the
# Pallas LPSA kernel and XLA's chunked flash attention sum in different
# orders and round a few outputs in 10^4 one bf16 ulp apart (check_kernels
# bounds that per kernel), and DAS's top-16-of-32 selection turns such a
# rounding at a near-tie into a whole swapped activation.  Fed back through
# the caches over the 44 tail tokens and 26 layers, that moved the
# prompt-end logits by 8-11% of the spread on a v5e (tp=4's all-reduce
# reorders sums the same way).  0.25 covers that with room; a wrong tile,
# slab or head is caught by check_kernels' tight per-kernel bounds and by
# the prefill logits, which agree exactly.
LOGIT_TOL = 0.25
GEMM_TOL = 1e-5
ATTN_TOL = 2.0 ** -7


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 path and the one-device "
                         "sharded engine it is compared with")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform!r}")
    need = 4 if args.four_chips else 1
    if len(jax.devices()) < need:
        fail(f"needs {need} chips, JAX sees {len(jax.devices())}")

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"the repo's src/repro is not next to this script ({src})")
    sys.path.insert(0, src)
    from repro.launch.serve import enable_compile_cache

    print(f"[smoke] device {dev.device_kind} x{len(jax.devices())}, "
          f"compile cache {enable_compile_cache()}", flush=True)
    (run_four_chips if args.four_chips else run_one_chip)()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def prompts(cfg):
    import numpy as np
    rng = np.random.default_rng(SEED)
    return rng.integers(0, cfg.vocab, (SLOTS, PROMPT_LEN)).astype(np.int32)


def prompt_logits(eng, toks):
    """(pack-prefill logits, prompt-end logits), each (B, V) f32, computed
    with the engine's own weights and kernel mode through the model API:
    the packed prefill of the pack-aligned prefix, then one decode step per
    tail token — the path the engine takes before its first sample."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as MD

    cfg, rt, max_len = eng.cfg, eng.rt, eng.max_len
    prefix = PROMPT_LEN // cfg.lpsa.chunk * cfg.lpsa.chunk

    @jax.jit
    def run(sp, x):
        lg_pack, caches = MD.prefill(sp, cfg, x[:, :prefix], rt,
                                     max_len=max_len)

        def feed(c, step):
            tok, t = step
            lg, c = MD.decode_step(sp, cfg, c, tok, t, rt)
            return c, lg

        _, lgs = jax.lax.scan(feed, caches, (x[:, prefix:].T,
                                             jnp.arange(prefix, PROMPT_LEN)))
        return lg_pack, lgs[-1]

    lp, le = run(eng.sparams, jnp.asarray(toks))
    return jax.device_get(lp), jax.device_get(le)


def memory(tag: str) -> None:
    import jax
    for d in jax.devices():
        ms = d.memory_stats() or {}
        print(f"[smoke]   {tag}: device {d.id} bytes_in_use "
              f"{ms.get('bytes_in_use', 'n/a')}, peak_bytes_in_use "
              f"{ms.get('peak_bytes_in_use', 'n/a')}", flush=True)


def serve(cfg, mode: str, toks, topology=None, sparams=None) -> dict:
    """Serve the requests twice (the first run pays the compiles) on an
    engine built through build_engine, or on the exported weights
    `sparams` of an earlier engine, and return what the checks need."""
    import jax
    from repro.kernels import ops
    from repro.launch.serve import build_engine
    from repro.models.transformer import Runtime
    from repro.serve import Request, ServeConfig, ServeEngine

    ops.reset_fallbacks()
    t0 = time.perf_counter()
    config = ServeConfig(max_slots=SLOTS, max_len=PROMPT_LEN + GEN,
                         seed=SEED, topology=topology)
    rt = Runtime(kernel_mode=mode)
    eng = (build_engine(cfg, rt, config=config) if sparams is None
           else ServeEngine(cfg, sparams, rt, config=config))
    build_s = time.perf_counter() - t0
    memory(f"{mode} engine built")
    reqs = [Request(uid=i, prompt=t, max_new_tokens=GEN)
            for i, t in enumerate(toks)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run()
    first_s = time.perf_counter() - t0
    eng.reset_clock()
    for r in reqs:
        eng.submit(r)
    res = eng.run()
    st = eng.stats
    t0 = time.perf_counter()
    lg_pack, lg_end = prompt_logits(eng, toks)
    logits_s = time.perf_counter() - t0
    out = {
        "sparams": eng.sparams,
        "tokens": [res[i].tokens.tolist() for i in range(len(reqs))],
        "lg_pack": lg_pack, "lg_end": lg_end,
        "fallbacks": dict(ops.fallback_counts()),
        "engine_fallbacks": dict(st.kernel_fallbacks),
        "mode": eng.rt.kernel_mode,
        "devices": {d for x in jax.tree.leaves(eng.sparams)
                    for d in x.devices()},
        "sharded_leaves": sum(not x.sharding.is_fully_replicated
                              for x in jax.tree.leaves(eng.sparams)),
    }
    tok_s = st.generated_tokens / max(st.wall_seconds, 1e-9)
    print(f"[smoke] {mode}{'' if topology is None else f' tp={topology.tp}'}"
          f": {'build+export' if sparams is None else 'engine'} "
          f"{build_s:.1f}s, first run (compiles) {first_s:.1f}s, logits "
          f"pass {logits_s:.1f}s, warm run {st.generated_tokens} tokens in "
          f"{st.wall_seconds:.3f}s ({tok_s:.1f} tok/s, {st.decode_steps} "
          f"decode steps)", flush=True)
    memory(f"{mode} served")
    del eng
    gc.collect()
    return out


def check_kernels(cfg) -> None:
    """The serving kernels on the chip against their jnp references at the
    model's decode shapes, from one fixed input: no recurrence between them
    to amplify rounding, so their tolerances are tight.  The GEMMs multiply
    bf16 activations by trits exactly and sum in f32, so they may differ
    only in the order of those sums (GEMM_TOL of the largest output).  The
    attention returns bf16, so it may differ by a bf16 rounding or two
    (ATTN_TOL of the largest output)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import das, twd
    from repro.kernels import ops

    rng = np.random.default_rng(SEED)
    keep, block = cfg.ternary.das.keep, cfg.ternary.das.block

    def check(name, got, want, tol):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = float(np.abs(got - want).max() / np.abs(want).max())
        print(f"[smoke] kernel {name}: max|d|/max|ref| {err:.3g} (tol "
              f"{tol:.3g})", flush=True)
        if not err <= tol:
            fail(f"kernel {name} disagrees with its reference")

    def pallas_and_ref(fn):
        got = fn("pallas")
        with jax.default_matmul_precision("highest"):   # the reference only
            want = fn("ref")
        return got, want

    for k, n in ((cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)):
        x = jnp.asarray(rng.standard_normal((SLOTS, k)), jnp.bfloat16)
        packed = twd.pack_ternary(rng.integers(-1, 2, (k, n)))
        ca = das.das_compact(x, block_size=block, keep=keep)
        got, want = pallas_and_ref(lambda m: ops.das_ternary_gemm(
            ca.values, ca.indices, packed, 0.5, keep=keep, block=block,
            mode=m))
        check(f"das_ternary_gemm M={SLOTS} K={k} N={n}", got, want,
              GEMM_TOL)
    ring = cfg.lpsa.sink + cfg.lpsa.window
    h, hd = cfg.n_heads, cfg.head_dim_
    q, kk, v = (jnp.asarray(rng.standard_normal((SLOTS, h, lq, hd)),
                            jnp.bfloat16) for lq in (1, ring, ring))
    q_pos = jnp.full((SLOTS, 1), PROMPT_LEN - 1, jnp.int32)
    k_pos = jnp.asarray(np.where(np.arange(ring) < PROMPT_LEN,
                                 np.arange(ring), -1)[None].repeat(SLOTS, 0),
                        jnp.int32)
    got, want = pallas_and_ref(lambda m: ops.sparse_attention(
        q, kk, v, q_pos, k_pos, sink=cfg.lpsa.sink, window=cfg.lpsa.window,
        mode=m))
    check(f"sparse_attention decode ring={ring}", got, want, ATTN_TOL)


def check_clean(run: dict) -> None:
    if run["fallbacks"] or run["engine_fallbacks"]:
        fail(f"{run['mode']}: kernel fallbacks to the jnp reference: "
             f"{run['fallbacks'] or run['engine_fallbacks']}")


def compare(name: str, got: dict, ref: dict) -> None:
    """Logits within LOGIT_TOL of the reference's spread; first tokens equal
    wherever the reference's top-1 logit leads its runner-up by more than
    the same tolerance."""
    import numpy as np
    for key in ("lg_pack", "lg_end"):
        a, b = np.asarray(got[key]), np.asarray(ref[key])
        if not (np.isfinite(a).all() and np.isfinite(b).all()):
            fail(f"{name}: non-finite {key}")
        spread = b.max(-1) - b.min(-1)
        rel = np.abs(a - b).max(-1) / spread
        print(f"[smoke] {name} {key}: max|dlogit|/spread per request "
              f"{np.round(rel, 5).tolist()} (tol {LOGIT_TOL})", flush=True)
        if (rel > LOGIT_TOL).any():
            fail(f"{name}: {key} disagree beyond {LOGIT_TOL} of the spread")
    b = np.asarray(ref["lg_end"])
    top2 = np.sort(b, -1)[:, -2:]
    lead = (top2[:, 1] - top2[:, 0]) / (b.max(-1) - b.min(-1))
    same_first = [g[0] == r[0] for g, r in zip(got["tokens"], ref["tokens"])]
    agree = sum(x == y for g, r in zip(got["tokens"], ref["tokens"])
                for x, y in zip(g, r))
    total = sum(len(r) for r in ref["tokens"])
    print(f"[smoke] {name} tokens: first-token match {same_first}, ref top-1 "
          f"lead/spread {np.round(lead, 5).tolist()}, {agree}/{total} "
          f"tokens agree position-wise", flush=True)
    for i, (same, clear) in enumerate(zip(same_first, lead > LOGIT_TOL)):
        if clear and not same:
            fail(f"{name}: request {i} first token differs although the "
                 f"reference's top-1 leads by {lead[i]:.4f} of the spread")
    print(f"[smoke] {name}: {int((lead > LOGIT_TOL).sum())}/{len(lead)} "
          f"first tokens were clear enough to be checked", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def run_one_chip() -> None:
    from repro.configs import get_config
    cfg = get_config(ARCH)
    check_kernels(cfg)
    toks = prompts(cfg)
    pallas = serve(cfg, "pallas", toks)
    check_clean(pallas)
    # the same exported weights: no second 6.6 GB master on the chip
    ref = serve(cfg, "ref", toks, sparams=pallas.pop("sparams"))
    check_clean(ref)
    compare("pallas vs ref", pallas, ref)


def run_four_chips() -> None:
    from repro.configs import get_config
    from repro.distributed.plan import Topology
    cfg = get_config(ARCH)
    toks = prompts(cfg)
    single = serve(cfg, "sharded", toks)
    tp = serve(cfg, "sharded", toks, topology=Topology(tp=4),
               sparams=single.pop("sparams"))
    if len(tp["devices"]) != 4 or not tp["sharded_leaves"]:
        fail(f"tp=4 params live on {len(tp['devices'])} device(s), "
             f"{tp['sharded_leaves']} leaves sharded")
    print(f"[smoke] tp=4 params span devices "
          f"{sorted(d.id for d in tp['devices'])}, {tp['sharded_leaves']} "
          f"leaves sharded", flush=True)
    compare("tp=4 vs one device", tp, single)


if __name__ == "__main__":
    main()
