"""Serving driver: pjit-able step builders + a CLI over repro.serve.

`make_prefill_step` / `make_decode_step` are the pjit-able pure steps the
dry-run lowers at production shapes.  `main` is now a thin CLI over
`repro.serve.ServeEngine`: export ternary weights (TWD packing), submit a
staggered trace of generation requests, and let the continuous-batching
engine prefill/decode them through per-sequence KV state.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch bitnet-1.3b --reduced \
      --prompt-len 64 --gen 32 --requests 4 --stagger 4 --temperature 0.8

``--serve-http`` flips the CLI from trace-replay into the always-on front
door: an asyncio HTTP server (repro.serve.server) over the same engine,
with an OpenAI-style streaming completions endpoint, 429 backpressure,
``/metrics`` live telemetry and SIGINT/SIGTERM-clean shutdown.
``--metrics-out`` writes the JSON-lines telemetry log in either mode.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced as reduced_cfg
from repro.distributed.plan import Topology, device_shortfall_hint
from repro.kernels.ops import KernelMode
from repro.models import model as MD
from repro.models.transformer import Runtime
from repro.serve import Request, ServeConfig, ServeEngine

# CLI defaults come straight from the ServeConfig field defaults, so the
# two can never drift (satellite of the Topology/ShardingPlan redesign)
_D = {f.name: f.default for f in dataclasses.fields(ServeConfig)}

__all__ = ["make_prefill_step", "make_decode_step", "build_engine",
           "enable_compile_cache", "main"]


def make_prefill_step(cfg, rt: Runtime, *, max_len: int):
    def prefill_step(sparams, inputs):
        return MD.prefill(sparams, cfg, inputs, rt, max_len=max_len)
    return prefill_step


def make_decode_step(cfg, rt: Runtime):
    def decode_step(sparams, caches, token, t):
        return MD.decode_step(sparams, cfg, caches, token, t, rt)
    return decode_step


def build_engine(cfg, rt: Runtime, config: ServeConfig | None = None,
                 **legacy) -> ServeEngine:
    """Init params, export TWD serving weights, wrap them in a ServeEngine.

    Pass ``config=ServeConfig(...)``; loose kwargs (max_slots, max_len, ...)
    are forwarded through the engine's deprecated back-compat shim."""
    seed = config.seed if config is not None else legacy.get("seed", 0)
    params = MD.init_params(jax.random.PRNGKey(seed), cfg)
    sparams = MD.export_serving(params, cfg)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(sparams))
    mbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    del params      # the master copy is most of the device memory; free it
                    # before the engine allocates its caches
    print(f"[serve] {cfg.name}: serving weights {nbytes/1e6:.1f} MB "
          f"(master {mbytes/1e6:.1f} MB, {mbytes/max(nbytes,1):.1f}x TWD+quant)")
    return ServeEngine(cfg, sparams, rt, config=config, **legacy)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself) and nothing else is set here.  Otherwise the cache lives at a
    fixed directory of the checkout, ``<repo>/.jax_cache``: the directory is
    part of the cache key, so a path that moved between runs would never
    hit.  Called by the entry points, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _make_prompt(cfg, rng, length: int):
    if MD.uses_embeds(cfg):
        return jnp.asarray(rng.standard_normal((length, cfg.d_model)),
                           jnp.float32)
    return np.asarray(rng.integers(0, cfg.vocab, (length,)), np.int32)


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="TENET serving CLI: trace replay or HTTP front door "
                    "over repro.serve.ServeEngine")

    eng = ap.add_argument_group(
        "engine", "model + ServeEngine knobs (defaults mirror ServeConfig)")
    eng.add_argument("--arch", default="bitnet-1.3b")
    eng.add_argument("--reduced", action="store_true")
    eng.add_argument("--slots", type=int, default=_D["max_slots"])
    eng.add_argument("--top-k", type=int, default=_D["top_k"])
    eng.add_argument("--no-sparse", action="store_true",
                     help="full attention + full KV cache (naive baseline)")
    eng.add_argument("--layout", choices=["auto", "paged"],
                     default=_D["layout"],
                     help="KV layout: 'auto' keeps per-slot caches; 'paged' "
                          "shares one refcounted page arena per full-attn "
                          "layer with lazy allocation + radix prefix sharing")
    eng.add_argument("--page-size", type=int, default=_D["page_size"],
                     help="tokens per KV page (paged layout)")
    eng.add_argument("--num-pages", type=int, default=_D["num_pages"],
                     help="pool capacity incl. the null page; 0 auto-sizes "
                          "to the per-slot worst case")
    eng.add_argument("--no-prefix-sharing", action="store_true",
                     help="disable the radix-trie prompt-prefix index "
                          "(paged layout)")
    eng.add_argument("--kernel-mode", default="ref",
                     type=lambda s: KernelMode.parse(s).value,
                     help="ternary-linear execution path (kernels/ops."
                          "KERNEL_MODES); kernel modes route slab-aligned "
                          "packed+DAS layers through the fused "
                          "das_ternary_gemm datapath; 'tuned' autotunes "
                          "per-shape at engine construction and caches "
                          "winners on disk; 'sharded' is the GSPMD-safe "
                          "path a --tp/--dp mesh forces")
    eng.add_argument("--moe-expert-capacity", type=int,
                     default=_D["moe_expert_capacity"],
                     help="bound the per-expert token load per decode tick "
                          "by deferring admissions (MoE configs only; 0 = "
                          "unbounded — decode itself never drops tokens)")
    eng.add_argument("--seed", type=int, default=_D["seed"])

    tr = ap.add_argument_group("trace replay", "synthetic request trace")
    tr.add_argument("--requests", type=int, default=4)
    tr.add_argument("--prompt-len", type=int, default=64)
    tr.add_argument("--gen", type=int, default=32)
    tr.add_argument("--stagger", type=int, default=0,
                    help="virtual decode steps between request arrivals")
    tr.add_argument("--temperature", type=float, default=0.0)
    tr.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append JSON-lines telemetry (one line per "
                         "finished request + periodic tick snapshots) to "
                         "PATH")

    sched = ap.add_argument_group("scheduler", "admission order + SLOs")
    sched.add_argument("--policy", choices=["continuous", "wave"],
                       default=_D["policy"])
    sched.add_argument("--scheduler", choices=["fifo", "deadline"],
                       default=None,
                       help="admission order: 'fifo' (aged priority-then-"
                            "arrival) or 'deadline' (earliest-effective-"
                            "deadline-first over Request.slo_steps); "
                            "defaults to 'deadline' under --serve-http, "
                            "else 'fifo'")
    sched.add_argument("--slo-steps", type=int, default=0,
                       help="per-request deadline budget in virtual decode "
                            "steps (0 = no SLO); attached to every trace "
                            "request and used as the server's default for "
                            "requests that don't carry slo_steps")
    sched.add_argument("--preemption", action="store_true",
                       help="deadline scheduler only: truncate-and-retire "
                            "the youngest over-SLO-budget slot when the "
                            "queue head would otherwise miss its deadline")

    http = ap.add_argument_group("HTTP front door", "--serve-http mode")
    http.add_argument("--serve-http", action="store_true",
                      help="run the always-on HTTP front door instead of a "
                           "trace replay (POST /v1/completions with "
                           "stream=true, GET /metrics, GET /healthz; "
                           "SIGINT/SIGTERM shut down cleanly)")
    http.add_argument("--host", default="127.0.0.1")
    http.add_argument("--port", type=int, default=8080,
                      help="listen port for --serve-http (0 = ephemeral)")
    http.add_argument("--max-queue-depth", type=int, default=64,
                      help="queued requests beyond which the server answers "
                           "429 (backpressure)")

    dist = ap.add_argument_group(
        "distributed", "SPMD serving over a (dp, tp) mesh + elastic "
        "recovery (run under XLA_FLAGS="
        "--xla_force_host_platform_device_count=N to emulate N devices)")
    dist.add_argument("--tp", type=int, default=None, metavar="N",
                      help="tensor-parallel ways: shard the packed weight "
                           "slabs Megatron column/row style over the "
                           "'model' mesh axis")
    dist.add_argument("--dp", type=int, default=None, metavar="N",
                      help="data-parallel ways: shard the slot batch over "
                           "the 'data' mesh axis")
    dist.add_argument("--print-plan", action="store_true",
                      help="print the resolved ShardingPlan (per-leaf "
                           "PartitionSpecs) and the cache specs")
    dist.add_argument("--inject-failure", type=int, action="append",
                      default=None, metavar="STEP",
                      help="inject a WorkerFailure before decode step STEP "
                           "(repeatable): exercises snapshot -> mesh "
                           "shrink -> reshard -> replay recovery")
    dist.add_argument("--inject-lost", type=int, default=1, metavar="N",
                      help="devices lost per injected failure (default 1)")
    return ap


def _check_topology(ap, cfg, args) -> Topology | None:
    """Resolve --tp/--dp into a Topology, rejecting shapes the config's
    head/FFN dims can't divide with a clear argparse error."""
    if args.tp is None and args.dp is None:
        return None     # single-device; --inject-failure still works
                        # (in-place recovery: snapshot + rebuild + replay)
    tp = args.tp or 1
    dp = args.dp or 1
    if tp < 1 or dp < 1:
        ap.error("--tp/--dp must be >= 1")
    if tp > 1:
        bad = [f"{name}={dim}" for name, dim in (
            ("n_heads", cfg.n_heads), ("n_kv_heads", cfg.n_kv_heads),
            ("d_ff", cfg.d_ff)) if dim % tp]
        if cfg.moe is not None and cfg.moe.n_experts % tp:
            bad.append(f"moe.n_experts={cfg.moe.n_experts}")
        if bad:
            ap.error(f"--tp {tp} does not divide {args.arch}'s "
                     f"{', '.join(bad)}; pick a tp that divides the "
                     f"head/FFN dims (try --reduced, or a smaller --tp)")
    topo = Topology(dp=dp, tp=tp)
    n_dev = len(jax.devices())
    if topo.n_devices > n_dev:
        ap.error(f"topology (dp={dp}, tp={tp}) needs {topo.n_devices} "
                 f"devices but jax sees {n_dev}; "
                 f"{device_shortfall_hint(topo.n_devices)}")
    return topo


def main(argv=None):
    ap = _build_parser()
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.scheduler is None:
        args.scheduler = "deadline" if args.serve_http else "fifo"
    if args.preemption and args.scheduler != "deadline":
        ap.error("--preemption requires --scheduler deadline")

    # gate bad configs here with argparse-style errors instead of letting
    # them traceback deep inside cache/engine init
    try:
        cfg = get_config(args.arch)
    except KeyError as e:
        ap.error(str(e.args[0] if e.args else e))
    if args.reduced:
        cfg = reduced_cfg(cfg)
    topology = _check_topology(ap, cfg, args)
    rt = Runtime(serve_sparse=not args.no_sparse,
                 kernel_mode=args.kernel_mode)
    max_len = args.prompt_len + args.gen
    if args.layout == "paged" and max_len % args.page_size:
        max_len += args.page_size - max_len % args.page_size

    try:
        sc = ServeConfig(max_slots=args.slots, max_len=max_len,
                         layout=args.layout, page_size=args.page_size,
                         num_pages=args.num_pages,
                         prefix_sharing=not args.no_prefix_sharing,
                         top_k=args.top_k, seed=args.seed,
                         policy=args.policy,
                         moe_expert_capacity=args.moe_expert_capacity,
                         scheduler=args.scheduler,
                         preemption=args.preemption,
                         topology=topology)
        eng = build_engine(cfg, rt, config=sc)
    except ValueError as e:
        ap.error(f"config not serveable: {e}")
    if args.inject_failure:
        from repro.distributed import fault
        eng.fault_injector = fault.FaultInjector(
            fail_at=tuple(sorted(set(args.inject_failure))))
        eng.fault_lost_devices = args.inject_lost
    if topology is not None:
        print(f"[serve] topology: dp={topology.dp} tp={topology.tp} "
              f"({topology.n_devices} devices, mesh axes "
              f"{topology.axis_names})")
    if args.print_plan and eng.plan is not None:
        print(eng.plan.describe(eng.sparams))

    # the resolved slot-state union (one entry per distinct layout, in
    # stack order) — the README's "serving the model zoo" table, live
    layouts: dict[str, int] = {}
    for row in eng.layout_summary():
        layouts[row["layout"]] = layouts.get(row["layout"], 0) + 1
    print("[serve] slot-state layouts: "
          + ", ".join(f"{k} x{v}" for k, v in layouts.items()))

    from repro.serve.metrics import Telemetry
    tele = Telemetry(engine=eng, jsonl_path=args.metrics_out)

    if args.serve_http:
        return _serve_http(args, eng, tele)

    rng = np.random.default_rng(args.seed)
    slo = args.slo_steps if args.slo_steps > 0 else None
    for i in range(args.requests):
        eng.submit(Request(uid=i, prompt=_make_prompt(cfg, rng, args.prompt_len),
                           max_new_tokens=args.gen,
                           temperature=args.temperature,
                           arrival=i * args.stagger, slo_steps=slo))
    results = eng.run()

    st = eng.stats
    print(f"[serve] {st.decode_steps} decode steps, slot utilization "
          f"{st.slot_utilization:.2f}, {st.generated_tokens} tokens in "
          f"{st.wall_seconds:.2f}s "
          f"({st.generated_tokens/max(st.wall_seconds,1e-9):.1f} tok/s)")
    if args.layout == "paged":
        pool = eng.pool_stats()
        if pool["num_pages"]:
            print(f"[serve] paged pool: {pool['pages_peak']}/"
                  f"{pool['num_pages']} pages peak "
                  f"({pool['bytes_peak']/1e6:.2f} MB vs dense "
                  f"{pool['dense_equiv_bytes']/1e6:.2f} MB), "
                  f"{st.prefix_hits} prefix hits "
                  f"({st.prompt_tokens_reused} tokens reused), "
                  f"{st.cow_copies} CoW copies")
        else:
            print("[serve] paged pool: no full-attention layers under this "
                  "config (LPSA/ring only) -> no page arenas; pass "
                  "--no-sparse to page the global layers")
    for uid in sorted(results):
        r = results[uid]
        slo_note = "" if r.slo_steps is None else \
            f", slo {'MET' if r.slo_met else 'MISS'} ({r.slo_steps})"
        print(f"[serve] req {uid}: ttft {r.ttft_steps} steps, latency "
              f"{r.latency_steps} steps{slo_note}, "
              f"ids {r.tokens[:8].tolist()}...")
    if st.reshards:
        t = eng.topology
        topo_note = "" if t is None else f", topology dp={t.dp} tp={t.tp}"
        if len(results) == args.requests:
            print(f"[serve] recovery clean: all {len(results)} in-flight "
                  f"requests completed (reshards={st.reshards}, recovery "
                  f"{st.recovery_seconds:.2f}s{topo_note})")
        else:
            print(f"[serve] recovery INCOMPLETE: {len(results)}/"
                  f"{args.requests} requests completed after "
                  f"{st.reshards} reshard(s){topo_note}")
    if args.slo_steps > 0:
        tracked = [r for r in results.values() if r.slo_steps is not None]
        met = sum(r.slo_met for r in tracked)
        print(f"[serve] SLO attainment: {met}/{len(tracked)} "
              f"({met/max(len(tracked), 1):.0%}) at {args.slo_steps} steps, "
              f"{eng.stats.preemptions} preemptions")
    if args.metrics_out:
        tele.close()
        print(f"[serve] telemetry JSONL -> {args.metrics_out}")
    return results


def _serve_http(args, eng, tele):
    """The always-on front door: run until SIGINT/SIGTERM, shut down
    cleanly (joins the engine thread, closes the telemetry log)."""
    import asyncio
    import contextlib
    import signal

    from repro.serve.server import ServeHTTPServer

    default_slo = args.slo_steps if args.slo_steps > 0 else None
    srv = ServeHTTPServer(eng, args.host, args.port,
                          max_queue_depth=args.max_queue_depth,
                          default_slo_steps=default_slo, telemetry=tele)

    async def _amain():
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            with contextlib.suppress(NotImplementedError):
                loop.add_signal_handler(sig, stop.set)
        await srv.start()
        print(f"[serve] http front door on http://{srv.host}:{srv.port} "
              f"(scheduler={args.scheduler}, "
              f"default_slo={default_slo}, "
              f"max_queue_depth={args.max_queue_depth}); "
              f"POST /v1/completions, GET /metrics", flush=True)
        await stop.wait()
        print("[serve] shutting down...", flush=True)
        await srv.stop()
        st = eng.stats
        print(f"[serve] clean shutdown: {st.decode_steps} decode steps, "
              f"{st.generated_tokens} tokens, "
              f"{tele.requests_finished} requests served", flush=True)

    asyncio.run(_amain())
    return None


if __name__ == "__main__":
    main()
