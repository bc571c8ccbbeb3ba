"""One run of one cell: set up, drive the closed loop, measure, check.

Set-up (counted in ``setup_s``): the compile cache, the serving weights
drawn from the seed on the device, the engine, one warm-up pass over every
shape the cell's traffic uses, then the traffic's own lead until the loop
is in its steady state (as many finished requests as there are clients).
The window then lasts ``seconds``; nothing compiles inside it.  After it
closes the peak memory is read, the program's state is freed and the
plain reference checks the window's work (``check.py``).
"""

from __future__ import annotations

import gc
import importlib.util
import os
import shutil
import sys
import time
import types

import numpy as np

from . import check, costs, spec, trace as TR, traffic, window

TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def chip_or_exit(chips: int):
    """The first device when it is a TPU of a known kind and JAX sees at
    least `chips` of them; otherwise exit non-zero with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX's first device is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    costs.peaks(devs[0].device_kind)
    return devs[0]


def _instrument(engine) -> None:
    """Host spans around the engine's admission and decode tick, written
    into the profiler's trace (traced runs only)."""
    from jax.profiler import TraceAnnotation

    def wrap(name, f):
        def g(*a, **k):
            with TraceAnnotation(name):
                return f(*a, **k)
        return g
    engine.step_decode = wrap("bench.tick", engine.step_decode)
    engine._admit_ready = wrap("bench.admission", engine._admit_ready)


def _warm(engine, cfg, mix: dict, vocab: int) -> None:
    """Compile and run once every shape the cell's traffic uses: the decode
    step, each packed-prefill length, the cache insert and scrub, and the
    first-token sampler of a prompt that is a whole number of packs."""
    from repro.serve import Request
    pack = cfg.lpsa.chunk
    lens = [n + 1 for n in spec.prefill_lengths(mix, pack)]
    lens = (lens + [pack]) if lens else [mix["prompt_tokens"]["min"]]
    for i, n in enumerate(lens):
        engine.submit(Request(uid=10 ** 9 + i,
                              prompt=traffic.prompt_tokens(0, i, n, vocab),
                              max_new_tokens=2))
    engine.run()
    engine.reset_clock()


def load_reader(name: str):
    path = os.path.join(spec.BENCH, "metrics", f"{name}.py")
    sp = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, *, kernel_mode: str = "pallas", cfg=None,
             fault=None, control: str | None = None) -> dict:
    """Run `cell` once and return the result line's object.  `cfg` (a
    program ModelConfig) and `fault` (a callable that breaks the engine)
    are for the tests; with `control` (a dtype) the control in the
    program's place and a token altered where it is produced are read and
    judged on the same sample, for setting the limits.  The command passes
    none of them."""
    import jax
    from repro.launch.serve import enable_compile_cache
    from repro.models import model as MD
    from repro.models.transformer import Runtime
    from repro.serve import ServeConfig, ServeEngine

    clock = time.perf_counter
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg_file, mix = cell["config"], cell["traffic"]
    model, context = spec.model_module(cfg_file), spec.max_len(cfg_file)
    spec.check_lengths(mix, context)
    if cfg is None:
        cfg = model.program_config(cfg_file)
    dev = jax.devices()[0]
    pk = costs.peaks(dev.device_kind) if dev.platform == "tpu" else None

    abstract = jax.eval_shape(
        lambda k: MD.export_serving(MD.init_params(k, cfg), cfg),
        jax.random.PRNGKey(0))
    key = spec.seed_key(seed)
    sparams = model.bind(key, cfg_file, abstract)
    jax.block_until_ready(sparams)
    say(f"weights drawn at {clock() - t_start:.1f}s")
    engine = ServeEngine(
        cfg, sparams, Runtime(kernel_mode=kernel_mode),
        config=ServeConfig(kernel_mode=kernel_mode, layout="auto",
                           max_slots=mix["clients"], max_len=context,
                           seed=seed % 2 ** 31))
    del sparams
    if fault is not None:
        fault(engine)
    say(f"engine built at {clock() - t_start:.1f}s")
    _warm(engine, cfg, mix, cfg.vocab)
    say(f"{cell['name']}: weights, engine and warm-up in "
        f"{clock() - t_start:.1f}s (compile cache {cache})")

    loop = traffic.ClosedLoop(engine, mix, seed, cfg.vocab, clock,
                              cfg.lpsa.chunk, context)
    engine.on_token, engine.on_finish = loop.on_token, loop.on_finish
    st = {"open": None, "tracing": False, "traced": None}
    if trace:
        _instrument(engine)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    def stop_trace():
        jax.block_until_ready(engine.caches)
        st["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        st["tracing"] = False
        st["traced"] = (st["tick0"], loop.tick(), st["uid0"], loop.next_uid)

    def poll():
        now = clock()
        if st["tracing"] and now >= st["trace_end"]:
            stop_trace()
        if st["open"] is None and loop.finished >= mix["open_after_finished"]:
            st["open"], st["open_tick"] = now, loop.tick()
            say(f"window opens at {now - t_start:.1f}s, tick {loop.tick()}")
        if trace and st["open"] is not None and st["traced"] is None \
                and not st["tracing"]:
            jax.block_until_ready(engine.caches)
            jax.profiler.start_trace(TRACE_DIR)
            st["span"] = jax.profiler.TraceAnnotation("bench.traced")
            st["span"].__enter__()
            st.update(tracing=True, tick0=loop.tick(), uid0=loop.next_uid,
                      trace_end=clock() + min(mix["trace_seconds"], seconds))
        if trace:
            with jax.profiler.TraceAnnotation("bench.client"):
                loop.poll()
        else:
            loop.poll()

    def should_stop():
        return st["open"] is not None and clock() >= st["open"] + seconds

    engine.run_forever(poll=poll, should_stop=should_stop)
    if st["tracing"]:
        stop_trace()
    t0, t1 = st["open"], st["open"] + seconds
    setup_s = t0 - t_start
    mem = [d.memory_stats() or {} for d in jax.devices()[:cell["chips"]]]
    peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    close_tick = engine.stats.decode_steps
    ticks_in_window = close_tick - st["open_tick"]
    snap = check.first_layer_cache(engine)
    engine.on_token = engine.on_finish = None
    loop.engine = None
    del engine
    gc.collect()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": peak}
    out: dict = {}
    if trace:
        tr = TR.Trace.from_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tick0, tick1, uid0, uid1 = st["traced"]
        # what a reader may use: the trace, the clients' log, the ticks and
        # the admissions (request uids) of the traced window
        ctx = {"trace": tr, "loop": loop, "cfg": cfg_file, "peaks": pk,
               "slots": mix["clients"], "ticks": (tick0, tick1),
               "uids": range(uid0, uid1), "patterns": TR.patterns(),
               "costs": costs}
        metrics = {}
        for m in cell["per_layer"] if tr.ops[0] else []:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s())
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps(10)}
        say(f"traced {tick1 - tick0} ticks, {uid1 - uid0} admissions, "
            f"{tr.window_s():.3f}s; device busy {tr.busy_s():.3f}s")
    else:
        e2e = window.metrics(loop.log, t0, t1)
        e2e["setup_s"] = (setup_s, "s", 1)
        metrics = {}
        for m in cell["end_to_end"]:
            v, unit, n = e2e[m["name"]]
            say(f"{m['name']}: {v} {unit} over {n} samples")
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": unit}
    say(f"window {seconds}s: {ticks_in_window} ticks, {loop.finished} "
        f"requests finished in all, peak device memory {peak} bytes")

    # -- correct: the plain reference over a sample of the window's work ----
    tc = clock()
    lim = cell["limits"]
    uids = check.sample(loop.log, t1, seed, mix["check"])

    def read(lp=loop, act_dtype=None):
        return check.read(model, key, cfg_file, lp, snap, close_tick, uids,
                          context, lim, act_dtype)
    got = read()
    correct, checks = check.judge(got, lim)
    correct = correct and not loop.failed
    say(f"reference check in {clock() - tc:.1f}s: " + ", ".join(
        f"{k} {v}" for k, v in got.items()))
    if control is not None:
        # the control in the program's place, and a token altered where it
        # is produced (one served token of each sampled request replaced by
        # the next id), each judged as the program is
        low = read(act_dtype=control)
        alt = {u: dict(r, tokens=list(r["tokens"]))
               for u, r in loop.log.items()}
        rng = np.random.default_rng([seed, 11])
        for u in uids:
            toks = alt[u]["tokens"]
            j = int(rng.integers(len(toks)))
            toks[j] = (toks[j] + 1) % cfg.vocab
        bad = read(types.SimpleNamespace(log=alt, start_pos=loop.start_pos))
        for name, r in (("control", low), ("fault_altered_token", bad)):
            ok, c = check.judge(r, lim)
            out[name] = {"correct": ok, "checks": c, "readings": r}
            say(f"{name}: correct {ok}, " + ", ".join(
                f"{k} {v['value']}" for k, v in c.items()))
        out["control"]["dtype"] = control
        out["readings"] = got
    for k, v in checks.items():
        say(f"check {k}: {v['value']} (limit {v['limit']})")
    return {"correct": bool(correct), "attempted": loop.next_uid,
            "failed": loop.failed + got["wrong_length_requests"],
            "metrics": metrics, "device": device, **out, "checks": checks}
