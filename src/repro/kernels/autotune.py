"""DSE-driven kernel autotuner: perfmodel ranking + timed confirmation.

The repo's DSE machinery (`core/dse.py`, `core/perfmodel.py`) explored the
TENET design space analytically but never fed the kernels.  This module
closes that loop for serving: per (op, backend, shape) it

  1. enumerates candidate configs — Pallas tile shapes (block_m/n/k) where
     the backend can compile them, XLA-native decode-GEMM implementations
     (kernels/xla_gemm.py) on CPU/GPU, chunked-flash kv-chunk sizes for
     attention;
  2. ranks them with :func:`repro.core.perfmodel.kernel_cost` (roofline);
  3. confirms the top ``budget`` candidates with real timed runs on random
     operands; and
  4. persists the winner to an on-disk JSON cache keyed by shape+backend.

Tuning must happen EAGERLY (``tune``), before jit tracing: ``ServeEngine``
warms up its decode/prefill shapes at construction, and a populated cache
makes later warmups free (zero timed runs — asserted in tests).  Inside a
trace, dispatch goes through ``lookup`` — a pure cache read that falls back
to the perfmodel's top-ranked candidate on a miss, never timing anything.
Note jit caches bake the config chosen at trace time: re-tune (or delete
the cache file) *before* building engines, not after.

Cache location: ``$TENET_AUTOTUNE_CACHE`` if set, else
``~/.cache/tenet-repro/autotune-<backend>.json``.  Format: one JSON object
``{"version": 1, "entries": {key: {impl, block_m, block_n, block_k, us}}}``
with keys like ``das_ternary_gemm|cpu|block32|k1280|keep16|m4|n512``.

CLI (bounded mode, exercised by CI):
    PYTHONPATH=src python -m repro.kernels.autotune \
        --backend interpret --budget 2 --cache .autotune/ci.json
"""

from __future__ import annotations

import argparse
import json
import os
import time
from dataclasses import asdict, dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import das as das_lib
from repro.core import perfmodel, twd
from repro.kernels import ref, xla_gemm
from repro.kernels.das_gemm import das_ternary_gemm as _das_gemm_pallas
from repro.kernels.sparse_attn import sparse_attention as _sparse_attn_pallas
from repro.kernels.ternary_gemm import (K_SLAB, TRITS_PER_BYTE,
                                        ternary_gemm as _ternary_gemm_pallas)

__all__ = [
    "TileConfig", "AutotuneCache", "default_cache", "reset_default_cache",
    "default_cache_path", "shape_key", "attn_dims", "candidates", "tune",
    "lookup", "run_gemm", "run_das_gemm", "main",
]

TUNED_OPS = ("ternary_gemm", "das_ternary_gemm", "sparse_attn")


@dataclass(frozen=True)
class TileConfig:
    """One candidate kernel configuration.

    ``impl``: "pallas" | "interpret" (tiled kernels), one of
    ``xla_gemm.XLA_GEMM_IMPLS`` / "xla_gather" (XLA decode-GEMMs),
    "xla_flash" (chunked attention; ``block_k`` = kv chunk), or "ref".
    ``block_*`` are tile shapes (0 = kernel default).
    """
    impl: str
    block_m: int = 0
    block_n: int = 0
    block_k: int = 0


def default_cache_path(backend: str | None = None) -> str:
    env = os.environ.get("TENET_AUTOTUNE_CACHE")
    if env:
        return env
    backend = backend or jax.default_backend()
    return os.path.join(os.path.expanduser("~"), ".cache", "tenet-repro",
                        f"autotune-{backend}.json")


class AutotuneCache:
    """On-disk shape+backend -> TileConfig map with write-through persist.

    ``timed_runs`` counts real timed candidate executions over this object's
    lifetime — a populated cache keeps it at zero (the "second warmup does
    no re-timing" property tests assert).
    """

    def __init__(self, path: str | None = None):
        self.path = path or default_cache_path()
        self.entries: dict[str, dict] = {}
        self.timed_runs = 0
        self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                payload = json.load(f)
            if payload.get("version") == 1:
                self.entries = payload.get("entries", {})
        except (OSError, ValueError):
            self.entries = {}

    def save(self) -> None:
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(self.path, "w") as f:
            json.dump({"version": 1, "entries": self.entries}, f, indent=1,
                      sort_keys=True)

    def get(self, key: str) -> TileConfig | None:
        e = self.entries.get(key)
        if e is None:
            return None
        return TileConfig(e["impl"], e.get("block_m", 0), e.get("block_n", 0),
                          e.get("block_k", 0))

    def put(self, key: str, cfg: TileConfig, us: float,
            failed: list[dict] = ()) -> None:
        """Persist the winner; ``failed`` keeps every candidate that raised
        while being timed, with its error."""
        self.entries[key] = {**asdict(cfg), "us": round(float(us), 2)}
        if failed:
            self.entries[key]["failed"] = list(failed)
        self.save()


_DEFAULT: AutotuneCache | None = None


def default_cache() -> AutotuneCache:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = AutotuneCache()
    return _DEFAULT


def reset_default_cache() -> None:
    """Drop the process-wide cache object (re-reads env var + disk)."""
    global _DEFAULT
    _DEFAULT = None


def shape_key(op: str, backend: str, **dims) -> str:
    return "|".join([op, backend] + [f"{k}{v}" for k, v in
                                     sorted(dims.items())])


def attn_dims(*, hq: int, hkv: int, lq: int, lk: int, d: int, sink: int,
              window: int) -> dict:
    """Canonical `sparse_attn` cache dims.  sink/window are clamped to the
    cache length so the full-causal sentinel (sink = 2**30) keys stay sane
    and masks that behave identically share one entry.  Use this on BOTH
    sides (warmup tune + trace-time lookup) so keys always match."""
    return dict(hq=hq, hkv=hkv, lq=lq, lk=lk, d=d,
                sink=min(sink, lk), window=min(window, lk))


# ---------------------------------------------------------------------------
# candidate enumeration
# ---------------------------------------------------------------------------

def candidates(op: str, backend: str, **dims) -> list[TileConfig]:
    """Feasible configs for `op` on `backend` at the given dims.

    ``backend="interpret"`` enumerates only Pallas tile configs run under
    ``interpret=True`` — the bounded CI mode that exercises the tuning
    machinery on runners without a Pallas-compiling backend.
    """
    if op == "sparse_attn":
        return _attn_candidates(backend, **dims)
    if op not in ("ternary_gemm", "das_ternary_gemm"):
        raise ValueError(f"candidates: unknown op {op!r}")
    m, k, n = dims["m"], dims["k"], dims["n"]
    keep, block = dims.get("keep", 0), dims.get("block", 0)
    das = keep > 0
    out: list[TileConfig] = []
    slab_ok = k % K_SLAB == 0 and (not das or (K_SLAB % block == 0
                                               and keep <= block))
    if backend in ("tpu", "gpu", "interpret") and slab_ok:
        impl = "interpret" if backend == "interpret" else "pallas"
        n_slab = k // K_SLAB
        bks = [b for b in (1, 2, 4) if n_slab % b == 0] or [1]
        bms = sorted({min(bm, m) for bm in ((8, 32) if das else (32, 128))})
        bns = sorted({min(bn, n) for bn in (128, 256, 512)})
        out += [TileConfig(impl, bm, bn, bk)
                for bm in bms for bn in bns for bk in bks]
    if backend != "interpret":
        f32_ok = k % TRITS_PER_BYTE == 0
        if das:
            if f32_ok:
                out.append(TileConfig("xla_dense_f32dec"))
            out.append(TileConfig("xla_dense_plain"))
            if k % block == 0:
                out.append(TileConfig("xla_gather"))
        else:
            if f32_ok:
                out.append(TileConfig("xla_f32dec"))
            out.append(TileConfig("xla_plain"))
    return list(dict.fromkeys(out))


def _attn_candidates(backend: str, *, hq, hkv, lq, lk, d,
                     sink=0, window=0) -> list[TileConfig]:
    out: list[TileConfig] = []
    if backend in ("tpu", "gpu", "interpret"):
        impl = "interpret" if backend == "interpret" else "pallas"
        bq = min(128, lq)
        out += [TileConfig(impl, block_m=bq, block_k=bk)
                for bk in sorted({min(b, lk) for b in (64, 128, 256)})]
    if backend != "interpret":
        out += [TileConfig("xla_flash", block_k=c)
                for c in sorted({min(c, lk) for c in (128, 256, 512, lk)})]
    return list(dict.fromkeys(out))


def _hw(backend: str):
    """The perfmodel peaks that rank candidates on `backend`, looked up by
    the device kind JAX reports (the interpreter runs on the host CPU)."""
    if backend in ("cpu", "interpret"):
        return perfmodel.backend_hw("cpu")
    return perfmodel.backend_hw(jax.devices(backend)[0].device_kind)


def _model_cost(hw, op: str, cfg: TileConfig, dims: dict) -> float:
    kd = {k: v for k, v in dims.items() if k not in ("sink", "window")}
    return perfmodel.kernel_cost(
        hw, op, cfg.impl, block_m=cfg.block_m, block_n=cfg.block_n,
        block_k=cfg.block_k, **kd)


# ---------------------------------------------------------------------------
# config executors (shared by tuned dispatch and timed confirmation)
# ---------------------------------------------------------------------------

def run_gemm(x, packed, w_scale, x_scale=None, *, cfg: TileConfig | None = None,
             **kw):
    """Dense ternary GEMM under a tuned (or given) config."""
    m, k = x.shape
    if cfg is None:
        cfg = lookup("ternary_gemm", m=m, k=k, n=packed.shape[1],
                     keep=0, block=0)
    if cfg.impl in ("pallas", "interpret"):
        return _ternary_gemm_pallas(
            x, packed, w_scale, x_scale, block_m=cfg.block_m or 128,
            block_n=cfg.block_n or 256, block_k=cfg.block_k or 1,
            interpret=(cfg.impl == "interpret"), **kw)
    if cfg.impl in xla_gemm.XLA_GEMM_IMPLS:
        return xla_gemm.decode_matmul(x, packed, w_scale, impl=cfg.impl,
                                      x_scale=x_scale)
    return ref.ternary_gemm_packed_ref(x, packed, w_scale, k, x_scale)


def run_das_gemm(values, indices, packed, w_scale, *, keep: int, block: int,
                 cfg: TileConfig | None = None, **kw):
    """Fused DAS->ternary GEMM from compacted activations under a config."""
    m, kc = values.shape
    k = kc * block // keep
    if cfg is None:
        cfg = lookup("das_ternary_gemm", m=m, k=k, n=packed.shape[1],
                     keep=keep, block=block)
    if cfg.impl in ("pallas", "interpret"):
        return _das_gemm_pallas(
            values, indices, packed, w_scale, keep=keep, block=block,
            block_m=cfg.block_m or 8, block_n=cfg.block_n or 256,
            block_k=cfg.block_k or 1, interpret=(cfg.impl == "interpret"),
            **kw)
    if cfg.impl.startswith("xla_dense"):
        dense = xla_gemm.scatter_dense(values, indices, k, keep=keep,
                                       block=block)
        return xla_gemm.decode_matmul(dense, packed, w_scale, impl=cfg.impl)
    return ref.das_ternary_gemm_ref(values, indices, packed, w_scale, k)


# ---------------------------------------------------------------------------
# tune / lookup
# ---------------------------------------------------------------------------

def _median_us(fn, *args, iters: int, warmup: int) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def _time_gemm(op: str, cfg: TileConfig, dims: dict, *, iters, warmup) -> float:
    m, k, n = dims["m"], dims["k"], dims["n"]
    keep, block = dims.get("keep", 0), dims.get("block", 0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    trits = rng.integers(-1, 2, size=(k, n)).astype(np.int8)
    packed = jnp.asarray(twd.pack_ternary(trits))    # no pad: kp*5 == k
    scale = jnp.float32(0.5)
    if op == "das_ternary_gemm":
        needs_ca = cfg.impl in ("pallas", "interpret", "xla_gather", "ref")

        def fn(xv, p):
            # time prep + GEMM end-to-end: prep cost differs per impl
            if needs_ca:
                ca = das_lib.das_compact(xv, block_size=block, keep=keep)
                return run_das_gemm(ca.values, ca.indices, p, scale,
                                    keep=keep, block=block, cfg=cfg)
            xs = xla_gemm.masked_dense(xv, keep=keep, block=block)
            return xla_gemm.decode_matmul(xs, p, scale, impl=cfg.impl)
    else:
        def fn(xv, p):
            return run_gemm(xv, p, scale, cfg=cfg)
    return _median_us(jax.jit(fn), x, packed, iters=iters, warmup=warmup)


def _time_attn(cfg: TileConfig, dims: dict, *, iters, warmup) -> float:
    hq, hkv, lq, lk, d = (dims[x] for x in ("hq", "hkv", "lq", "lk", "d"))
    sink, window = dims.get("sink", 0), dims.get("window", lk)
    rng = np.random.default_rng(0)
    q_pos = jnp.arange(lk - lq, lk, dtype=jnp.int32)
    k_pos = jnp.arange(lk, dtype=jnp.int32)
    if cfg.impl == "xla_flash":
        from repro.models.attention import flash_masked  # lazy: no cycle
        q = jnp.asarray(rng.standard_normal((1, lq, hq, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, lk, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, lk, hkv, d)), jnp.float32)
        fn = jax.jit(lambda a, b, c: flash_masked(
            a, b, c, q_pos, k_pos, sink=sink, window=window,
            kv_chunk=cfg.block_k or min(512, lk)))
        return _median_us(fn, q, k, v, iters=iters, warmup=warmup)
    q = jnp.asarray(rng.standard_normal((hq, lq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hkv, lk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hkv, lk, d)), jnp.float32)
    fn = jax.jit(lambda a, b, c: _sparse_attn_pallas(
        a, b, c, q_pos, k_pos, sink=sink, window=window,
        block_q=cfg.block_m or 128, block_k=cfg.block_k or 128,
        interpret=(cfg.impl == "interpret")))
    return _median_us(fn, q, k, v, iters=iters, warmup=warmup)


def tune(op: str, *, backend: str | None = None,
         cache: AutotuneCache | None = None, budget: int = 3, iters: int = 3,
         warmup: int = 1, **dims) -> TileConfig:
    """Pick (and persist) the best config for one op+shape.

    Cache hit returns immediately with ZERO timed runs.  On a miss the
    perfmodel ranks all candidates and the top ``budget`` are confirmed with
    real timed runs (each bumps ``cache.timed_runs``).  Call eagerly — never
    from inside a jit trace.
    """
    backend = backend or jax.default_backend()
    cache = cache if cache is not None else default_cache()
    key = shape_key(op, backend, **dims)
    hit = cache.get(key)
    if hit is not None:
        return hit
    cands = candidates(op, backend, **dims)
    if not cands:
        cfg = TileConfig("ref")
        cache.put(key, cfg, -1.0)
        return cfg
    hw = _hw(backend)
    ranked = sorted(cands, key=lambda c: _model_cost(hw, op, c, dims))
    tried = ranked[:max(1, budget)]
    best, best_us = ranked[0], float("inf")
    failed: list[dict] = []
    for cfg in tried:
        try:
            if op == "sparse_attn":
                us = _time_attn(cfg, dims, iters=iters, warmup=warmup)
            else:
                us = _time_gemm(op, cfg, dims, iters=iters, warmup=warmup)
        except Exception as e:     # infeasible candidate: record it, go on
            failed.append({**asdict(cfg),
                           "error": f"{type(e).__name__}: {e}"[:500]})
            continue
        cache.timed_runs += 1
        if us < best_us:
            best, best_us = cfg, us
    pallas_tried = sum(c.impl == "pallas" for c in tried)
    if backend == "tpu" and pallas_tried and sum(
            f["impl"] == "pallas" for f in failed) == pallas_tried:
        # on the chip a compiler refusal is a defect to fix, never a quiet
        # "pick the next impl"
        raise RuntimeError(f"autotune {key}: every Pallas candidate failed: "
                           + "; ".join(f["error"] for f in failed))
    cache.put(key, best, best_us if best_us < float("inf") else -1.0, failed)
    return best


def lookup(op: str, *, backend: str | None = None,
           cache: AutotuneCache | None = None, **dims) -> TileConfig:
    """Trace-safe config resolution: cache read, else perfmodel top-1.

    Never times, never persists — safe to call while tracing ``tlin_apply``
    / ``attn_decode``.  A miss means the shape wasn't warmed up; the
    perfmodel choice is deterministic, so traces stay reproducible.
    """
    backend = backend or jax.default_backend()
    cache = cache if cache is not None else default_cache()
    hit = cache.get(shape_key(op, backend, **dims))
    if hit is not None:
        return hit
    cands = candidates(op, backend, **dims)
    if not cands:
        return TileConfig("ref")
    hw = _hw(backend)
    return min(cands, key=lambda c: _model_cost(hw, op, c, dims))


# ---------------------------------------------------------------------------
# CLI: bounded tuning run (CI smoke + manual re-tuning)
# ---------------------------------------------------------------------------

_SMALL_SHAPES = [
    ("das_ternary_gemm", dict(m=2, k=320, n=128, keep=16, block=32)),
    ("das_ternary_gemm", dict(m=4, k=640, n=256, keep=16, block=32)),
    ("ternary_gemm", dict(m=4, k=320, n=128, keep=0, block=0)),
    ("sparse_attn", dict(hq=4, hkv=2, lq=1, lk=64, d=64, sink=4, window=60)),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="Bounded autotune run: rank + time candidates for a "
                    "small shape set and persist the winners.")
    ap.add_argument("--backend", default=None,
                    help="tuning backend (default: the JAX backend); "
                         "'interpret' exercises the Pallas tile search in "
                         "emulation on any host")
    ap.add_argument("--budget", type=int, default=2,
                    help="max timed candidates per shape")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--cache", default=None,
                    help="cache path (default: $TENET_AUTOTUNE_CACHE or "
                         "~/.cache/tenet-repro/autotune-<backend>.json)")
    args = ap.parse_args(argv)

    cache = AutotuneCache(args.cache) if args.cache else default_cache()
    for op, dims in _SMALL_SHAPES:
        t0 = time.perf_counter()
        cfg = tune(op, backend=args.backend, cache=cache, budget=args.budget,
                   iters=args.iters, **dims)
        key = shape_key(op, args.backend or jax.default_backend(), **dims)
        us = cache.entries[key]["us"]
        print(f"{key} -> {cfg.impl} bm={cfg.block_m} bn={cfg.block_n} "
              f"bk={cfg.block_k} ({us:.1f}us, {time.perf_counter()-t0:.1f}s "
              f"to tune)")
    print(f"cache: {cache.path} ({len(cache.entries)} entries, "
          f"{cache.timed_runs} timed runs this invocation)")


if __name__ == "__main__":
    main()
