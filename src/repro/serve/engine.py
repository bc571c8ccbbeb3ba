"""ServeEngine: slot-based continuous batching over per-sequence KV caches.

The engine owns one decode-cache pytree sized for ``max_slots`` sequences
and runs ONE jitted decode step for the whole batch every tick — the
decode step's shapes are static, so it never recompiles as requests come
and go (admission prefill compiles once per pack-aligned prefix length,
a set bounded by max_len / chunk; `reset_clock` lets benchmarks warm
those caches before a timed replay).  Per-slot lifecycle:

  FREE ──admit──> PREFILL ──tail consumed──> DECODE ──eos/max──> FREE

Admission prefills the longest pack-aligned prompt *prefix* through the
LPSA streaming dataflow (batch=1) and writes the resulting layer caches
into the slot's rows; the remaining prompt tail is fed token-by-token
through the shared batched decode step while the other slots keep
generating (token-level admission, Orca-style).  Because every cache row
carries its own position cursor (models/kvcache.attn_write with t: (B,)),
a slot at prompt position 7 coexists with a slot at decode position 900.

Time is virtual: 1 unit == one batched decode step.  Requests carry
arrival times in the same units so traces replay deterministically, and a
request's tokens are bitwise independent of its batch-mates (per-row
attention masks + per-(uid, token) sampling keys) — see
tests/test_serve_engine.py for the batch-invariance check.

``policy="wave"`` degrades the same machinery to lock-step gang
scheduling (admit only when ALL slots are free, barrier until all
finish): the baseline the benchmarks compare against.

Per-slot state is a tagged union over kvcache.CacheSpec layouts, resolved
per layer from ``cfg.layer_kinds()`` (``layout_summary()`` prints it):
full/ring/paged KV for attention layers, O(1) recurrent state for
mamba (ssm carry + chunk-replay buffers), rwkv (wkv + shifts) and gla
(state matrix).  Recurrent-only configs have ``_chunk = 1``: the whole
prompt absorbs through batch-1 prefill (one compile per prompt length)
and decode carries pure state — hybrid stacks mix both in one pytree.
MoE configs prefill and decode with no-drop expert capacity (models/moe
serve_capacity); ``ServeConfig.moe_expert_capacity`` optionally bounds
the per-expert tick load via admission control instead of token drops.

``ServeConfig(layout="paged")`` swaps the dense per-slot full caches for a
block-paged KV pool (kvcache.CacheSpec layout="paged"): one refcounted
page arena per full-attention layer, per-slot int32 page tables passed to
the SAME jitted decode step (shapes stay static — the table is data, not
structure), pages allocated lazily as slots cross page boundaries, and a
radix-trie prefix index (serve.kvpool.RadixIndex) that lets admission
reuse the pages + states of the longest cached pack-aligned prompt
prefix instead of re-prefilling it.  Shared pages are copy-on-write: the
first divergent write to a page with refcount > 1 copies it; retiring a
slot releases its references and scrubs pages that drop free.  Used pool
memory therefore tracks live tokens, not ``max_slots * max_len``.

Tracing: the engine writes host spans into the JAX profiler's trace (on
the device trace's clock; next to free while no trace is being taken).
Each decode tick is ``serve.tick`` (a step marker, ``step_num``) with
children ``serve.tick.prepare`` (host arrays, page table, transfers),
``.dispatch`` (the jitted step's call), ``.wait`` (the token sync) and
``.deliver`` (per-slot delivery, ``on_token``, retirements).  Each
admission is ``serve.admit`` (``uid``, ``prompt_len``, ``packs``) with
``serve.admit.prefill``, ``.insert``, ``.sample`` and ``.wait``; then
``serve.poll`` (the ``run_forever`` poll hook), ``serve.retire`` (``uid``)
and ``serve.recover``.  On the device the executables are ``jit__step_fn``,
``jit_prefill``, ``jit_sample_first`` and the cache helpers, and the model
code carries named scopes (README "Telemetry").
"""

from __future__ import annotations

import threading
import time
import warnings
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import fault
from repro.distributed.plan import ShardingPlan
from repro.kernels import ops
from repro.models import attention as A
from repro.models import kvcache as KV
from repro.models import model as MD
from repro.models.transformer import Runtime, layer_cache_spec
from repro.serve.config import ServeConfig
from repro.serve.kvpool import PagePool, PrefixEntry, RadixIndex
from repro.serve.sampler import make_sampler, sample_token
from repro.serve.scheduler import DeadlineScheduler, FifoScheduler, Request

__all__ = ["ServeEngine", "ServeConfig", "EngineStats", "RequestResult"]

FREE, PREFILL, DECODE = 0, 1, 2


@dataclass
class RequestResult:
    uid: int
    tokens: np.ndarray            # generated ids (eos included when hit)
    prompt_len: int
    arrival: int                  # vtime units (1 = one batched decode step)
    admit_vtime: int
    first_token_vtime: int
    finish_vtime: int
    admitted_with_active: int = 0  # slots already mid-stream at admission
                                   # (admitted in an earlier tick)
    slo_steps: int | None = None   # deadline budget the request carried
    preempted: bool = False        # truncated by the deadline-rescue hook
    # wall-clock stamps (time.perf_counter seconds) at submit, admission
    # and first token: the instants the serve.admit / serve.tick spans mark
    submit_time: float | None = None
    admit_time: float | None = None
    first_token_time: float | None = None

    @property
    def latency_steps(self) -> int:
        return self.finish_vtime - self.arrival

    @property
    def ttft_steps(self) -> int:
        return self.first_token_vtime - self.arrival

    @property
    def queue_wait_steps(self) -> int:
        return self.admit_vtime - self.arrival

    @property
    def queue_wait_s(self) -> float | None:
        """Wall seconds from submit to admission (None when unstamped)."""
        if self.submit_time is None or self.admit_time is None:
            return None
        return self.admit_time - self.submit_time

    @property
    def ttft_s(self) -> float | None:
        """Wall seconds from submit to the first token."""
        if self.submit_time is None or self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    @property
    def slo_met(self) -> bool:
        """True when the request finished within its deadline budget (a
        preempted request is truncated, so it never counts as met);
        requests without an SLO vacuously meet it."""
        if self.slo_steps is None:
            return True
        return not self.preempted and self.latency_steps <= self.slo_steps


@dataclass
class EngineStats:
    max_slots: int = 0
    decode_steps: int = 0         # batched step invocations
    active_slot_steps: int = 0    # sum over steps of |active slots|
    generated_tokens: int = 0     # sampled tokens delivered to requests
    prefill_tokens: int = 0       # prompt tokens absorbed via batch-1 prefill
    wall_seconds: float = 0.0
    autotune_timed_runs: int = 0  # timed candidate runs spent in warmup
                                  # (0 when the on-disk cache was already hot)
    kernel_fallbacks: dict = field(default_factory=dict)
                                  # "op(shape)" -> count of silent jnp-ref
                                  # fallbacks observed (kernels/ops counters)
    # paged-pool accounting (zero under the per-slot layout)
    prefix_hits: int = 0          # admissions that reused a cached prefix
    prompt_tokens_reused: int = 0  # prompt tokens absorbed via prefix reuse
    cow_copies: int = 0           # copy-on-write page copies
    prefix_evictions: int = 0     # trie entries evicted to free pages
    pool_peak_pages: int = 0      # peak pages in use during this run
    moe_capacity_deferrals: int = 0  # admissions deferred by the MoE
                                     # expert-capacity bound (ticks a ready
                                     # request waited for a slot to retire)
    # MoE routing (zero without MoE layers): read from the decode ticks
    # (models/moe._dispatch_compute; serving capacity drops no token)
    moe_experts_hit: int = 0      # sum over ticks and MoE layers of the
                                  # experts a tick's tokens reached
    moe_max_load: int = 0         # most tokens one expert took in a tick
    preemptions: int = 0          # over-budget slots truncated to rescue a
                                  # deadline-critical queued request
    # elastic recovery (zero unless a WorkerFailure was survived)
    reshards: int = 0             # snapshot -> mesh shrink -> reshard cycles
    recovery_seconds: float = 0.0  # wall time spent rebuilding device state

    @property
    def slot_utilization(self) -> float:
        """Mean fraction of decode-batch rows doing useful work."""
        return self.active_slot_steps / max(1, self.decode_steps
                                            * max(1, self.max_slots))


class _Slot:
    __slots__ = ("state", "req", "input_tok", "input_x", "input_pos",
                 "tail", "tail_idx", "out", "admit_vtime", "first_tok_vtime",
                 "admit_t", "first_tok_t", "admitted_with_active", "pages",
                 "page_budget")

    def __init__(self):
        self.state = FREE
        self.req = None
        self.pages = None          # paged layout: logical->physical page ids
        self.page_budget = 0       # pages this slot may still allocate


class ServeEngine:
    """Continuous-batching engine over an exported serving-params tree.

    cfg/sparams/rt as elsewhere in the repo; ``max_len`` bounds prompt +
    generation when any layer keeps a full (non-ring) cache.  ``top_k`` is
    static for the jitted step (0 = unrestricted); per-request temperature
    is dynamic.  ``policy``: "continuous" (default) or "wave" (lock-step
    gang-scheduling baseline).  ``kernel_mode`` overrides ``rt.kernel_mode``
    (see kernels/ops.KERNEL_MODES) — with packed weights and DAS enabled
    the kernel modes route decode through the fused ``das_ternary_gemm``
    datapath (compacted activations straight against base-3 packed weights)
    on every slab-aligned layer.

    ``kernel_mode="tuned"`` additionally runs an eager autotune warmup at
    construction: every (op, shape) the jitted decode/prefill steps will
    trace is tuned via kernels/autotune (perfmodel-ranked candidates
    confirmed by timed runs) and persisted to the on-disk cache, so a second
    engine over the same shapes constructs with ZERO timed runs
    (``stats.autotune_timed_runs``).  Re-tune (delete the cache file) after
    changing backends — jit traces bake the config chosen at trace time.
    """

    _LEGACY_KWARGS = ("max_slots", "max_len", "top_k", "seed", "policy",
                      "kernel_mode", "layout", "page_size", "num_pages",
                      "prefix_sharing")

    def __init__(self, cfg: ModelConfig, sparams: dict,
                 rt: Runtime = Runtime(), config: ServeConfig | None = None,
                 **legacy):
        if legacy:
            unknown = set(legacy) - set(self._LEGACY_KWARGS)
            if unknown:
                raise TypeError(f"unknown ServeEngine kwarg(s): "
                                f"{', '.join(sorted(unknown))}")
            warnings.warn(
                "loose ServeEngine kwargs are deprecated; pass "
                "config=ServeConfig(...) (repro.serve.config)",
                DeprecationWarning, stacklevel=2)
            config = (config or ServeConfig()).with_updates(**legacy)
        config = config or ServeConfig()
        if config.kernel_mode is not None:
            rt = replace(rt, kernel_mode=config.kernel_mode)
        else:
            rt = replace(rt,
                         kernel_mode=ops.KernelMode.parse(rt.kernel_mode).value)
        if config.topology is not None and rt.kernel_mode != "sharded":
            if rt.kernel_mode != "ref":
                warnings.warn(
                    f"kernel_mode={rt.kernel_mode!r} is a single-device "
                    f"path; a Topology forces the GSPMD-safe 'sharded' mode",
                    stacklevel=2)
            rt = replace(rt, kernel_mode="sharded")
        self.cfg, self.sparams, self.rt = cfg, sparams, rt
        self.config = config
        max_slots, max_len = config.max_slots, config.max_len
        self.max_slots, self.max_len = max_slots, max_len
        self.policy = config.policy
        if config.scheduler == "deadline":
            self.scheduler = DeadlineScheduler(
                aging_steps=config.aging_steps,
                default_slo=config.slo_default_steps)
        else:
            self.scheduler = FifoScheduler(aging_steps=config.aging_steps)
        self._preempt = config.preemption
        self.stats = EngineStats(max_slots=max_slots)
        self.vtime = 0
        # per-engine baseline of the PROCESS-WIDE kernels/ops fallback
        # counters: stats report deltas vs this snapshot, so two engines in
        # one process never attribute each other's fallbacks
        self._fallback_base: dict = dict(ops.fallback_counts())
        # live-serving hooks (all optional): the HTTP front door streams
        # tokens through on_token/on_finish; a metrics.Telemetry sink
        # attached as .telemetry observes admissions/ticks/finishes
        self.telemetry = None
        self.on_token = None      # callable(uid, token_id) per sampled token
        self.on_finish = None     # callable(RequestResult) at retirement
        # submit/pop_result may be called from another thread than the one
        # driving run_forever (the HTTP server's event loop vs the engine
        # thread); this lock covers the scheduler + result-dict handoffs
        self._lock = threading.RLock()
        self._uses_embeds = MD.uses_embeds(cfg)
        # MoE configs also return the routing counters from the step
        self.moe_tick: dict | None = None   # the last tick's, per MoE layer
        self._cache_dtype = jnp.dtype(cfg.dtype)
        kinds = cfg.layer_kinds()
        sw = [A.kind_sink_window(cfg, k, rt.serve_sparse) for k in kinds
              if k in ("attn", "local")]
        self._has_full = any(s >= A.FULL_SINK for s, _ in sw)
        self._has_stream = any(s < A.FULL_SINK for s, _ in sw)
        # streaming prefill consumes whole packs; prompts prefill their
        # longest pack-aligned prefix and decode the tail token-by-token
        self._chunk = (cfg.lpsa.chunk if cfg.lpsa else 256) \
            if self._has_stream else 1

        # ---- paged pool (layout="paged") --------------------------------
        self._paged = config.layout == "paged"
        self._share = self._paged and config.prefix_sharing \
            and not self._uses_embeds   # embeds have no token ids to key on
        self._page_size = config.page_size
        # only full-attention layers become arenas; a paged engine over a
        # pure ring/recurrent config still shares exact prefix *states*
        # through the trie, just with zero pages per entry
        self._pages_per_seq = config.pages_per_seq if self._has_full else 0
        page_size = self._page_size if self._pages_per_seq else 0
        num_pages = config.resolved_num_pages() if self._pages_per_seq else 0
        self._cache_page_size = page_size
        self._cache_num_pages = num_pages
        # explicit per-layer CacheSpec union: the engine's source of truth
        # for which layers are shared page arenas vs per-slot rows (ring /
        # full / recurrent).  Mirrors the cache pytree structure.
        self._layer_specs = self._build_layer_specs(page_size, num_pages)
        self._paged_stacked = tuple(
            s.layout == "paged" for s in (self._layer_specs["stacked"] or ()))
        self._paged_tail = tuple(
            s.layout == "paged" for s in self._layer_specs["tail"])
        self._rest_is_empty = self._paged and not self._has_non_paged_rows()
        if config.moe_expert_capacity and cfg.moe is None:
            raise ValueError(
                f"moe_expert_capacity={config.moe_expert_capacity} is set "
                f"but config {cfg.name!r} has no MoE layers; drop the bound "
                f"or serve a MoE config")
        self._moe_slot_cap = (config.moe_expert_capacity
                              if cfg.moe is not None else 0)
        self._slots = [_Slot() for _ in range(max_slots)]
        self._results: dict[int, RequestResult] = {}
        self._pending_uids: set[int] = set()
        self._submit_t: dict[int, float] = {}   # uid -> perf_counter at submit
        self._base_key = jax.random.PRNGKey(config.seed)
        self._sampler = make_sampler(config.top_k)
        self._top_k = config.top_k

        # ---- SPMD / elastic-recovery state ------------------------------
        self._topology = config.topology   # live: shrinks on recovery
        self._mesh = None
        self.plan: ShardingPlan | None = None
        self._replays: list[dict] = []     # slot snapshots awaiting re-admit
        # test/ops hook: a fault.FaultInjector checked at each tick top;
        # fault_lost_devices is how many devices a triggered failure costs
        self.fault_injector = None
        self.fault_lost_devices = 1

        if rt.kernel_mode == "tuned":
            self._autotune_warmup()   # eager: must precede any jit trace

        self._build_device_state()

    def _build_device_state(self) -> None:
        """(Re)build everything that lives on devices: the KV pool / radix
        index / page table, the cache pytrees, the mesh + ShardingPlan
        placement of params and caches, and every jitted step.  Called once
        at construction and again by `recover()` after a device loss — the
        jits retrace against the (possibly shrunk) mesh."""
        cfg, rt = self.cfg, self.rt
        max_slots, max_len = self.max_slots, self.max_len
        page_size, num_pages = self._cache_page_size, self._cache_num_pages

        self._pool = PagePool(num_pages, self._page_size) \
            if self._pages_per_seq else None
        self._radix = RadixIndex() if self._share else None
        self._pt = np.zeros((max_slots, max(self._pages_per_seq, 1)),
                            np.int32) if self._paged else None

        self.caches = MD.init_caches(None, cfg, max_slots, max_len, rt,
                                     self._cache_dtype, page_size=page_size,
                                     num_pages=num_pages)
        self._empty1 = MD.init_caches(None, cfg, 1, max_len, rt,
                                      self._cache_dtype)
        # spec-derived flags must agree with the allocated structure
        assert self._paged_stacked == tuple(
            KV.is_paged(c) for c in (self.caches["stacked"] or ()))
        assert self._paged_tail == tuple(
            KV.is_paged(c) for c in self.caches["tail"])
        self._page_bytes = self._compute_page_bytes()

        step_kw: dict = {}
        cache_kw: dict = {}
        if self._topology is not None:
            self._mesh = self._topology.build_mesh()
            plan = ShardingPlan.for_tree(self.sparams, self._topology)
            plan = plan.with_caches(self.caches, batch=max_slots)
            self.plan = plan
            psh = plan.named(self._mesh)
            csh = plan.cache_named(self._mesh)
            rep = NamedSharding(self._mesh, P())
            # commit params/caches to the mesh once; on recovery this is
            # the reshard (old-mesh arrays redistribute onto the survivors)
            self.sparams = jax.device_put(self.sparams, psh)
            self.caches = jax.device_put(self.caches, csh)
            self._empty1 = jax.device_put(
                self._empty1, jax.tree.map(lambda _: rep, self._empty1))
            # explicit in/out shardings on the decode step: params keep the
            # Megatron column/row placement (one all-reduce per block half),
            # caches stay put so donation round-trips without resharding
            step_kw = {"in_shardings": ((psh, csh,
                                         rep if self._paged else None)
                                        + (rep,) * 8),
                       "out_shardings": (rep, csh)}
            cache_kw = {"out_shardings": csh}

        # named functions: the executables compile as jit_prefill and
        # jit_sample_first, the names a trace shows for them
        def prefill(sp, x):
            return MD.prefill(sp, cfg, x, rt, max_len=max_len)

        def sample_first(lg, uid, ctr, temp):
            with jax.named_scope("sample"):
                return sample_token(lg, self._fold_key(uid, ctr), temp,
                                    self._top_k)

        self._prefill = jax.jit(prefill)
        self._step = jax.jit(self._step_fn, donate_argnums=(1,), **step_kw)
        self._insert = jax.jit(self._insert_fn, donate_argnums=(0,),
                               **cache_kw)
        self._insert_paged = jax.jit(self._insert_paged_fn,
                                     donate_argnums=(0,), **cache_kw)
        self._insert_shared = jax.jit(self._insert_shared_fn,
                                      donate_argnums=(0,), **cache_kw)
        self._copy_page = jax.jit(self._copy_page_fn, donate_argnums=(0,),
                                  **cache_kw)
        self._scrub = jax.jit(self._scrub_fn, donate_argnums=(0,), **cache_kw)
        self._scrub_slot = jax.jit(self._scrub_slot_fn, donate_argnums=(0,),
                                   **cache_kw)
        self._sample1 = jax.jit(sample_first)

    # -- layer-layout structure helpers -----------------------------------

    def _build_layer_specs(self, page_size: int, num_pages: int) -> dict:
        """Resolve every layer's serving CacheSpec (the tagged slot-state
        union: paged / full / ring KV, mamba / rwkv / gla recurrent state).
        Keyed like the cache pytree: one spec per scanned pattern position
        plus one per unrolled tail layer."""
        cfg = self.cfg
        kinds = cfg.layer_kinds()
        plen = len(cfg.layer_pattern)
        n_groups, tail = (divmod(cfg.n_layers, plen) if cfg.scan_layers
                          else (0, cfg.n_layers))

        def spec(kind):
            return layer_cache_spec(cfg, kind, self.max_slots, self.max_len,
                                    self.rt, self._cache_dtype,
                                    page_size=page_size, num_pages=num_pages)

        stacked = (tuple(spec(k) for k in cfg.layer_pattern)
                   if n_groups else None)
        return {"stacked": stacked,
                "tail": tuple(spec(kinds[n_groups * plen + i])
                              for i in range(tail))}

    def layout_summary(self) -> list[dict]:
        """Ordered per-layer {layer, kind, layout} — the engine's resolved
        slot-state union over the whole stack (see README "serving the
        model zoo")."""
        kinds = self.cfg.layer_kinds()
        sp = self._layer_specs
        n_tail = len(sp["tail"])
        n_scanned = self.cfg.n_layers - n_tail
        out = []
        for i in range(self.cfg.n_layers):
            spec = (sp["stacked"][i % len(self.cfg.layer_pattern)]
                    if i < n_scanned else sp["tail"][i - n_scanned])
            out.append({"layer": i, "kind": kinds[i], "layout": spec.layout})
        return out

    def _has_non_paged_rows(self) -> bool:
        """True when any layer keeps per-slot (non-arena) state — ring
        caches or recurrent states that prefix reuse must snapshot."""
        flags = list(self._paged_stacked) + list(self._paged_tail)
        return any(not f for f in flags)

    def _compute_page_bytes(self) -> int:
        """Device bytes per pool page, summed over every paged layer (scan
        groups included: a stacked arena leaf is (G, P, ...))."""
        total = 0
        for flags, layers, ax in ((self._paged_stacked,
                                   self.caches["stacked"] or (), 1),
                                  (self._paged_tail, self.caches["tail"], 0)):
            for paged, layer in zip(flags, layers):
                if paged:
                    total += sum(leaf.nbytes // leaf.shape[ax]
                                 for leaf in layer.values())
        return total

    def _autotune_warmup(self) -> None:
        """Tune every (op, shape) the serving steps will trace, eagerly.

        GEMM shapes: the standard transformer projection pairs at the decode
        row count (``max_slots``) and the streaming-prefill pack length
        (``self._chunk``).  Attention: one entry per layer-kind
        (sink, window) at the decode cache length.  Shapes that miss at
        trace time (exotic archetypes, odd prefill prefixes) fall back to
        the deterministic perfmodel ranking — same impl family, still zero
        timed runs inside the trace.
        """
        from repro.kernels import autotune
        cfg, tc, rt = self.cfg, self.cfg.ternary, self.rt
        cache = autotune.default_cache()
        before = cache.timed_runs
        das = tc.das if (tc.enabled and tc.das is not None) else None
        pairs = {(cfg.d_model, cfg.q_dim), (cfg.d_model, cfg.kv_dim),
                 (cfg.q_dim, cfg.d_model), (cfg.d_model, cfg.d_ff),
                 (cfg.d_ff, cfg.d_model)}
        for m in sorted({self.max_slots, self._chunk}):
            for k, n in sorted(pairs):
                if das is not None:
                    autotune.tune("das_ternary_gemm", cache=cache, m=m, k=k,
                                  n=n, keep=das.keep, block=das.block)
                else:
                    autotune.tune("ternary_gemm", cache=cache, m=m, k=k, n=n,
                                  keep=0, block=0)
        for kind in set(cfg.layer_kinds()) & {"attn", "local"}:
            sink, window = A.kind_sink_window(cfg, kind, rt.serve_sparse)
            lk = (sink + window) if sink < A.FULL_SINK else self.max_len
            autotune.tune("sparse_attn", cache=cache, **autotune.attn_dims(
                hq=cfg.n_heads, hkv=cfg.n_kv_heads, lq=1, lk=lk,
                d=cfg.head_dim_, sink=sink, window=window))
        self.stats.autotune_timed_runs += cache.timed_runs - before

    # -- jitted pieces ----------------------------------------------------

    def _fold_key(self, uid, counter):
        return jax.random.fold_in(jax.random.fold_in(self._base_key, uid),
                                  counter)

    def _step_fn(self, sparams, caches, pt, tok, t, temps, uids, counters,
                 active, forced, forced_x):
        """One batched decode tick: embed -> decode_step -> sample.

        tok (B,) int32 inputs; t (B,) per-sequence positions (paged layout:
        -1 on inactive rows routes their writes to the null page); pt is the
        (B, pages_per_seq) page table (None under per-slot layouts) — passed
        as plain data so host-side page allocation never retraces; forced/
        forced_x override the input with raw prompt embeddings for
        stub-frontend models still absorbing their prompt tail.
        """
        if self._uses_embeds:
            x = jnp.take(sparams["embed"], tok, axis=0).astype(jnp.float32)
            x = jnp.where(forced[:, None], forced_x, x)[:, None, :]
        else:
            x = tok
        logits, caches, *moe = MD.decode_step(
            sparams, self.cfg, caches, x, t, self.rt, pt,
            with_stats=self.cfg.moe is not None)
        with jax.named_scope("sample"):
            keys = jax.vmap(self._fold_key)(uids, counters)
            next_tok = self._sampler(logits, keys, temps)
            next_tok = jnp.where(active, next_tok, 0)
        return (next_tok, caches, *moe)

    def _insert_fn(self, big, small, slot):
        """Overwrite one slot's rows with a batch-1 cache pytree."""
        stacked = None
        if big["stacked"] is not None:
            stacked = jax.tree.map(lambda bg, sm: bg.at[:, slot].set(
                sm[:, 0].astype(bg.dtype)), big["stacked"], small["stacked"])
        tail = jax.tree.map(lambda bg, sm: bg.at[slot].set(
            sm[0].astype(bg.dtype)), big["tail"], small["tail"])
        return {"stacked": stacked, "tail": tail}

    # -- paged-layout jitted pieces ---------------------------------------
    # All trace once per engine: layer structure (which layers are arenas)
    # is static, page ids / slot index are data.

    def _insert_paged_fn(self, big, small, slot, page_vec):
        """Insert a fresh batch-1 prefill under the paged layout: dense full
        caches scatter page-by-page into the arenas at ``page_vec`` (0 =
        unmapped -> lands in the null page, whose positions stay -1 since
        unprefilled dense rows carry pos -1); per-slot layers row-copy."""
        ps, n = self._page_size, self._pages_per_seq

        def paged(bg, sm, stacked):
            def put(pages, dense):
                if stacked:    # (G, P, ps, ...) <- (G, 1, n*ps, ...)
                    rows = dense[:, 0].reshape(
                        (dense.shape[0], n, ps) + dense.shape[3:])
                    return pages.at[:, page_vec].set(rows.astype(pages.dtype))
                rows = dense[0].reshape((n, ps) + dense.shape[2:])
                return pages.at[page_vec].set(rows.astype(pages.dtype))
            return {"k_pages": put(bg["k_pages"], sm["k"]),
                    "v_pages": put(bg["v_pages"], sm["v"]),
                    "pos_pages": put(bg["pos_pages"], sm["pos"])}

        def rows(bg, sm, stacked):
            if stacked:
                return jax.tree.map(lambda b_, s_: b_.at[:, slot].set(
                    s_[:, 0].astype(b_.dtype)), bg, sm)
            return jax.tree.map(lambda b_, s_: b_.at[slot].set(
                s_[0].astype(b_.dtype)), bg, sm)

        stacked = None
        if big["stacked"] is not None:
            stacked = tuple(
                paged(bg, sm, True) if is_p else rows(bg, sm, True)
                for is_p, bg, sm in zip(self._paged_stacked, big["stacked"],
                                        small["stacked"]))
        tail = tuple(
            paged(bg, sm, False) if is_p else rows(bg, sm, False)
            for is_p, bg, sm in zip(self._paged_tail, big["tail"],
                                    small["tail"]))
        return {"stacked": stacked, "tail": tail}

    def _insert_shared_fn(self, big, rest, slot):
        """Restore a prefix entry's snapshot of the NON-paged layers into
        one slot's rows (arenas untouched: shared pages arrive via the page
        table).  ``rest`` mirrors the cache structure with paged layers
        replaced by empty tuples (_snapshot_rest)."""
        def one(bg, sm, stacked):
            if KV.is_paged(bg):
                return bg
            if stacked:
                return jax.tree.map(lambda b_, s_: b_.at[:, slot].set(
                    s_[:, 0].astype(b_.dtype)), bg, sm)
            return jax.tree.map(lambda b_, s_: b_.at[slot].set(
                s_[0].astype(b_.dtype)), bg, sm)

        stacked = None
        if big["stacked"] is not None:
            stacked = tuple(one(bg, sm, True) for bg, sm in
                            zip(big["stacked"], rest["stacked"]))
        tail = tuple(one(bg, sm, False) for bg, sm in
                     zip(big["tail"], rest["tail"]))
        return {"stacked": stacked, "tail": tail}

    def _snapshot_rest(self, small):
        """Host (numpy) snapshot of the non-paged layers of a batch-1 cache
        pytree, with paged layers as empty tuples; None when every layer is
        paged (nothing beyond pages to restore)."""
        if self._rest_is_empty:
            return None

        def one(is_p, sm):
            return () if is_p else jax.tree.map(np.asarray,
                                                jax.device_get(sm))
        stacked = None
        if small["stacked"] is not None:
            stacked = tuple(one(is_p, sm) for is_p, sm in
                            zip(self._paged_stacked, small["stacked"]))
        tail = tuple(one(is_p, sm) for is_p, sm in
                     zip(self._paged_tail, small["tail"]))
        return {"stacked": stacked, "tail": tail}

    def _copy_page_fn(self, caches, src, dst):
        """Copy-on-write: duplicate arena page ``src`` into ``dst`` in every
        paged layer."""
        def one(is_p, layer, stacked):
            if not is_p:
                return layer
            if stacked:
                return {k: v.at[:, dst].set(v[:, src])
                        for k, v in layer.items()}
            return {k: v.at[dst].set(v[src]) for k, v in layer.items()}

        stacked = None
        if caches["stacked"] is not None:
            stacked = tuple(one(is_p, c, True) for is_p, c in
                            zip(self._paged_stacked, caches["stacked"]))
        tail = tuple(one(is_p, c, False) for is_p, c in
                     zip(self._paged_tail, caches["tail"]))
        return {"stacked": stacked, "tail": tail}

    def _scrub_fn(self, caches, ids):
        """Reset pos_pages to -1 for the (fixed-length, 0-padded) page-id
        vector ``ids`` — freed pages must be masked before reuse (the null
        page 0 is always -1, so padding is harmless)."""
        def one(is_p, layer, stacked):
            if not is_p:
                return layer
            pp = layer["pos_pages"]
            pp = pp.at[:, ids].set(-1) if stacked else pp.at[ids].set(-1)
            return {**layer, "pos_pages": pp}

        stacked = None
        if caches["stacked"] is not None:
            stacked = tuple(one(is_p, c, True) for is_p, c in
                            zip(self._paged_stacked, caches["stacked"]))
        tail = tuple(one(is_p, c, False) for is_p, c in
                     zip(self._paged_tail, caches["tail"]))
        return {"stacked": stacked, "tail": tail}

    def _scrub_slot_fn(self, big, empty, slot):
        """Retirement hygiene: reset one slot's rows in every NON-paged
        layer back to the empty cache (full/ring rows to pos -1, recurrent
        states and ssd replay buffers to zeros).  Admission always
        overwrites these rows anyway, but scrubbing at retirement keeps a
        finished request's KV and state from outliving it — no layout of
        the union is exempt (paged arenas are scrubbed page-wise by
        `_scrub_pages` instead)."""
        def one(is_p, bg, sm, stacked):
            if is_p:
                return bg
            if stacked:
                return jax.tree.map(lambda b_, s_: b_.at[:, slot].set(
                    s_[:, 0].astype(b_.dtype)), bg, sm)
            return jax.tree.map(lambda b_, s_: b_.at[slot].set(
                s_[0].astype(b_.dtype)), bg, sm)

        stacked = None
        if big["stacked"] is not None:
            stacked = tuple(one(is_p, bg, sm, True) for is_p, bg, sm in
                            zip(self._paged_stacked, big["stacked"],
                                empty["stacked"]))
        tail = tuple(one(is_p, bg, sm, False) for is_p, bg, sm in
                     zip(self._paged_tail, big["tail"], empty["tail"]))
        return {"stacked": stacked, "tail": tail}

    def _scrub_pages(self, freed: list) -> None:
        """Host wrapper: scrub freed pages in fixed-size batches so the
        jitted scrub never retraces."""
        if not freed or not self._pages_per_seq:
            return
        w = self._pages_per_seq
        for i in range(0, len(freed), w):
            ids = np.zeros(w, np.int32)
            chunk = freed[i:i + w]
            ids[:len(chunk)] = chunk
            self.caches = self._scrub(self.caches, jnp.asarray(ids))

    # -- public API -------------------------------------------------------

    def validate(self, req: Request) -> None:
        """Shape/capacity checks for a prospective request (raises
        ValueError).  Pure read — safe to call from any thread before
        handing the request to `submit` (the HTTP front door validates in
        its event loop so a bad request 400s without touching the engine
        thread)."""
        if req.prompt_len < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.uid}: max_new_tokens must be >= 1")
        if self._has_full and req.prompt_len + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt {req.prompt_len} + gen "
                f"{req.max_new_tokens} exceeds max_len {self.max_len} "
                f"(a full-cache layer is active)")
        if self._pages_per_seq:
            worst = -(-(req.prompt_len + req.max_new_tokens)
                      // self._page_size)
            usable = self._pool.num_pages - 1
            if worst > usable:
                raise ValueError(
                    f"request {req.uid}: needs up to {worst} KV pages but "
                    f"the pool holds {usable} (raise num_pages or page_size)")

    def submit(self, req: Request) -> None:
        self.validate(req)
        with self._lock:
            # duplicate uids among in-flight work would collide in the
            # results dict AND share a sampling-key stream (correlated
            # draws); a finished-but-unclaimed result would be clobbered —
            # pop_result/drain_results release the uid for reuse
            in_flight = {s.req.uid for s in self._slots if s.req is not None}
            in_flight |= {snap["req"].uid for snap in self._replays}
            if req.uid in in_flight or req.uid in self._pending_uids:
                raise ValueError(f"request uid {req.uid} already in flight")
            if req.uid in self._results:
                raise ValueError(
                    f"request uid {req.uid} has an unclaimed result; "
                    f"pop_result/drain_results it before resubmitting")
            self._pending_uids.add(req.uid)
            self._submit_t[req.uid] = time.perf_counter()
            self.scheduler.add(req)

    def pop_result(self, uid: int) -> RequestResult | None:
        """Claim (and remove) one finished result, releasing its uid for
        reuse; None when the uid has no finished result yet.  The
        long-running counterpart of `run()`'s bulk drain — an always-on
        server pops each result as it streams out so `_results` stays
        bounded and uids can cycle."""
        with self._lock:
            return self._results.pop(uid, None)

    def drain_results(self) -> dict[int, RequestResult]:
        """Claim every finished result (uid -> RequestResult), releasing
        all their uids for reuse."""
        with self._lock:
            out, self._results = self._results, {}
            return out

    def kernel_fallback_deltas(self) -> dict:
        """THIS engine's silent jnp-reference fallbacks: the process-wide
        kernels/ops counters minus the baseline snapshotted at
        construction / reset_clock, so co-resident engines (two engines in
        one benchmark process) never attribute each other's fallbacks."""
        out = {}
        for (op, key), cnt in ops.fallback_counts().items():
            delta = cnt - self._fallback_base.get((op, key), 0)
            if delta > 0:
                out[f"{op}{key}"] = delta
        return out

    @property
    def num_active(self) -> int:
        return sum(s.state != FREE for s in self._slots)

    def reset_clock(self) -> None:
        """Zero the virtual clock and stats between traces (caches and jit
        compilation caches survive — use to warm up before a timed replay).
        Only valid when the engine is drained."""
        if self.num_active or self.scheduler or self._replays:
            raise RuntimeError("reset_clock on a non-drained engine")
        self.vtime = 0
        self.stats = EngineStats(
            max_slots=self.max_slots,
            autotune_timed_runs=self.stats.autotune_timed_runs)
        self._fallback_base = dict(ops.fallback_counts())

    def timed_replay(self, trace) -> dict[int, RequestResult]:
        """Replay `trace` twice — once to pay the XLA compiles, then timed
        with warm caches — and return the timed run's results (wall-clock
        stats reflect only the second replay)."""
        for r in trace:
            self.submit(r)
        self.run()
        self.reset_clock()
        for r in trace:
            self.submit(r)
        return self.run()

    def run(self) -> dict[int, RequestResult]:
        """Drain the queue; returns uid -> RequestResult."""
        t0 = time.perf_counter()
        while self.scheduler or self.num_active or self._replays:
            self._admit_ready()
            if not self.num_active:
                if self._replays:
                    continue      # deferred replay admission: retry
                nxt = self.scheduler.next_arrival()
                if nxt is None:   # nothing queued, nothing active
                    break
                self.vtime = max(self.vtime, nxt)   # idle fast-forward
                continue
            try:
                self.step_decode()
            except fault.WorkerFailure:
                self.recover()
        self.stats.wall_seconds += time.perf_counter() - t0
        # surface THIS engine's silent jnp-reference fallbacks (deltas vs
        # the per-engine baseline; a populated dict under a kernel mode
        # means some layer shapes are not slab-aligned and are quietly
        # running the slow reference path)
        self.stats.kernel_fallbacks = self.kernel_fallback_deltas()
        return self.drain_results()

    def run_forever(self, *, should_stop=None, poll=None,
                    idle_wait=None) -> None:
        """Always-on step-driver: the sibling of `run()` the HTTP front
        door owns.  Never drains `_results` — callers consume results
        incrementally via `on_finish` / `pop_result` (which is what keeps
        memory and the uid space bounded over an unbounded request
        stream).

        should_stop: checked once per iteration; True exits the loop.
        poll: called once per iteration before admission — the server
            drains its thread-safe submission inbox here so `submit` runs
            on the engine thread (the event loop never blocks on a jitted
            prefill).
        idle_wait: called when there is nothing active, nothing admissible
            and nothing future-dated — should block briefly for new work
            (e.g. wait on an event) and return False to exit.  When None,
            an idle engine exits (drain-and-return semantics, like run()).

        Future-dated arrivals still fast-forward the virtual clock, so a
        replayed trace behaves exactly as under `run()`.
        """
        t0 = time.perf_counter()
        try:
            while True:
                if should_stop is not None and should_stop():
                    break
                if poll is not None:
                    with TraceAnnotation("serve.poll"):
                        poll()
                self._admit_ready()
                if self.num_active:
                    try:
                        self.step_decode()
                    except fault.WorkerFailure:
                        self.recover()
                    continue
                if self._replays:
                    continue      # deferred replay admission: retry
                nxt = self.scheduler.next_arrival()
                if nxt is not None:
                    if nxt > self.vtime:
                        self.vtime = nxt   # idle fast-forward
                    # else: a deferred (paged-pool) admission retries next
                    # iteration at the same vtime
                    continue
                if idle_wait is None or idle_wait() is False:
                    break
        finally:
            self.stats.wall_seconds += time.perf_counter() - t0
            self.stats.kernel_fallbacks = self.kernel_fallback_deltas()

    # -- elastic recovery --------------------------------------------------

    @property
    def topology(self):
        """The live Topology (None single-device); shrinks on recovery."""
        return self._topology

    def recover(self, lost_devices: int | None = None) -> None:
        """Survive a device/host loss mid-serving: snapshot every active
        slot (request + tokens generated so far), shrink the topology by
        ``lost_devices`` (default ``fault_lost_devices``; tp is preserved
        while it divides the survivor count, per elastic.plan_remesh),
        rebuild mesh/plan/caches/jits, and queue the snapshots for replay
        admission — in-flight requests resume from their last token, never
        dropped.  Single-device engines rebuild in place (lost capacity 0).
        """
        t0 = time.perf_counter()
        with TraceAnnotation("serve.recover"), self._lock:
            snaps = []
            for s in self._slots:
                if s.state == FREE:
                    continue
                snaps.append({
                    "req": s.req, "out": list(s.out),
                    "admit_vtime": s.admit_vtime, "admit_t": s.admit_t,
                    # no first token yet -> let replay stamp it on emission
                    "first_tok_vtime": s.first_tok_vtime if s.out else None,
                    "first_tok_t": s.first_tok_t if s.out else None,
                    "admitted_with_active": s.admitted_with_active})
                s.state = FREE
                s.req = None
                s.input_x = None
                s.tail = None
                s.pages = None
                s.page_budget = 0
            lost = (self.fault_lost_devices if lost_devices is None
                    else lost_devices)
            if self._topology is not None and lost > 0:
                self._topology = self._topology.shrink(
                    self._topology.n_devices - lost)
            self._build_device_state()
            self._replays.extend(snaps)
            self.stats.reshards += 1
            dt = time.perf_counter() - t0
            self.stats.recovery_seconds += dt
        if self.telemetry is not None:
            self.telemetry.on_reshard(self, lost=lost, seconds=dt,
                                      in_flight=len(snaps))

    # -- admission --------------------------------------------------------

    def _admit_ready(self) -> None:
        with self._lock:
            self._admit_ready_locked()

    def _maybe_preempt(self) -> None:
        """Deadline rescue: when every slot is busy and the queue head
        would miss its SLO even if admitted right now, truncate-and-retire
        the YOUNGEST active slot whose own deadline has already passed
        (its result is delivered as-is with ``preempted=True``).  Work
        that can still meet its SLO is never preempted, and requests
        without an SLO have no budget to be over — they are left alone."""
        if self.num_active < self.max_slots:
            return
        head = self.scheduler.peek_ready(self.vtime)
        if head is None or head.slo_steps is None:
            return
        slack = head.arrival + head.slo_steps - self.vtime
        # steps to finish once admitted: the unabsorbed prompt tail feeds
        # one token per tick, then one tick per generated token
        prefix = (head.prompt_len // self._chunk) * self._chunk
        needed = (head.prompt_len - prefix) + head.max_new_tokens
        if slack > needed:
            return   # still meetable without making room
        victim = None
        for i, s in enumerate(self._slots):
            if s.state != DECODE or s.req is None or s.req.slo_steps is None:
                continue
            if self.vtime <= s.req.arrival + s.req.slo_steps:
                continue   # within budget: not preemptible
            if victim is None or s.admit_vtime > self._slots[victim].admit_vtime:
                victim = i
        if victim is not None:
            self.stats.preemptions += 1
            self._retire(victim, preempted=True)

    def _admit_ready_locked(self) -> None:
        # recovery replays outrank fresh admissions: these requests were
        # already mid-stream when the failure hit and must never be dropped
        while self._replays:
            idx = next((i for i, s in enumerate(self._slots)
                        if s.state == FREE), None)
            if idx is None:
                break
            if not self._replay_admit(idx, self._replays[0]):
                break   # pool too tight right now: retry next tick
            self._replays.pop(0)
        if self.policy == "wave" and self.num_active:
            return
        if self._preempt:
            self._maybe_preempt()
        for i, slot in enumerate(self._slots):
            if slot.state != FREE:
                continue
            if self._moe_slot_cap and self.num_active >= self._moe_slot_cap:
                # expert-capacity accounting: each active slot contributes at
                # most one token per decode tick, and one expert can receive
                # at most one routed copy of each token — so active slots ==
                # the worst-case per-expert load.  Hold admissions until a
                # retirement frees capacity (never drop tokens mid-decode).
                nxt = self.scheduler.next_arrival()
                if nxt is not None and nxt <= self.vtime:
                    self.stats.moe_capacity_deferrals += 1
                return
            req = self.scheduler.pop_ready(self.vtime)
            if req is None:
                return
            if not self._admit(i, req):
                # pool too tight right now: requeue and retry next tick
                # (active slots retiring / evictions will free pages; with
                # zero active slots every non-slot page is evictable, so
                # the submit-time capacity check guarantees progress)
                self._pending_uids.add(req.uid)
                self.scheduler.add(req)
                return

    def _admit(self, idx: int, req: Request) -> bool:
        """Claim slot ``idx`` for ``req``; False defers admission (paged
        layout only: the pool cannot cover the request's worst case yet)."""
        slot = self._slots[idx]
        p = req.prompt_len
        prefix = (p // self._chunk) * self._chunk
        with TraceAnnotation("serve.admit", uid=req.uid, prompt_len=p,
                             packs=prefix // self._chunk):
            self._pending_uids.discard(req.uid)
            # mid-decode admission metric: slots already mid-stream
            # (admitted in an EARLIER tick) — same-tick co-admissions don't
            # count
            slot.admitted_with_active = sum(
                1 for s2 in self._slots
                if s2.state != FREE and s2.admit_vtime < self.vtime)
            slot.req = req
            slot.admit_vtime = self.vtime
            slot.admit_t = time.perf_counter()
            slot.out = []
            slot.input_x = None
            slot.first_tok_vtime = None
            slot.first_tok_t = None
            if self._paged:
                ok = self._admit_paged(idx, slot, req, prefix)
                if not ok:
                    slot.req = None     # back off: slot stays FREE
                return ok
            self._admit_dense(idx, slot, req, prefix)
            return True

    def _replay_admit(self, idx: int, snap: dict) -> bool:
        """Re-admit a snapshot taken by `recover()`: prefill the original
        prompt prefix, then force-feed prompt tail + already-generated
        tokens, so the slot's caches and sampling counters land exactly
        where they were — in-flight requests resume, never restart."""
        slot = self._slots[idx]
        req = snap["req"]
        prefix = (req.prompt_len // self._chunk) * self._chunk
        with TraceAnnotation("serve.admit", uid=req.uid,
                             prompt_len=req.prompt_len,
                             packs=prefix // self._chunk):
            slot.admitted_with_active = snap["admitted_with_active"]
            slot.req = req
            slot.admit_vtime = snap["admit_vtime"]
            slot.admit_t = snap["admit_t"]
            slot.out = list(snap["out"])
            slot.input_x = None
            slot.first_tok_vtime = None
            slot.first_tok_t = None
            replay = tuple(snap["out"])
            if self._paged:
                if not self._admit_paged(idx, slot, req, prefix,
                                         replay=replay, notify=False):
                    slot.req = None     # back off: slot stays FREE
                    return False
            else:
                self._admit_dense(idx, slot, req, prefix,
                                  replay=replay, notify=False)
            if snap["first_tok_vtime"] is not None:
                slot.first_tok_vtime = snap["first_tok_vtime"]
                slot.first_tok_t = snap["first_tok_t"]
            return True

    def _admit_dense(self, idx: int, slot: _Slot, req: Request,
                     prefix: int, replay: tuple = (),
                     notify: bool = True) -> None:
        if prefix > 0:
            logits, small = self._prefill_prompt(req, prefix)
        else:
            logits, small = None, self._empty1
        with TraceAnnotation("serve.admit.insert", uid=req.uid):
            self.caches = self._insert(self.caches, small, jnp.int32(idx))
        self._start_slot(idx, slot, req, prefix,
                         logits[0] if logits is not None else None,
                         replay=replay, notify=notify)

    def _prefill_prompt(self, req: Request, prefix: int):
        """Dispatch the batch-1 packed prefill of the prompt's first
        ``prefix`` tokens -> (logits (1, V), batch-1 caches)."""
        with TraceAnnotation("serve.admit.prefill", uid=req.uid):
            out = self._prefill(self.sparams,
                                jnp.asarray(req.prompt)[None, :prefix])
        self.stats.prefill_tokens += prefix
        return out

    def _feed(self, slot: _Slot, nxt) -> None:
        """Route one tail element into the decode step's input: raw
        embedding rows through forced_x, token ids through input_tok (a
        replayed tail mixes both for stub-frontend models — prompt rows are
        vectors, previously generated tokens are ids)."""
        if self._uses_embeds and np.ndim(nxt) > 0:
            slot.input_tok = 0
            slot.input_x = np.asarray(nxt, np.float32)
        else:
            slot.input_tok = int(nxt)
            slot.input_x = None

    def _start_slot(self, idx: int, slot: _Slot, req: Request,
                    absorbed: int, logits, replay: tuple = (),
                    notify: bool = True) -> None:
        """Common tail of admission: first token from prefill/stored logits
        when the whole prompt is absorbed, else token-by-token tail feed
        from position ``absorbed``.  ``replay`` (elastic recovery) appends
        already-generated tokens to the tail so the slot re-derives its
        exact pre-failure state through the same forced-feed machinery —
        sampling resumes at counter len(out), bitwise-continuing the
        original stream."""
        p = req.prompt_len
        if notify and self.telemetry is not None:
            self.telemetry.on_admit(req, self.vtime)
        if absorbed == p and not replay:
            with TraceAnnotation("serve.admit.sample", uid=req.uid):
                tok = self._sample1(jnp.asarray(logits), jnp.int32(req.uid),
                                    jnp.int32(len(slot.out)),
                                    jnp.float32(req.temperature))
            with TraceAnnotation("serve.admit.wait", uid=req.uid):
                tok = int(tok)
            slot.state = DECODE
            if slot.first_tok_vtime is None:
                slot.first_tok_vtime = self.vtime
                slot.first_tok_t = time.perf_counter()
            slot.out.append(tok)
            slot.input_tok = tok
            slot.input_pos = p
            self.stats.generated_tokens += 1
            if self.on_token is not None:
                self.on_token(req.uid, tok)
            if self._finished(slot, tok):
                self._retire(idx)
        else:
            slot.state = PREFILL
            slot.tail = list(req.prompt[absorbed:]) + list(replay)
            slot.tail_idx = 1
            slot.input_pos = absorbed
            self._feed(slot, slot.tail[0])

    # -- paged admission ---------------------------------------------------

    def _admit_paged(self, idx: int, slot: _Slot, req: Request,
                     prefix: int, replay: tuple = (),
                     notify: bool = True) -> bool:
        p, g, ps = req.prompt_len, req.max_new_tokens, self._page_size
        n_seq = self._pages_per_seq
        tokens = None
        if self._share and not self._uses_embeds:
            tokens = tuple(int(t) for t in np.asarray(req.prompt))

        # -- choose the best cached prefix --------------------------------
        # exact entry: pages + per-slot states + logits, bitwise-identical
        # to a fresh prefill of that prefix.  page-donor: whole pages inside
        # the longest common prefix with any stored prompt — reusable alone
        # only when every layer is paged (no ring/recurrent state to miss).
        shared_len, kind, entry = 0, None, None
        if tokens is not None:
            best, donor, common = self._radix.lookup(tokens)
            if best is not None and best.length >= 1:
                shared_len, kind, entry = best.length, "exact", best
            if self._rest_is_empty and donor is not None and n_seq:
                l_pages = (min(common, p - 1) // ps) * ps  # keep >=1 to feed
                if l_pages > shared_len:
                    shared_len, kind, entry = l_pages, "pages", donor

        total = -(-(p + g) // ps) if n_seq else 0
        register = self._share and tokens is not None and prefix > 0
        while True:
            if kind == "exact":
                n_cov = -(-shared_len // ps) if n_seq else 0
                shared_pages = tuple(entry.pages[:n_cov])
                # +1: a partial boundary page pinned by the trie gets CoW'd
                # on this slot's first write into it
                budget = (total - n_cov + (1 if shared_len % ps else 0)) \
                    if n_seq else 0
                immediate = 0
            elif kind == "pages":
                n_cov = shared_len // ps
                shared_pages = tuple(entry.pages[:n_cov])
                budget = total - n_cov
                immediate = 0
            else:
                n_cov, shared_pages = 0, ()
                immediate = -(-prefix // ps) if n_seq else 0
                budget = total + (1 if n_seq and register and prefix % ps
                                  else 0)
            if not n_seq or self._paged_room(budget, shared_pages):
                break
            # headroom short for this plan: degrade before deferring --
            # shared reuse -> fresh w/ registration -> fresh w/o -> defer.
            # The bare fresh plan needs exactly ``total`` pages, which the
            # submit-time capacity check bounds, so with zero active slots
            # (everything evictable) admission always eventually succeeds.
            if kind is not None:
                kind, entry, shared_len = None, None, 0
            elif register and prefix % ps:
                register = False
            else:
                return False

        # -- populate the slot's page table -------------------------------
        pages = [0] * max(n_seq, 1)
        if kind is not None:
            if shared_pages:
                self._pool.retain(shared_pages)
            pages[:len(shared_pages)] = [int(x) for x in shared_pages]
            entry.last_used = self.vtime
            entry.hits += 1
            self.stats.prefix_hits += 1
            self.stats.prompt_tokens_reused += shared_len
            if kind == "exact" and entry.state is not None:
                with TraceAnnotation("serve.admit.insert", uid=req.uid):
                    self.caches = self._insert_shared(
                        self.caches, entry.state, jnp.int32(idx))
            logits = entry.logits if (kind == "exact" and shared_len == p) \
                else None
            absorbed = shared_len
        else:
            if prefix > 0:
                lg, small = self._prefill_prompt(req, prefix)
            else:
                lg, small = None, self._empty1
            fresh = [self._alloc_page() for _ in range(immediate)]
            pages[:len(fresh)] = fresh
            page_vec = np.zeros(max(n_seq, 1), np.int32)
            page_vec[:len(fresh)] = fresh
            with TraceAnnotation("serve.admit.insert", uid=req.uid):
                self.caches = self._insert_paged(self.caches, small,
                                                jnp.int32(idx),
                                                jnp.asarray(page_vec))
            if register:
                ent = PrefixEntry(length=prefix, pages=tuple(fresh),
                                  state=self._snapshot_rest(small),
                                  logits=np.asarray(lg[0]),
                                  last_used=self.vtime)
                if self._radix.insert(tokens[:prefix], ent) and fresh:
                    self._pool.retain(fresh)
            logits = lg[0] if (lg is not None and prefix == p) else None
            absorbed = prefix
            budget -= immediate

        slot.pages = pages
        slot.page_budget = budget
        self._pt[idx, :] = pages
        if self._pool is not None:
            self.stats.pool_peak_pages = max(self.stats.pool_peak_pages,
                                             self._pool.pages_in_use)
        self._start_slot(idx, slot, req, absorbed, logits,
                         replay=replay, notify=notify)
        return True

    def _paged_room(self, need_new: int, reserve_exclude=()) -> bool:
        """Best-effort admission control: can the pool cover ``need_new``
        future allocations on top of every active slot's outstanding budget?
        Free pages plus trie-only (evictable) pages count; pages the request
        is about to retain are excluded.  Conservative against generation
        worst cases but not a hard guarantee — an exhausted pool raises at
        allocation time."""
        free = self._pool.free_count
        hold: dict[int, int] = {}
        for _, e in self._radix.items() if self._radix is not None else ():
            for pg in e.pages:
                hold[pg] = hold.get(pg, 0) + 1
        excl = {int(x) for x in reserve_exclude}
        evictable = sum(1 for pg, c in hold.items()
                        if pg not in excl and self._pool.refs[pg] == c)
        outstanding = sum(s.page_budget for s in self._slots
                          if s.state != FREE)
        return need_new + outstanding <= free + evictable

    def _alloc_page(self) -> int:
        pg = self._pool.alloc()
        while pg is None:
            if not self._evict_one():
                raise RuntimeError(
                    "kv page pool exhausted: every page is pinned by an "
                    "active slot (raise num_pages)")
            pg = self._pool.alloc()
        return pg

    def _evict_one(self) -> bool:
        """Drop the least-recently-used prefix entry, freeing its pages
        (those not also held by active slots)."""
        if self._radix is None or not len(self._radix):
            return False
        lru_toks, lru_used = None, None
        for toks, e in self._radix.items():
            if lru_used is None or e.last_used < lru_used:
                lru_toks, lru_used = toks, e.last_used
        entry = self._radix.remove(lru_toks)
        self._scrub_pages(self._pool.release(entry.pages))
        self.stats.prefix_evictions += 1
        return True

    def _ensure_writable_pages(self) -> None:
        """Pre-tick page-fault pass: every active slot's write position this
        tick must map a page this slot owns exclusively.  Null mapping ->
        lazy alloc; shared mapping (refcount > 1) -> copy-on-write."""
        ps = self._page_size
        for i, s in enumerate(self._slots):
            if s.state == FREE:
                continue
            pi = s.input_pos // ps
            phys = s.pages[pi]
            if phys == 0:
                new = self._alloc_page()
                s.pages[pi] = new
                self._pt[i, pi] = new
                s.page_budget = max(s.page_budget - 1, 0)
            elif self._pool.refs[phys] > 1:
                new = self._alloc_page()
                self.caches = self._copy_page(self.caches, jnp.int32(phys),
                                              jnp.int32(new))
                self._pool.release([phys])   # others still hold it: no free
                s.pages[pi] = new
                self._pt[i, pi] = new
                s.page_budget = max(s.page_budget - 1, 0)
                self.stats.cow_copies += 1
        self.stats.pool_peak_pages = max(self.stats.pool_peak_pages,
                                         self._pool.pages_in_use)

    # -- the decode tick --------------------------------------------------

    def step_decode(self) -> None:
        step = self.stats.decode_steps
        with StepTraceAnnotation("serve.tick", step_num=step):
            tick_t0 = time.perf_counter()
            if self.fault_injector is not None:
                # simulated device/host loss lands here, mid-serving; the
                # run loops catch WorkerFailure and call recover()
                self.fault_injector.maybe_fail(step)
            with TraceAnnotation("serve.tick.prepare", step_num=step):
                n_active, args = self._tick_inputs()
            with TraceAnnotation("serve.tick.dispatch", step_num=step):
                next_tok, self.caches, *moe = self._step(
                    self.sparams, self.caches, *args)
            with TraceAnnotation("serve.tick.wait", step_num=step):
                if moe:
                    next_tok = self._read_moe(next_tok, moe[0])
                next_tok = np.asarray(next_tok)

            self.stats.decode_steps += 1
            self.stats.active_slot_steps += n_active
            self.vtime += 1
            with TraceAnnotation("serve.tick.deliver", step_num=step):
                self._tick_deliver(next_tok)

            if self.telemetry is not None:
                self.telemetry.on_tick(self, n_active,
                                       time.perf_counter() - tick_t0)

    def _read_moe(self, next_tok, tick: dict):
        """Fetch a tick's tokens with its MoE routing counters (one
        transfer); fold them into the stats.  -> the tokens, on the host."""
        next_tok, tick = jax.device_get((next_tok, tick))
        self.moe_tick = tick
        st = self.stats
        st.moe_experts_hit += int(tick["experts_hit"].sum())
        st.moe_max_load = max(st.moe_max_load, int(tick["max_load"].max()))
        return next_tok

    def _tick_inputs(self) -> tuple[int, tuple]:
        """The decode step's per-slot inputs, built on the host and
        transferred: (active slot count, the step's arguments after the
        params and caches)."""
        b = self.max_slots
        tok = np.zeros((b,), np.int32)
        # paged: inactive rows carry t = -1 so their writes land on the null
        # page with pos -1 (keeping it permanently masked); dense layouts
        # keep the historical t = 0 don't-care
        t = np.full((b,), -1 if self._paged else 0, np.int32)
        temps = np.zeros((b,), np.float32)
        uids = np.zeros((b,), np.int32)
        counters = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        forced = np.zeros((b,), bool)
        d_model = self.cfg.d_model
        forced_x = np.zeros((b, d_model), np.float32)
        for i, s in enumerate(self._slots):
            if s.state == FREE:
                continue
            active[i] = True
            tok[i] = s.input_tok
            t[i] = s.input_pos
            temps[i] = s.req.temperature
            uids[i] = s.req.uid
            counters[i] = len(s.out)
            if s.input_x is not None:
                forced[i] = True
                forced_x[i] = s.input_x

        if self._paged and self._pages_per_seq:
            self._ensure_writable_pages()

        pt = jnp.asarray(self._pt) if self._paged else None
        return int(active.sum()), (
            pt, jnp.asarray(tok), jnp.asarray(t), jnp.asarray(temps),
            jnp.asarray(uids), jnp.asarray(counters), jnp.asarray(active),
            jnp.asarray(forced), jnp.asarray(forced_x))

    def _tick_deliver(self, next_tok: np.ndarray) -> None:
        """Advance every slot by the tick's tokens: feed the next prompt
        tail element, or deliver the sampled token (retiring finished
        slots)."""
        now = time.perf_counter()
        for i, s in enumerate(self._slots):
            if s.state == PREFILL:
                if s.tail_idx < len(s.tail):
                    s.input_pos += 1
                    self._feed(s, s.tail[s.tail_idx])
                    s.tail_idx += 1
                else:
                    # last prompt token went in this tick -> first sample
                    # (a replayed slot keeps its original first-token time)
                    s.state = DECODE
                    s.input_x = None
                    if s.first_tok_vtime is None:
                        s.first_tok_vtime = self.vtime
                        s.first_tok_t = now
                    self._deliver(i, int(next_tok[i]))
            elif s.state == DECODE:
                self._deliver(i, int(next_tok[i]))

    def _deliver(self, idx: int, tok: int) -> None:
        s = self._slots[idx]
        s.out.append(tok)
        s.input_tok = tok
        s.input_pos = s.req.prompt_len + len(s.out) - 1
        self.stats.generated_tokens += 1
        if self.on_token is not None:
            self.on_token(s.req.uid, tok)
        if self._finished(s, tok):
            self._retire(idx)

    def _finished(self, s: _Slot, tok: int) -> bool:
        return (len(s.out) >= s.req.max_new_tokens
                or (s.req.eos_id is not None and tok == s.req.eos_id))

    def _retire(self, idx: int, preempted: bool = False) -> None:
        s = self._slots[idx]
        r = s.req
        with TraceAnnotation("serve.retire", uid=r.uid):
            result = RequestResult(
                uid=r.uid, tokens=np.asarray(s.out, np.int32),
                prompt_len=r.prompt_len, arrival=r.arrival,
                admit_vtime=s.admit_vtime,
                first_token_vtime=s.first_tok_vtime,
                finish_vtime=self.vtime,
                admitted_with_active=s.admitted_with_active,
                slo_steps=r.slo_steps, preempted=preempted,
                submit_time=self._submit_t.pop(r.uid, None),
                admit_time=s.admit_t, first_token_time=s.first_tok_t)
            self._results[r.uid] = result
            if self._paged and s.pages is not None:
                held = [pg for pg in s.pages if pg]
                if held:
                    self._scrub_pages(self._pool.release(held))
                self._pt[idx, :] = 0
                s.pages = None
                s.page_budget = 0
            self.caches = self._scrub_slot(self.caches, self._empty1,
                                           jnp.int32(idx))
            s.state = FREE
            s.req = None
            s.input_x = None
            s.tail = None
            if self.telemetry is not None:
                self.telemetry.on_finish(result, self)
            if self.on_finish is not None:
                self.on_finish(result)

    # -- pool introspection ------------------------------------------------

    def pool_stats(self) -> dict:
        """Paged-pool occupancy snapshot (zeros for dense layouts).

        ``page_bytes`` is the per-page footprint summed across every paged
        layer arena; ``dense_equiv_bytes`` is what the same layers would pin
        under the per-slot full layout (max_slots x max_len rows)."""
        if not self._paged or self._pool is None:
            return {"layout": "dense", "page_size": 0, "num_pages": 0,
                    "pages_in_use": 0, "pages_peak": 0, "page_bytes": 0,
                    "bytes_in_use": 0, "bytes_peak": 0,
                    "dense_equiv_bytes": 0, "prefix_entries": 0}
        peak = max(self.stats.pool_peak_pages, self._pool.pages_in_use)
        return {
            "layout": "paged",
            "page_size": self._page_size,
            "num_pages": self._pool.num_pages,
            "pages_in_use": self._pool.pages_in_use,
            "pages_peak": peak,
            "page_bytes": self._page_bytes,
            "bytes_in_use": self._pool.pages_in_use * self._page_bytes,
            "bytes_peak": peak * self._page_bytes,
            "dense_equiv_bytes": (self.max_slots * self._pages_per_seq
                                  * self._page_bytes),
            "prefix_entries": len(self._radix) if self._radix else 0,
        }
