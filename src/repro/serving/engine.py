"""Layer-stepped serving engine with QoS token buffering (Algorithm 2).

Continuous-batching decode engine for LM-family models.  Each forward
iteration advances every active request by one token, executing the
network at MoE-boundary granularity so the engine can apply the paper's
token buffering exactly where Algorithm 2 specifies: *after* a layer's
gate is computed and *before* its experts execute.  A deferred request
keeps its post-attention hidden state (the carried residual stream) and
sub-layer progress and resumes from the same MoE boundary in a later
iteration — outputs are bit-identical to an undeferred run (asserted by
tests); only latency changes.

Two execution paths share one set of per-layer entry points
(``transformer.decode_*``), so they are bit-identical by construction:

* **fused** (default) — everything between MoE boundaries runs as one
  donated-buffer jitted mega-step (``repro.serving.megastep``): a
  steady-state decode iteration is ``k + 1`` compiled dispatches.  When
  no boundary decision needs host values (deferral off, static
  schedule) the pass is **sync-free**: the segments are dispatched back
  to back on device-resident masks, and every boundary's counts (and
  the prefill chunk's) are read with the logits batch in **one**
  fetch, after which the host bookkeeping runs in boundary order
  (``stats["sync_free_passes"]``).  With Algorithm-2 deferral or a
  dynamic schedule the host decides at each boundary: **one host sync
  per MoE boundary** (a single ``device_get((counts, indices))``
  feeding deferral, the workload trace, and the LoadTracker EMA) plus
  one logits fetch for sampling (and a recount at a boundary that
  defers a row).  Every read goes through ``Engine._fetch``, counted
  in ``stats["host_syncs"]`` and pinned by tests;
* **legacy** (``ServeConfig(fused=False)``, and the automatic fallback
  under a distributed mesh) — the original eager per-layer Python loop.

Each MoE layer is **routed exactly once per iteration** (the pipeline's
route stage, ``repro.core.gating``): the same :class:`Routing` drives
the deferral decision, the paired-load trace, *and* the expert
execution (threaded into ``moe_block(routing=...)``), so the gate never
runs twice.  Per-layer :class:`~repro.core.trajectory.LoadTracker`
EMAs feed the observed expert counts back into the scheduler; with
``ExecutionSpec.schedule == "dynamic"`` each layer executes along the
EMA-built paired-load trajectory — in the fused path the trajectory
enters the compiled segment as a traced ``(E,)`` order array, so
re-planning every iteration never retraces.

Admission comes in two flavors: the legacy one-shot ``submit`` (full
prompt prefilled at batch=1 and merged into the batched cache slots) and
**chunked prefill** (``submit_chunked`` — no compute at admission; each
iteration's prefill-chunk stage appends up to ``chunk_tokens`` prompt
tokens per prefilling slot in one batched pass piggybacked on the decode
batch, so long prompts never block an iteration — the continuous-batching
scheduler in ``repro.serving.scheduler`` drives this path).  The
per-iteration expert token counts (decode route stage *and* prefill
chunks, tagged ``phase``) feed the paired-load policy and the deferral
decisions, and are exported for the chiplet simulator to replay (the JAX
engine and the cycle-level sim share one workload trace format — see
docs/trace-format.md).

Sequence state lives in the **paged state pool**
(``repro.serving.statepool``): attention KV in fixed-size physical
pages indexed per slot through one host page table (pushed to the
device once per iteration, traced — never retraces), Mamba2 state dense
per slot with by-value snapshots.  The pool underpins **prefix
caching** (content-hashed prompt prefixes admit with near-zero compute,
``ServeConfig.prefix_cache``) and **preemption**
(:meth:`Engine.preempt` / :meth:`Engine.restore` — bit-identical
eviction and resumption, driven by the scheduler under queue pressure).

Every trace record also carries ``modeled_s`` — the closed-form
chiplet-array seconds of that layer's observed expert flow
(``autotune.ServingCostModel``); their per-iteration sum is surfaced as
``last_step_modeled_s``, which the scheduler's modeled clock integrates
into machine-independent TTFT/TPOT seconds (see docs/benchmarks.md and
the ``sim.modes.replay_trace`` referee).

Host spans (``jax.profiler.TraceAnnotation``, free unless a profiler
trace is being captured) split each iteration on the device's clock:
``engine.step`` > ``engine.pages`` / ``engine.dispatch`` (one per
jitted call) / ``engine.fetch`` / ``engine.boundary`` /
``engine.sample``, and ``gc`` around each garbage collection; JAX
compile events inside a step are counted in ``stats["compiles"]`` (see
docs/architecture.md, "Spans and counters").
"""
from __future__ import annotations

import gc
import itertools
import threading
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core import autotune, gating, trajectory
from repro.core.policies import TokenBufferPolicy, paired_load_order
from repro.models import api, transformer
from repro.serving import megastep, statepool

_ALIAS_WARNED: set = set()

# JAX's compile-path events (jax 0.9.0: ``jax._src.dispatch`` and
# ``jax._src.compiler``): tracing a function to a jaxpr, lowering the
# jaxpr to MLIR, the backend compile, and a load from the persistent
# compile cache (which happens inside the backend-compile event).
JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_EVENTS = (JAXPR_TRACE, LOWERING, BACKEND_COMPILE, CACHE_LOAD)


class CompileCounter:
    """Count of JAX's compile-path events in this process, and the wall
    seconds they cover.

    Traces nest (tracing a jitted function traces the jitted functions
    it calls), so the seconds are the length of the union of the
    events' time spans, not the sum of their durations.  A span ends
    after every span nested in it, so the union is kept as a stack of
    disjoint intervals ordered by end.  One listener serves every engine
    (JAX's listeners are process-wide): :meth:`Engine.step` reads it
    before and after an iteration."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self._covered: List[Tuple[float, float]] = []
        self._lock = threading.Lock()

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            with self._lock:
                self.count += 1

    def on_span(self, event: str, start: float, end: float, **_) -> None:
        if event not in COMPILE_EVENTS:
            return
        with self._lock:
            cov = self._covered
            while cov and cov[-1][1] >= start:
                a, b = cov.pop()
                self.seconds -= b - a
                start, end = min(start, a), max(end, b)
            cov.append((start, end))
            self.seconds += end - start

    def read(self) -> Tuple[int, float]:
        with self._lock:
            return self.count, self.seconds


class GcSpan:
    """A ``gc`` host span around each collection of Python's garbage
    collector (``generation`` in its args), so a pause shows in a
    profiler trace under its own name wherever in the loop it lands."""

    def __init__(self):
        self._span = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._span = TraceAnnotation("gc", generation=info["generation"])
            self._span.__enter__()
        elif self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None


_COMPILES: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    """The process's compile counter.  Its first use registers the
    process-wide hooks: the counter's JAX listeners and the ``gc`` span."""
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(
            _COMPILES.on_duration)
        jax.monitoring.register_event_time_span_listener(_COMPILES.on_span)
        gc.callbacks.append(GcSpan())
    return _COMPILES


def _warn_alias(old: str, new: str) -> None:
    """One-shot DeprecationWarning per legacy ServeConfig alias."""
    if old in _ALIAS_WARNED:
        return
    _ALIAS_WARNED.add(old)
    warnings.warn(f"ServeConfig.{old} is deprecated; use {new} "
                  f"(see README migration table)", DeprecationWarning,
                  stacklevel=4)


@dataclass
class ServeConfig:
    max_batch: int = 8
    max_ctx: int = 256
    buffering_slack: float = 0.0
    theta_min: int = 2
    n_threshold: Optional[int] = None   # default derived from slack
    chunk_tokens: int = 16              # prefill chunk size (submit_chunked)
    # paged state pool (repro.serving.statepool): attention KV lives in
    # fixed-size physical pages indexed per slot through a host page
    # table; Mamba2 state stays dense per slot and snapshots by value.
    # pool_pages=None sizes the pool at twice the slot capacity, the
    # headroom prefix entries and preemption handles live in.
    page_size: int = 8
    pool_pages: Optional[int] = None
    # prefix caching: chunked-prefill state is content-hashed by the
    # prompt-prefix chain; a later request sharing a cached prefix
    # admits with the pages attached and only computes the suffix.
    # Off by default — it changes stats["prefill_tokens"] accounting.
    prefix_cache: bool = False
    max_prefix_entries: int = 64
    # preemption: when the scheduler's admission queue is deeper than
    # this bound and no slot is free, one restorable request is evicted
    # to the pool per step (None = never preempt)
    preempt_queue_depth: Optional[int] = None
    # Serving must be batching-invariant: a request's tokens may not
    # depend on who shares the batch.  Capacity dispatch drops tokens
    # past C = ceil(T*k/E * capacity_factor) per expert, and *which*
    # tokens overflow depends on the other rows — so by default the
    # engine raises the capacity factor to the drop-free bound (C = T*k).
    # Set False for the paper-faithful finite-buffer EP semantics.
    drop_free: bool = True
    # fused mega-step iteration (repro.serving.megastep): one compiled
    # segment per MoE-boundary span, at most one host sync per boundary
    # (one per iteration when no boundary needs a host decision).
    # False keeps the eager per-layer loop (bit-identical, much slower);
    # a distributed mesh falls back to the legacy loop automatically.
    fused: bool = True
    # single MoE execution configuration object (repro.core.strategy):
    # a spec, strategy name, or dict; replaces the old moe_impl/autotune
    # string knobs (kept below as deprecated aliases merged into it)
    spec: Optional[object] = None
    moe_impl: Optional[str] = None      # deprecated: use spec
    autotune: Optional[str] = None      # deprecated: use spec.autotune
    ema_decay: float = 0.8              # LoadTracker decay (dynamic sched)
    # EMA-hot expert weight tiering: pin each MoE layer's LoadTracker-
    # hottest experts resident on-package under this total byte budget
    # (split evenly across MoE layers); resident experts skip their DDR
    # stream in the modeled clock, the trace records (``resident``), and
    # the ``sim.modes.replay_trace`` referee.  Accounting-only — tokens
    # are bit-identical with tiering on or off.  0 disables the tier.
    resident_budget_mb: float = 0.0
    # hybrid two-tier placement: fast-tier expert count per MoE layer
    # when the spec uses the ``hybrid`` strategy (None = the registry
    # default, ``strategy.default_hot`` — top quartile).  The engine
    # repartitions per iteration off each layer's LoadTracker EMA and
    # records the partition in the trace (``hot`` ids, like
    # ``resident``); on homogeneous hardware the partition is
    # placement-only and tokens are bit-identical either way.
    hot_experts: Optional[int] = None
    temperature: float = 0.0            # 0 = greedy
    seed: int = 0

    def __post_init__(self):
        from dataclasses import replace
        from repro.core.strategy import ExecutionSpec
        if self.moe_impl is not None:
            _warn_alias("moe_impl",
                        'ServeConfig.spec=ExecutionSpec(strategy=...)')
        if self.autotune is not None:
            _warn_alias("autotune", "ExecutionSpec.autotune")
        base = self.spec if self.spec is not None else (self.moe_impl
                                                        or "capacity")
        sp = ExecutionSpec.coerce(base, default="capacity")
        if self.autotune is not None:
            sp = replace(sp, autotune=self.autotune)
        elif sp.autotune is None:
            sp = replace(sp, autotune="analytic")
        self.spec = sp.validate()
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.preempt_queue_depth is not None \
                and self.preempt_queue_depth < 0:
            raise ValueError("preempt_queue_depth must be >= 0 (or None "
                             "to disable preemption)")
        if self.resident_budget_mb < 0:
            raise ValueError("resident_budget_mb must be >= 0 "
                             f"(got {self.resident_budget_mb})")


@dataclass
class RequestState:
    rid: str
    slot: int
    prompt_len: int
    max_new: int
    generated: List[int] = field(default_factory=list)
    progress: int = 0                   # sub-layer pointer: 2*layer (+1 = moe pending)
    done: bool = False
    deferred_iterations: int = 0
    # chunked-prefill lifecycle: "prefill" rows consume chunk_tokens
    # prompt tokens per iteration until the prompt is exhausted, then
    # join the decode batch ("decode") with their first sampled token
    phase: str = "decode"
    prompt: List[int] = field(default_factory=list)   # pending prompt tokens
    prefill_pos: int = 0                              # tokens already cached
    # prompt-prefix hash chain (statepool.hash_chain), computed once at
    # chunked admission when ServeConfig.prefix_cache is on
    prefix_keys: List[bytes] = field(default_factory=list)
    preemptions: int = 0                # times evicted to the state pool


class QueueFullError(RuntimeError):
    """No free engine slot.  A RuntimeError subclass so pre-existing
    ``except RuntimeError`` callers keep working; the continuous-batching
    scheduler catches this *type* to requeue instead of crashing."""


# deferral disabled when the activation threshold is effectively inf
_DEFER_OFF = 1 << 29


class Engine:
    def __init__(self, params, cfg: ModelConfig, scfg: ServeConfig):
        assert not cfg.is_encoder_decoder, "engine serves LM-family models"
        self.params = params
        if scfg.drop_free and cfg.moe is not None \
                and cfg.moe.capacity_factor < cfg.moe.num_experts:
            import dataclasses
            cfg = cfg.replace(moe=dataclasses.replace(
                cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
        self.cfg = cfg
        self.scfg = scfg
        self.p, self.plan = transformer.cached_period_plan(cfg)
        self.L = cfg.num_layers
        pages_per_slot = -(-scfg.max_ctx // scfg.page_size)
        num_pages = (scfg.pool_pages if scfg.pool_pages is not None
                     else 2 * scfg.max_batch * pages_per_slot)
        self.caches = transformer.init_paged_caches(
            cfg, scfg.max_batch, num_pages, scfg.page_size)
        page_b, ssm_b = statepool.state_bytes(self.caches)
        # host-side page/refcount/prefix bookkeeping; device arrays stay
        # owned by the engine (self.caches), the pool tells it what to do
        self.pool = statepool.StatePool(
            max_batch=scfg.max_batch, max_ctx=scfg.max_ctx,
            page_size=scfg.page_size, num_pages=num_pages,
            max_prefix_entries=scfg.max_prefix_entries,
            bytes_per_page=page_b, ssm_bytes_per_row=ssm_b)
        self._has_ssm = statepool.has_ssm(self.caches)
        # a latent-attention model's pages hold only latents
        self._latent_page_b = (page_b if statepool.has_latent(self.caches)
                               else 0)
        self._table_dev = jnp.asarray(self.pool.table)
        # host-side cache lengths: mutated in place (no device round-trip
        # per finished token), converted to a device array at call sites
        self.cache_len = np.zeros((scfg.max_batch,), np.int32)
        self.requests: Dict[str, RequestState] = {}
        # O(1) slot recycling: popleft to assign, append to recycle
        # (the old list.pop(0) was O(max_batch) per admission)
        self.free_slots = deque(range(scfg.max_batch))
        self.policy = TokenBufferPolicy.from_slack(scfg.buffering_slack,
                                                   theta_min=scfg.theta_min)
        if scfg.n_threshold is not None:
            self.policy.n_threshold = scfg.n_threshold
        self._x = jnp.zeros((scfg.max_batch, 1, cfg.d_model), jnp.dtype(cfg.dtype))
        self._rid = itertools.count()
        self._rng = np.random.default_rng(scfg.seed)
        self.iterations = 0
        self.stats = {"deferrals": 0, "expert_loads": 0, "expert_loads_saved": 0,
                      # experts with >= 1 counted row, and the rest, per
                      # MoE boundary and prefill layer (_count_streamed)
                      "experts_streamed": 0, "experts_skipped": 0,
                      "iterations": 0, "tokens_emitted": 0,
                      "dynamic_schedules": 0,
                      "prefill_chunks": 0, "prefill_tokens": 0,
                      # blocking device reads through _fetch: every one
                      # of the fused path (the sync-free pass's one read,
                      # or boundary counts, logits batch, prefill counts;
                      # first-token rows, deferral recounts); the legacy
                      # loop's slot recounts
                      "host_syncs": 0,
                      # fused decode passes that ran sync-free
                      "sync_free_passes": 0,
                      # JAX trace / lowering / compile / cache-load events
                      # inside Engine.step, and their seconds
                      "compiles": 0, "compile_s": 0.0,
                      # bytes of latent-attention pages in use, sampled
                      # at each step once its pages are allocated
                      "latent_cache_bytes": 0,
                      "preemptions": 0, "restores": 0}
        # state-pool counters (pages in use / peak, cache hit/miss/evict,
        # prefill tokens saved, resident bytes) live in the same dict:
        # the pool mutates engine stats directly
        self.stats.update(self.pool.stats)
        self.pool.stats = self.stats
        self.trace: List[dict] = []     # per (iter, layer) expert counts
        # per-MoE-layer EMA of observed expert counts — the load vector
        # fed back into the dynamic trajectory scheduler each iteration
        self.load_trackers: Dict[int, trajectory.LoadTracker] = {}
        # latest EMA-built Schedule per layer (written at the boundary,
        # executed by the following segment / _apply_moe)
        self._layer_schedules: Dict[int, trajectory.Schedule] = {}
        self.dynamic_schedule = scfg.spec.schedule == "dynamic"
        # closed-form chiplet-array clock: modeled seconds per trace
        # record, integrated per iteration into last_step_modeled_s.
        # The spec's streamed weight dtype feeds the clock its expert
        # bytes-per-param so int8/fp8 runs model the smaller DDR stream.
        from repro.kernels import quant
        self.cost_model = (autotune.ServingCostModel.from_config(
            cfg, weight_bytes=quant.weight_bytes(scfg.spec.weight_dtype))
            if cfg.moe is not None else None)
        # EMA-hot expert weight tier: the resident_budget_mb bytes split
        # evenly over MoE layers pin this many experts per layer
        n_moe = sum(1 for l in range(self.L)
                    if self._layer_kind(l)[1] == "moe")
        self._n_resident = 0
        if scfg.resident_budget_mb > 0 and self.cost_model is not None \
                and n_moe:
            per_layer = int(scfg.resident_budget_mb * 2 ** 20) // n_moe
            self._n_resident = int(min(cfg.moe.num_experts,
                                       per_layer // self.cost_model.expert_bytes))
        self.stats["resident_weight_bytes"] = (
            self._n_resident * n_moe * self.cost_model.expert_bytes
            if self.cost_model is not None else 0)
        self.stats["ddr_bytes_saved"] = 0
        # hybrid two-tier placement: per-iteration hot/cold repartition
        # off the LoadTracker EMA, recorded per trace record (``hot``)
        self._n_hot = 0
        if cfg.moe is not None \
                and "hybrid" in scfg.spec.strategies_used():
            from repro.core.strategy import default_hot
            self._n_hot = int(scfg.hot_experts
                              if scfg.hot_experts is not None
                              else default_hot(cfg.moe.num_experts))
            self._n_hot = max(1, min(cfg.moe.num_experts, self._n_hot))
        self._last_hot: Dict[int, Tuple[int, ...]] = {}
        self.stats["hybrid_repartitions"] = 0
        self.last_step_modeled_s = 0.0
        self._iter_modeled_s = 0.0
        # the sync-free pass's row mask on the device, and its slots
        self._pass_slots: Optional[Tuple[int, ...]] = None
        self._pass_mask_dev = None

    # ------------------------------------------------------------------
    # slot/param helpers
    # ------------------------------------------------------------------

    def _slot_params(self, layer: int):
        period_idx, slot = divmod(layer, self.p)
        return jax.tree.map(lambda a: a[period_idx], self.params["periods"][slot])

    def _layer_kind(self, layer: int) -> Tuple[str, str]:
        return self.plan[layer % self.p]

    # ------------------------------------------------------------------
    # admission (full-prompt prefill into a slot)
    # ------------------------------------------------------------------

    def _validate_request(self, prompt: List[int], max_new: int) -> None:
        if not prompt:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if len(prompt) + max_new > self.scfg.max_ctx:
            raise ValueError(
                f"request does not fit the context: len(prompt)={len(prompt)}"
                f" + max_new={max_new} > max_ctx={self.scfg.max_ctx} — "
                f"shorten the prompt or raise ServeConfig.max_ctx "
                f"(generation would be silently truncated)")

    def submit(self, prompt: List[int], max_new: int) -> str:
        self._validate_request(prompt, max_new)
        if not self.free_slots:
            raise QueueFullError("engine full — wait for completions")
        slot = self.free_slots.popleft()
        rid = f"req{next(self._rid)}"
        tokens = jnp.asarray(prompt, jnp.int32)[None]
        logits, caches1 = api.prefill_fn(self.params, {"tokens": tokens},
                                         self.cfg, self.scfg.max_ctx,
                                         spec=self.scfg.spec)
        # scatter the per-request dense caches into the slot's pool
        # pages (KV) and state row (SSM)
        self.pool.ensure(slot, len(prompt))
        self.caches = statepool.merge_prefill(
            self.caches, caches1, self.pool.slot_pages[slot], slot,
            self.scfg.page_size)
        self.cache_len[slot] = len(prompt)
        st = RequestState(rid=rid, slot=slot, prompt_len=len(prompt), max_new=max_new)
        first = self._sample(logits[0, -1])
        st.generated.append(int(first))
        self.requests[rid] = st
        return rid

    def submit_chunked(self, prompt: List[int], max_new: int) -> str:
        """Admit a request for chunked prefill: no compute happens here.

        The prompt is consumed ``chunk_tokens`` at a time by subsequent
        :meth:`step` calls (piggybacked on the decode batch), so
        admission never blocks an iteration; the first token is emitted
        by the step that caches the final prompt chunk.

        With ``ServeConfig.prefix_cache`` on, the longest cached prompt
        prefix (content-hashed chain, see repro.serving.statepool) is
        attached instead of recomputed: full pages share by refcount,
        the partial tail page copies, the SSM snapshot restores by
        value, and prefill resumes at the cached length — bit-identical
        to the cold run because per-token prefill outputs are
        chunk-partition-invariant under ``drop_free``."""
        self._validate_request(prompt, max_new)
        if not self.free_slots:
            raise QueueFullError("engine full — wait for completions")
        slot = self.free_slots.popleft()
        rid = f"req{next(self._rid)}"
        self.cache_len[slot] = 0
        st = RequestState(rid=rid, slot=slot, prompt_len=len(prompt),
                          max_new=max_new, phase="prefill",
                          prompt=list(prompt))
        hit = None
        if self.scfg.prefix_cache:
            st.prefix_keys = statepool.hash_chain(prompt)
            # at least one prompt token must run so first-token logits
            # exist — cap the usable prefix at len(prompt) - 1
            hit = self.pool.lookup_prefix(st.prefix_keys, len(prompt) - 1)
            if hit is not None:
                try:
                    copy = self.pool.attach_prefix(hit, slot)
                except statepool.PoolExhausted:
                    hit = None
            if hit is not None:
                if copy is not None:
                    self.caches = statepool.copy_page(self.caches, *copy)
                if hit.ssm != ():
                    self.caches = statepool.restore_ssm(self.caches,
                                                        hit.ssm, slot)
                self.cache_len[slot] = hit.length
                st.prefill_pos = hit.length
                self._record_event("cache_hit", rid=rid, slot=slot,
                                   cached_tokens=hit.length)
            else:
                self.stats["cache_misses"] += 1
        if hit is None and self._has_ssm:
            # a recycled slot must not leak the previous occupant's
            # recurrent state into a fresh prompt
            self.caches = statepool.zero_ssm(self.caches, slot)
        self.requests[rid] = st
        return rid

    def _sample_row(self, r: RequestState, logits) -> int:
        """Sample request ``r``'s next token from its logits row: a
        chunked-prefill request's first token, and every decode token.
        The seam a caller overrides to inspect the logits behind them."""
        return self._sample(logits)

    def _sample(self, logits) -> int:
        lf = np.asarray(logits, np.float32)
        if self.scfg.temperature <= 0:
            return int(lf.argmax())
        p = np.exp((lf - lf.max()) / self.scfg.temperature)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    # ------------------------------------------------------------------
    # one forward iteration (all active requests advance <= 1 token)
    # ------------------------------------------------------------------

    def active(self) -> List[RequestState]:
        return [r for r in self.requests.values() if not r.done]

    def prefilling(self) -> List[RequestState]:
        return [r for r in self.requests.values()
                if not r.done and r.phase == "prefill"]

    def _resident_for(self, layer: int) -> List[int]:
        """The layer's EMA-hot resident expert set: the ``_n_resident``
        hottest experts by LoadTracker EMA, ties broken by expert id —
        deterministic even before any traffic has been observed."""
        tracker = self.load_trackers.get(layer)
        if tracker is None or tracker.steps == 0:
            return list(range(self._n_resident))
        ema = np.asarray(tracker.ema, np.float64)
        hot = sorted(range(len(ema)), key=lambda e: (-ema[e], e))
        return sorted(hot[:self._n_resident])

    def _hot_for(self, layer: int) -> List[int]:
        """The layer's hybrid fast-tier expert set: the ``_n_hot``
        hottest experts by LoadTracker EMA (ties to the lower id) —
        identity prefix before any traffic, like ``_resident_for``."""
        tracker = self.load_trackers.get(layer)
        if tracker is None or tracker.steps == 0:
            return list(range(self._n_hot))
        ema = np.asarray(tracker.ema, np.float64)
        hot = sorted(range(len(ema)), key=lambda e: (-ema[e], e))
        return sorted(hot[:self._n_hot])

    def _record(self, rec: dict) -> None:
        """Append one workload-trace record, stamped with its modeled
        chiplet-array seconds (the per-iteration sum becomes
        ``last_step_modeled_s`` — the scheduler's modeled clock).

        With the EMA-hot weight tier on, the record also carries the
        layer's ``resident`` expert ids; resident experts that would
        have streamed this record skip their DDR term in the modeled
        clock and accrue ``stats["ddr_bytes_saved"]``.  With the
        ``hybrid`` strategy, it carries the fast-tier ``hot`` ids —
        the dynamic EMA repartition the two-tier replay referee
        (``sim.modes.replay_trace``) and the modeled clock price."""
        resident_n = 0
        if self._n_resident and "layer" in rec:
            resident = self._resident_for(rec["layer"])
            rec["resident"] = resident
            counts = rec["counts"]
            if rec["schedule"] == "dynamic":
                # a dynamic trajectory already skips idle experts: only
                # resident experts that routed tokens save a stream
                resident_n = sum(1 for e in resident if counts[int(e)] > 0)
            else:
                resident_n = len(resident)  # static plan loads every expert
            self.stats["ddr_bytes_saved"] += (resident_n
                                              * self.cost_model.expert_bytes)
        hot = None
        if self._n_hot and "layer" in rec:
            hot = self._hot_for(rec["layer"])
            rec["hot"] = hot
            prev = self._last_hot.get(rec["layer"])
            if prev is not None and prev != tuple(hot):
                self.stats["hybrid_repartitions"] += 1
            self._last_hot[rec["layer"]] = tuple(hot)
        if self.cost_model is not None:
            rec["modeled_s"] = self.cost_model.layer_s(
                rec["counts"], dynamic=rec["schedule"] == "dynamic",
                resident=resident_n, hot=hot)
            self._iter_modeled_s += rec["modeled_s"]
        self.trace.append(rec)

    def _record_event(self, event: str, **fields) -> None:
        """Append one *event* trace record (``cache_hit`` / ``preempt``
        / ``restore`` / ``compile``).  Event records carry no ``counts``
        and no modeled seconds — consumers that aggregate expert flow
        skip them (see docs/trace-format.md)."""
        self.trace.append({"iter": self.iterations, "event": event,
                           **fields})

    def _ensure_pages(self) -> None:
        """Host-side page allocation covering every KV write the coming
        iteration performs (the page table is read-only inside the
        jitted step).  A decode row writes one position; a prefill row
        writes its chunk, plus one more when the prompt completes (the
        row joins the decode batch in the same iteration)."""
        K = max(1, self.scfg.chunk_tokens)
        with TraceAnnotation("engine.pages"):
            for r in self.active():
                if r.phase == "prefill":
                    k_r = min(K, len(r.prompt) - r.prefill_pos)
                    length = int(self.cache_len[r.slot]) + k_r
                    if r.prefill_pos + k_r >= len(r.prompt):
                        length += 1
                else:
                    length = int(self.cache_len[r.slot]) + 1
                self.pool.ensure(r.slot, min(length, self.scfg.max_ctx))
            self._table_dev = jnp.asarray(self.pool.table)

    def _fetch(self, x, site: str, layer: int = -1):
        """A blocking device-to-host read (every one of the fused path
        goes through here): counted in ``stats["host_syncs"]`` and
        spanned as ``engine.fetch`` (the wait for the device plus the
        transfer)."""
        self.stats["host_syncs"] += 1
        with TraceAnnotation("engine.fetch", site=site, layer=layer):
            return jax.device_get(x)

    def _register_prefix(self, r: RequestState) -> None:
        """Cache the slot's state at this chunk boundary under the
        prompt-prefix content hash.  Full pages are shared by refcount;
        the pool returns a (src, dst) plan when the partial tail page
        needs its own copy.  Skipped quietly when the pool cannot spare
        a tail page even after LRU eviction."""
        P = r.prefill_pos
        snap = (statepool.snapshot_ssm(self.caches, r.slot)
                if self._has_ssm else ())
        try:
            copy = self.pool.register_prefix(r.prefix_keys[P - 1], P,
                                             r.slot, ssm=snap)
        except statepool.PoolExhausted:
            return
        if copy is not None:
            self.caches = statepool.copy_page(self.caches, *copy)

    def _prefill_chunk_step(self, fused: bool = False,
                            pending: Optional[list] = None
                            ) -> List[Tuple[str, int]]:
        """Advance every prefilling request by one prompt chunk.

        One batched ``prefill_chunk`` call covers all prefilling slots
        (decode/idle slots ride along fully masked, bit-untouched);
        per-layer expert counts from the chunk's gate pass feed the
        workload trace and the LoadTracker EMAs exactly like the decode
        path's route stage.  Requests whose prompt completes sample
        their first token from the last valid chunk position — the
        emission the scheduler timestamps as TTFT.  Given ``pending``
        (the sync-free pass), the counts are not read here: they join
        the pass's one read as ``(None, (), counts)``."""
        pre = self.prefilling()
        if not pre:
            return []
        scfg = self.scfg
        B, K = scfg.max_batch, max(1, scfg.chunk_tokens)
        tokens = np.zeros((B, K), np.int32)
        mask = np.zeros((B, K), bool)
        took: Dict[str, int] = {}
        for r in pre:
            k_r = min(K, len(r.prompt) - r.prefill_pos)
            tokens[r.slot, :k_r] = r.prompt[r.prefill_pos:r.prefill_pos + k_r]
            mask[r.slot, :k_r] = True
            took[r.rid] = k_r
        if fused:
            ms = megastep.get_megastep(self.cfg, self.scfg)
            with TraceAnnotation("engine.dispatch", segment=ms.PREFILL):
                # a copy: the loop below bumps cache_len in place while
                # the program may still wait to read it
                hid, self.caches, counts = ms.prefill(
                    self.params, tokens, self.caches,
                    jnp.asarray(self.cache_len.copy()), self._table_dev,
                    jnp.asarray(mask))
            if pending is None:
                counts = self._fetch(counts, "prefill_counts")
        else:
            hid, self.caches, counts = api.prefill_chunk_fn(
                self.params, jnp.asarray(tokens), self.caches,
                jnp.asarray(self.cache_len), self.cfg, spec=scfg.spec,
                token_mask=jnp.asarray(mask), return_hidden=True,
                page_table=self._table_dev)
        if pending is None:
            self._prefill_records(np.asarray(counts, np.int64))
        else:
            pending.append((None, (), counts))

        out: List[Tuple[str, int]] = []
        head = self.params.get("lm_head")
        head = head if head is not None else self.params["embed"].T
        with TraceAnnotation("engine.sample"):
            for r in pre:
                k_r = took[r.rid]
                self.cache_len[r.slot] += k_r
                r.prefill_pos += k_r
                self.stats["prefill_tokens"] += k_r
                if scfg.prefix_cache and r.prefix_keys:
                    self._register_prefix(r)
                if r.prefill_pos < len(r.prompt):
                    continue
                # prompt fully cached: unembed just this row's final
                # chunk position, emit the first token, and join decode
                row = hid[r.slot, k_r - 1] @ head
                if fused:
                    row = self._fetch(row, "first_token")
                first = self._sample_row(r, row)
                r.generated.append(int(first))
                r.phase = "decode"
                r.progress = 0
                r.prompt = []
                out.append((r.rid, int(first)))
                self.stats["tokens_emitted"] += 1
                if len(r.generated) >= r.max_new:
                    r.done = True
                    self.free_slots.append(r.slot)
                    self.pool.release_slot(r.slot)
                    self.policy.drop(r.rid)
        self.stats["prefill_chunks"] += len(pre)
        return out

    def _prefill_records(self, counts) -> None:
        """A prefill chunk's per-layer bookkeeping from its expert counts
        (np.int64, one row per layer): LoadTracker EMA, workload-trace
        record, expert loads."""
        with TraceAnnotation("engine.boundary"):
            for layer in range(self.L):
                if self._layer_kind(layer)[1] != "moe":
                    continue
                cnt = counts[layer // self.p, layer % self.p]
                tracker = self.load_trackers.setdefault(
                    layer, trajectory.LoadTracker(self.cfg.moe.num_experts,
                                                  decay=self.scfg.ema_decay))
                tracker.update(cnt)
                self._record({
                    "iter": self.iterations, "layer": layer,
                    "phase": "prefill", "counts": cnt.copy(),
                    "order": paired_load_order(cnt),
                    "schedule": ("dynamic" if self.dynamic_schedule
                                 else "static")})
                self._count_streamed(cnt)

    def _count_streamed(self, counts) -> None:
        """Expert loads at one MoE boundary or prefill layer: the experts
        with at least one row (``expert_loads``, ``experts_streamed`` —
        the ragged kernel streams only those) and the rest
        (``experts_skipped``), from the counts the engine read anyway."""
        hit = int((counts > 0).sum())
        self.stats["expert_loads"] += hit
        self.stats["experts_streamed"] += hit
        self.stats["experts_skipped"] += len(counts) - hit

    def step(self) -> List[Tuple[str, int]]:
        """One iteration, spanned as ``engine.step``.  JAX compile-path
        events inside it add to ``stats["compiles"]`` / ``["compile_s"]``
        and leave a ``compile`` event record in the trace."""
        self.last_step_modeled_s = 0.0
        if not self.active():
            return []
        with StepTraceAnnotation("engine.step",
                                 step_num=self.iterations + 1):
            n0, s0 = compile_counter().read()
            self._iter_modeled_s = 0.0
            # allocate pages for this iteration's KV writes and push the
            # table once; it enters every jitted segment as a traced array
            self._ensure_pages()
            self.stats["latent_cache_bytes"] = (self.pool.pages_in_use()
                                                * self._latent_page_b)
            from repro.parallel import meshctx
            if self.scfg.fused and meshctx.get_mesh() is None:
                out = self._step_fused()
            else:
                out = self._step_legacy()
            self.last_step_modeled_s = self._iter_modeled_s
            n1, s1 = compile_counter().read()
            if n1 > n0:
                self.stats["compiles"] += n1 - n0
                self.stats["compile_s"] += s1 - s0
                self._record_event("compile", count=n1 - n0,
                                   seconds=s1 - s0)
        return out

    # ------------------------------------------------------------------
    # fused mega-step iteration (repro.serving.megastep)
    # ------------------------------------------------------------------

    def _start_masks(self, act):
        """Fresh-token vector + start mask for rows beginning a pass."""
        B = self.scfg.max_batch
        token_vec = np.zeros((B,), np.int32)
        start_mask = np.zeros((B,), bool)
        for r in act:
            if r.progress == 0:
                token_vec[r.slot] = r.generated[-1]
                start_mask[r.slot] = True
        return token_vec, start_mask

    def _step_fused(self) -> List[Tuple[str, int]]:
        self.iterations += 1
        self.stats["iterations"] += 1
        # sync-free when nothing at a boundary is decided on the host: no
        # Algorithm-2 deferral, no EMA trajectory for the next segment.
        # Read every step: the policy's threshold may change between them
        sync_free = (self.policy.n_threshold >= _DEFER_OFF
                     and not self.dynamic_schedule)
        pending = [] if sync_free else None
        out = self._prefill_chunk_step(fused=True, pending=pending)
        act = [r for r in self.active() if r.phase == "decode"]
        if not act:
            return self._settle_pass(pending, act, None, out) \
                if sync_free else out

        ms = megastep.get_megastep(self.cfg, self.scfg)
        token_vec, start_mask = self._start_masks(act)
        bnds = ms.boundaries
        if sync_free:
            self.stats["sync_free_passes"] += 1
            mask = self._pass_mask
            start = mask(np.flatnonzero(start_mask))
        else:
            mask, start = self._mask, start_mask

        if not bnds:
            with TraceAnnotation("engine.dispatch", segment=ms.ONLY):
                self._x, self.caches, logits = ms.seg_only(
                    self.params, self._x, self.caches,
                    jnp.asarray(self.cache_len), self._table_dev,
                    token_vec, start)
            for r in act:
                if start_mask[r.slot]:
                    r.progress = 2 * self.L
            if sync_free:
                return self._settle_pass(pending, act, logits, out)
            return self._finish(act, logits, out, fetch=True)

        def boundary(layer, run_ffn, routing, counts):
            if not sync_free:
                return self._boundary_fused(layer, run_ffn, routing, counts,
                                            ms)
            # nothing to decide: the rows go on, the counts wait for the
            # pass's one read
            if run_ffn:
                pending.append((layer, run_ffn, counts))
            return run_ffn, ms.identity_order

        # segment 0: embed merge + layers [0, b0) + mixer(b0) + route(b0)
        b0 = bnds[0]
        for r in act:
            if r.progress == 0:
                r.progress = 2 * b0 + 1
        run_ffn = [r for r in act if not r.done and r.progress == 2 * b0 + 1]
        with TraceAnnotation("engine.dispatch", segment=ms.FIRST):
            cl = jnp.asarray(self.cache_len)
            self._x, self.caches, h, routing, counts = ms.seg_first(
                self.params, self._x, self.caches, cl, self._table_dev,
                token_vec, start, mask([r.slot for r in run_ffn]))
        kept, order = boundary(b0, run_ffn, routing, counts)

        for j, b in enumerate(bnds[1:], start=1):
            for r in kept:
                r.progress = 2 * b + 1
            run_ffn = [r for r in act
                       if not r.done and r.progress == 2 * b + 1]
            with TraceAnnotation("engine.dispatch",
                                 segment=ms.mid_names[j - 1]):
                self._x, self.caches, h, routing, counts = ms.seg_mid[j - 1](
                    self.params, self._x, self.caches, cl, self._table_dev,
                    h, routing, order, mask([r.slot for r in kept]),
                    mask([r.slot for r in run_ffn]))
            kept, order = boundary(b, run_ffn, routing, counts)

        with TraceAnnotation("engine.dispatch", segment=ms.LAST):
            self._x, self.caches, logits = ms.seg_last(
                self.params, self._x, self.caches, cl, self._table_dev,
                h, routing, order, mask([r.slot for r in kept]))
        for r in kept:
            r.progress = 2 * self.L
        if sync_free:
            return self._settle_pass(pending, act, logits, out)
        return self._finish(act, logits, out, fetch=True)

    def _pass_mask(self, slots):
        """``_mask`` for the sync-free pass: every segment of a pass that
        no row joins mid-way takes the same rows, so the last mask stays
        on the device and is reused while the set of rows is unchanged
        (at batch 1, always)."""
        key = tuple(sorted(int(s) for s in slots))
        if key != self._pass_slots:
            self._pass_slots, self._pass_mask_dev = key, self._mask(list(key))
        return self._pass_mask_dev

    def _settle_pass(self, pending, act, logits, out):
        """The sync-free pass's one read (site ``pass``): the prefill
        chunk's and every boundary's expert counts, with the logits batch
        if a row finished the pass.  Then the host bookkeeping in the
        order of the per-boundary path: the prefill chunk's records,
        each boundary's in order, the sampling."""
        if not any(r.progress == 2 * self.L for r in act):
            logits = None
        if not pending and logits is None:
            return out
        counts, logits = self._fetch(([c for *_, c in pending], logits),
                                     "pass")
        for (layer, rows, _), cnt in zip(pending, counts):
            cnt = np.asarray(cnt, np.int64)
            if layer is None:
                self._prefill_records(cnt)
            else:
                self._boundary_host(layer, rows, cnt, None, None)
        return self._finish(act, logits, out)

    def _boundary_fused(self, layer, run_ffn, routing, counts_dev, ms):
        """Host work at one MoE boundary on the fused path: ONE device
        fetch (counts + routing indices) feeding deferral, the trace,
        and the EMA — then the shared boundary bookkeeping.  Returns
        (kept rows, trajectory order for the next segment)."""
        if not run_ffn:
            # nobody reaches this boundary: no fetch, no record, no EMA
            # (matches the legacy loop's `if not run_ffn: continue`)
            return [], ms.identity_order
        if self.policy.n_threshold < _DEFER_OFF:
            counts_np, idx = self._fetch((counts_dev, routing.indices),
                                         "boundary", layer)
        else:
            counts_np, idx = self._fetch(counts_dev, "boundary", layer), None
        kept = self._boundary_host(layer, run_ffn,
                                   np.asarray(counts_np, np.int64), idx,
                                   routing)
        order = ms.identity_order
        if self.dynamic_schedule and kept:
            self.stats["dynamic_schedules"] += 1
            order = jnp.asarray(self._layer_schedules[layer].order, jnp.int32)
        return kept, order

    def _finish(self, act, logits, out, fetch=False):
        """Emit a token for every request that completed the pass, bump
        cache_len, reset progress.  ``fetch=True`` pulls the full logits
        batch in one transfer (the per-boundary fused path's sampling
        read; the sync-free pass hands over logits it already read)."""
        cfg, scfg = self.cfg, self.scfg
        finish = [r for r in act if not r.done and r.progress == 2 * self.L]
        if not finish:
            return out
        with TraceAnnotation("engine.sample"):
            if fetch:
                logits = self._fetch(logits, "logits")
            for r in finish:
                tok = self._sample_row(r, logits[r.slot, 0])
                r.generated.append(tok)
                out.append((r.rid, tok))
                self.stats["tokens_emitted"] += 1
                r.progress = 0
                self.cache_len[r.slot] += 1
                self.policy.on_forward_pass(r.rid)
                if len(r.generated) >= r.max_new or \
                        int(self.cache_len[r.slot]) >= scfg.max_ctx - 1:
                    r.done = True
                    self.free_slots.append(r.slot)
                    self.pool.release_slot(r.slot)
                    self.policy.drop(r.rid)
        return out

    # ------------------------------------------------------------------
    # legacy eager per-layer iteration (fused=False / distributed mesh)
    # ------------------------------------------------------------------

    def _step_legacy(self) -> List[Tuple[str, int]]:
        self.iterations += 1
        self.stats["iterations"] += 1

        # chunked-prefill stage: every prefilling slot consumes up to
        # chunk_tokens prompt tokens this iteration (one batched pass,
        # emitting first tokens for prompts that complete)
        out = self._prefill_chunk_step()
        act = [r for r in self.active() if r.phase == "decode"]
        if not act:
            return out

        # fresh-token embedding for requests starting a new pass
        token_vec, start_mask = self._start_masks(act)
        x = transformer.decode_embed_merge(self.params, self._x, token_vec,
                                           start_mask, self.cfg)
        for layer in range(self.L):
            _, ffn_kind = self._layer_kind(layer)
            run_attn = [r for r in act if not r.done and r.progress == 2 * layer]
            if run_attn:
                x = self._apply_mixer(x, layer, [r.slot for r in run_attn])
                for r in run_attn:
                    r.progress = 2 * layer + 1
            run_ffn = [r for r in act if not r.done and r.progress == 2 * layer + 1]
            if not run_ffn:
                continue
            if ffn_kind == "moe":
                # route ONCE: the same Routing drives deferral, the
                # trace, the EMA feedback, and the expert execution
                h, routing, _ = transformer.decode_route(self.params, x,
                                                         self.cfg, layer)
                run_ffn = self._defer_cold(routing, layer, run_ffn)
                if not run_ffn:
                    continue
                x = self._apply_moe(x, h, routing,
                                    [r.slot for r in run_ffn], layer)
            else:
                x = transformer.decode_ffn(self.params, x, self.cfg, layer,
                                           self._mask([r.slot for r in run_ffn]))
            for r in run_ffn:
                r.progress = 2 * (layer + 1)
        self._x = x

        # finishers: emit a token, bump cache_len, reset progress
        logits = transformer.decode_logits(self.params, x, self.cfg)
        return self._finish(act, logits, out)

    # ------------------------------------------------------------------
    # sub-layer executors (masked batched updates)
    # ------------------------------------------------------------------

    def _mask(self, slots: List[int]):
        m = np.zeros((self.scfg.max_batch,), bool)
        m[slots] = True
        return jnp.asarray(m)

    def _apply_mixer(self, x, layer, slots):
        x, self.caches = transformer.decode_mixer(
            self.params, x, self.caches, jnp.asarray(self.cache_len),
            self.cfg, layer, self._mask(slots),
            page_table=self._table_dev)
        return x

    def _slot_counts(self, routing, slots, layer):
        """Expert counts restricted to the given slots
        (``gating.expert_token_counts`` with a row mask), read through
        :meth:`_fetch`."""
        return np.asarray(self._fetch(gating.expert_token_counts(
            routing, self._mask(slots)), "slot_counts", layer), np.int64)

    def _boundary_host(self, layer, run_ffn, counts, idx, routing):
        """Shared host bookkeeping at one MoE boundary (both paths):
        LoadTracker EMA update, workload-trace record (with the EMA
        trajectory under dynamic scheduling), and the Algorithm-2
        deferral sweep.  ``counts`` are this boundary's observed expert
        counts (np.int64), ``idx`` the per-row routed expert ids (None
        when deferral is off).  Returns the non-deferred rows."""
        with TraceAnnotation("engine.boundary"):
            tracker = self.load_trackers.setdefault(
                layer, trajectory.LoadTracker(self.cfg.moe.num_experts,
                                              decay=self.scfg.ema_decay))
            tracker.update(counts)
            rec = {"iter": self.iterations, "layer": layer, "phase": "decode",
                   "counts": counts.copy(),
                   "order": paired_load_order(counts),
                   "schedule": ("dynamic" if self.dynamic_schedule
                                else "static")}
            if self.dynamic_schedule:
                # build the EMA schedule once; the expert execution that
                # follows (next segment / _apply_moe) runs along it.  Under
                # the hybrid strategy the plan carries the engine's fast-tier
                # width so the executed partition matches the trace's ``hot``
                plan = None
                if self._n_hot:
                    plan = autotune.Plan(mode="hybrid", family="hybrid",
                                         micro_slices=1,
                                         hot_experts=self._n_hot)
                sched = tracker.schedule(plan=plan)
                self._layer_schedules[layer] = sched
                rec["trajectory"] = list(sched.order)
            self._record(rec)
            self._count_streamed(counts)
            if self.policy.n_threshold >= _DEFER_OFF:
                return list(run_ffn)
            kept = []
            for r in run_ffn:
                acts = [int(e) for e in idx[r.slot]]
                if self.policy.should_defer(r.rid, acts, counts):
                    self.stats["deferrals"] += 1
                    r.deferred_iterations += 1
                else:
                    kept.append(r)
            if len(kept) != len(run_ffn):
                counts2 = self._slot_counts(routing,
                                            [r.slot for r in kept], layer)
                self.stats["expert_loads_saved"] += int((counts > 0).sum()
                                                        - (counts2 > 0).sum())
            return kept

    def _defer_cold(self, routing, layer, run_ffn):
        """Algorithm 2 at the MoE boundary (legacy eager path); returns
        the non-deferred set.  Also the *schedule* stage's observation
        point: the counts feed the layer's LoadTracker EMA and the
        exported workload trace."""
        counts = self._slot_counts(routing, [r.slot for r in run_ffn], layer)
        idx = None
        if self.policy.n_threshold < _DEFER_OFF:
            idx = np.asarray(routing.indices)          # (B, k)
        return self._boundary_host(layer, run_ffn, counts, idx, routing)

    def _apply_moe(self, x, h, routing, slots, layer):
        """Dispatch + combine stages: execute the experts on the already
        routed activations, along the EMA-built trajectory when the
        spec's schedule is dynamic."""
        from repro.parallel import meshctx
        schedule = None
        if self.dynamic_schedule:
            schedule = self._layer_schedules[layer]   # built in _defer_cold
            self.stats["dynamic_schedules"] += 1
        # a precomputed Routing only matches the single-process layout;
        # distributed strategies re-route their local rows in shard_map
        routing_arg = routing if meshctx.get_mesh() is None else None
        return transformer.decode_moe_exec(
            self.params, x, h, routing_arg, self.cfg, layer,
            self._mask(slots), spec=self.scfg.spec, schedule=schedule)

    # ------------------------------------------------------------------
    # preemption: evict a request's state to the pool / restore it
    # ------------------------------------------------------------------

    def preempt(self, rid: str) -> statepool.PreemptedState:
        """Evict an active request's state to the pool, freeing its slot.

        Only requests at an iteration boundary (``progress == 0`` — not
        mid-pass with a deferred hidden state in the residual buffer)
        are restorable.  The page-table row detaches in O(1) (page
        ownership transfers to the handle, no data movement) and the
        SSM rows snapshot by value; :meth:`restore` resumes the request
        bit-identically in any free slot."""
        r = self.requests.get(rid)
        if r is None or r.done:
            raise ValueError(f"no active request {rid!r}")
        if r.progress != 0:
            raise ValueError(
                f"request {rid!r} is mid-pass (progress={r.progress}): its "
                f"deferred hidden state lives in the residual buffer and "
                f"cannot be evicted — pick a victim at progress == 0")
        snap = (statepool.snapshot_ssm(self.caches, r.slot)
                if self._has_ssm else ())
        handle = statepool.PreemptedState(
            request=r,
            page_ids=self.pool.detach_slot(r.slot, has_ssm=snap != ()),
            cache_len=int(self.cache_len[r.slot]), ssm=snap)
        del self.requests[rid]
        self.free_slots.append(r.slot)
        r.preemptions += 1
        self.stats["preemptions"] += 1
        self._record_event("preempt", rid=rid, slot=r.slot,
                           cache_len=handle.cache_len)
        return handle

    def restore(self, handle: statepool.PreemptedState) -> str:
        """Resume a preempted request in a free slot (same engine rid,
        so scheduler bookkeeping keyed on it stays valid)."""
        if not self.free_slots:
            raise QueueFullError("engine full — cannot restore preempted "
                                 "request; wait for completions")
        r = handle.request
        slot = self.free_slots.popleft()
        r.slot = slot
        self.pool.attach_pages(slot, handle.page_ids,
                               has_ssm=handle.ssm != ())
        self.cache_len[slot] = handle.cache_len
        if handle.ssm != ():
            self.caches = statepool.restore_ssm(self.caches, handle.ssm,
                                                slot)
        self.requests[r.rid] = r
        self.stats["restores"] += 1
        self._record_event("restore", rid=r.rid, slot=slot,
                           cache_len=handle.cache_len)
        return r.rid

    # ------------------------------------------------------------------

    def run(self, max_iterations: int = 10_000) -> Dict[str, List[int]]:
        for _ in range(max_iterations):
            if not self.active():
                break
            self.step()
        return {rid: r.generated for rid, r in self.requests.items()}
