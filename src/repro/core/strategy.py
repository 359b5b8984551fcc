"""Unified MoE execution-strategy API: one registry, one spec.

The paper's thesis is that expert execution should be *chosen at
runtime along dynamic trajectories*.  This module is the surface that
makes the choice a first-class object instead of an if/elif chain over
string ``impl`` names:

* :class:`MoEStrategy` — the protocol every execution family
  implements: ``plan(ctx) -> Plan`` (pure, trace-time) and
  ``execute(params, x, moe, activation, plan) -> (y, aux)``, where
  ``execute`` is the family's realization of the shared four-stage
  pipeline **route -> schedule -> dispatch -> combine**
  (``repro.core.trajectory``): routing is computed once (or accepted
  precomputed via ``routing=``), the schedule stage turns the routing's
  ``expert_token_counts`` into an expert trajectory when
  ``ExecutionSpec.schedule == "dynamic"`` (or consumes a host-built
  ``trajectory.Schedule``), and dispatch/combine bracket the family's
  dataflow (ring stream, all-to-all, psum, capacity gather);
* a named **registry** (:func:`register` / :func:`get_strategy`):
  ``fse_dp`` (the paper's expert streaming), ``ep`` / ``tp`` (the
  baselines), ``capacity`` / ``dense`` (single-device paths),
  ``hybrid`` (two-tier hot/cold placement on heterogeneous hardware
  with a near-memory tier), and ``auto`` — a cross-family planner that
  scores the EP and TP cost curves *alongside* the three FSE-DP modes
  (and ``hybrid`` when the profile has an NDP tier) so the winning
  family, not just the winning FSE-DP mode, is picked per shape
  (validated against ``sim.modes.rank_families``);
* :class:`ExecutionSpec` — a frozen, JSON-round-trippable configuration
  object (strategy name, per-phase and per-layer overrides, autotune
  level, kernels on/off, sorted dispatch) that replaces ``moe.impl``
  strings, ``ServeConfig.moe_impl``/``autotune``, and the ad-hoc
  context toggles at every call site.  ``models.moe.moe_block`` is a
  thin registry lookup over it.

Future strategies (NDP offload, cacheless on-demand loading,
multi-chiplet topologies) plug in with ``@register("name")`` — no
caller changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import warnings
from dataclasses import dataclass
from typing import Any, Dict, Optional, Protocol, Tuple, runtime_checkable

from repro.configs.base import MoEConfig
from . import autotune
from .autotune import HardwareProfile, Plan

PHASES = ("train", "prefill", "decode")

# cross-family candidates of the ``auto`` planner, in tie-break priority
# order (ties go to the earlier family — deterministic, mirrored by the
# simulator referee ``sim.modes.rank_families``).  BASE_FAMILIES race on
# any hardware; ``hybrid`` (two-tier hot/cold placement) joins only when
# the profile carries a near-memory tier (``HardwareProfile.ndp_flops``),
# appended last so the homogeneous trio keeps its tie-break priority.
BASE_FAMILIES = ("fse_dp", "ep", "tp")
FAMILIES = BASE_FAMILIES + ("hybrid",)


def default_hot(E: int) -> int:
    """Fast-tier expert count when nothing better is known: the top
    quartile of experts by load (≥1) — the static top-N baseline the
    dynamic EMA repartition is measured against."""
    return max(1, E // 4)

# (B, S, E, d_expert, P) cross-family validation sweep shared by
# tests/test_strategy.py and benchmarks: tiny-token shapes where TP
# (weights stationary, everything replicated) is the only dataflow that
# lowers cheaply, decode shapes where EP's token-side all-to-all beats
# moving weights, and prefill shapes with E % P != 0 (EP cannot split
# the experts; streaming d_expert slices can) where FSE-DP wins.  Each
# family wins at least once.
FAMILY_SWEEP: Tuple[Tuple[int, int, int, int, int], ...] = (
    (1, 1, 16, 512, 4), (1, 2, 64, 256, 8), (2, 1, 16, 768, 4),
    (8, 1, 16, 512, 4), (32, 1, 16, 512, 4), (16, 1, 8, 1024, 2),
    (512, 1, 32, 256, 8), (1024, 2, 64, 256, 8), (4, 16, 8, 256, 4),
    (1, 128, 16, 512, 4),
    (4, 512, 12, 512, 8), (1, 512, 12, 768, 8), (2, 1024, 18, 512, 4),
    (2, 2048, 18, 768, 4),
)

# (B, S, E, d_expert, P, zipf_s) two-tier validation sweep on NDP
# hardware (``sim.hardware.with_ndp(scaled(...))``), shared by
# tests/test_hybrid.py and benchmarks/jax_moe_strategies.py: low-batch
# decode where offloading cold experts near memory wins (hybrid),
# batch-heavy decode where the token all-to-all wins (ep), and long
# prefill where hybrid's un-sharded dispatch tax bites (fse_dp).  Each
# of hybrid/ep/fse_dp wins at least one point; zipf_s > 0 points load
# the race with a rank-permuted Zipf vector (``sim.workload``, seed 0).
HYBRID_SWEEP: Tuple[Tuple[int, int, int, int, int, float], ...] = (
    (1, 1, 64, 1408, 4, 1.2), (4, 1, 64, 1408, 4, 1.2),
    (2, 1, 128, 768, 4, 1.2), (32, 1, 16, 512, 4, 0.0),
    (1, 2, 64, 256, 8, 1.2), (16, 1, 8, 1024, 2, 0.0),
    (512, 1, 32, 256, 8, 0.0), (1024, 2, 64, 256, 8, 1.2),
    (4, 512, 16, 512, 4, 0.0),
    (2, 1024, 18, 512, 4, 0.0), (2, 2048, 18, 768, 4, 1.2),
)


# ---------------------------------------------------------------------------
# ExecutionSpec — the single configuration object
# ---------------------------------------------------------------------------


def _freeze_overrides(overrides) -> Tuple[Tuple[int, str], ...]:
    if not overrides:
        return ()
    if isinstance(overrides, dict):
        items = overrides.items()
    else:
        items = tuple(overrides)
    return tuple(sorted((int(k), str(v)) for k, v in items))


@dataclass(frozen=True)
class ExecutionSpec:
    """One serializable description of how MoE layers execute.

    Resolution order at a call site: ``layer_overrides[layer]`` >
    per-phase field (``prefill`` / ``decode`` / ``train``) >
    ``strategy``.  ``autotune`` / ``use_kernels`` / ``sorted_dispatch``
    scope the corresponding context toggles around the executed block
    (``None`` inherits the ambient setting).
    """

    strategy: str = "auto"
    prefill: Optional[str] = None
    decode: Optional[str] = None
    train: Optional[str] = None
    layer_overrides: Tuple[Tuple[int, str], ...] = ()
    autotune: Optional[str] = None          # off | analytic | measured
    schedule: Optional[str] = None          # static | dynamic (None=static)
    use_kernels: Optional[bool] = None      # None = ambient kernels toggle
    sorted_dispatch: Optional[bool] = None  # None = ambient dispatch mode
    weight_dtype: Optional[str] = None      # fp32 | bf16 | int8 | fp8
                                            # (streamed expert-weight format,
                                            # kernels.quant; None = params
                                            # as-is)

    def __post_init__(self):
        object.__setattr__(self, "layer_overrides",
                           _freeze_overrides(self.layer_overrides))
        if self.autotune not in (None, "off", "analytic", "measured"):
            raise ValueError(f"unknown autotune level {self.autotune!r}")
        if self.schedule not in (None, "static", "dynamic"):
            raise ValueError(f"unknown schedule policy {self.schedule!r} "
                             f"(want 'static' or 'dynamic')")
        from repro.kernels import quant
        quant.check_weight_dtype(self.weight_dtype)

    # ---- resolution ---------------------------------------------------

    def resolve(self, phase: Optional[str] = None,
                layer: Optional[int] = None) -> str:
        """Strategy name for one call site."""
        if layer is not None:
            for lyr, name in self.layer_overrides:
                if lyr == layer:
                    return name
        if phase is not None:
            if phase not in PHASES:
                raise ValueError(f"unknown phase {phase!r} (want {PHASES})")
            override = getattr(self, phase)
            if override:
                return override
        return self.strategy

    def strategies_used(self) -> Tuple[str, ...]:
        """Every strategy name this spec can resolve to (for validation)."""
        names = {self.strategy}
        names |= {getattr(self, p) for p in PHASES if getattr(self, p)}
        names |= {name for _, name in self.layer_overrides}
        return tuple(sorted(names))

    def validate(self) -> "ExecutionSpec":
        """Raise if any referenced strategy is not registered."""
        for name in self.strategies_used():
            get_strategy(name)
        return self

    # ---- context scoping ---------------------------------------------

    @contextlib.contextmanager
    def scope(self):
        """Apply the spec's autotune / kernels / dispatch toggles."""
        with contextlib.ExitStack() as stack:
            if self.autotune is not None:
                stack.enter_context(autotune.use_autotune(self.autotune))
            if self.use_kernels is not None:
                from repro.kernels import ops as kops
                stack.enter_context(kops.use_kernels(self.use_kernels))
            if self.sorted_dispatch is not None:
                from repro.models.moe import use_sorted_dispatch
                stack.enter_context(use_sorted_dispatch(self.sorted_dispatch))
            if self.weight_dtype is not None:
                from repro.kernels import quant
                stack.enter_context(quant.use_weight_dtype(self.weight_dtype))
            yield self

    # ---- (de)serialization -------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"strategy": self.strategy}
        for p in PHASES:
            if getattr(self, p) is not None:
                out[p] = getattr(self, p)
        if self.layer_overrides:
            out["layer_overrides"] = {str(k): v
                                      for k, v in self.layer_overrides}
        for f in ("autotune", "schedule", "use_kernels", "sorted_dispatch",
                  "weight_dtype"):
            if getattr(self, f) is not None:
                out[f] = getattr(self, f)
        return out

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ExecutionSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown ExecutionSpec fields {sorted(unknown)} "
                             f"(known: {sorted(known)})")
        return cls(**d)

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), sort_keys=True, **kw)

    @classmethod
    def from_json(cls, s: str) -> "ExecutionSpec":
        return cls.from_dict(json.loads(s))

    @classmethod
    def load(cls, path: str) -> "ExecutionSpec":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    @classmethod
    def coerce(cls, value, default: str = "auto") -> "ExecutionSpec":
        """Build a spec from anything callers pass: ``None`` (use
        ``default``), a strategy name, a dict, or a spec."""
        if value is None:
            return cls(strategy=default)
        if isinstance(value, cls):
            return value
        if isinstance(value, str):
            return cls(strategy=value)
        if isinstance(value, dict):
            if "strategy" not in value:
                value = dict(value, strategy=default)
            return cls.from_dict(value)
        raise TypeError(f"cannot coerce {type(value).__name__} to "
                        f"ExecutionSpec")


# ---------------------------------------------------------------------------
# strategy protocol + registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StrategyContext:
    """Static shape/config facts a strategy needs to plan one call."""

    B: int                   # per-model-group batch (global B / data axes)
    S: int
    d_model: int
    moe: MoEConfig
    activation: str
    P: int = 1               # model-axis size
    dtype_bytes: int = 2     # activation bytes per element
    level: Optional[str] = None
    profile: Optional[HardwareProfile] = None
    load: Optional[Tuple[float, ...]] = None  # per-expert load shares
    weight_bytes: Optional[int] = None  # streamed expert-weight bytes/param
                                        # (None = dtype_bytes)

    @classmethod
    def from_inputs(cls, x, moe: MoEConfig, activation: str,
                    axis: str = "model", *,
                    load: Optional[Tuple[float, ...]] = None
                    ) -> "StrategyContext":
        import jax.numpy as jnp
        from repro.parallel import meshctx
        mesh = meshctx.get_mesh()
        P_ = 1 if mesh is None or axis not in mesh.axis_names \
            else mesh.shape[axis]
        B, S, d = x.shape
        if mesh is not None:
            batch = meshctx.batch_axes(mesh, axis)
            bsz = 1
            for a in batch:
                bsz *= mesh.shape[a]
            if batch and B % bsz == 0:
                B //= bsz
        from repro.kernels import quant
        return cls(B=int(B), S=int(S), d_model=int(d), moe=moe,
                   activation=activation, P=int(P_),
                   dtype_bytes=jnp.dtype(x.dtype).itemsize, load=load,
                   weight_bytes=quant.weight_bytes())


@runtime_checkable
class MoEStrategy(Protocol):
    """One pluggable execution family."""

    name: str

    def plan(self, ctx: StrategyContext) -> Plan:
        """Trace-time decision (pure Python, memoizable)."""
        ...

    def execute(self, params, x, moe: MoEConfig, activation: str,
                plan: Optional[Plan] = None, *, axis: str = "model",
                routing=None, schedule=None):
        """x: (B, S, d) global. Returns ``(y, aux)``.

        One route -> schedule -> dispatch -> combine pass: ``routing``
        pre-computes the route stage (single-device strategies only),
        ``schedule`` the schedule stage (``trajectory.Schedule``)."""
        ...


_REGISTRY: Dict[str, MoEStrategy] = {}


def register(name: str):
    """Class decorator: instantiate and register an execution strategy."""
    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls()
        return cls
    return deco


def get_strategy(name: str) -> MoEStrategy:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown MoE strategy {name!r}; "
                       f"registered: {available()}") from None


def available() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def execute(name_or_spec, params, x, moe: MoEConfig, activation: str, *,
            plan: Optional[Plan] = None, axis: str = "model",
            phase: Optional[str] = None, layer: Optional[int] = None,
            routing=None, schedule=None):
    """Functional entry: run one MoE layer under a strategy name or an
    :class:`ExecutionSpec`.  Returns ``(y, aux)``.

    ``routing`` / ``schedule`` pre-compute the pipeline's route and
    schedule stages; with neither, a spec whose ``schedule`` field is
    ``"dynamic"`` derives the trajectory in-graph."""
    spec = ExecutionSpec.coerce(name_or_spec)
    name = spec.resolve(phase=phase, layer=layer)
    if schedule is None and spec.schedule == "dynamic":
        from . import trajectory
        schedule = trajectory.DYNAMIC
    with spec.scope():
        return get_strategy(name).execute(params, x, moe, activation, plan,
                                          axis=axis, routing=routing,
                                          schedule=schedule)


_ENTRY_WARNED: set = set()


def warn_deprecated_entry(old: str, name: str) -> None:
    """One-shot DeprecationWarning for a legacy ``*_moe_3d`` entry point."""
    if old in _ENTRY_WARNED:
        return
    _ENTRY_WARNED.add(old)
    warnings.warn(f"{old} is deprecated; use repro.core.strategy."
                  f"execute({name!r}, ...) or moe_block(spec=...)",
                  DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# cross-family cost curves + the auto planner
# ---------------------------------------------------------------------------


def ep_feasible(B: int, S: int, E: int, P: int) -> bool:
    """EP lowers when experts split evenly and tokens can seq- or
    batch-shard over the model axis (``core.baselines.moe_ep``)."""
    return P > 1 and E % P == 0 and (S % P == 0 or B % P == 0)


def family_costs(B: int, S: int, d_model: int, moe: MoEConfig,
                 activation: str, P: int, *,
                 profile: Optional[HardwareProfile] = None,
                 dtype_bytes: int = 2,
                 load: Optional[Tuple[float, ...]] = None,
                 weight_bytes: Optional[int] = None) -> Dict[str, float]:
    """Predicted seconds per candidate family for one MoE layer.

    ``load`` conditions every family's cost curve on a normalized
    per-expert load vector (``None`` = the uniform shape-only model —
    bit-identical to the pre-load behavior).

    ``fse_dp`` is scored as the best *ring* (streaming) schedule —
    stream/index with per-mode-optimized micro-slices.  When no ring
    layout lowers for the shape, the fse_dp family leaves the race:
    its degraded slice dataflow is exactly the TP dataflow, which the
    ``tp`` entry already owns (a spec-forced ``fse_dp`` still executes
    via the slice fallback).  ``tp`` is the weights-stationary cost
    curve; ``ep`` the all-to-all cost curve when it can lower (experts
    split evenly, tokens seq- or batch-shardable).
    """
    profile = profile or HardwareProfile.detect()
    n_mats = 3 if activation == "swiglu" else 2
    E, de = moe.num_experts, moe.d_expert
    k, cf = moe.top_k, moe.capacity_factor
    de_loc = max(1, de // P)
    out: Dict[str, float] = {}

    ring = [m for m in autotune.feasible_modes(B, S, P) if m != "slice"]
    if ring:
        out["fse_dp"] = min(
            autotune.mode_cost(m, B, S, d_model, E, de, k, cf, n_mats, P,
                               profile, M, dtype_bytes, load,
                               weight_bytes)["total_s"]
            for m in ring
            for M in autotune._micro_candidates(de_loc, moe.micro_slices))
    if ep_feasible(B, S, E, P):
        out["ep"] = autotune.ep_cost(B, S, d_model, E, de, k, cf, n_mats,
                                     P, profile, dtype_bytes, load,
                                     weight_bytes)["total_s"]
    out["tp"] = autotune.mode_cost("slice", B, S, d_model, E, de, k, cf,
                                   n_mats, P, profile, 1,
                                   dtype_bytes, load,
                                   weight_bytes)["total_s"]
    if profile.ndp_flops and profile.ndp_bw:
        out["hybrid"] = autotune.hybrid_cost(
            B, S, d_model, E, de, k, cf, n_mats, P, profile,
            dtype_bytes, load, weight_bytes)["total_s"]
    return out


def pick_family(costs: Dict[str, float]) -> str:
    """Deterministic argmin in FAMILIES priority order (ties -> earlier)."""
    return min((f for f in FAMILIES if f in costs), key=lambda f: costs[f])


@functools.lru_cache(maxsize=4096)
def _plan_family_cached(B: int, S: int, d_model: int, moe: MoEConfig,
                        activation: str, P: int,
                        profile: Optional[HardwareProfile],
                        dtype_bytes: int, level: str,
                        load: Optional[Tuple[float, ...]],
                        weight_bytes: Optional[int]) -> Plan:
    if P == 1:
        return Plan(mode="capacity", family="capacity", micro_slices=1,
                    source="fallback")
    if level == "off":
        # zero-knowledge fallback: the registry default family with the
        # legacy static heuristic (no pick_mode call — routed through
        # fallback_plan, which the deprecated pick_mode also wraps)
        return autotune.fallback_plan(B, S, P, moe.micro_slices)
    costs = family_costs(B, S, d_model, moe, activation, P,
                         profile=profile, dtype_bytes=dtype_bytes, load=load,
                         weight_bytes=weight_bytes)
    family = pick_family(costs)
    per_family = tuple(sorted((f, float(s)) for f, s in costs.items()))
    if family == "fse_dp":
        plan = autotune.plan_moe(B, S, d_model, moe, activation, P,
                                 profile=profile, dtype_bytes=dtype_bytes,
                                 level=level, load=load,
                                 weight_bytes=weight_bytes)
        return dataclasses.replace(plan, per_mode_s=plan.per_mode_s
                                   + per_family)
    if family == "hybrid":
        c = autotune.hybrid_cost(
            B, S, d_model, moe.num_experts, moe.d_expert, moe.top_k,
            moe.capacity_factor, 3 if activation == "swiglu" else 2, P,
            profile or HardwareProfile.detect(), dtype_bytes, load,
            weight_bytes)
        return Plan(mode="hybrid", family="hybrid", micro_slices=1,
                    predicted_s=costs["hybrid"], per_mode_s=per_family,
                    source="analytic", hot_experts=int(c["hot_n"]))
    return Plan(mode=family, family=family, micro_slices=1,
                predicted_s=costs[family], per_mode_s=per_family,
                source="analytic")


def plan_family(B: int, S: int, d_model: int, moe: MoEConfig,
                activation: str, P: int, *,
                profile: Optional[HardwareProfile] = None,
                dtype_bytes: int = 2,
                level: Optional[str] = None,
                load: Optional[Tuple[float, ...]] = None,
                weight_bytes: Optional[int] = None) -> Plan:
    """Cross-family planner: score EP and TP cost curves alongside the
    FSE-DP ring modes and return the winning family's Plan.  ``load``
    conditions the race on an observed per-expert load vector (dynamic
    trajectory re-planning); ``weight_bytes`` on the streamed
    expert-weight byte width (quantized storage).  Pure Python — call
    freely at trace time; memoized."""
    level = level or autotune.autotune_level()
    if load is not None:
        load = tuple(float(v) for v in load)
    return _plan_family_cached(int(B), int(S), int(d_model), moe,
                               activation, int(P), profile,
                               int(dtype_bytes), level, load,
                               None if weight_bytes is None
                               else int(weight_bytes))


# ---------------------------------------------------------------------------
# the built-in strategies
# ---------------------------------------------------------------------------


class _SingleDevice:
    """Shared machinery for the global-routing single-device paths.

    The pipeline stages are explicit here: :meth:`route` computes (or
    accepts) the Routing, the executors hand the schedule stage down to
    ``models.moe`` (which derives the trajectory from the routing's
    counts when the schedule is dynamic), and dispatch/combine are the
    capacity/dense dataflows in ``models.moe``.
    """

    def plan(self, ctx: StrategyContext) -> Plan:
        return Plan(mode=self.name, family=self.name, micro_slices=1,
                    source="analytic")

    def route(self, params, x, moe, routing=None):
        from repro.core import gating
        x2d = x.reshape(-1, x.shape[-1])
        if routing is None:
            routing = gating.route_moe(params["router"], x2d, moe)
        return x2d, routing

    # kept for any external callers of the old private helper
    _route = route


@register("dense")
class DenseStrategy(_SingleDevice):
    """Every expert on every token, masked combine (oracle; tests)."""

    def execute(self, params, x, moe, activation, plan=None, *,
                axis="model", routing=None, schedule=None):
        from repro.core import gating
        from repro.models import moe as moe_mod
        x2d, routing = self.route(params, x, moe, routing)
        y = moe_mod.moe_dense(params, x2d, routing, activation,
                              schedule=schedule)
        return (y.reshape(x.shape),
                gating.aux_load_balance_loss(routing, moe.num_experts))


@register("capacity")
class CapacityStrategy(_SingleDevice):
    """Switch-style capacity dispatch (efficient single-device XLA)."""

    def execute(self, params, x, moe, activation, plan=None, *,
                axis="model", routing=None, schedule=None):
        from repro.core import gating
        from repro.models import moe as moe_mod
        x2d, routing = self.route(params, x, moe, routing)
        y = moe_mod.moe_capacity(params, x2d, routing, moe, activation,
                                 schedule=schedule)
        return (y.reshape(x.shape),
                gating.aux_load_balance_loss(routing, moe.num_experts))


@register("fse_dp")
class FseDpStrategy:
    """The paper's expert streaming (ring ppermute, repro.core.fse_dp)."""

    def plan(self, ctx: StrategyContext) -> Plan:
        if ctx.P == 1:
            return Plan(mode="capacity", family="capacity", micro_slices=1,
                        source="fallback")
        return autotune.plan_moe(ctx.B, ctx.S, ctx.d_model, ctx.moe,
                                 ctx.activation, ctx.P,
                                 profile=ctx.profile,
                                 dtype_bytes=ctx.dtype_bytes,
                                 level=ctx.level, load=ctx.load,
                                 weight_bytes=ctx.weight_bytes)

    def execute(self, params, x, moe, activation, plan=None, *,
                axis="model", routing=None, schedule=None):
        from repro.core import fse_dp
        return fse_dp.moe_fse_dp(params, x, moe, activation, axis=axis,
                                 plan=plan, routing=routing,
                                 schedule=schedule)


@register("ep")
class EpStrategy:
    """Expert parallelism: all_to_all token exchange to expert owners."""

    def plan(self, ctx: StrategyContext) -> Plan:
        if ctx.P == 1 or not ep_feasible(ctx.B, ctx.S,
                                         ctx.moe.num_experts, ctx.P):
            return get_strategy("fse_dp").plan(ctx)
        profile = ctx.profile or HardwareProfile.detect()
        n_mats = 3 if ctx.activation == "swiglu" else 2
        c = autotune.ep_cost(ctx.B, ctx.S, ctx.d_model,
                             ctx.moe.num_experts, ctx.moe.d_expert,
                             ctx.moe.top_k, ctx.moe.capacity_factor,
                             n_mats, ctx.P, profile, ctx.dtype_bytes,
                             ctx.load, ctx.weight_bytes)
        return Plan(mode="ep", family="ep", micro_slices=1,
                    predicted_s=c["total_s"], source="analytic")

    def execute(self, params, x, moe, activation, plan=None, *,
                axis="model", routing=None, schedule=None):
        from repro.core import baselines
        return baselines.moe_ep(params, x, moe, activation, axis=axis,
                                routing=routing, schedule=schedule)


@register("tp")
class TpStrategy:
    """Tensor parallelism: d_expert sharded, tokens replicated, psum."""

    def plan(self, ctx: StrategyContext) -> Plan:
        if ctx.P == 1:
            return get_strategy("fse_dp").plan(ctx)
        profile = ctx.profile or HardwareProfile.detect()
        n_mats = 3 if ctx.activation == "swiglu" else 2
        c = autotune.mode_cost("slice", ctx.B, ctx.S, ctx.d_model,
                               ctx.moe.num_experts, ctx.moe.d_expert,
                               ctx.moe.top_k, ctx.moe.capacity_factor,
                               n_mats, ctx.P, profile, 1, ctx.dtype_bytes,
                               ctx.load, ctx.weight_bytes)
        return Plan(mode="tp", family="tp", micro_slices=1,
                    predicted_s=c["total_s"], source="analytic")

    def execute(self, params, x, moe, activation, plan=None, *,
                axis="model", routing=None, schedule=None):
        from repro.core import baselines
        return baselines.moe_tp(params, x, moe, activation, axis=axis,
                                routing=routing, schedule=schedule)


@register("hybrid")
class HybridStrategy(_SingleDevice):
    """Two-tier hot/cold placement: hot experts stream through the fast
    chiplet array, cold experts execute in place on the near-memory tier
    (``HardwareConfig.ndp``); the layer finishes at ``max`` of the
    tiers.  The tier split is a *placement* decision — it changes where
    experts run, never the result — so execution partitions the expert
    trajectory into a hot prefix and a cold tail and is bit-identical
    to the single-tier capacity path (tests/test_hybrid.py)."""

    def plan(self, ctx: StrategyContext) -> Plan:
        profile = ctx.profile or HardwareProfile.detect()
        E = ctx.moe.num_experts
        if not (profile.ndp_flops and profile.ndp_bw):
            # homogeneous hardware: placement-only plan, static top-N
            return Plan(mode="hybrid", family="hybrid", micro_slices=1,
                        source="fallback", hot_experts=default_hot(E))
        n_mats = 3 if ctx.activation == "swiglu" else 2
        c = autotune.hybrid_cost(ctx.B, ctx.S, ctx.d_model, E,
                                 ctx.moe.d_expert, ctx.moe.top_k,
                                 ctx.moe.capacity_factor, n_mats, ctx.P,
                                 profile, ctx.dtype_bytes, ctx.load,
                                 ctx.weight_bytes)
        return Plan(mode="hybrid", family="hybrid", micro_slices=1,
                    predicted_s=c["total_s"], source="analytic",
                    hot_experts=int(c["hot_n"]))

    def execute(self, params, x, moe, activation, plan=None, *,
                axis="model", routing=None, schedule=None):
        from repro.parallel import meshctx
        mesh = meshctx.get_mesh()
        if mesh is not None and axis in mesh.axis_names \
                and mesh.shape[axis] > 1:
            # under a model mesh the hot tier's flow IS the FSE-DP ring;
            # the tier split doesn't map to an SPMD axis, so delegate
            return get_strategy("fse_dp").execute(
                params, x, moe, activation, None, axis=axis,
                routing=routing, schedule=schedule)
        from repro.core import gating
        from repro.models import moe as moe_mod
        x2d, routing = self.route(params, x, moe, routing)
        if plan is None and schedule is not None:
            plan = schedule.plan
        H = plan.hot_experts if plan is not None \
            and plan.hot_experts is not None \
            else default_hot(moe.num_experts)
        y = moe_mod.moe_hybrid(params, x2d, routing, moe, activation,
                               hot_experts=H, schedule=schedule)
        return (y.reshape(x.shape),
                gating.aux_load_balance_loss(routing, moe.num_experts))


@register("auto")
class AutoStrategy:
    """Cross-family planner: EP / TP cost curves scored alongside the
    FSE-DP ring modes; dispatches to the winning family's strategy."""

    def plan(self, ctx: StrategyContext) -> Plan:
        return plan_family(ctx.B, ctx.S, ctx.d_model, ctx.moe,
                           ctx.activation, ctx.P, profile=ctx.profile,
                           dtype_bytes=ctx.dtype_bytes, level=ctx.level,
                           load=ctx.load, weight_bytes=ctx.weight_bytes)

    def execute(self, params, x, moe, activation, plan=None, *,
                axis="model", routing=None, schedule=None):
        load = None if schedule is None else schedule.load
        ctx = StrategyContext.from_inputs(x, moe, activation, axis, load=load)
        if ctx.P == 1:
            return get_strategy("capacity").execute(params, x, moe,
                                                    activation, axis=axis,
                                                    routing=routing,
                                                    schedule=schedule)
        plan = plan or (schedule.plan if schedule is not None
                        and schedule.plan is not None else None) \
            or self.plan(ctx)
        family = plan.family
        inner = plan if family == "fse_dp" else None
        return get_strategy(family).execute(params, x, moe, activation,
                                            inner, axis=axis,
                                            routing=routing,
                                            schedule=schedule)
