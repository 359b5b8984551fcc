"""Expert-trajectory scheduling — the *schedule* stage of the MoE pipeline.

Every execution family now runs the same four-stage pipeline
(``repro.core.strategy``):

  route    — compute a :class:`~repro.core.gating.Routing` once (or accept
             a precomputed one, e.g. from the serving engine's gate pass);
  schedule — build a :class:`Schedule` here: an expert *trajectory* (the
             order experts move through the compute/DDR pipeline), the
             complementary hot/cold stream pairing of the paper's
             paired-load policy (§IV-A), and the plan-level knobs (mode,
             micro-slices) from the load-aware cost model;
  dispatch — gather tokens into per-expert rows, reindexed into
             trajectory order;
  combine  — weighted scatter of expert outputs back to tokens (always
             in canonical expert order — see below).

A ``static`` schedule is shape-only: identity trajectory, uniform-load
cost model — bit-identical to the pre-pipeline execution paths.  A
``dynamic`` schedule is built from the *observed* per-expert token
counts (``gating.expert_token_counts``), either host-side (the engine's
EMA-tracked counts via :class:`LoadTracker`) or in-graph from the
current call's own routing (:func:`traced_order`).

The SPMD realization of a trajectory is a permutation of the expert
groups the grouped expert GEMM runs through in order: the ragged
capacity path lays its groups out in trajectory order (the kernel's
tile map then streams each expert's weights in that order), the padded
``(E, C, d)`` layouts permute the expert axis of the dispatched buffer
and the matching weight stacks.  Either way the permutation genuinely
reorders per-expert compute/weight-load timing, and the combine sums
each token's expert outputs in an order it does not touch — so a
dynamic schedule changes execution order only, never values.  This is the paper's virtualization
argument (§III) made checkable: ``tests`` assert dynamic == static bit
for bit while the chiplet simulator (``sim.modes.simulate_trajectory``)
shows the paired trajectory beating the static one in step time on
skewed gating.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from .autotune import Plan
from .policies import expert_pairs, paired_load_order

SCHEDULE_POLICIES = ("static", "dynamic")


@dataclass(frozen=True)
class Schedule:
    """One expert-trajectory decision for one MoE layer call.

    ``order`` is the trajectory (a permutation of expert ids, hot/cold
    interleaved for ``dynamic``): a host-side tuple, **or** a traced
    ``(E,)`` int array when the schedule is constructed inside a jitted
    computation (the serving engine's fused mega-steps feed the EMA
    trajectory in as a traced argument so the compiled step never
    retraces as the trajectory drifts).  ``None`` means *derive it
    in-graph* from the call's own routing counts when the policy is
    dynamic, or the identity trajectory when static.  ``pairs`` are the
    complementary (hot, cold) stream pairs of the paired-load policy;
    ``load`` the normalized per-expert load vector the schedule was
    planned from (``None`` = uniform); ``plan`` the load-aware
    :class:`~repro.core.autotune.Plan` when one was computed.
    """

    policy: str = "static"
    order: Optional[Tuple[int, ...]] = None
    pairs: Tuple[Tuple[int, Optional[int]], ...] = ()
    load: Optional[Tuple[float, ...]] = None
    plan: Optional[Plan] = None
    predicted_s: float = 0.0

    def __post_init__(self):
        if self.policy not in SCHEDULE_POLICIES:
            raise ValueError(f"unknown schedule policy {self.policy!r} "
                             f"(want {SCHEDULE_POLICIES})")
        # host sequences coerce to an int tuple; jax arrays / tracers
        # (anything carrying a dtype) pass through untouched so a
        # Schedule can be built at trace time from a traced order
        if self.order is not None and not hasattr(self.order, "dtype"):
            object.__setattr__(self, "order",
                               tuple(int(e) for e in self.order))

    @property
    def dynamic(self) -> bool:
        return self.policy == "dynamic"


# the sentinel moe_block passes down when ExecutionSpec.schedule ==
# "dynamic" and no host-built Schedule was provided: every strategy
# derives the trajectory in-graph from its own routing counts
DYNAMIC = Schedule(policy="dynamic")


def static_order(num_experts: int) -> Tuple[int, ...]:
    """The shape-only trajectory: canonical expert-index order."""
    return tuple(range(num_experts))


def normalized_load(counts: Sequence[float]) -> Optional[Tuple[float, ...]]:
    """Counts -> per-expert load shares (sum 1); None for an all-zero
    vector (no information — callers fall back to uniform)."""
    c = np.asarray(counts, np.float64)
    tot = float(c.sum())
    if tot <= 0:
        return None
    return tuple(float(v) for v in c / tot)


def build_schedule(counts: Optional[Sequence[int]] = None, *,
                   policy: str = "dynamic",
                   plan: Optional[Plan] = None,
                   predicted_s: float = 0.0) -> Schedule:
    """Host-side schedule from observed (or EMA-tracked) expert counts.

    ``static`` ignores the counts entirely (identity trajectory, uniform
    load).  ``dynamic`` orders the trajectory by the paired-load policy
    and records the pairing + the normalized load vector, so the plan
    the caller computed from that load travels with the schedule.
    """
    if policy == "static" or counts is None:
        return Schedule(policy="static", plan=plan, predicted_s=predicted_s)
    return Schedule(policy="dynamic",
                    order=tuple(paired_load_order(counts)),
                    pairs=tuple(expert_pairs(counts)),
                    load=normalized_load(counts),
                    plan=plan, predicted_s=predicted_s)


# ---------------------------------------------------------------------------
# EMA load feedback (decode re-plans as gating drifts)
# ---------------------------------------------------------------------------


@dataclass
class LoadTracker:
    """Exponential moving average of per-expert activation counts.

    The serving engine keeps one per MoE layer and feeds each
    iteration's observed counts back in, so the next iteration's
    dynamic schedule (and the load-aware cost model) tracks gating
    drift instead of re-planning from a single noisy step.
    """

    num_experts: int
    decay: float = 0.8
    steps: int = 0
    ema: np.ndarray = field(default=None)  # type: ignore[assignment]

    def __post_init__(self):
        if self.ema is None:
            self.ema = np.zeros((self.num_experts,), np.float64)

    def update(self, counts: Sequence[int]) -> np.ndarray:
        c = np.asarray(counts, np.float64)
        if self.steps == 0:
            self.ema = c.copy()
        else:
            self.ema = self.decay * self.ema + (1.0 - self.decay) * c
        self.steps += 1
        return self.ema

    def load_vector(self) -> Optional[Tuple[float, ...]]:
        """Normalized EMA load shares; None before any observation."""
        if self.steps == 0:
            return None
        return normalized_load(self.ema)

    def schedule(self, *, plan: Optional[Plan] = None) -> Schedule:
        """A dynamic Schedule from the tracked EMA counts."""
        if self.steps == 0:
            return Schedule(policy="dynamic")      # derive in-graph
        return build_schedule(self.ema, policy="dynamic", plan=plan)


# ---------------------------------------------------------------------------
# in-graph trajectory (traced counts -> traced order)
# ---------------------------------------------------------------------------


def traced_order(counts):
    """jnp analogue of ``policies.paired_load_order`` for traced counts.

    Hot/cold interleave of the descending-stable sort: order[2i] is the
    i-th hottest expert, order[2i+1] the i-th coldest.  Idle experts
    (zero counts) sort as the coldest and interleave with the hot end
    rather than trailing as in the host version — they carry zero rows,
    so their position is timing-immaterial; the fixed-shape interleave
    keeps the computation trace-safe.
    """
    import jax.numpy as jnp
    E = counts.shape[0]
    desc = jnp.argsort(-jnp.asarray(counts), stable=True).astype(jnp.int32)
    half = (E + 1) // 2
    order = jnp.zeros((E,), jnp.int32)
    order = order.at[0::2].set(desc[:half])
    order = order.at[1::2].set(desc[half:][::-1])
    return order


def resolve_order(schedule: Optional[Schedule],
                  counts_fn: Callable[[], "object"]):
    """The trajectory permutation one execution body should apply.

    ``None`` (static — the untouched fast path), a constant array (a
    host-built dynamic schedule, e.g. the engine's EMA trajectory), or
    a traced array derived from this call's own routing counts
    (``counts_fn`` is only invoked in that case).
    """
    if schedule is None or not schedule.dynamic:
        return None
    import jax.numpy as jnp
    if schedule.order is not None:
        return jnp.asarray(schedule.order, jnp.int32)
    return traced_order(counts_fn())


def apply_order(order, *arrays):
    """Reindex the leading (expert) axis of each array into trajectory
    order.  ``None`` entries (gateless w_gate) pass through."""
    import jax.numpy as jnp
    return tuple(None if a is None else jnp.take(a, order, axis=0)
                 for a in arrays)


def restore_order(order, ye):
    """Undo :func:`apply_order` on the expert outputs *before* the
    combine, so a dynamic trajectory never changes combine numerics."""
    import jax.numpy as jnp
    return jnp.take(ye, jnp.argsort(order), axis=0)
