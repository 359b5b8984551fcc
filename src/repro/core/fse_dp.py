"""FSE-DP — Fully Sharded Expert Data-parallelism (the paper's §III–IV).

TPU-native realization of expert streaming:

* every device on the ``model`` mesh axis holds ``1/P`` of **every**
  expert's FFN weights, sliced along ``d_expert`` (exactly one copy of
  each expert per model group — the paper's "pooled buffer");
* tokens stay **stationary** (sequence-sharded over the same axis —
  handed over reduce-scatter style from attention, so no replication);
* expert slices **stream** around a logical ring via
  ``jax.lax.ppermute`` (point-to-point collective-permute — the D2D
  link analogue; *no all-to-all anywhere*);
* each per-device slice is further cut into ``micro_slices`` so the
  ring runs P·M finer steps; the scan carries the in-flight micro-slice
  and XLA's async collective-permute overlaps the transfer of step
  *s+1* with the grouped GEMM of step *s* — the paper's micro-slice
  flow (Fig. 4) in SPMD form;
* the partial-output sum over slices is order-invariant (elementwise
  activation commutes with the d_expert split), which is the paper's
  virtualization argument: trajectory timing/ordering is immaterial.

Each shard_map body is one pass of the shared route -> schedule ->
dispatch -> combine pipeline (``repro.core.trajectory``): under a
dynamic schedule the dispatched expert rows and arriving weight
micro-slices are reindexed into the gating-count-built paired-load
trajectory (and restored before the combine), so dynamic scheduling
reorders per-expert execution without changing a single bit of output.

Three execution modes, chosen statically from the token layout
(paper Fig. 3(a) vs 3(b)):

  stream — tokens seq-sharded, weight slices circulate  (train/prefill)
  index  — tokens replicated; each device takes a 1/P token slice and
           outputs are all-gathered (decode with enough tokens)
  slice  — tiny-token fallback: weights stay put, every device computes
           its d_expert slice for all tokens, partial outputs psum'd
           (the paper's own observation that token-side exchange wins
           when the token count is small)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.kernels import ops as kops
from repro.parallel import meshctx
from . import gating


def shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes checker off:
    ``pallas_call`` (the streamed-MoE kernel inside the body) has no
    vma rule.  All replicated outputs here (aux, index-mode y) are
    explicitly pmean/psum'd, so the check is redundant.  With the
    checker off no value is typed invariant, so a plain ``pmean``
    transposes to a plain ``pmean`` — inserting ``pvary`` first would
    break the gradient through the ring."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# local grouped-GEMM over one micro-slice
# ---------------------------------------------------------------------------

def _expert_partial(xe, w_g, w_u, w_d, activation, kopts=None):
    """xe: (E,C,d); w_g/w_u: (E,d,m); w_d: (E,m,d) -> partial y (E,C,d) fp32.

    Dispatches through ``kernels.ops.streamed_moe``: the Pallas micro-slice
    kernel when kernels are enabled, the jnp oracle under
    ``use_kernels(False)`` / REPRO_NO_PALLAS.  ``kopts`` is a tuple of
    (name, value) tile kwargs from an autotune :class:`Plan`; ``None``
    consults the ambient-level tile planner for this call's shape."""
    if kopts is None:
        return kops.streamed_moe_autotuned(xe, w_g, w_u, w_d, activation)
    return kops.streamed_moe(xe, w_g, w_u, w_d, activation, **dict(kopts))


def _ring_stream(xe, w_g, w_u, w_d, activation, axis, P_, micro_slices,
                 kopts=None, order=None):
    """Accumulate full expert outputs for local dispatched tokens ``xe``
    while streaming weight micro-slices around the ``axis`` ring.

    w_*: local shard (E, d, de_loc) / (E, de_loc, d).

    ``order`` is an optional expert-trajectory permutation (dynamic
    schedule, ``core.trajectory``): the dispatched rows and each
    arriving weight micro-slice are reindexed into trajectory order so
    the grouped-GEMM grid walks hot/cold experts interleaved, and the
    accumulated outputs are restored to canonical order afterwards —
    per-expert compute is independent, so values are bit-identical to
    the static path.  The circulated slices stay in canonical order
    (each rank applies its *own* trajectory locally).
    """
    from . import trajectory
    E, C, d = xe.shape
    de_loc = w_g.shape[-1] if w_g is not None else w_u.shape[-1]
    M = max(1, min(micro_slices, de_loc))
    while de_loc % M:
        M -= 1  # largest feasible micro-slice count <= requested
    mic = de_loc // M

    if order is not None:
        (xe,) = trajectory.apply_order(order, xe)
    ring = [(i, (i + 1) % P_) for i in range(P_)]
    # zeros_like inherits xe's varying-manual-axes so the scan carry typechecks
    acc = jnp.zeros_like(xe, jnp.float32)

    for m in range(M):
        sl = slice(m * mic, (m + 1) * mic)
        cur = (
            w_g[..., sl] if w_g is not None else None,
            w_u[..., sl],
            w_d[:, sl, :],
        )

        def step(carry, _):
            acc, (cg, cu, cd) = carry
            # Rule 1: forward the micro-slice being computed — the permute
            # is issued first so XLA's async collective-permute overlaps
            # it with the grouped GEMM below (micro-slice flow, Fig. 4b).
            ng = jax.lax.ppermute(cg, axis, ring) if cg is not None else None
            nu = jax.lax.ppermute(cu, axis, ring)
            nd = jax.lax.ppermute(cd, axis, ring)
            if order is None:
                kg, ku, kd = cg, cu, cd
            else:
                kg, ku, kd = trajectory.apply_order(order, cg, cu, cd)
            acc = acc + _expert_partial(xe, kg, ku, kd, activation, kopts)
            return (acc, (ng, nu, nd)), None

        (acc, _), _ = jax.lax.scan(step, (acc, cur), None, length=P_)
    if order is not None:
        acc = trajectory.restore_order(order, acc)
    return acc


# ---------------------------------------------------------------------------
# shard_map bodies — each one is the route -> schedule -> dispatch ->
# combine pipeline (repro.core.trajectory) over its SPMD dataflow
# ---------------------------------------------------------------------------

def _route(wr, x2d, moe):
    """Pipeline *route* stage: Routing for the local token rows."""
    return gating.route({"w_router": wr}, x2d, top_k=moe.top_k)


def _schedule_order(schedule, routing):
    """Pipeline *schedule* stage: the expert-trajectory permutation, or
    ``None`` for a static schedule (identity trajectory, untouched fast
    path).  A dynamic schedule without a host-built order derives it
    in-graph from this rank's own routing counts."""
    from . import trajectory
    return trajectory.resolve_order(
        schedule, lambda: gating.expert_token_counts(routing))


def _dispatch(x2d, routing, moe, order=None):
    """Pipeline *dispatch* stage: (xe, combiner) — combiner(ye fp32
    (E,C,d)) -> y (T,d) fp32.  ``order`` reindexes the dispatched rows
    into trajectory order; the combiner always consumes canonical-order
    outputs (callers restore before combining)."""
    from repro.models.moe import (capacity_of, dispatch_masks, dispatch_tables,
                                  gather_dispatch, scatter_combine,
                                  sorted_dispatch_enabled)
    from . import trajectory
    T = x2d.shape[0]
    C = capacity_of(T, moe)
    if sorted_dispatch_enabled():
        idx, wts = dispatch_tables(routing, T, moe.num_experts, C)
        xe = gather_dispatch(x2d, idx)
        if order is not None:
            (xe,) = trajectory.apply_order(order, xe)
        return xe, lambda ye: scatter_combine(ye, idx, wts, T)
    dispatch, combine = dispatch_masks(routing, T, moe.num_experts, C)
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x2d.dtype), x2d)
    if order is not None:
        (xe,) = trajectory.apply_order(order, xe)
    comb = lambda ye: jnp.einsum("tec,ecd->td", combine.astype(jnp.float32), ye)
    return xe, comb


def _local_moe_stream(x, wr, w_g, w_u, w_d, *, moe, activation, axis, P_,
                      pm_axes, micro_slices=None, kopts=None, schedule=None):
    """x: (B_loc, S_loc, d) — tokens stationary, weights stream."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    routing = _route(wr, x2d, moe)
    order = _schedule_order(schedule, routing)
    # the ring applies the trajectory itself (per arriving micro-slice)
    xe, combine = _dispatch(x2d, routing, moe)
    ye = _ring_stream(xe, w_g, w_u, w_d, activation, axis, P_,
                      micro_slices or moe.micro_slices, kopts, order)
    y = combine(ye.reshape(moe.num_experts, -1, d))
    aux = gating.aux_load_balance_loss(routing, moe.num_experts)
    aux = jax.lax.pmean(aux, pm_axes)
    return y.reshape(B, S, d).astype(x.dtype), aux


def _local_moe_index(x, wr, w_g, w_u, w_d, *, moe, activation, axis, P_,
                     pm_axes, micro_slices=None, kopts=None, schedule=None):
    """x replicated over ``axis``: each rank handles a 1/P token slice,
    streams the weights, then all-gathers the outputs."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    T = x2d.shape[0]
    T_loc = T // P_
    r = jax.lax.axis_index(axis)
    x_loc = jax.lax.dynamic_slice_in_dim(x2d, r * T_loc, T_loc, 0)
    routing = _route(wr, x_loc, moe)
    order = _schedule_order(schedule, routing)
    xe, combine = _dispatch(x_loc, routing, moe)
    ye = _ring_stream(xe, w_g, w_u, w_d, activation, axis, P_,
                      micro_slices or moe.micro_slices, kopts, order)
    y_loc = combine(ye.reshape(moe.num_experts, -1, d))
    # scatter-into-zeros + psum == all-gather, but provably replicated
    # under shard_map's varying-axes checker
    y = jnp.zeros((T, d), jnp.float32)
    y = jax.lax.dynamic_update_slice_in_dim(y, y_loc, r * T_loc, 0)
    y = jax.lax.psum(y, axis).astype(x.dtype)
    aux = jax.lax.pmean(gating.aux_load_balance_loss(routing, moe.num_experts), pm_axes)
    return y.reshape(B, S, d), aux


def _local_moe_slice(x, wr, w_g, w_u, w_d, *, moe, activation, axis, P_,
                     pm_axes, micro_slices=None, kopts=None, schedule=None):
    """Tiny-token fallback (paper Fig. 3(b) regime): weights stationary,
    every rank computes its d_expert slice for all tokens, psum combine."""
    from . import trajectory
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    routing = _route(wr, x2d, moe)
    order = _schedule_order(schedule, routing)
    xe, combine = _dispatch(x2d, routing, moe, order)
    if order is not None:
        w_g, w_u, w_d = trajectory.apply_order(order, w_g, w_u, w_d)
    ye = _expert_partial(xe, w_g, w_u, w_d, activation, kopts)
    if order is not None:
        ye = trajectory.restore_order(order, ye)
    y = combine(ye)
    y = jax.lax.psum(y, axis)
    aux = gating.aux_load_balance_loss(routing, moe.num_experts)
    aux = jax.lax.pmean(aux, pm_axes)
    return y.reshape(B, S, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

# deprecated zero-knowledge mode heuristic — kept as the historical export;
# survives as the fallback of the cost-model autotuner (autotune.fallback_plan)
from .autotune import pick_mode  # noqa: E402


def moe_fse_dp(params, x, moe: MoEConfig, activation, *, axis="model",
               plan=None, schedule=None, routing=None):
    """x: (B, S, d) global. Returns (y, aux). Falls back to the
    single-device capacity path when no model-parallel mesh is active.

    Execution mode, ring micro-slice count, and kernel tile shapes come
    from a ``core.autotune.Plan``: pass one explicitly (forced mode), or
    leave ``plan=None`` to let the cost-model planner score
    {stream, index, slice} x micro_slices x tiles for this shape at the
    ambient autotune level.  Level 'off' applies the legacy static
    heuristic — evaluated on the per-model-group batch (B/data-axis),
    which the shard_map bodies actually see, not the global B the old
    ``pick_mode`` call used; for shapes where those differ the per-group
    choice is the one whose divisibility requirements actually hold.

    ``schedule`` (a ``core.trajectory.Schedule``) selects the expert
    trajectory: ``None``/static is the untouched fast path; dynamic
    reindexes per-expert compute into paired-load order (bit-identical
    outputs, reordered execution).  A schedule that carries a load-aware
    plan supplies it when no explicit ``plan`` is given (an explicit
    plan always wins).  ``routing`` pre-computes the route stage —
    only the single-device fallback accepts it (the distributed bodies
    route their local token rows inside ``shard_map``)."""
    if schedule is not None and schedule.plan is not None and plan is None:
        plan = schedule.plan
    mesh = meshctx.get_mesh()
    P_ = 1 if mesh is None or axis not in mesh.axis_names else mesh.shape[axis]
    if P_ == 1:
        from repro.models.moe import moe_capacity
        shape = x.shape
        x2d = x.reshape(-1, shape[-1])
        if routing is None:
            routing = gating.route(params["router"], x2d, top_k=moe.top_k)
        y = moe_capacity(params, x2d, routing, moe, activation,
                         schedule=schedule)
        return y.reshape(shape), gating.aux_load_balance_loss(routing, moe.num_experts)
    if routing is not None:
        raise ValueError("precomputed Routing is only supported on the "
                         "single-device path; distributed bodies route "
                         "their local token rows inside shard_map")

    B, S, d = x.shape
    batch = meshctx.batch_axes(mesh, axis)
    import numpy as _np
    bsz = int(_np.prod([mesh.shape[a] for a in batch])) if batch else 1
    b_ax = batch if (batch and B % bsz == 0) else None
    B_grp = B // bsz if b_ax else B         # tokens within one model group

    if plan is None:
        from . import autotune
        from repro.kernels import quant
        plan = autotune.plan_moe(B_grp, S, d, moe, activation, P_,
                                 dtype_bytes=jnp.dtype(x.dtype).itemsize,
                                 weight_bytes=quant.weight_bytes())
    mode = plan.mode
    kopts = tuple(sorted(plan.kernel_opts().items()))
    body = {"stream": _local_moe_stream,
            "index": _local_moe_index,
            "slice": _local_moe_slice}[mode]

    x_spec = P(b_ax, axis if mode == "stream" else None, None)
    specs_in = (
        x_spec,
        P(None, None),            # router
        P(None, None, axis),      # w_gate (E,d,de)
        P(None, None, axis),      # w_up
        P(None, axis, None),      # w_down (E,de,d)
    )
    specs_out = (x_spec, P())

    fn = functools.partial(body, moe=moe, activation=activation, axis=axis,
                           P_=P_, pm_axes=tuple(mesh.axis_names),
                           micro_slices=plan.micro_slices, kopts=kopts,
                           schedule=schedule)
    w_g = params.get("w_gate")
    if w_g is None:
        # relu2/gelu experts: no gate projection; reuse w_up spec slot
        def fn2(x, wr, wu, wd):
            return fn(x, wr, None, wu, wd)
        return shard_map(fn2, mesh=mesh,
                         in_specs=(specs_in[0], specs_in[1], specs_in[3], specs_in[4]),
                         out_specs=specs_out)(
            x, params["router"]["w_router"], params["w_up"], params["w_down"])

    def fn3(x, wr, wg, wu, wd):
        return fn(x, wr, wg, wu, wd)

    return shard_map(fn3, mesh=mesh, in_specs=specs_in, out_specs=specs_out)(
        x, params["router"]["w_router"], w_g, params["w_up"], params["w_down"])


def fse_dp_moe_3d(params, x, moe, activation, *, axis="model", plan=None):
    """Deprecated shim: use ``repro.core.strategy.execute('fse_dp', ...)``."""
    from .strategy import warn_deprecated_entry
    warn_deprecated_entry("fse_dp_moe_3d", "fse_dp")
    return moe_fse_dp(params, x, moe, activation, axis=axis, plan=plan)
