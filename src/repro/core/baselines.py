"""Baseline distributed MoE strategies the paper compares against.

EP — expert parallelism (the de-facto baseline, paper §VI-A): each
device on the ``model`` axis *owns* ``E/P`` full experts; tokens are
routed to the owning device via ``all_to_all`` and routed back after
expert compute.  Token buffers are capacity-bounded, so skewed routing
drops tokens (or forces a large capacity factor) — the long-tail
failure mode the paper profiles.

TP — tensor parallelism: every expert's ``d_expert`` is sharded, tokens
are **replicated** on the model axis, partial outputs all-reduced
(the paper's critique: token duplication).

DP (replicated experts) exists only as an accounting mode in the
benchmarks — it needs no code beyond unsharded weights.

Both baselines dispatch their expert GEMMs through
``fse_dp._expert_partial`` with no explicit tile opts, which routes to
``kernels.ops.streamed_moe_autotuned`` — the same cost-model tile
scheduler (``core.autotune``) the FSE-DP modes use, so kernel-level
comparisons between strategies are tile-for-tile fair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.parallel import meshctx
from . import gating
from .fse_dp import _expert_partial, _route, shard_map


def _capacity(T_loc: int, moe: MoEConfig) -> int:
    return moe.capacity_rows(T_loc)


def _local_trajectory(schedule, counts_fn):
    """Schedule stage for the baseline bodies: the local expert-axis
    trajectory permutation, or ``None`` for static (untouched path)."""
    from . import trajectory
    return trajectory.resolve_order(schedule, counts_fn)


# ---------------------------------------------------------------------------
# EP — all-to-all dispatch to expert owners
# ---------------------------------------------------------------------------

def _local_ep(x, wr, w_g, w_u, w_d, *, moe, activation, axis, P_, pm_axes,
              schedule=None):
    """x: (B_loc, S_loc, d) seq-sharded. w_*: (E_loc, d, de) expert-sharded.

    Pipeline: route local rows -> schedule (dynamic: a trajectory over
    this rank's *owned* experts, ordered by the psum'd global gating
    counts) -> all-to-all dispatch -> grouped FFN -> all-to-all return
    -> combine.  The trajectory permutes the owned-expert batch axis
    around the FFN only, so outputs are bit-identical to static."""
    from repro.models.moe import dispatch_masks
    from . import trajectory
    B, S, d = x.shape
    E = moe.num_experts
    E_loc = E // P_
    x2d = x.reshape(B * S, d)
    T_loc = x2d.shape[0]
    C = _capacity(T_loc, moe)

    routing = _route(wr, x2d, moe)

    def _owned_counts():
        counts = jax.lax.psum(gating.expert_token_counts(routing), axis)
        r = jax.lax.axis_index(axis)
        return jax.lax.dynamic_slice_in_dim(counts, r * E_loc, E_loc, 0)

    # a host-built Schedule.order indexes GLOBAL experts; this body
    # schedules its owned E_loc shard, so a dynamic schedule always
    # derives the local trajectory in-graph from the psum'd counts
    order = None
    if schedule is not None and schedule.dynamic:
        order = trajectory.traced_order(_owned_counts())
    dispatch, combine = dispatch_masks(routing, T_loc, E, C)          # (T,E,C)
    xsend = jnp.einsum("tec,td->ecd", dispatch.astype(x2d.dtype), x2d)  # (E,C,d)
    xsend = xsend.reshape(P_, E_loc, C, d)
    # all-to-all: rows -> expert owners; received leading dim = source rank
    xrecv = jax.lax.all_to_all(xsend, axis, split_axis=0, concat_axis=0, tiled=True)
    xrecv = xrecv.reshape(P_, E_loc, C, d).transpose(1, 0, 2, 3).reshape(E_loc, P_ * C, d)

    if order is None:
        ye = _expert_partial(xrecv, None if w_g is None else w_g, w_u, w_d,
                             activation)
    else:
        xrecv, w_g, w_u, w_d = trajectory.apply_order(order, xrecv, w_g,
                                                      w_u, w_d)
        ye = _expert_partial(xrecv, w_g, w_u, w_d, activation)
        ye = trajectory.restore_order(order, ye)
    ye = ye.astype(x.dtype)

    ysend = ye.reshape(E_loc, P_, C, d).transpose(1, 0, 2, 3).reshape(P_ * E_loc, C, d)
    yrecv = jax.lax.all_to_all(ysend.reshape(P_, E_loc, C, d), axis,
                               split_axis=0, concat_axis=0, tiled=True)
    yrecv = yrecv.reshape(E, C, d)
    y = jnp.einsum("tec,ecd->td", combine.astype(jnp.float32),
                   yrecv.astype(jnp.float32))
    aux = jax.lax.pmean(gating.aux_load_balance_loss(routing, E), pm_axes)
    return y.reshape(B, S, d).astype(x.dtype), aux


def moe_ep(params, x, moe: MoEConfig, activation, *, axis="model",
           schedule=None, routing=None):
    mesh = meshctx.get_mesh()
    P_ = 1 if mesh is None or axis not in mesh.axis_names else mesh.shape[axis]
    if P_ == 1 or moe.num_experts % P_:
        from .fse_dp import moe_fse_dp
        return moe_fse_dp(params, x, moe, activation, axis=axis,
                          schedule=schedule, routing=routing)
    if routing is not None:
        raise ValueError("precomputed Routing is only supported on the "
                         "single-device path")
    batch = meshctx.batch_axes(mesh, axis)
    import numpy as _np
    bsz = int(_np.prod([mesh.shape[a] for a in batch])) if batch else 1
    if x.shape[0] % max(bsz, 1):
        batch = None
        bsz = 1
    B_grp = x.shape[0] // max(bsz, 1)
    # token layout: seq-shard S over the model axis when it divides
    # (the train/prefill layout); otherwise shard the batch dim over
    # (data axes x model) — decode shapes with S < P (HD-MoE's hybrid
    # regime); otherwise EP cannot lower, degrade to expert streaming.
    if x.shape[1] % P_ == 0:
        x_spec = P(batch, axis, None)
    elif B_grp % P_ == 0:
        x_spec = P((tuple(batch) if batch else ()) + (axis,), None, None)
    else:
        from .fse_dp import moe_fse_dp
        return moe_fse_dp(params, x, moe, activation, axis=axis,
                          schedule=schedule)
    w_g = params.get("w_gate")
    fn = functools.partial(_local_ep, moe=moe, activation=activation, axis=axis, P_=P_, pm_axes=tuple(mesh.axis_names), schedule=schedule)
    if w_g is None:
        def fn2(x, wr, wu, wd):
            return fn(x, wr, None, wu, wd)
        return shard_map(fn2, mesh=mesh,
                         in_specs=(x_spec, P(None, None), P(axis, None, None),
                                   P(axis, None, None)),
                         out_specs=(x_spec, P()))(
            x, params["router"]["w_router"], params["w_up"], params["w_down"])

    def fn3(x, wr, wg, wu, wd):
        return fn(x, wr, wg, wu, wd)
    return shard_map(fn3, mesh=mesh,
                     in_specs=(x_spec, P(None, None), P(axis, None, None),
                               P(axis, None, None), P(axis, None, None)),
                     out_specs=(x_spec, P()))(
        x, params["router"]["w_router"], w_g, params["w_up"], params["w_down"])


# ---------------------------------------------------------------------------
# TP — d_expert sharding, replicated tokens, all-reduce combine
# ---------------------------------------------------------------------------

def _local_tp(x, wr, w_g, w_u, w_d, *, moe, activation, axis, P_, pm_axes,
              schedule=None):
    """Pipeline: route (replicated tokens) -> schedule -> dispatch ->
    sliced FFN -> psum combine.  The dynamic trajectory spans all E
    experts (weights are d_expert-sliced, not expert-sharded)."""
    from repro.models.moe import dispatch_masks
    from . import trajectory
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    T = x2d.shape[0]
    C = _capacity(T, moe)
    routing = _route(wr, x2d, moe)
    order = _local_trajectory(
        schedule, lambda: gating.expert_token_counts(routing))
    dispatch, combine = dispatch_masks(routing, T, moe.num_experts, C)
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x2d.dtype), x2d)
    if order is None:
        ye = _expert_partial(xe, w_g, w_u, w_d, activation)
    else:
        xe, w_g, w_u, w_d = trajectory.apply_order(order, xe, w_g, w_u, w_d)
        ye = trajectory.restore_order(
            order, _expert_partial(xe, w_g, w_u, w_d, activation))
    y = jnp.einsum("tec,ecd->td", combine.astype(jnp.float32), ye)
    y = jax.lax.psum(y, axis)
    aux = gating.aux_load_balance_loss(routing, moe.num_experts)
    aux = jax.lax.pmean(aux, pm_axes)
    return y.reshape(B, S, d).astype(x.dtype), aux


def moe_tp(params, x, moe: MoEConfig, activation, *, axis="model",
           schedule=None, routing=None):
    mesh = meshctx.get_mesh()
    P_ = 1 if mesh is None or axis not in mesh.axis_names else mesh.shape[axis]
    if P_ == 1:
        from .fse_dp import moe_fse_dp
        return moe_fse_dp(params, x, moe, activation, axis=axis,
                          schedule=schedule, routing=routing)
    if routing is not None:
        raise ValueError("precomputed Routing is only supported on the "
                         "single-device path")
    batch = meshctx.batch_axes(mesh, axis)
    import numpy as _np
    bsz = int(_np.prod([mesh.shape[a] for a in batch])) if batch else 1
    if x.shape[0] % max(bsz, 1):
        batch = None
    x_spec = P(batch, None, None)
    fn = functools.partial(_local_tp, moe=moe, activation=activation, axis=axis, P_=P_, pm_axes=tuple(mesh.axis_names), schedule=schedule)
    w_g = params.get("w_gate")
    if w_g is None:
        def fn2(x, wr, wu, wd):
            return fn(x, wr, None, wu, wd)
        return shard_map(fn2, mesh=mesh,
                         in_specs=(x_spec, P(None, None), P(None, None, axis),
                                   P(None, axis, None)),
                         out_specs=(x_spec, P()))(
            x, params["router"]["w_router"], params["w_up"], params["w_down"])

    def fn3(x, wr, wg, wu, wd):
        return fn(x, wr, wg, wu, wd)
    return shard_map(fn3, mesh=mesh,
                     in_specs=(x_spec, P(None, None), P(None, None, axis),
                               P(None, None, axis), P(None, axis, None)),
                     out_specs=(x_spec, P()))(
        x, params["router"]["w_router"], w_g, params["w_up"], params["w_down"])


def ep_moe_3d(params, x, moe, activation, *, axis="model"):
    """Deprecated shim: use ``repro.core.strategy.execute('ep', ...)``."""
    from .strategy import warn_deprecated_entry
    warn_deprecated_entry("ep_moe_3d", "ep")
    return moe_ep(params, x, moe, activation, axis=axis)


def tp_moe_3d(params, x, moe, activation, *, axis="model"):
    """Deprecated shim: use ``repro.core.strategy.execute('tp', ...)``."""
    from .strategy import warn_deprecated_entry
    warn_deprecated_entry("tp_moe_3d", "tp")
    return moe_tp(params, x, moe, activation, axis=axis)
