"""MoE FFN block.

Weight layout (stacked over experts — shardable on any axis):
  w_gate, w_up : (E, d_model, d_expert)       (w_gate only for swiglu)
  w_down       : (E, d_expert, d_model)

Execution is dispatched through the strategy registry
(``repro.core.strategy``): ``moe_block`` resolves an
:class:`ExecutionSpec` (or legacy ``impl`` string) to a registered
strategy — dense / capacity (single-device, implemented here), fse_dp
(``repro.core.fse_dp``), ep / tp (``repro.core.baselines``), or the
cross-family ``auto`` planner.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core import gating
from repro.kernels import ops as kops
from .layers import dense_init
from .mlp import ffn_init, ffn


def moe_init(key, d_model, moe: MoEConfig, activation, dtype):
    ks = jax.random.split(key, 5)
    E, de = moe.num_experts, moe.d_expert
    p = {
        "router": gating.router_init(ks[0], d_model, E, dtype, moe.scoring),
        "w_up": _stack_init(ks[1], E, d_model, de, dtype),
        "w_down": _stack_init(ks[2], E, de, d_model, dtype),
    }
    if activation == "swiglu":
        p["w_gate"] = _stack_init(ks[3], E, d_model, de, dtype)
    if moe.num_shared_experts:
        p["shared"] = ffn_init(ks[4], d_model, de * moe.num_shared_experts, activation, dtype)
    return p


def _stack_init(key, E, d_in, d_out, dtype):
    ks = jax.random.split(key, E)
    return jnp.stack([dense_init(k, d_in, d_out, dtype) for k in ks])


def _expert_act(params, xe, activation):
    """xe: (..., E-batched leading dims with x (..., d)) applied per expert.

    params w_*: (E, d, de). xe: (E, C, d) -> (E, C, d).
    """
    if activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])) \
            * jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    else:
        from .layers import activation_fn
        h = activation_fn(activation)(jnp.einsum("ecd,edf->ecf", xe, params["w_up"]))
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


# ---------------------------------------------------------------------------
# dense oracle — O(T·E) compute, exact
# ---------------------------------------------------------------------------

def moe_dense(params, x2d, routing, activation, schedule=None):
    """x2d: (T,d); returns (T,d). Computes all experts, weighted combine.

    A dynamic ``schedule`` reindexes the per-expert batch axis into
    trajectory order (outputs restored before the combine — values are
    bit-identical; only per-expert execution order changes)."""
    from repro.core import trajectory
    T, d = x2d.shape
    E = params["w_up"].shape[0]
    order = trajectory.resolve_order(
        schedule, lambda: gating.expert_token_counts(routing))
    xe = jnp.broadcast_to(x2d[None], (E, T, d))
    p = params if order is None else _reorder_experts(params, order)
    ye = _expert_act(p, xe, activation)               # (E,T,d)
    if order is not None:
        ye = trajectory.restore_order(order, ye)
    return jnp.einsum("te,etd->td", routing.combine, ye)


# ---------------------------------------------------------------------------
# capacity dispatch — Switch-style, efficient on one device
# ---------------------------------------------------------------------------

def capacity_of(T, moe: MoEConfig):
    return moe.capacity_rows(T)


def dispatch_masks(routing, T, E, C):
    """Build (T,E,C) dispatch one-hot + (T,E,C) combine weights.

    Tokens beyond an expert's capacity C are dropped (standard EP
    baseline semantics — the paper's EP baseline also has finite
    per-die buffering).
    """
    onehot = jax.nn.one_hot(routing.indices, E, dtype=jnp.int32).sum(1)   # (T,E) 0/1
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1                          # position in expert queue
    keep = (pos >= 0) & (pos < C)
    pos = jnp.clip(pos, 0, C - 1)
    dispatch = jax.nn.one_hot(pos, C) * keep[..., None]                    # (T,E,C)
    combine = dispatch * routing.combine[..., None]                        # (T,E,C)
    return dispatch, combine


def _expert_ffn(params, xe, activation):
    """(E,C,d) -> (E,C,d) fp32 via the ``kernels.ops.streamed_moe_autotuned``
    dispatch layer (Pallas micro-slice kernel with planner-chosen tiles, or
    the jnp oracle under ``use_kernels(False)``)."""
    return kops.streamed_moe_autotuned(xe, params.get("w_gate"),
                                       params["w_up"], params["w_down"],
                                       activation)


def _reorder_experts(params, order):
    """Expert-stacked weight views in trajectory order (router/shared
    untouched — they are not expert-indexed)."""
    out = dict(params)
    for k in ("w_gate", "w_up", "w_down"):
        if k in params:
            out[k] = jnp.take(params[k], order, axis=0)
    return out


def moe_capacity(params, x2d, routing, moe: MoEConfig, activation,
                 schedule=None):
    """Capacity dispatch -> grouped expert FFN -> combine.

    The route stage happened upstream (``routing``); a dynamic
    ``schedule`` (``repro.core.trajectory``) reindexes the dispatched
    rows and weight stacks into trajectory order for the expert FFN and
    restores canonical order before the combine, so outputs are
    bit-identical to the static path."""
    from repro.core import trajectory
    T, d = x2d.shape
    E = moe.num_experts
    C = capacity_of(T, moe)
    order = trajectory.resolve_order(
        schedule, lambda: gating.expert_token_counts(routing))
    p = params if order is None else _reorder_experts(params, order)
    if sorted_dispatch_enabled():
        idx, wts = dispatch_tables(routing, T, E, C)
        g_idx = idx if order is None else jnp.take(idx, order, axis=0)
        xe = gather_dispatch(x2d, g_idx)                                   # (E,C,d)
        ye = _expert_ffn(p, xe, activation)
        if order is not None:
            ye = trajectory.restore_order(order, ye)
        return scatter_combine(ye, idx, wts, T)
    dispatch, combine = dispatch_masks(routing, T, E, C)
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x2d.dtype), x2d)        # (E,C,d)
    if order is not None:
        (xe,) = trajectory.apply_order(order, xe)
    ye = _expert_ffn(p, xe, activation)                                    # (E,C,d) fp32
    if order is not None:
        ye = trajectory.restore_order(order, ye)
    return jnp.einsum("tec,ecd->td", combine.astype(jnp.float32),
                      ye).astype(x2d.dtype)


# ---------------------------------------------------------------------------
# hybrid two-tier dispatch — hot prefix on the fast array, cold tail near
# memory.  The tier split is placement only: the expert axis is a pure
# batch axis of the grouped FFN, so computing it as two groups (and on
# real two-tier hardware, two *places*) is bit-identical to one group.
# ---------------------------------------------------------------------------


def _expert_ffn_tiered(params, xe, activation, hot):
    """(E,C,d) -> (E,C,d) fp32 computed as a hot prefix + cold tail.

    ``hot`` is the fast-tier expert count H over the (already
    trajectory-ordered) expert axis: rows ``[:H]`` model the chiplet
    array's streamed flow, rows ``[H:]`` the near-memory tier.  Each
    group runs the same grouped-FFN dispatch layer; per-expert compute
    is independent and the kernel's tile choice is E-invariant, so the
    split never changes values (tests/test_hybrid.py)."""
    E = xe.shape[0]
    H = max(0, min(int(hot), E))
    if H in (0, E):
        return _expert_ffn(params, xe, activation)

    def _slice(a, b):
        return {k: (v[a:b] if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in params.items()}

    y_hot = _expert_ffn(_slice(0, H), xe[:H], activation)
    y_cold = _expert_ffn(_slice(H, E), xe[H:], activation)
    return jnp.concatenate([y_hot, y_cold], axis=0)


def moe_hybrid(params, x2d, routing, moe: MoEConfig, activation, *,
               hot_experts, schedule=None):
    """Capacity dispatch -> two-tier grouped FFN -> combine.

    Experts are reindexed into load-descending order (the host EMA load
    when a schedule carries one, else this call's own routing counts,
    derived in-graph so the fused serving steps never retrace), the
    hottest ``hot_experts`` form the fast-tier prefix, and canonical
    order is restored before the combine — outputs are bit-identical to
    ``moe_capacity`` on the same routing."""
    from repro.core import trajectory
    T, d = x2d.shape
    E = moe.num_experts
    C = capacity_of(T, moe)
    if schedule is not None and schedule.load is not None:
        import numpy as np
        order = jnp.asarray(
            np.argsort(-np.asarray(schedule.load), kind="stable"),
            jnp.int32)
    else:
        counts = gating.expert_token_counts(routing)
        order = jnp.argsort(-jnp.asarray(counts), stable=True) \
            .astype(jnp.int32)
    p = _reorder_experts(params, order)
    if sorted_dispatch_enabled():
        idx, wts = dispatch_tables(routing, T, E, C)
        xe = gather_dispatch(x2d, jnp.take(idx, order, axis=0))     # (E,C,d)
        ye = _expert_ffn_tiered(p, xe, activation, hot_experts)
        ye = trajectory.restore_order(order, ye)
        return scatter_combine(ye, idx, wts, T)
    dispatch, combine = dispatch_masks(routing, T, E, C)
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x2d.dtype), x2d)  # (E,C,d)
    (xe,) = trajectory.apply_order(order, xe)
    ye = _expert_ffn_tiered(p, xe, activation, hot_experts)          # fp32
    ye = trajectory.restore_order(order, ye)
    return jnp.einsum("tec,ecd->td", combine.astype(jnp.float32),
                      ye).astype(x2d.dtype)


# ---------------------------------------------------------------------------
# sorted dispatch — gather/scatter instead of one-hot einsums
#
# The one-hot dispatch/combine einsums cost O(T·E·C·d) MXU flops (3-4x the
# useful expert GEMMs for fine-grained MoEs); sorting token-choices by
# expert and using gather/scatter moves the same data with zero matmul
# flops.  Enabled via ``use_sorted_dispatch`` (a §Perf hillclimb knob; the
# one-hot path stays as the paper-faithful capacity baseline + oracle).
# ---------------------------------------------------------------------------

import contextlib
import contextvars

_SORTED = contextvars.ContextVar("repro_sorted_dispatch", default=False)


@contextlib.contextmanager
def use_sorted_dispatch(enabled: bool = True):
    tok = _SORTED.set(enabled)
    try:
        yield
    finally:
        _SORTED.reset(tok)


def sorted_dispatch_enabled() -> bool:
    from repro.parallel import meshctx
    return _SORTED.get() or meshctx.opt_enabled("sorted")


def dispatch_tables(routing, T, E, C):
    """(idx (E,C) int32 token ids [T = padding sentinel], wts (E,C))."""
    k = routing.indices.shape[1]
    e_flat = routing.indices.reshape(-1)                       # (T*k,)
    t_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    w_flat = routing.weights.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    e_s, t_s, w_s = e_flat[order], t_flat[order], w_flat[order]
    # position within the expert group (first occurrence offsets)
    start = jnp.searchsorted(e_s, e_s, side="left")
    pos = jnp.arange(T * k, dtype=jnp.int32) - start.astype(jnp.int32)
    # overflow entries keep pos >= C and fall out via mode="drop" (clipping
    # them would clobber the legitimate occupant of slot C-1)
    idx = jnp.full((E, C), T, jnp.int32)
    idx = idx.at[e_s, pos].set(t_s, mode="drop")
    wts = jnp.zeros((E, C), w_s.dtype)
    wts = wts.at[e_s, pos].set(w_s, mode="drop")
    return idx, wts


def gather_dispatch(x2d, idx):
    """x2d: (T,d); idx: (E,C) -> (E,C,d) with zero rows for padding."""
    xpad = jnp.concatenate([x2d, jnp.zeros((1, x2d.shape[1]), x2d.dtype)])
    return xpad[idx]


def scatter_combine(ye, idx, wts, T):
    """ye: (E,C,d) -> (T,d) weighted scatter-add."""
    d = ye.shape[-1]
    contrib = (ye.astype(jnp.float32) * wts[..., None].astype(jnp.float32))
    y = jnp.zeros((T + 1, d), jnp.float32)
    y = y.at[idx.reshape(-1)].add(contrib.reshape(-1, d), mode="drop")
    return y[:T]


# ---------------------------------------------------------------------------
# block entry point
# ---------------------------------------------------------------------------

def moe_block(params, x, moe: MoEConfig, activation, *, impl=None, spec=None,
              phase=None, layer=None, mesh_axis="model", return_aux=False,
              routing=None, schedule=None):
    """x: (B,S,d) or (T,d); thin lookup into the execution-strategy
    registry (``repro.core.strategy``).

    ``spec`` is anything :meth:`ExecutionSpec.coerce` accepts (a spec, a
    strategy name, a dict); ``impl`` is the legacy string knob, kept as
    an alias.  With neither, ``moe.impl`` names the default strategy.
    ``phase`` ('train' | 'prefill' | 'decode') and ``layer`` select the
    spec's per-phase / per-layer overrides.  Distributed strategies
    (fse_dp / ep / tp) route *inside* shard_map on local tokens and
    return a pmean'd aux loss; single-device strategies route globally.

    Pipeline inputs: ``routing`` pre-computes the route stage (e.g. the
    serving engine's gate pass — single-device strategies only);
    ``schedule`` pre-computes the schedule stage (a host-built
    ``trajectory.Schedule``).  With neither, the spec's ``schedule``
    knob still applies: ``"dynamic"`` makes every strategy derive its
    expert trajectory in-graph from its own routing counts.
    """
    from repro.core import strategy as strat
    from repro.core import trajectory
    sp = strat.ExecutionSpec.coerce(spec if spec is not None else impl,
                                    default=moe.impl)
    name = sp.resolve(phase=phase, layer=layer)
    if schedule is None and sp.schedule == "dynamic":
        schedule = trajectory.DYNAMIC
    shape = x.shape
    if x.ndim == 2:
        x = x[None]
    with sp.scope(), jax.named_scope("expert_ffn"):
        y, aux = strat.get_strategy(name).execute(params, x, moe, activation,
                                                  axis=mesh_axis,
                                                  routing=routing,
                                                  schedule=schedule)
    if moe.num_shared_experts:
        with jax.named_scope("shared_experts"):
            y = y + ffn(params["shared"], x, activation)
    y = y.reshape(shape)
    if return_aux:
        return y, aux
    return y
