"""Top-K expert gating (router) with load-balancing auxiliary loss.

Routing follows the Mixtral/DeepSeek convention: softmax over all expert
logits, select top-k, renormalize the selected probabilities; or
DeepSeek-V3's sigmoid scores with a selection bias (:func:`route_moe`).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


class Routing(NamedTuple):
    indices: jnp.ndarray      # (T, k) int32 selected experts
    weights: jnp.ndarray      # (T, k) renormalized gate weights
    probs: jnp.ndarray        # (T, E) full softmax, or sigmoid scores
                              # (for aux loss / stats)
    combine: jnp.ndarray      # (T, E) scatter of weights into expert slots


def router_init(key, d_model, num_experts, dtype, scoring="softmax"):
    p = {"w_router": dense_init(key, d_model, num_experts, dtype, scale=0.02)}
    if scoring == "sigmoid":
        # learned by the aux-loss-free balancing update; zero at init
        p["e_score_correction_bias"] = jnp.zeros((num_experts,), jnp.float32)
    return p


def route(params, x, *, top_k, jitter=0.0, key=None) -> Routing:
    """x: (T, d) -> Routing over E experts."""
    logits = (x @ params["w_router"]).astype(jnp.float32)     # (T, E)
    if jitter and key is not None:
        logits = logits + jitter * jax.random.normal(key, logits.shape)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, indices = jax.lax.top_k(probs, top_k)            # (T,k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    E = probs.shape[-1]
    onehot = jax.nn.one_hot(indices, E, dtype=jnp.float32)    # (T,k,E)
    combine = jnp.einsum("tk,tke->te", weights, onehot)       # (T,E)
    return Routing(indices, weights.astype(x.dtype), probs, combine.astype(x.dtype))


def route_moe(params, x, moe) -> Routing:
    """The router a :class:`~repro.configs.base.MoEConfig` names, on
    x: (T, d).  ``softmax``: :func:`route`.  ``sigmoid`` (DeepSeek-V3,
    in float32 from the logits on): scores ``s = sigmoid(x W_r)``; the
    top-k of ``s + b`` (``b``: the router's ``e_score_correction_bias``)
    are chosen, so the bias moves the choice but not the weights; the
    weights are ``s`` at the chosen experts, renormalised to sum to one
    (``norm_topk_prob``), times ``moe.routed_scaling``."""
    if moe.scoring == "softmax":
        return route(params, x, top_k=moe.top_k)
    if moe.scoring != "sigmoid":
        raise ValueError(f"unknown router scoring {moe.scoring!r}")
    logits = jnp.matmul(x, params["w_router"],                # (T, E)
                        preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    biased = scores + params["e_score_correction_bias"].astype(jnp.float32)
    _, indices = jax.lax.top_k(biased, moe.top_k)             # (T, k)
    weights = jnp.take_along_axis(scores, indices, axis=-1)
    weights = weights / weights.sum(-1, keepdims=True) * moe.routed_scaling
    E = scores.shape[-1]
    onehot = jax.nn.one_hot(indices, E, dtype=jnp.float32)    # (T,k,E)
    combine = jnp.einsum("tk,tke->te", weights, onehot)       # (T,E)
    return Routing(indices, weights.astype(x.dtype), scores,
                   combine.astype(x.dtype))


def aux_load_balance_loss(routing: Routing, num_experts: int) -> jnp.ndarray:
    """Switch-transformer style: E * sum_e f_e * p_e."""
    T = routing.probs.shape[0]
    assign = (routing.combine > 0).astype(jnp.float32)        # (T,E)
    f = assign.sum(0) / jnp.maximum(assign.sum(), 1.0)        # fraction routed
    p = routing.probs.mean(0)                                 # mean prob
    return num_experts * jnp.sum(f * p)


def expert_token_counts(routing: Routing, mask=None) -> jnp.ndarray:
    """(E,) number of tokens activating each expert (the paper's n_e).

    ``mask`` restricts the count to a boolean (T,) subset of the routed
    rows — e.g. the serving engine counting only its active slots."""
    assign = routing.combine > 0                              # (T,E)
    if mask is not None:
        assign = assign & jnp.asarray(mask)[:, None]
    return assign.sum(0)
