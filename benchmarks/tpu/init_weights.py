"""Random weights made on the device from the seed, in one jitted call.

The program's parameter tree (its structure and dtypes, read with
``jax.eval_shape`` of ``api.init_params``, which computes nothing) is
filled by one compiled program: each leaf is drawn from its own key,
directly in the dtype it is served in.  The values are the
benchmark's own, so the plain reference, which reads the same arrays,
takes nothing the program made.

Scales: each projection ``(..., d_in, d_out)`` is N(0, 1/d_in), which
keeps activations of order one through the layers; the router is drawn
the same way, so its logits spread over a few units like a trained
router's, rather than sitting in near-ties that bfloat16 rounding
flips; embedding rows are N(0, 1/d_model), so a tied head gives logits
of order one; norm scales are one and biases zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` below 2**64."""
    key = jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(key, (int(seed) >> 32) & 0xFFFFFFFF)


def _leaf_rule(path: str, shape) -> tuple:
    """(kind, std) for one leaf, from its path in the tree."""
    if path.endswith("scale"):
        return "ones", 0.0
    if path.endswith("bias"):
        return "zeros", 0.0
    if path.endswith("embed"):
        return "normal", float(shape[-1]) ** -0.5
    return "normal", float(shape[-2]) ** -0.5


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def make_params(cfg, seed: int):
    """The params of ``cfg`` drawn from ``seed`` on the default device."""
    from repro.models import api
    shapes = jax.eval_shape(lambda k: api.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = [(_leaf_rule(path_name(p), s.shape), s.shape, s.dtype)
             for p, s in flat]

    def build(key):
        leaves = []
        for i, ((kind, std), shape, dtype) in enumerate(rules):
            if kind == "ones":
                leaves.append(jnp.ones(shape, dtype))
            elif kind == "zeros":
                leaves.append(jnp.zeros(shape, dtype))
            else:
                k = jax.random.fold_in(key, i)
                leaves.append(jax.random.normal(k, shape, dtype)
                              * jnp.asarray(std, dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.block_until_ready(jax.jit(build)(seed_key(seed)))
