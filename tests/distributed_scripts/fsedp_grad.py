"""Gradients flow through the FSE-DP ring (ppermute transpose) and match
the single-device capacity implementation."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.core import strategy
from repro.models import moe as moe_mod
from repro.parallel import meshctx
from repro.launch.mesh import make_mesh

E, k, d, de = 8, 2, 32, 64
moe = MoEConfig(num_experts=E, top_k=k, d_expert=de, capacity_factor=E / k,
                micro_slices=2)
params = moe_mod.moe_init(jax.random.PRNGKey(1), d, moe, "swiglu", jnp.float32)
mesh = make_mesh((2, 4), ("data", "model"))
x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, d), jnp.float32)


def loss_dist(p, x):
    with meshctx.with_mesh(mesh):
        y, aux = strategy.execute("fse_dp", p, x, moe, "swiglu")
    return jnp.sum(y ** 2) + 0.0 * aux


def loss_ref(p, x):
    from repro.core import gating
    x2d = x.reshape(-1, d)
    r = gating.route(p["router"], x2d, top_k=k)
    y = moe_mod.moe_capacity(p, x2d, r, moe, "swiglu")
    return jnp.sum(y ** 2)


g1 = jax.jit(jax.grad(loss_dist))(params, x)
g2 = jax.grad(loss_ref)(params, x)
for key in ("w_gate", "w_up", "w_down"):
    np.testing.assert_allclose(np.asarray(g1[key]), np.asarray(g2[key]),
                               rtol=5e-3, atol=5e-4)
print("FSE-DP gradients match reference")
