import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs.base import MoEConfig
from repro.models import moe as moe_mod
from repro.core import gating, fse_dp, strategy
from repro.parallel import meshctx
from repro.launch.mesh import make_mesh

E, k, d, de = 8, 2, 32, 64
moe = MoEConfig(num_experts=E, top_k=k, d_expert=de, capacity_factor=E/k, micro_slices=2)
key = jax.random.PRNGKey(1)
params = moe_mod.moe_init(key, d, moe, "swiglu", jnp.float32)

mesh = make_mesh((2, 4), ("data", "model"))
B, S = 4, 16
x = jax.random.normal(jax.random.PRNGKey(2), (B, S, d), jnp.float32)

# oracle (dense)
x2d = x.reshape(-1, d)
routing = gating.route(params["router"], x2d, top_k=moe.top_k)
y_ref = moe_mod.moe_dense(params, x2d, routing, "swiglu").reshape(B, S, d)

with meshctx.with_mesh(mesh):
    for name in ("fse_dp", "ep", "tp"):
        y, aux = jax.jit(lambda p, x, n=name: strategy.execute(n, p, x, moe, "swiglu"))(params, x)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        print(f"{name:8s} maxerr={err:.2e} aux={float(aux):.4f}")
        assert err < 2e-4, (name, err)
    # index + slice modes directly
    for mode_body, nm in [(fse_dp._local_moe_index, "index"), (fse_dp._local_moe_slice, "slice")]:
        
        body = functools.partial(mode_body, moe=moe, activation="swiglu", axis="model", P_=4, pm_axes=("data","model"))
        xs = P(("data",), None, None)
        y, aux = jax.jit(fse_dp.shard_map(
            lambda x, wr, wg, wu, wd: body(x, wr, wg, wu, wd), mesh=mesh,
            in_specs=(xs, P(None,None), P(None,None,"model"), P(None,None,"model"), P(None,"model",None)),
            out_specs=(xs, P())))(x, params["router"]["w_router"], params["w_gate"], params["w_up"], params["w_down"])
        err = float(jnp.max(jnp.abs(y - y_ref)))
        print(f"{nm:8s} maxerr={err:.2e}")
        assert err < 2e-4, (nm, err)
print("ALL MODES MATCH ORACLE")
