"""use_kernels(True) (Pallas, interpret) vs use_kernels(False) (jnp oracle)
parity for every FSE-DP shard_map mode on 8 fake devices — the acceptance
check that the ring step's expert GEMM really flows through the kernel
dispatch layer without changing results."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.core import fse_dp, strategy
from repro.kernels import ops as kops
from repro.models import moe as moe_mod
from repro.parallel import meshctx
from repro.launch.mesh import make_mesh

E, k, d, de = 8, 2, 32, 64
mesh = make_mesh((2, 4), ("data", "model"))
x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, d), jnp.float32)


def run(activation, enabled):
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=de,
                    capacity_factor=E / k, micro_slices=2)
    params = moe_mod.moe_init(jax.random.PRNGKey(1), d, moe, activation,
                              jnp.float32)
    outs = {}
    with meshctx.with_mesh(mesh), kops.use_kernels(enabled):
        y, _ = jax.jit(lambda p, x: strategy.execute("fse_dp", p, x, moe, activation))(params, x)
        outs["stream"] = np.asarray(y)
        for body, nm in [(fse_dp._local_moe_index, "index"),
                         (fse_dp._local_moe_slice, "slice")]:
            fn = functools.partial(body, moe=moe, activation=activation,
                                   axis="model", P_=4,
                                   pm_axes=("data", "model"))
            xs = P(("data",), None, None)
            wspecs = (P(None, None), P(None, None, "model"),
                      P(None, None, "model"), P(None, "model", None))
            if activation == "swiglu":
                sm = fse_dp.shard_map(
                    lambda x, wr, wg, wu, wd: fn(x, wr, wg, wu, wd),
                    mesh=mesh, in_specs=(xs,) + wspecs, out_specs=(xs, P()))
                y, _ = jax.jit(sm)(x, params["router"]["w_router"],
                                   params["w_gate"], params["w_up"],
                                   params["w_down"])
            else:  # gateless: no w_gate operand anywhere
                sm = fse_dp.shard_map(
                    lambda x, wr, wu, wd: fn(x, wr, None, wu, wd),
                    mesh=mesh, in_specs=(xs, wspecs[0], wspecs[2], wspecs[3]),
                    out_specs=(xs, P()))
                y, _ = jax.jit(sm)(x, params["router"]["w_router"],
                                   params["w_up"], params["w_down"])
            outs[nm] = np.asarray(y)
    return outs


for activation in ("swiglu", "gelu"):
    with_kernel = run(activation, True)
    with_ref = run(activation, False)
    for mode in ("stream", "index", "slice"):
        err = float(np.max(np.abs(with_kernel[mode] - with_ref[mode])))
        print(f"{activation:8s} {mode:8s} maxerr={err:.2e}")
        assert err < 2e-5, (activation, mode, err)
print("KERNEL PARITY OK")
