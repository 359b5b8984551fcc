"""Plan-driven dispatch == forced-mode execution, bit for bit, on 8 fake
devices: for each of stream/index/slice, ``strategy.execute("fse_dp", ..., plan=...)`` must
produce exactly the arrays of a hand-built shard_map over the same body
with the same micro-slice count and kernel tile opts.  Also checks the
default (auto) plan equals its own forced re-execution, and that the
level='off' fallback reproduces the legacy static dispatch."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import MoEConfig
from repro.core import autotune, fse_dp, strategy
from repro.models import moe as moe_mod
from repro.parallel import meshctx
from repro.launch.mesh import make_mesh

E, k, d, de = 8, 2, 32, 64
moe = MoEConfig(num_experts=E, top_k=k, d_expert=de, capacity_factor=E / k,
                micro_slices=2)
params = moe_mod.moe_init(jax.random.PRNGKey(1), d, moe, "swiglu", jnp.float32)
mesh = make_mesh((2, 4), ("data", "model"))
B, S = 4, 16
x = jax.random.normal(jax.random.PRNGKey(2), (B, S, d), jnp.float32)
P_ = 4
B_grp = B // 2                                   # data axis is 2-way

BODIES = {"stream": fse_dp._local_moe_stream,
          "index": fse_dp._local_moe_index,
          "slice": fse_dp._local_moe_slice}


def forced_reference(plan):
    """Hand-built shard_map mirroring the fse_dp strategy for this plan."""
    body = BODIES[plan.mode]
    kopts = tuple(sorted(plan.kernel_opts().items()))
    fn = functools.partial(body, moe=moe, activation="swiglu", axis="model",
                           P_=P_, pm_axes=("data", "model"),
                           micro_slices=plan.micro_slices, kopts=kopts)
    xs = P(("data",), "model" if plan.mode == "stream" else None, None)
    return jax.jit(fse_dp.shard_map(
        lambda x, wr, wg, wu, wd: fn(x, wr, wg, wu, wd), mesh=mesh,
        in_specs=(xs, P(None, None), P(None, None, "model"),
                  P(None, None, "model"), P(None, "model", None)),
        out_specs=(xs, P())))(
        x, params["router"]["w_router"], params["w_gate"],
        params["w_up"], params["w_down"])


with meshctx.with_mesh(mesh):
    for mode in ("stream", "index", "slice"):
        plan = autotune.plan_moe(B_grp, S, d, moe, "swiglu", P_,
                                 dtype_bytes=4, mode=mode)
        y_plan, aux_plan = jax.jit(
            lambda p, x: strategy.execute("fse_dp", p, x, moe, "swiglu", plan=plan)
        )(params, x)
        y_ref, aux_ref = forced_reference(plan)
        assert np.array_equal(np.asarray(y_plan), np.asarray(y_ref)), \
            f"{mode}: plan-driven != forced (max diff " \
            f"{np.abs(np.asarray(y_plan) - np.asarray(y_ref)).max():.2e})"
        assert np.array_equal(np.asarray(aux_plan), np.asarray(aux_ref)), mode
        print(f"{mode:8s} plan-driven == forced  M={plan.micro_slices} "
              f"kopts={plan.kernel_opts()}")

    # default (auto) plan == its own forced re-execution
    auto = autotune.plan_moe(B_grp, S, d, moe, "swiglu", P_, dtype_bytes=4)
    y_auto, _ = jax.jit(
        lambda p, x: strategy.execute("fse_dp", p, x, moe, "swiglu"))(params, x)
    y_ref, _ = forced_reference(auto)
    assert np.array_equal(np.asarray(y_auto), np.asarray(y_ref))
    print(f"auto plan ({auto.mode}, source={auto.source}) == forced")

    # level='off' reproduces the legacy static heuristic dispatch
    with autotune.use_autotune("off"):
        off = autotune.plan_moe(B_grp, S, d, moe, "swiglu", P_, dtype_bytes=4)
        assert off.source == "fallback" and off.mode == "stream" \
            and off.micro_slices == moe.micro_slices
        y_off, _ = jax.jit(
            lambda p, x: strategy.execute("fse_dp", p, x, moe, "swiglu"))(params, x)
    y_ref_off, _ = forced_reference(off)
    assert np.array_equal(np.asarray(y_off), np.asarray(y_ref_off))
    print("off-level fallback == legacy static dispatch")

print("AUTOTUNE PLAN PARITY OK")
