"""Dynamic trajectory scheduling on 8 fake devices: for every
distributed family (fse_dp stream/index/slice forced + planned, ep, tp)
and for a host-built EMA schedule, ``schedule=dynamic`` must produce
exactly the arrays of the static run — the paper's virtualization
argument (scheduling changes expert execution order/timing only, never
values), checked bit for bit through the real shard_map lowerings."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.core import autotune, strategy as strat, trajectory
from repro.core.strategy import ExecutionSpec
from repro.models import moe as moe_mod
from repro.parallel import meshctx
from repro.launch.mesh import make_mesh

moe = MoEConfig(num_experts=8, top_k=2, d_expert=64, capacity_factor=4.0,
                micro_slices=2)
D = 32
params = moe_mod.moe_init(jax.random.PRNGKey(0), D, moe, "swiglu",
                          jnp.float32)
mesh = make_mesh((2, 4), ("data", "model"))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, D), jnp.float32)

with meshctx.with_mesh(mesh):
    # families via the registry spec knob (in-graph traced trajectory)
    for fam in ("fse_dp", "ep", "tp"):
        ys, auxs = jax.jit(lambda p, xx, f=fam: strat.execute(
            f, p, xx, moe, "swiglu"))(params, x)
        yd, auxd = jax.jit(lambda p, xx, f=fam: strat.execute(
            ExecutionSpec(strategy=f, schedule="dynamic"),
            p, xx, moe, "swiglu"))(params, x)
        assert np.array_equal(np.asarray(ys), np.asarray(yd)), \
            f"{fam}: dynamic != static (max diff " \
            f"{np.abs(np.asarray(ys) - np.asarray(yd)).max():.2e})"
        assert np.array_equal(np.asarray(auxs), np.asarray(auxd)), fam
        print(f"{fam}: dynamic == static bit-identical")

    # every forced FSE-DP mode (B_grp=2 per model group, S=16, P=4)
    for mode in ("stream", "index", "slice"):
        plan = autotune.plan_moe(2, 16, D, moe, "swiglu", 4, mode=mode)
        ys, _ = strat.execute("fse_dp", params, x, moe, "swiglu", plan=plan)
        yd, _ = strat.execute(ExecutionSpec(strategy="fse_dp",
                                            schedule="dynamic"),
                              params, x, moe, "swiglu", plan=plan)
        assert np.array_equal(np.asarray(ys), np.asarray(yd)), mode
        print(f"fse_dp[{mode}]: dynamic == static bit-identical")

    # host-built EMA schedule (the serving engine's feedback path),
    # including a load-aware re-plan from the same EMA vector
    tracker = trajectory.LoadTracker(moe.num_experts, decay=0.8)
    rng = np.random.default_rng(0)
    for _ in range(4):
        tracker.update(rng.integers(0, 12, size=moe.num_experts))
    plan = autotune.plan_moe(2, 16, D, moe, "swiglu", 4,
                             load=tracker.load_vector())
    sched = tracker.schedule(plan=plan)
    assert sched.order is not None and sched.plan is not None
    ys, _ = strat.execute("fse_dp", params, x, moe, "swiglu", plan=plan)
    yd, _ = strat.execute("fse_dp", params, x, moe, "swiglu", schedule=sched)
    assert np.array_equal(np.asarray(ys), np.asarray(yd)), "EMA schedule"
    print("fse_dp[EMA host schedule + load-aware plan]: bit-identical")

    # a host-built (global-order) schedule on the expert-sharded EP body:
    # the body must re-derive its owned-expert trajectory locally, not
    # apply the global E-length order to its E_loc shard
    ys, _ = strat.execute("ep", params, x, moe, "swiglu")
    yd, _ = strat.execute("ep", params, x, moe, "swiglu",
                          schedule=tracker.schedule())
    assert np.array_equal(np.asarray(ys), np.asarray(yd)), "EP host schedule"
    print("ep[EMA host schedule]: bit-identical")

print("DYNAMIC SCHEDULE PARITY OK")
