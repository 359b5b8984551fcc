"""TPU-adaptation analogue of Fig. 9/12: per-device weight bytes and HLO
collective traffic of DP / TP / EP / FSE-DP / auto MoE layers on a (2,4)
mesh (8 host devices — runs in a subprocess so the parent stays 1-device).

Every strategy is reached through the execution-strategy registry
(``repro.core.strategy``); the ``auto`` row lets the cross-family
planner pick the winning family for the shape.  A second, host-side
sweep routes Zipf-skewed token loads through the chiplet trajectory
simulation (``sim.modes.schedule_step_times``) and records the static
(shape-only) vs dynamic (gating-count-built paired trajectory) step
time per point — the regression gate requires the dynamic schedule to
keep beating the static plan on a majority of skewed points.  Emits CSVs
plus ``artifacts/bench/BENCH_moe_strategies.json``; the committed copy
under ``benchmarks/baselines/`` is the CI regression baseline
(``check_regression.py``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from .common import emit

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "bench")

# (tokens_per_iter, zipf_s, seed) — low-batch decode through prefill-ish
# iteration sizes, at two skew strengths, deterministic routing seeds
SKEW_SWEEP = (
    (16, 1.1, 0), (16, 1.5, 1),
    (32, 1.1, 2), (32, 1.5, 3),
    (128, 1.1, 4), (128, 1.5, 5),
    (512, 1.1, 6), (512, 1.5, 7),
)


def skewed_schedule_rows():
    """Static-vs-dynamic simulated step times on Zipf-routed gating."""
    import numpy as np
    from repro.sim import modes as sim_modes, workload
    from repro.sim.hardware import PROTOTYPE_2X2, ModelSpec

    spec = ModelSpec("skew-bench", 2048, 1408, 64, 6, 3)
    rows = []
    for tokens, zipf_s, seed in SKEW_SWEEP:
        rng = np.random.default_rng(seed)
        p = workload.sample_expert_probs(spec.num_experts, rng, zipf_s)
        counts = workload.route_tokens(spec.num_experts, spec.top_k,
                                       tokens, p, rng)
        t = sim_modes.schedule_step_times(PROTOTYPE_2X2, spec, counts)
        rows.append({"tokens": tokens, "zipf_s": zipf_s, "seed": seed,
                     "active_experts": int((counts > 0).sum()),
                     "static_us": t["static"] * 1e6,
                     "dynamic_us": t["dynamic"] * 1e6,
                     "dynamic_unpaired_us": t["dynamic_unpaired"] * 1e6,
                     "win": bool(t["dynamic"] < t["static"])})
    return rows

# (E, d_expert, tokens, zipf_s) — compute-sensitive Zipf points where
# the load-aware fast-tier partition must beat the static id-prefix
DYN_PARTITION_SWEEP = (
    (64, 1408, 256, 1.2), (64, 1408, 512, 1.2),
    (64, 768, 512, 1.4), (32, 1408, 256, 1.2),
)


def hybrid_sweep_rows():
    """Two-tier hybrid referee sweep (host-side, deterministic).

    For every point of the committed ``strategy.HYBRID_SWEEP`` the
    analytic family cost model picks a winner and the chiplet simulator
    referees it; the regression gate requires >=80% agreement and each
    of hybrid / EP / FSE-DP winning somewhere.  A second block prices
    the dynamic (EMA-hottest) fast-tier partition against the static
    top-N id prefix on Zipf-skewed load.
    """
    import numpy as np
    from repro.configs.base import MoEConfig
    from repro.core import autotune
    from repro.core import strategy as strat
    from repro.sim import hardware as hwmod
    from repro.sim import modes as sim_modes, workload

    def ndp_hw(P):
        base = {2: hwmod.scaled(1, 2), 4: hwmod.scaled(2, 2),
                8: hwmod.scaled(2, 4)}[P]
        return hwmod.with_ndp(base)

    sweep = []
    for (B, S, E, de, P, zs) in strat.HYBRID_SWEEP:
        hw = ndp_hw(P)
        profile = autotune.HardwareProfile.from_chiplet(hw)
        moe = MoEConfig(num_experts=E, top_k=2, d_expert=de)
        loads = None
        if zs > 0:
            rng = np.random.default_rng(0)
            loads = workload.sample_expert_probs(E, rng, zipf_s=zs)
        lt = None if loads is None else tuple(float(v) for v in loads)
        costs = strat.family_costs(B, S, 512, moe, "swiglu", P,
                                   profile=profile, load=lt)
        chosen = strat.pick_family(costs)
        sim = sim_modes.rank_families(
            hw, hwmod.ModelSpec("s", 512, de, E, 2), B * S, B=B, S=S,
            loads=loads)
        best = min((f for f in strat.FAMILIES if f in sim),
                   key=lambda f: sim[f])
        sweep.append({"B": B, "S": S, "E": E, "d_expert": de, "P": P,
                      "zipf_s": zs, "cost_family": chosen,
                      "sim_family": best, "sim_us": sim[best] * 1e6,
                      "agree": bool(chosen == best)})

    hw = ndp_hw(4)
    partition = []
    for (E, de, tokens, zs) in DYN_PARTITION_SWEEP:
        spec = hwmod.ModelSpec("s", 512, de, E, 2)
        rng = np.random.default_rng(7)
        loads = workload.sample_expert_probs(E, rng, zipf_s=zs)
        N = strat.default_hot(E)
        static = sim_modes.simulate_hybrid(
            hw, spec, tokens, loads=loads, hot_ids=range(N)).latency
        dyn_ids = np.argsort(-loads, kind="stable")[:N]
        dynamic = sim_modes.simulate_hybrid(
            hw, spec, tokens, loads=loads, hot_ids=dyn_ids).latency
        partition.append({"E": E, "d_expert": de, "tokens": tokens,
                          "zipf_s": zs, "hot_n": N,
                          "static_us": static * 1e6,
                          "dynamic_us": dynamic * 1e6,
                          "win": bool(dynamic < static)})
    return {"sweep": sweep, "partition": partition}


_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs.base import MoEConfig
from repro.models import moe as moe_mod
from repro.core import autotune, strategy
from repro.parallel import meshctx
from repro.launch.mesh import make_mesh
from repro.launch.analysis import collective_bytes

E, k, d, de = 16, 2, 256, 512
moe = MoEConfig(num_experts=E, top_k=k, d_expert=de, micro_slices=4)
params = moe_mod.moe_init(jax.random.PRNGKey(0), d, moe, "swiglu", jnp.bfloat16)
mesh = make_mesh((2, 4), ("data", "model"))
B, S = 8, 64
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d), jnp.bfloat16)

# one registry for every strategy: the fse_dp row pins the paper's
# signature stream trajectory via a forced plan; fse_dp_auto lets the
# within-family cost model pick mode/micro-slices/tiles; auto lets the
# cross-family planner pick the winning *family* for this shape
B_grp = B // 2                       # data axis is 2-way
stream_plan = autotune.plan_moe(B_grp, S, d, moe, "swiglu", 4,
                                dtype_bytes=2, mode="stream")
family_plan = strategy.plan_family(B_grp, S, d, moe, "swiglu", 4,
                                   dtype_bytes=2)

def run(name, plan=None):
    def fn(p, x, moe, act):
        return strategy.execute(name, p, x, moe, act, plan=plan)
    return fn

def lower(fn, w_specs):
    in_sh = (jax.tree.map(lambda s: NamedSharding(mesh, s), w_specs),
             NamedSharding(mesh, P("data", "model", None)))
    with meshctx.with_mesh(mesh):
        return jax.jit(lambda p, x: fn(p, x, moe, "swiglu"),
                       in_shardings=in_sh).lower(params_like(w_specs), x).compile()

def params_like(_):
    return params

W = sum(int(v.size) * 2 for kk, v in params.items() if kk.startswith("w_"))
rows = []
specs_fse = {"router": {"w_router": P()}, "w_gate": P(None, None, "model"),
             "w_up": P(None, None, "model"), "w_down": P(None, "model", None)}
specs_ep = {"router": {"w_router": P()}, "w_gate": P("model", None, None),
            "w_up": P("model", None, None), "w_down": P("model", None, None)}
specs_dp = {"router": {"w_router": P()}, "w_gate": P(), "w_up": P(), "w_down": P()}

auto_specs = {"fse_dp": specs_fse, "tp": specs_fse, "ep": specs_ep}
for name, fn, specs, shard_frac in [
        ("dp_replicated", run("fse_dp"), specs_dp, 1.0),
        ("tp", run("tp"), specs_fse, 0.25),
        ("ep", run("ep"), specs_ep, 0.25),
        ("fse_dp", run("fse_dp", stream_plan), specs_fse, 0.25),
        ("fse_dp_auto", run("fse_dp"), specs_fse, 0.25),
        ("auto", run("auto"), auto_specs[family_plan.family], 0.25)]:
    compiled = lower(fn, specs)
    coll = collective_bytes(compiled.as_text())
    rows.append({"strategy": name,
                 "weight_bytes_per_device": int(W * shard_frac),
                 "coll_total": coll["total"],
                 "all_to_all": coll["all-to-all"],
                 "collective_permute": coll["collective-permute"],
                 "all_gather": coll["all-gather"],
                 "all_reduce": coll["all-reduce"] + coll["reduce-scatter"]})
print(json.dumps({"rows": rows, "auto_family": family_plan.family,
                  "shape": {"B": B, "S": S, "E": E, "d_model": d,
                            "d_expert": de, "mesh": "2x4"}}))
"""


def run():
    # a CPU rehearsal by design: the child never competes with a parent
    # that may hold the chip
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(f"child failed: {out.stderr[-2000:]}")
    data = json.loads(out.stdout.strip().splitlines()[-1])
    rows = [[r["strategy"], r["weight_bytes_per_device"], int(r["coll_total"]),
             int(r["all_to_all"]), int(r["collective_permute"]),
             int(r["all_gather"]), int(r["all_reduce"])] for r in data["rows"]]
    emit("jax_moe_strategies", rows,
         ["strategy", "weight_B_per_dev", "coll_total_B", "all_to_all_B",
          "collective_permute_B", "all_gather_B", "all_reduce_B"])

    skewed = skewed_schedule_rows()
    emit("jax_moe_strategies_skewed",
         [[r["tokens"], r["zipf_s"], r["active_experts"],
           round(r["static_us"], 2), round(r["dynamic_us"], 2),
           int(r["win"])] for r in skewed],
         ["tokens", "zipf_s", "active_E", "static_us", "dynamic_us", "win"])
    wins = sum(r["win"] for r in skewed)
    print(f"# skewed gating: dynamic schedule wins {wins}/{len(skewed)} "
          f"points")

    hybrid = hybrid_sweep_rows()
    emit("jax_moe_strategies_hybrid",
         [[r["B"], r["S"], r["E"], r["d_expert"], r["P"], r["zipf_s"],
           r["cost_family"], r["sim_family"], int(r["agree"])]
          for r in hybrid["sweep"]],
         ["B", "S", "E", "d_expert", "P", "zipf_s", "cost_family",
          "sim_family", "agree"])
    n_agree = sum(r["agree"] for r in hybrid["sweep"])
    part_wins = sum(r["win"] for r in hybrid["partition"])
    print(f"# hybrid two-tier: cost/sim agreement "
          f"{n_agree}/{len(hybrid['sweep'])}, dynamic partition wins "
          f"{part_wins}/{len(hybrid['partition'])}")

    import jax
    payload = {
        "bench": "jax_moe_strategies",
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "unix_time": int(time.time()),
        "auto_family": data["auto_family"],
        "shape": data["shape"],
        "rows": data["rows"],
        "skewed": skewed,
        "hybrid": hybrid,
    }
    os.makedirs(ART, exist_ok=True)
    path = os.path.join(ART, "BENCH_moe_strategies.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# auto family: {data['auto_family']} -> {os.path.relpath(path)}")
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
