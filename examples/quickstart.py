"""Quickstart: the paper's idea in 60 seconds on CPU.

1. builds a small MoE layer,
2. shows FSE-DP expert streaming == the dense oracle (order-invariant
   micro-slice partial sums),
3. runs one chiplet-simulator comparison (FSE-DP vs EP latency+memory).

  PYTHONPATH=src python examples/quickstart.py
"""
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.core import gating
from repro.kernels import ref
from repro.kernels.streamed_moe import streamed_moe_kernel
from repro.models import moe as moe_mod
from repro.sim import PROTOTYPE_2X2, PAPER_SPECS, iteration_workloads, simulate_layer


def main():
    print("== 1. micro-slice order invariance (the virtualization argument) ==")
    E, C, d, de, M = 4, 16, 32, 64, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    xe = jax.random.normal(ks[0], (E, C, d), jnp.float32)
    wg = jax.random.normal(ks[1], (E, d, de)) * 0.1
    wu = jax.random.normal(ks[2], (E, d, de)) * 0.1
    wd = jax.random.normal(ks[3], (E, de, d)) * 0.1
    full = ref.streamed_moe_ref(xe, wg, wu, wd, "swiglu")
    mic = de // M
    order = np.random.default_rng(0).permutation(M)
    parts = sum(streamed_moe_kernel(xe, wg[..., i*mic:(i+1)*mic],
                                    wu[..., i*mic:(i+1)*mic],
                                    wd[:, i*mic:(i+1)*mic, :], activation="swiglu")
                for i in order)
    err = float(jnp.max(jnp.abs(parts - full)))
    print(f"   Σ(micro-slices in random order {list(order)}) vs whole expert: "
          f"max err = {err:.2e}  ✓ trajectory order is immaterial")

    print("== 2. FSE-DP distributed == dense oracle (8 fake devices) ==")
    script = os.path.join(os.path.dirname(__file__), "..", "tests",
                          "distributed_scripts", "fsedp_modes.py")
    # a CPU rehearsal by design (8 fake devices): the child never
    # competes with this process for an accelerator
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, script], env=env, capture_output=True,
                         text=True, timeout=900)
    print("   " + out.stdout.strip().splitlines()[-1])

    print("== 3. chiplet simulator: FSE-DP vs EP (paper Table-I hardware) ==")
    hw = PROTOTYPE_2X2
    spec = PAPER_SPECS["qwen3-a3b"]
    wl = iteration_workloads(spec, tokens_per_iter=64,
                             num_chiplets=hw.num_chiplets, seed=0)[0]
    for strat in ("ep", "fse_dp_paired"):
        r = simulate_layer(hw, spec, wl, strat)
        print(f"   {strat:14s} latency={r.latency*1e6:8.0f}us  "
              f"package-mem={r.peak_buffer_bytes/2**20:6.1f}MB  "
              f"util={r.utilization:.3f}")


if __name__ == "__main__":
    main()
