"""Benchmark suite entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` summary CSV lines (full tables land
in artifacts/bench/*.csv)::

  PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import sys
import time


def _summary(name, t0, derived):
    us = (time.time() - t0) * 1e6
    print(f"{name},{us:.0f},{derived}")


def main() -> None:
    fast = "--fast" in sys.argv
    from . import (fig09_isolated, fig12_memory, fig14_e2e, fig15_ablation,
                   fig16_dse, fig17_granularity, fig18_scalability,
                   jax_moe_strategies, roofline)

    print("name,us_per_call,derived")

    t0 = time.time()
    rows = fig09_isolated.run(timeline=not fast)
    sp = [r[4] for r in rows if r[2] == "fse_dp_paired"]
    _summary("fig09_isolated_layer", t0,
             f"fse_dp_paired speedup vs EP: min={min(sp):.2f}x "
             f"mean={sum(sp)/len(sp):.2f}x max={max(sp):.2f}x")

    t0 = time.time()
    rows = fig12_memory.run()
    sav = [r[3] for r in rows if r[1] == "fse_dp_paired"]
    _summary("fig12_memory", t0,
             f"memory saving vs EP: {min(sav):.1f}%..{max(sav):.1f}%")

    t0 = time.time()
    rows = fig14_e2e.run(iterations=6 if fast else 12,
                         layer_sample=4 if fast else 6)
    sp = [r[4] for r in rows if r[1] == "fse_dp_paired" and r[2] == 0.2]
    _summary("fig14_e2e", t0,
             f"e2e speedup vs EP @20% slack: mean={sum(sp)/len(sp):.2f}x")

    t0 = time.time()
    rows = fig15_ablation.run()
    _summary("fig15_ablation", t0,
             "A1..A5 utilization: " + " ".join(
                 f"{r[1]}={r[4]:.3f}" for r in rows if r[0] == "qwen3-a3b"))

    if not fast:
        t0 = time.time()
        fig16_dse.run()
        _summary("fig16_dse", t0, "see artifacts/bench/fig16_dse.csv")

        t0 = time.time()
        fig17_granularity.run()
        _summary("fig17_granularity", t0, "see artifacts/bench/fig17_granularity.csv")

    t0 = time.time()
    rows = fig18_scalability.run()
    u = {(r[0], r[1]): r[2] for r in rows}
    _summary("fig18_scalability", t0,
             f"util 2x2->4x4: ep {u[('2x2','ep')]:.3f}->{u[('4x4','ep')]:.3f} "
             f"fse_dp {u[('2x2','fse_dp_paired')]:.3f}->{u[('4x4','fse_dp_paired')]:.3f}")

    t0 = time.time()
    rows = jax_moe_strategies.run()
    fse = next(r for r in rows if r[0] == "fse_dp")
    ep = next(r for r in rows if r[0] == "ep")
    _summary("jax_moe_strategies", t0,
             f"fse_dp a2a={fse[3]}B permute={fse[4]}B | ep a2a={ep[3]}B")

    t0 = time.time()
    rows = roofline.run()
    ok = [r for r in rows if r[3] == "ok"]
    _summary("roofline", t0,
             f"{len(ok)} compiled cells aggregated (artifacts/bench/roofline.csv)")


if __name__ == "__main__":
    main()
