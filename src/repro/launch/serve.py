"""Serving driver: low-batch decode with the layer-stepped engine
(chunked admission, continuous batching, Algorithm-2 token buffering).

  PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-1b-a400m \
      --reduced --requests 6 --max-new 16 --slack 0.2

Closed-loop traffic mode (``--traffic``) drives the continuous-batching
scheduler with Poisson arrivals instead of a hand-fed batch: requests
stream through a bounded admission queue (``--queue-capacity``,
``--queue-policy``), prompts prefill in ``--chunk-tokens``-token chunks
piggybacked on the decode batch, and the run reports TTFT / TPOT /
queue-delay p50/p95/p99 plus throughput:

  PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-1b-a400m \
      --reduced --traffic --traffic-requests 32 --traffic-rate 0.8 \
      --chunk-tokens 8

``--traffic --dry-run`` runs a tiny deterministic closed loop (CI smoke).
State lives in the paged pool (``--page-size``); ``--prefix-cache``
turns on content-hashed prompt-prefix reuse, ``--preempt-depth`` lets
the scheduler evict/restore requests under queue pressure, and
``--traffic-mix`` shapes the stream (``zipf_prefix`` shared system
prompts, ``diurnal`` arrival bursts).

MoE execution is configured by a single :class:`ExecutionSpec`
(``repro.core.strategy``): ``--strategy`` names a registered strategy
(fse_dp / ep / tp / hybrid / capacity / dense / auto), ``--moe-spec
path.json``
loads a full spec (per-phase + per-layer overrides, autotune level,
kernels/dispatch toggles); ``--autotune`` overrides the spec's level.
``--dry-run`` validates the spec (JSON round-trip + registry lookup) and
builds the engine through one tiny request without the full decode loop.
"""
from __future__ import annotations

import argparse
import time


def build_spec(args):
    import dataclasses
    from repro.core.strategy import ExecutionSpec
    if args.moe_spec:
        spec = ExecutionSpec.load(args.moe_spec)
        if args.strategy:
            spec = dataclasses.replace(spec, strategy=args.strategy)
    else:
        spec = ExecutionSpec(strategy=args.strategy or "capacity")
    # CLI overrides fold straight into the spec (ServeConfig's autotune
    # alias is deprecated)
    if getattr(args, "autotune", None):
        spec = dataclasses.replace(spec, autotune=args.autotune)
    if getattr(args, "schedule", None):
        spec = dataclasses.replace(spec, schedule=args.schedule)
    if getattr(args, "weight_dtype", None):
        spec = dataclasses.replace(spec, weight_dtype=args.weight_dtype)
    return spec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slack", type=float, default=0.0)
    ap.add_argument("--theta-min", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--strategy", default=None,
                    help="MoE execution strategy (registry name: fse_dp, "
                         "ep, tp, hybrid, capacity, dense, auto); "
                         "default capacity")
    ap.add_argument("--moe-spec", default=None,
                    help="path to an ExecutionSpec JSON (see "
                         "examples/moe-spec.json); --strategy overrides "
                         "its default strategy field")
    ap.add_argument("--autotune", choices=("off", "analytic", "measured"),
                    default=None,
                    help="override the spec's autotune level "
                         "(core.autotune); 'measured' times kernel "
                         "candidates once and caches them under "
                         "artifacts/autotune/")
    ap.add_argument("--schedule", choices=("static", "dynamic"),
                    default=None,
                    help="expert-trajectory scheduling (core.trajectory): "
                         "'dynamic' re-plans each layer's trajectory from "
                         "the EMA of observed gating counts (outputs are "
                         "bit-identical; execution order changes)")
    ap.add_argument("--weight-dtype", choices=("fp32", "bf16", "int8", "fp8"),
                    default=None,
                    help="streamed storage format for expert FFN weights "
                         "(kernels.quant): int8/fp8 quantize in-graph with "
                         "per-channel scales and halve/quarter the expert "
                         "DDR stream; default keeps params as-is (see "
                         "docs/quantization.md)")
    ap.add_argument("--resident-budget-mb", type=float, default=0.0,
                    help="EMA-hot expert weight tier: total bytes of "
                         "expert weights pinned resident on-package "
                         "(split evenly across MoE layers; hottest "
                         "experts by LoadTracker EMA); resident experts "
                         "skip their DDR stream in the modeled clock and "
                         "trace. 0 disables the tier")
    ap.add_argument("--hot-experts", type=int, default=None,
                    help="hybrid two-tier placement: fast-tier expert "
                         "count per MoE layer (default: top quartile, "
                         "strategy.default_hot); the engine repartitions "
                         "per iteration off the LoadTracker EMA and "
                         "records the hot ids in the trace")
    ap.add_argument("--dry-run", action="store_true",
                    help="validate the spec (JSON round-trip + registry) "
                         "and exercise one tiny request, then exit "
                         "(with --traffic: a tiny closed loop)")
    ap.add_argument("--traffic", action="store_true",
                    help="closed-loop mode: Poisson arrivals through the "
                         "continuous-batching scheduler (chunked prefill), "
                         "reporting TTFT/TPOT/queue-delay percentiles")
    ap.add_argument("--traffic-requests", type=int, default=32)
    ap.add_argument("--traffic-rate", type=float, default=0.5,
                    help="mean Poisson arrivals per second (wall clock)")
    ap.add_argument("--traffic-mix", default="poisson",
                    help="traffic mix: 'poisson' plus '+'-separated "
                         "modifiers 'zipf_prefix' (Zipf-shared system "
                         "prompts) and 'diurnal' (arrival-rate bursts), "
                         "e.g. poisson+zipf_prefix+diurnal")
    ap.add_argument("--avg-prompt", type=int, default=12)
    ap.add_argument("--chunk-tokens", type=int, default=8,
                    help="prefill chunk size piggybacked per iteration")
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument("--queue-policy", choices=("fcfs", "spf"),
                    default="fcfs")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per physical KV page in the state pool "
                         "(repro.serving.statepool)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-hash prompt prefixes and admit shared "
                         "prefixes with near-zero prefill compute")
    ap.add_argument("--preempt-depth", type=int, default=None,
                    help="queue depth past which the scheduler preempts "
                         "one running request per step to the state pool "
                         "(default: never preempt; 0 forces preemption "
                         "whenever the queue is non-empty and full)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import numpy as np
    from repro.configs import get_config, reduced_config
    from repro.launch.compile_cache import configure_compile_cache
    from repro.core.strategy import ExecutionSpec
    from repro.models import api
    from repro.serving import Engine, ServeConfig

    configure_compile_cache()
    spec = build_spec(args)
    roundtrip = ExecutionSpec.from_json(spec.to_json())
    if roundtrip != spec:
        raise SystemExit(f"spec JSON round-trip mismatch:\n{spec}\n{roundtrip}")
    spec.validate()
    print(f"moe spec: {spec.to_json()}")

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.reduced:
        cfg = cfg.replace(dtype="float32")
    params = api.init_params(jax.random.PRNGKey(args.seed), cfg)

    if args.traffic:
        from repro.serving import (Scheduler, SchedulerConfig, TrafficConfig,
                                   make_traffic, run_closed_loop)
        n_req = (args.traffic_requests if args.traffic_requests != 32
                 else 4) if args.dry_run else args.traffic_requests
        max_prompt = max(2, min(args.avg_prompt * 2,
                                args.prompt_len + args.avg_prompt))
        tcfg = TrafficConfig(num_requests=n_req, rate=args.traffic_rate,
                             avg_prompt=args.avg_prompt,
                             max_prompt=max_prompt, min_new=2,
                             max_new=args.max_new, vocab=cfg.vocab_size,
                             seed=args.seed, mix=args.traffic_mix)
        traffic = make_traffic(tcfg)
        need_ctx = max_prompt + args.max_new + 1
        eng = Engine(params, cfg, ServeConfig(
            max_batch=args.max_batch, max_ctx=need_ctx,
            buffering_slack=args.slack, theta_min=args.theta_min,
            chunk_tokens=args.chunk_tokens, spec=spec, seed=args.seed,
            page_size=args.page_size, prefix_cache=args.prefix_cache,
            preempt_queue_depth=args.preempt_depth,
            resident_budget_mb=args.resident_budget_mb,
            hot_experts=args.hot_experts))
        clock = None if args.dry_run else time.monotonic
        sched = Scheduler(eng, SchedulerConfig(
            queue_capacity=args.queue_capacity, policy=args.queue_policy),
            clock=clock)
        res = run_closed_loop(sched, traffic)
        m = res["metrics"]
        unit = "iters" if args.dry_run else "s"
        if args.dry_run and m.completed < n_req:
            raise SystemExit(f"traffic dry-run incomplete: "
                             f"{m.completed}/{n_req}")
        print(f"traffic: {m.completed} completed, {len(res['dropped'])} "
              f"dropped, {m.rejected} rejected, {m.iterations} iterations")
        for name, pct in (("ttft", m.ttft), ("tpot", m.tpot),
                          ("queue_delay", m.queue_delay)):
            print(f"  {name:12s} p50={pct['p50']:.3f} p95={pct['p95']:.3f} "
                  f"p99={pct['p99']:.3f} {unit}")
        print(f"  throughput   {m.throughput:.2f} tok/{unit} "
              f"({m.tokens_emitted} tokens, "
              f"{eng.stats['prefill_chunks']} prefill chunks, "
              f"{eng.stats['deferrals']} deferrals)")
        s = eng.stats
        print(f"  state pool   peak {s['pool_peak_pages']}/"
              f"{s['pool_pages']} pages, "
              f"{s['peak_resident_state_bytes']} peak resident bytes, "
              f"{s['cache_hits']} cache hits / {s['cache_misses']} misses "
              f"({s['prefill_tokens_saved']} prefill tokens saved), "
              f"{s['preemptions']} preemptions / {s['restores']} restores")
        print(f"  weight tier  {spec.weight_dtype or cfg.dtype} weights, "
              f"{s['resident_weight_bytes']} resident expert bytes "
              f"({eng._n_resident}/layer), "
              f"{s['ddr_bytes_saved']} DDR bytes saved")
        if args.dry_run and args.preempt_depth is not None \
                and s["preemptions"] < 1:
            raise SystemExit("preemption smoke: --preempt-depth was set "
                             "but no request was ever preempted — queue "
                             "pressure never materialized (check "
                             "--traffic-requests vs --max-batch)")
        if args.dry_run:
            print("traffic dry-run OK")
        return

    if args.dry_run:
        eng = Engine(params, cfg, ServeConfig(
            max_batch=2, max_ctx=16, spec=spec, seed=args.seed,
            resident_budget_mb=args.resident_budget_mb,
            hot_experts=args.hot_experts))
        eng.submit([1, 2, 3, 4], max_new=2)
        outs = eng.run(max_iterations=8)
        n = sum(len(t) for t in outs.values())
        if n < 1:
            raise SystemExit("dry-run emitted no tokens")
        s = eng.stats
        print(f"dry-run OK: spec={eng.scfg.spec.to_json()} tokens={n}")
        print(f"  weight tier  {spec.weight_dtype or cfg.dtype} weights, "
              f"{s['resident_weight_bytes']} resident expert bytes "
              f"({eng._n_resident}/layer), "
              f"{s['ddr_bytes_saved']} DDR bytes saved")
        return

    eng = Engine(params, cfg, ServeConfig(
        max_batch=args.max_batch, max_ctx=args.prompt_len + args.max_new + 8,
        buffering_slack=args.slack, theta_min=args.theta_min,
        spec=spec, seed=args.seed,
        resident_budget_mb=args.resident_budget_mb,
        hot_experts=args.hot_experts))

    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=args.prompt_len).tolist()
        eng.submit(prompt, max_new=args.max_new)
    outs = eng.run()
    dt = time.time() - t0
    for rid, toks in outs.items():
        print(f"{rid}: {toks[:12]}{'...' if len(toks) > 12 else ''}")
    s = eng.stats
    print(f"tokens={s['tokens_emitted']} iterations={s['iterations']} "
          f"deferrals={s['deferrals']} expert_loads={s['expert_loads']} "
          f"loads_saved={s['expert_loads_saved']} "
          f"dynamic_schedules={s['dynamic_schedules']} "
          f"throughput={s['tokens_emitted']/dt:.1f} tok/s")
    print(f"weight tier: {spec.weight_dtype or cfg.dtype} weights, "
          f"{s['resident_weight_bytes']} resident expert bytes "
          f"({eng._n_resident}/layer), "
          f"{s['ddr_bytes_saved']} DDR bytes saved")


if __name__ == "__main__":
    main()
