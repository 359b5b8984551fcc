"""Readings that set a cell's correctness limits: sound runs, the
control, and a token altered where it is produced.

    python3 benchmarks/tpu/control.py --workload granite.b1_decode \\
        --seconds 15 --seeds 1 2 3 ... [--dtypes fp8 int8]

For each seed, in one process (one warm-up compile for all seeds): the
weights from the seed, a window of the cell's own traffic at its own
load, and the check's sample of served requests, all through the same
code as a benchmark run.  Three judgements of ``check.Judge`` follow,
each against the same reference:

* ``sound``: the window's own tokens and logits, as ``run.py`` judges
  them;
* ``<dtype>`` (the control): the program's own lower-precision path,
  ``ExecutionSpec(weight_dtype=...)`` (int8 or fp8 expert streaming),
  teacher-forced over the same prompts and served tokens; its logits
  at each position, and the token it puts first there;
* ``altered``: the sound logits with every served token moved to the
  next vocabulary id, which is what a token altered after its logits
  were made reads.

Each judgement prints its ``correct`` under the cell's committed
limits; the limits lie between the largest sound reading and the
smallest reading of the control or the fault (PERF.md).  Not run by
the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

import check  # noqa: E402


def forced(params, cfg, scfg, picked, weight_dtype, keep):
    """Teacher-force ``picked`` requests through the engine with its
    expert weights streamed as ``weight_dtype``.  Returns, per request,
    the tokens the engine put first at the served positions and its
    logits rows there at the vocabulary ids ``keep``."""
    import serve_window as sw

    spec = dataclasses.replace(scfg.spec, weight_dtype=weight_dtype)
    force = {}
    eng = sw.logit_keeping_engine(
        params, cfg, dataclasses.replace(scfg, spec=spec), keep, force)
    out = [None] * len(picked)
    todo = list(enumerate(picked))
    while todo:
        wave, todo = todo[:scfg.max_batch], todo[scfg.max_batch:]
        rids = []
        for i, s in wave:
            rid = eng.submit_chunked(list(s.req.prompt), len(s.tokens))
            force[rid] = list(s.tokens)
            rids.append((i, rid))
        eng.run()
        for i, rid in rids:
            out[i] = (eng.chose[rid], eng.kept[rid])
    return out


def readings(cell, seeds, seconds, dtypes, *, require_chip=True, log=print):
    """{seed: {"sound": judgement, <dtype>: judgement, "altered":
    judgement}}, each judgement as ``check.Judge`` gives it."""
    import init_weights
    import loadgen
    import run as R
    import serve_window as sw
    R.device_record(require_chip, cell.chips)
    cfg = R.build_config(cell.config)
    lim = cell.limits
    out = {}
    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        params = init_weights.make_params(cfg, seed)
        scfg = sw.engine_config(cfg, cell.traffic, seed)
        if n == 0:
            sw.warm_up(params, cfg, scfg)
        stream = loadgen.make_stream(cell.traffic, seed, seconds,
                                     cfg.vocab_size)
        keep = check.vocab_sample(seed, cfg.vocab_size, lim["vocab_sample"])
        window = sw.run_window(params, cfg, scfg, cell.traffic, stream,
                               seconds, keep)
        gc.collect()
        picked = check.sample(window.served, seed, int(lim["tokens"]),
                              int(lim["max_requests"]))
        judge = check.Judge(params, cell, picked, seed)
        tokens = [s.tokens for s in picked]
        logits = [s.logits for s in picked]
        res = {"sound": judge(tokens, logits),
               "altered": judge([[(t + 1) % cfg.vocab_size for t in toks]
                                 for toks in tokens], logits)}
        for dt in dtypes:
            got = forced(params, cfg, scfg, picked, dt, keep)
            res[dt] = judge([c for c, _ in got], [k for _, k in got])
        out[seed] = res
        log(f"seed {seed}: " + json.dumps(
            {k: {"correct": v["correct"], **v["readings"]}
             for k, v in res.items()})
            + f" ({res['sound']['tokens']} tokens, "
              f"{time.perf_counter() - t0:.1f} s)")
        del params, judge, window
        gc.collect()
    return out


def main(argv=None):
    import run as R
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dtypes", nargs="*", default=["fp8", "int8"])
    ap.add_argument("--dump", help="write every position's readings "
                    "of every judgement to this JSON file")
    args = ap.parse_args(argv)
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    configure_compile_cache()
    res = readings(R.load_cell(args.workload), args.seeds, args.seconds,
                   args.dtypes, log=lambda s: print(s, flush=True))
    print(json.dumps({str(k): {j: v["readings"] for j, v in r.items()}
                      for k, r in res.items()}))
    if args.dump:
        with open(args.dump, "w") as f:
            json.dump({str(k): {j: v["positions"] for j, v in r.items()}
                       for k, r in res.items()}, f)


if __name__ == "__main__":
    main()
