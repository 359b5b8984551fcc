"""Find the knee of an open-loop traffic mix: serve it at several fixed
rates.

    python3 benchmarks/tpu/sweep.py --config granite-moe-1b-a400m \\
        --traffic chat_open.json --seconds 30 --rates 0.5 1.0 1.5

One process, one warm-up; for each rate a window of the mix with
``rate_rps`` replaced, and one JSON line of what it served.  The knee
is the highest rate at which the requests due in the last quarter of
the window wait no longer for their first token than those due earlier
(no growing backlog).  A cell then runs at about four fifths of it; the
rate is written into its traffic file by hand, with the sweep's lines
in PERF.md.  Not run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

VOCAB_SAMPLE = 2048


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True,
                    help="a configuration's name in BENCHMARK.json")
    ap.add_argument("--traffic", required=True,
                    help="an open-loop traffic mix (JSON file)")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    import check
    import init_weights
    import loadgen
    import run as R
    import serve_window as sw
    import windowstats as ws
    import work
    from repro.launch.compile_cache import configure_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    configure_compile_cache()
    bench = R._json(os.path.join(R.ROOT, "BENCHMARK.json"))
    conf = {c["name"]: c for c in bench["configs"]}[args.config]
    with open(args.traffic) as f:
        mix = json.load(f)
    cell = R.Cell(name=args.config + ".sweep", chips=1,
                  config=R._json(os.path.join(R.ROOT, conf["file"])),
                  traffic=mix, limits={})
    dev = R.device_record(True, cell.chips)
    cfg = R.build_config(cell.config)
    params = init_weights.make_params(cfg, args.seed)
    scfg = sw.engine_config(cfg, mix, args.seed)
    sw.warm_up(params, cfg, scfg)
    model = work.Model.from_config(cell.config["model"])
    keep = check.vocab_sample(args.seed, cfg.vocab_size, VOCAB_SAMPLE)
    for rate in args.rates:
        traffic = dict(mix, rate_rps=rate)
        stream = loadgen.make_stream(traffic, args.seed, args.seconds,
                                     cfg.vocab_size)
        w = sw.run_window(params, cfg, scfg, traffic, stream, args.seconds,
                          keep)
        run = R.Run(cell=cell, model=model, peak=work.peaks(dev["kind"]),
                    window=w, setup_s=0.0, peak_bytes=0)
        ttft = ws.ttfts(run)
        cut = w.start + 0.75 * args.seconds
        early = [t for t, s in zip(ttft, w.served) if s.due < cut]
        late = [t for t, s in zip(ttft, w.served) if s.due >= cut]
        print(json.dumps({
            "rate": rate, "offered": len(w.served),
            "finished": sum(s.finished is not None for s in w.served),
            "output_tok_s": ws.output_tokens(run) / w.seconds,
            "ttft_p50_ms": 1e3 * float(np.median(ttft)),
            "ttft_p90_ms": 1e3 * ws.percentile(ttft, 90),
            "ttft_early_p50_ms": 1e3 * float(np.median(early)) if early else None,
            "ttft_late_p50_ms": 1e3 * float(np.median(late)) if late else None,
            "itl_p95_ms": 1e3 * (ws.percentile(ws.inter_token_gaps(run), 95)
                                 or 0.0),
            "unadmitted_at_close": sum(s.admitted is None for s in w.served),
            "decode_iter_ms": ws.mean_step_ms(
                run, lambda s: s.decode and not s.prefill),
            "prefill_iter_ms": ws.mean_step_ms(run, lambda s: bool(s.prefill)),
        }), flush=True)


if __name__ == "__main__":
    main()
