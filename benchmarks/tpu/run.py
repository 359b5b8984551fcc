"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/tpu/run.py --workload granite.b1_decode \\
        --seed 7 --seconds 10 --trace 0

Everything a cell is made of is data, found by name from
``BENCHMARK.json``: the configuration (``configs/<config>.json``), the
traffic mix (``traffic/<traffic>.json``), the correctness limits
(``checks/<cell>.json``) and one reader per metric
(``metrics/<metric>.py``).  A run:

1. refuses anything but a TPU with the chips the cell asks for, and
   ``REPRO_NO_PALLAS``;
2. makes the weights on the device from the seed (one jitted call);
3. warms up the cell's own programs (JAX's persistent compile cache
   under the checkout, or ``JAX_COMPILATION_CACHE_DIR``);
4. serves the seeded traffic through ``Scheduler`` -> ``Engine`` for
   ``--seconds`` (``--trace 1``: under the profiler);
5. reads device memory, frees the serving state, and compares the
   logits and tokens of a seeded sample of the served requests with
   the float32 reference;
6. prints the compared numbers beside their limits on stderr, and the
   result as the last line of stdout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import check  # noqa: E402
import loadgen  # noqa: E402
import work  # noqa: E402


@dataclass
class Cell:
    """One entry of ``workloads`` with the files it names."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)     # metric entries
    per_layer: list = field(default_factory=list)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(os.path.join(root, conf["file"])),
        traffic=_json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
        limits=_json(os.path.join(HERE, "checks", name + ".json")),
        end_to_end=mine(bench["end_to_end"]),
        per_layer=mine(bench["per_layer"]))


def build_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry with every size of the file's ``model`` section applied."""
    import dataclasses
    from repro.configs import get_config
    base = get_config(conf["registry"])
    m = conf["model"]
    moe = dataclasses.replace(
        base.moe, num_experts=m["num_experts"], top_k=m["top_k"],
        d_expert=m["d_expert"], num_shared_experts=m["num_shared_experts"])
    return base.replace(
        num_layers=m["num_layers"], d_model=m["d_model"],
        num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
        head_dim=m["head_dim"], vocab_size=m["vocab_size"],
        rope_theta=float(m["rope_theta"]),
        tie_embeddings=bool(m["tie_embeddings"]), moe=moe,
        dtype=m["dtype"])


def load_reader(metric: str):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Run:
    """What the readers see of one run."""
    cell: Cell
    model: work.Model
    peak: dict
    window: object
    setup_s: float
    peak_bytes: int
    reduced: Optional[object] = None


def device_record(require_chip: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_chip:
        if rec["platform"] != "tpu":
            raise SystemExit(f"run.py: JAX found no TPU (platform "
                             f"{rec['platform']!r}); refusing to run")
        if rec["count"] < chips:
            raise SystemExit(f"run.py: the cell needs {chips} chips, JAX "
                             f"found {rec['count']}")
        if os.environ.get("REPRO_NO_PALLAS"):
            raise SystemExit("run.py: REPRO_NO_PALLAS is set; the cell "
                             "runs the Pallas kernels, not their oracles")
    return rec


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             log=print) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    import init_weights
    import serve_window as sw
    import trace_reduce

    dev = device_record(require_chip, cell.chips)
    peak = work.peaks(dev["kind"]) if require_chip else work.PEAKS[
        "TPU v5 lite"]
    cfg = build_config(cell.config)
    model = work.Model.from_config(cell.config["model"])
    params = init_weights.make_params(cfg, seed)
    log(f"weights made at {time.perf_counter() - t_start:.2f} s "
        f"after start")
    scfg = sw.engine_config(cfg, cell.traffic, seed)
    stream = loadgen.make_stream(cell.traffic, seed, seconds,
                                 cfg.vocab_size)
    keep = check.vocab_sample(seed, cfg.vocab_size,
                              cell.limits["vocab_sample"])
    sw.warm_up(params, cfg, scfg)
    counter = sw.CompileCounter()
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    try:
        with (jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
              if trace else contextlib.nullcontext()):
            window = sw.run_window(params, cfg, scfg, cell.traffic, stream,
                                   seconds, keep, annotate=trace,
                                   counter=counter)
    finally:
        if trace:
            jax.profiler.stop_trace()
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    reduced = None
    if trace:
        reduced = trace_reduce.reduce(trace_reduce.load(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
    run = Run(cell=cell, model=model, peak=peak, window=window,
              setup_s=setup_s, peak_bytes=peak_bytes, reduced=reduced)
    log(f"window {window.seconds:.3f} s, {len(window.steps)} steps, "
        f"{sum(len(s.tokens) for s in window.served)} tokens, "
        f"{len(window.served)} requests offered, "
        f"{window.compiles} compiles inside the window")
    lateness = [s.offered - s.due for s in window.served]
    if cell.traffic["loop"] == "open" and lateness:
        lateness.sort()
        log(f"generator lateness: median {1e3 * lateness[len(lateness) // 2]:.3f}"
            f" ms, max {1e3 * lateness[-1]:.3f} ms over {len(lateness)}")
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    compared = check.compare(params, cell, window, seed)
    dev["memory_peak_bytes"] = peak_bytes
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
    out = {"correct": compared["correct"],
           "attempted": len(window.served), "failed": 0,
           "metrics": metrics, "device": dev}
    if reduced is not None and reduced.ops:
        out["breakdown"] = reduced.breakdown()
    out["check"] = compared["numbers"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("run.py: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    import jax
    device_record(True, cell.chips)
    from repro.launch.compile_cache import configure_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache = configure_compile_cache()
    print(f"compile cache: {cache}", flush=True)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START,
                   log=lambda s: print(s, flush=True))
    for name, v in out["check"].items():
        print(f"check {name}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
