"""Bring-up smoke: serve full-width granite-moe-1b-a400m on one TPU chip.

    python chip_smoke.py              # one chip: serve through the engine
    python chip_smoke.py --chips 4    # four chips: FSE-DP and EP on a mesh

One chip (the default) runs the serving main path once at the model's
published widths (24 layers, d_model 1024, 32 experts top-8, d_expert 512,
vocab 49155) in bf16, with random weights from ``--seed``: eight requests
through ``Scheduler`` -> ``Engine`` on the fused mega-step path with the
default spec (``capacity`` dispatch, Pallas ``streamed_moe``), chunked
prefill and the paged state pool.  It then checks what came out:

* every request completed with its token count;
* one MoE layer served in bf16 agrees with a float32 ``jax.numpy``
  reference on the same input and routing (``LAYER_RTOL``);
* the engine's first-token logits for two prompts agree with a float32
  ``jax.numpy`` forward of the same weights (``LOGITS_RTOL``);
* the compiled MoE segment holds the Pallas kernel (``tpu_custom_call``)
  and no kernel resolved to the interpreter.

``--chips 4`` runs only the multi-chip path: one granite-width MoE layer
and the full-width ``api.prefill_fn`` with ``fse_dp`` and ``ep``, weights
sharded over the ``model`` axis of a 1x4 mesh, each compared with the
one-device ``capacity`` result on the same inputs.

The script refuses to run off a TPU, and with ``REPRO_NO_PALLAS`` set.
It is one process and starts none.  Timings it prints are for reading
only.  The last line of its output is the JSON device record.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

ARCH = "granite-moe-1b-a400m"

# Relative L2 error of bf16-served logits (first token and every decode
# token) against the float32 reference, set by a rule fixed before the
# run that measured it (PERF.md): the geometric mean of the worst sound
# reading (0.087 over 5 seeds at full width on a v5e) and the weakest
# planted fault (0.147: a page table off by one in decode, which random
# weights' near-uniform attention mutes).
LOGITS_RTOL = 0.113
# One MoE layer on identical inputs and routing: only the bf16 rounding
# of the expert hidden state and of the output (~0.3%) remains.
LAYER_RTOL = 2e-2
# sequences per strategy in the four-chip phase
MESH_BATCH = 2


def rel_err(got, want):
    """(relative L2 error, max abs error) of ``got`` against ``want``."""
    import numpy as np
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    return (float(np.linalg.norm(g - w) / np.linalg.norm(w)),
            float(np.abs(g - w).max()))


def device_record() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def require_tpu() -> dict:
    """Print the device JAX found; refuse anything but a TPU, and the
    env switch that would swap the kernels for their jnp oracles."""
    import jax
    dev = device_record()
    print(f"jax {jax.__version__} platform={dev['platform']} "
          f"kind={dev['kind']!r} count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev['platform']!r}); refusing to run on it")
    if os.environ.get("REPRO_NO_PALLAS"):
        raise SystemExit("chip_smoke: REPRO_NO_PALLAS is set; the chip "
                         "path runs the Pallas kernels, not the oracles")
    return dev


def drop_free(cfg):
    """``cfg`` with every expert able to take every routed row (what the
    engine's ``drop_free`` serving does), so strategies and the dense
    reference compute the same function."""
    import dataclasses
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.num_experts)))


def _f32(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _layer0_moe(params):
    import jax
    return jax.tree.map(lambda a: a[0], params["periods"][0]["moe"])


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# ---------------------------------------------------------------------------
# one chip: serve through the engine, check against the f32 reference
# ---------------------------------------------------------------------------

def reference_logits(params, cfg, checked):
    """For each checked request ``(prompt, tokens, _)``: the float32
    ``jax.numpy`` logits behind each served token, from one
    teacher-forced forward over the prompt and the served tokens (no
    Pallas kernel, highest matmul precision, dense MoE)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops as kops
    from repro.models import transformer
    cfg32 = cfg.replace(dtype="float32")
    p32 = _f32(params)
    out = []
    for prompt, tokens, _ in checked:
        start = len(prompt) - 1
        fwd = jax.jit(lambda p, t: transformer.forward(
            p, t, cfg32, spec="dense")[0][0, start:])
        seq = jnp.asarray(list(prompt) + list(tokens[:-1]), jnp.int32)[None]
        with kops.use_kernels(False), jax.default_matmul_precision("highest"):
            out.append(np.asarray(fwd(p32, seq)))
    return out


def layer_check(params, cfg, *, seed, tokens=64):
    """One MoE layer: the served path (bf16, ``capacity``, kernels on)
    against the float32 dense reference on the same input and the same
    routing, so only the expert FFN's arithmetic differs.  The served
    router rounds its logits to bf16 and so flips near-tied top-k
    choices against float32 for a few tokens; the whole-model logits
    check is the one that covers that."""
    import jax
    import jax.numpy as jnp
    from repro.core import gating
    from repro.kernels import ops as kops
    from repro.models import moe as moe_mod
    moe = drop_free(cfg).moe
    layer = _layer0_moe(params)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (tokens, cfg.d_model), jnp.dtype(cfg.dtype))
    routing = jax.jit(lambda p, x: gating.route(p["router"], x,
                                                top_k=moe.top_k))(layer, x)
    block = lambda spec: jax.jit(lambda p, x, r: moe_mod.moe_block(
        p, x, moe, cfg.activation, spec=spec, routing=r))
    y = block("capacity")(layer, x, routing)
    routing32 = routing._replace(weights=routing.weights.astype(jnp.float32),
                                 combine=routing.combine.astype(jnp.float32))
    with kops.use_kernels(False), jax.default_matmul_precision("highest"):
        y_ref = block("dense")(_f32(layer), x.astype(jnp.float32), routing32)
    return rel_err(y, y_ref)


def serve_phase(params, cfg, *, seed, n_requests=8, prompt_lens=(64, 256),
                max_new=32, max_batch=8, chunk_tokens=64, page_size=16,
                n_checked=2):
    """Serve ``n_requests`` through Scheduler -> Engine (after a warm-up
    request that compiles every segment) and check the results.  Returns
    the engine and a dict of what was measured; raises on any failure."""
    import jax
    import numpy as np
    from repro.serving import (Engine, Scheduler, SchedulerConfig,
                               ServeConfig)

    class CapturingEngine(Engine):
        """The engine, keeping the logits row behind every token."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.row_logits = {}

        def _sample_row(self, r, logits):
            self.row_logits.setdefault(r.rid, []).append(
                np.asarray(logits, np.float32))
            return super()._sample_row(r, logits)

    lo, hi = prompt_lens
    scfg = ServeConfig(max_batch=max_batch, max_ctx=hi + max_new + page_size,
                       chunk_tokens=chunk_tokens, page_size=page_size,
                       seed=seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).tolist()
               for n in rng.integers(lo, hi + 1, size=n_requests)]

    t0 = time.perf_counter()      # warm-up: compiles prefill + every segment
    warm = Engine(params, cfg, scfg)
    warm.submit_chunked(prompts[0][:chunk_tokens + 1], max_new=2)
    warm.run()
    warmup_s = time.perf_counter() - t0
    del warm

    eng = CapturingEngine(params, cfg, scfg)
    sched = Scheduler(eng, SchedulerConfig(queue_capacity=n_requests))
    rids = [sched.offer(pr, max_new) for pr in prompts]
    if None in rids:
        raise SystemExit("chip_smoke: the admission queue refused a request")
    step_s, decode_s = [], []
    t0 = time.perf_counter()
    while sched.pending():
        prefilling = bool(eng.prefilling()) or bool(sched.queue)
        t1 = time.perf_counter()
        sched.step()            # ends in a host fetch of the sampled logits
        step_s.append(time.perf_counter() - t1)
        if not prefilling:
            decode_s.append(step_s[-1])
    serve_s = time.perf_counter() - t0

    outs = sched.outputs()
    m = sched.metrics()
    if m.completed != n_requests or sorted(map(len, outs.values())) \
            != [max_new] * n_requests:
        raise SystemExit(f"chip_smoke: {m.completed}/{n_requests} requests "
                         f"completed, lengths {sorted(map(len, outs.values()))}")
    res = {"warmup_s": warmup_s, "serve_s": serve_s,
           "tokens": eng.stats["tokens_emitted"],
           "tok_per_s": eng.stats["tokens_emitted"] / serve_s,
           "decode_ms": (1e3 * float(np.median(decode_s))
                         if decode_s else float("nan")),
           "decode_iters": len(decode_s), "iterations": len(step_s),
           "host_syncs": eng.stats["host_syncs"]}

    res["checked"] = [
        (prompts[i], outs[rid],
         np.stack(eng.row_logits[sched.tickets[rid].engine_rid]))
        for i, rid in enumerate(rids[:n_checked])]
    res["peak_bytes"] = _peak_bytes(jax.devices()[0])
    return eng, res


def compare_phase(params, cfg, checked, *, seed):
    """The served numbers against float32 references on ``params``: one
    MoE layer (``layer_check``), and for each checked request the logits
    behind its first token (prefill) and behind its decode tokens (the
    worst of them), with how many positions agree on the argmax."""
    res = {}
    res["layer_rel"], res["layer_max_abs"] = layer_check(params, cfg,
                                                         seed=seed)
    res["logits"] = []
    for (pr, toks, got), want in zip(checked,
                                     reference_logits(params, cfg, checked)):
        errs = [rel_err(g, w) for g, w in zip(got, want)]
        k = max(range(1, len(errs)), key=lambda i: errs[i][0])
        res["logits"].append({
            "prompt_len": len(pr), "rel": errs[0][0], "max_abs": errs[0][1],
            "decode_rel": errs[k][0], "decode_max_abs": errs[k][1],
            "decode_pos": k, "tokens": len(toks),
            "argmax_agree": int((got.argmax(-1) == want.argmax(-1)).sum())})
    return res


def check_tolerances(res):
    bad = []
    if not res["layer_rel"] <= LAYER_RTOL:
        bad.append(f"MoE layer rel {res['layer_rel']:.3e} > {LAYER_RTOL}")
    for c in res["logits"]:
        for what, rel in (("first-token", c["rel"]),
                          ("decode", c["decode_rel"])):
            if not rel <= LOGITS_RTOL:
                bad.append(f"{what} logits rel {rel:.3e} > {LOGITS_RTOL} "
                           f"(prompt of {c['prompt_len']})")
    if bad:
        raise SystemExit("chip_smoke: " + "; ".join(bad))


def check_kernel_native(eng):
    """The compiled MoE segment holds the Pallas kernel, and the kernels'
    interpret default resolved to native lowering."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops as kops
    from repro.serving import megastep
    if kops.interpret_default():
        raise SystemExit("chip_smoke: kernels resolve to the interpreter")
    ms = megastep.get_megastep(eng.cfg, eng.scfg)
    B = eng.scfg.max_batch
    rows = jnp.ones((B,), bool)
    cl = jnp.asarray(eng.cache_len)
    x, caches, h, routing, _ = jax.eval_shape(
        ms.seg_first, eng.params, eng._x, eng.caches, cl, eng._table_dev,
        np.zeros((B,), np.int32), np.ones((B,), bool), rows)
    hlo = ms.seg_last.lower(eng.params, x, caches, cl, eng._table_dev, h,
                            routing, ms.identity_order, rows
                            ).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise SystemExit("chip_smoke: the compiled MoE segment holds no "
                         "tpu_custom_call (kernel not lowered natively)")


def one_chip(seed: int) -> None:
    import jax
    from repro.configs import get_config
    from repro.models import api
    cfg = get_config(ARCH)
    print(f"config {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.moe.num_experts} experts top-"
          f"{cfg.moe.top_k}, d_expert {cfg.moe.d_expert}, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}", flush=True)
    t0 = time.perf_counter()
    # eager, as serve.py and the trainer call it: one jitted program
    # holding every expert's random draw takes the compiler tens of GiB
    params = jax.block_until_ready(api.init_params(jax.random.PRNGKey(seed),
                                                   cfg))
    n = sum(a.size for a in jax.tree.leaves(params))
    print(f"params {n / 1e9:.3f} B ({sum(a.nbytes for a in jax.tree.leaves(params)) / 2**30:.2f} GiB) "
          f"initialized in {time.perf_counter() - t0:.1f} s", flush=True)
    eng, res = serve_phase(params, cfg, seed=seed)
    res.update(compare_phase(params, cfg, res.pop("checked"), seed=seed))
    print(f"set-up (compile) {res['warmup_s']:.1f} s; served "
          f"{res['tokens']} tokens in {res['serve_s']:.2f} s "
          f"({res['iterations']} iterations): {res['tok_per_s']:.1f} tok/s, "
          f"decode iteration median {res['decode_ms']:.2f} ms over "
          f"{res['decode_iters']}, host_syncs {res['host_syncs']}, "
          f"peak_bytes_in_use {res['peak_bytes']}", flush=True)
    print(f"MoE layer vs f32 reference: rel {res['layer_rel']:.3e} "
          f"max_abs {res['layer_max_abs']:.3e} (tol {LAYER_RTOL})",
          flush=True)
    for c in res["logits"]:
        print(f"served logits vs f32 reference (prompt {c['prompt_len']}): "
              f"first token rel {c['rel']:.3e} max_abs {c['max_abs']:.3e}; "
              f"worst decode token ({c['decode_pos']} of {c['tokens']}) rel "
              f"{c['decode_rel']:.3e} max_abs {c['decode_max_abs']:.3e} "
              f"(tol {LOGITS_RTOL}); argmax agrees at {c['argmax_agree']}/"
              f"{c['tokens']}", flush=True)
    check_tolerances(res)
    check_kernel_native(eng)
    print("compiled MoE segment holds tpu_custom_call; interpret=False",
          flush=True)


# ---------------------------------------------------------------------------
# four chips: FSE-DP and EP on a 1x4 mesh against one-device capacity
# ---------------------------------------------------------------------------

def _placement(params, mesh, strategy):
    """Shardings for ``params`` on ``mesh``: the repo's rules (expert
    d_expert over ``model``, FSE-DP's layout), with the expert axis over
    ``model`` instead for ``ep``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel.sharding import param_shardings, path_key
    sh = param_shardings(params, mesh, fsdp=False)
    if strategy != "ep":
        return sh

    def by_expert(path, s):
        k = path_key(path).split("/")
        if "moe" in k and k[-1] in ("w_gate", "w_up", "w_down"):
            return NamedSharding(mesh, P(None, "model"))
        return s
    return jax.tree_util.tree_map_with_path(by_expert, sh)


def mesh_phase(cfg, *, seed, devices, seq=128):
    """Returns {strategy: (layer rel, layer max_abs, logits rel, logits
    max_abs)}; raises when a strategy disagrees with ``capacity`` or its
    experts are not split over the devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.strategy import ExecutionSpec
    from repro.launch.mesh import make_mesh
    from repro.models import api, moe as moe_mod
    from repro.parallel import meshctx

    cfg = drop_free(cfg)
    n = len(devices)
    mesh = make_mesh((1, n), ("data", "model"), devices=devices)
    one = jax.sharding.SingleDeviceSharding(devices[0])
    with jax.default_device(devices[0]):
        params = api.init_params(jax.random.PRNGKey(seed), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (MESH_BATCH, seq), 0, cfg.vocab_size)
    x = jax.random.normal(jax.random.PRNGKey(seed + 2),
                          (MESH_BATCH, seq, cfg.d_model), jnp.dtype(cfg.dtype))

    def run(strategy, params, x, tokens):
        spec = ExecutionSpec(strategy=strategy)
        y = jax.jit(lambda p, x: moe_mod.moe_block(
            _layer0_moe(p), x, cfg.moe, cfg.activation, spec=spec))(params, x)
        logits = jax.jit(lambda p, t: api.prefill_fn(
            p, {"tokens": t}, cfg, seq, spec=spec)[0])(params, tokens)
        return jax.device_get((y, logits))

    want_y, want_logits = run("capacity", params, jax.device_put(x, one),
                              jax.device_put(tokens, one))
    rep = NamedSharding(mesh, P())
    placed = {s: jax.device_put(params, _placement(params, mesh, s))
              for s in ("fse_dp", "ep")}
    del params
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"device {d.id}: bytes_in_use "
              f"{stats.get('bytes_in_use', 'not reported')}", flush=True)
    E, m = cfg.moe.num_experts, cfg.moe.d_expert
    out = {}
    with meshctx.with_mesh(mesh):
        for s, p in placed.items():
            w_up = p["periods"][0]["moe"]["w_up"]          # (L, E, d, m)
            shard = {sh.data.shape for sh in w_up.addressable_shards}
            split = (w_up.shape[0], E // n, cfg.d_model, m) if s == "ep" \
                else (w_up.shape[0], E, cfg.d_model, m // n)
            if shard != {split} or len(w_up.addressable_shards) != n:
                raise SystemExit(f"chip_smoke: {s} experts are not split "
                                 f"over {n} devices: shards {shard}")
            y, logits = run(s, p, jax.device_put(x, rep),
                            jax.device_put(tokens, rep))
            out[s] = rel_err(y, want_y) + rel_err(logits, want_logits)
            print(f"{s:7s} vs one-device capacity: MoE layer rel "
                  f"{out[s][0]:.3e} max_abs {out[s][1]:.3e} (tol "
                  f"{LAYER_RTOL}); prefill logits rel {out[s][2]:.3e} "
                  f"max_abs {out[s][3]:.3e} (tol {LOGITS_RTOL}); "
                  f"w_up shard {split}", flush=True)
    bad = [s for s, r in out.items()
           if not (r[0] <= LAYER_RTOL and r[2] <= LOGITS_RTOL)]
    if bad:
        raise SystemExit(f"chip_smoke: {bad} disagree with capacity")
    return out


def four_chips(seed: int) -> None:
    import jax
    from repro.configs import get_config
    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"chip_smoke --chips 4: found {len(devices)} "
                         f"devices, need 4")
    mesh_phase(get_config(ARCH), seed=seed, devices=devices)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve through the engine on one chip; 4: "
                         "only the FSE-DP / EP mesh phase on four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = require_tpu()
    from repro.launch.compile_cache import configure_compile_cache
    print(f"compile cache: {configure_compile_cache()}", flush=True)
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
