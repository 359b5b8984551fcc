"""Host spans and the compile counter of the serving loop: what a
profiler trace of ``Scheduler`` -> ``Engine`` holds, the compile records
in ``Engine.trace``, and the stable names of the decode programs."""
import contextlib
import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import reduced_config
from repro.kernels.ops import use_kernels
from repro.models import api
from repro.serving import Engine, Scheduler, ServeConfig, megastep

PROMPTS = ((1, 2, 3, 4, 5, 6), (9, 8, 7))
SCHEDULES = ["static", "dynamic"]


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("granite-moe-1b-a400m").replace(dtype="float32")
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params, schedule, **geometry):
    geometry = {"max_batch": 2, "max_ctx": 32, "chunk_tokens": 4,
                **geometry}
    return Engine(params, cfg, ServeConfig(
        spec={"strategy": "capacity", "schedule": schedule}, **geometry))


def _serve(cfg, params, schedule, profile_dir=None):
    eng = _engine(cfg, params, schedule)
    sched = Scheduler(eng)
    for p in PROMPTS:
        sched.offer(list(p), 5)
    with (jax.profiler.trace(profile_dir) if profile_dir
          else contextlib.nullcontext()):
        sched.drain()
    return eng, sched


def _spans(trace_dir):
    """(name, start, end, args) of the program's spans in the trace."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("sched.", "engine.", "gc")):
                    args = {k: v for k, v in e.stats}
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, args))
    return out


def _inside(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2]


def _layer_records(eng):
    return [(r["iter"], r["layer"], r["phase"], tuple(r["counts"]))
            for r in eng.trace if "counts" in r]


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_spans_per_iteration(setup, schedule, tmp_path):
    cfg, params = setup
    eng, sched = _serve(cfg, params, schedule, str(tmp_path))
    spans = _spans(str(tmp_path))
    named = {n: [s for s in spans if s[0] == n]
             for n in ("sched.step", "engine.step", "engine.dispatch",
                       "engine.fetch", "engine.boundary")}
    iters = eng.stats["iterations"]
    assert iters == sched.iteration > 0
    assert len(named["sched.step"]) == len(named["engine.step"]) == iters
    assert len(named["engine.fetch"]) == eng.stats["host_syncs"]
    for name in ("engine.dispatch", "engine.fetch", "engine.boundary"):
        for s in named[name]:
            assert any(_inside(s, st) for st in named["engine.step"]), name
    for st in named["engine.step"]:
        assert any(_inside(st, s) for s in named["sched.step"])

    # per iteration: one prefill program if a chunk ran, then the
    # nb + 1 decode segments if any row decoded
    ms = megastep.get_megastep(eng.cfg, eng.scfg)
    decode = [ms.FIRST] + ms.mid_names + [ms.LAST]
    phases = {(r["iter"], r["phase"]) for r in eng.trace if "phase" in r}
    for st in named["engine.step"]:
        it = st[3]["step_num"]
        want = (([ms.PREFILL] if (it, "prefill") in phases else [])
                + (decode if (it, "decode") in phases else []))
        got = [s[3]["segment"] for s in named["engine.dispatch"]
               if _inside(s, st)]
        assert got == want, it


def test_gc_pause_is_spanned(setup, tmp_path):
    cfg, params = setup
    eng = _engine(cfg, params, "static")
    eng.submit_chunked([1, 2, 3], max_new=2)
    eng.step()                           # registers the process's hooks
    with jax.profiler.trace(str(tmp_path)):
        gc.collect()
    spans = [s for s in _spans(str(tmp_path)) if s[0] == "gc"]
    assert any(s[3]["generation"] == 2 for s in spans)


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_outputs_same_with_and_without_profiler(setup, schedule, tmp_path):
    cfg, params = setup
    e0, s0 = _serve(cfg, params, schedule)
    e1, s1 = _serve(cfg, params, schedule, str(tmp_path))
    assert s0.outputs() == s1.outputs()
    assert _layer_records(e0) == _layer_records(e1)
    assert e0.stats["host_syncs"] == e1.stats["host_syncs"]


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_new_shape_leaves_a_compile_record(setup, schedule):
    cfg, params = setup
    megastep._CACHE.clear()
    # a geometry no other test serves: every program traces anew
    eng = _engine(cfg, params, schedule, max_batch=3, max_ctx=40)
    for p in ((1, 2, 3), (4, 5)):
        eng.submit_chunked(list(p), max_new=8)
    eng.step()
    first = [r for r in eng.trace if r.get("event") == "compile"]
    assert first and first[0]["iter"] == 1
    assert first[0]["count"] >= 1 and first[0]["seconds"] > 0
    assert eng.stats["compiles"] >= 1
    assert eng.stats["compile_s"] == pytest.approx(
        sum(r["seconds"] for r in first))
    eng.step()                           # decode only from here on
    n, n_rec = eng.stats["compiles"], len(eng.trace)
    for _ in range(3):
        eng.step()
    assert eng.stats["compiles"] == n, "steady-state decode compiled"
    assert not [r for r in eng.trace[n_rec:] if "event" in r]


@pytest.mark.parametrize("segment", ["mid", "last"])
def test_decode_segment_names_scopes_and_kernel(setup, segment):
    cfg, params = setup
    with use_kernels(True):
        eng = _engine(cfg, params, "static")
        ms = megastep.get_megastep(eng.cfg, eng.scfg)
        assert ms.seg_mid, "the reduced config has one MoE boundary"
        B = eng.scfg.max_batch
        cl = jnp.asarray(eng.cache_len)
        mask = np.ones((B,), bool)
        x, caches, h, routing, _ = jax.eval_shape(
            ms.seg_first, params, eng._x, eng.caches, cl, eng._table_dev,
            np.zeros((B,), np.int32), mask, mask)
        common = (params, x, caches, cl, eng._table_dev, h, routing,
                  ms.identity_order, mask)
        if segment == "mid":
            fn, name, args = ms.seg_mid[0], ms.mid_names[0], common + (mask,)
        else:
            fn, name, args = ms.seg_last, ms.LAST, common
        lowered = fn.lower(*args)
    assert f"@jit_{name}" in lowered.as_text()
    text = lowered.as_text(debug_info=True)
    assert f"jit({name})/expert_ffn/streamed_moe/pallas_call" in text
    scopes = ("attn", "route") if segment == "mid" else ("head",)
    for scope in scopes:
        assert f"jit({name})/{scope}/" in text, scope
