"""Drive the serving main path for one measured window.

``Scheduler.step`` -> ``Engine.step`` on the fused mega-step path: the
chunked prefill program, the decode segments between MoE boundaries,
the paged state pool and the default ``capacity`` spec with the Pallas
expert kernel.  The harness only offers requests, calls ``step`` and
reads clocks and counters; it changes nothing on the served path.

Stamps are taken on one host clock (``time.perf_counter``): when each
request was due, offered and admitted, when each of its tokens came
out, and the start and end of every step, together with what the step
advanced (read from the engine's request states before and after it).
Behind every served token the engine's own logits are kept at a seeded
sample of vocabulary ids, for the check against the reference.
"""
from __future__ import annotations

import contextlib
import gc
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from loadgen import Request
from work import Rows


@dataclass
class Served:
    """One request as the window saw it."""
    req: Request
    due: float                       # absolute clock
    offered: float
    admitted: Optional[float] = None
    tokens: List[int] = field(default_factory=list)
    stamps: List[float] = field(default_factory=list)
    finished: Optional[float] = None
    logits: List[np.ndarray] = field(default_factory=list)  # per token


@dataclass
class Step:
    t0: float
    t1: float
    iteration: int                   # engine iteration this step ran
    prefill: List[Rows]
    decode: List[Rows]


@dataclass
class Window:
    start: float
    end: float
    served: List[Served]
    steps: List[Step]
    host_syncs: int
    iterations: int
    trace_records: List[dict]        # Engine.trace records of the window
    compiles: int                    # XLA compilations inside the window

    @property
    def seconds(self) -> float:
        return self.end - self.start


def engine_config(cfg, traffic: dict, seed: int):
    """The ServeConfig of a cell: its batch, chunk and page size, and a
    context that holds the longest prompt plus the longest output."""
    from repro.serving import ServeConfig
    eng = traffic["engine"]
    page = int(eng.get("page_size", 16))
    ctx = int(traffic["prompt"]["max"]) + int(traffic["output"]["max"])
    return ServeConfig(max_batch=int(eng["max_batch"]),
                       max_ctx=-(-(ctx + 1) // page) * page,
                       chunk_tokens=int(eng["chunk_tokens"]),
                       page_size=page, seed=int(seed) & 0xFFFFFFFF)


def warm_up(params, cfg, scfg) -> None:
    """Compile every program the window runs, at the window's shapes:
    the prefill chunk (two chunks, so a prompt completes in a chunk),
    every decode segment, and the first token's head projection.  The
    shapes depend on the engine geometry alone, not on lengths."""
    from repro.serving import Engine
    warm = Engine(params, cfg, scfg)
    warm.submit_chunked([1] * (scfg.chunk_tokens + 1), max_new=3)
    warm.run()
    del warm
    gc.collect()


def logit_keeping_engine(params, cfg, scfg, keep: np.ndarray,
                         force: Optional[Dict[str, List[int]]] = None):
    """An ``Engine`` that keeps, behind every token it samples (the
    first token from the prefill program, each decode token from the
    fetched logits batch), that logits row at the vocabulary ids
    ``keep``, in ``kept[engine rid]``.  It samples from the same
    float32 row the engine's own sampler reads, with no extra fetch.

    ``force`` (engine rid -> tokens) teacher-forces: the engine's own
    choice is kept in ``chose[engine rid]`` and the forced token is
    emitted in its place."""
    from repro.serving import Engine

    class Keeping(Engine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.kept: Dict[str, List[np.ndarray]] = {}
            self.chose: Dict[str, List[int]] = {}

        def _sample_row(self, r, logits):
            row = np.asarray(logits, np.float32)
            self.kept.setdefault(r.rid, []).append(row[keep])
            tok = super()._sample_row(r, row)
            if force is None:
                return tok
            self.chose.setdefault(r.rid, []).append(tok)
            return int(force[r.rid][len(r.generated)])

    return Keeping(params, cfg, scfg)


class CompileCounter:
    """Counts XLA backend compilations while installed."""

    def __init__(self):
        from jax._src import monitoring
        self.n = 0
        self.on = False

        def listen(event, duration, **_):
            if self.on and event.endswith("backend_compile_duration"):
                self.n += 1
        monitoring.register_event_duration_secs_listener(listen)


def _snapshot(engine) -> Dict[str, tuple]:
    return {rid: (r.prefill_pos, len(r.generated))
            for rid, r in engine.requests.items() if not r.done}


def _advanced(engine, before: Dict[str, tuple], known: set):
    """(prefill rows, decode rows) the last engine step advanced, from
    each request's cached prompt tokens and generated tokens before and
    after it.  A prompt that completes emits its first token from the
    prefill program; the same step's decode pass then advances it too."""
    prefill, decode = [], []
    new = [rid for rid in engine.requests if rid not in known]
    for rid in list(before) + new:
        known.add(rid)
        r = engine.requests[rid]
        p0, g0 = before.get(rid, (0, 0))
        p1, g1 = r.prefill_pos, len(r.generated)
        first = int(g0 == 0 and g1 > 0)
        if p1 > p0:
            prefill.append(Rows(ctx=p0, tokens=p1 - p0, emit=bool(first)))
        if g1 - g0 - first > 0:
            decode.append(Rows(ctx=r.prompt_len + g0 + first - 1,
                               tokens=g1 - g0 - first, emit=True))
    return prefill, decode


def run_window(params, cfg, scfg, traffic: dict, stream: List[Request],
               seconds: float, keep: np.ndarray, *, annotate: bool = False,
               counter: Optional[CompileCounter] = None) -> Window:
    """Serve ``stream`` for ``seconds`` of wall clock and return what
    happened.  Open loop: each request is offered once it is due.
    Closed loop: each client offers its next request as soon as its
    previous one finished.  Requests still in flight when the window
    closes are left there; what they served counts.  ``keep``: the
    vocabulary ids at which each token's logits are kept."""
    import jax
    from repro.serving import Scheduler, SchedulerConfig

    clock = time.perf_counter
    span = (jax.profiler.TraceAnnotation if annotate
            else lambda name: contextlib.nullcontext())
    eng = logit_keeping_engine(params, cfg, scfg, keep)
    served: Dict[str, Served] = {}

    def on_token(rid, tok):
        s = served[rid]
        s.tokens.append(int(tok))
        s.stamps.append(clock())

    sched = Scheduler(eng, SchedulerConfig(queue_capacity=max(1, len(stream))),
                      on_token=on_token)
    open_loop = traffic["loop"] == "open"
    clients: Dict[int, List[Request]] = {}
    for r in stream:
        clients.setdefault(r.client, []).append(r)
    next_of = {c: 0 for c in clients}
    in_flight: Dict[int, str] = {}
    waiting: List[str] = []          # offered, not yet admitted
    running: List[str] = []          # offered, not yet finished
    known: set = set()
    steps: List[Step] = []

    def offer(req: Request, due: float) -> str:
        now = clock()
        rid = sched.offer(req.prompt, req.max_new)
        if rid is None:
            raise RuntimeError("the admission queue refused a request")
        served[rid] = Served(req=req, due=due, offered=now)
        waiting.append(rid)
        running.append(rid)
        return rid

    syncs0, iters0 = eng.stats["host_syncs"], eng.stats["iterations"]
    trace0 = len(eng.trace)
    if counter is not None:
        counter.on = True
    start = clock()
    end_at = start + seconds
    i_open = 0
    while True:
        now = clock()
        if now >= end_at:
            break
        with span("bench.offer"):
            if open_loop:
                while i_open < len(stream) \
                        and start + stream[i_open].due <= now:
                    offer(stream[i_open], start + stream[i_open].due)
                    i_open += 1
            else:
                for c, reqs in clients.items():
                    rid = in_flight.get(c)
                    if rid is None or served[rid].finished is not None:
                        req = reqs[next_of[c] % len(reqs)]
                        next_of[c] += 1
                        in_flight[c] = offer(req, now)
        if not sched.pending():
            nxt = (start + stream[i_open].due if i_open < len(stream)
                   else end_at)
            with span("bench.idle"):
                time.sleep(max(0.0, min(nxt, end_at) - clock()))
            continue
        before = _snapshot(eng)
        t0 = clock()
        with span("bench.step"):
            sched.step()
        t1 = clock()
        prefill, decode = _advanced(eng, before, known)
        steps.append(Step(t0=t0, t1=t1, iteration=eng.iterations,
                          prefill=prefill, decode=decode))
        for rid in waiting:
            if sched.tickets[rid].engine_rid is not None:
                served[rid].admitted = t0
        waiting = [rid for rid in waiting if served[rid].admitted is None]
        for rid in running:
            if sched.tickets[rid].done:
                served[rid].finished = served[rid].stamps[-1]
        running = [rid for rid in running if served[rid].finished is None]
    end = max(clock(), steps[-1].t1 if steps else start)
    if counter is not None:
        counter.on = False
    for rid, s in served.items():
        s.logits = eng.kept.get(sched.tickets[rid].engine_rid, [])
        if len(s.logits) != len(s.tokens):
            raise RuntimeError(f"request {rid}: {len(s.tokens)} tokens "
                               f"but {len(s.logits)} logits rows kept")
    return Window(start=start, end=end, served=list(served.values()),
                  steps=steps,
                  host_syncs=eng.stats["host_syncs"] - syncs0,
                  iterations=eng.stats["iterations"] - iters0,
                  trace_records=eng.trace[trace0:],
                  compiles=counter.n if counter is not None else 0)
