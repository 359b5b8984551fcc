"""Fused jitted mega-steps for the serving engine's decode iteration.

The legacy engine walks the network layer by layer in Python, paying a
host round-trip per sub-layer — fine for exactness, hopeless for the
paper's fine-grained overlap story, where the host must not be the
bottleneck.  This module fuses everything *between* MoE boundaries into
one compiled segment, so a steady-state decode iteration is ``k + 1``
device dispatches (``k`` = number of MoE layers) with at most one host
sync per boundary, and one per iteration when no boundary needs a host
decision:

* ``seg_first``  — fresh-token embed merge, the full layers before the
  first boundary ``b0``, the mixer at ``b0``, and the *route* stage at
  ``b0`` (routing + in-graph expert counts over the rows that will
  reach the boundary);
* ``seg_mid[j]`` — expert execution at boundary ``b_{j-1}`` (on the
  previous segment's routing, along the host-fed EMA trajectory when
  the schedule is dynamic), the span of full layers up to ``b_j``, the
  mixer at ``b_j``, and the route stage at ``b_j``;
* ``seg_last``   — expert execution at the final boundary, the trailing
  full layers, final norm and logits;
* ``seg_only``   — the no-MoE degenerate case (one segment end to end).

Each segment returns its boundary's expert counts.  Where the host
decides at a boundary (Algorithm-2 deferral, or the EMA trajectory of a
dynamic schedule) it reads them between segments, one
``jax.device_get((counts, indices))`` per boundary, and does the
decision, the workload-trace record and the LoadTracker EMA update
there.  Otherwise the engine dispatches every segment back to back and
reads all the counts with the logits in one fetch (the sync-free pass,
``repro.serving.engine``); the segments are the same.  Every segment body
is built from the same ``transformer.decode_*`` entry points the legacy
eager loop calls, so fused and legacy iterations are bit-identical by
construction (asserted token-for-token and trace-for-trace in
``tests/test_megastep.py``).

Residual stream and caches are donated (``donate_argnums``) — the
engine rebinds both from each segment's outputs, so decode steps run
without per-iteration buffer growth.  Row selection is by traced
boolean masks and the dynamic trajectory enters as a traced ``(E,)``
order array, so steady-state decode (and deferral/finish churn) never
retraces: ``MegaStep.traces`` counts trace events and the test suite
pins it flat after warmup.

Each jitted program has a stable name, which names its module on the
device and the engine's ``engine.dispatch`` span: ``prefill_chunk``,
``decode_seg_first``, ``decode_seg_mid_<b>`` (ending at boundary ``b``),
``decode_seg_last`` and ``decode_seg_only``.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.core import trajectory
from repro.kernels import ops as kops
from repro.models import moe as moe_mod, transformer


class MegaStep:
    """Compiled decode segments + chunked-prefill step for one
    (model config, execution spec, engine geometry) cell.

    Instances are cached per configuration *and* per ambient kernel /
    sorted-dispatch flag (see :func:`get_megastep`): the flags are read
    at trace time inside ``ExecutionSpec.scope()``, so a segment traced
    with kernels on must never be reused with kernels off.
    """

    PREFILL = "prefill_chunk"
    FIRST = "decode_seg_first"
    LAST = "decode_seg_last"
    ONLY = "decode_seg_only"

    def __init__(self, cfg, spec, *, max_batch: int, max_ctx: int,
                 chunk_tokens: int):
        self.cfg = cfg
        self.spec = spec
        p, plan = transformer.cached_period_plan(cfg)
        L = cfg.num_layers
        self.boundaries: List[int] = [l for l in range(L)
                                      if plan[l % p][1] == "moe"]
        self.dynamic = spec is not None and spec.schedule == "dynamic"
        E = cfg.moe.num_experts if cfg.moe else 1
        # the static trajectory: canonical order (a no-op permutation);
        # dynamic segments overwrite it with the host-fed EMA order
        self.identity_order = jnp.arange(E, dtype=jnp.int32)
        # trace-event counter: each compiled-segment (re)trace bumps it
        # once (Python side effect in the traced body) — the recompile
        # guard in tests/test_megastep.py reads it
        self.traces = 0
        self.mid_names = [f"decode_seg_mid_{b}" for b in self.boundaries[1:]]
        self._build()

    # ------------------------------------------------------------------

    def _schedule(self, order):
        """The per-boundary Schedule executed inside a segment: the
        host-fed EMA trajectory as a traced order (dynamic), or None
        (static — the untouched fast path)."""
        if not self.dynamic:
            return None
        return trajectory.Schedule(policy="dynamic", order=order)

    def _build(self):
        cfg, spec = self.cfg, self.spec
        L = cfg.num_layers
        bnds = self.boundaries

        # every segment takes the paged-KV ``table`` ((B, NP) int32) as a
        # traced array right after cache_len: page allocation happens on
        # the host between iterations, so table churn never retraces

        def prefill(params, tokens, caches, cache_len, table, token_mask):
            self.traces += 1
            return transformer.prefill_chunk(
                params, tokens, caches, cache_len, cfg, spec=spec,
                token_mask=token_mask, return_hidden=True, page_table=table)

        self.prefill = _jit(prefill, self.PREFILL, donate_argnums=(2,))

        if not bnds:
            def only(params, x, caches, cache_len, table, token_vec,
                     start_mask):
                self.traces += 1
                x = transformer.decode_embed_merge(params, x, token_vec,
                                                   start_mask, cfg)
                x, caches = transformer.decode_span(params, x, caches,
                                                    cache_len, cfg, 0, L,
                                                    start_mask,
                                                    page_table=table)
                return x, caches, transformer.decode_logits(params, x, cfg)

            self.seg_only = _jit(only, self.ONLY, donate_argnums=(1, 2))
            self.seg_first = self.seg_mid = self.seg_last = None
            return

        b0 = bnds[0]

        def first(params, x, caches, cache_len, table, token_vec, start_mask,
                  count_mask):
            self.traces += 1
            x = transformer.decode_embed_merge(params, x, token_vec,
                                               start_mask, cfg)
            x, caches = transformer.decode_span(params, x, caches, cache_len,
                                                cfg, 0, b0, start_mask,
                                                page_table=table)
            x, caches = transformer.decode_mixer(params, x, caches, cache_len,
                                                 cfg, b0, start_mask,
                                                 page_table=table)
            h, routing, counts = transformer.decode_route(params, x, cfg, b0,
                                                          count_mask)
            return x, caches, h, routing, counts

        self.seg_first = _jit(first, self.FIRST, donate_argnums=(1, 2))

        def make_mid(b_prev: int, b: int, name: str):
            def mid(params, x, caches, cache_len, table, h, routing, order,
                    exec_mask, count_mask):
                self.traces += 1
                x = transformer.decode_moe_exec(
                    params, x, h, routing, cfg, b_prev, exec_mask,
                    spec=spec, schedule=self._schedule(order))
                x, caches = transformer.decode_span(
                    params, x, caches, cache_len, cfg, b_prev + 1, b,
                    exec_mask, page_table=table)
                x, caches = transformer.decode_mixer(
                    params, x, caches, cache_len, cfg, b, exec_mask,
                    page_table=table)
                h, routing, counts = transformer.decode_route(params, x, cfg,
                                                              b, count_mask)
                return x, caches, h, routing, counts
            return _jit(mid, name, donate_argnums=(1, 2))

        self.seg_mid = [make_mid(bnds[j - 1], bnds[j], self.mid_names[j - 1])
                        for j in range(1, len(bnds))]

        b_tail = bnds[-1]

        def last(params, x, caches, cache_len, table, h, routing, order,
                 exec_mask):
            self.traces += 1
            x = transformer.decode_moe_exec(
                params, x, h, routing, cfg, b_tail, exec_mask,
                spec=spec, schedule=self._schedule(order))
            x, caches = transformer.decode_span(params, x, caches, cache_len,
                                                cfg, b_tail + 1, L, exec_mask,
                                                page_table=table)
            return x, caches, transformer.decode_logits(params, x, cfg)

        self.seg_last = _jit(last, self.LAST, donate_argnums=(1, 2))
        self.seg_only = None


def _jit(fn, name: str, **kw):
    """``jax.jit`` of ``fn`` under a stable program name."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **kw)


_CACHE: dict = {}


def get_megastep(cfg, scfg) -> MegaStep:
    """The (cached) MegaStep for one engine configuration.

    Keyed on everything that changes the compiled segments: the model
    config, the execution spec, the engine geometry, and the *ambient*
    kernel / sorted-dispatch flags (contextvars read at trace time).
    Called once per engine iteration — a dict hit in the steady state.
    Unhashable configs fall back to an uncached instance.
    """
    try:
        key = (cfg, scfg.spec, scfg.max_batch, scfg.max_ctx,
               scfg.chunk_tokens, scfg.page_size, scfg.pool_pages,
               kops.kernels_enabled(),
               moe_mod.sorted_dispatch_enabled())
        hash(key)
    except TypeError:
        return MegaStep(cfg, scfg.spec, max_batch=scfg.max_batch,
                        max_ctx=scfg.max_ctx, chunk_tokens=scfg.chunk_tokens)
    ms = _CACHE.get(key)
    if ms is None:
        ms = _CACHE[key] = MegaStep(cfg, scfg.spec, max_batch=scfg.max_batch,
                                    max_ctx=scfg.max_ctx,
                                    chunk_tokens=scfg.chunk_tokens)
    return ms
