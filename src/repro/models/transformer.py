"""Decoder-only LM assembly (dense / MoE / SSM / hybrid / VLM-prefix).

Layers are grouped into the smallest repeating *period* of identical
structure (1 for homogeneous stacks; 8 for Jamba's 1:7 attn:ssm
interleave with MoE every 2nd layer) and scanned over periods with
slot-wise stacked parameters.  This keeps the lowered HLO size
O(period) instead of O(num_layers) — essential for the 96-layer
nemotron-4-340b dry-run — while supporting heterogeneous layer plans.

Caches (KV / latent / SSM state) are carried through the same scan as
per-period xs/ys so decode works for every family.

Leading dense layers (``ModelConfig.first_dense``) break the periodicity,
so such a stack's period is the whole depth: every layer is a slot of
its own, stacked over one period.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import attention as attn_mod
from . import mamba2 as ssm_mod
from . import mla as mla_mod
from . import moe as moe_mod
from .layers import embed_init, norm_init, apply_norm
from .mlp import ffn_init, ffn


# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------

def period_plan(cfg: ModelConfig):
    """Smallest p dividing num_layers with kinds[i] == kinds[i mod p]."""
    kinds = list(zip(cfg.layer_kinds(), cfg.ffn_kinds()))
    L = cfg.num_layers
    for p in range(1, L + 1):
        if L % p == 0 and all(kinds[i] == kinds[i % p] for i in range(L)):
            return p, kinds[:p]
    return L, kinds


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _slot_init(key, cfg: ModelConfig, mixer: str, ffn_kind: str):
    ks = jax.random.split(key, 4)
    slot: dict = {"norm1": norm_init(cfg.norm, cfg.d_model)}
    if mixer == "attn":
        slot["attn"] = attn_mod.attn_init(
            ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, jnp.dtype(cfg.dtype))
    elif mixer == "mla":
        slot["mla"] = mla_mod.mla_init(ks[0], cfg.d_model, cfg.num_heads,
                                       cfg.mla, jnp.dtype(cfg.dtype))
    else:
        slot["ssm"] = ssm_mod.mamba2_init(ks[0], cfg.d_model, cfg.ssm, jnp.dtype(cfg.dtype))
    if ffn_kind != "none":
        slot["norm2"] = norm_init(cfg.norm, cfg.d_model)
        if ffn_kind == "moe":
            slot["moe"] = moe_mod.moe_init(ks[1], cfg.d_model, cfg.moe,
                                           cfg.activation, jnp.dtype(cfg.dtype))
        else:
            slot["ffn"] = ffn_init(ks[1], cfg.d_model, cfg.d_ff,
                                   cfg.activation, jnp.dtype(cfg.dtype))
    return slot


def init_lm(key, cfg: ModelConfig):
    p, plan = period_plan(cfg)
    n_periods = cfg.num_layers // p
    ks = jax.random.split(key, n_periods * p + 3)
    dtype = jnp.dtype(cfg.dtype)
    periods = []
    for s, (mixer, ffn_kind) in enumerate(plan):
        per = [_slot_init(ks[c * p + s], cfg, mixer, ffn_kind) for c in range(n_periods)]
        periods.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
    params = {
        "embed": embed_init(ks[-1], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.norm, cfg.d_model),
        "periods": tuple(periods),
    }
    if not cfg.tie_embeddings:
        from .layers import dense_init
        params["lm_head"] = dense_init(ks[-2], cfg.d_model, cfg.vocab_size, dtype)
    return params


# ---------------------------------------------------------------------------
# slot application
# ---------------------------------------------------------------------------

def _coerce_spec(spec):
    """Accept None / strategy name / dict / ExecutionSpec (see
    ``repro.core.strategy``); None keeps the config default."""
    if spec is None:
        return None
    from repro.core.strategy import ExecutionSpec
    return ExecutionSpec.coerce(spec)


def _needs_unroll(spec) -> bool:
    """Per-layer strategy overrides need a different lowering per
    period, so the scan-over-periods must unroll into a Python loop."""
    return spec is not None and bool(spec.layer_overrides)


def _mla_kw(cfg: ModelConfig) -> dict:
    return dict(n_heads=cfg.num_heads, mla=cfg.mla, rope_theta=cfg.rope_theta)


def _apply_slot_full(slot, x, cfg: ModelConfig, mixer, ffn_kind, *,
                     positions=None, spec=None, phase="train", layer=None,
                     use_flash=False):
    """Full-sequence forward for one layer slot. Returns (x, aux)."""
    h = apply_norm(cfg.norm, slot["norm1"], x)
    if mixer == "mla":
        h = mla_mod.mla_attention(slot["mla"], h, positions=positions,
                                  **_mla_kw(cfg))
    elif mixer == "attn":
        h = attn_mod.attention(slot["attn"], h, n_heads=cfg.num_heads,
                               n_kv=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
                               rope_theta=cfg.rope_theta, positions=positions,
                               use_flash=use_flash)
    else:
        h = ssm_mod.mamba2_block(slot["ssm"], h, cfg.ssm, cfg.d_model)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if ffn_kind != "none":
        h = apply_norm(cfg.norm, slot["norm2"], x)
        if ffn_kind == "moe":
            h, aux = moe_mod.moe_block(slot["moe"], h, cfg.moe, cfg.activation,
                                       spec=spec, phase=phase, layer=layer,
                                       return_aux=True)
        else:
            h = ffn(slot["ffn"], h, cfg.activation)
        x = x + h
    return x, aux


class SlotCache(NamedTuple):
    """Per-slot decode cache — exactly one of kv / ssm is meaningful
    (``kv`` holds a KVCache, or a LatentCache for ``mla`` slots)."""
    kv: Any
    ssm: Any


def _apply_slot_decode(slot, x, cache: SlotCache, cache_len, cfg: ModelConfig,
                       mixer, ffn_kind, *, spec=None, layer=None):
    h = apply_norm(cfg.norm, slot["norm1"], x)
    if mixer == "mla":
        h, new_kv = mla_mod.mla_append(slot["mla"], h, cache.kv, cache_len,
                                       **_mla_kw(cfg))
        new_cache = SlotCache(new_kv, cache.ssm)
    elif mixer == "attn":
        h, new_kv = attn_mod.attention_decode(
            slot["attn"], h, cache.kv, cache_len, n_heads=cfg.num_heads,
            n_kv=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta)
        new_cache = SlotCache(new_kv, cache.ssm)
    else:
        h, new_state = ssm_mod.mamba2_decode(slot["ssm"], h, cache.ssm, cfg.ssm, cfg.d_model)
        new_cache = SlotCache(cache.kv, new_state)
    x = x + h
    if ffn_kind != "none":
        h = apply_norm(cfg.norm, slot["norm2"], x)
        if ffn_kind == "moe":
            h = moe_mod.moe_block(slot["moe"], h, cfg.moe, cfg.activation,
                                  spec=spec, phase="decode", layer=layer)
        else:
            h = ffn(slot["ffn"], h, cfg.activation)
        x = x + h
    return x, new_cache


# ---------------------------------------------------------------------------
# forward (train / scoring)
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg, prefix_embeds=None):
    x = params["embed"][tokens]                      # (B,S,d) gather
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return x


def _unembed(params, x, cfg):
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return x @ head


def forward(params, tokens, cfg: ModelConfig, *, prefix_embeds=None,
            spec=None, use_flash=False, remat=False, unshard=False,
            return_hidden=False):
    """tokens: (B,S) -> (logits (B,S_total,V), aux_loss scalar).

    ``spec``: MoE execution spec (strategy name / dict / ExecutionSpec).
    Per-layer strategy overrides unroll the period scan (each layer may
    lower differently); otherwise layers scan as before.
    ``unshard``: apply the per-layer ZeRO-3 gather constraint inside the
    scan body (FSDP layouts).  ``return_hidden``: skip the unembedding
    (the fused-CE loss path consumes hidden states chunk-wise).
    """
    p, plan = period_plan(cfg)
    sp = _coerce_spec(spec)
    x = _embed(params, tokens, cfg, prefix_embeds)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    # SP residual stream pays off for attention-only stacks; an SSM layer's
    # sequential inter-chunk recurrence would regather the full sequence
    # every layer, so hybrid/ssm families keep the batch-sharded stream
    use_sp = not any(m == "ssm" for m, _ in plan)

    def period_body(carry, period_params, layer_base=None):
        x, aux = carry
        from repro.parallel.sharding import constrain_seq_sharded, unshard_slot_params
        if use_sp:
            x = constrain_seq_sharded(x)
        if unshard:
            period_params = tuple(unshard_slot_params(s) for s in period_params)
        for s, (mixer, ffn_kind) in enumerate(plan):
            layer = None if layer_base is None else layer_base + s
            x, a = _apply_slot_full(period_params[s], x, cfg, mixer, ffn_kind,
                                    positions=positions, spec=sp,
                                    phase="train", layer=layer,
                                    use_flash=use_flash)
            aux = aux + a
        if use_sp:
            x = constrain_seq_sharded(x)   # pin the saved carry to SP layout
        return (x, aux), None

    carry = (x, jnp.zeros((), jnp.float32))
    if _needs_unroll(sp):
        body = period_body
        if remat:
            body = jax.checkpoint(period_body, prevent_cse=False,
                                  static_argnums=(2,))
        for c in range(cfg.num_layers // p):
            pp = jax.tree.map(lambda a: a[c], params["periods"])
            carry, _ = body(carry, pp, c * p)
        x, aux = carry
    else:
        body = period_body
        if remat:
            body = jax.checkpoint(period_body, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(body, carry, params["periods"])
    x = apply_norm(cfg.norm, params["final_norm"], x)
    if return_hidden:
        return x, aux
    return _unembed(params, x, cfg), aux


# ---------------------------------------------------------------------------
# prefill + decode
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_seq: int):
    """Stacked per-period SlotCache tuple matching the scan layout."""
    p, plan = period_plan(cfg)
    n_periods = cfg.num_layers // p
    dtype = jnp.dtype(cfg.dtype)
    caches = []
    for mixer, _ in plan:
        if mixer in ("attn", "mla"):
            kv = (mla_mod.init_latent_cache(batch, max_seq, cfg.mla, dtype)
                  if mixer == "mla" else
                  attn_mod.init_kv_cache(batch, max_seq, cfg.num_kv_heads,
                                         cfg.resolved_head_dim, dtype))
            kv = jax.tree.map(lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape), kv)
            caches.append(SlotCache(kv, ()))
        else:
            st = ssm_mod.init_ssm_state(batch, cfg.d_model, cfg.ssm, dtype)
            st = jax.tree.map(lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape), st)
            caches.append(SlotCache((), st))
    return tuple(caches)


def init_paged_caches(cfg: ModelConfig, batch: int, num_pages: int,
                      page_size: int):
    """Stacked per-period caches with attention KV in pool pages.

    Attention slots hold (n_periods, num_pages, page_size, n_kv, hd)
    physical pages (``mla`` slots: latent pages, (n_periods, num_pages,
    page_size, kv_lora_rank) and (..., qk_rope_head_dim), the
    ``cache_width`` values per token) shared by every serving slot
    through one page table
    (``repro.serving.statepool``); SSM slots keep dense per-row state —
    it is O(1) per slot, so the pool snapshots it by value instead of
    paging it.  Page allocation is in lockstep across layers, so a
    single (B, NP) table indexes every layer's pages."""
    p, plan = period_plan(cfg)
    n_periods = cfg.num_layers // p
    dtype = jnp.dtype(cfg.dtype)
    caches = []
    for mixer, _ in plan:
        if mixer in ("attn", "mla"):
            kv = (mla_mod.init_latent_cache(num_pages, page_size, cfg.mla,
                                            dtype)
                  if mixer == "mla" else
                  attn_mod.init_paged_kv_cache(num_pages, page_size,
                                               cfg.num_kv_heads,
                                               cfg.resolved_head_dim, dtype))
            kv = jax.tree.map(lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape), kv)
            caches.append(SlotCache(kv, ()))
        else:
            st = ssm_mod.init_ssm_state(batch, cfg.d_model, cfg.ssm, dtype)
            st = jax.tree.map(lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape), st)
            caches.append(SlotCache((), st))
    return tuple(caches)


def prefill(params, tokens, cfg: ModelConfig, max_seq: int, *,
            prefix_embeds=None, spec=None):
    """Run the prompt, returning (logits, caches filled up to S)."""
    p, plan = period_plan(cfg)
    sp = _coerce_spec(spec)
    x = _embed(params, tokens, cfg, prefix_embeds)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S)[None, :]

    use_sp = not any(m == "ssm" for m, _ in plan)

    def period_body(x, period_in, layer_base=None):
        from repro.parallel.sharding import constrain_seq_sharded
        if use_sp:
            x = constrain_seq_sharded(x)
        period_params = period_in
        new_caches = []
        for s, (mixer, ffn_kind) in enumerate(plan):
            h = apply_norm(cfg.norm, period_params[s]["norm1"], x)
            if mixer == "mla":
                h, kv = mla_mod.mla_append(
                    period_params[s]["mla"], h,
                    mla_mod.init_latent_cache(B, max_seq, cfg.mla, x.dtype),
                    jnp.zeros((B,), jnp.int32), **_mla_kw(cfg))
                new_caches.append(SlotCache(kv, ()))
            elif mixer == "attn":
                kv = attn_mod.prefill_kv(period_params[s]["attn"], h,
                                         n_kv=cfg.num_kv_heads,
                                         head_dim=cfg.resolved_head_dim,
                                         rope_theta=cfg.rope_theta, positions=positions)
                # pad cache to max_seq
                pad = max_seq - S
                kv = attn_mod.KVCache(
                    jnp.pad(kv.k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                    jnp.pad(kv.v, ((0, 0), (0, pad), (0, 0), (0, 0))))
                h = attn_mod.attention(period_params[s]["attn"], h,
                                       n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads,
                                       head_dim=cfg.resolved_head_dim,
                                       rope_theta=cfg.rope_theta, positions=positions)
                new_caches.append(SlotCache(kv, ()))
            else:
                h, st = ssm_mod.mamba2_prefill(period_params[s]["ssm"], h, cfg.ssm, cfg.d_model)
                new_caches.append(SlotCache((), st))
            x = x + h
            if ffn_kind != "none":
                h = apply_norm(cfg.norm, period_params[s]["norm2"], x)
                if ffn_kind == "moe":
                    layer = None if layer_base is None else layer_base + s
                    h = moe_mod.moe_block(period_params[s]["moe"], h, cfg.moe,
                                          cfg.activation, spec=sp,
                                          phase="prefill", layer=layer)
                else:
                    h = ffn(period_params[s]["ffn"], h, cfg.activation)
                x = x + h
        return x, tuple(new_caches)

    if _needs_unroll(sp):
        per_period = []
        for c in range(cfg.num_layers // p):
            pp = jax.tree.map(lambda a: a[c], params["periods"])
            x, ncs = period_body(x, pp, c * p)
            per_period.append(ncs)
        caches = jax.tree.map(lambda *xs: jnp.stack(xs), *per_period)
    else:
        x, caches = jax.lax.scan(period_body, x, params["periods"])
    x = apply_norm(cfg.norm, params["final_norm"], x)
    return _unembed(params, x, cfg), caches


def prefill_chunk(params, tokens, caches, cache_len, cfg: ModelConfig, *,
                  spec=None, token_mask=None, return_hidden=False,
                  page_table=None):
    """Append a K-token prompt chunk to existing decode caches.

    The chunked-prefill entry point for continuous-batching serving:
    instead of one monolithic ``prefill`` per prompt, K tokens at a time
    are appended to the per-slot caches, so long prompts never block an
    engine iteration.

    tokens: (B,K) int32; caches: stacked per-period SlotCache tuple from
    ``init_caches``; cache_len: (B,) tokens already cached per row;
    token_mask: (B,K) valid chunk prefix per row (all-False rows pass
    through with their cache bit-untouched — decode-phase and idle slots
    piggyback in the same batch).

    Returns (logits (B,K,V), new_caches, counts); with
    ``return_hidden=True`` the first element is the final-normed hidden
    state (B,K,d) instead — the serving engine reads one position per
    prompt-completing row, so it skips the full (B,K,V) unembed and
    projects just the rows it samples.  ``counts`` is an
    (n_periods, p, E) int32 array of per-layer expert-activation counts
    over the valid tokens (zero rows for non-MoE slots; counts for layer
    L live at ``counts[L // p, L % p]``) — the serving engine's workload
    trace and the chiplet simulator share this feed.  Counts are only
    collected single-process (distributed strategies route their local
    rows inside shard_map).
    """
    p, plan = period_plan(cfg)
    sp = _coerce_spec(spec)
    x = _embed(params, tokens, cfg)
    B, K = tokens.shape
    if token_mask is None:
        token_mask = jnp.ones((B, K), bool)
    E = cfg.moe.num_experts if cfg.moe else 1

    def period_body(x, period_in, layer_base=None):
        from repro.core import gating
        from repro.parallel import meshctx
        period_params, period_caches = period_in
        new_caches = []
        counts = []
        for s, (mixer, ffn_kind) in enumerate(plan):
            with jax.named_scope(mixer):
                h = apply_norm(cfg.norm, period_params[s]["norm1"], x)
                if mixer == "mla":
                    append = (mla_mod.mla_append if page_table is None else
                              partial(mla_mod.mla_append_paged,
                                      page_table=page_table))
                    h, kv = append(period_params[s]["mla"], h,
                                   period_caches[s].kv, cache_len=cache_len,
                                   token_mask=token_mask, scope="mla_prefill",
                                   **_mla_kw(cfg))
                    new_caches.append(SlotCache(kv, period_caches[s].ssm))
                elif mixer == "attn":
                    if page_table is not None:
                        h, kv = attn_mod.attention_append_paged(
                            period_params[s]["attn"], h, period_caches[s].kv,
                            page_table, cache_len, n_heads=cfg.num_heads,
                            n_kv=cfg.num_kv_heads,
                            head_dim=cfg.resolved_head_dim,
                            rope_theta=cfg.rope_theta, token_mask=token_mask)
                    else:
                        h, kv = attn_mod.attention_append(
                            period_params[s]["attn"], h, period_caches[s].kv,
                            cache_len, n_heads=cfg.num_heads,
                            n_kv=cfg.num_kv_heads,
                            head_dim=cfg.resolved_head_dim,
                            rope_theta=cfg.rope_theta, token_mask=token_mask)
                    new_caches.append(SlotCache(kv, period_caches[s].ssm))
                else:
                    h, st = ssm_mod.mamba2_chunk(
                        period_params[s]["ssm"], h, period_caches[s].ssm,
                        cfg.ssm, cfg.d_model, token_mask=token_mask)
                    new_caches.append(SlotCache(period_caches[s].kv, st))
            x = x + h
            cnt = jnp.zeros((E,), jnp.int32)
            if ffn_kind != "none":
                h = apply_norm(cfg.norm, period_params[s]["norm2"], x)
                if ffn_kind == "moe":
                    layer = None if layer_base is None else layer_base + s
                    routing = None
                    if meshctx.get_mesh() is None:
                        # route ONCE: the same Routing feeds the trace
                        # counts and the expert execution
                        with jax.named_scope("route"):
                            routing = gating.route_moe(
                                period_params[s]["moe"]["router"],
                                h.reshape(-1, h.shape[-1]), cfg.moe)
                            cnt = gating.expert_token_counts(
                                routing, token_mask.reshape(-1)
                            ).astype(jnp.int32)
                    h = moe_mod.moe_block(period_params[s]["moe"], h, cfg.moe,
                                          cfg.activation, spec=sp,
                                          phase="prefill", layer=layer,
                                          routing=routing)
                else:
                    with jax.named_scope("dense_ffn"):
                        h = ffn(period_params[s]["ffn"], h, cfg.activation)
                x = x + h
            counts.append(cnt)
        return x, (tuple(new_caches), jnp.stack(counts))

    if _needs_unroll(sp):
        per_period, per_counts = [], []
        for c in range(cfg.num_layers // p):
            pin = jax.tree.map(lambda a: a[c], (params["periods"], caches))
            x, (ncs, cnt) = period_body(x, pin, c * p)
            per_period.append(ncs)
            per_counts.append(cnt)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *per_period)
        counts = jnp.stack(per_counts)
    else:
        x, (new_caches, counts) = jax.lax.scan(
            period_body, x, (params["periods"], caches))
    with jax.named_scope("head"):
        x = apply_norm(cfg.norm, params["final_norm"], x)
        if return_hidden:
            return x, new_caches, counts
        return _unembed(params, x, cfg), new_caches, counts


# ---------------------------------------------------------------------------
# serving decode segments (masked per-layer sub-steps)
# ---------------------------------------------------------------------------
#
# The serving engine executes the network layer by layer so Algorithm 2
# can defer requests exactly at MoE boundaries.  These entry points are
# the single source of truth for that per-layer math: the engine's
# legacy eager loop calls them one layer at a time, and the fused
# mega-steps (repro.serving.megastep) trace the same functions into one
# compiled segment per MoE-boundary span — bit-identical by
# construction.  All row selection is by boolean (B,) masks realized as
# jnp.where merges, so an all-False mask is a bitwise no-op (matching
# the eager loop's skip).

_PLAN_CACHE: dict = {}


def cached_period_plan(cfg: ModelConfig):
    """Memoized :func:`period_plan` (configs are frozen dataclasses;
    unhashable ones fall through to the direct computation)."""
    try:
        hit = _PLAN_CACHE.get(cfg)
    except TypeError:                      # unhashable config
        return period_plan(cfg)
    if hit is None:
        hit = _PLAN_CACHE[cfg] = period_plan(cfg)
    return hit


def _layer_slot(params, layer: int, p: int):
    """Parameters of one absolute layer out of the period-stacked tree."""
    period_idx, slot = divmod(layer, p)
    return jax.tree.map(lambda a: a[period_idx], params["periods"][slot])


def decode_embed_merge(params, x, token_vec, start_mask, cfg: ModelConfig):
    """Embed the fresh tokens of rows starting a new pass; other rows
    keep their carried residual stream.  token_vec: (B,) int."""
    emb = params["embed"][jnp.asarray(token_vec)][:, None, :]
    return jnp.where(jnp.asarray(start_mask)[:, None, None], emb, x)


def decode_mixer(params, x, caches, cache_len, cfg: ModelConfig,
                 layer: int, mask, page_table=None):
    """Masked one-token mixer (attention / SSM) step for one layer.

    Only ``mask`` rows advance: their cache entry and residual stream
    update; everything else is bit-untouched.  Returns (x, caches) with
    the full stacked cache tuple rebuilt functionally.  With a
    ``page_table`` (B, NP), attention layers read/write through the
    paged state pool (the scatter applies the row mask itself — masked
    rows are dropped out of range, so the merge below is skipped).
    """
    p, plan = cached_period_plan(cfg)
    mixer, _ = plan[layer % p]
    period_idx, slot_i = divmod(layer, p)
    slot = _layer_slot(params, layer, p)
    with jax.named_scope(mixer):
        mask = jnp.asarray(mask)
        h = apply_norm(cfg.norm, slot["norm1"], x)
        if mixer in ("attn", "mla") and page_table is not None:
            pages = jax.tree.map(lambda a: a[period_idx], caches[slot_i].kv)
            if mixer == "mla":
                h, new_pages = mla_mod.mla_append_paged(
                    slot["mla"], h, pages, page_table, cache_len,
                    token_mask=mask[:, None], scope="mla_decode",
                    **_mla_kw(cfg))
            else:
                h, new_pages = attn_mod.attention_decode_paged(
                    slot["attn"], h, pages, page_table, cache_len,
                    n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads,
                    head_dim=cfg.resolved_head_dim,
                    rope_theta=cfg.rope_theta, row_mask=mask)
            new_stack = jax.tree.map(lambda st, n: st.at[period_idx].set(n),
                                     caches[slot_i].kv, new_pages)
            caches = tuple(c if i != slot_i else SlotCache(new_stack, c.ssm)
                           for i, c in enumerate(caches))
            return jnp.where(mask[:, None, None], x + h, x), caches
        cache = jax.tree.map(lambda a: a[period_idx], caches[slot_i])
        if mixer == "mla":
            h, new_kv = mla_mod.mla_append(
                slot["mla"], h, cache.kv, cache_len,
                token_mask=mask[:, None], scope="mla_decode", **_mla_kw(cfg))
            new_cache = SlotCache(new_kv, cache.ssm)
        elif mixer == "attn":
            h, new_kv = attn_mod.attention_decode(
                slot["attn"], h, cache.kv, cache_len,
                n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta)
            new_cache = SlotCache(new_kv, cache.ssm)
        else:
            h, new_state = ssm_mod.mamba2_decode(slot["ssm"], h, cache.ssm,
                                                 cfg.ssm, cfg.d_model)
            new_cache = SlotCache(cache.kv, new_state)

        # masked cache update (only active slots advance)
        def upd(old_stack, old, new):
            if not hasattr(new, "ndim") or new.ndim == 0:
                return old_stack
            m = mask.reshape((-1,) + (1,) * (new.ndim - 1))
            merged = jnp.where(m, new, old)
            return old_stack.at[period_idx].set(merged)

        caches = tuple(
            c if i != slot_i else jax.tree.map(
                lambda stack, o, n: upd(stack, o, n), caches[slot_i], cache,
                new_cache)
            for i, c in enumerate(caches))
        return jnp.where(mask[:, None, None], x + h, x), caches


def decode_route(params, x, cfg: ModelConfig, layer: int, count_mask=None):
    """Pipeline *route* stage at one MoE boundary: normed activations +
    Routing for every slot row (routed once — the same Routing feeds
    deferral, the workload trace, and the expert execution).  With a
    ``count_mask`` the per-expert token counts over those rows are
    computed in-graph too (the fused path fetches them in one transfer
    instead of a separate eager count pass)."""
    from repro.core import gating
    p, _ = cached_period_plan(cfg)
    slot = _layer_slot(params, layer, p)
    with jax.named_scope("route"):
        h = apply_norm(cfg.norm, slot["norm2"], x)
        routing = gating.route_moe(slot["moe"]["router"], h[:, 0, :],
                                   cfg.moe)
        counts = None
        if count_mask is not None:
            counts = gating.expert_token_counts(routing,
                                                jnp.asarray(count_mask))
    return h, routing, counts


def decode_moe_exec(params, x, h, routing, cfg: ModelConfig, layer: int,
                    mask, *, spec=None, schedule=None):
    """Dispatch + combine stages at one MoE boundary: execute the
    experts on the already-routed activations (along the EMA trajectory
    when ``schedule`` is dynamic) and merge the masked residual."""
    p, _ = cached_period_plan(cfg)
    slot = _layer_slot(params, layer, p)
    mask = jnp.asarray(mask)
    h = moe_mod.moe_block(slot["moe"], h, cfg.moe, cfg.activation,
                          spec=spec, phase="decode", layer=layer,
                          routing=routing, schedule=schedule)
    return jnp.where(mask[:, None, None], x + h, x)


def decode_ffn(params, x, cfg: ModelConfig, layer: int, mask):
    """Masked dense-FFN sub-step (no-op for ffn_kind == 'none')."""
    p, plan = cached_period_plan(cfg)
    _, ffn_kind = plan[layer % p]
    if ffn_kind == "none":
        return x
    slot = _layer_slot(params, layer, p)
    mask = jnp.asarray(mask)
    with jax.named_scope("dense_ffn"):
        h = apply_norm(cfg.norm, slot["norm2"], x)
        h = ffn(slot["ffn"], h, cfg.activation)
    return jnp.where(mask[:, None, None], x + h, x)


def decode_span(params, x, caches, cache_len, cfg: ModelConfig,
                lo: int, hi: int, mask, page_table=None):
    """Run the non-MoE layers ``[lo, hi)`` (mixer + dense FFN each) for
    the masked rows — the body of one mega-step segment between MoE
    boundaries (which must not contain an MoE layer)."""
    p, plan = cached_period_plan(cfg)
    for layer in range(lo, hi):
        assert plan[layer % p][1] != "moe", \
            f"layer {layer} is an MoE boundary, not span interior"
        x, caches = decode_mixer(params, x, caches, cache_len, cfg,
                                 layer, mask, page_table=page_table)
        x = decode_ffn(params, x, cfg, layer, mask)
    return x, caches


def decode_logits(params, x, cfg: ModelConfig):
    """Final norm + unembed of the carried (B,1,d) residual stream."""
    with jax.named_scope("head"):
        h = apply_norm(cfg.norm, params["final_norm"], x)
        return _unembed(params, h, cfg)


def decode_step(params, token, caches, cache_len, cfg: ModelConfig, *,
                spec=None, unshard=False):
    """token: (B,1) int32; caches from init_caches/prefill; cache_len: (B,).

    Returns (logits (B,1,V), new caches).
    """
    p, plan = period_plan(cfg)
    sp = _coerce_spec(spec)
    x = _embed(params, token, cfg)

    def period_body(x, period_in, layer_base=None):
        period_params, period_caches = period_in
        if unshard:
            from repro.parallel.sharding import unshard_slot_params
            period_params = tuple(unshard_slot_params(s) for s in period_params)
        new_caches = []
        for s, (mixer, ffn_kind) in enumerate(plan):
            layer = None if layer_base is None else layer_base + s
            x, nc = _apply_slot_decode(period_params[s], x, period_caches[s],
                                       cache_len, cfg, mixer, ffn_kind,
                                       spec=sp, layer=layer)
            new_caches.append(nc)
        return x, tuple(new_caches)

    if _needs_unroll(sp):
        per_period = []
        for c in range(cfg.num_layers // p):
            pin = jax.tree.map(lambda a: a[c], (params["periods"], caches))
            x, ncs = period_body(x, pin, c * p)
            per_period.append(ncs)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *per_period)
    else:
        x, new_caches = jax.lax.scan(period_body, x, (params["periods"], caches))
    x = apply_norm(cfg.norm, params["final_norm"], x)
    return _unembed(params, x, cfg), new_caches
