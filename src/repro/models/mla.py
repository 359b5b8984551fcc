"""Multi-head latent attention (DeepSeek-V2/V3), without q-LoRA.

For a token ``x``:

* ``q = x W_q``, split per head into ``q_nope`` (qk_nope_head_dim) and
  ``q_pe`` (qk_rope_head_dim);
* ``[c, k_pe] = x W_kva``: the latent ``c`` (kv_lora_rank), normed by
  an RMSNorm of its own, and one rotary key ``k_pe`` shared by every
  head;
* ``[k_nope, v] = c W_kvb`` per head; RoPE on ``q_pe`` and ``k_pe``;
* scores ``(q_nope . k_nope + q_pe . k_pe) / sqrt(qk_head_dim)`` under a
  causal softmax; the heads' outputs concatenated and projected by
  ``W_o``.

The cache holds ``[c, k_pe]`` (:class:`LatentCache`, ``cache_width``
values per token per layer) and never the per-head keys and values.
Against the cache, attention runs in the *absorbed* form:
``q_lat = q_nope W_kvb_k^T`` (heads x kv_lora_rank), scores
``q_lat . c + q_pe . k_pe``, output ``(sum p c) W_kvb_v`` — exactly the
full form above, with the up-projection moved from every cached
position onto the queries.  The full-sequence path (:func:`mla_attention`)
is the up-projected form.

The paged variants mirror ``attention.py``'s: gather the slot's pages
into a dense view, run the dense math, scatter the new positions back.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig
from .layers import apply_rope, dense_init, rmsnorm, rmsnorm_init


class LatentCache(NamedTuple):
    """Dense (B, S, ·) per slot, or pool pages (P, page_size, ·)."""
    c: jnp.ndarray          # (..., S, kv_lora_rank) normed latent
    k_pe: jnp.ndarray       # (..., S, qk_rope_head_dim) RoPE'd shared key


def mla_init(key, d_model, n_heads, mla: MLAConfig, dtype):
    ks = jax.random.split(key, 4)
    r = mla.kv_lora_rank
    return {
        "wq": dense_init(ks[0], d_model, n_heads * mla.qk_head_dim, dtype),
        "wkv_a": dense_init(ks[1], d_model, mla.cache_width, dtype),
        "kv_norm": rmsnorm_init(r),
        "wkv_b": dense_init(ks[2], r, n_heads * (mla.qk_nope_head_dim
                                                 + mla.v_head_dim), dtype),
        "wo": dense_init(ks[3], n_heads * mla.v_head_dim, d_model, dtype),
    }


def _project(params, x, positions, n_heads, mla: MLAConfig, theta):
    """x (B,S,d) -> q_nope (B,S,H,dn), q_pe (B,S,H,dr) and the cache
    entries c (B,S,r), k_pe (B,S,dr), RoPE at ``positions`` (B,S)."""
    B, S, _ = x.shape
    dn, r = mla.qk_nope_head_dim, mla.kv_lora_rank
    q = (x @ params["wq"]).reshape(B, S, n_heads, mla.qk_head_dim)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    kva = x @ params["wkv_a"]
    c = rmsnorm(params["kv_norm"], kva[..., :r])
    k_pe = kva[..., None, r:]                               # (B,S,1,dr)
    q_pe = apply_rope(q_pe, positions, theta, mla.rope_interleave)
    k_pe = apply_rope(k_pe, positions, theta, mla.rope_interleave)[:, :, 0]
    return q_nope, q_pe, c, k_pe


def _kvb(params, n_heads, mla: MLAConfig):
    """W_kvb split per head: (r, H, dn) for keys, (r, H, dv) for values."""
    w = params["wkv_b"].reshape(mla.kv_lora_rank, n_heads,
                                mla.qk_nope_head_dim + mla.v_head_dim)
    return w[..., :mla.qk_nope_head_dim], w[..., mla.qk_nope_head_dim:]


def mla_attention(params, x, *, n_heads, mla: MLAConfig, rope_theta,
                  positions=None):
    """Causal self-attention over the full sequence, up-projected form.
    x: (B,S,d)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    positions = jnp.broadcast_to(positions, (B, S))
    q_nope, q_pe, c, k_pe = _project(params, x, positions, n_heads, mla,
                                     rope_theta)
    wk, wv = _kvb(params, n_heads, mla)
    k_nope = jnp.einsum("bsr,rhn->bshn", c, wk)
    v = jnp.einsum("bsr,rhv->bshv", c, wv)
    s = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe,
                      preferred_element_type=jnp.float32))
    s = s * jnp.float32(mla.qk_head_dim) ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal[None, None], s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhv->bqhv", p, v)
    return o.reshape(B, S, n_heads * mla.v_head_dim) @ params["wo"]


def _absorbed(params, q_nope, q_pe, cache: LatentCache, valid, n_heads,
              mla: MLAConfig, dtype):
    """Attention of queries (B,K,H,·) over a dense latent cache
    (B,S,·), absorbed form; ``valid`` (B,K,S) marks visible keys.
    Returns the heads' outputs (B,K,H*dv), before ``W_o``."""
    B, K = q_nope.shape[:2]
    wk, wv = _kvb(params, n_heads, mla)
    q_lat = jnp.einsum("bkhn,rhn->bkhr", q_nope, wk)
    s = (jnp.einsum("bkhr,bsr->bhks", q_lat, cache.c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bkhd,bsd->bhks", q_pe, cache.k_pe,
                      preferred_element_type=jnp.float32))
    s = s * jnp.float32(mla.qk_head_dim) ** -0.5
    s = jnp.where(valid[:, None], s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    o_lat = jnp.einsum("bhks,bsr->bkhr", p, cache.c)
    o = jnp.einsum("bkhr,rhv->bkhv", o_lat, wv)
    return o.reshape(B, K, n_heads * mla.v_head_dim)


def init_latent_cache(batch, seq, mla: MLAConfig, dtype):
    return LatentCache(jnp.zeros((batch, seq, mla.kv_lora_rank), dtype),
                       jnp.zeros((batch, seq, mla.qk_rope_head_dim), dtype))


def _write_attend(params, q_nope, q_pe, c_new, kpe_new, cache: LatentCache,
                  pos, token_mask, n_heads, mla, dtype):
    """Write the chunk's cache entries at ``pos`` (B,K) (masked positions
    drop) and attend over the dense cache; (heads' outputs, cache)."""
    B = pos.shape[0]
    S = cache.c.shape[1]
    idx = jnp.where(token_mask, pos, S)                     # masked -> drop
    rows = jnp.arange(B)[:, None]
    cache = LatentCache(
        cache.c.at[rows, idx].set(c_new.astype(cache.c.dtype), mode="drop"),
        cache.k_pe.at[rows, idx].set(kpe_new.astype(cache.k_pe.dtype),
                                     mode="drop"))
    valid = (jnp.arange(S)[None, None, :]
             <= jnp.minimum(pos, S - 1)[:, :, None])        # (B,K,S)
    return _absorbed(params, q_nope, q_pe, cache, valid, n_heads, mla,
                     dtype), cache


def mla_append(params, x, cache: LatentCache, cache_len, *, n_heads,
               mla: MLAConfig, rope_theta, token_mask=None,
               scope: str = "mla_attend"):
    """Append a K-token chunk (K = 1 for decode) to a dense latent cache
    and attend over it.  x: (B,K,d); cache c/k_pe: (B,S,·); cache_len:
    (B,) tokens already cached.  Masked positions (``token_mask`` (B,K)
    False) neither write the cache nor become visible to a valid query.
    The latent attention (cache write, absorbed attention, up to
    ``W_kvb_v``) runs under ``jax.named_scope(scope)``; the projections
    in and out do not.  Returns (out (B,K,d), new cache)."""
    B, K, _ = x.shape
    if token_mask is None:
        token_mask = jnp.ones((B, K), bool)
    pos = cache_len[:, None] + jnp.arange(K)[None, :]       # (B,K)
    q_nope, q_pe, c_new, kpe_new = _project(params, x, pos, n_heads, mla,
                                            rope_theta)
    with jax.named_scope(scope):
        o, cache = _write_attend(params, q_nope, q_pe, c_new, kpe_new, cache,
                                 pos, token_mask, n_heads, mla, x.dtype)
    return o @ params["wo"], cache


# ---------------------------------------------------------------------------
# Paged latent cache (serving state pool)
# ---------------------------------------------------------------------------


def gather_latent_pages(pages: LatentCache, page_table) -> LatentCache:
    """Dense per-slot view (B, NP*page_size, ·) of the paged pool."""
    B, NP = page_table.shape
    ps = pages.c.shape[1]
    return LatentCache(*(a[page_table].reshape(B, NP * ps, a.shape[-1])
                         for a in pages))


def mla_append_paged(params, x, pages: LatentCache, page_table, cache_len,
                     *, n_heads, mla: MLAConfig, rope_theta,
                     token_mask=None, scope: str = "mla_attend"):
    """:func:`mla_append` against the paged pool: the latent attention
    (under ``scope``) gathers the slot's pages into a dense view, and
    each valid position ``pos = cache_len + i`` is scattered into
    ``(page_table[b, pos // ps], pos % ps)``; masked positions drop."""
    B, K, _ = x.shape
    ps = pages.c.shape[1]
    S = page_table.shape[1] * ps
    if token_mask is None:
        token_mask = jnp.ones((B, K), bool)
    pos = cache_len[:, None] + jnp.arange(K)[None, :]       # (B,K)
    q_nope, q_pe, c_new, kpe_new = _project(params, x, pos, n_heads, mla,
                                            rope_theta)
    with jax.named_scope(scope):
        o, nd = _write_attend(params, q_nope, q_pe, c_new, kpe_new,
                              gather_latent_pages(pages, page_table), pos,
                              token_mask, n_heads, mla, x.dtype)
        rows = jnp.arange(B)[:, None]
        safe = jnp.minimum(pos, S - 1)
        phys = page_table[rows, safe // ps]                 # (B,K)
        off = jnp.where(token_mask, pos % ps, ps)           # masked -> drop
        new = LatentCache(*(p.at[phys, off].set(d[rows, safe].astype(p.dtype),
                                                mode="drop")
                            for p, d in zip(pages, nd)))
    return o @ params["wo"], new
