"""The DeepSeek-V3 block (kanana-2-30b-a3b, reduced): latent attention
against plain numpy, the absorbed form against the up-projected one,
paged latent prefill + decode against the full forward, interleaved
RoPE, the sigmoid router with its selection bias, the leading dense
layer, and the engine's fused path with preemption and prefix caching
on latent pages."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.configs.base import MoEConfig
from repro.core import gating
from repro.models import api, transformer
from repro.models import mla as mla_mod
from repro.models.layers import apply_rope
from repro.serving import Engine, ServeConfig, megastep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPTS = ((5, 9, 3, 7, 2, 8, 4, 6, 11, 1), (12, 4, 4, 9))

# Tolerances, float32 program against itself or a float64 numpy model:
# the paths differ only in summation order and in where the latent is
# up-projected, so they agree to ~1e-6 relative; 1e-4 leaves room for
# that and is ~30x below what a bfloat16 latent cache (3 significant
# digits, ~4e-3 relative per value) reads (test_latent_cache_precision).
RTOL = 1e-4


@pytest.fixture(scope="module")
def kanana():
    """Reduced kanana in float32, with a nonzero random selection bias
    in every MoE layer (the chip's weights draw it as zeros)."""
    cfg = reduced_config("kanana-2-30b-a3b").replace(dtype="float32")
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    periods = list(params["periods"])
    for s, (_, ffn) in enumerate(transformer.period_plan(cfg)[1]):
        if ffn == "moe":
            r = periods[s]["moe"]["router"]
            r["e_score_correction_bias"] = 0.3 * jax.random.normal(
                jax.random.PRNGKey(10 + s), r["e_score_correction_bias"].shape)
    params["periods"] = tuple(periods)
    return cfg, params


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _slot(params, cfg, layer):
    p, _ = transformer.period_plan(cfg)
    return jax.tree.map(lambda a: a[layer // p],
                        params["periods"][layer % p])


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------


def _numpy_mla(a, x, cfg, interleave=True):
    """Plain float64 latent attention, full form, on x (S, d)."""
    m, H = cfg.mla, cfg.num_heads
    a = jax.tree.map(lambda v: np.asarray(v, np.float64), a)
    S = x.shape[0]
    dn, dr, dv, r = (m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
                     m.kv_lora_rank)
    q = (x @ a["wq"]).reshape(S, H, dn + dr)
    kva = x @ a["wkv_a"]
    c = kva[:, :r]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + 1e-6) \
        * a["kv_norm"]["scale"]
    kv = (c @ a["wkv_b"]).reshape(S, H, dn + dv)
    freq = cfg.rope_theta ** (-np.arange(0, dr, 2) / dr)
    ang = np.arange(S)[:, None] * freq                       # (S, dr/2)

    def rope(v):                                             # (S, ..., dr)
        cs = np.cos(ang).reshape((S,) + (1,) * (v.ndim - 2) + (-1,))
        sn = np.sin(ang).reshape(cs.shape)
        if interleave:
            e, o = v[..., 0::2], v[..., 1::2]
            out = np.empty_like(v)
            out[..., 0::2], out[..., 1::2] = e * cs - o * sn, o * cs + e * sn
            return out
        e, o = v[..., :dr // 2], v[..., dr // 2:]
        return np.concatenate([e * cs - o * sn, o * cs + e * sn], -1)

    k = np.concatenate([kv[..., :dn], np.broadcast_to(
        rope(kva[:, None, r:]), (S, H, dr))], -1)
    qq = np.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
    s = np.einsum("qhd,khd->hqk", qq, k) / np.sqrt(dn + dr)
    s = np.where(np.tril(np.ones((S, S), bool))[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("hqk,khd->qhd", p, kv[..., dn:])
    return o.reshape(S, H * dv) @ a["wo"]


def test_mla_full_sequence_matches_numpy(kanana):
    cfg, params = kanana
    a = _slot(params, cfg, 1)["mla"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.d_model))
    got = mla_mod.mla_attention(a, x, n_heads=cfg.num_heads, mla=cfg.mla,
                                rope_theta=cfg.rope_theta)
    for b in range(2):
        want = _numpy_mla(a, np.asarray(x[b], np.float64), cfg)
        assert _rel(got[b], want) < RTOL


@pytest.mark.parametrize("chunks", [(12,), (5, 4, 3), (1,) * 12],
                         ids=["one_chunk", "chunks", "token_by_token"])
def test_absorbed_form_equals_up_projected(kanana, chunks):
    """Appending to the latent cache and attending in the absorbed form,
    in one chunk, in several, or one token at a time (decode), gives the
    full up-projected form's output at every position."""
    cfg, params = kanana
    a = _slot(params, cfg, 2)["mla"]
    B, S = 2, sum(chunks)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model))
    full = mla_mod.mla_attention(a, x, n_heads=cfg.num_heads, mla=cfg.mla,
                                 rope_theta=cfg.rope_theta)
    cache = mla_mod.init_latent_cache(B, 16, cfg.mla, jnp.float32)
    outs, lo = [], 0
    for k in chunks:
        o, cache = mla_mod.mla_append(
            a, x[:, lo:lo + k], cache, jnp.full((B,), lo, jnp.int32),
            n_heads=cfg.num_heads, mla=cfg.mla, rope_theta=cfg.rope_theta)
        outs.append(o)
        lo += k
    assert _rel(jnp.concatenate(outs, 1), full) < RTOL
    # the cache holds the normed latent and the RoPE'd shared key
    assert cache.c.shape == (B, 16, cfg.mla.kv_lora_rank)
    assert cache.k_pe.shape == (B, 16, cfg.mla.qk_rope_head_dim)


def test_rope_interleave_is_what_the_config_names(kanana):
    """Interleaved pairs and rotate-half differ on the same input; the
    program follows ``rope_interleave`` (the numpy model with the other
    pairing does not match it)."""
    cfg, params = kanana
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 6, 2, 8))
    pos = jnp.arange(6)[None]
    inter = apply_rope(x, pos, 1e4, interleave=True)
    half = apply_rope(x, pos, 1e4, interleave=False)
    assert _rel(inter, half) > 0.1
    # a rotation: norms of each pair are kept
    np.testing.assert_allclose(jnp.linalg.norm(inter, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    a = _slot(params, cfg, 1)["mla"]
    xs = jax.random.normal(jax.random.PRNGKey(4), (10, cfg.d_model))
    for interleave in (True, False):
        c = cfg.replace(mla=dataclasses.replace(cfg.mla,
                                                rope_interleave=interleave))
        got = mla_mod.mla_attention(a, xs[None], n_heads=c.num_heads,
                                    mla=c.mla, rope_theta=c.rope_theta)[0]
        x64 = np.asarray(xs, np.float64)
        assert _rel(got, _numpy_mla(a, x64, c, interleave)) < RTOL
        assert _rel(got, _numpy_mla(a, x64, c, not interleave)) > 1e-2


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------


def test_sigmoid_router_bias_moves_choice_not_weights(kanana):
    cfg, params = kanana
    moe = cfg.moe
    router = _slot(params, cfg, 1)["moe"]["router"]
    x = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model))
    r = gating.route_moe(router, x, moe)
    unbiased = dict(router, e_score_correction_bias=jnp.zeros_like(
        router["e_score_correction_bias"]))
    r0 = gating.route_moe(unbiased, x, moe)
    s = jax.nn.sigmoid(x @ router["w_router"])
    # the bias changes which experts are chosen ...
    assert (np.sort(r.indices, 1) != np.sort(r0.indices, 1)).any()
    # ... by the top-k of s + b ...
    want = jax.lax.top_k(s + router["e_score_correction_bias"], moe.top_k)[1]
    np.testing.assert_array_equal(r.indices, want)
    # ... while the weights are s at the chosen experts, renormalised and
    # scaled: they sum to routed_scaling
    w = jnp.take_along_axis(s, r.indices, 1)
    np.testing.assert_allclose(r.weights, w / w.sum(1, keepdims=True)
                               * moe.routed_scaling, rtol=1e-6)
    np.testing.assert_allclose(r.weights.sum(1), moe.routed_scaling,
                               rtol=1e-6)
    np.testing.assert_allclose(r.combine.sum(1), moe.routed_scaling,
                               rtol=1e-6)


def test_softmax_route_unchanged():
    """A softmax MoEConfig routes through the unchanged ``route``, bit for
    bit, and that equals the softmax-renormalise rule written out."""
    moe = MoEConfig(num_experts=8, top_k=2, d_expert=16)
    params = gating.router_init(jax.random.PRNGKey(6), 32, 8, jnp.float32)
    assert set(params) == {"w_router"}
    x = jax.random.normal(jax.random.PRNGKey(7), (16, 32))
    a = gating.route_moe(params, x, moe)
    b = gating.route(params, x, top_k=2)
    for u, v in zip(a, b):
        np.testing.assert_array_equal(u, v)
    probs = jax.nn.softmax((x @ params["w_router"]).astype(jnp.float32), -1)
    w, i = jax.lax.top_k(probs, 2)
    np.testing.assert_array_equal(a.indices, i)
    np.testing.assert_array_equal(
        a.weights, w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9))


# ---------------------------------------------------------------------------
# leading dense layer
# ---------------------------------------------------------------------------


def test_leading_dense_layer_has_no_moe_boundary(kanana):
    cfg, params = kanana
    assert cfg.ffn_kinds() == ("dense", "moe", "moe")
    assert cfg.layer_kinds() == ("mla",) * 3
    p, plan = transformer.period_plan(cfg)
    assert p == cfg.num_layers                 # the dense layer breaks periods
    assert "ffn" in params["periods"][0] and "moe" not in params["periods"][0]
    assert params["periods"][0]["ffn"]["w_up"].shape[-1] == cfg.d_ff
    ms = megastep.MegaStep(cfg, ServeConfig().spec, max_batch=2, max_ctx=32,
                           chunk_tokens=4)
    assert ms.boundaries == [1, 2]
    assert ms.mid_names == ["decode_seg_mid_2"]
    eng = Engine(params, cfg, ServeConfig(max_batch=2, max_ctx=32,
                                          chunk_tokens=4, page_size=4))
    eng.submit_chunked(list(PROMPTS[0]), max_new=3)
    eng.run()
    layers = {r["layer"] for r in eng.trace if "layer" in r}
    assert layers == {1, 2}
    assert all(len(r["counts"]) == cfg.moe.num_experts
               for r in eng.trace if "layer" in r)


# ---------------------------------------------------------------------------
# the engine on latent pages
# ---------------------------------------------------------------------------


def _keeping_engine(params, cfg, scfg, force=None):
    """An engine that keeps the logits row behind every token it emits;
    with ``force`` (rid -> tokens) it emits those tokens instead."""

    class Keeping(Engine):
        kept: dict = {}

        def _sample_row(self, r, logits):
            row = np.asarray(logits, np.float32)
            self.kept.setdefault(r.rid, []).append(row)
            tok = super()._sample_row(r, row)
            if force is None:
                return tok
            return int(force[r.rid][len(r.generated)])

    eng = Keeping(params, cfg, scfg)
    eng.kept = {}
    return eng


def _served_against_forward(kanana, scfg, admit="submit_chunked"):
    cfg, params = kanana
    eng = _keeping_engine(params, cfg, scfg)
    rids = [getattr(eng, admit)(list(p), max_new=6) for p in PROMPTS]
    outs = eng.run()
    worst = 0.0
    for rid, prompt in zip(rids, PROMPTS):
        seq = list(prompt) + outs[rid][:-1]
        full, _ = transformer.forward(params, jnp.asarray([seq]), cfg)
        kept = np.stack(eng.kept[rid])
        # one-shot admission samples its first token outside _sample_row
        want = full[0, len(prompt) - 1 + len(outs[rid]) - len(kept):]
        worst = max(worst, _rel(kept, want))
    return eng, outs, worst


SCFG = dict(max_batch=2, max_ctx=32, chunk_tokens=4, page_size=4)


def test_paged_prefill_then_decode_matches_forward(kanana):
    """Chunked prefill through the paged latent cache, then decode on the
    fused path's sync-free pass: the logits behind every served token
    equal the full forward's over the served sequence."""
    eng, _, worst = _served_against_forward(kanana, ServeConfig(**SCFG))
    assert worst < RTOL, worst
    assert eng.stats["sync_free_passes"] == eng.stats["iterations"]
    # one read per pass, plus one per prompt's first token
    assert eng.stats["host_syncs"] == eng.stats["iterations"] + len(PROMPTS)


def test_one_shot_prefill_into_latent_pages(kanana):
    """One-shot admission: the whole prompt through a dense latent cache
    (``transformer.prefill``), scattered into the slot's latent pages,
    then decode: the same logits as the full forward."""
    _, _, worst = _served_against_forward(kanana, ServeConfig(**SCFG),
                                          admit="submit")
    assert worst < RTOL, worst


def test_dense_cache_decode_step_matches_forward(kanana):
    """``api.prefill_fn`` then ``api.decode_fn`` on dense latent caches
    (the launcher's serve step) give the full forward's logits."""
    cfg, params = kanana
    seq = list(PROMPTS[0])
    full, _ = transformer.forward(params, jnp.asarray([seq]), cfg)
    logits, caches = api.prefill_fn(params, {"tokens": jnp.asarray([seq[:6]])},
                                    cfg, 16)
    got = [logits[0, -1]]
    for t in range(6, len(seq) - 1):
        out, caches = api.decode_fn(params, jnp.asarray([[seq[t]]]), caches,
                                    jnp.asarray([t]), cfg)
        got.append(out[0, 0])
    assert _rel(jnp.stack(got), full[0, 5:len(seq) - 1]) < RTOL


def test_latent_cache_precision_is_seen(kanana, monkeypatch):
    """The tolerance above is tight enough to see a bfloat16 latent cache
    under a float32 model."""
    init = mla_mod.init_latent_cache
    monkeypatch.setattr(
        mla_mod, "init_latent_cache",
        lambda n, ps, m, dtype: init(n, ps, m, jnp.bfloat16))
    monkeypatch.setattr(megastep, "_CACHE", {})
    _, _, worst = _served_against_forward(kanana, ServeConfig(**SCFG))
    assert worst > 3 * RTOL, worst


def test_latent_cache_bytes_counter(kanana):
    cfg, params = kanana
    eng = Engine(params, cfg, ServeConfig(**SCFG))
    assert eng.stats["latent_cache_bytes"] == 0
    eng.submit_chunked(list(PROMPTS[0]), max_new=3)
    eng.step()
    per_page = cfg.num_layers * 4 * cfg.mla.cache_width * 4   # f32
    used = eng.pool.pages_in_use()
    assert used > 0
    assert eng.stats["latent_cache_bytes"] == used * per_page
    eng.run()
    granite = reduced_config("granite-moe-1b-a400m").replace(dtype="float32")
    g = Engine(api.init_params(jax.random.PRNGKey(0), granite), granite,
               ServeConfig(**SCFG))
    g.submit_chunked([1, 2, 3], max_new=2)
    g.run()
    assert g.stats["latent_cache_bytes"] == 0


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "legacy"])
def test_preempt_restore_and_prefix_hit_on_latent_pages(kanana, fused):
    """Preemption (pages detach and re-attach) and a prefix-cache hit
    (full latent pages shared, the partial tail page copied) give the
    tokens of an uninterrupted run."""
    cfg, params = kanana
    scfg = ServeConfig(fused=fused, **SCFG)
    ref = Engine(params, cfg, scfg)
    rids = [ref.submit_chunked(list(p), max_new=5) for p in PROMPTS]
    want = ref.run()

    eng = Engine(params, cfg, scfg)
    aids = [eng.submit_chunked(list(p), max_new=5) for p in PROMPTS]
    for _ in range(4):
        eng.step()
    victim = aids[0]
    assert eng.requests[victim].generated and not eng.requests[victim].done
    handle = eng.preempt(victim)
    eng.submit_chunked([3, 1, 2, 7, 7], max_new=2)     # dirties freed pages
    eng.run()
    assert eng.restore(handle) == victim
    got = eng.run()
    assert got[victim] == want[rids[0]]
    assert got[aids[1]] == want[rids[1]]

    hot = Engine(params, cfg, dataclasses.replace(scfg, prefix_cache=True))
    outs = []
    for _ in range(2):
        rid = hot.submit_chunked(list(PROMPTS[0]), max_new=5)
        outs.append(hot.run()[rid])
    assert outs == [want[rids[0]]] * 2
    assert hot.stats["cache_hits"] == 1
    assert hot.stats["prefill_tokens_saved"] == 8


# ---------------------------------------------------------------------------
# the benchmark's configuration file
# ---------------------------------------------------------------------------


def test_config_file_matches_registry():
    """The configuration file's ``model`` keys that ``run.build_config``
    does not apply (latent attention, dense layer, router) equal the
    registry entry's, and its published keys describe the same model."""
    path = os.path.join(ROOT, "benchmarks/tpu/configs/kanana-2-30b-a3b-l7.json")
    with open(path) as f:
        conf = json.load(f)
    cfg = get_config(conf["registry"])
    m = conf["model"]
    mla, moe = cfg.mla, cfg.moe
    assert {k: m[k] for k in ("kv_lora_rank", "qk_nope_head_dim",
                              "qk_rope_head_dim", "v_head_dim",
                              "rope_interleave")} \
        == {k: getattr(mla, k) for k in ("kv_lora_rank", "qk_nope_head_dim",
                                         "qk_rope_head_dim", "v_head_dim",
                                         "rope_interleave")}
    assert (m["first_dense"], m["d_ff"]) == (cfg.first_dense, cfg.d_ff)
    assert (m["scoring"], m["routed_scaling"]) \
        == (moe.scoring, moe.routed_scaling)
    assert m["norm_topk"] is True             # the sigmoid router renormalises
    assert m["rms_norm_eps"] == 1e-6          # the program's rmsnorm default
    # published keys, as the file states them
    assert conf["first_k_dense_replace"] == cfg.first_dense
    assert conf["intermediate_size"] == cfg.d_ff
    assert conf["kv_lora_rank"] == mla.kv_lora_rank
    assert conf["qk_head_dim"] == mla.qk_head_dim
    assert conf["v_head_dim"] == mla.v_head_dim == m["head_dim"]
    assert conf["routed_scaling_factor"] == moe.routed_scaling
    assert conf["scoring_func"] == moe.scoring
    assert conf["norm_topk_prob"] is True
    assert conf["rope_interleave"] == mla.rope_interleave
    assert conf["num_hidden_layers"] == m["num_layers"]
    assert conf["rope_theta"] == m["rope_theta"] == cfg.rope_theta
    assert (conf["n_group"], conf["topk_group"]) == (1, 1)
    assert conf["q_lora_rank"] is None
