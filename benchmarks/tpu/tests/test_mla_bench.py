"""The DeepSeek-V3-block cell's benchmark parts on the CPU: the required
work of ``work_mla`` by hand, the plain reference against a per-token
loop, and the check failing a run whose timed path is broken."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import init_weights
import run as R
import tiny
import work
import work_mla
from references import mla_moe_transformer as ref_mod

CELL = "kanana.decode_c8"


def test_work_mla_by_hand():
    m = work_mla.MlaModel(
        num_layers=3, d_model=16, num_heads=2, vocab_size=50,
        num_experts=4, top_k=2, d_expert=8, num_shared_experts=1, d_ff=24,
        first_dense=1, kv_lora_rank=6, qk_nope_head_dim=4,
        qk_rope_head_dim=2, v_head_dim=3)
    wq, wkva, wkvb, wo = 16 * 2 * 6, 16 * 8, 6 * 2 * 7, 2 * 3 * 16
    attn = wq + wkva + wkvb + wo
    dense_layer = attn + 3 * 16 * 24 + 2 * 16 + 6
    moe_layer = attn + 17 * 4 + 1 * 3 * 16 * 8 + 2 * 16 + 6
    expert = 3 * 16 * 8
    assert m.active_matmul_params() == dense_layer + 2 * (moe_layer
                                                          + 2 * expert)
    rows = [work.Rows(ctx=5, tokens=1, emit=True),
            work.Rows(ctx=0, tokens=3, emit=False)]
    f, b = work_mla.step_work(m, rows, experts_hit=[3, 1])
    keys = 6 + 6                                   # 5 + 1; 1 + 2 + 3
    pair = 2 * 2 * (8 + 6)                         # scores 8, values 6
    assert f == 2 * m.active_matmul_params() * 4 + 3 * pair * keys \
        + 2 * 16 * 50
    latent = 3 * 8 * 2 * (6 + 3)                   # 8 values, bf16
    assert b == 2 * (dense_layer + 2 * moe_layer + 4 * expert + 16 * 50
                     + 16 * 4) + latent
    # latent attention alone, one call of either phase: scores and
    # values over the keys, W_kvb twice per new position; bytes of the
    # latent context and W_kvb per layer
    fa, ba = work_mla.attn_work(m, rows[:1])       # a decode step
    assert fa == 3 * (pair * 6 + 2 * wkvb)
    assert ba == 3 * (8 * 2 * 6 + 2 * wkvb)
    fa, ba = work_mla.attn_work(m, rows[1:])       # a 3-token chunk
    assert fa == 3 * (pair * 6 + 2 * wkvb * 3)
    assert ba == 3 * (8 * 2 * 3 + 2 * wkvb)
    assert work_mla.attn_work(m, []) == (0, 0)
    assert work_mla.step_work(m, [], []) == (0, 0)


def _cfg_params(seed):
    c = tiny.cell(CELL)
    cfg = R.build_config(c.config)
    params = init_weights.make_params(cfg, seed)
    periods = list(params["periods"])
    for s, slot in enumerate(periods):
        if "moe" in slot:
            r = slot["moe"]["router"]
            r["e_score_correction_bias"] = 0.3 * jax.random.normal(
                jax.random.PRNGKey(s), r["e_score_correction_bias"].shape)
    params["periods"] = tuple(periods)
    return c, cfg, params


def _loop_hidden(params, m, seq):
    """Final hidden states of one sequence, one token at a time against
    a growing cache of latents and rotary keys, in float64 numpy."""
    P = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    H, r = m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    p = len(P["periods"])
    caches = [([], []) for _ in range(m["num_layers"])]

    def rms(x, s):
        return x / np.sqrt(np.mean(x * x) + m["rms_norm_eps"]) * s

    def rope(v, t):
        f = m["rope_theta"] ** (-np.arange(0, dr, 2) / dr) * t
        e, o = v[..., 0::2], v[..., 1::2]
        out = np.empty_like(v)
        out[..., 0::2] = e * np.cos(f) - o * np.sin(f)
        out[..., 1::2] = o * np.cos(f) + e * np.sin(f)
        return out

    def swiglu(h, f):
        g = h @ f["w_gate"]
        return (g / (1 + np.exp(-g)) * (h @ f["w_up"])) @ f["w_down"]

    out = []
    for t, tok in enumerate(seq):
        x = P["embed"][tok]
        for layer in range(m["num_layers"]):
            w = jax.tree.map(lambda a: a[layer // p], P["periods"][layer % p])
            a = w["mla"]
            h = rms(x, w["norm1"]["scale"])
            q = (h @ a["wq"]).reshape(H, dn + dr)
            kva = h @ a["wkv_a"]
            cs, ks = caches[layer]
            cs.append(rms(kva[:r], a["kv_norm"]["scale"]))
            ks.append(rope(kva[r:], t))
            kv = (np.stack(cs) @ a["wkv_b"]).reshape(t + 1, H, dn + dv)
            qp = rope(q[:, dn:], t)
            s = (np.einsum("hn,khn->hk", q[:, :dn], kv[..., :dn])
                 + np.einsum("hd,kd->hk", qp, np.stack(ks))) / np.sqrt(dn + dr)
            pr = np.exp(s - s.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            o = np.einsum("hk,khv->hv", pr, kv[..., dn:]).reshape(-1)
            x = x + o @ a["wo"]
            h = rms(x, w["norm2"]["scale"])
            if "moe" in w:
                e = w["moe"]
                sc = 1 / (1 + np.exp(-(h @ e["router"]["w_router"])))
                idx = np.argsort(-(sc + e["router"]["e_score_correction_bias"]),
                                 kind="stable")[:m["top_k"]]
                g = sc[idx] / sc[idx].sum() * m["routed_scaling"]
                y = swiglu(h, e["shared"])
                for gi, ei in zip(g, idx):
                    y = y + gi * swiglu(h, {k: e[k][ei] for k in
                                            ("w_gate", "w_up", "w_down")})
            else:
                y = swiglu(h, w["ffn"])
            x = x + y
        out.append(x)
    return np.stack(out)


def test_reference_matches_per_token_loop():
    c, cfg, params = _cfg_params(7)
    m = c.config["model"]
    seqs = [[3, 17, 200, 5, 9, 44, 1, 0, 255, 8], [4, 4, 30]]
    ref = ref_mod.Reference(params, m, seqs, rows=2, pad_to=16)
    for b, s in enumerate(seqs):
        want = _loop_hidden(params, m, s)
        got = np.asarray(ref.hidden[b, :len(s)], np.float64)
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert err < 1e-4, (b, err)


def _run(trace=False):
    return R.run_cell(tiny.cell(CELL), 2 ** 31 + 3, 2.0, trace,
                      t_start=time.perf_counter(), require_chip=False,
                      log=lambda s: None)


@pytest.fixture
def fresh(monkeypatch):
    """Segments traced anew for the patched program functions."""
    from repro.serving import megastep
    monkeypatch.setattr(megastep, "_CACHE", {})
    return monkeypatch


def test_sound_traced_run(fresh):
    out = _run(trace=True)
    assert out["correct"], out["check"]
    # the CPU has no device plane: the roofline is left out, the
    # whole-step share reads from the window and the routing
    assert "mla_attn_roofline" not in out["metrics"]
    assert 0 < out["metrics"]["step_mfu.mla"]["value"] < 100


def test_token_altered(fresh):
    from repro.serving import Engine
    orig = Engine._sample_row
    fresh.setattr(Engine, "_sample_row", lambda self, r, logits:
                  (orig(self, r, logits) + 1) % self.cfg.vocab_size)
    out = _run()
    assert not out["correct"], out["check"]


def test_latent_state_unchanged(fresh):
    from repro.models import mla
    orig = mla.mla_append_paged

    def append(params, x, pages, *a, **kw):
        out, _ = orig(params, x, pages, *a, **kw)
        return out, pages
    fresh.setattr(mla, "mla_append_paged", append)
    out = _run()
    assert not out["correct"], out["check"]


def test_bias_left_out_of_the_choice():
    """The reference chooses by s + b: leaving the bias out changes what
    it computes where the bias is nonzero."""
    c, cfg, params = _cfg_params(9)
    m = c.config["model"]
    seqs = [[3, 17, 200, 5, 9, 44, 1, 0]]
    a = ref_mod.Reference(params, m, seqs, rows=1, pad_to=8).hidden
    periods = tuple(
        dict(s, moe=dict(s["moe"], router=dict(
            s["moe"]["router"], e_score_correction_bias=jnp.zeros_like(
                s["moe"]["router"]["e_score_correction_bias"]))))
        if "moe" in s else s for s in params["periods"])
    b = ref_mod.Reference(dict(params, periods=periods), m, seqs, rows=1,
                          pad_to=8).hidden
    assert float(jnp.abs(a - b).max()) > 1e-3


# Device op names of one decode segment, as a traced chip window of the
# cell records them (PERF.md section 3): the latent attention's page
# gather, scores, softmax reduction, heads' output and page scatter,
# then the expert kernel, the W_o projection (which reads the heads'
# output as an operand) and the query projection.
RECORDED = [
    ("%fusion = bf16[456,16,512]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[912,16"
     ",512]{2,1,0:T(8,128)(2,1)} %bitcast.204, s32[1024]{0:T(1024)S(1)} %pad"
     "_clamp_fusion), kind=kCustom, calls=%fused_computation", True),
    ("%fusion.43 = f32[8,912,32]{1,2,0:T(8,128)S(1)} fusion(bf16[8,912,512]{"
     "2,1,0:T(8,128)(2,1)S(1)} %fusion.6, bf16[8,32,512]", True),
    ("%bitcast_reduce_fusion = (f32[8,32]{1,0:T(8,128)S(1)}, f32[8,912,1,32]"
     "{1,3,0,2:T(8,128)S(1)}) fusion(f32[8,912", True),
    ("%fusion.22 = bf16[32,128,8]{1,2,0:T(8,128)(2,1)S(1)} fusion(bf16[512,3"
     "2,256]{0,2,1:T(8,128)(2,1)S(1)} %bitcast", True),
    ("%fusion.8 = bf16[912,16,512]{2,1,0:T(8,128)(2,1)} fusion(bf16[912,16,5"
     "12]{2,1,0:T(8,128)(2,1)} %bitcast.205, s", True),
    ("%streamed_moe.1 = f32[128,48,2048]{2,1,0:T(8,128)S(1)} custom-call(bf16"
     "[128,48,2048]{2,1,0:T(8,128)(2,1)S(1)} %fusion.79", False),
    ("%fusion.53 = (f32[8]{0:T(128)S(1)}, bf16[8,1,2048]{2,0,1:T(8,128)(2,1)"
     "S(1)}) fusion(bf16[8,1,2048]{2,0,1:T(8,128)(2,1)S(1)} %get-tuple-elem"
     "ent.138, bf16[1,4096,2048]{2,1,0:T(8,128)(2,1)S(1)} %copy-done, bf16[3"
     "2,128,8]{1,2,0:T(8,128)(2,1)S(1)} %fusion.22", False),
    ("%convolution_bitcast_fusion = bf16[8,1,32,192]{3,0,2,1:T(8,128)(2,1)S("
     "1)} fusion(bf16[32,192,2048]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.196", False),
]
# The prefill chunk's, as its program compiled for a described v5e
# names them (``compiled.as_text()``, op names under ``mla_prefill`` but
# for the projections): scores, the softmax's scores and sums, its
# normalised scores, the latent output, the heads' output; then the
# query and latent projections and a new cache entry, left out.
RECORDED += [
    ("%fusion.201 = f32[8,912,64,32]{1,2,3,0:T(8,128)} fusion(", True),
    ("%fusion.742 = (f32[8,32,64]{2,1,0:T(8,128)S(1)}, f32[8,32,64,912]{3,2,"
     "1,0:T(8,128)}) fusion(", True),
    ("%divide_convert_fusion.6 = bf16[8,32,64,912]{3,2,1,0:T(8,128)(2,1)S(1)"
     "} fusion(", True),
    ("%fusion.154 = bf16[8,64,32,512]{3,1,0,2:T(8,128)(2,1)S(1)} fusion(",
     True),
    ("%fusion.356 = bf16[32,8,8,8,128]{4,3,2,1,0:T(8,128)(2,1)S(1)} fusion(",
     True),
    ("%fusion.478 = bf16[8,64,32,192]{3,1,2,0:T(8,128)(2,1)S(1)} fusion(",
     False),
    ("%fusion.388 = bf16[8,64,576]{2,1,0:T(8,128)(2,1)S(1)} fusion(", False),
    ("%copy.508 = bf16[512,64]{1,0:T(8,128)(2,1)S(1)} copy(", False),
]


@pytest.mark.parametrize("name,want", RECORDED,
                         ids=[n.split(" ")[0] for n, _ in RECORDED])
def test_roofline_matches_recorded_latent_ops(name, want):
    """``mla_attn_roofline`` finds the latent attention's device events by
    their output shapes at the cell's geometry, and nothing else."""
    import re
    import types
    reader = R.load_reader("mla_attn_roofline").__globals__
    pat = reader["pattern"](types.SimpleNamespace(cell=R.load_cell(CELL)))
    assert bool(re.search(pat, name)) is want


def test_control_is_not_correct():
    """The fp8 control and an altered token fail the check at the tiny
    size where the sound run passes, as for the other cells."""
    import control
    c = tiny.cell(CELL)
    dtype = c.limits["control"]["dtype"]
    got = control.readings(c, (1, 2), 2.0, [dtype], require_chip=False,
                           log=lambda s: None)
    for seed, r in got.items():
        assert r["sound"]["correct"], (seed, r["sound"])
        assert not r[dtype]["correct"], (seed, r[dtype])
        assert not r["altered"]["correct"], (seed, r["altered"])
