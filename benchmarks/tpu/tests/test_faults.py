"""The check fails a run whose timed path is broken underneath: a token
altered where it is produced, a step that leaves the KV state
unchanged, half of the batch left out of the expert layer.  (One chip:
there is no exchange between chips to leave out.)"""
import time

import jax.numpy as jnp
import pytest

import run as R
import tiny


def _run():
    return R.run_cell(tiny.cell("deepseek.decode_c8"), 2 ** 31 + 3, 2.0,
                      False, t_start=time.perf_counter(),
                      require_chip=False, log=lambda s: None)


@pytest.fixture
def fresh(monkeypatch):
    """Segments traced anew for the patched model functions."""
    from repro.serving import megastep
    monkeypatch.setattr(megastep, "_CACHE", {})
    return monkeypatch


def test_sound_run_is_correct(fresh):
    assert _run()["correct"]


def test_token_altered(fresh):
    from repro.serving import Engine
    orig = Engine._sample_row
    fresh.setattr(Engine, "_sample_row", lambda self, r, logits:
                  (orig(self, r, logits) + 1) % self.cfg.vocab_size)
    out = _run()
    assert not out["correct"], out["check"]


def test_state_unchanged(fresh):
    from repro.models import transformer
    orig = transformer.decode_mixer

    def mixer(params, x, caches, *a, **kw):
        x, _ = orig(params, x, caches, *a, **kw)
        return x, caches
    fresh.setattr(transformer, "decode_mixer", mixer)
    out = _run()
    assert not out["correct"], out["check"]


def test_half_batch_left_out(fresh):
    from repro.models import transformer
    orig = transformer.decode_moe_exec

    def moe_exec(params, x, h, routing, cfg, layer, mask, **kw):
        B = x.shape[0]
        keep = jnp.arange(B) < B // 2
        return orig(params, x, h, routing, cfg, layer,
                    jnp.asarray(mask) & keep, **kw)
    fresh.setattr(transformer, "decode_moe_exec", moe_exec)
    out = _run()
    assert not out["correct"], out["check"]
