"""``chip_smoke.py`` rehearsed on the CPU at the reduced granite widths:
the serving phase and its checks through the same functions the chip run
calls, the four-chip phase on fake devices, and the refusal to run off a
TPU."""
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from conftest import run_distributed_script
from repro.configs import reduced_config
from repro.models import api
from repro.serving import Engine

ROOT = os.path.join(os.path.dirname(__file__), "..")
# the serving run of the tests: 3 requests of 5-12 prompt tokens, 3 new
# tokens each, two slots, 4-token chunks and pages
SERVE = dict(n_requests=3, prompt_lens=(5, 12), max_new=3, max_batch=2,
             chunk_tokens=4, page_size=4)


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_at_reduced_width():
    cs = _chip_smoke()
    cfg = reduced_config(cs.ARCH)               # bf16, as served
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    eng, res = cs.serve_phase(params, cfg, seed=0, **SERVE)
    res.update(cs.compare_phase(params, cfg, res.pop("checked"), seed=0))
    cs.check_tolerances(res)
    assert res["tokens"] == 9
    assert res["host_syncs"] > 0
    assert len(res["logits"]) == 2
    assert all(c["tokens"] == 3 for c in res["logits"])


def _experts_rolled_in_layer0(params):
    """Layer 0's expert weights shifted by one expert (the router keeps
    its order), so every routed row runs the wrong expert there."""
    moe = dict(params["periods"][0]["moe"])
    for k in ("w_gate", "w_up", "w_down"):
        moe[k] = moe[k].at[0].set(jnp.roll(moe[k][0], 1, axis=0))
    periods = list(params["periods"])
    periods[0] = dict(periods[0], moe=moe)
    return dict(params, periods=periods)


def _page_table_off_by_one(monkeypatch):
    """In every iteration with no prefill, each slot's page table is
    shifted by one page, so decode reads (and writes) its neighbour's
    KV page; the prefill, and so every first token, stays sound."""
    ensure = Engine._ensure_pages

    def shifted(self):
        ensure(self)
        if not self.prefilling():
            self._table_dev = jnp.roll(self._table_dev, 1, axis=1)
    monkeypatch.setattr(Engine, "_ensure_pages", shifted)


@pytest.mark.parametrize("fault", ["experts_rolled_in_layer0",
                                   "page_table_off_by_one"])
def test_planted_fault_exceeds_the_logits_limit(fault, monkeypatch):
    """A real fault in what is served lands above ``LOGITS_RTOL``
    against the reference on the sound weights, so the check can fail."""
    cs = _chip_smoke()
    cfg = reduced_config(cs.ARCH)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    served = params
    if fault == "experts_rolled_in_layer0":
        served = _experts_rolled_in_layer0(params)
    else:
        _page_table_off_by_one(monkeypatch)
    _, res = cs.serve_phase(served, cfg, seed=0, **SERVE)
    res.update(cs.compare_phase(params, cfg, res.pop("checked"), seed=0))
    print(fault, [(c["rel"], c["decode_rel"]) for c in res["logits"]])
    with pytest.raises(SystemExit, match="logits rel"):
        cs.check_tolerances(res)
    worst = max(c["decode_rel"] for c in res["logits"])
    assert worst > 2 * cs.LOGITS_RTOL
    if fault == "page_table_off_by_one":
        # only the decode check can see it
        assert all(c["rel"] <= cs.LOGITS_RTOL for c in res["logits"])


def test_mesh_phase_on_four_fake_devices():
    out = run_distributed_script("chip_smoke_mesh.py")
    assert "MESH PHASE OK" in out


def test_refuses_to_run_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_NO_PALLAS", None)
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
