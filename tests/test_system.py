"""End-to-end behaviour of the whole system (paper claims on CPU scale):
train a small MoE -> serve it with token buffering -> replay its expert
trace in the chiplet simulator and check the paper's orderings."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.data import DataConfig
from repro.models import api
from repro.serving import Engine, ServeConfig
from repro.sim import PROTOTYPE_2X2, LayerWorkload, simulate_layer, spec_from_config
from repro.training import TrainConfig, train


@pytest.mark.slow
def test_train_serve_simulate_pipeline():
    cfg = reduced_config("granite-moe-1b-a400m").replace(dtype="float32")
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8, seed=3)

    # 1) train briefly — loss must improve
    res = train(cfg, dcfg, TrainConfig(total_steps=30, warmup=5, lr=3e-3,
                                       log_every=29), seed=0)
    assert res.losses[-1][1] < res.losses[0][1]

    # 2) serve the trained model with token buffering
    eng = Engine(res.params, cfg, ServeConfig(max_batch=4, max_ctx=48,
                                              buffering_slack=0.3, theta_min=2))
    eng.policy.n_threshold = 2
    for i in range(3):
        eng.submit([1 + i, 2 + i, 3 + i], max_new=5)
    outs = eng.run()
    assert all(len(v) == 5 for v in outs.values())

    # 3) replay the engine's measured expert counts in the chiplet sim
    #    (expert dims scaled to the full granite sizes so the memory
    #    comparison is meaningful): FSE-DP must beat EP on memory
    import dataclasses
    spec = dataclasses.replace(spec_from_config(cfg), d_model=1024, d_expert=512)
    hw = PROTOTYPE_2X2
    counts_trace = [t["counts"] for t in eng.trace
                    if "counts" in t and t["counts"].sum() > 0][:4]
    assert counts_trace
    ratios = []
    for counts in counts_trace:
        per_chip = np.zeros((hw.num_chiplets, spec.num_experts), np.int64)
        for e, n in enumerate(counts):
            for j in range(int(n)):
                per_chip[j % hw.num_chiplets, e] += 1
        wl = LayerWorkload(counts=per_chip)
        r_fse = simulate_layer(hw, spec, wl, "fse_dp_paired")
        r_ep = simulate_layer(hw, spec, wl, "ep")
        # both fetch each active expert exactly once (work conservation)
        np.testing.assert_allclose(r_fse.ddr_bytes, r_ep.ddr_bytes)
        ratios.append(r_fse.peak_buffer_bytes / max(r_ep.peak_buffer_bytes, 1))
    # across the trace, FSE-DP's eager Rule-4 staging must not exceed EP's
    # whole-expert residency on average (tiny 6-activation layers are noisy,
    # hence the mean; large-workload dominance is asserted in test_sim)
    assert np.mean(ratios) <= 1.25, ratios
