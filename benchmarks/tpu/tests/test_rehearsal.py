"""The harness end to end on the CPU at a tiny size: the window on the
serving main path, the metric readers and the check against the plain
reference, by calling run_cell past the entry's refusal of a CPU."""
import json
import os
import time

import pytest

import run as R
import tiny

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                       "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    c = tiny.cell(name)
    out = R.run_cell(c, 2 ** 31 + 11, 2.0, False, t_start=time.perf_counter(),
                     require_chip=False, log=lambda s: None)
    assert out["correct"], out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "check"
    want = {m["name"] for m in c.end_to_end} - {"peak_hbm_gib"}
    assert want <= set(out["metrics"]), out["metrics"]
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_traced_open_loop_run_reports_layers():
    """An open-loop run of the tiny model, traced, with the readers an
    open-loop chat cell would add (PERF.md, open questions)."""
    c = tiny.cell("granite.b1_decode")
    c.traffic.update(loop="open", rate_rps=4.0)
    c.traffic["engine"]["max_batch"] = 8
    c.per_layer += [{"name": n, "unit": "ms"} for n in
                    ("queue_wait_p90_ms", "prefill_iter_ms")] + [
                   {"name": "step_mfu.chat", "unit": "%"}]
    out = R.run_cell(c, 5, 2.0, True, t_start=time.perf_counter(),
                     require_chip=False, log=lambda s: None)
    assert out["correct"]
    # the CPU has no device plane: device-trace metrics are left out
    assert "device_idle_share" not in out["metrics"]
    got = set(out["metrics"])
    assert {"queue_wait_p90_ms", "host_syncs_per_iter", "decode_iter_ms",
            "prefill_iter_ms", "step_mfu.chat"} <= got
    assert out["device"]["window_s"] > 0


def test_entry_refuses_a_cpu(capsys):
    with pytest.raises(SystemExit):
        R.main(["--workload", "granite.b1_decode", "--seed", "1",
                "--seconds", "1", "--trace", "0"])
    assert capsys.readouterr().out == ""
