"""End-to-end and layer numbers of a synthetic window."""
import pytest

import run as R
import serve_window as sw
import windowstats as ws
import work
from loadgen import Request


def req(i, plen=4):
    return Request(index=i, client=-1, due=0.0, prompt=[1] * plen,
                   max_new=8)


def window():
    a = sw.Served(req(0), due=10.0, offered=10.0, admitted=10.1,
                  tokens=[1, 2, 3], stamps=[10.5, 10.6, 10.9])
    b = sw.Served(req(1), due=11.0, offered=11.0, admitted=None)  # waits
    c = sw.Served(req(2), due=10.2, offered=10.2, admitted=10.3,
                  tokens=[4, 5], stamps=[10.7, 12.5])        # 12.5: after close
    steps = [sw.Step(10.4, 10.5, 1, [work.Rows(0, 4, True)], []),
             sw.Step(10.5, 10.6, 2, [], [work.Rows(4, 1, True)]),
             sw.Step(10.6, 10.9, 3, [work.Rows(0, 4, True)],
                     [work.Rows(5, 1, True)])]
    return sw.Window(start=10.0, end=12.0, served=[a, b, c], steps=steps,
                     host_syncs=30, iterations=3,
                     trace_records=[{"iter": 2, "layer": 0,
                                     "phase": "decode",
                                     "counts": [1, 0, 1, 0]}],
                     compiles=0)


def run_of(w):
    m = work.Model(num_layers=1, d_model=8, num_heads=2, num_kv_heads=1,
                   head_dim=4, vocab_size=16, num_experts=4, top_k=2,
                   d_expert=8)
    return R.Run(cell=None, model=m, peak=work.PEAKS["TPU v5 lite"],
                 window=w, setup_s=1.0, peak_bytes=2 ** 30)


def test_rates_and_tails():
    r = run_of(window())
    assert ws.output_tokens(r) == 4
    assert sorted(ws.inter_token_gaps(r)) == pytest.approx([0.1, 0.3])
    # b has no first token at the close: it counts its wait, 12 - 11
    assert sorted(ws.ttfts(r)) == pytest.approx([0.5, 0.5, 1.0])
    assert sorted(ws.queue_waits(r)) == pytest.approx([0.1, 0.1, 1.0])
    assert ws.mean_step_ms(r, lambda s: s.decode and not s.prefill) == \
        pytest.approx(100.0)
    assert ws.mean_step_ms(r, lambda s: bool(s.prefill)) == \
        pytest.approx(200.0)


def test_step_mfu_counts_each_call():
    r = run_of(window())
    total = 0.0
    for rows, hit in (([work.Rows(0, 4, True)], []),
                      ([work.Rows(4, 1, True)], [2]),
                      ([work.Rows(0, 4, True)], []),
                      ([work.Rows(5, 1, True)], [])):
        total += work.least_seconds(*work.step_work(r.model, rows, hit),
                                    r.peak)
    assert ws.required_step_seconds(r) == pytest.approx(total)
    assert ws.step_mfu(r) == pytest.approx(100 * total / 2.0)


def test_nothing_to_read_is_none():
    w = window()
    w.trace_records = []
    r = run_of(w)
    assert ws.step_mfu(r) is None and ws.expert_least_seconds(r) is None
