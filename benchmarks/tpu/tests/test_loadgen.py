"""Traffic generator: seeded determinism, the same work for every seed,
lognormal and uniform lengths, open-loop due times, closed-loop
clients."""
import json
import os

import numpy as np
import pytest

import loadgen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG_SEED = 2 ** 31 + 977


# An open-loop chat mix (no cell runs one yet; PERF.md, open questions).
CHAT = {"loop": "open", "rate_rps": 0.35,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                   "min": 32, "max": 1536},
        "output": {"dist": "lognormal", "median": 48, "sigma": 0.8,
                   "min": 16, "max": 256},
        "zipf_s": 1.1,
        "engine": {"max_batch": 8, "chunk_tokens": 64, "page_size": 16}}


def mix(name):
    if name == "chat":
        return dict(CHAT)
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["b1_decode", "decode_c8", "chat"])
def test_same_seed_same_stream(name):
    a = loadgen.make_stream(mix(name), BIG_SEED, 20.0, 49155)
    b = loadgen.make_stream(mix(name), BIG_SEED, 20.0, 49155)
    assert [(r.due, r.prompt, r.max_new) for r in a] == \
        [(r.due, r.prompt, r.max_new) for r in b]
    c = loadgen.make_stream(mix(name), BIG_SEED + 1, 20.0, 49155)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", ["b1_decode", "decode_c8", "chat"])
def test_every_seed_gets_the_same_sizes(name):
    """The same lengths, due times and clients, in the same order, for
    every seed: a window sees the same work whatever the seed."""
    work = [[(len(r.prompt), r.max_new, r.due, r.client)
             for r in loadgen.make_stream(mix(name), s, 20.0, 49155)]
            for s in (1, 2, BIG_SEED)]
    assert work[0] == work[1] == work[2]


def test_lognormal_quantiles():
    d = {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32,
         "max": 1536}
    q = loadgen.quantiles(d, 1001)
    assert q.min() >= 32 and q.max() <= 1536
    assert np.median(q) == 256
    # heavy right tail: the mean sits above the median
    assert q.mean() > 300
    u = loadgen.quantiles({"dist": "uniform", "min": 128, "max": 512}, 385)
    assert u.min() == 128 and u.max() == 512 and len(set(u)) == 385


def test_open_loop_due_times():
    t = mix("chat")
    reqs = loadgen.make_stream(t, BIG_SEED, 50.0, 49155)
    assert len(reqs) == round(t["rate_rps"] * 50.0)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 50.0
    gaps = np.diff([0.0] + dues)
    # exponential gaps: coefficient of variation near one
    assert 0.8 < gaps.std() / gaps.mean() < 1.2
    assert all(r.client == -1 for r in reqs)


def test_closed_loop_clients():
    t = mix("decode_c8")
    reqs = loadgen.make_stream(t, BIG_SEED, 20.0, 102400)
    assert sorted({r.client for r in reqs}) == list(range(8))
    assert len(reqs) == 8 * t["pool"]
    assert all(r.due == 0.0 for r in reqs)
    assert all(0 <= tok < 102400 for r in reqs for tok in r.prompt)


def test_zipf_affinity_is_skewed():
    rng = loadgen.rng_for(5, 9)
    toks = loadgen.zipf_tokens(rng, 49155, 4000, 1.1)
    _, counts = np.unique(toks, return_counts=True)
    top = np.sort(counts)[::-1]
    assert top[0] > 20 * np.median(counts)
