"""Request streams for a serving cell, made from a traffic file and a seed.

Extends the idea of ``repro.serving.traffic`` (Zipf token affinity per
request) with what a chip benchmark needs:

* a wall-clock **open loop**: Poisson arrivals at a fixed rate, each
  request timed from when it is *due*, not from when the loop got to it;
* a **closed loop** of N clients, each sending its next request the
  moment its previous one finished;
* **lognormal** (heavy-tailed) or uniform lengths, clipped to a range.

Every seed gets the same lengths and gaps (stratified quantiles of the
distribution, in one fixed shuffled order) with different tokens: a
window sees only the first few requests of each client, so an order
that moved with the seed would change the work a window holds, and runs
with different seeds would not be comparable.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

SEED_MASK = (1 << 64) - 1


@dataclass
class Request:
    index: int
    client: int          # closed loop: which client sends it; open: -1
    due: float           # open loop: seconds after the window opens
    prompt: List[int]
    max_new: int


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent numpy stream per (seed, purpose...)."""
    return np.random.default_rng([int(seed) & SEED_MASK, *stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws, (i + 0.5) / n quantiles, of a length
    distribution ``{"dist": "lognormal"|"uniform", ...}`` clipped to
    ``[min, max]`` and rounded to whole tokens."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        v = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif kind == "uniform":
        v = lo + u * (hi + 1 - lo) - 0.5
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def zipf_tokens(rng: np.random.Generator, vocab: int, n: int,
                s: float) -> List[int]:
    """``n`` tokens from a private Zipf(s) affinity over the vocabulary:
    rank r has weight r^-s, ranks mapped to ids by a random permutation
    (the construction of ``repro.sim.workload.sample_expert_probs``)."""
    ranks = rng.zipf(s, size=4 * n) if s > 1 else None
    if ranks is not None:
        ranks = ranks[ranks <= vocab][:n]
    if ranks is None or len(ranks) < n:
        w = 1.0 / np.arange(1, vocab + 1) ** s
        ranks = rng.choice(vocab, size=n, p=w / w.sum()) + 1
    perm_a = int(rng.integers(1, vocab))
    while math.gcd(perm_a, vocab) != 1:
        perm_a += 1
    perm_b = int(rng.integers(0, vocab))
    # an affine permutation of the ranks: a private vocabulary order
    # without materialising a vocab-sized permutation per request
    return [int((perm_a * (int(r) - 1) + perm_b) % vocab) for r in ranks]


def make_stream(traffic: dict, seed: int, seconds: float,
                vocab: int) -> List[Request]:
    """The requests of one run.

    Open loop: ``round(rate * seconds)`` requests, due at the cumulative
    sums of stratified exponential gaps scaled so the last is due before
    the window closes.  Closed loop: ``pool`` requests per client, sent
    in order; a client that exhausts its pool starts it again.
    """
    loop = traffic["loop"]
    if loop == "open":
        n = max(1, int(round(float(traffic["rate_rps"]) * seconds)))
    elif loop == "closed":
        n = int(traffic["clients"]) * int(traffic["pool"])
    else:
        raise ValueError(f"unknown loop {loop!r}")
    order = rng_for(0, 1)            # the same order for every seed
    prompts = quantiles(traffic["prompt"], n)[order.permutation(n)]
    outputs = quantiles(traffic["output"], n)[order.permutation(n)]
    dues = np.zeros(n)
    if loop == "open":
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)      # Exp(1) quantiles
        gaps = gaps[order.permutation(n)]
        dues = np.cumsum(gaps)
        dues *= seconds * n / (n + 1) / dues[-1]
    s = float(traffic.get("zipf_s", 1.1))
    out = []
    for i in range(n):
        trng = rng_for(seed, 2, i)
        client = i % int(traffic["clients"]) if loop == "closed" else -1
        out.append(Request(index=i, client=client, due=float(dues[i]),
                           prompt=zipf_tokens(trng, vocab, int(prompts[i]), s),
                           max_new=int(outputs[i])))
    return out
