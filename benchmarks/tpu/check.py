"""Decide ``correct``: the timed path's logits and tokens against the
float32 reference.

While the window runs, the engine keeps the logits behind every token
it serves at a sample of vocabulary ids drawn from the seed
(``serve_window.logit_keeping_engine``).  After the window, a sample of
the served requests drawn from the seed (always with the one that
served most tokens in it) is run through the configuration's plain
reference once, over each prompt followed by the tokens it was served
(teacher forcing).  At each served position two numbers are read:

* the relative logit error: the L2 distance between the program's
  logits and the reference's at the kept ids, over the reference's norm
  there; its median, or its 10th percentile over the positions, is
  compared.  It sees the precision of every layer (embedding, attention
  over the paged cache, router, expert kernel, shared experts, norms,
  head).  Its widest reading is printed, not compared: it falls where a
  near-tied router choice flips in bfloat16, and reads alike with the
  lower-precision control; the 10th percentile reads the positions
  where no choice flipped.
* ``max_gap``: the widest gap by which a served token's logit lies
  below the reference's best at its position.  It sees a token that is
  altered after its logits were made, which the logits cannot show.

Each number that the cell's check file (``checks/<cell>.json``) gives a
limit is held to it; PERF.md gives the readings each limit was set
from.  ``judge`` is the one comparison for the window's outputs and for
the control's (``control.py``).
"""
from __future__ import annotations

import importlib
import sys
from typing import List, Sequence

import numpy as np

import loadgen


def reference_class(conf: dict):
    """The ``Reference`` of the configuration's plain reference module
    (``references/<name>.py``)."""
    return importlib.import_module(
        "references." + conf["reference"]).Reference


def vocab_sample(seed: int, vocab: int, n: int) -> np.ndarray:
    """The sorted vocabulary ids at which logits are kept and compared."""
    n = min(int(n), int(vocab))
    return np.sort(loadgen.rng_for(seed, 4).choice(vocab, n, replace=False))


def sample(served, seed: int, tokens: int, max_requests: int) -> List:
    """Requests to check: the one that served most tokens, then others
    in seeded order until ``tokens`` served tokens or ``max_requests``."""
    cands = [s for s in served if s.tokens]
    if not cands:
        return []
    first = max(cands, key=lambda s: (len(s.tokens), -s.req.index))
    rest = [s for s in cands if s is not first]
    order = loadgen.rng_for(seed, 3).permutation(len(rest))
    out, total = [first], len(first.tokens)
    for i in order:
        if total >= tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        total += len(rest[i].tokens)
    return out


def rows_of(picked, served_tokens) -> list:
    """(sequence, position, token) of each served token: token i of a
    request came from the logits at position len(prompt) - 1 + i."""
    rows = []
    for b, (s, toks) in enumerate(zip(picked, served_tokens)):
        base = len(s.req.prompt) - 1
        rows += [(b, base + i, t) for i, t in enumerate(toks)]
    return rows


def teacher_seqs(picked) -> list:
    return [list(s.req.prompt) + list(s.tokens[:-1]) for s in picked]


def readings(gaps, logits, ref_logits) -> dict:
    """Numbers of one comparison (the widest, mean, median and 10th
    percentile relative logit error, the widest and mean gap, and the
    share of positions whose token was not the reference's first
    choice), and the relative logit error of each position."""
    gaps = np.asarray(gaps, np.float64)
    z = np.asarray(logits, np.float64)
    zr = np.asarray(ref_logits, np.float64)
    rel = np.linalg.norm(z - zr, axis=1) / np.linalg.norm(zr, axis=1)
    return {"logit_rel_err": float(rel.max()),
            "mean_logit_rel_err": float(rel.mean()),
            "median_logit_rel_err": float(np.median(rel)),
            "p10_logit_rel_err": float(np.quantile(rel, 0.1)),
            "max_gap": float(gaps.max()), "mean_gap": float(gaps.mean()),
            "miss_share": float((gaps > 0).mean())}, rel


class Judge:
    """The reference over a sample of served requests, computed once;
    ``__call__`` reads any tokens and logits at those positions."""

    def __init__(self, params, cell, picked, seed: int):
        lim = cell.limits
        self.cell, self.picked = cell, picked
        self.keep = vocab_sample(seed, cell.config["model"]["vocab_size"],
                                 lim["vocab_sample"])
        self.ref = reference_class(cell.config)(
            params, cell.config["model"], teacher_seqs(picked),
            rows=int(lim["max_requests"]))

    def __call__(self, tokens: Sequence[Sequence[int]],
                 logits: Sequence[Sequence[np.ndarray]]) -> dict:
        """``{"correct", "numbers": {name: {"value", "limit"}},
        "readings"}`` for ``tokens`` and ``logits`` (one row per token,
        at the kept ids) served at the sample's positions."""
        gaps, _, zr = self.ref.read(rows_of(self.picked, tokens),
                                    self.keep)
        got, rel = readings(
            gaps, np.concatenate([np.stack(r) for r in logits]), zr)
        lim = self.cell.limits
        numbers = {k: {"value": v, "limit": float(lim[k]["limit"])}
                   for k, v in got.items()
                   if isinstance(lim.get(k), dict) and "limit" in lim[k]}
        return {"correct": bool(numbers) and all(
                    n["value"] <= n["limit"] for n in numbers.values()),
                "numbers": numbers, "readings": got, "tokens": len(gaps),
                "positions": {"rel": rel.tolist(), "gap": gaps.tolist(),
                              "lens": [len(t) for t in tokens]}}


def compare(params, cell, window, seed: int) -> dict:
    """The judgement of the window's own served tokens and logits."""
    lim = cell.limits
    picked = sample(window.served, seed, int(lim["tokens"]),
                    int(lim["max_requests"]))
    if not picked:
        return {"correct": False, "numbers": {
            "served_tokens": {"value": 0, "limit": 1}}}
    out = Judge(params, cell, picked, seed)(
        [s.tokens for s in picked], [s.logits for s in picked])
    print(f"check: {out['tokens']} served tokens of {len(picked)} requests "
          f"against the reference; readings {out['readings']}",
          file=sys.stderr, flush=True)
    return out
