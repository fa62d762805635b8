"""The one general generator of every traffic mix: training batches and
open-loop serving requests, pure in the run's seed.

A mix is a data file under ``traffic/`` whose ``kind`` says which of the
two it is; everything else in it is a parameter read here.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


def _ss(seed: int, *more: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed) & (2 ** 64 - 1), *more])


# ---------------------------------------------------------------------------
# Training: Zipf unigrams with repeated 8-token motifs, pure in
# (seed, step, row) -- the rule of the program's SyntheticLMDataset
# ---------------------------------------------------------------------------

def train_rows(seed: int, step: int, rows: int, seq_len: int,
               vocab: int) -> np.ndarray:
    """(rows, seq_len + 1) int32 token rows of one step; every (step, row)
    pair draws its own stream, so no two rows of a run repeat."""
    out = np.empty((rows, seq_len + 1), np.int32)
    for r in range(rows):
        rng = np.random.default_rng(_ss(seed, step, r))
        base = rng.zipf(1.3, size=seq_len + 1) % vocab
        motif = rng.integers(0, vocab, size=8)
        pos = rng.integers(0, max(1, seq_len - 8),
                           size=max(1, seq_len // 32))
        for p in pos:
            base[p:p + 8] = motif
        out[r] = base
    return out


def train_batch(seed: int, step: int, mix: dict, vocab: int) -> dict:
    rows = train_rows(seed, step, mix["global_batch"], mix["seq_len"], vocab)
    return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


# ---------------------------------------------------------------------------
# Serving: open-loop arrivals.  Every seed gets the same multiset of
# prompt lengths, output lengths and gaps between arrivals (quantiles of
# the mix's distributions), in its own order, with its own token ids.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Arrival:
    rid: int
    due: float            # seconds after the arrival clock starts
    prompt: List[int]
    max_new: int


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def _segment(rng, mix: dict, n: int, rate: float):
    """n prompt lengths, output lengths and gaps: the quantiles of the
    mix's distributions, in an order drawn from ``rng``."""
    p, o = mix["prompt"], mix["output"]
    plens = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                 p["max"])
    olens = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                 o["max"])
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])
    return rng.permutation(plens), rng.permutation(olens), \
        rng.permutation(gaps)


def arrivals(seed: int, mix: dict, vocab: int, warm_s: float,
             window_s: float) -> List[Arrival]:
    """Requests due over ``warm_s`` and then ``window_s`` seconds at the
    mix's rate.  Each of the two spans holds its own fixed multiset of
    sizes and gaps, in an order drawn from the mix's ``order_seed``: the
    schedule is the mix's, the same for every run, and the run's seed
    draws the token ids.  (Drawn from the run's seed, the order moved the
    tail of the gaps between tokens by more than two runs of one seed
    differ: how many long prompts overlap sets how many prefill chunks
    share a step.)"""
    rate = float(mix["rate_per_s"])
    rng = np.random.default_rng(_ss(mix["order_seed"], 1))
    plens, olens, due = [], [], []
    for start, span in ((0.0, warm_s), (warm_s, window_s)):
        p, o, gaps = _segment(rng, mix, max(1, int(round(rate * span))),
                              rate)
        # arrivals at the middle of each gap, the gaps stretched to fill
        # the span exactly: every request of a span is due inside it
        mid = np.cumsum(gaps) - gaps / 2
        plens += list(p)
        olens += list(o)
        due += list(start + span * mid / gaps.sum())
    out = []
    for i in range(len(plens)):
        ids = np.random.default_rng(_ss(seed, 2, i)).integers(
            0, vocab, size=int(plens[i]))
        out.append(Arrival(rid=i, due=float(due[i]), prompt=ids.tolist(),
                           max_new=int(olens[i])))
    return out
