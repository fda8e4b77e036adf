"""Open-loop request traffic, generated from the seed before the window.

A mix file gives the arrival rate, the request sizes and the skew of the
node ids; every request is one list of node ids whose scores a client
wants.  Arrivals are a Poisson process given its count: rate x seconds
requests at independent uniform times in the window.  Sizes are
log-uniform integers in ``[size_min, size_max]``, drawn once from
``sizes_seed`` so that every seed offers the same requests, in its own
order.  Ids follow a Zipf law of exponent ``zipf_s`` over a seeded
permutation of the nodes (rank r drawn with weight r^-s).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Requests(NamedTuple):
    due: np.ndarray        # [R] seconds after the window opens, ascending
    sizes: np.ndarray      # [R]
    ends: np.ndarray       # [R] end of each request in ``ids`` (exclusive)
    ids: np.ndarray        # [sum(sizes)] node ids, requests back to back


def open_loop(mix: dict, n: int, seconds: float, seed: int) -> Requests:
    rng = np.random.default_rng([seed, 2])
    r = int(round(float(mix["rate_rps"]) * seconds))
    due = np.sort(rng.uniform(0.0, seconds, r))
    lo, hi = int(mix["size_min"]), int(mix["size_max"])
    fixed = np.random.default_rng([int(mix["sizes_seed"]), 4])
    sizes = np.floor(np.exp(fixed.uniform(np.log(lo), np.log(hi + 1), r)))
    sizes = rng.permutation(np.clip(sizes.astype(np.int64), lo, hi))
    ends = np.cumsum(sizes)
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(mix["zipf_s"])
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    ranks = np.searchsorted(cdf, rng.random(int(ends[-1]) if r else 0),
                            side="right")
    ranks = np.minimum(ranks, n - 1)
    ids = rng.permutation(n)[ranks].astype(np.int32)
    return Requests(due, sizes, ends, ids)


def sample(req: Requests, count: int, seed: int) -> np.ndarray:
    """Indices of the requests whose answers are checked: ``count`` drawn
    from the seed, plus the longest request."""
    rng = np.random.default_rng([seed, 3])
    r = len(req.due)
    pick = rng.choice(r, size=min(count, r), replace=False) if r else \
        np.zeros(0, np.int64)
    if r:
        pick = np.union1d(pick, [int(np.argmax(req.sizes))])
    return np.sort(pick)
