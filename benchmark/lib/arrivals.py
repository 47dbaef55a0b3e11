"""The one general request generator: lengths by stratified sampling,
arrivals open-loop or as a standing backlog.

Every seed gets the same multiset of prompt lengths and of output lengths,
block by block, and, in the open loop, exactly ``rate x seconds`` requests
due inside the window: seeds differ in order, in the pairing of arrival
time, prompt and output, and in token values, not in how much work the
window holds.

A mix's ``lengths`` group names two clipped lognormals.  ``strata`` fixes
how many distinct values each takes (the distribution's quantile
midpoints), so the set of prompt shapes the server ever sees is small and
known, and set-up can warm every one of them.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    due_s: float  # seconds after the window opens
    prompt: tuple[int, ...]
    max_new: int


def lognormal_strata(median: float, sigma: float, lo: int, hi: int,
                     strata: int) -> list[int]:
    """``strata`` quantile midpoints of a lognormal clipped to [lo, hi]."""
    nd = statistics.NormalDist()
    out = []
    for i in range(strata):
        z = nd.inv_cdf((i + 0.5) / strata)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def length_pairs(lengths: dict, n: int, rs: np.random.RandomState):
    """``n`` (prompt, output) pairs.  Requests come in blocks of ``strata``:
    every block holds each prompt stratum once and each output stratum
    once, each in an order of its own drawn from the seed.  So whatever
    prefix of the list a window gets through, it holds nearly the same
    multiset of lengths for every seed."""
    k = lengths["strata"]
    p = lognormal_strata(**lengths["prompt"], strata=k)
    o = lognormal_strata(**lengths["output"], strata=k)
    ps, os_ = [], []
    while len(ps) < n:
        ps += [p[i] for i in rs.permutation(k)]
        os_ += [o[i] for i in rs.permutation(k)]
    return ps[:n], os_[:n]


def distinct_prompt_lengths(lengths: dict) -> list[int]:
    return sorted(set(lognormal_strata(**lengths["prompt"],
                                       strata=lengths["strata"])))


def due_times(arrivals: dict, seconds: float, rs: np.random.RandomState):
    """Due times in [0, seconds).  ``poisson``: exponential gaps in a seeded
    order, scaled so that exactly ``round(rate x seconds)`` fall in the window.
    ``backlog``: ``requests_per_second x seconds`` requests, all due at 0."""
    kind = arrivals["kind"]
    if kind == "backlog":
        return [0.0] * int(round(arrivals["requests_per_second"] * seconds))
    if kind == "poisson":
        # the exponential's quantile midpoints, one gap each, in an order
        # drawn from the seed: every seed has the same multiset of gaps,
        # and exactly n requests fall due inside the window
        n = int(round(arrivals["rate_per_s"] * seconds))
        gaps = np.array([-math.log(1.0 - (i + 0.5) / (n + 1))
                         for i in range(n + 1)])[rs.permutation(n + 1)]
        t = np.cumsum(gaps)[:n]
        return list(t * (seconds / float(np.sum(gaps))))
    raise ValueError(f"unknown arrivals kind {kind!r}")


def plan(mix: dict, seed: int, seconds: float, vocab: int) -> list[Planned]:
    rs = np.random.RandomState(int(seed) % 2**32)
    due = due_times(mix["arrivals"], seconds, rs)
    prompts, outs = length_pairs(mix["lengths"], len(due), rs)
    # token 0 is kept out of prompts (the engine pads with it)
    return [Planned(float(t), tuple(int(x) for x in rs.randint(1, vocab, size=p)),
                    int(o)) for t, p, o in zip(due, prompts, outs)]
