"""The one traffic generator: a mix's parameters + a seed -> the work.

A mix is a data file (``traffic/<mix>.json``).  Its ``drive`` says how
the work reaches the system (a module of ``benchmark/drives/``); the rest
are parameters of the draws below.  Every
seed gets the SAME multiset of gaps, sizes and ``num`` values in another
order, so two seeds differ in which users ask and when, never in how
much work a run holds.

Keys a serving mix may carry (defaults in brackets):

    arrival.rate_per_s          offered rate, open loop
    arrival.burst_factor [1]    >1: gaps inside a burst shrink by this
    arrival.burst_share  [0]    share of requests that arrive in bursts
    arrival.burst_len    [32]   requests in one burst
    users.draw [distinct]       distinct | uniform | zipf
    users.zipf_s [1.1]          exponent when draw = zipf
    num [[10, 1.0]]             [[k, weight], ...] of the query's ``num``
    chunk                       queries per call, closed loop

Bursts and the ``uniform`` and ``zipf`` draws are here before a cell
uses them because a later PR may add data files and no code: the mixes
``PERF.md`` keeps for later (``serve-zipf``, ``serve-burst``) need them.
The tests hold them to their counts; the first cell that uses one
proves it on the chip.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of ``seed`` (any whole number;
    the driver's seeds pass 2**31)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def arrival_times(mix: Dict[str, Any], seed: int, seconds: float
                  ) -> np.ndarray:
    """Due times in [0, seconds): exponential gaps of a Poisson process
    at ``rate_per_s`` — the quantiles of the exponential, one each,
    shuffled by the seed, so every seed offers exactly ``rate * seconds``
    requests with the same gaps in another order."""
    arr = mix["arrival"]
    rate = float(arr["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng = rng_for(seed, 1)
    rng.shuffle(gaps)
    share = float(arr.get("burst_share", 0.0))
    factor = float(arr.get("burst_factor", 1.0))
    if share > 0 and factor > 1:
        # Whole runs of burst_len consecutive gaps shrink by the factor
        # (which runs: the seed's choice); the rest stretch so the mean
        # rate stays rate_per_s.
        run = int(arr.get("burst_len", 32))
        blocks = math.ceil(n / run)
        hot = rng.permutation(blocks)[:int(round(blocks * share))]
        in_burst = np.zeros(blocks * run, bool)
        in_burst.reshape(blocks, run)[hot] = True
        in_burst = in_burst[:n]
        nb = int(in_burst.sum())
        gaps = np.where(in_burst, gaps / factor,
                        gaps * (n - nb / factor) / max(n - nb, 1))
    due = np.cumsum(gaps)
    # Mean gap is 1/rate by construction; rescale the last few ulps so
    # every request is due inside the window.
    return due * (seconds * (1 - 0.5 / n) / due[-1])


def draw_users(mix: Dict[str, Any], seed: int, n: int, population: int
               ) -> np.ndarray:
    """``n`` user indices in [0, population)."""
    users = mix.get("users", {})
    draw = users.get("draw", "distinct")
    rng = rng_for(seed, 2)
    if draw == "distinct":
        # Distinct users: the result cache cannot answer.  Past the
        # population the draw starts a fresh permutation.
        reps = math.ceil(n / population)
        return np.concatenate(
            [rng.permutation(population) for _ in range(reps)])[:n]
    if draw == "uniform":
        return rng.integers(0, population, n)
    if draw == "zipf":
        s = float(users.get("zipf_s", 1.1))
        w = np.arange(1, population + 1, dtype=np.float64) ** -s
        cdf = np.cumsum(w / w.sum())
        ranks = np.searchsorted(cdf, rng.random(n))
        # Popularity rank -> a seeded relabelling, so the hot users move.
        return rng.permutation(population)[np.minimum(ranks, population - 1)]
    raise ValueError(f"users.draw {draw!r} is not distinct|uniform|zipf")


def draw_nums(mix: Dict[str, Any], seed: int, n: int) -> np.ndarray:
    """The ``num`` of each query: each listed k in its weight's share,
    shuffled by the seed."""
    table = mix.get("num", [[10, 1.0]])
    ks = np.array([int(k) for k, _ in table])
    w = np.array([float(x) for _, x in table])
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[0] += n - counts.sum()
    nums = np.repeat(ks, counts)
    rng_for(seed, 3).shuffle(nums)
    return nums


def serving_requests(mix: Dict[str, Any], seed: int, seconds: float,
                     population: int
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(due_s, user_idx, num) of an open-loop serving run."""
    due = arrival_times(mix, seed, seconds)
    return (due, draw_users(mix, seed, len(due), population),
            draw_nums(mix, seed, len(due)))


def query_json(user_idx: int, num: int) -> Dict[str, Any]:
    return {"user": f"u{int(user_idx)}", "num": int(num)}
