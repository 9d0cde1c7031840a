"""Closed-loop batches: ``query_batch`` (what ``pio batchpredict``
calls) on ``chunk`` queries, back to back, one caller."""

from __future__ import annotations

import time
from typing import List

from benchmark import compare, traffic
from benchmark.drives import Window, call_ms, distinct_nums, sample


def warm(system, mix) -> None:
    for num in distinct_nums(mix):
        system.query_batch([traffic.query_json(u, num)
                            for u in range(int(mix["chunk"]))])


def run(system, mix, config, seed: int, seconds: float,
        window_span) -> Window:
    chunk = int(mix["chunk"])
    # More users than any window can score; distinct until the draw
    # wraps.
    budget = int(mix.get("max_queries", 1_000_000))
    users = traffic.draw_users(mix, seed, budget, system.population)
    nums = traffic.draw_nums(mix, seed, budget)
    keep_every = max(1, int(mix.get("keep_every", 8)))
    kept: List[tuple] = []
    answered = calls = 0
    ends = []
    with window_span():
        t0 = time.perf_counter()
        while True:
            lo = calls * chunk
            if lo + chunk > budget:
                raise RuntimeError("the mix's max_queries ran out inside "
                                   "the window; raise it")
            queries = [traffic.query_json(u, k) for u, k in
                       zip(users[lo:lo + chunk], nums[lo:lo + chunk])]
            out = system.query_batch(queries)
            answered += len(out)
            if calls % keep_every == 0:
                kept.append((lo, out))
            last = (lo, out)
            calls += 1
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if elapsed >= seconds:
                break
    if kept[-1][0] != last[0]:
        kept.append(last)
    w = Window()
    w.attempted = calls * chunk
    w.metrics = {"queries_per_s": answered / elapsed}
    w.extras = {"calls": calls, "elapsed_s": elapsed, "chunk": chunk,
                "call_ms": call_ms(ends)}
    # The comparison's sample: seeded picks across the kept calls, the
    # last call among them.
    pool = [(lo + j, ans) for lo, out in kept for j, ans in enumerate(out)]
    pick = sample(seed, len(pool), int(mix.get("check_answers", 256)))
    samples = [(int(users[pool[i][0]]), int(nums[pool[i][0]]), pool[i][1])
               for i in pick]
    w.check = lambda: compare.serving_numbers(config, seed, samples)
    return w
