"""Closed-loop batches of session TURNS: ``query_batch`` (what ``pio
batchpredict`` calls) on ``chunk`` queries ``{user, num, events}``, back
to back from one caller, for the whole window.  What ``batch_closed_loop``
is to a model without state.

The residents are walked in successive seeded permutations, ``chunk`` a
call (``chunk`` divides the population, so two turns of a user never
share a call); every pass deals the same multiset of turn sizes
(``events_per_turn``: quantiles of a log-normal) to the residents anew,
so a pass is the same work for every seed.

Set-up builds every resident's state THROUGH THE ENGINE: ``warm`` first
runs every program shape once on users that exist only for that (``w<i>``;
the residents then evict them), then sends each resident's seeded history
as that user's first turn through ``query_batch`` (the engine takes it in
chunks of a program's token bucket).  No side door writes cache arrays.

Mix parameters: ``chunk``, ``events_per_turn``, ``max_calls`` (the schedule
is made before the window, for this many calls), ``prefill_users_per_call``,
``check_users`` and ``check_answers`` (the comparison reads
``check_answers`` answers of ``check_users`` residents, so that one pass
of the reference serves several).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import compare_sala, datagen_seq, prom, traffic
from benchmark.drives import Window, call_ms, sample


def _turn(user: str, items: np.ndarray, num: int = 10) -> Dict[str, Any]:
    return {"user": user, "num": int(num),
            "events": [f"i{int(j)}" for j in items]}


def warm(system, mix) -> None:
    config, seed = system.config, system.seed
    events = datagen_seq.Events(config, seed)
    t0 = time.perf_counter()
    # Every (token bucket, answer bucket) the mix can reach: a call of
    # one-event turns (the short-turn program), one long turn (a prefill
    # chunk), a call of turns that together pass the short bucket.
    chunk = int(mix["chunk"])
    system.query_batch([_turn(f"w{w}", events.of(10_000 + w, 1))
                        for w in range(chunk)])
    system.query_batch([_turn(f"w{chunk}", events.of(10_000 + chunk, 300))])
    system.query_batch([_turn(f"w{w}", events.of(10_000 + w, 6)[1:])
                        for w in range(chunk)])
    system.split["seq_compile_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    lengths = datagen_seq.history_lengths(config, seed)
    per_call = int(mix.get("prefill_users_per_call", 8))
    for lo in range(0, len(lengths), per_call):
        system.query_batch([
            _turn(f"u{u}", events.of(u, lengths[u]))
            for u in range(lo, min(lo + per_call, len(lengths)))])
    system.split["state_cache_build_s"] = time.perf_counter() - t0


def schedule(mix, config, seed: int):
    """(user, events in the turn) of every query of ``max_calls`` calls,
    [calls, chunk] each."""
    chunk, calls = int(mix["chunk"]), int(mix["max_calls"])
    population = int(config["n_users"])
    if population % chunk:
        raise ValueError(f"chunk {chunk} does not divide the {population} "
                         "residents: two turns of a user could share a call")
    e = mix["events_per_turn"]
    sizes = datagen_seq.lognormal_quantiles(
        population, e["median"], e["sigma"], e["min"], e["max"])
    passes = -(-calls * chunk // population)
    rng_users, rng_sizes = traffic.rng_for(seed, 2), traffic.rng_for(seed, 31)
    users = np.concatenate([rng_users.permutation(population)
                            for _ in range(passes)])
    per_turn = np.concatenate([rng_sizes.permutation(sizes)
                               for _ in range(passes)])
    n = calls * chunk
    return users[:n].reshape(calls, chunk), per_turn[:n].reshape(calls, chunk)


def run(system, mix, config, seed: int, seconds: float,
        window_span) -> Window:
    users, sizes = schedule(mix, config, seed)
    calls_made, chunk = users.shape
    lengths = datagen_seq.history_lengths(config, seed).astype(np.int64)
    after = np.empty(users.shape, np.int64)
    count = lengths.copy()
    for c in range(calls_made):
        count[users[c]] += sizes[c]
        after[c] = count[users[c]]
    events = datagen_seq.Events(config, seed)
    # Each resident's events past its history, once, before the window.
    # A call's queries are made inside the loop, as ``batch_closed_loop``
    # makes them: 3,000 calls' worth made ahead are a million dicts, lists
    # and strings that every full pass of the interpreter's cycle
    # collector walks inside the window; made here they die by reference
    # count, and a full pass is 1.5 ms (``PERF.md``, finding 5 of PR 34).
    streams = [events.of(u, count[u])[lengths[u]:]
               for u in range(len(lengths))]
    names = [f"u{u}" for u in range(len(lengths))]
    first = after - sizes - lengths[users]      # offsets into the streams
    watched = set(int(u) for u in sample(
        seed, len(lengths), int(mix.get("check_users", 4))))
    kept: List[tuple] = []
    misses = {"result": "miss"}
    before = prom.snapshot()
    answered = calls = 0
    ends = []
    with window_span():
        t0 = time.perf_counter()
        while True:
            if calls == calls_made:
                raise RuntimeError("the mix's max_calls ran out inside the "
                                   "window; raise it")
            out = system.query_batch([
                _turn(names[u], streams[u][lo:lo + n]) for u, n, lo in
                zip(users[calls], sizes[calls], first[calls])])
            answered += len(out)
            for j, u in enumerate(users[calls]):
                if int(u) in watched:
                    kept.append((int(u), int(after[calls, j]), 10, out[j]))
            calls += 1
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if elapsed >= seconds:
                break
    after_window = prom.snapshot()
    w = Window()
    w.attempted = calls * chunk
    w.failed = w.attempted - answered
    w.metrics = {"queries_per_s": answered / elapsed}
    w.extras = {"calls": calls, "elapsed_s": elapsed, "chunk": chunk,
                "call_ms": call_ms(ends),
                "new_events": int(sizes[:calls].sum()),
                "seq_dispatches": prom.delta(before, after_window,
                                             "pio_seq_dispatches_total")}
    missed = prom.delta(before, after_window, "pio_seq_state_total", misses)
    # The comparison's sample: seeded picks among the watched residents'
    # answers, each resident's last among them.
    pick = set(int(i) for i in sample(seed, len(kept),
                                      int(mix.get("check_answers", 32))))
    samples = [kept[i] for i in sorted(pick)]

    def check() -> Dict[str, float]:
        return {**compare_sala.numbers(config, seed, samples),
                "state_misses_in_window": float(missed)}

    w.check = check
    return w
