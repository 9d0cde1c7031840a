"""Closed-loop batches of session TURNS, for any backbone of the sequence
engine: ``seq_bulk_closed_loop``'s loop (``query_batch`` on ``chunk``
queries ``{user, num, events}``, back to back from one caller, the
residents walked in successive seeded permutations, every pass the same
multiset of turn sizes; its ``schedule`` and ``_turn`` are used as they
are), with the comparison that decides ``correct`` taken from the
CONFIGURATION (``"compare"``: a module beside ``compare_sala`` with
``numbers(config, seed, samples)``) where that drive names
``compare_sala``.

Set-up builds every resident's state THROUGH THE ENGINE, as there: every
program shape once on users that exist only for that (``w<i>``; the
residents then evict them), then each resident's seeded history as that
user's first turn through ``query_batch``.  The shapes: a call of
one-event turns (the short-turn program), one turn longer than a token
bucket (a chunk that ends no turn, then the rest of it), a call of turns
that together pass the short bucket.

Mix parameters: those of ``seq_bulk_closed_loop``.
"""

from __future__ import annotations

import importlib
import time
from typing import Dict, List

import numpy as np

from benchmark import datagen_seq, prom
from benchmark.drives import Window, call_ms, sample
from benchmark.drives.seq_bulk_closed_loop import _turn, schedule

__all__ = ["warm", "run", "schedule"]

# Events of the one long warm-up turn: past the largest token bucket, so
# that the program of a chunk that ends no turn runs once too.
_LONG_TURN = 1100


def warm(system, mix) -> None:
    config, seed = system.config, system.seed
    events = datagen_seq.Events(config, seed)
    t0 = time.perf_counter()
    chunk = int(mix["chunk"])
    system.query_batch([_turn(f"w{w}", events.of(10_000 + w, 1))
                        for w in range(chunk)])
    system.query_batch([_turn(f"w{chunk}",
                              events.of(10_000 + chunk, _LONG_TURN))])
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


def run(system, mix, config, seed: int, seconds: float,
        window_span) -> Window:
    compare = importlib.import_module(f"benchmark.{config['compare']}")
    users, sizes = schedule(mix, config, seed)
    calls_made, chunk = users.shape
    lengths = datagen_seq.history_lengths(config, seed).astype(np.int64)
    after = np.empty(users.shape, np.int64)
    count = lengths.copy()
    for c in range(calls_made):
        count[users[c]] += sizes[c]
        after[c] = count[users[c]]
    events = datagen_seq.Events(config, seed)
    # Each resident's events past its history, once, before the window; a
    # call's queries are made inside the loop and die by reference count
    # (``seq_bulk_closed_loop`` says why).
    streams = [events.of(u, count[u])[lengths[u]:]
               for u in range(len(lengths))]
    names = [f"u{u}" for u in range(len(lengths))]
    first = after - sizes - lengths[users]      # offsets into the streams
    watched = set(int(u) for u in sample(
        seed, len(lengths), int(mix.get("check_users", 4))))
    kept: List[tuple] = []
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
    missed = prom.delta(before, after_window, "pio_seq_state_total",
                        {"result": "miss"})
    # The comparison's sample: seeded picks among the watched residents'
    # answers, each resident's last among them.
    pick = set(int(i) for i in sample(seed, len(kept),
                                      int(mix.get("check_answers", 32))))
    samples = [kept[i] for i in sorted(pick)]

    def check() -> Dict[str, float]:
        return {**compare.numbers(config, seed, samples),
                "state_misses_in_window": float(missed)}

    w.check = check
    return w
