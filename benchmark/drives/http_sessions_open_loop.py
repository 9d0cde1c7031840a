"""Open-loop HTTP sessions: each request is one TURN of a signed-in
user's session, ``POST /queries.json {user, num, events}``, carrying the
user's events since their last query.  Sent when due by the child
process of ``benchmark/loadgen.py`` (no jax, no shared interpreter lock).

Set-up builds every resident user's state THROUGH THE ENGINE: ``warm``
sends each user's seeded history as that user's first turn through
``query_batch`` (a turn longer than a program's token bucket is taken in
chunks by the engine), after it has run every program shape once on
users that exist only for that (``w<i>``; never asked again, never
checked).  No side door writes cache arrays.

Mix parameters: ``arrival`` (``traffic.arrival_times``),
``events_per_turn`` (quantiles of a log-normal, the same multiset for
every seed), ``users.min_gap_s`` (two turns of one user at least this
far apart in the schedule, so a user's turns reach the server in
order), ``prefill_users_per_call``.
"""

from __future__ import annotations

import collections
import http.client
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark import compare_seq, datagen_seq, prom, traffic
from benchmark.drives import FAILED_MS, Window, sample

CHECKOUT = Path(__file__).resolve().parents[2]


def _turn(user: str, items: np.ndarray, num: int = 10) -> Dict[str, Any]:
    return {"user": user, "num": int(num),
            "events": [f"i{int(j)}" for j in items]}


def _shape_turns(events: datagen_seq.Events, token_buckets, read_buckets
                 ) -> List[List[Dict[str, Any]]]:
    """One cohort for every (token bucket, answer bucket) the engine
    compiles, on warm-up users: the smaller answer bucket from one user,
    the larger from one more user than the smaller holds."""
    cohorts, w = [], 0
    lo = 1
    for t in token_buckets:
        for r_lo, r in zip((0,) + tuple(read_buckets), read_buckets):
            users = r_lo + 1
            per = max((lo + t) // 2 // users, 1)
            cohort = []
            for _ in range(users):
                cohort.append(_turn(f"w{w}", events.of(10_000 + w, per)))
                w += 1
            cohorts.append(cohort)
        lo = t
    return cohorts


def warm(system, mix) -> None:
    from predictionio_tpu.models.lfm2 import READ_BUCKETS, TOKEN_BUCKETS

    config, seed = system.config, system.seed
    events = datagen_seq.Events(config, seed)
    t0 = time.perf_counter()
    cohorts = _shape_turns(events, TOKEN_BUCKETS, READ_BUCKETS)
    if sum(len(c) for c in cohorts) > int(config["state"]["warm_users"]):
        raise ValueError("the configuration holds too few warm_users for "
                         "the engine's program shapes")
    for cohort in cohorts:
        system.query_batch(cohort)
    system.split["seq_compile_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    lengths = datagen_seq.history_lengths(config, seed)
    per_call = int(mix.get("prefill_users_per_call", 8))
    for lo in range(0, len(lengths), per_call):
        system.query_batch([
            _turn(f"u{u}", events.of(u, lengths[u]))
            for u in range(lo, min(lo + per_call, len(lengths)))])
    system.split["state_cache_build_s"] = time.perf_counter() - t0
    conn = http.client.HTTPConnection("127.0.0.1", system.port, timeout=60)
    try:
        conn.request("POST", "/queries.json",
                     body=json.dumps(_turn("w0", events.of(10_000, 3)[2:])),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read()
        if resp.status != 200:
            raise RuntimeError(f"warm-up query answered {resp.status}")
    finally:
        conn.close()


def schedule(mix, config, seed: int, seconds: float
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(due_s, user, events in the turn) of every request: users in
    successive seeded permutations, a user skipped (and kept at the head
    of the line) while its last turn is less than ``min_gap_s`` old."""
    due = traffic.arrival_times(mix, seed, seconds)
    n, population = len(due), int(config["n_users"])
    e = mix["events_per_turn"]
    sizes = datagen_seq.lognormal_quantiles(n, e["median"], e["sigma"],
                                            e["min"], e["max"])
    traffic.rng_for(seed, 31).shuffle(sizes)
    gap = float(mix.get("users", {}).get("min_gap_s", 1.0))
    rng = traffic.rng_for(seed, 2)
    line: collections.deque = collections.deque(rng.permutation(population))
    last = np.full(population, -np.inf)
    users = np.empty(n, np.int64)
    for i, t in enumerate(due):
        if len(line) < population:
            line.extend(rng.permutation(population))
        for j, u in enumerate(line):
            if t - last[u] >= gap:
                del line[j]
                break
        else:
            raise ValueError(f"no user is free at {t:.3f} s: the rate needs "
                             "more users than the configuration holds")
        users[i], last[u] = u, t
    return due, users, sizes


def events_after(config, seed: int, users: np.ndarray, sizes: np.ndarray
                 ) -> np.ndarray:
    """The events each turn's user has once the turn is applied: the
    seeded history and every turn of the schedule up to this one."""
    count = datagen_seq.history_lengths(config, seed).astype(np.int64)
    after = np.empty(len(users), np.int64)
    for i, (u, n) in enumerate(zip(users, sizes)):
        count[u] += n
        after[i] = count[u]
    return after


def run(system, mix, config, seed: int, seconds: float,
        window_span) -> Window:
    due, users, sizes = schedule(mix, config, seed, seconds)
    events = datagen_seq.Events(config, seed)
    after = events_after(config, seed, users, sizes)
    final = np.zeros(int(config["n_users"]), np.int64)
    np.maximum.at(final, users, after)
    streams = {int(u): events.of(u, final[u]) for u in np.unique(users)}
    bodies = [json.dumps(_turn(f"u{u}", streams[int(u)][a - n:a]))
              for u, n, a in zip(users, sizes, after)]
    spec = {"port": system.port, "due_s": due.tolist(), "bodies": bodies,
            "connections": int(mix.get("connections", 128)),
            "timeout_s": float(mix.get("timeout_s", 30.0))}
    tmp = tempfile.mkdtemp(prefix="bench_loadgen_")
    spec_path, out_path = f"{tmp}/spec.json", f"{tmp}/out.json"
    with open(spec_path, "w", encoding="utf-8") as f:
        json.dump(spec, f)
    child = subprocess.Popen(
        [sys.executable, "-m", "benchmark.loadgen", spec_path, out_path],
        cwd=str(CHECKOUT), stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        text=True)
    misses = {"result": "miss"}
    before = prom.snapshot()
    try:
        if child.stdout.readline().strip() != "ready":
            raise RuntimeError("load generator did not start")
        with window_span():
            child.stdin.write("go\n")
            child.stdin.flush()
            child.wait(timeout=seconds + spec["timeout_s"] + 30)
        if child.returncode != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
        with open(out_path, encoding="utf-8") as f:
            res = json.load(f)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        for p in (spec_path, out_path):
            if os.path.exists(p):
                os.unlink(p)
        os.rmdir(tmp)
    after_window = prom.snapshot()
    missed = prom.delta(before, after_window, "pio_seq_state_total", misses)
    status = np.array(res["status"])
    done, sent = np.array(res["done_s"]), np.array(res["sent_s"])
    ok = status == 200
    lat = np.where(ok, (done - due) * 1e3, FAILED_MS)
    w = Window()
    w.attempted, w.failed = len(due), int((~ok).sum())
    w.metrics = {
        "query_p50_ms": float(np.percentile(lat, 50)),
        "query_p95_ms": float(np.percentile(lat, 95)),
        "queries_per_s": float((ok & (done <= seconds)).sum() / seconds),
    }
    # A turn can be checked if every earlier turn of its user had been
    # answered before it was sent: then the server applied them in the
    # schedule's order, whatever the connections did.
    in_order = np.ones(len(due), bool)
    busy_until: Dict[int, float] = {}
    for i in np.argsort(sent, kind="stable"):
        u = int(users[i])
        if sent[i] < busy_until.get(u, -np.inf) or not ok[i]:
            in_order[i] = False
        busy_until[u] = max(busy_until.get(u, -np.inf),
                            done[i] if ok[i] else np.inf)
    w.extras = {"late_ms": (sent - due) * 1e3, "latency_ms": lat,
                "offered_per_s": len(due) / seconds,
                "new_events": int(sizes.sum()),
                "seq_dispatches": prom.delta(before, after_window,
                                             "pio_seq_dispatches_total"),
                "overlapped_turns": int((ok & ~in_order).sum()),
                "statuses": {int(s): int((status == s).sum())
                             for s in np.unique(status)}}
    checkable = np.flatnonzero(ok & in_order)
    pick = checkable[sample(seed, len(checkable),
                            int(mix.get("check_answers", 64)))] \
        if len(checkable) else checkable
    samples = [(int(users[i]), int(after[i]), 10, res["answers"][i])
               for i in pick]

    def check() -> Dict[str, float]:
        return {**compare_seq.numbers(config, seed, samples),
                "state_misses_in_window": float(missed)}

    w.check = check
    return w
