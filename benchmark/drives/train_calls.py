"""Training calls: ``sweep_call`` for ONE sweep from the seeded initial
factors, to completion, back to back.  One sweep because the reference
follows one (``reference_als.als_one_sweep``); a train runs 10-20 in one
dispatch of the same program (``iterations`` is traced)."""

from __future__ import annotations

import time

import numpy as np

from benchmark import compare_als
from benchmark.drives import Window, call_ms

SWEEPS_PER_CALL = 1


def warm(system, mix) -> None:
    system.sweep_call(SWEEPS_PER_CALL)


def run(system, mix, config, seed: int, seconds: float,
        window_span) -> Window:
    import jax

    calls = 0
    ends = []
    with window_span():
        t0 = time.perf_counter()
        while True:
            model = system.sweep_call(SWEEPS_PER_CALL)
            calls += 1
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if elapsed >= seconds:
                break
    sweeps = calls * SWEEPS_PER_CALL
    w = Window()
    w.attempted = calls
    w.metrics = {"rating_iters_per_s": sweeps * system.n_ratings / elapsed}
    w.extras = {"calls": calls, "sweeps": sweeps, "elapsed_s": elapsed,
                "call_ms": call_ms(ends)}
    coo, init_seed = system.coo, system.init_seed
    items = compare_als.sample_items(config, seed, coo)
    # What the LAST timed call returned, on the host before the
    # program's state is freed.
    uf = np.asarray(jax.device_get(model.user_factors))
    rows = np.asarray(jax.device_get(model.item_factors))[items]
    del model
    w.check = lambda: compare_als.training_numbers(
        config, init_seed, coo, items, uf, rows)
    return w
