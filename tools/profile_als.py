#!/usr/bin/env python
"""Per-phase ALS iteration profiler (VERDICT r2 item 1).

Times each phase of one ALS sweep at the bench shape: gather, gram+rhs
build, ridge solve — per bucket, both sides.  Every phase is measured by
the SLOPE method (fori_loop of N reps inside one jit, timed at two rep
counts) because one dispatch plus host read-back costs more than most
phases.  A runtime-zero feedback
term defeats loop-invariant hoisting.  Prints a JSON phase table.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import functools

import jax
import jax.numpy as jnp

from predictionio_tpu.models.als import (
    ALSConfig, prepare_als_inputs, _gram_pieces, _ridge,
)

SCALE = float(os.environ.get("PIO_BENCH_SCALE", "1.0"))
N_USERS = max(64, int(162_541 * SCALE))
N_ITEMS = max(64, int(59_047 * SCALE))
N_RATINGS = max(4096, int(25_000_000 * SCALE))
RANK = int(os.environ.get("PIO_BENCH_RANK", "64"))
R1, R2 = 2, 10


def synth(seed=0):
    rng = np.random.default_rng(seed)
    users = rng.integers(0, N_USERS, N_RATINGS)
    items = (rng.zipf(1.25, size=N_RATINGS) % N_ITEMS).astype(np.int64)
    ratings = (rng.integers(1, 11, N_RATINGS) * 0.5).astype(np.float32)
    return users, items, ratings


def slope(repeat_fn, *args):
    """ms per rep via (T(R2)-T(R1))/(R2-R1); one host read per run."""
    def run(n):
        t0 = time.perf_counter()
        out = repeat_fn(jnp.int32(n), jnp.float32(0.0), *args)
        float(jnp.sum(out))
        return time.perf_counter() - t0
    run(R1)  # compile
    t1 = run(R1)
    t2 = run(R2)
    return (t2 - t1) / (R2 - R1) * 1e3


@jax.jit
def rep_gather(n, zero, factors, indices):
    def body(_, carry):
        f = (factors + carry * zero)[indices]
        return jnp.float32(f[0, 0, 0])
    c = jax.lax.fori_loop(0, n, body, jnp.float32(0.0))
    return c


@jax.jit
def rep_gram(n, zero, factors, indices, vals, msk):
    def body(_, carry):
        a, b, deg = _gram_pieces(indices, vals + carry * zero, msk, factors,
                                 jnp.float32(1.0), False, False, jnp.float32)
        return jnp.float32(a[0, 0, 0] + b[0, 0] + deg[0])
    return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))


def rep_solve(solver):
    @jax.jit
    def f(n, zero, a, b, regv):
        def body(_, carry):
            x = _ridge(a + carry * zero, b, regv, solver)
            return jnp.float32(x[0, 0])
        return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))
    return f


rep_gj = rep_solve("gj")
rep_ch = rep_solve("cholesky")


def main():
    users, items, ratings = synth()
    cfg = ALSConfig(rank=RANK, iterations=2, reg=0.01, seed=1)
    t0 = time.perf_counter()
    inputs = prepare_als_inputs(users, items, ratings, N_USERS, N_ITEMS, cfg)
    prep_s = time.perf_counter() - t0
    print(f"prep_s={prep_s:.2f}", file=sys.stderr)

    # Bucket-level inputs (round 4): expand the HBM chunks exactly as the
    # training loop does, so per-bucket gathers below stay within the
    # chunk budget instead of materializing a whole jumbo bucket.
    from predictionio_tpu.models.als import _expand_chunks
    import jax as _jax
    if inputs.chunk_specs is not None:
        expand = _jax.jit(_expand_chunks, static_argnames=("specs",))
        u_kinds = []
        for b, spec in zip(inputs.user_buckets, inputs.chunk_specs[0]):
            u_kinds.extend([b[0]] * max(len(spec[-1]), 1))
        i_kinds = []
        for b, spec in zip(inputs.item_buckets, inputs.chunk_specs[1]):
            i_kinds.extend([b[0]] * max(len(spec[-1]), 1))
        ub = expand(tuple(tuple(b[1:]) for b in inputs.user_buckets),
                    specs=inputs.chunk_specs[0])
        ib = expand(tuple(tuple(b[1:]) for b in inputs.item_buckets),
                    specs=inputs.chunk_specs[1])
        inputs.user_buckets = [(k, *a) for k, a in zip(u_kinds, ub)]
        inputs.item_buckets = [(k, *a) for k, a in zip(i_kinds, ib)]
        inputs.chunk_specs = None

    report = {"shape": f"{N_USERS}x{N_ITEMS}x{N_RATINGS} rank{RANK}",
              "prep_s": round(prep_s, 2), "sides": {}}
    reg = jnp.float32(0.01)
    gram_once = jax.jit(lambda i, v, m, f: _gram_pieces(
        i, v, m, f, jnp.float32(1.0), False, False, jnp.float32))

    totals = dict(gather=0.0, gram=0.0, gj=0.0, chol=0.0)
    for side, buckets, factors in (("user", inputs.user_buckets, inputs.itf0),
                                   ("item", inputs.item_buckets, inputs.uf0)):
        rows = []
        for kind, idx, vals, msk, *rest in buckets:
            r, l = idx.shape
            ms_gather = slope(rep_gather, factors, idx)
            ms_gram = slope(rep_gram, factors, idx, vals, msk)
            a, b, deg = gram_once(idx, vals, msk, factors)
            regv = reg * jnp.maximum(deg, 1.0)
            ms_gj = slope(rep_gj, a, b, regv)
            ms_ch = slope(rep_ch, a, b, regv)
            totals["gather"] += ms_gather
            totals["gram"] += ms_gram
            totals["gj"] += ms_gj
            totals["chol"] += ms_ch
            rows.append({"kind": kind, "rows": r, "len": l,
                         "padded_nnz_m": round(idx.size / 1e6, 2),
                         "gather_ms": round(ms_gather, 2),
                         "gram_ms": round(ms_gram, 2),
                         "solve_gj_ms": round(ms_gj, 2),
                         "solve_chol_ms": round(ms_ch, 2)})
        report["sides"][side] = rows
    report["totals_ms"] = {k: round(v, 2) for k, v in totals.items()}

    from predictionio_tpu.models.als import train_als_prepared

    def run(iters):
        c = ALSConfig(rank=RANK, iterations=iters, reg=0.01, seed=1)
        t0 = time.perf_counter()
        m = train_als_prepared(inputs, c)
        float(jnp.sum(m.user_factors))
        return time.perf_counter() - t0

    run(2)
    t1 = run(2)
    t2 = run(6)
    report["per_iter_ms"] = round((t2 - t1) / 4 * 1e3, 2)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
