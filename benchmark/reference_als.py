"""The plain reference of the training cells: one ALS-WR sweep (Zhou et
al. 2008) in straightforward ``jax.numpy`` at float32 ``highest``: sort
the ratings by row, pad rows to powers of two, build each row's normal
equations and solve them by Gauss-Jordan.  No kernel, no bucket plan;
imports nothing of the program and takes nothing it made: the ratings
are the harness's COO, the initial factors are re-drawn here.

``operand_dtype`` exists for the CONTROL: the reference put in the
program's place with the gathered factor rows one precision step below
the configuration's bfloat16 (float8), which the comparison must refuse.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def als_init_factors(n_users: int, n_items: int, rank: int, seed: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """The ALS initial factors as MLlib and the program draw them:
    normal / sqrt(rank) from ``PRNGKey(seed)`` split in two.  Written
    out here so the reference needs nothing of the program."""
    ku, ki = jax.random.split(jax.random.PRNGKey(seed))
    scale = np.sqrt(rank).astype(np.float32)
    return (jax.random.normal(ku, (n_users, rank), jnp.float32) / scale,
            jax.random.normal(ki, (n_items, rank), jnp.float32) / scale)


_SLOTS = 1 << 21     # gathered slots per block
_ROWS = 1 << 14      # rows per block at most: their K x K systems are
                     # 16384 * 64 * 65 * 4 B = 273 MB at rank 64
_LMAX = 1 << 15      # longest padded row; longer rows go in segments


@functools.partial(jax.jit, static_argnames=("length", "precision"))
def _gram_block(starts, lens, cols, vals, src, *, length, precision):
    """Normal-equation pieces of R rows padded to ``length``: row r is
    the ``lens[r]`` sorted ratings from ``starts[r]`` on."""
    pos = starts[:, None] + jnp.arange(length, dtype=jnp.int32)[None, :]
    mask = jnp.arange(length)[None, :] < lens[:, None]
    pos = jnp.where(mask, pos, 0)
    g = src[cols[pos]] * mask[..., None].astype(src.dtype)
    r = vals[pos] * mask
    a = jnp.einsum("rlk,rlj->rkj", g, g, precision=precision,
                   preferred_element_type=jnp.float32)
    b = jnp.einsum("rlk,rl->rk", g, r.astype(g.dtype), precision=precision,
                   preferred_element_type=jnp.float32)
    return a, b


def _ridge(a, b, reg):
    """x of (A + diag(reg)) x = b for a batch of SPD systems: plain
    Gauss-Jordan on the augmented [R, K, K+1] block, vectorised over the
    batch (no pivoting: A + reg*I is positive definite).  XLA's batched
    LU took 17 s for the 480,189 user systems of the Netflix shape; this
    takes under one."""
    k = a.shape[-1]
    m = jnp.concatenate(
        [a + reg[:, None, None] * jnp.eye(k, dtype=a.dtype), b[..., None]],
        axis=-1)

    def step(j, m):
        row = jax.lax.dynamic_slice_in_dim(m, j, 1, axis=1)
        row = row / jax.lax.dynamic_slice_in_dim(row, j, 1, axis=2)
        col = jax.lax.dynamic_slice_in_dim(m, j, 1, axis=2)
        return jax.lax.dynamic_update_slice_in_dim(m - col * row, row, j,
                                                   axis=1)

    return jax.lax.fori_loop(0, k, step, m)[..., -1]


_solve = jax.jit(_ridge)


@functools.partial(jax.jit, static_argnames=("length", "precision"))
def _gram_solve_block(starts, lens, reg, cols, vals, src, *, length,
                      precision):
    a, b = _gram_block.__wrapped__(starts, lens, cols, vals, src,
                                   length=length, precision=precision)
    return _ridge(a, b, reg)


def _blocks(segs: np.ndarray, length: int):
    per = max(8, min(_ROWS, _SLOTS // int(length)))
    for lo in range(0, len(segs), per):
        yield segs[lo:lo + per], per


def _padded(values: np.ndarray, per: int, dtype, fill=0) -> jax.Array:
    out = np.full(per, fill, dtype)
    out[:len(values)] = values
    return jnp.asarray(out)


def solve_rows(row_ids: np.ndarray, ptr: np.ndarray, deg: np.ndarray,
               cols, vals, src, lam: float, *, precision: str = "highest",
               operand_dtype=None) -> np.ndarray:
    """ALS-WR rows ``row_ids`` of one side: x = (sum v v^T + lam*n*I)^-1
    sum r v over the row's ratings, ``src`` the other side's factors.
    ``cols``/``vals`` are the ratings sorted by this side's row id,
    ``ptr``/``deg`` each row's start and count in them.  Rows are padded
    to the next power of two and solved a block at a time; a row longer
    than ``_LMAX`` is summed from segments first."""
    prec = jax.lax.Precision(precision)
    if operand_dtype is not None:
        src = src.astype(operand_dtype).astype(jnp.float32)
    k = src.shape[1]
    row_ids = np.asarray(row_ids)
    d = deg[row_ids].astype(np.int64)
    reg = (lam * np.maximum(d, 1)).astype(np.float32)
    out = np.empty((len(row_ids), k), np.float32)

    def classes(lengths):
        return np.maximum(8, 1 << np.ceil(np.log2(np.maximum(lengths, 1))
                                          ).astype(np.int64))

    short = np.flatnonzero(d <= _LMAX)
    cls = classes(d[short])
    for length in np.unique(cls):
        for part, per in _blocks(short[cls == length], length):
            x = _gram_solve_block(
                _padded(ptr[row_ids[part]], per, np.int32),
                _padded(d[part], per, np.int32),
                _padded(reg[part], per, np.float32, 1.0),
                cols, vals, src, length=int(length), precision=prec)
            out[part] = np.asarray(x)[:len(part)]
    long_rows = np.flatnonzero(d > _LMAX)
    if len(long_rows):
        nseg = -(-d[long_rows] // _LMAX)
        owner = np.repeat(np.arange(len(long_rows)), nseg)
        first = np.concatenate([[0], np.cumsum(nseg)[:-1]])
        within = np.arange(len(owner)) - first[owner]
        seg_start = ptr[row_ids[long_rows]][owner] + within * _LMAX
        seg_len = np.minimum(d[long_rows][owner] - within * _LMAX, _LMAX)
        a_all = np.zeros((len(long_rows), k, k), np.float32)
        b_all = np.zeros((len(long_rows), k), np.float32)
        cls = classes(seg_len)
        for length in np.unique(cls):
            for part, per in _blocks(np.flatnonzero(cls == length), length):
                a, b = jax.device_get(_gram_block(
                    _padded(seg_start[part], per, np.int32),
                    _padded(seg_len[part], per, np.int32),
                    cols, vals, src, length=int(length), precision=prec))
                np.add.at(a_all, owner[part], a[:len(part)])
                np.add.at(b_all, owner[part], b[:len(part)])
        out[long_rows] = np.asarray(_solve(
            jnp.asarray(a_all), jnp.asarray(b_all),
            jnp.asarray(reg[long_rows])))
    return out


def _sorted_side(rows_host: np.ndarray, n_rows: int, other, vals):
    order = jnp.argsort(jnp.asarray(rows_host), stable=True)
    deg = np.bincount(rows_host, minlength=n_rows)
    ptr = np.concatenate([[0], np.cumsum(deg)[:-1]]).astype(np.int64)
    return ptr, deg, other[order], vals[order]


@functools.partial(jax.jit, static_argnames=("chunk",))
def _sse_chunk(uf, vrows, users_sorted, vals_sorted, pos, slot, valid, *,
               chunk):
    del chunk
    pred = jnp.sum(uf[users_sorted[pos]] * vrows[slot], axis=-1)
    return jnp.sum(jnp.where(valid, (pred - vals_sorted[pos]) ** 2, 0.0))


def als_one_sweep(config: Dict[str, Any], init_seed: int, coo,
                  sample_items: np.ndarray, program_uv=None, *,
                  operand_dtype=None) -> Dict[str, Any]:
    """One ALS-WR sweep from the seeded initial factors: every user row
    from the initial item factors, then the ``sample_items`` rows from
    those user rows.  Also the squared error over the sampled items'
    ratings, of the reference's factors and (if given) the program's
    ``(U, V[sample_items])``."""
    import time

    clock = [time.perf_counter()]
    timing: Dict[str, float] = {}

    def lap(name):
        clock.append(time.perf_counter())
        timing[name] = clock[-1] - clock[-2]

    n_users, n_items = config["n_users"], config["n_items"]
    rank, lam = config["rank"], float(config["lambda"])
    users_h, items_h, stars_h = coo
    items_d = jnp.asarray(items_h)
    users_d = jnp.asarray(users_h)
    stars_d = jnp.asarray(stars_h)
    _, itf0 = als_init_factors(n_users, n_items, rank, init_seed)
    ptr, deg, cols, vals = _sorted_side(users_h, n_users, items_d, stars_d)
    jax.block_until_ready(cols)
    lap("sort_users_s")
    u_ref = solve_rows(np.arange(n_users), ptr, deg, cols, vals, itf0, lam,
                       operand_dtype=operand_dtype)
    del cols, vals
    lap("user_rows_s")
    ptr, deg, cols, vals = _sorted_side(items_h, n_items, users_d, stars_d)
    jax.block_until_ready(cols)
    lap("sort_items_s")
    u_ref_d = jnp.asarray(u_ref)
    v_ref = solve_rows(sample_items, ptr, deg, cols, vals, u_ref_d, lam,
                       operand_dtype=operand_dtype)
    lap("item_rows_s")
    # Squared error over the sampled items' ratings.
    d = deg[sample_items]
    pos = np.concatenate([np.arange(p, p + n) for p, n in
                          zip(ptr[sample_items], d)]).astype(np.int32)
    slot = np.repeat(np.arange(len(sample_items)), d).astype(np.int32)
    chunk = 1 << 22

    def sse(uf, vrows):
        total = 0.0
        uf, vrows = jnp.asarray(uf), jnp.asarray(vrows)
        for lo in range(0, len(pos), chunk):
            n = min(chunk, len(pos) - lo)
            p = np.zeros(chunk, np.int32)
            s = np.zeros(chunk, np.int32)
            p[:n], s[:n] = pos[lo:lo + n], slot[lo:lo + n]
            total += float(_sse_chunk(
                uf, vrows, cols, vals, jnp.asarray(p), jnp.asarray(s),
                jnp.arange(chunk) < n, chunk=chunk))
        return total

    out = {"u_ref": u_ref, "v_ref": v_ref, "n_sampled_ratings": len(pos),
           "sse_ref": sse(u_ref_d, v_ref)}
    if program_uv is not None:
        out["sse_program"] = sse(*program_uv)
    lap("sse_s")
    out["timing"] = timing
    return out
