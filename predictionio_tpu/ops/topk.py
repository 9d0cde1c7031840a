"""Dot-product top-K retrieval — the serving hot path.

Reference behavior: predict = user-factor · item-factorsᵀ, top-K (MLlib
ALS `recommendProducts`, SURVEY.md §2.2).  TPU shape: one [B, K] × [K, N]
matmul (MXU) + `jax.lax.top_k`; for sharded item factors each shard computes
a local top-K and the K·shards candidates are reduced — O(N/shards) memory
per device and a tiny all-gather instead of gathering N scores.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["top_k_scores", "chunked_top_k", "sharded_top_k", "host_top_k"]

NEG_INF = jnp.float32(-3.4e38)
# Scores the exact rungs return are float32 dot products.  XLA:TPU's
# default rounds f32 matmul operands to bfloat16 whenever the batch is
# wide enough for the MXU: measured on a v5e at B=64 that moved scores by
# 1.6e-3 relative and changed the top-10 of 3 queries in 64 against the
# host numpy rung, while B=1 stayed exact.  HIGHEST costs ~2% on these
# memory-bound shapes (PERF.md, PR 21) and makes every batch size agree.
SCORE_PRECISION = jax.lax.Precision.HIGHEST


@partial(jax.jit, static_argnames=("k",))
def top_k_scores(
    queries: jax.Array,   # [B, K] float
    items: jax.Array,     # [N, K] float
    k: int,
    *,
    exclude: Optional[jax.Array] = None,  # [B, N] bool — True = mask out
    biases: Optional[jax.Array] = None,   # [N] additive item biases
) -> Tuple[jax.Array, jax.Array]:
    """Scores+ids of the top-k items per query. Returns ([B,k], [B,k] int32).

    Jitted (k static): the serving hot path must be ONE dispatch, not
    eager op-by-op — every eager op is its own dispatch.
    """
    scores = jnp.einsum(
        "bk,nk->bn", queries, items, precision=SCORE_PRECISION,
        preferred_element_type=jnp.float32
    )
    if biases is not None:
        scores = scores + biases[None, :]
    if exclude is not None:
        scores = jnp.where(exclude, NEG_INF, scores)
    return jax.lax.top_k(scores, k)


def chunked_top_k(
    queries: jax.Array,
    items: jax.Array,
    k: int,
    *,
    chunk: int = 8192,
    biases: Optional[jax.Array] = None,
    exclude: Optional[jax.Array] = None,  # [B, N] bool — True = mask out
    n_valid: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k with bounded [B, chunk] score materialization.

    `lax.scan` over item chunks keeps HBM flat for huge catalogs: each step
    scores one chunk and merges with the running top-k.  Any catalog size
    works — the tail chunk reads a clamped (overlapping) window via
    ``dynamic_slice`` and masks the rows it re-reads, so callers no longer
    pad the corpus to a chunk multiple (and no padded copy is ever
    materialized).  ``n_valid`` additionally masks trailing padding rows a
    blocked/sharded model carries; ``exclude`` is the per-query mask of
    :func:`top_k_scores`, sliced chunk-by-chunk.
    """
    n, dim = items.shape
    b = queries.shape[0]
    limit = n if n_valid is None else min(n_valid, n)
    if n <= chunk:
        # Single-dispatch small corpus: fold the n_valid tail mask into
        # exclude and take the one-matmul path.
        excl = exclude
        if limit < n:
            pad_rows = jnp.broadcast_to(
                (jnp.arange(n, dtype=jnp.int32) >= limit)[None, :], (b, n))
            excl = pad_rows if excl is None else (excl | pad_rows)
        return top_k_scores(queries, items, k, exclude=excl, biases=biases)
    steps = -(-n // chunk)
    init = (
        jnp.full((b, k), NEG_INF, dtype=jnp.float32),
        jnp.zeros((b, k), dtype=jnp.int32),
    )

    def step(carry, nominal):
        best_s, best_i = carry
        # The tail chunk's window clamps to [n - chunk, n): rows below the
        # nominal boundary were already scored by the previous chunk and
        # are masked out below — static shapes, no recompile per catalog
        # size, no duplicate candidates.
        start = jnp.minimum(nominal, n - chunk)
        tile = jax.lax.dynamic_slice(items, (start, 0), (chunk, dim))
        s = jnp.einsum("bk,nk->bn", queries, tile,
                       precision=SCORE_PRECISION,
                       preferred_element_type=jnp.float32)
        ids = start + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        if biases is not None:
            s = s + jax.lax.dynamic_slice(biases, (start,), (chunk,))[None, :]
        invalid = (ids < nominal) | (ids >= limit)
        if exclude is not None:
            invalid = invalid | jax.lax.dynamic_slice(
                exclude, (0, start), (b, chunk))
        s = jnp.where(invalid, NEG_INF, s)
        merged_s = jnp.concatenate([best_s, s], axis=1)
        merged_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, s.shape)], axis=1)
        top_s, pos = jax.lax.top_k(merged_s, k)
        top_i = jnp.take_along_axis(merged_i, pos, axis=1)
        return (top_s, top_i), None

    starts = (jnp.arange(steps, dtype=jnp.int32) * chunk)
    (best_s, best_i), _ = jax.lax.scan(step, init, starts)
    return best_s, best_i


def sharded_top_k(
    mesh: Mesh,
    axis: str,
    queries: jax.Array,   # [B, K] replicated
    items: jax.Array,     # [N, K] sharded on `axis` along dim 0
    k: int,
    n_valid: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k over item factors row-sharded on a mesh axis.

    Each shard scores its N/shards slice and takes a local top-k; the
    k·shards candidates are all-gathered (tiny) and reduced — the ICI
    traffic is O(k·shards·B), never O(N·B).  ``n_valid`` masks the
    mesh-padding rows a blocked model carries at the tail (they are
    zero vectors and would outrank genuinely negative scores).
    """
    n = items.shape[0]
    n_shards = mesh.shape[axis]
    assert n % n_shards == 0, f"pad catalog ({n}) to a multiple of {n_shards}"
    per = n // n_shards

    def local(q, it):  # it: [N/shards, K]
        shard = jax.lax.axis_index(axis)
        excl = None
        if n_valid is not None and n_valid < n:
            gid = shard * per + jnp.arange(per, dtype=jnp.int32)
            excl = jnp.broadcast_to(gid[None, :] >= n_valid,
                                    (q.shape[0], per))
        s, i = top_k_scores(q, it, min(k, per), exclude=excl)
        i = i + shard * per
        # Gather every shard's candidates, then reduce to the global top-k.
        all_s = jax.lax.all_gather(s, axis, axis=1).reshape(q.shape[0], -1)
        all_i = jax.lax.all_gather(i, axis, axis=1).reshape(q.shape[0], -1)
        top_s, pos = jax.lax.top_k(all_s, k)
        return top_s, jnp.take_along_axis(all_i, pos, axis=1)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axis)),
        out_specs=(P(), P()),
        # Outputs ARE replicated (identical post-all_gather reduction on every
        # shard) but the static varying-axes check can't prove it.
        check_vma=False,
    )
    return fn(queries, items)


def host_top_k(
    queries,              # np [B, K]
    items,                # np [N, K]
    k: int,
    *,
    exclude=None,         # np [B, N] bool — True = mask out
    biases=None,          # np [N]
):
    """Numpy top-k for the host-resident serving fast path.

    A B=1 predict over even ML-25M-scale item factors is ~4M MACs — far
    below the cost of one device dispatch round-trip.
    Serving keeps a host copy of the factors and answers small batches
    here; large batches still go to the device (ops.topk.top_k_scores).
    Returns ([B, k], [B, k] int32) sorted descending like lax.top_k.
    """
    import numpy as np

    if k <= 0:  # lax.top_k parity: k=0 → empty, never the whole catalog
        return (np.empty((queries.shape[0], 0), np.float32),
                np.empty((queries.shape[0], 0), np.int32))
    scores = queries @ items.T                      # [B, N]
    if biases is not None:
        scores = scores + biases[None, :]
    if exclude is not None:
        scores = np.where(exclude, -3.4e38, scores)
    n = scores.shape[1]
    k = min(k, n)
    if k < n:
        part = np.argpartition(scores, -k, axis=1)[:, -k:]
    else:
        part = np.broadcast_to(np.arange(n), scores.shape).copy()
    part_scores = np.take_along_axis(scores, part, axis=1)
    order = np.argsort(-part_scores, axis=1, kind="stable")
    ids = np.take_along_axis(part, order, axis=1).astype(np.int32)
    return np.take_along_axis(part_scores, order, axis=1), ids
