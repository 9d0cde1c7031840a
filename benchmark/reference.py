"""The plain reference of the serving cells: exact top-k over the seeded
corpus in straightforward ``jax.numpy`` at float32 ``highest``, with no
kernel, cache or batching, importing nothing of the program and taking
nothing it made.  The factors are re-made from the seed (``datagen``).

``precision`` / ``operand_dtype`` exist for the CONTROL: the reference
put in the program's place one precision step below what the
configuration states (``high`` for the float32-``highest`` scores),
which ``compare`` must refuse.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import datagen

NEG = -3.0e38


@functools.partial(jax.jit, static_argnames=("rows", "dim", "k",
                                             "precision", "operand_dtype"))
def _topk_block(key, block, q, best_s, best_i, served, *, rows, dim, k,
                precision, operand_dtype=None):
    items = datagen.factor_block(key, block, rows, dim)
    if operand_dtype is not None:
        items = items.astype(operand_dtype).astype(jnp.float32)
        q = q.astype(operand_dtype).astype(jnp.float32)
    s = jax.lax.dot_general(q, items, (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32)
    start = block * rows
    bs, bi = jax.lax.top_k(s, k)
    cat_s = jnp.concatenate([best_s, bs], axis=1)
    cat_i = jnp.concatenate([best_i, bi + start], axis=1)
    top_s, pos = jax.lax.top_k(cat_s, k)
    top_i = jnp.take_along_axis(cat_i, pos, axis=1)
    local = served - start
    here = (local >= 0) & (local < rows)
    got = jnp.take_along_axis(s, jnp.clip(local, 0, rows - 1), axis=1)
    return top_s, top_i, jnp.where(here, got, NEG)


def topk(config: Dict[str, Any], seed: int, user_idx: np.ndarray, k: int,
         served_ids: Optional[np.ndarray] = None, *,
         precision: str = "highest", operand_dtype=None
         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Exact top-``k`` of each listed user over the seeded corpus, one
    block of items at a time: ([S,k] scores, [S,k] ids, [S,W] the
    reference's score of each id in ``served_ids``)."""
    rank, rows = config["rank"], config["factor_block_rows"]
    n_items = config["n_items"]
    ukey, ikey = datagen.seed_key(seed, 2), datagen.seed_key(seed, 1)
    user_idx = np.asarray(user_idx)
    # Query rows: re-make only the blocks the sampled users live in.
    q = np.empty((len(user_idx), rank), np.float32)
    for b in np.unique(user_idx // rows):
        blk = np.asarray(datagen.factor_block(ukey, int(b), rows, rank))
        sel = user_idx // rows == b
        q[sel] = blk[user_idx[sel] % rows]
    q = jnp.asarray(q)
    s_n = len(user_idx)
    if served_ids is None:
        served_ids = np.zeros((s_n, 1), np.int32)
    served = jnp.asarray(served_ids, jnp.int32)
    best_s = jnp.full((s_n, k), NEG, jnp.float32)
    best_i = jnp.zeros((s_n, k), jnp.int32)
    at_served = jnp.full(served.shape, NEG, jnp.float32)
    prec = jax.lax.Precision(precision)
    for b in range(n_items // rows):
        best_s, best_i, got = _topk_block(
            ikey, b, q, best_s, best_i, served, rows=rows, dim=rank, k=k,
            precision=prec, operand_dtype=operand_dtype)
        at_served = jnp.maximum(at_served, got)
    return tuple(np.asarray(a) for a in jax.device_get(
        (best_s, best_i, at_served)))
