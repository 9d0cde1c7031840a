"""The sequence engine's output head: a turn's last hidden rows against
the item table in bfloat16, and the best ``k`` of each row."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["top_k_head"]


def top_k_head(h: jax.Array, table: jax.Array, k: int
               ) -> Tuple[jax.Array, jax.Array]:
    """``h`` [R, d] (normed and scaled) against ``table`` [V, d] ->
    (scores [R, k] float32, item ids [R, k]): a bfloat16 product with
    float32 accumulation under the scope ``seq_head``."""
    with jax.named_scope("seq_head"):
        logits = jax.lax.dot_general(
            h.astype(jnp.bfloat16), table, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return jax.lax.top_k(logits, k)
