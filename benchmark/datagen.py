"""Weights from ``--seed``, made on the device in one jitted call.
Shared by the systems the harness builds and by the plain reference,
which makes its own copy from the seed and takes nothing the program has
touched.

Factors are drawn block by block (block b of a matrix from
``fold_in(key, b)``), so the reference can re-make any block alone
without holding the whole matrix.  (Ratings: ``benchmark/ratings.py``.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int) -> jax.Array:
    """A threefry key from any whole number (the driver's seeds pass
    2**31, which ``jax.random.PRNGKey`` takes only with x64 on)."""
    seed = int(seed)
    data = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    dtype=np.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(data), stream)


def factor_block(key: jax.Array, block: jax.Array, rows: int, dim: int
                 ) -> jax.Array:
    """Rows [block*rows, (block+1)*rows) of a seeded normal/sqrt(dim)
    factor matrix, float32."""
    k = jax.random.fold_in(key, block)
    return jax.random.normal(k, (rows, dim), jnp.float32) / np.sqrt(
        np.float32(dim))


@functools.partial(jax.jit, static_argnames=("n_rows", "dim", "block_rows"))
def make_factors(key: jax.Array, *, n_rows: int, dim: int, block_rows: int
                 ) -> jax.Array:
    """The whole [n_rows, dim] matrix on the device, filled in place one
    block at a time (peak = the matrix + one block)."""
    if n_rows % block_rows:
        raise ValueError(f"{n_rows} rows do not divide into blocks of "
                         f"{block_rows}")

    def body(b, buf):
        return jax.lax.dynamic_update_slice(
            buf, factor_block(key, b, block_rows, dim), (b * block_rows, 0))

    return jax.lax.fori_loop(0, n_rows // block_rows, body,
                             jnp.zeros((n_rows, dim), jnp.float32))
