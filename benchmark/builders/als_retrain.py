"""``prepare_als_inputs`` + ``train_als_prepared`` over seeded ratings
at the configuration's shape (``benchmark/ratings.py``): one compiled
ALS loop over prepared inputs; ``sweep_call`` is what
``ALSAlgorithm.train`` reaches through ``train_als``."""

from __future__ import annotations

import time
from typing import Any, Callable, Dict

import jax
import numpy as np

from benchmark import ratings


def host_coo(config: Dict[str, Any], seed: int):
    """What a train gets from the store: the seeded COO on the host.
    The generator's device copies die here; prep uploads its own."""
    return tuple(np.asarray(a) for a in
                 jax.device_get(ratings.ratings_coo(seed, config)))


class RetrainSystem:

    def __init__(self, config: Dict[str, Any], seed: int,
                 split: Dict[str, float]):
        from predictionio_tpu.models.als import (
            ALSConfig, prepare_als_inputs,
        )

        self.config = config
        self.n_ratings = int(config["n_ratings"])
        t0 = time.perf_counter()
        self.coo = host_coo(config, seed)
        split["data_s"] = time.perf_counter() - t0
        self.init_seed = int(seed) % (2 ** 31 - 1)
        self.als_config: Callable[[int], Any] = lambda sweeps: ALSConfig(
            rank=config["rank"], iterations=sweeps, reg=config["lambda"],
            seed=self.init_seed)
        t0 = time.perf_counter()
        users, items, stars = self.coo
        self.inputs = prepare_als_inputs(
            users, items, stars, config["n_users"], config["n_items"],
            self.als_config(1))
        jax.block_until_ready(
            (self.inputs.uf0, self.inputs.itf0,
             [b[1:] for b in self.inputs.user_buckets],
             [b[1:] for b in self.inputs.item_buckets]))
        split["train_prep_s"] = time.perf_counter() - t0

    def sweep_call(self, sweeps: int):
        """``sweeps`` ALS sweeps from the seeded initial factors, to
        completion; returns the program's model."""
        from predictionio_tpu.models.als import train_als_prepared

        model = train_als_prepared(self.inputs, self.als_config(sweeps))
        jax.block_until_ready((model.user_factors, model.item_factors))
        return model

    def free(self) -> None:
        self.inputs = None


def build(config: Dict[str, Any], seed: int, split: Dict[str, float]):
    return RetrainSystem(config, seed, split)


def control(config: Dict[str, Any], seed: int, operand_dtype=None
            ) -> Dict[str, float]:
    """The reference in the program's place with the factor rows it
    gathers in float8 where the configuration states bfloat16, compared
    as a run's factors are."""
    import jax.numpy as jnp

    from benchmark import compare_als, reference_als

    if operand_dtype is None:
        operand_dtype = jnp.float8_e4m3fn
    init_seed = int(seed) % (2 ** 31 - 1)
    coo = host_coo(config, seed)
    items = compare_als.sample_items(config, seed, coo)
    low = reference_als.als_one_sweep(config, init_seed, coo, items,
                                      operand_dtype=operand_dtype)
    return compare_als.training_numbers(config, init_seed, coo, items,
                                        low["u_ref"], low["v_ref"])
