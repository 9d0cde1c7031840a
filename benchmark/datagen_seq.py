"""Weights, histories and turns of the sequence cells, from ``--seed``.
Shared by the system the harness builds and by the plain reference,
which makes its own copy from the seed, one layer at a time, and takes
nothing the program has touched.  Everything here reads the published
keys of the configuration file; it imports nothing of the program.

Weights: a layer is named by its PUBLISHED index (``held_layers``
indexes the published ``layer_types``).  Where the equations split a
product, the factors are column blocks of one matrix, in the order the
equations name them: ``w_in`` = [B | C | X], ``w_qkv`` = [q | k | v],
``w13`` = [W1 | W3].  Products are normal / sqrt(fan-in) in bfloat16,
drawn a slab at a time (an expert, a block of rows) so the float32
draw never holds a whole matrix; norm weights 1 + 0.1 normal, the
router normal / sqrt(d) and the experts' bias 0.05 normal, float32.

Events: user ``u``'s ``k``-th event is the ``k``-th draw of the user's
own stream, history and turns alike, so a user's whole sequence up to
any turn is the stream's first ``count`` draws.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import traffic
from benchmark.datagen import seed_key

_SLAB_ROWS = 8192


def held_layers(config: Dict[str, Any]) -> List[int]:
    return [int(i) for i in config.get(
        "held_layers", range(int(config["num_hidden_layers"])))]


def is_dense(config: Dict[str, Any], layer: int) -> bool:
    return layer < int(config["num_dense_layers"])


def layer_shapes(config: Dict[str, Any], layer: int
                 ) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of published layer ``layer``'s weights."""
    d = int(config["hidden_size"])
    hd = d // int(config["num_attention_heads"])
    kv = int(config["num_key_value_heads"]) * hd
    out: Dict[str, Tuple[int, ...]] = {"op_norm": (d,), "ffn_norm": (d,)}
    if config["layer_types"][layer] == "conv":
        out.update(w_in=(d, 3 * d), conv_w=(int(config["conv_L_cache"]), d),
                   w_out=(d, d))
    else:
        out.update(w_qkv=(d, d + 2 * kv), q_norm=(hd,), k_norm=(hd,),
                   w_o=(d, d))
    if is_dense(config, layer):
        f = int(config["intermediate_size"])
        out.update(w13=(d, 2 * f), w2=(f, d))
    else:
        e, f = int(config["num_experts"]), int(config["moe_intermediate_size"])
        out.update(w_g=(d, e), b=(e,), w13=(e, d, 2 * f), w2=(e, f, d))
    return out


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _slabs(key, *, shape, scale, dtype):
    """normal * scale of ``shape`` in ``dtype``, a slab of the leading
    axis at a time (slab ``i`` from ``fold_in(key, i)``)."""
    lead = shape[0]
    step = 1 if len(shape) == 3 else min(lead, _SLAB_ROWS)
    if lead % step:
        step = math.gcd(lead, step)
    slab = (step,) + tuple(shape[1:])

    def body(i, buf):
        x = jax.random.normal(jax.random.fold_in(key, i), slab, jnp.float32)
        return jax.lax.dynamic_update_slice(
            buf, (x * scale).astype(dtype), (i * step,) + (0,) * (len(shape)
                                                                  - 1))

    return jax.lax.fori_loop(0, lead // step, body, jnp.zeros(shape, dtype))


def _weight(key, name: str, shape: Tuple[int, ...]):
    if name.endswith("_norm"):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name == "b":
        return 0.05 * jax.random.normal(key, shape, jnp.float32)
    if name == "w_g":
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(
            shape[0])
    fan_in = shape[0] if name == "conv_w" else shape[-2]
    return _slabs(key, shape=tuple(shape), scale=1.0 / math.sqrt(fan_in),
                  dtype=jnp.bfloat16)


def layer_weights(config: Dict[str, Any], seed: int, layer: int
                  ) -> Dict[str, jax.Array]:
    """Published layer ``layer``'s weights, on the device."""
    base = jax.random.fold_in(seed_key(seed, 11), layer)
    return {name: _weight(jax.random.fold_in(base, i), name, shape)
            for i, (name, shape) in enumerate(sorted(
                layer_shapes(config, layer).items()))}


def embedding(config: Dict[str, Any], seed: int) -> jax.Array:
    """The tied embedding / head [V, d]: normal / sqrt(d), bfloat16."""
    d = int(config["hidden_size"])
    return _slabs(seed_key(seed, 12), shape=(int(config["vocab_size"]), d),
                  scale=1.0 / math.sqrt(d), dtype=jnp.bfloat16)


def final_norm(config: Dict[str, Any], seed: int) -> jax.Array:
    return _weight(seed_key(seed, 13), "final_norm",
                   (int(config["hidden_size"]),))


# -- histories and turns -----------------------------------------------------

def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a log-normal, rounded and clipped: one
    multiset for every seed."""
    from statistics import NormalDist

    q = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(
        np.int64)


def history_lengths(config: Dict[str, Any], seed: int) -> np.ndarray:
    """Events each resident user has before the window: the fixed
    multiset, dealt to the users by the seed."""
    h = config["history"]
    lengths = lognormal_quantiles(int(config["n_users"]), h["median"],
                                  h["sigma"], h["min"], h["max"])
    traffic.rng_for(seed, 21).shuffle(lengths)
    return lengths


def _zipf_cdf(config: Dict[str, Any]) -> np.ndarray:
    w = np.arange(1, int(config["vocab_size"]) + 1, dtype=np.float64) \
        ** -float(config.get("item_zipf_s", 1.0))
    return np.cumsum(w / w.sum())


def item_labels(config: Dict[str, Any], seed: int) -> np.ndarray:
    """Popularity rank -> item id, a seeded relabelling."""
    return traffic.rng_for(seed, 22).permutation(int(config["vocab_size"]))


class Events:
    """The item streams of one seed: ``of(user, count)`` is the first
    ``count`` item ids of the user's stream (ranks drawn Zipf, then the
    seeded relabelling)."""

    def __init__(self, config: Dict[str, Any], seed: int):
        self.seed = seed
        self.cdf = _zipf_cdf(config)
        self.labels = item_labels(config, seed)

    def of(self, user: int, count: int) -> np.ndarray:
        u = traffic.rng_for(self.seed, 1000 + int(user)).random(int(count))
        ranks = np.minimum(np.searchsorted(self.cdf, u), len(self.cdf) - 1)
        return self.labels[ranks].astype(np.int32)
