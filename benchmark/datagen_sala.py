"""Weights of the block-selected / lightning cells, from ``--seed``.
Shared by the system the harness builds and by the plain reference, which
makes its own copy from the seed, one layer at a time, and takes nothing
the program has touched.  Reads the published keys of the configuration
file; imports nothing of the program.  Histories, turns and items are
``datagen_seq``'s (``history_lengths``, ``Events``).

A layer is named by its PUBLISHED index (``held_layers`` indexes the
published ``mixer_types``).  Where the equations split a product the
factors are column blocks of one matrix, in the order the equations name
them: ``w_qkv`` = [q | k | v], ``w13`` = [W_gate | W_up].  Products are
normal / sqrt(fan-in) in bfloat16; norm weights 1 + 0.1 normal, float32,
the q and k gains of a ``minicpm4`` layer times ``assumed.qk_gain``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.datagen import seed_key
from benchmark.datagen_seq import _slabs

LIGHTNING = "lightning-attn"


def held_layers(config: Dict[str, Any]) -> List[int]:
    return [int(i) for i in config["held_layers"]]


def layer_shapes(config: Dict[str, Any], layer: int
                 ) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of published layer ``layer``'s weights."""
    d, f = int(config["hidden_size"]), int(config["intermediate_size"])
    out: Dict[str, Tuple[int, ...]] = {
        "op_norm": (d,), "ffn_norm": (d,), "w13": (d, 2 * f), "w2": (f, d)}
    if config["mixer_types"][layer] == LIGHTNING:
        hd = int(config["lightning_head_dim"])
        w = int(config["lightning_nh"]) * hd
        out.update(w_qkv=(d, 3 * w), o_norm=(w,))
    else:
        hd = int(config["head_dim"])
        w = int(config["num_attention_heads"]) * hd
        out.update(w_qkv=(d, w + 2 * int(config["num_key_value_heads"]) * hd))
    out.update(q_norm=(hd,), k_norm=(hd,), w_z=(d, w), w_o=(w, d))
    return out


def _weight(key, name: str, shape: Tuple[int, ...], gain: float = 1.0):
    if name.endswith("_norm"):
        return gain * (1.0 + 0.1 * jax.random.normal(key, shape,
                                                     jnp.float32))
    return _slabs(key, shape=tuple(shape), scale=1.0 / math.sqrt(shape[0]),
                  dtype=jnp.bfloat16)


def layer_weights(config: Dict[str, Any], seed: int, layer: int
                  ) -> Dict[str, jax.Array]:
    """Published layer ``layer``'s weights, on the device."""
    base = jax.random.fold_in(seed_key(seed, 41), layer)
    sparse = config["mixer_types"][layer] != LIGHTNING
    gain = float(config["assumed"]["qk_gain"]) if sparse else 1.0
    return {name: _weight(jax.random.fold_in(base, i), name, shape,
                          gain if name in ("q_norm", "k_norm") else 1.0)
            for i, (name, shape) in enumerate(sorted(
                layer_shapes(config, layer).items()))}


def vocab_matrix(config: Dict[str, Any], seed: int, which: str
                 ) -> jax.Array:
    """The input embedding (``embed``) or the untied head (``head``)
    [V, d]: normal / sqrt(d), bfloat16."""
    d = int(config["hidden_size"])
    return _slabs(seed_key(seed, {"embed": 42, "head": 43}[which]),
                  shape=(int(config["vocab_size"]), d),
                  scale=1.0 / math.sqrt(d), dtype=jnp.bfloat16)


def final_norm(config: Dict[str, Any], seed: int) -> jax.Array:
    return _weight(seed_key(seed, 44), "final_norm",
                   (int(config["hidden_size"]),))
