"""Weights of the Mamba-2 / no-position attention cells, from ``--seed``.
Shared by the system the harness builds and by the plain reference, which
makes its own copy from the seed, one layer at a time, and takes nothing
the program has touched.  Reads the published keys of the configuration
file; imports nothing of the program.  Histories, turns and items are
``datagen_seq``'s (``history_lengths``, ``Events``).

A layer's mixer is the published ``layer_types`` entry.  Where the
equations split a product the factors are column blocks of one matrix,
in the order the equations name them: ``w_in`` = [z | xBC | dt], ``xBC``
= [x | B | C], ``w_qkv`` = [q | k | v], ``w13`` = [a | b].  Products are
normal / sqrt(fan-in) in bfloat16; norm gains 1 + 0.1 normal, the
convolution's bias 0.1 normal and its taps normal / sqrt(4), float32;
the query and key columns of ``w_qkv`` times ``assumed.qk_gain`` (at
unit gain the published 1 / 64 scale gives scores of spread 1 / 8: a
uniform average over thousands of events, which no comparison could tell
from no attention at all; a trained model's attention is peaked);
Mamba-2's published initial values: ``A`` uniform in 1 .. 16 a head
(stored as ``A_log``), ``D = 1``, ``dt_bias`` the inverse softplus of a
log-uniform step in [1e-3, 1e-1].
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark.datagen import seed_key
from benchmark.datagen_seq import _slabs

MAMBA, ATTENTION = "mamba", "attention"


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """d, f, E, N, H, P, conv width, heads, kv heads, head size."""
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    return {"d": d, "f": int(config["shared_intermediate_size"]),
            "e": int(config["mamba_expand"]) * d,
            "n": int(config["mamba_d_state"]),
            "h": int(config["mamba_n_heads"]),
            "p": int(config["mamba_d_head"]),
            "w": int(config["mamba_d_conv"]), "heads": heads,
            "kv": int(config["num_key_value_heads"]), "hd": d // heads}


def layer_shapes(config: Dict[str, Any], layer: int
                 ) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of published layer ``layer``'s weights."""
    s = sizes(config)
    d, f, e = s["d"], s["f"], s["e"]
    out: Dict[str, Tuple[int, ...]] = {
        "mixer_norm": (d,), "ffn_norm": (d,), "w13": (d, 2 * f),
        "w2": (f, d)}
    if config["layer_types"][layer] == MAMBA:
        cw = e + 2 * s["n"]
        out.update(w_in=(d, e + cw + s["h"]), conv_w=(s["w"], cw),
                   conv_b=(cw,), dt_b=(s["h"],), a_log=(s["h"],),
                   d_skip=(s["h"],), gate_norm=(e,), w_out=(e, d))
    else:
        qw = s["heads"] * s["hd"]
        out.update(w_qkv=(d, qw + 2 * s["kv"] * s["hd"]), w_o=(qw, d))
    return out


def _weight(key, name: str, shape: Tuple[int, ...]):
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0))
    if name == "d_skip":
        return jnp.ones(shape, jnp.float32)
    if name == "dt_b":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if len(shape) == 2 and name != "conv_w":
        return _slabs(key, shape=tuple(shape),
                      scale=1.0 / math.sqrt(shape[0]), dtype=jnp.bfloat16)
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_norm"):
        return 1.0 + 0.1 * x
    if name == "conv_w":
        return x / math.sqrt(shape[0])
    return 0.1 * x


def layer_weights(config: Dict[str, Any], seed: int, layer: int
                  ) -> Dict[str, jax.Array]:
    """Published layer ``layer``'s weights, on the device."""
    base = jax.random.fold_in(seed_key(seed, 61), layer)
    out = {name: _weight(jax.random.fold_in(base, i), name, shape)
           for i, (name, shape) in enumerate(sorted(
               layer_shapes(config, layer).items()))}
    if "w_qkv" in out:
        s = sizes(config)
        qk = (s["heads"] + s["kv"]) * s["hd"]
        gain = jnp.where(jnp.arange(out["w_qkv"].shape[1]) < qk,
                         float(config["assumed"]["qk_gain"]), 1.0)
        out["w_qkv"] = (out["w_qkv"] * gain).astype(jnp.bfloat16)
    return out


def embedding(config: Dict[str, Any], seed: int) -> jax.Array:
    """The tied embedding / head [V, d]: normal / sqrt(d), bfloat16."""
    d = int(config["hidden_size"])
    return _slabs(seed_key(seed, 62), shape=(int(config["vocab_size"]), d),
                  scale=1.0 / math.sqrt(d), dtype=jnp.bfloat16)


def final_norm(config: Dict[str, Any], seed: int) -> jax.Array:
    return _weight(seed_key(seed, 63), "final_norm",
                   (int(config["hidden_size"]),))
