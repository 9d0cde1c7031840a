"""Weights of the Mamba / sliding-window / shared-cache cells, from
``--seed``.  Shared by the system the harness builds and by the plain
reference, which makes its own copy from the seed, one layer at a time,
and takes nothing the program has touched.  Reads the published keys of
the configuration file and its ``assumed`` sizes; imports nothing of the
program.  Histories, turns and items are ``datagen_seq``'s
(``history_lengths``, ``Events``).

The layer pattern follows from the published depth ``L`` (``kinds``):
even layers up to ``L / 2`` are Mamba, odd layers below ``L / 2``
sliding-window attention, layer ``L / 2 + 1`` the full-attention layer
whose keys and values are the shared cache, the odd layers after it
cross-attention on that cache (``W_q`` only), the even ones gated memory
units.  Where the equations split a product the factors are column
blocks of one matrix, in the order the equations name them: ``w_in`` =
[x | z], ``w_x`` = [r | B | C], ``w_qkv`` = [q | k | v], ``w13`` = [g |
v].  Products are normal / sqrt(fan-in) in bfloat16; norm gains 1 + 0.1
normal, every bias and the lambda vectors 0.1 normal, the convolution's
taps normal / sqrt(4), float32; Mamba's published initial values: ``A_log
= log(1 .. N)`` (stored [N, E]), ``D = 1``, ``b_dt`` the inverse softplus
of a log-uniform step in [1e-3, 1e-1].
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.datagen import seed_key
from benchmark.datagen_seq import _slabs

MAMBA, WINDOW, FULL, CROSS, GMU = "mamba", "window", "full", "cross", "gmu"


def kinds(config: Dict[str, Any]) -> List[str]:
    """The mixer of each published layer."""
    n = int(config["num_hidden_layers"])
    half = n // 2
    return [(MAMBA if layer <= half else GMU) if layer % 2 == 0 else
            (WINDOW if layer < half else FULL if layer == half + 1
             else CROSS) for layer in range(n)]


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """d, f, E, N, R, conv width, heads, kv heads, head size."""
    a = config["assumed"]
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    return {"d": d, "f": int(config["intermediate_size"]),
            "e": int(a["expand"]) * d, "n": int(a["d_state"]),
            "r": int(a["dt_rank"]), "w": int(a["d_conv"]), "heads": heads,
            "kv": int(config["num_key_value_heads"]), "hd": d // heads}


def layer_shapes(config: Dict[str, Any], layer: int
                 ) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of published layer ``layer``'s weights."""
    s = sizes(config)
    d, f, e, n, hd = s["d"], s["f"], s["e"], s["n"], s["hd"]
    qw, kvw = s["heads"] * hd, 2 * s["kv"] * hd
    out: Dict[str, Tuple[int, ...]] = {
        "norm1_g": (d,), "norm1_b": (d,), "norm2_g": (d,), "norm2_b": (d,),
        "w13": (d, 2 * f), "w2": (f, d)}
    kind = kinds(config)[layer]
    if kind == MAMBA:
        out.update(w_in=(d, 2 * e), conv_w=(s["w"], e), conv_b=(e,),
                   w_x=(e, s["r"] + 2 * n), w_dt=(s["r"], e), dt_b=(e,),
                   a_log=(n, e), d_skip=(e,), w_out=(e, d))
    elif kind == GMU:
        out.update(w_in=(d, e), w_out=(e, d))
    else:
        if kind == CROSS:
            out.update(w_q=(d, qw), b_q=(qw,))
        else:
            out.update(w_qkv=(d, qw + kvw), b_qkv=(qw + kvw,))
        out.update(w_o=(qw, d), b_o=(d,), lam=(4, hd), sub_g=(2 * hd,))
    return out


def _weight(key, name: str, shape: Tuple[int, ...]):
    if name == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
    if name == "d_skip":
        return jnp.ones(shape, jnp.float32)
    if name == "dt_b":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if len(shape) == 2 and name not in ("conv_w", "lam"):
        return _slabs(key, shape=tuple(shape),
                      scale=1.0 / math.sqrt(shape[0]), dtype=jnp.bfloat16)
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_g"):
        return 1.0 + 0.1 * x
    if name == "conv_w":
        return x / math.sqrt(shape[0])
    return 0.1 * x


def layer_weights(config: Dict[str, Any], seed: int, layer: int
                  ) -> Dict[str, jax.Array]:
    """Published layer ``layer``'s weights, on the device."""
    base = jax.random.fold_in(seed_key(seed, 51), layer)
    return {name: _weight(jax.random.fold_in(base, i), name, shape)
            for i, (name, shape) in enumerate(sorted(
                layer_shapes(config, layer).items()))}


def embedding(config: Dict[str, Any], seed: int) -> jax.Array:
    """The tied embedding / head [V, d]: normal / sqrt(d), bfloat16."""
    d = int(config["hidden_size"])
    return _slabs(seed_key(seed, 52), shape=(int(config["vocab_size"]), d),
                  scale=1.0 / math.sqrt(d), dtype=jnp.bfloat16)


def final_norm(config: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    d = (int(config["hidden_size"]),)
    return {"final_g": _weight(seed_key(seed, 53), "final_g", d),
            "final_b": _weight(seed_key(seed, 54), "final_b", d)}
