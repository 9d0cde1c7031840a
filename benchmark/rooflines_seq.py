"""Operations and bytes of the sequence backbone, from the published
keys of the configuration file.  Useful work only: padded tokens, the
rows a grouped product masks and the second read of an expert whose rows
span two tiles are not credited."""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import datagen_seq


def params_per_token(config: Dict[str, Any]) -> float:
    """Parameters one token multiplies with: every held layer's mixer,
    the dense MLP or its top-k experts, and the tied head (the embedding
    row it reads is a gather, not a product)."""
    d = int(config["hidden_size"])
    kv = (d // int(config["num_attention_heads"])
          * int(config["num_key_value_heads"]))
    total = float(int(config["vocab_size"]) * d)
    for layer in datagen_seq.held_layers(config):
        if config["layer_types"][layer] == "conv":
            total += d * 3 * d + d * d + int(config["conv_L_cache"]) * d
        else:
            total += d * (d + 2 * kv) + d * d
        if datagen_seq.is_dense(config, layer):
            total += 3 * d * int(config["intermediate_size"])
        else:
            total += d * int(config["num_experts"]) \
                + int(config["num_experts_per_tok"]) * 3 * d \
                * int(config["moe_intermediate_size"])
    return total


def step_flops(config: Dict[str, Any], tokens: float, attended_keys: float
               ) -> float:
    """Flops of running ``tokens`` new events whose attention reads
    ``attended_keys`` (query, key) pairs in each attention layer: 2 per
    parameter and token, and q k^T and p v (2 x 2 x d) per pair."""
    n_attn = sum(config["layer_types"][layer] != "conv"
                 for layer in datagen_seq.held_layers(config))
    return (2.0 * params_per_token(config) * tokens
            + 4.0 * int(config["hidden_size"]) * attended_keys * n_attn)


def moe_counts(config: Dict[str, Any], assignments: float,
               experts_touched: float) -> Tuple[float, float]:
    """(flops, bytes) of the grouped expert products: three d x F
    products per assignment; the weights of each expert picked at least
    once read once (bfloat16); per assignment the token's row read, the
    gate-and-up row written (float32) and read back (bfloat16), the
    result written (float32)."""
    d, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    flops = 2.0 * 3 * d * f * assignments
    nbytes = (experts_touched * 3 * d * f * 2
              + assignments * (d * 2 + 2 * f * 4 + f * 2 + d * 4))
    return flops, float(nbytes)
