"""Operations and bytes of the block-selected / lightning backbone, from
the published keys of the configuration file and the program's counters.
Useful work only, the same whatever implements it: padded tokens and
tiles, a page read for one of its two blocks, and a block read again by
a user's second tile are not credited."""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import datagen_sala


def layers_of(config: Dict[str, Any], lightning: bool) -> int:
    """Held layers of the one kind or the other."""
    return sum((config["mixer_types"][layer] == datagen_sala.LIGHTNING)
               == lightning for layer in datagen_sala.held_layers(config))


def params_per_token(config: Dict[str, Any]) -> float:
    """Parameters one token multiplies with: every held layer's matrices
    and the untied head (the embedding row it reads is a gather)."""
    total = float(int(config["vocab_size"]) * int(config["hidden_size"]))
    for layer in datagen_sala.held_layers(config):
        total += sum(a * b for a, b in (
            s for s in datagen_sala.layer_shapes(config, layer).values()
            if len(s) == 2))
    return total


def step_flops(config: Dict[str, Any], tokens: float, selected_keys: float,
               index_pairs: float) -> float:
    """Flops of running ``tokens`` new events: 2 per parameter and token;
    q k^T and p v (2 x 2 x hd) per head of a group and attended key
    (``selected_keys``: events attended, summed over groups and sparse
    layers); q K^T (2 x hd) per head of a group and scored pooled key
    (``index_pairs``, summed likewise); the state's update and read-out
    (4 x hd^2 a head) per event and lightning layer.  Dense-path queries'
    keys are not counted by the program and not credited."""
    hd = int(config["head_dim"])
    per_group = int(config["num_attention_heads"]) \
        // int(config["num_key_value_heads"])
    lhd, lnh = int(config["lightning_head_dim"]), int(config["lightning_nh"])
    return (2.0 * params_per_token(config) * tokens
            + 4.0 * hd * per_group * selected_keys
            + 2.0 * hd * per_group * index_pairs
            + 4.0 * lhd * lhd * lnh * tokens * layers_of(config, True))


def sparse_attention_counts(config: Dict[str, Any], selected_keys: float,
                            user_dispatches: float) -> Tuple[float, float]:
    """(flops, bytes) of the selected-block attention: q k^T and p v per
    head of a group and attended key; each selected block read once a
    (user, group, sparse layer) and dispatch: ``topk`` blocks of
    ``block_size`` events x (k, v) x hd bfloat16, the least a user's
    queries can share (``user_dispatches``: users in a dispatch, summed
    over dispatches)."""
    hd = int(config["head_dim"])
    groups = int(config["num_key_value_heads"])
    per_group = int(config["num_attention_heads"]) // groups
    sp = config["sparse_config"]
    flops = 4.0 * hd * per_group * selected_keys
    nbytes = (user_dispatches * groups * layers_of(config, False)
              * int(sp["topk"]) * int(sp["block_size"]) * 2 * hd * 2)
    return flops, float(nbytes)


def lightning_counts(config: Dict[str, Any], tokens: float,
                     state_updates: float) -> Tuple[float, float]:
    """(flops, bytes) of the lightning update and read-out: 4 x hd^2 a
    head per event and layer; the state read and written once a (user,
    layer) and dispatch (float32), the rows of q, k, v (bfloat16) and o
    (float32) per event and layer."""
    hd, nh = int(config["lightning_head_dim"]), int(config["lightning_nh"])
    layers = layers_of(config, True)
    flops = 4.0 * hd * hd * nh * tokens * layers
    nbytes = (state_updates * 2 * nh * hd * hd * 4
              + tokens * layers * nh * hd * (3 * 2 + 4))
    return flops, float(nbytes)
