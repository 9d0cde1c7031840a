"""Operations and bytes of the Mamba-2 / no-position attention backbone,
from the published keys of the configuration file and the program's
counters.  Useful work only, the same whatever implements it: padded
tokens, tiles and query rows, a page fetched for a few of its rows, and
the zero halves of a kv pair's query rows are not credited."""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import datagen_granite_h as gen


def layers_of(config: Dict[str, Any], kind: str) -> int:
    return sum(k == kind for k in config["layer_types"])


def matrix_params(config: Dict[str, Any]) -> float:
    """Parameters every new event multiplies with: the projections and
    feed-forwards of all layers (the head is the read rows')."""
    return float(sum(a * b for layer in range(len(config["layer_types"]))
                     for a, b in (
        s for name, s in gen.layer_shapes(config, layer).items()
        if len(s) == 2 and name != "conv_w")))


def ssd_counts(config: Dict[str, Any], tokens: float, updates: float
               ) -> Tuple[float, float]:
    """(flops, bytes) of the Mamba-2 recurrence: per event, layer and
    state element the decay's multiply, the input's outer product and its
    add, and the read-out's multiply-add (5), and the decay's exponential
    once an event, layer and head; the state read and written once a
    (user, layer) and dispatch (float32: ``updates``), the rows of x and
    y (E), B and C (N) and dt (H) per event and layer (float32)."""
    s = gen.sizes(config)
    layers = layers_of(config, gen.MAMBA)
    flops = (5.0 * s["e"] * s["n"] + s["h"]) * tokens * layers
    nbytes = (updates * 2 * s["e"] * s["n"] * 4
              + tokens * layers * (2 * s["e"] + 2 * s["n"] + s["h"]) * 4)
    return flops, float(nbytes)


def attention_counts(config: Dict[str, Any], keys: float,
                     rows: float = 0.0) -> Tuple[float, float]:
    """(flops, bytes) of the attention layers: q k^T and p v (2 x hd each)
    per query head and attended key (``keys``: events attended, summed
    over the layers); each (user, layer, dispatch) reads its history's
    keys and values once for all of the user's new events (``rows``,
    summed over the layers): 8 heads x 64 of each in bfloat16."""
    s = gen.sizes(config)
    return (keys * s["heads"] * 4.0 * s["hd"],
            rows * 2 * s["kv"] * s["hd"] * 2.0)


def step_flops(config: Dict[str, Any], tokens: float, reads: float,
               keys: float) -> float:
    """Flops of running ``tokens`` new events of which ``reads`` end a
    turn: 2 a parameter and new event over all layers, the tied head on
    the read rows, the attention products, the recurrence."""
    s = gen.sizes(config)
    return (2.0 * matrix_params(config) * tokens
            + 2.0 * int(config["vocab_size"]) * s["d"] * reads
            + attention_counts(config, keys)[0]
            + ssd_counts(config, tokens, 0.0)[0])
