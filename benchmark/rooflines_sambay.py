"""Operations and bytes of the Mamba / sliding-window / shared-cache
backbone, from the published keys of the configuration file and the
program's counters.  Useful work only, the same whatever implements it:
padded tokens, tiles and query rows, a window page fetched for a few of
its rows, and a history read again by a user's second read row are not
credited."""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import datagen_sambay as gen


def layers_of(config: Dict[str, Any], kind: str) -> int:
    return sum(k == kind for k in gen.kinds(config))


def _matrix_params(config: Dict[str, Any], layers) -> float:
    return float(sum(a * b for layer in layers for a, b in (
        s for name, s in gen.layer_shapes(config, layer).items()
        if len(s) == 2 and name not in ("conv_w", "lam", "a_log"))))


def params_per_token(config: Dict[str, Any]) -> Tuple[float, float]:
    """(parameters every new event multiplies with: layers 0-16 and the
    full layer's key/value projection; parameters a READ row multiplies
    with beyond those: the full layer's query and output projections and
    its MLP, layers 18-31, and the tied head)."""
    kinds = gen.kinds(config)
    full = kinds.index(gen.FULL)
    s = gen.sizes(config)
    qw, kvw = s["heads"] * s["hd"], 2 * s["kv"] * s["hd"]
    every = _matrix_params(config, range(full)) + s["d"] * kvw
    read = (_matrix_params(config, range(full, len(kinds)))
            - s["d"] * kvw
            + float(int(config["vocab_size"]) * s["d"]))
    assert qw == s["d"]
    return every, read


def attention_flops(config: Dict[str, Any], keys: float) -> float:
    """q k^T (2 x hd) and p v (2 x 2 hd: a pair's values are two heads
    wide) per query head and attended key (``keys``: events attended,
    summed over the layers)."""
    s = gen.sizes(config)
    return keys * s["heads"] * 6.0 * s["hd"]


def scan_counts(config: Dict[str, Any], tokens: float, updates: float
                ) -> Tuple[float, float]:
    """(flops, bytes) of the selective scan: per event, layer and state
    element the decay's product and exponential, the state's multiply-add,
    the input's outer product and the read-out's multiply-add (7); the
    state read and written once a (user, layer) and dispatch (float32:
    ``updates``), the rows of xc, Delta, y (E) and B, C (N) per event and
    layer (float32)."""
    s = gen.sizes(config)
    layers = layers_of(config, gen.MAMBA)
    flops = 7.0 * s["e"] * s["n"] * tokens * layers
    nbytes = (updates * 2 * s["e"] * s["n"] * 4
              + tokens * layers * (3 * s["e"] + 2 * s["n"]) * 4)
    return flops, float(nbytes)


def window_counts(config: Dict[str, Any], keys: float, rows: float
                  ) -> Tuple[float, float]:
    """(flops, bytes) of the sliding-window attention: products per query
    head and attended key; each (user, layer, dispatch) reads its window's
    rows and its new events' rows once (``rows``, summed over the layers):
    keys and values of 20 heads x 64 in bfloat16."""
    s = gen.sizes(config)
    return attention_flops(config, keys), rows * 2 * s["kv"] * s["hd"] * 2.0


def shared_counts(config: Dict[str, Any], keys: float
                  ) -> Tuple[float, float]:
    """(flops, bytes) of the shared cache's readers: products per query
    head and attended key; each (read row, layer of the 8, dispatch)
    reads its history's keys and values once (``keys``: events attended,
    summed over the layers)."""
    s = gen.sizes(config)
    return attention_flops(config, keys), keys * 2 * s["kv"] * s["hd"] * 2.0


def step_flops(config: Dict[str, Any], tokens: float, reads: float,
               window_keys: float, shared_keys: float) -> float:
    """Flops of running ``tokens`` new events of which ``reads`` end a
    turn: 2 per parameter and row on either side of the decoder split,
    the attention products, the scan."""
    every, read = params_per_token(config)
    return (2.0 * every * tokens + 2.0 * read * reads
            + attention_flops(config, window_keys + shared_keys)
            + scan_counts(config, tokens, 0.0)[0])
