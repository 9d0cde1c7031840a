"""Roofline share of one of the block-selected / lightning backbone's
kernels over the window, %.  Least time: operations and bytes from
``rooflines_sala`` over the program's counters (``kernel``: ``sparse`` =
``sparse_attention_counts`` over ``pio_seq_sparse_keys_total`` and the
users of each dispatch; ``lightning`` = ``lightning_counts`` over the new
events and ``pio_seq_recurrent_updates_total``).  Measured time: the
summed device seconds of the ops matching ``pattern``."""

from typing import Optional

from benchmark import prom, rooflines, rooflines_sala, trace_reduce


def read(ctx, kernel: str, pattern: str) -> Optional[float]:
    t = ctx["trace"]
    if not t:
        return None
    seconds = trace_reduce.kernel_seconds(t, pattern)

    def grew(series):
        return prom.delta(ctx["before"], ctx["after"], series)

    updates = grew("pio_seq_recurrent_updates_total")
    if seconds <= 0 or updates <= 0:
        return None
    config = ctx["config"]
    if kernel == "sparse":
        lightning_layers = rooflines_sala.layers_of(config, True)
        flops, nbytes = rooflines_sala.sparse_attention_counts(
            config, grew("pio_seq_sparse_keys_total"),
            updates / max(lightning_layers, 1))
    elif kernel == "lightning":
        flops, nbytes = rooflines_sala.lightning_counts(
            config, grew("pio_seq_tokens_total"), updates)
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    share = rooflines.roofline_share(flops, nbytes, seconds,
                                     ctx["device_kind"])
    return share["pct"] if share else None
