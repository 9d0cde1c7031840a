"""Roofline share of one of the Mamba-2 / no-position attention
backbone's kernels over the window, %.  Least time: operations and bytes
from ``rooflines_granite_h`` over the program's counters (``kernel``:
``ssd`` = ``ssd_counts`` over the new events and
``pio_seq_recurrent_updates_total``; ``attention`` = ``attention_counts``
over ``pio_seq_attended_keys_total`` and
``pio_seq_attention_rows_total``).  Measured time: the summed device
seconds of the ops matching ``pattern``.  Nothing where the program has
no such counters."""

from typing import Optional

from benchmark import prom, rooflines, rooflines_granite_h, trace_reduce


def read(ctx, kernel: str, pattern: str) -> Optional[float]:
    t = ctx["trace"]
    if not t:
        return None
    seconds = trace_reduce.kernel_seconds(t, pattern)

    def grew(series):
        return prom.delta(ctx["before"], ctx["after"], series)

    keys = grew("pio_seq_attended_keys_total")
    if seconds <= 0 or keys <= 0:
        return None
    config = ctx["config"]
    if kernel == "ssd":
        flops, nbytes = rooflines_granite_h.ssd_counts(
            config, grew("pio_seq_tokens_total"),
            grew("pio_seq_recurrent_updates_total"))
    elif kernel == "attention":
        flops, nbytes = rooflines_granite_h.attention_counts(
            config, keys, grew("pio_seq_attention_rows_total"))
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    share = rooflines.roofline_share(flops, nbytes, seconds,
                                     ctx["device_kind"])
    return share["pct"] if share else None
