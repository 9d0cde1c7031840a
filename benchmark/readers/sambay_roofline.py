"""Roofline share of one of the Mamba / sliding-window / shared-cache
backbone's kernels over the window, %.  Least time: operations and bytes
from ``rooflines_sambay`` over the program's counters (``kernel``:
``shared`` = ``shared_counts`` over ``pio_seq_shared_keys_total``;
``window`` = ``window_counts`` over ``pio_seq_window_keys_total`` and
``pio_seq_window_rows_total``; ``scan`` = ``scan_counts`` over the new
events and ``pio_seq_recurrent_updates_total``).  Measured time: the
summed device seconds of the ops matching ``pattern``.  Nothing where the
program has no such counters."""

from typing import Optional

from benchmark import prom, rooflines, rooflines_sambay, trace_reduce


def read(ctx, kernel: str, pattern: str) -> Optional[float]:
    t = ctx["trace"]
    if not t:
        return None
    seconds = trace_reduce.kernel_seconds(t, pattern)

    def grew(series):
        return prom.delta(ctx["before"], ctx["after"], series)

    if seconds <= 0 or grew("pio_seq_cross_rows_total") <= 0:
        return None
    config = ctx["config"]
    if kernel == "shared":
        flops, nbytes = rooflines_sambay.shared_counts(
            config, grew("pio_seq_shared_keys_total"))
    elif kernel == "window":
        flops, nbytes = rooflines_sambay.window_counts(
            config, grew("pio_seq_window_keys_total"),
            grew("pio_seq_window_rows_total"))
    elif kernel == "scan":
        flops, nbytes = rooflines_sambay.scan_counts(
            config, grew("pio_seq_tokens_total"),
            grew("pio_seq_recurrent_updates_total"))
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    share = rooflines.roofline_share(flops, nbytes, seconds,
                                     ctx["device_kind"])
    return share["pct"] if share else None
