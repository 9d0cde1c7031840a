"""The whole step's share of the chip's peak over the traced window, %,
on the Mamba / sliding-window / shared-cache backbone: the flops the
window's new events and read rows need (``rooflines_sambay.step_flops``
over the program's ``pio_seq_tokens_total``, ``pio_seq_cross_rows_total``,
``pio_seq_window_keys_total`` and ``pio_seq_shared_keys_total``) over
window seconds x peak flops.  Nothing where the program has no such
counters."""

from typing import Optional

from benchmark import prom, rooflines, rooflines_sambay


def read(ctx) -> Optional[float]:
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or not t["chips_traced"]:
        return None

    def grew(series):
        return prom.delta(ctx["before"], ctx["after"], series)

    tokens, reads = grew("pio_seq_tokens_total"), \
        grew("pio_seq_cross_rows_total")
    if tokens <= 0 or reads <= 0:
        return None
    peak = rooflines.peaks(ctx["device_kind"])["flops_per_s"]
    flops = rooflines_sambay.step_flops(
        ctx["config"], tokens, reads, grew("pio_seq_window_keys_total"),
        grew("pio_seq_shared_keys_total"))
    return 100.0 * flops / (t["window_s"] * peak)
