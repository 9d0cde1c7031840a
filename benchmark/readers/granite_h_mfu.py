"""The whole step's share of the chip's peak over the traced window, %,
on the Mamba-2 / no-position attention backbone: the flops the window's
new events and answered turns need (``rooflines_granite_h.step_flops``
over the program's ``pio_seq_tokens_total``, the window's answered
queries and ``pio_seq_attended_keys_total``) over window seconds x peak
flops.  Nothing where the program has no such counters."""

from typing import Optional

from benchmark import prom, rooflines, rooflines_granite_h


def read(ctx) -> Optional[float]:
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or not t["chips_traced"]:
        return None

    def grew(series):
        return prom.delta(ctx["before"], ctx["after"], series)

    tokens, keys = grew("pio_seq_tokens_total"), \
        grew("pio_seq_attended_keys_total")
    if tokens <= 0 or keys <= 0:
        return None
    window = ctx["window"]
    peak = rooflines.peaks(ctx["device_kind"])["flops_per_s"]
    flops = rooflines_granite_h.step_flops(
        ctx["config"], tokens, window.attempted - window.failed, keys)
    return 100.0 * flops / (t["window_s"] * peak)
