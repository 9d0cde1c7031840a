"""The whole step's share of the chip's peak over the traced window, %:
the flops the window's new events need (``rooflines_seq.step_flops``
over the program's ``pio_seq_tokens_total`` and
``pio_seq_attended_keys_total``) over window seconds x peak flops.  Low
by the cell's nature where a dispatch streams every expert for a few
dozen tokens."""

from typing import Optional

from benchmark import prom, rooflines, rooflines_seq


def read(ctx) -> Optional[float]:
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or not t["chips_traced"]:
        return None
    tokens = prom.delta(ctx["before"], ctx["after"], "pio_seq_tokens_total")
    keys = prom.delta(ctx["before"], ctx["after"],
                      "pio_seq_attended_keys_total")
    if tokens <= 0:
        return None
    peak = rooflines.peaks(ctx["device_kind"])["flops_per_s"]
    return 100.0 * rooflines_seq.step_flops(ctx["config"], tokens, keys) \
        / (t["window_s"] * peak)
