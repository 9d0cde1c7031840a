"""The whole step's share of the chip's peak over the traced window, %,
on the block-selected / lightning backbone: the flops the window's new
events need (``rooflines_sala.step_flops`` over the program's
``pio_seq_tokens_total``, ``pio_seq_sparse_keys_total`` and
``pio_seq_index_pairs_total``) over window seconds x peak flops.  Nothing
where the program has no such counters."""

from typing import Optional

from benchmark import prom, rooflines, rooflines_sala


def read(ctx) -> Optional[float]:
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or not t["chips_traced"]:
        return None

    def grew(series):
        return prom.delta(ctx["before"], ctx["after"], series)

    tokens = grew("pio_seq_tokens_total")
    if tokens <= 0 or grew("pio_seq_recurrent_updates_total") <= 0:
        return None
    peak = rooflines.peaks(ctx["device_kind"])["flops_per_s"]
    flops = rooflines_sala.step_flops(
        ctx["config"], tokens, grew("pio_seq_sparse_keys_total"),
        grew("pio_seq_index_pairs_total"))
    return 100.0 * flops / (t["window_s"] * peak)
