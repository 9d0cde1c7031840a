"""Roofline share of the grouped expert products over the window, %.

Least time: operations and bytes from ``rooflines_seq.moe_counts`` over
the window's token-to-expert assignments and the experts picked at least
once in a dispatch (the program's ``pio_moe_assignments_total`` and
``pio_moe_experts_touched_total``).  Measured time: the summed device
seconds of the ops matching ``pattern``."""

from typing import Optional

from benchmark import prom, rooflines, rooflines_seq, trace_reduce


def read(ctx, pattern: str) -> Optional[float]:
    t = ctx["trace"]
    if not t:
        return None
    seconds = trace_reduce.kernel_seconds(t, pattern)
    assigned = prom.delta(ctx["before"], ctx["after"],
                          "pio_moe_assignments_total")
    touched = prom.delta(ctx["before"], ctx["after"],
                         "pio_moe_experts_touched_total")
    if seconds <= 0 or assigned <= 0:
        return None
    flops, nbytes = rooflines_seq.moe_counts(ctx["config"], assigned,
                                             touched)
    share = rooflines.roofline_share(flops, nbytes, seconds,
                                     ctx["device_kind"])
    return share["pct"] if share else None
