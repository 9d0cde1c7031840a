"""Roofline share of the exact top-k kernel over the window, %.

Least time: the window's retrieval calls and scored rows come from the
program's own counters (calls = delta of pio_retrieval_requests_total,
queries = delta of pio_retrieval_candidates_total / n_items); operations
and bytes per call from ``rooflines.fused_topk_counts``.  Measured time:
the summed device seconds of the ops matching ``pattern``."""

from typing import Optional

from benchmark import prom, rooflines, trace_reduce


def read(ctx, pattern: str, k: int = 10) -> Optional[float]:
    t = ctx["trace"]
    if not t:
        return None
    seconds = trace_reduce.kernel_seconds(t, pattern)
    calls = prom.delta(ctx["before"], ctx["after"],
                       "pio_retrieval_requests_total")
    cfg = ctx["config"]
    queries = prom.delta(ctx["before"], ctx["after"],
                         "pio_retrieval_candidates_total") / cfg["n_items"]
    if seconds <= 0 or calls <= 0:
        return None
    flops, corpus = rooflines.fused_topk_counts(
        1, cfg["n_items"], cfg["rank"], k)
    # flops scale with the queries scored, the corpus read with calls.
    share = rooflines.roofline_share(
        flops * queries, corpus * calls, seconds, ctx["device_kind"])
    return share["pct"] if share else None
