"""Roofline share of an ALS kernel over the window's sweeps, %.

``phase`` picks the count function (``gram``: both sides' normal
equations over the real ratings; ``solve``: one K x K solve per user and
per item); measured time is the summed device seconds of the ops
matching ``pattern``."""

from typing import Optional

from benchmark import rooflines, trace_reduce


def read(ctx, pattern: str, phase: str) -> Optional[float]:
    t, sweeps = ctx["trace"], ctx["window"].extras.get("sweeps")
    if not t or not sweeps:
        return None
    seconds = trace_reduce.kernel_seconds(t, pattern)
    cfg = ctx["config"]
    if phase == "gram":
        flops, nbytes = rooflines.als_gram_counts(
            2 * cfg["n_ratings"], cfg["rank"])
    elif phase == "solve":
        flops, nbytes = rooflines.als_solve_counts(
            cfg["n_users"] + cfg["n_items"], cfg["rank"])
    else:
        raise ValueError(f"phase {phase!r} is not gram|solve")
    share = rooflines.roofline_share(flops * sweeps, nbytes * sweeps,
                                     seconds, ctx["device_kind"])
    return share["pct"] if share else None
