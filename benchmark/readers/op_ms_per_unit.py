"""Device milliseconds of the ops matching ``pattern`` per unit of work
the drive counted (``unit``: a key of the window's extras)."""

from typing import Optional

from benchmark import trace_reduce


def read(ctx, pattern: str, unit: str) -> Optional[float]:
    t, n = ctx["trace"], ctx["window"].extras.get(unit)
    if not t or not n:
        return None
    seconds = trace_reduce.kernel_seconds(t, pattern)
    return 1e3 * seconds / n if seconds > 0 else None
