"""Device busy milliseconds of the window per unit of work the drive
counted (``unit``: a key of the window's extras, e.g. ``sweeps``)."""

from typing import Optional


def read(ctx, unit: str) -> Optional[float]:
    t, n = ctx["trace"], ctx["window"].extras.get(unit)
    if not t or not n or t["busy_s"] <= 0:
        return None
    return 1e3 * t["busy_s"] / n
