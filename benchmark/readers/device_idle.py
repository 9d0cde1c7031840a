"""Device idle share of the traced window, %: 1 - busy / window."""

from typing import Optional


def read(ctx) -> Optional[float]:
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or not t["chips_traced"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
