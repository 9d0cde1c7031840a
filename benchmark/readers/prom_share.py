"""A part of a program histogram family's time as a share of the traced
window, %, for a family whose series tile one thread's wall (the
batcher's ``pio_batcher_thread_ms{phase}``: every instant of the thread
belongs to one phase, so the family's growth between the two snapshots
IS that thread's wall between them).

The snapshots lie outside the window: the harness takes them before it
starts its load generator and after it has stopped the profiler.  With
``outside_window_is_part`` the wall the family counted beyond the
window's length is taken off ``part``: right for a part that is what the
thread does when no work is offered (``wait_empty``; the drives offer
work inside the window only).  A phase is observed when it closes, and
an idle batcher closes one every 50 ms, so a reading is off by about two
such slices at most."""

from typing import Dict, Optional

from benchmark import prom


def read(ctx, family: str, part: Dict[str, str],
         outside_window_is_part: bool = False) -> Optional[float]:
    t = ctx.get("trace")
    whole_ms = prom.delta(ctx["before"], ctx["after"], family + "_sum")
    if not t or t["window_s"] <= 0 or whole_ms <= 0:
        return None
    window_ms = 1e3 * t["window_s"]
    part_ms = prom.delta(ctx["before"], ctx["after"], family + "_sum", part)
    if outside_window_is_part:
        part_ms = max(part_ms - max(whole_ms - window_ms, 0.0), 0.0)
    return 100.0 * part_ms / window_ms
