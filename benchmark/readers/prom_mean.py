"""Mean of a program histogram over the window, from the registry's own
text exposition: (sum after - sum before) / (count after - count before)
over every series of ``family`` whose labels hold ``match``.  ``terms``
adds several such means (ingress + serialize).  Means, not medians: the
families' buckets (…25, 50, 100, 250 ms) are too coarse to interpolate a
median from."""

from typing import Any, Dict, List, Optional

from benchmark import prom


def read(ctx, terms: List[Dict[str, Any]]) -> Optional[float]:
    out = 0.0
    for t in terms:
        match = t.get("match", {})
        count = prom.delta(ctx["before"], ctx["after"],
                           t["family"] + "_count", match)
        if count <= 0:
            return None
        out += prom.delta(ctx["before"], ctx["after"],
                          t["family"] + "_sum", match) / count
    return out
