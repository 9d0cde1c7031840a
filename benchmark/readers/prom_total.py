"""The running total of program histogram series as it stands when the
window has closed (``after``), times ``scale``: for work that ran once,
in set-up, before the first snapshot was taken (the phases of ALS prep).
``terms`` are added; nothing where the program has none of them."""

from typing import Any, Dict, List, Optional

from benchmark import prom


def read(ctx, terms: List[Dict[str, Any]], scale: float = 1.0
         ) -> Optional[float]:
    seen, total = 0.0, 0.0
    for t in terms:
        match = t.get("match", {})
        seen += prom.delta({}, ctx["after"], t["family"] + "_count", match)
        total += prom.delta({}, ctx["after"], t["family"] + "_sum", match)
    return total * scale if seen > 0 else None
