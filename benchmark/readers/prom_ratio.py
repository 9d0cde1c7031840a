"""A ratio of program counters over the window: the summed growth of the
``num`` series over that of the ``den`` series, times ``scale`` and, with
``scale_config``, times that key of the configuration (the experts of a
layer).  A term is ``{"family", "match"}``; nothing where the program
has no such series or the denominator did not move."""

from typing import Any, Dict, List, Optional

from benchmark import prom


def _sum(ctx, terms: List[Dict[str, Any]]) -> float:
    return sum(prom.delta(ctx["before"], ctx["after"], t["family"],
                          t.get("match", {})) for t in terms)


def read(ctx, num: List[Dict[str, Any]], den: List[Dict[str, Any]],
         scale: float = 1.0, scale_config: Optional[str] = None
         ) -> Optional[float]:
    below = _sum(ctx, den)
    if below <= 0:
        return None
    if scale_config is not None:
        scale = scale * float(ctx["config"][scale_config])
    return scale * _sum(ctx, num) / below
