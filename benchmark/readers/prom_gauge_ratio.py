"""A ratio of program gauges as they stand when the window has closed
(``after``): the summed ``num`` series over the summed ``den`` series,
times ``scale`` and, with ``per_config``, divided by that number of the
configuration (a path of keys: the bytes of a page).  A term is
``{"family", "match"}``; nothing where the program has no such series or
the denominator reads 0."""

from typing import Any, Dict, List, Optional

from benchmark import prom


def _sum(ctx, terms: List[Dict[str, Any]]) -> float:
    return sum(prom.delta({}, ctx["after"], t["family"], t.get("match", {}))
               for t in terms)


def _present(ctx, term: Dict[str, Any]) -> bool:
    want = [f'{k}="{v}"' for k, v in term.get("match", {}).items()]
    return any(key.split("{", 1)[0] == term["family"]
               and all(w in key for w in want) for key in ctx["after"])


def read(ctx, num: List[Dict[str, Any]], den: List[Dict[str, Any]],
         scale: float = 1.0, per_config: Optional[List[str]] = None
         ) -> Optional[float]:
    below = _sum(ctx, den)
    if below <= 0 or not any(_present(ctx, t) for t in num):
        return None
    if per_config:
        value: Any = ctx["config"]
        for key in per_config:
            value = value[key]
        scale = scale / float(value)
    return scale * _sum(ctx, num) / below
