"""What a parent span spends outside one of its children, per call of
the parent, over the window: (delta of the parent's ``_sum`` - delta of
the child's ``_sum``) / delta of the parent's ``_count``.  Per call of
the parent, so a call that never opens the child (another rung) counts
its whole time.  Nothing where the program has no such child series."""

from typing import Any, Dict, Optional

from benchmark import prom


def _delta(ctx, spec: Dict[str, Any], suffix: str) -> float:
    return prom.delta(ctx["before"], ctx["after"], spec["family"] + suffix,
                      spec.get("match", {}))


def read(ctx, parent: Dict[str, Any], child: Dict[str, Any]
         ) -> Optional[float]:
    calls = _delta(ctx, parent, "_count")
    if calls <= 0 or _delta(ctx, child, "_count") <= 0:
        return None
    return (_delta(ctx, parent, "_sum") - _delta(ctx, child, "_sum")) / calls
