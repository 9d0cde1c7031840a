"""The program's metrics registry as its own text exposition renders it,
and deltas of it over the window: what the ``program_span`` and
``program_counter`` readers and the harness's rung check read."""

from __future__ import annotations

from typing import Dict, Optional


def snapshot() -> Dict[str, float]:
    """``{'name{label="v",...}': value}`` of every series."""
    from predictionio_tpu.obs import get_registry

    out: Dict[str, float] = {}
    for line in get_registry().render().splitlines():
        if line and not line.startswith("#"):
            key, _, value = line.rpartition(" ")
            try:
                out[key] = float(value)
            except ValueError:
                continue
    return out


def delta(before: Dict[str, float], after: Dict[str, float], series: str,
          match: Optional[Dict[str, str]] = None) -> float:
    """Growth over the window of every series named ``series`` whose
    labels hold ``match``, summed."""
    want = [f'{k}="{v}"' for k, v in (match or {}).items()]
    return sum(value - before.get(key, 0.0)
               for key, value in after.items()
               if key.split("{", 1)[0] == series
               and all(w in key for w in want))
