"""How a traffic mix reaches the system: one module per drive, found by
the name the mix gives (``"drive"``), so a new kind of drive is a new
file here and no edit:

    benchmark/drives/<drive>.py
        warm(system, mix)            every shape the mix will use (set-up)
        run(system, mix, config, seed, seconds, window_span) -> Window

A drive reads its parameters from the mix and knows no cell by name.
It hands back every end-to-end number it can take from the host clock,
the counts, and a ``check`` that compares what the window produced with
the plain reference once the program's state has been freed.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, List

import numpy as np

from benchmark import traffic

FAILED_MS = 60_000.0   # latency a failed request is charged


class Window:
    """What a drive hands back from the measured window."""

    def __init__(self):
        self.metrics: Dict[str, float] = {}
        self.attempted = 0
        self.failed = 0
        self.extras: Dict[str, Any] = {}      # for the per-layer readers
        self.check: Callable[[], Dict[str, float]] = dict


def load(name: str):
    """The module of drive ``name``."""
    try:
        return importlib.import_module(f"benchmark.drives.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.drives.{name}":
            raise
        raise ValueError(f"the traffic mix names drive {name!r}; there is "
                         f"no benchmark/drives/{name}.py") from None


def distinct_nums(mix) -> List[int]:
    return sorted({int(k) for k, _ in mix.get("num", [[10, 1.0]])})


def call_ms(ends: List[float]) -> Dict[str, float]:
    """Shortest, median and longest call of a closed loop, from the
    times its calls ended: a stall inside the window shows here."""
    per_call = np.diff(ends, prepend=0.0) * 1e3
    return {"min": float(per_call.min()),
            "median": float(np.median(per_call)),
            "max": float(per_call.max())}


def sample(seed: int, n: int, size: int) -> np.ndarray:
    """``size`` of ``n`` indices, drawn by the seed."""
    return traffic.rng_for(seed, 4).choice(n, min(size, n), replace=False)
