"""The systems under test, built in-process from a configuration file
and ``--seed``: one module per kind of system, found by the name the
configuration gives (``"builder"``), so a new kind (a two-tower model,
an IVF-PQ corpus) is a new file here and no edit; another size of a kind
that is here is a new configuration file and no code.

    benchmark/builders/<builder>.py
        build(config, seed, split) -> system   (times its parts into split)
        control(config, seed) -> numbers       (the reference one precision
                                                step down, for control.py)

A system has ``free()``: stop what it started and drop every device
array it held, so the reference runs on an empty chip.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict


def load(name: str):
    """The module of builder ``name``."""
    try:
        return importlib.import_module(f"benchmark.builders.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.builders.{name}":
            raise
        raise ValueError(f"the configuration names builder {name!r}; there "
                         f"is no benchmark/builders/{name}.py") from None


def build(config: Dict[str, Any], seed: int, split: Dict[str, float]):
    return load(config["builder"]).build(config, seed, split)
