"""One part of the set-up split the harness times (``key``), seconds."""

from typing import Optional


def read(ctx, key: str) -> Optional[float]:
    return ctx["split"].get(key)
