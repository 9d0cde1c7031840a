"""How late the load generator ran: a percentile of (sent - due), ms."""

from typing import Optional

import numpy as np


def read(ctx, percentile: float = 95.0) -> Optional[float]:
    late = ctx["window"].extras.get("late_ms")
    if late is None or not len(late):
        return None
    return float(np.percentile(late, percentile))
