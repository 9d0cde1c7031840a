"""Operations and bytes each kernel's algorithm needs, from shapes, and
the table of peaks.  The yardstick: a roofline share is

    max(flops / peak_flops, bytes / peak_bytes) / measured kernel seconds

with the kernel seconds summed from the device trace.  Useful work only:
padding the kernel adds (batch padded to a tile, a 6-pass float32 matmul)
is not credited, so a share cannot pass 100% on a consistent input.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Optional, Tuple

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; a device that is not in
    ``peaks.json`` is an error, never a default."""
    with open(_PEAKS, encoding="utf-8") as f:
        table = json.load(f)
    try:
        return table[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            f"benchmark/peaks.json with its source (known: {sorted(table)})"
        ) from None


def fused_topk_counts(batch: int, n_items: int, dim: int, k: int,
                      itemsize: int = 4) -> Tuple[float, float]:
    """(flops, bytes) of one exact top-k call: B·N dot products of length
    D, one read of the corpus and of the queries, one write of the k
    (score, id) pairs per query."""
    flops = 2.0 * batch * n_items * dim
    nbytes = (n_items * dim * itemsize + batch * dim * itemsize
              + batch * k * 8)
    return flops, float(nbytes)


def als_gram_counts(nnz: int, rank: int, gather_itemsize: int = 2
                    ) -> Tuple[float, float]:
    """(flops, bytes) of one side's normal-equation build over ``nnz``
    real ratings: a rank-K outer product and a K-vector per rating; one
    gathered factor row, one rating and one index read per rating.
    Padded slots are not credited."""
    flops = 2.0 * nnz * rank * rank + 2.0 * nnz * rank
    nbytes = nnz * (rank * gather_itemsize + 4 + 4)
    return flops, float(nbytes)


def als_solve_counts(n_rows: int, rank: int) -> Tuple[float, float]:
    """(flops, bytes) of ``n_rows`` K×K SPD solves: K^3/3 (the
    Cholesky-equivalent count the repo's bench.py credits) plus the two
    triangular solves; A, b read and x written in float32."""
    flops = n_rows * (rank ** 3 / 3.0 + 2.0 * rank * rank)
    nbytes = n_rows * (rank * rank + 2 * rank) * 4
    return flops, float(nbytes)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> Optional[Dict[str, float]]:
    """Share (in %) of the roofline reached, and which side bounds it.
    None when no kernel time was read."""
    if seconds <= 0:
        return None
    pk = peaks(device_kind)
    t_flops = flops / pk["flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    return {"pct": 100.0 * least / seconds,
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "least_s": least}
