"""What decides ``correct`` in a training cell: one timed sweep's
factors against the plain reference's one sweep (``reference_als``)."""

from __future__ import annotations

import json
from typing import Any, Dict

import numpy as np

from benchmark import reference_als


def _row_errors(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    norms = np.linalg.norm(ref, axis=1)
    scale = np.maximum(norms, np.median(norms))
    return np.linalg.norm(got.astype(np.float64) - ref, axis=1) / scale


def training_numbers(config: Dict[str, Any], init_seed: int, coo,
                     sample_items: np.ndarray, user_factors: np.ndarray,
                     item_rows: np.ndarray) -> Dict[str, float]:
    """One timed sweep's factors against the reference's one sweep.

    user_rows_max / _mean  per-user |x - x_ref| over max(|x_ref|, the
                           median row norm): every user
    item_rows_max / _mean  the same over the sampled items (the heaviest
                           two and a seeded draw)
    rmse_gap               |rmse - rmse_ref| / rmse_ref over the sampled
                           items' ratings
    """
    ref = reference_als.als_one_sweep(config, init_seed, coo, sample_items,
                                  program_uv=(user_factors, item_rows))
    print("reference timing: " + json.dumps(
        {k: round(v, 2) for k, v in ref.get("timing", {}).items()}),
        flush=True)
    ue = _row_errors(user_factors, ref["u_ref"])
    ie = _row_errors(item_rows, ref["v_ref"])
    n = ref["n_sampled_ratings"]
    rmse_ref = np.sqrt(ref["sse_ref"] / n)
    rmse = np.sqrt(ref["sse_program"] / n)
    return {"user_rows_max": float(ue.max()),
            "user_rows_mean": float(ue.mean()),
            "item_rows_max": float(ie.max()),
            "item_rows_mean": float(ie.mean()),
            "rmse_gap": float(abs(rmse - rmse_ref) / rmse_ref)}


def sample_items(config: Dict[str, Any], seed: int, coo) -> np.ndarray:
    """The item rows compared: the two with most ratings (the rows the
    program splits into partial rows) and a seeded draw of the rest."""
    from benchmark.traffic import rng_for

    deg = np.bincount(coo[1], minlength=config["n_items"])
    heavy = np.argsort(-deg)[:2]
    rest = np.setdiff1d(np.arange(config["n_items"]), heavy)
    n = min(int(config.get("check_items", 64)) - 2, len(rest))
    return np.concatenate([heavy, rng_for(seed, 5).choice(rest, n, False)])
