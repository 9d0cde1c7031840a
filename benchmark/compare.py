"""What decides ``correct``: the timed path's own outputs against the
plain reference, each number beside a limit of its own.  The limits live
in the configuration file (``"limits"``), set from chip readings that
``PERF.md`` records; a number without a limit is an error.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from benchmark import reference


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    compared = {}
    ok = True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"the configuration gives no limit for {name!r}")
        limit = float(limits[name])
        value = float(value)
        compared[name] = {"value": value, "limit": limit}
        # A NaN compares false: it fails.
        ok = ok and bool(value <= limit)
    return ok, compared


def parse_answers(samples: Sequence[Tuple[int, int, Any]]
                  ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Served (ids, scores) of each sampled answer, padded to the widest
    ``num`` with id -1; and how many answers were malformed: not a JSON
    object with exactly ``num`` well-formed ``itemScores``."""
    width = max(n for _, n, _ in samples)
    ids = np.full((len(samples), width), -1, np.int64)
    scores = np.full((len(samples), width), np.nan, np.float64)
    malformed = 0
    for r, (_, num, answer) in enumerate(samples):
        try:
            if isinstance(answer, (str, bytes)):
                answer = json.loads(answer)
            hits = answer["itemScores"]
            if len(hits) != num:
                raise ValueError("wrong count")
            for c, h in enumerate(hits):
                item = h["item"]
                if not item.startswith("i"):
                    raise ValueError("not an item id")
                ids[r, c] = int(item[1:])
                scores[r, c] = float(h["score"])
        except (KeyError, TypeError, ValueError, AttributeError):
            malformed += 1
            ids[r], scores[r] = -1, np.nan
    return ids, scores, malformed


def serving_numbers(config: Dict[str, Any], seed: int,
                    samples: Sequence[Tuple[int, int, Any]]
                    ) -> Dict[str, float]:
    """The sampled answers ``(user_idx, num, answer)`` against the exact
    top-``num`` of the seeded corpus.

    malformed      answers that are not ``num`` (item, score) pairs
    unordered      answers whose scores rise somewhere
    score_rel_err  widest |served score - reference score of that item|
                   over the reference score
    rank_gap       widest gap by which a served item's reference score
                   lies below the reference's ``num``-th best, over that
                   score (0 where the item belongs to the true top)
    """
    if not samples:
        return {"malformed": float("inf"), "unordered": 0.0,
                "score_rel_err": float("inf"), "rank_gap": float("inf")}
    ids, scores, malformed = parse_answers(samples)
    nums = np.array([n for _, n, _ in samples])
    users = np.array([u for u, _, _ in samples])
    kmax = int(nums.max())
    ref_s, _, at_served = reference.topk(config, seed, users, kmax,
                                         served_ids=np.maximum(ids, 0))
    valid = ids >= 0
    kth = ref_s[np.arange(len(samples)), nums - 1][:, None]
    with np.errstate(invalid="ignore", divide="ignore"):
        rel = np.abs(scores - at_served) / np.abs(at_served)
        gap = np.maximum(kth - at_served, 0.0) / np.abs(kth)
        rising = (np.diff(scores, axis=1) > 0) & valid[:, 1:]
    return {
        "malformed": float(malformed),
        "unordered": float(rising.any(axis=1).sum()),
        "score_rel_err": float(np.max(rel[valid], initial=0.0))
        if valid.any() else float("inf"),
        "rank_gap": float(np.max(gap[valid], initial=0.0))
        if valid.any() else float("inf"),
    }


def control_answers(config: Dict[str, Any], seed: int,
                    users: np.ndarray, num: int, precision: str,
                    operand_dtype=None) -> List[Tuple[int, int, Any]]:
    """The reference in the program's place at a lower ``precision``,
    shaped as served answers (the control ``compare`` must refuse)."""
    s, i, _ = reference.topk(config, seed, users, num, precision=precision,
                             operand_dtype=operand_dtype)
    return [(int(u), num, {"itemScores": [
        {"item": f"i{int(ii)}", "score": float(ss)}
        for ii, ss in zip(i[r], s[r])]}) for r, u in enumerate(users)]
