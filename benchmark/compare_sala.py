"""What decides ``correct`` in the block-selected / lightning cells:
sampled answers of the timed path against the plain reference's logits
after the user's WHOLE history up to that turn (``reference_sala``), so
set-up's prefill and every earlier turn, through all three kinds of
state, have to add up to one forward pass.  Several sampled turns of one
user are read from ONE pass over that user's longest sampled history.

The numbers are ``compare_seq``'s, defined there: ``malformed``,
``unordered``, and over the sampled answers the median, 90th percentile
and widest ERROR (widest |served score - reference logit of that item|
over an answer's items) and the 90th percentile and widest RANK GAP (how
far the reference's logit of a served item lies below the reference's
``num``-th best).  Absolute: the head is scaled by ``dim_model_base /
hidden_size``, so logits here are of order 0.1, and so are the limits'
units.

Why quantiles carry the tight limits here too: a query picks the top 64
of ~300-1,000 block scores, and where the 64th and the 65th lie closer
than bfloat16 keys and pooled keys resolve, the stated precision does not
determine the pick; a turned pick moves that answer, and every later one
of that user whose history it stays in.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, Sequence, Tuple

import numpy as np

from benchmark import compare, datagen_seq, reference_sala

EMPTY = {"malformed": float("inf"), "unordered": 0.0,
         "score_abs_err_p50": float("inf"),
         "score_abs_err_p90": float("inf"),
         "score_abs_err_max": float("inf"), "rank_gap_p90": float("inf"),
         "rank_gap_max": float("inf")}


def reference_logits(config: Dict[str, Any], seed: int,
                     samples: Sequence[Tuple[int, int]], **variant
                     ) -> np.ndarray:
    """[n, V] logits of the reference after ``count`` events of ``user``
    for each (user, count): one pass a user."""
    events = datagen_seq.Events(config, seed)
    users = sorted({u for u, _ in samples})
    longest = {u: max(c for v, c in samples if v == u) for u in users}
    timings: Dict[str, float] = {}
    rows = reference_sala.logits_at(
        config, seed, [events.of(u, longest[u]) for u in users],
        [[c - 1 for v, c in samples if v == u] for u in users],
        timings=timings, **variant)
    print("reference_sala seconds: " + ", ".join(
        f"{k} {v:.1f}" for k, v in timings.items()), file=sys.stderr)
    taken = {u: 0 for u in users}
    out = []
    for u, _ in samples:
        out.append(rows[users.index(u)][taken[u]])
        taken[u] += 1
    return np.stack(out)


def numbers(config: Dict[str, Any], seed: int,
            samples: Sequence[Tuple[int, int, int, Any]], logits=None
            ) -> Dict[str, float]:
    """``samples``: (user, events the user has once the turn is applied,
    num, the answer as served); ``logits``: the reference's, where the
    caller has them."""
    if not samples:
        return dict(EMPTY)
    ids, scores, malformed = compare.parse_answers(
        [(u, num, answer) for u, _, num, answer in samples])
    if logits is None:
        logits = reference_logits(config, seed,
                                  [(u, c) for u, c, _, _ in samples])
    nums = np.array([num for _, _, num, _ in samples])
    valid = ids >= 0
    at_served = np.take_along_axis(logits, np.maximum(ids, 0), axis=1)
    ordered = -np.sort(-logits, axis=1)
    kth = ordered[np.arange(len(samples)), nums - 1][:, None]
    with np.errstate(invalid="ignore"):
        err = np.where(valid, np.abs(scores - at_served), 0.0).max(axis=1)
        gap = np.where(valid, np.maximum(kth - at_served, 0.0),
                       0.0).max(axis=1)
        rising = (np.diff(scores, axis=1) > 0) & valid[:, 1:]
    # A malformed answer is counted as such and as the worst there is.
    err[~valid.any(axis=1)] = 1e30
    gap[~valid.any(axis=1)] = 1e30
    # Every answer's two readings, for whoever sets the limits.
    print("compare_sala answers: " + json.dumps({
        "users": [u for u, _, _, _ in samples],
        "err": err.round(6).tolist(), "gap": gap.round(6).tolist(),
        "logit_spread": float(np.std(logits))}), file=sys.stderr)
    return {
        "malformed": float(malformed),
        "unordered": float(rising.any(axis=1).sum()),
        "score_abs_err_p50": float(np.percentile(err, 50)),
        "score_abs_err_p90": float(np.percentile(err, 90)),
        "score_abs_err_max": float(err.max()),
        "rank_gap_p90": float(np.percentile(gap, 90)),
        "rank_gap_max": float(gap.max()),
    }


def as_answers(samples: Sequence[Tuple[int, int, int]], logits: np.ndarray
               ) -> list:
    """Logits shaped as served answers (a control in the program's
    place)."""
    out = []
    for (u, count, num), row in zip(samples, logits):
        top = np.argsort(-row)[:num]
        out.append((u, count, num, {"itemScores": [
            {"item": f"i{int(i)}", "score": float(row[i])} for i in top]}))
    return out
