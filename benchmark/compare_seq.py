"""What decides ``correct`` in the sequence cells: sampled answers of the
timed path against the plain reference's logits after the user's WHOLE
history up to that turn (``reference_seq``), so set-up's prefill and
every earlier turn, through both kinds of state, have to add up to one
forward pass.

malformed          answers that are not ``num`` (item, score) pairs
unordered          answers whose scores rise somewhere

Per answer: its ERROR is the widest |served score - reference logit of
that item| over its items, its RANK GAP the widest gap by which the
reference's logit of a served item lies below the reference's ``num``-th
best logit (0 where the item belongs to the true top).  Absolute, not
relative: logits are of order 1 (the embedding rows have unit norm and
the last norm makes |h| = sqrt(d)), the served top ten lie near 4, and a
relative error would blow up on a logit near 0.

score_abs_err_p50  the median and the 90th percentile of the error and
score_abs_err_p90  the 90th percentile of the rank gap over the sampled
rank_gap_p90       answers: wrong arithmetic (a lower precision, the
                   bias left out, a layer's state lost) moves EVERY
                   answer
score_abs_err_max  the widest error and rank gap of ANY answer: one
rank_gap_max       answer computed at another event than its last (a
                   lost turn, two turns misordered, a refill that is not
                   the history) is off by the spread of the logits
                   themselves, several units

Why the quantiles carry the tight limits and the widest a loose one: a
router picks the top 4 of 64 scores, and where the fourth and the fifth
lie closer than bfloat16 rounding resolves, the stated precision does
not determine the pick.  A turned pick anywhere in the part of the
history the last event still reads (through the convolutions' taps and
the attention) moves that answer by tenths, up to 1.4 in 448 answers
read, where an answer with none is off by hundredths (``PERF.md`` has
the readings, and the witness: the reference with its products'
operands rounded to bfloat16 reads the same tenths, and hundredths on
every answer once the float32 reference's picks are forced on it).  The
reference's router margins at a history's last eight events do not
tell the two apart (tried, PR 28: correlation -0.19), so no class of
answers is held tighter than the rest.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, Sequence, Tuple

import numpy as np

from benchmark import compare, datagen_seq, reference_seq


def numbers(config: Dict[str, Any], seed: int,
            samples: Sequence[Tuple[int, int, int, Any]]
            ) -> Dict[str, float]:
    """``samples``: (user, events the user has once the turn is applied,
    num, the answer as served)."""
    if not samples:
        return {"malformed": float("inf"), "unordered": 0.0,
                "score_abs_err_p50": float("inf"),
                "score_abs_err_p90": float("inf"),
                "score_abs_err_max": float("inf"),
                "rank_gap_p90": float("inf"),
                "rank_gap_max": float("inf")}
    ids, scores, malformed = compare.parse_answers(
        [(u, num, answer) for u, _, num, answer in samples])
    events = datagen_seq.Events(config, seed)
    timings: Dict[str, float] = {}
    logits = reference_seq.logits_at_end(
        config, seed, [events.of(u, count) for u, count, _, _ in samples],
        timings=timings)
    print("reference_seq seconds: " + ", ".join(
        f"{k} {v:.1f}" for k, v in timings.items()), file=sys.stderr)
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
    print("compare_seq answers: " + json.dumps({
        "err": err.round(5).tolist(), "gap": gap.round(5).tolist()}),
        file=sys.stderr)
    return {
        "malformed": float(malformed),
        "unordered": float(rising.any(axis=1).sum()),
        "score_abs_err_p50": float(np.percentile(err, 50)),
        "score_abs_err_p90": float(np.percentile(err, 90)),
        "score_abs_err_max": float(err.max()),
        "rank_gap_p90": float(np.percentile(gap, 90)),
        "rank_gap_max": float(gap.max()),
    }


def control_answers(config: Dict[str, Any], seed: int,
                    samples: Sequence[Tuple[int, int, int]], weight_dtype
                    ) -> list:
    """The reference in the program's place with its weights rounded to
    ``weight_dtype``, shaped as served answers."""
    events = datagen_seq.Events(config, seed)
    logits = reference_seq.logits_at_end(
        config, seed, [events.of(u, count) for u, count, _ in samples],
        weight_dtype=weight_dtype)
    out = []
    for (u, count, num), row in zip(samples, logits):
        top = np.argsort(-row)[:num]
        out.append((u, count, num, {"itemScores": [
            {"item": f"i{int(i)}", "score": float(row[i])} for i in top]}))
    return out
