"""What decides ``correct`` in the Mamba-2 / no-position attention cells:
sampled answers of the timed path against the plain reference's logits
after the user's WHOLE history up to that turn (``reference_granite_h``),
so set-up's prefill and every earlier turn, through both kinds of state,
have to add up to one forward pass.  Several sampled turns of one user
are read from ONE pass over that user's longest sampled history.

The numbers are ``compare_seq``'s, defined there and computed by
``compare_sala.numbers`` from the logits handed to it: ``malformed``,
``unordered``, and over the sampled answers the median, 90th percentile
and widest ERROR (widest |served score - reference logit of that item|
over an answer's items) and the 90th percentile and widest RANK GAP (how
far the reference's logit of a served item lies below the reference's
``num``-th best).  Absolute: the embedding rows have unit norm, the last
norm makes |h| = sqrt(d) and ``logits_scaling`` divides by 8, so logits
have spread 1 / 8, and so have the limits' units.

No pick decides an answer here (no router, no selection): every number
moves with the arithmetic alone.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, Sequence, Tuple

import numpy as np

from benchmark import compare_sala, datagen_seq, reference_granite_h

EMPTY = compare_sala.EMPTY
as_answers = compare_sala.as_answers


def reference_logits(config: Dict[str, Any], seed: int,
                     samples: Sequence[Tuple[int, int]], **variant
                     ) -> np.ndarray:
    """[n, V] logits of the reference after ``count`` events of ``user``
    for each (user, count): one pass a user."""
    events = datagen_seq.Events(config, seed)
    users = sorted({u for u, _ in samples})
    longest = {u: max(c for v, c in samples if v == u) for u in users}
    timings: Dict[str, float] = {}
    rows = reference_granite_h.logits_at(
        config, seed, [events.of(u, longest[u]) for u in users],
        [[c - 1 for v, c in samples if v == u] for u in users],
        timings=timings, **variant)
    print("reference_granite_h seconds: " + ", ".join(
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
    if logits is None:
        logits = reference_logits(config, seed,
                                  [(u, c) for u, c, _, _ in samples])
    return compare_sala.numbers(config, seed, samples, logits=logits)
