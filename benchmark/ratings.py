"""Explicit ratings from ``--seed`` at a published shape: both sides'
degree sequences follow the statistics the configuration gives
(``"ratings"``: median, mean, largest and smallest degree of the users
and of the items, and the star histogram), no (user, item) pair repeats,
and the degree histograms are the same for every seed.  The ALS
program's bucket plan, and with it the compiled loop, depends on those
histograms; a seed that changed them would compile inside every run's
set-up and change the work.

Degrees: the quantiles of a log-normal truncated to [min, max] with the
given median, its width solved so that the degrees sum to ``n_ratings``
(host, a few hundred thousand numbers).

Pairs: the users of one degree form a class; class by class, heaviest
first, every user takes the items of largest remaining degree (Ryser's
construction; ``plan``, on the host, a few seconds), and the class's
ratings are dealt to its users in turn (``_deal``, one jitted call), so
no pair repeats and both sides' degrees are exact; the tests check
both.  Names are given once (two permutations from the
configuration's ``shape_seed``): the ALS program bakes per-row plan
arrays into its compiled loop, so an id keeps its degree for every seed
or every seed compiles anew (seen on the chip, PR 24).  The seed decides
which user of a class sits in which of the class's places, so who rated
what changes while every id's degree stays, and draws the stars.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.datagen import seed_key


def degree_sequence(n: int, total: int, law: Dict[str, Any]) -> np.ndarray:
    """``n`` degrees, falling, that sum to ``total``: the quantiles of a
    log-normal truncated to ``[law["min"], law["max"]]`` whose median is
    ``law["median"]``; the width is solved for the sum.  The largest is
    then set to ``max`` and the smallest to ``min`` (the source states
    both), and the last few units go to the rows around the middle."""
    from scipy.special import ndtr, ndtri

    lo, hi = int(law.get("min", 1)), int(law["max"])
    u = ((np.arange(n) + 0.5) / n)[::-1]
    log_med = np.log(float(law["median"]))

    def at(sigma):
        # Truncation moves the median; a few fixed-point steps put it
        # back on the stated one.
        mu = log_med
        for _ in range(8):
            p_lo = ndtr((np.log(lo) - mu) / sigma)
            p_hi = ndtr((np.log(hi) - mu) / sigma)
            mu = log_med - sigma * ndtri(p_lo + 0.5 * (p_hi - p_lo))
        return np.exp(mu + sigma * ndtri(p_lo + u * (p_hi - p_lo)))

    a, b = 0.05, 8.0
    if not at(a).sum() <= total <= at(b).sum():
        raise ValueError(f"no log-normal of median {law['median']} "
                         f"truncated to [{lo}, {hi}] gives {n} degrees "
                         f"that sum to {total}")
    for _ in range(60):
        mid = 0.5 * (a + b)
        if at(mid).sum() < total:
            a = mid
        else:
            b = mid
    deg = np.clip(np.floor(at(a)), lo, hi).astype(np.int64)
    deg[0], deg[-1] = hi, lo
    # A few units off: one each, up or down, for the rows nearest the
    # middle that have room (never the first or the last).
    while (short := int(total - deg.sum())) != 0:
        step = 1 if short > 0 else -1
        room = 1 + np.flatnonzero((deg[1:-1] < hi) if step > 0
                                  else (deg[1:-1] > lo))
        if not len(room):
            raise ValueError("the degree sequence cannot reach the total")
        nearest = room[np.argsort(np.abs(room - n // 2), kind="stable")]
        deg[nearest[:abs(short)]] += step
    return np.sort(deg)[::-1].copy()


def degree_sequences(config: Dict[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
    law = config["ratings"]
    return (degree_sequence(config["n_users"], config["n_ratings"],
                            law["user_degrees"]),
            degree_sequence(config["n_items"], config["n_ratings"],
                            law["item_degrees"]))


def plan(user_deg: np.ndarray, item_deg: np.ndarray) -> Dict[str, np.ndarray]:
    """The host's part of the pairing: for each class of users of one
    degree d (m of them), heaviest class first, how many of the class's
    m*d ratings each item takes.  Every user of the class takes the items
    of largest remaining degree (Ryser's construction, a whole class at
    a time): item j gives ``clip(left_j - level, 0, m)`` with the level
    set so that the class is served exactly."""
    degrees, members = np.unique(user_deg, return_counts=True)
    degrees, members = degrees[::-1], members[::-1]
    left = item_deg.astype(np.int64).copy()
    counts = np.zeros((len(degrees), len(item_deg)), np.int32)
    for k, (d, m) in enumerate(zip(degrees, members)):
        need = int(d) * int(m)
        if np.minimum(left, m).sum() < need:
            raise ValueError(f"the {m} users of degree {d} want {need} "
                             "ratings; the items have fewer left")
        lo, hi = -1, int(left.max())     # give(lo) >= need > give(hi)
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if np.clip(left - mid, 0, m).sum() >= need:
                lo = mid
            else:
                hi = mid
        give = np.clip(left - hi, 0, m)
        # Between the two levels each item differs by at most one: the
        # first few that can give one more do.
        more = np.flatnonzero(np.clip(left - lo, 0, m) > give)
        give[more[:need - int(give.sum())]] += 1
        left -= give
        counts[k] = give
    return {"degrees": degrees, "members": members, "counts": counts}


@functools.partial(jax.jit, static_argnames=("n",))
def _deal(counts, degrees, members, *, n: int):
    """(user, item) of all ``n`` ratings.  A class's ratings lie item by
    item (item j ``counts[k, j]`` times in a row) and go to the class's
    m users in turn: an item's run is at most m long, so it meets m
    different users, and a user's ratings lie m apart, so in m different
    runs."""
    n_classes, n_items = counts.shape
    size = degrees * members
    start = jnp.cumsum(size) - size
    first_user = jnp.cumsum(members) - members
    t = jnp.arange(n, dtype=jnp.int32)
    k = jnp.repeat(jnp.arange(n_classes, dtype=jnp.int32), size,
                   total_repeat_length=n)
    users = first_user[k] + (t - start[k]) % members[k]
    items = jnp.repeat(
        jnp.tile(jnp.arange(n_items, dtype=jnp.int32), n_classes),
        counts.reshape(-1), total_repeat_length=n)
    return users.astype(jnp.int32), items


@functools.partial(jax.jit, static_argnames=("n_users", "n_items"))
def _relabel(shape_key, key, users_t, items_t, user_class, star_cdf, *,
             n_users: int, n_items: int):
    ku, ki = jax.random.split(shape_key)
    kc, ks = jax.random.split(key)
    # The seed: which user of a class sits in which of its places.
    within = jnp.lexsort((jax.random.uniform(kc, (n_users,)), user_class))
    users = jax.random.permutation(ku, n_users)[within[users_t]]
    items = jax.random.permutation(ki, n_items)[items_t]
    u = jax.random.uniform(ks, users_t.shape)
    stars = 1 + jnp.minimum(jnp.searchsorted(star_cdf, u, side="right"),
                            star_cdf.shape[0] - 1)
    return (users.astype(jnp.int32), items.astype(jnp.int32),
            stars.astype(jnp.float32))


def ratings_coo(seed: int, config: Dict[str, Any]
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(user, item, stars) COO on the device."""
    p = plan(*degree_sequences(config))
    members = jnp.asarray(p["members"], jnp.int32)
    users_t, items_t = _deal(jnp.asarray(p["counts"]),
                             jnp.asarray(p["degrees"], jnp.int32), members,
                             n=int(config["n_ratings"]))
    user_class = jnp.repeat(jnp.arange(len(p["members"]), dtype=jnp.int32),
                            members, total_repeat_length=config["n_users"])
    hist = np.asarray(config["ratings"]["stars"], np.float64)
    cdf = jnp.asarray(np.cumsum(hist / hist.sum()), jnp.float32)
    return _relabel(seed_key(config["ratings"].get("shape_seed", 0), 7),
                    seed_key(seed, 8), users_t, items_t, user_class, cdf,
                    n_users=config["n_users"], n_items=config["n_items"])
