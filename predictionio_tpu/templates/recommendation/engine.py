"""Recommendation template — ALS personal recommendations.

Reference: examples/scala-parallel-recommendation (SURVEY.md §2.2) — the
canonical MLlib-ALS template.  Contract preserved:

- events: ``rate`` (user→item, properties.rating) and ``buy`` (user→item,
  implicit, treated as rating 4.0)
- query JSON: ``{"user": "u1", "num": 4}``
- result JSON: ``{"itemScores": [{"item": "i1", "score": 1.2}, ...]}``
- algorithm params: rank / numIterations / lambda / alpha / implicitPrefs /
  seed — the MLlib ``ALS.train`` knob set

Substrate: :mod:`predictionio_tpu.models.als` (batched XLA normal
equations) instead of Spark MLlib; serving top-K is one MXU matmul +
``lax.top_k`` rather than a JVM loop over ``recommendProducts``.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import os
import threading
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from predictionio_tpu.controller import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    ItemScoreColumns,
    Preparator,
    RuntimeContext,
    WarmStartFallback,
)
from predictionio_tpu.controller.params import Params
from predictionio_tpu.data.event import BiMap
from predictionio_tpu.models import als as als_lib
from predictionio_tpu.obs import dispatch_stage
from predictionio_tpu.obs.quality import Scorecard, scorecard_from_matrix
from predictionio_tpu.obs.recall import (
    RecallScorecard,
    build_recall_scorecard,
)
from predictionio_tpu.retrieval import (
    IVFIndex,
    PQCodebook,
    Retriever,
    build_train_index,
    build_train_pq,
    cached_retriever,
    hit_columns,
)

__all__ = [
    "engine",
    "Query",
    "ItemScore",
    "PredictedResult",
    "Ratings",
    "DataSourceParams",
    "RecommendationDataSource",
    "RecommendationPreparator",
    "ALSAlgorithmParams",
    "ALSAlgorithm",
    "ALSModelWrapper",
]


# -- query / result (JSON contract, Appendix A) -----------------------------

@dataclasses.dataclass
class Query:
    user: str
    num: int = 10


@dataclasses.dataclass
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass
class PredictedResult:
    # From ``batch_predict`` an ItemScoreColumns: a list of ItemScore to
    # whoever reads it, two columns to the JSON.
    itemScores: Sequence[ItemScore]  # noqa: N815 — reference JSON field name


# -- training data ----------------------------------------------------------

@dataclasses.dataclass
class Ratings:
    """COO ratings plus the string↔int entity indexes.

    Reference: the template's ``TrainingData(ratings: RDD[Rating])`` — here
    the RDD is columnar numpy destined for device transfer, and the BiMaps
    (reference: ``ALSModel`` members userStringIntMap/itemStringIntMap)
    travel with the data.
    """

    user_ids: np.ndarray
    item_ids: np.ndarray
    ratings: np.ndarray
    user_index: BiMap
    item_index: BiMap
    # Serving fold-in context (ISSUE 10): the trained wrapper needs to
    # know WHERE its events live and how to weigh them so an unseen
    # user's recent events can be solved in at predict time.  Filled by
    # the datasource; defaults keep older pickles/tests loading.
    app_name: Optional[str] = None
    event_names: Sequence[str] = ()
    buy_rating: float = 4.0


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    appName: str  # noqa: N815 — engine.json key parity
    eventNames: Sequence[str] = ("rate", "buy")  # noqa: N815
    buyRating: float = 4.0  # noqa: N815 — implicit "buy" becomes this rating
    evalK: Optional[int] = None  # noqa: N815 — folds for pio eval
    evalQueryNum: int = 10  # noqa: N815
    seed: int = 3


class RecommendationDataSource(DataSource):
    """Reads rate/buy events into COO ratings (reference: DataSource.scala)."""

    params_class = DataSourceParams

    def _read(self, ctx: RuntimeContext) -> Ratings:
        p: DataSourceParams = self.params
        table = ctx.event_store.find_columnar(
            p.appName,
            entity_type="user",
            target_entity_type="item",
            event_names=list(p.eventNames),
            # Training is order-independent (the reference's RDD scan is
            # unordered too) and only these four columns feed the COO —
            # both save seconds at the ML-25M shape.
            ordered=False,
            columns=["event", "entity_id", "target_entity_id",
                     "properties_json"],
        )
        # Columnar end-to-end (VERDICT.md round-1 item 4): dictionary-encode
        # ids and regex-extract the rating — Arrow kernels, no Python loop
        # over events.
        from predictionio_tpu.data.columnar import (
            encode_ids, event_mask, numeric_property,
        )

        user_ids, user_index = encode_ids(table.column("entity_id"))
        item_ids, item_index = encode_ids(table.column("target_entity_id"))
        is_rate = event_mask(table, ["rate"])
        raw = numeric_property(table, "rating", default=np.nan)
        ratings = np.where(is_rate, raw, p.buyRating).astype(np.float32)
        # Decided semantic (round-2 verdict item 8, PARITY.md): a `rate`
        # event with no numeric `rating` property is DROPPED with a
        # warning — never trained as rating 0.0 (a strong negative signal
        # in explicit ALS).  Upstream's DataSource would throw and fail
        # the whole train; dropping keeps one malformed producer from
        # taking down retraining.
        bad = is_rate & ~np.isfinite(ratings)
        if bad.any():
            import logging

            logging.getLogger(__name__).warning(
                "dropping %d rate event(s) without a numeric 'rating' "
                "property", int(bad.sum()))
            keep = ~bad
            user_ids, item_ids = user_ids[keep], item_ids[keep]
            ratings = ratings[keep]
        return Ratings(
            user_ids=user_ids,
            item_ids=item_ids,
            ratings=ratings,
            user_index=user_index,
            item_index=item_index,
            app_name=p.appName,
            event_names=tuple(p.eventNames),
            buy_rating=p.buyRating,
        )

    def read_training(self, ctx: RuntimeContext) -> Ratings:
        return self._read(ctx)

    def read_eval(self, ctx: RuntimeContext):
        """K-fold split by rating index; queries ask top-N for each user with
        held-out positives as actuals (reference: DataSource.readEval)."""
        p: DataSourceParams = self.params
        if not p.evalK:
            return []
        data = self._read(ctx)
        n = len(data.user_ids)
        rng = np.random.default_rng(p.seed)
        fold_of = rng.integers(0, p.evalK, n)
        folds = []
        for k in range(p.evalK):
            train_sel = fold_of != k
            test_sel = ~train_sel
            td = Ratings(
                user_ids=data.user_ids[train_sel],
                item_ids=data.item_ids[train_sel],
                ratings=data.ratings[train_sel],
                user_index=data.user_index,
                item_index=data.item_index,
            )
            inv_user = data.user_index.inverse
            inv_item = data.item_index.inverse
            qa: Dict[str, set] = {}
            for u, i, r in zip(data.user_ids[test_sel], data.item_ids[test_sel],
                               data.ratings[test_sel]):
                if r > 0:
                    qa.setdefault(inv_user[u], set()).add(inv_item[i])
            queries = [
                (Query(user=u, num=p.evalQueryNum), sorted(actual))
                for u, actual in sorted(qa.items())
            ]
            folds.append((td, None, queries))
        return folds


class RecommendationPreparator(Preparator):
    """Reference: Preparator.scala — identity over the ratings."""

    def prepare(self, ctx: RuntimeContext, training_data: Ratings) -> Ratings:
        return training_data


# -- algorithm --------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    numIterations: int = 10  # noqa: N815 — MLlib knob names
    lambda_: float = 0.01
    alpha: float = 1.0
    implicitPrefs: bool = False  # noqa: N815
    maxDegree: Optional[int] = None  # noqa: N815 — ragged truncation cap
    seed: Optional[int] = None
    # Mesh runs: "auto" row-shards the persistent factor matrices once
    # they exceed the HBM threshold (blocked ALS, SURVEY §2.4 row 2);
    # "replicated"/"sharded" force.  Meshless runs ignore it.
    factorSharding: str = "auto"  # noqa: N815
    # Blocked runs: "auto" windows each HBM chunk's factor gather to the
    # rows it touches (transient ∝ working set, not matrix size);
    # True/False force.  Ignored unless the factors are sharded.
    gatherWindow: Union[bool, str] = "auto"  # noqa: N815


def _fold_in_enabled() -> bool:
    from predictionio_tpu.config import env_bool

    return env_bool(os.environ.get("PIO_FOLD_IN"), True)


def _env_int(key: str, default: int) -> int:
    try:
        return int(os.environ.get(key, str(default)) or default)
    except ValueError:
        return default


def _fold_metric():
    from predictionio_tpu.obs import get_registry

    return get_registry().counter(
        "pio_fold_in_total",
        "Serve-time ALS fold-in attempts by outcome "
        "(cached/solved/no_events/unavailable).", ("result",))


# Negative fold-in cache TTL: a user with NO mappable events is cached
# too (an unknown-user query storm must not pay one event-store read —
# a remote RPC on pioserver storage — per request inside the cohort
# dispatch), but only briefly: their first events should become
# recommendations within seconds, not a generation lifetime.
_FOLD_NEG_TTL_S = 30.0


# -- durable shared fold-in cache (ISSUE 15) --------------------------------
#
# N fleet instances each solving the SAME visitor is wasted work and a
# restarted instance re-solves everyone from zero.  Solved factors are
# therefore persisted (best-effort) in the storage layer's shared KV,
# keyed by (factor fingerprint, user): the fingerprint — a SHA-1 of the
# generation's item-factor bytes — identifies the EXACT matrix the solve
# is valid against, so two instances serving the same promoted pickle
# share entries while a rollback/reload to different factors naturally
# misses.  The local per-generation LRU stays the read-through layer;
# the KV is only consulted on an LRU miss.  Entries carry the event-time
# watermark of the newest event they were solved from — a shared hit may
# be staler than a fresh solve would be (documented README caveat; the
# next refresh trains the user in either way).  Negative outcomes are
# never shared: "no events yet" goes stale in seconds.

def _fold_shared_enabled() -> bool:
    from predictionio_tpu.config import env_bool

    return env_bool(os.environ.get("PIO_FOLD_IN_SHARED"), True)


def _fold_encode(vec: np.ndarray, watermark_us: Optional[int]) -> bytes:
    """Header carries the SOLVE time (``ts``, epoch s — the max-age
    gate's anchor: age of the entry, so a re-solve refreshes it) and the
    event-time watermark of the newest event consumed (``wm`` — the
    operator-facing freshness record)."""
    import json as _json
    import time as _time

    v = np.ascontiguousarray(vec, dtype=np.float32)
    head = _json.dumps({"n": int(v.shape[0]), "wm": watermark_us,
                        "ts": round(_time.time(), 3)},
                       separators=(",", ":")).encode()
    return head + b"\n" + v.tobytes()


def _fold_decode(blob: bytes
                 ) -> Optional[Tuple[np.ndarray, Optional[float]]]:
    """(vector, solve-time epoch-s) — the solve time anchors the
    max-age gate."""
    import json as _json

    try:
        head, raw = blob.split(b"\n", 1)
        meta = _json.loads(head)
        vec = np.frombuffer(raw, dtype=np.float32)
        if vec.shape[0] != int(meta["n"]):
            return None
        ts = meta.get("ts")
        return vec.copy(), (float(ts) if ts is not None else None)
    except Exception:
        return None


def _fold_shared_max_age_s() -> float:
    """``PIO_FOLD_IN_SHARED_MAX_AGE_S`` (0 = accept any age): a shared
    entry SOLVED longer ago than this is treated as a MISS so the
    reader re-solves (picking up any events that arrived since).  Anchor
    is the solve time, NOT the user's newest event time — gating on
    event recency would permanently expire every idle user's entry and
    churn re-solves exactly where sharing is safest."""
    try:
        return float(os.environ.get("PIO_FOLD_IN_SHARED_MAX_AGE_S",
                                    "0") or 0)
    except ValueError:
        return 0.0


# eq=False: wrapper identity IS the model generation — keeps the object
# hashable for the weak-keyed retriever cache.
@dataclasses.dataclass(eq=False)
class ALSModelWrapper:
    """Trained factors + indexes (reference: template ALSModel).

    ``ivf`` is the optional train-time coarse index (ISSUE 8) — it rides
    INSIDE this pickle, so the staged-reload/rollback generation swap
    moves model and index as one artifact: a rollback can never serve
    generation-N factors through a generation-N+1 index (the retrieval
    facade's fingerprint check makes any future violation loud).

    Serve-time fold-in (ISSUE 10): an UNSEEN user with recent events
    gets one ridge solve against the frozen item factors
    (``models.als.fold_in``) instead of a cold-start empty result.  The
    folded factor lives in a bounded per-generation LRU — per-process
    and ephemeral by design; the next refresh trains the user in and
    makes it durable.
    """

    model: als_lib.ALSModel
    user_index: BiMap
    item_index: BiMap
    ivf: Optional[IVFIndex] = None
    # Residual PQ codes (ISSUE 13): unlike IVF, safe for these
    # norm-variant factors WITHOUT an opt-in — the exact re-rank
    # re-scores every returned candidate, so quantization error orders
    # a shortlist but never the final top-k.  Same atomic-swap +
    # fingerprint-tripwire contract as ``ivf``.
    pq: Optional[PQCodebook] = None
    # Training-time score-distribution baseline (ISSUE 11): rides the
    # same atomic-swap contract as ``ivf`` — serving drift is judged
    # against THIS generation's own baseline.
    quality: Optional[Scorecard] = None
    # Training-time expected-recall baseline (ISSUE 16): offline
    # recall@k of THIS generation's own ivf/pq structures on a seeded
    # query sample — the online recall monitor trips on regression vs
    # this, never an absolute floor.  None when neither structure was
    # built (exact serving).  Old pickles backfill via __setstate__.
    recall: Optional[RecallScorecard] = None
    # Fold-in context (ISSUE 10), persisted with the generation.
    app_name: Optional[str] = None
    fold_event_names: Sequence[str] = ()
    buy_rating: float = 4.0
    reg: float = 0.01
    alpha: float = 1.0
    # Training-set size of this generation — the warm-start delta
    # fraction gate (ISSUE 17) compares the delta window against it.
    # Old pickles backfill 0 via __setstate__, which makes warm_start
    # decline (prev_n <= 0) rather than guess.
    n_examples: int = 0
    # Host-resident factor copies for the serving fast path: a B=1
    # predict is ~N·K MACs — orders of magnitude below one device
    # dispatch round-trip — so small batches are answered in numpy from
    # these (pulled once, lazily).  None until first host predict.
    _host: Optional[Tuple[np.ndarray, np.ndarray]] = None
    _host_uf: Optional[np.ndarray] = None

    def __post_init__(self):
        self._init_transients()

    def _init_transients(self) -> None:
        # Per-generation serving state — never pickled, dies with the
        # wrapper on reload/rollback (exactly the bounded-cache contract).
        # Values are (vector | None, monotonic-stamp): None is a TTL'd
        # negative entry (user had no usable events at stamp time).
        self._fold_cache: "collections.OrderedDict[str, tuple]" = \
            collections.OrderedDict()
        self._fold_lock = threading.Lock()
        self._event_store = None
        self._yty: Optional[np.ndarray] = None
        # Durable shared cache (ISSUE 15): the KV handle arrives at
        # post_load (the one hook that sees the serving ctx), the
        # fingerprint binds entries to THIS generation's factors.
        self._shared_kv = None
        self._fold_fp: Optional[str] = None
        self._fold_puts = 0

    def __getstate__(self):
        # serving caches are transient (a reloaded model rebuilds them;
        # the per-generation Retriever lives in retrieval.cached_retriever
        # keyed weakly on this object, so it never rides the pickle)
        d = self.__dict__.copy()
        d["_host"] = None
        d["_host_uf"] = None
        for k in ("_fold_cache", "_fold_lock", "_event_store", "_yty",
                  "_shared_kv", "_fold_fp", "_fold_puts"):
            d.pop(k, None)
        return d

    def __setstate__(self, d):
        # Backfill fields a pre-ISSUE-10 pickle lacks, then rebuild the
        # transient serving state.
        for f in dataclasses.fields(self):
            if f.name not in d and f.default is not dataclasses.MISSING:
                d[f.name] = f.default
        self.__dict__.update(d)
        self._init_transients()

    def retriever(self) -> Retriever:
        """THE serving route to the item corpus (retrieval facade):
        host/device/chunked/sharded/IVF routing, jit caches, metrics —
        one per loaded generation, dying with it."""
        # host_fn must hold the wrapper WEAKLY: the retriever is the
        # weak-keyed cache's VALUE, so a strong self capture would pin
        # its own key alive and leak every swapped-out generation.  It
        # is only ever called through a live wrapper's retriever().
        ref = weakref.ref(self)
        return cached_retriever(self, lambda: Retriever(
            self.model.item_factors,
            n_items=len(self.item_index),
            ivf=getattr(self, "ivf", None),
            pq=getattr(self, "pq", None),
            name="als",
            host_fn=lambda: ref().host_factors()[1]))

    def host_user_factors(self) -> np.ndarray:
        """User factors only — batch_predict needs just the query rows;
        pulling host_factors() there would device_get and retain the
        FULL item matrix even when a device rung serves the corpus."""
        if self._host is not None:
            return self._host[0]
        if self._host_uf is None:
            uf = jax.device_get(self.model.user_factors)
            self._host_uf = np.asarray(uf)[: len(self.user_index)]
        return self._host_uf

    def host_factors(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._host is None:
            uf, itf = jax.device_get(
                (self.model.user_factors, self.model.item_factors))
            # a post_load re-shard pads rows to the mesh size; the host
            # copies keep the true extents
            self._host = (uf[:len(self.user_index)],
                          itf[:len(self.item_index)])
        return self._host

    # -- serve-time fold-in (ISSUE 10) ---------------------------------

    def fold_in_user(self, user: str) -> Optional[np.ndarray]:
        """Solve an unseen user's factor from their recent events against
        the frozen item factors; None when fold-in is off, no event
        store is attached (non-serving contexts like eval), or the user
        has no mappable events.  Cached per generation (bounded LRU) so
        repeat visitors never re-solve — the cache dies with the
        wrapper on reload/rollback, exactly when the factors it was
        solved against do."""
        import time as _time

        es = getattr(self, "_event_store", None)
        app = getattr(self, "app_name", None)
        if es is None or not app or not _fold_in_enabled():
            return None
        with self._fold_lock:
            hit = self._fold_cache.get(user)
            if hit is not None:
                vec, t = hit
                if vec is not None or \
                        _time.monotonic() - t < _FOLD_NEG_TTL_S:
                    self._fold_cache.move_to_end(user)
                    _fold_metric().inc(result="cached")
                    return vec
                del self._fold_cache[user]  # expired negative: re-check
        # Shared read-through (ISSUE 15): another instance may already
        # have solved this visitor against the SAME factors — one KV get
        # beats an event-store read plus a ridge solve, and a restarted
        # instance warms from the fleet's work.
        shared_vec = self._fold_shared_get(user)
        if shared_vec is not None:
            self._fold_store(user, shared_vec)
            _fold_metric().inc(result="shared")
            return shared_vec
        from predictionio_tpu.obs import span

        try:
            with span("fold_in", user=user):
                events = es.find_by_entity(
                    app, "user", user,
                    event_names=list(self.fold_event_names) or None,
                    target_entity_type="item",
                    limit=_env_int("PIO_FOLD_IN_EVENTS", 50), latest=True)
        except Exception:
            # A storage blip must degrade to a cold-start answer, never
            # fail the cohort this member rides in.
            logging.getLogger(__name__).debug("fold-in event read failed",
                                              exc_info=True)
            _fold_metric().inc(result="unavailable")
            return None
        ids: List[int] = []
        vals: List[float] = []
        watermark_us: Optional[int] = None
        for ev in events:
            idx = self.item_index.get(ev.target_entity_id)
            if idx is None:
                continue  # item unknown to this generation
            if ev.event == "rate":
                r = ev.properties.get("rating")
                if not isinstance(r, (int, float)) or not np.isfinite(r):
                    continue  # same drop rule as the training read
                vals.append(float(r))
            else:
                vals.append(float(self.buy_rating))
            ids.append(int(idx))
            from predictionio_tpu.data.storage.base import epoch_us

            us = epoch_us(ev.event_time)
            if us is not None and (watermark_us is None
                                   or us > watermark_us):
                watermark_us = us
        if not ids:
            self._fold_store(user, None)
            _fold_metric().inc(result="no_events")
            return None
        _, itf = self.host_factors()
        if self.model.implicit and self._yty is None:
            f = itf.astype(np.float64)
            self._yty = f.T @ f
        vec = als_lib.fold_in(
            itf, np.asarray(ids), np.asarray(vals, np.float32),
            reg=float(getattr(self, "reg", 0.01)),
            alpha=float(getattr(self, "alpha", 1.0)),
            implicit=self.model.implicit, yty=self._yty)
        self._fold_store(user, vec)
        self._fold_shared_put(user, vec, watermark_us)
        _fold_metric().inc(result="solved")
        return vec

    # -- durable shared cache plumbing (ISSUE 15) ----------------------

    def _fold_ns(self) -> str:
        """KV namespace binding entries to THIS generation's factors:
        two instances serving the same promoted pickle hash identical
        bytes and share; different factors (rollback, refresh) miss."""
        if self._fold_fp is None:
            import hashlib

            _, itf = self.host_factors()
            self._fold_fp = hashlib.sha1(
                np.ascontiguousarray(itf, dtype=np.float32).tobytes()
            ).hexdigest()[:16]
        return f"foldin:{self._fold_fp}"

    def _fold_shared_get(self, user: str) -> Optional[np.ndarray]:
        kv = getattr(self, "_shared_kv", None)
        if kv is None or not _fold_shared_enabled():
            return None
        try:
            blob = kv.get(self._fold_ns(), user)
        except Exception:
            # A KV blip must never fail the request — the local solve
            # path below still answers.
            logging.getLogger(__name__).debug(
                "shared fold-in get failed", exc_info=True)
            return None
        if not blob:
            return None
        decoded = _fold_decode(blob)
        if decoded is None:
            return None
        vec, solved_at = decoded
        if vec.shape[0] != self.model.item_factors.shape[-1]:
            return None
        max_age = _fold_shared_max_age_s()
        if max_age > 0 and solved_at is not None:
            import time as _time

            if _time.time() - solved_at > max_age:
                return None  # stale solve: miss → re-solve fresh
        return vec

    def _fold_shared_put(self, user: str, vec: np.ndarray,
                         watermark_us: Optional[int]) -> None:
        """Best-effort write-through; every 256th put prunes the
        namespace to ``PIO_FOLD_IN_SHARED_CAP`` so the shared cache
        stays bounded without any instance owning an eviction thread."""
        kv = getattr(self, "_shared_kv", None)
        if kv is None or not _fold_shared_enabled():
            return
        try:
            ns = self._fold_ns()
            kv.put(ns, user, _fold_encode(vec, watermark_us))
            self._fold_puts += 1
            if self._fold_puts % 256 == 0:
                kv.prune(ns, _env_int("PIO_FOLD_IN_SHARED_CAP", 100_000))
        except Exception:
            logging.getLogger(__name__).debug(
                "shared fold-in put failed", exc_info=True)

    def _fold_store(self, user: str, vec: Optional[np.ndarray]) -> None:
        """Bounded-LRU insert; ``vec=None`` is the (TTL'd) negative
        entry for a user with no usable events."""
        import time as _time

        with self._fold_lock:
            self._fold_cache[user] = (vec, _time.monotonic())
            self._fold_cache.move_to_end(user)
            cap = _env_int("PIO_FOLD_IN_CACHE", 10000)
            while len(self._fold_cache) > max(cap, 1):
                self._fold_cache.popitem(last=False)

    def post_load(self, ctx) -> None:
        """Serving-time re-parallelization (reference: SURVEY §3.2, P
        models re-parallelize in CreateServer): with a serving mesh and
        a corpus above ``PIO_SERVE_SHARD_ABOVE`` items, row-shard the
        item matrix over the ``data`` axis at model-load time — the
        facade's :meth:`~predictionio_tpu.retrieval.Retriever.maybe_shard`
        pads host-side and stages shard-by-shard, and predict then
        routes through the mesh-sharded exact rung (per-chip memory and
        score work scale 1/n_chips).

        Also the fold-in attachment point (ISSUE 10): ``post_load`` is
        the one hook that sees the serving RuntimeContext, so the
        wrapper stashes the event store here — transient, never
        pickled — and ``batch_predict`` can then solve unseen users in
        from their recent events."""
        store = getattr(ctx, "event_store", None)
        if store is not None:
            self._event_store = store
        # Durable fold-in cache (ISSUE 15): stash the shared KV when the
        # serving storage supports it — read-through on LRU misses,
        # write-through after solves.  Unsupported backends (parquetlog)
        # leave it None and fold-in stays LRU-only, exactly as before.
        storage = getattr(ctx, "storage", None)
        if storage is not None and _fold_shared_enabled():
            try:
                self._shared_kv = storage.get_kv()
            except Exception:
                self._shared_kv = None
        mesh = getattr(ctx, "mesh", None)
        if mesh is None:
            return
        r = self.retriever()
        if r.maybe_shard(mesh):
            from jax.sharding import NamedSharding, PartitionSpec as P

            from predictionio_tpu.parallel.mesh import put_sharded

            # Sync the wrapper's reference to the facade's sharded copy
            # so the pre-shard whole-corpus device array can be freed.
            self.model.item_factors = r.vecs
            # queries gather a handful of user rows per request —
            # replicated
            self.model.user_factors = put_sharded(
                np.asarray(jax.device_get(self.model.user_factors)),
                mesh, NamedSharding(mesh, P()))


def _warm_ridge_sweep(target: np.ndarray, frozen: np.ndarray,
                      row_ids: np.ndarray, col_ids: np.ndarray,
                      vals: np.ndarray, *, reg: float, alpha: float,
                      implicit: bool) -> None:
    """One half-sweep of ALS warm-start continuation (ISSUE 17): re-solve
    each delta-touched row of ``target`` against the frozen complement —
    the same normal equation as :func:`models.als.fold_in`, but anchored
    at the row's carried factor (``λn·u_prev`` on the right-hand side)
    so one new event updates a trained row instead of wiping it."""
    order = np.argsort(row_ids, kind="stable")
    rs = row_ids[order]
    cs = col_ids[order]
    vs = vals[order]
    starts = np.flatnonzero(np.r_[True, rs[1:] != rs[:-1]])
    f64 = frozen.astype(np.float64)
    k = f64.shape[1]
    yty = f64.T @ f64 if implicit else None
    bounds = list(starts) + [len(rs)]
    eye = np.eye(k)
    for a, b in zip(bounds[:-1], bounds[1:]):
        row = int(rs[a])
        y = f64[cs[a:b]]
        r = vs[a:b]
        if implicit:
            w = alpha * np.abs(r)
            c = (1.0 + w) * (r > 0)
            mat = yty + (y * w[:, None]).T @ y
            rhs = y.T @ c
        else:
            mat = y.T @ y
            rhs = y.T @ r
        lam = reg * (b - a)
        mat = mat + lam * eye
        rhs = rhs + lam * target[row].astype(np.float64)
        try:
            sol = np.linalg.solve(mat, rhs)
        except np.linalg.LinAlgError:
            sol = np.linalg.lstsq(mat, rhs, rcond=None)[0]
        target[row] = sol.astype(np.float32)


class ALSAlgorithm(Algorithm):
    params_class = ALSAlgorithmParams

    def train(self, ctx: RuntimeContext, prepared_data: Ratings) -> ALSModelWrapper:
        p: ALSAlgorithmParams = self.params
        if len(prepared_data.user_ids) == 0:
            raise ValueError(
                "No rating events found — check appName/eventNames "
                "(reference template raises the same assertion)."
            )
        cfg = als_lib.ALSConfig(
            rank=p.rank,
            iterations=p.numIterations,
            reg=p.lambda_,
            alpha=p.alpha,
            implicit=p.implicitPrefs,
            max_degree=p.maxDegree,
            seed=p.seed if p.seed is not None else ctx.seed,
            factor_sharding=p.factorSharding,
            gather_window=p.gatherWindow,
        )
        # `pio train --checkpoint-dir D --checkpoint-every N` (or the
        # PIO_CHECKPOINT_* env pair) makes a killed train resume from the
        # last complete sweep, bitwise-equal to an uninterrupted run.
        ck_dir = os.environ.get("PIO_CHECKPOINT_DIR")
        ck_every = int(os.environ.get("PIO_CHECKPOINT_EVERY", "0") or 0)
        model = als_lib.train_als(
            prepared_data.user_ids,
            prepared_data.item_ids,
            prepared_data.ratings,
            n_users=len(prepared_data.user_index),
            n_items=len(prepared_data.item_index),
            config=cfg,
            mesh=ctx.mesh,
            checkpoint_dir=(os.path.join(ck_dir, "als") if ck_dir else None),
            save_every=ck_every,
        )
        itf_host = np.asarray(
            jax.device_get(model.item_factors))[: len(prepared_data.item_index)]
        uf_host = np.asarray(
            jax.device_get(model.user_factors))[: len(prepared_data.user_index)]
        # Train-time coarse index — serialized with the model so the
        # generation swap moves both atomically.  Raw ALS factors
        # carry popularity-scaled norms (a poor IVF fit: cells
        # partition by direction), so the index builds only under an
        # explicit PIO_IVF=on, never auto.
        ivf_idx = build_train_index(itf_host, name="als", seed=cfg.seed,
                                    require_explicit=True)
        # Residual PQ codes (ISSUE 13): auto-gated like the deep
        # templates — the exact re-rank makes quantization safe for
        # norm-variant factors, so no explicit opt-in is required.
        pq = build_train_pq(itf_host, name="als", ivf=ivf_idx,
                            seed=cfg.seed)
        return ALSModelWrapper(
            model=model,
            user_index=prepared_data.user_index,
            item_index=prepared_data.item_index,
            ivf=ivf_idx,
            pq=pq,
            # Quality baseline (ISSUE 11): top-K reconstruction scores
            # of a seeded user sample against the item factors — the
            # population serving's itemScores come from.
            quality=scorecard_from_matrix(uf_host, itf_host,
                                          seed=cfg.seed or 0, name="als"),
            # Expected-recall baseline (ISSUE 16): offline recall of the
            # structures just built, through the same search paths and
            # nprobe/rerank formulas serving will use.
            recall=build_recall_scorecard(uf_host, itf_host, ivf=ivf_idx,
                                          pq=pq, seed=cfg.seed or 0,
                                          name="als"),
            # Fold-in context (ISSUE 10): where this generation's events
            # live + the solve hyper-parameters it was trained with, so
            # serve-time fold-in solves the SAME normal equation the
            # training sweep would.
            app_name=getattr(prepared_data, "app_name", None),
            fold_event_names=tuple(
                getattr(prepared_data, "event_names", ()) or ()),
            buy_rating=float(getattr(prepared_data, "buy_rating", 4.0)),
            reg=float(p.lambda_),
            alpha=float(p.alpha),
            n_examples=len(prepared_data.ratings),
        )

    def warm_start(self, ctx: RuntimeContext, prepared_delta: Ratings,
                   prev_model: ALSModelWrapper, warm: Any) -> ALSModelWrapper:
        """Delta warm-start (ISSUE 17) — the one refresh rung ALS lacked.

        Factor-init + reduced-sweep retrain: the previous generation's
        factors carry over, delta-new entities get fresh
        normal/sqrt(rank) rows (the :func:`models.als._init_factors`
        scale), and a reduced number of host ridge half-sweeps re-solve
        ONLY the delta-touched rows against the frozen complement,
        anchored at their carried values.  Gates mirror the deep
        templates (DLRM/two-tower): config compatibility, the shared
        delta-fraction gate, and an eval-regression check — RMSE on a
        delta sample restricted to (user, item) pairs the previous
        generation already knew, so before/after is apples-to-apples.
        Any doubt raises :class:`WarmStartFallback` → full retrain.
        """
        log = logging.getLogger(__name__)
        p: ALSAlgorithmParams = self.params
        prev_n = int(getattr(prev_model, "n_examples", 0))
        delta_n = int(len(prepared_delta.ratings))
        if (prev_model.model.rank != p.rank
                or prev_model.model.implicit != p.implicitPrefs
                or float(getattr(prev_model, "reg", p.lambda_))
                != float(p.lambda_)
                or float(getattr(prev_model, "alpha", p.alpha))
                != float(p.alpha)):
            raise WarmStartFallback("algorithm config changed")
        max_frac = getattr(warm, "max_delta_fraction", 0.5)
        if prev_n <= 0 or delta_n > max_frac * prev_n:
            raise WarmStartFallback(
                f"delta window too large for continuation ({delta_n} "
                f"events vs {prev_n} trained; max fraction {max_frac:g})")
        if delta_n == 0:
            # Nothing new: carry the generation forward.  A FRESH wrapper
            # (replace() re-runs __post_init__) because wrapper identity
            # is the serving generation — caches must not be shared.
            return dataclasses.replace(prev_model)
        seed_now = p.seed if p.seed is not None else ctx.seed
        k = int(p.rank)
        uf_prev, itf_prev = prev_model.host_factors()
        # Union-extend the id spaces: previous entities keep their rows,
        # delta-new entities append contiguous fresh indices.
        u_map: Dict[str, int] = dict(prev_model.user_index.items())
        i_map: Dict[str, int] = dict(prev_model.item_index.items())
        for key in prepared_delta.user_index.to_numpy_keys():
            u_map.setdefault(str(key), len(u_map))
        for key in prepared_delta.item_index.to_numpy_keys():
            i_map.setdefault(str(key), len(i_map))
        user_index = BiMap(u_map)
        item_index = BiMap(i_map)
        rng = np.random.default_rng(seed_now if seed_now is not None else 0)
        scale = np.float32(np.sqrt(k))

        def _extend(prev: np.ndarray, n_total: int) -> np.ndarray:
            out = np.array(prev, np.float32, copy=True)
            if n_total <= out.shape[0]:
                return out
            fresh = rng.standard_normal(
                (n_total - out.shape[0], k)).astype(np.float32) / scale
            return np.concatenate([out, fresh], axis=0)

        uf = _extend(uf_prev, len(user_index))
        itf = _extend(itf_prev, len(item_index))
        # Remap delta triplets from the delta read's local indices to the
        # union index space.
        u_lut = np.asarray(
            [u_map[str(kk)]
             for kk in prepared_delta.user_index.to_numpy_keys()], np.int64)
        i_lut = np.asarray(
            [i_map[str(kk)]
             for kk in prepared_delta.item_index.to_numpy_keys()], np.int64)
        rows_u = u_lut[np.asarray(prepared_delta.user_ids, np.int64)]
        rows_i = i_lut[np.asarray(prepared_delta.item_ids, np.int64)]
        vals = np.asarray(prepared_delta.ratings, np.float64)
        # Eval sample: pairs the PREVIOUS generation could already score.
        # All-new-entity deltas have no comparable pairs — the fraction
        # gate above already bounds how much unchecked change they carry.
        known = np.flatnonzero(
            (rows_u < len(prev_model.user_index))
            & (rows_i < len(prev_model.item_index)))
        su = si = sv = None
        if known.size:
            sel = rng.choice(known, size=min(known.size, 1024),
                             replace=False)
            su, si = rows_u[sel], rows_i[sel]
            sv = ((vals[sel] > 0).astype(np.float64)
                  if p.implicitPrefs else vals[sel])

        def _sample_rmse() -> float:
            pred = np.einsum("ij,ij->i", uf[su].astype(np.float64),
                             itf[si].astype(np.float64))
            return float(np.sqrt(np.mean((pred - sv) ** 2)))

        rmse_before = _sample_rmse() if known.size else None
        sweeps = max(1, int(p.numIterations) // 5)
        for _ in range(sweeps):
            _warm_ridge_sweep(uf, itf, rows_u, rows_i, vals,
                              reg=float(p.lambda_), alpha=float(p.alpha),
                              implicit=bool(p.implicitPrefs))
            _warm_ridge_sweep(itf, uf, rows_i, rows_u, vals,
                              reg=float(p.lambda_), alpha=float(p.alpha),
                              implicit=bool(p.implicitPrefs))
        tol = getattr(warm, "eval_tolerance", 0.1)
        if known.size:
            rmse_after = _sample_rmse()
            if not np.isfinite(rmse_after) \
                    or rmse_after > rmse_before * (1.0 + tol) + 1e-9:
                raise WarmStartFallback(
                    f"warm-started eval regressed on the delta sample "
                    f"(rmse {rmse_before:.4f} → {rmse_after:.4f}, "
                    f"tolerance {tol:g})")
            log.info("als warm-start: +%d events (%d sweeps), "
                     "delta-sample rmse %.4f → %.4f", delta_n, sweeps,
                     rmse_before, rmse_after)
        else:
            log.info("als warm-start: +%d events (%d sweeps), all-new "
                     "entities — no comparable eval pairs", delta_n, sweeps)
        import jax.numpy as jnp

        model = als_lib.ALSModel(
            user_factors=jnp.asarray(uf), item_factors=jnp.asarray(itf),
            rank=k, implicit=bool(p.implicitPrefs))
        # Retrieval structures and baselines are derived from THIS
        # generation's factors — rebuild them exactly as train() does;
        # carrying the parent's would mis-route the rows just moved.
        ivf_idx = build_train_index(itf, name="als", seed=seed_now,
                                    require_explicit=True)
        pq = build_train_pq(itf, name="als", ivf=ivf_idx, seed=seed_now)
        return ALSModelWrapper(
            model=model,
            user_index=user_index,
            item_index=item_index,
            ivf=ivf_idx,
            pq=pq,
            quality=scorecard_from_matrix(uf, itf, seed=seed_now or 0,
                                          name="als"),
            recall=build_recall_scorecard(uf, itf, ivf=ivf_idx, pq=pq,
                                          seed=seed_now or 0, name="als"),
            app_name=getattr(prepared_delta, "app_name", None)
            or getattr(prev_model, "app_name", None),
            fold_event_names=tuple(
                getattr(prepared_delta, "event_names", ()) or ())
            or tuple(getattr(prev_model, "fold_event_names", ()) or ()),
            buy_rating=float(getattr(prepared_delta, "buy_rating", 4.0)),
            reg=float(p.lambda_),
            alpha=float(p.alpha),
            n_examples=prev_n + delta_n,
        )

    def predict(self, model: ALSModelWrapper, query: Query) -> PredictedResult:
        # One query = a batch of one: the same facade routing (host MACs
        # threshold, sharded/chunked/IVF device paths) applies, so a
        # corpus that outgrew the host fast path serves B=1 correctly too.
        return self.batch_predict(model, [(0, query)])[0][1]

    def batch_predict(self, model: ALSModelWrapper, queries):
        """Vectorized eval/serving path — ONE retrieval-facade call for
        the whole cohort.

        All routing (host fast path under ``PIO_SERVE_HOST_MACS``,
        mesh-sharded / chunked device scoring, the train-time IVF index,
        pow2 batch + K-menu compile discipline) lives in
        :mod:`predictionio_tpu.retrieval` — this template only maps ids.

        Unseen users try serve-time fold-in first (ISSUE 10,
        :meth:`ALSModelWrapper.fold_in_user`): a repeat visitor's cached
        (or freshly solved) factor rides the SAME cohort retrieval as
        trained users, so fold-in costs one extra query row, not a
        second dispatch.  Users with no usable events still answer the
        cold-start empty result.

        The cohort's answers leave as columns: the facade's two arrays
        become per-row Python lists in one pass
        (:func:`~predictionio_tpu.retrieval.hit_columns`), the item ids
        their strings in another, and each ``itemScores`` is an
        :class:`~predictionio_tpu.controller.ItemScoreColumns` over
        them; no ``ItemScore`` exists until somebody reads one.
        """
        with dispatch_stage("predict.lookup", "lookup"):
            known = [(i, q) for i, q in queries
                     if q.user in model.user_index]
            rows: List[np.ndarray] = []
            cold: List[Tuple[int, "Query"]] = []
            folded: List[Tuple[int, "Query"]] = []
            for i, q in queries:
                if q.user in model.user_index:
                    continue
                vec = model.fold_in_user(q.user)
                if vec is None:
                    cold.append((i, q))
                else:
                    folded.append((i, q))
                    rows.append(vec)
            out = [(i, PredictedResult(itemScores=[])) for i, q in cold]
            answerable = known + folded
            if not answerable:
                return out
            num = max(q.num for _, q in answerable)
            uf = model.host_user_factors()
            qmat_parts = []
            if known:
                idxs = np.asarray([model.user_index[q.user]
                                   for _, q in known])
                qmat_parts.append(uf[idxs])
            if rows:
                qmat_parts.append(np.stack(rows))
            qmat = np.concatenate(qmat_parts, axis=0) \
                if len(qmat_parts) > 1 else qmat_parts[0]
        scores, ids, _info = model.retriever().topk(qmat, num)
        with dispatch_stage("predict.assemble", "assemble"):
            keys_of = model.item_index.keys_of
            columns = hit_columns(scores, ids,
                                  [q.num for _, q in answerable])
            out.extend(
                (i, PredictedResult(itemScores=ItemScoreColumns(
                    keys_of(item_ids), item_scores, ItemScore)))
                for (i, _), (item_ids, item_scores)
                in zip(answerable, columns))
        return out


def engine() -> Engine:
    """Reference: RecommendationEngine EngineFactory."""
    return Engine(
        datasource_class=RecommendationDataSource,
        preparator_class=RecommendationPreparator,
        algorithm_classes={"als": ALSAlgorithm},
        serving_class=FirstServing,
        query_class=Query,
    )
