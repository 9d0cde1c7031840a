"""Two-tower retrieval template — neural personal recommendations.

TPU-era engine (BASELINE config 4; absent in the reference — SURVEY.md
§2.2).  Same external contract as the recommendation template so clients
can switch engines without changing queries:

- events: any positive-interaction names (default view/buy/rate)
- query JSON: ``{"user": "u1", "num": 4}``
- result JSON: ``{"itemScores": [{"item", "score"}]}``

Substrate: :mod:`models.two_tower` — in-batch sampled-softmax training,
DP over the ``data`` mesh axis, MIPS top-K serve.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from predictionio_tpu.controller import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    IdentityPreparator,
    ItemScoreColumns,
    RuntimeContext,
    WarmStartFallback,
)
from predictionio_tpu.controller.params import Params
from predictionio_tpu.data.event import BiMap
from predictionio_tpu.models import two_tower as tt_lib
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
    "Query", "ItemScore", "PredictedResult", "InteractionData",
    "DataSourceParams", "TwoTowerDataSource", "TwoTowerAlgorithmParams",
    "TwoTowerAlgorithm", "engine",
]


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
    itemScores: Sequence[ItemScore]  # noqa: N815


@dataclasses.dataclass
class InteractionData:
    user_ids: np.ndarray
    item_ids: np.ndarray
    user_index: BiMap
    item_index: BiMap


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    appName: str  # noqa: N815
    eventNames: Sequence[str] = ("view", "buy", "rate")  # noqa: N815


class TwoTowerDataSource(DataSource):
    params_class = DataSourceParams

    def read_training(self, ctx: RuntimeContext) -> InteractionData:
        p: DataSourceParams = self.params
        table = ctx.event_store.find_columnar(
            p.appName, entity_type="user", target_entity_type="item",
            event_names=list(p.eventNames),
            ordered=False, columns=["entity_id", "target_entity_id"])
        from predictionio_tpu.data.columnar import encode_ids

        user_ids, user_index = encode_ids(table.column("entity_id"))
        item_ids, item_index = encode_ids(table.column("target_entity_id"))
        return InteractionData(
            user_ids=user_ids,
            item_ids=item_ids,
            user_index=user_index,
            item_index=item_index,
        )


@dataclasses.dataclass(frozen=True)
class TwoTowerAlgorithmParams(Params):
    embedDim: int = 32  # noqa: N815
    hiddenDims: Sequence[int] = (64,)  # noqa: N815
    outDim: int = 32  # noqa: N815
    learningRate: float = 1e-3  # noqa: N815
    temperature: float = 0.05
    batchSize: int = 512  # noqa: N815
    epochs: int = 5
    seed: Optional[int] = None


# eq=False: wrapper identity IS the model generation — keeps the object
# hashable for the weak-keyed retriever cache.
@dataclasses.dataclass(eq=False)
class TwoTowerModelWrapper:
    """Precomputed encoded item corpus + user embeddings for serving.

    ``ivf`` is the optional train-time coarse index (ISSUE 8).  It rides
    INSIDE this pickle, so the staged-reload/rollback generation swap
    moves model and index as one artifact — a rollback can never serve
    generation-N vectors through a generation-N+1 index (the retrieval
    facade's corpus fingerprint check makes any future violation loud).
    """

    user_vecs: np.ndarray   # [U, D] — encoded user representations
    item_vecs: np.ndarray   # [I, D] (L2-normalized tower outputs)
    user_index: BiMap
    item_index: BiMap
    ivf: Optional[IVFIndex] = None
    # Residual PQ codes + codebooks (ISSUE 13): same atomic-swap
    # contract as ``ivf`` — the quantized corpus a generation serves is
    # ALWAYS the one built over its own vectors, fingerprint-pinned.
    pq: Optional[PQCodebook] = None
    # Training-time score-distribution baseline (ISSUE 11): rides the
    # same atomic-swap contract as ``ivf`` — serving drift is always
    # judged against THIS generation's own baseline, fingerprint-pinned
    # to the corpus it was scored over.
    quality: Optional[Scorecard] = None
    # Training-time expected-recall baseline (ISSUE 16): offline
    # recall@k of THIS generation's own ivf/pq structures on a seeded
    # query sample, fingerprint-pinned like ``quality`` — the online
    # recall monitor trips on regression vs this, not an absolute floor.
    recall: Optional[RecallScorecard] = None
    # Warm-start carry (ISSUE 10): the host-numpy train state + the
    # config it was trained under + the interaction count — what the
    # next refresh needs to CONTINUE training on a delta window instead
    # of retraining from scratch.  None on wrappers from older
    # generations (warm_start then falls back to a full retrain).
    train_state: Optional[Dict] = None
    train_cfg: Optional[tt_lib.TwoTowerConfig] = None
    n_examples: int = 0

    def __setstate__(self, d):
        """Old-pickle backfill: wrappers serialized before newer
        optional fields existed (``recall``, …) restore with every
        missing field at its dataclass default."""
        for f in dataclasses.fields(self):
            if f.name not in d and f.default is not dataclasses.MISSING:
                d[f.name] = f.default
        self.__dict__.update(d)

    def retriever(self) -> Retriever:
        """THE serving route to the item corpus (retrieval facade):
        host/device/chunked/sharded/IVF routing, jit caches, metrics —
        one per loaded generation, dying with it."""
        return cached_retriever(self, lambda: Retriever(
            self.item_vecs,
            n_items=len(self.item_index),
            ivf=getattr(self, "ivf", None),
            pq=getattr(self, "pq", None),
            name="twotower"))

    def post_load(self, ctx) -> None:
        """Serving-time re-parallelization: with a serving mesh and a
        corpus above ``PIO_SERVE_SHARD_ABOVE`` items, row-shard the item
        matrix over the ``data`` axis at model-load time so predict
        routes through the mesh-sharded exact rung — per-chip memory and
        score work scale 1/n_chips for corpora that outgrow one chip."""
        mesh = getattr(ctx, "mesh", None)
        if mesh is not None:
            self.retriever().maybe_shard(mesh)


def _merge_index(prev: BiMap, delta: BiMap) -> BiMap:
    """Extend ``prev`` with delta-only keys appended AFTER the existing
    range (existing entities keep their embedding rows; new ones map to
    the grown tail).  Delta keys append in their first-seen order, so
    the merge is deterministic."""
    m = dict(prev.items())
    for k in delta:
        if k not in m:
            m[k] = len(m)
    return BiMap(m)


def _remap_codes(codes: np.ndarray, delta_index: BiMap,
                 merged: BiMap) -> np.ndarray:
    """Delta-local int codes → merged global ids (one vectorized take)."""
    lookup = np.asarray([merged[k] for k in delta_index.to_numpy_keys()],
                        np.int64)
    return lookup[np.asarray(codes, np.int64)]


class TwoTowerAlgorithm(Algorithm):
    params_class = TwoTowerAlgorithmParams

    def _config(self, ctx: RuntimeContext, n_users: int,
                n_items: int) -> tt_lib.TwoTowerConfig:
        p: TwoTowerAlgorithmParams = self.params
        return tt_lib.TwoTowerConfig(
            n_users=n_users,
            n_items=n_items,
            embed_dim=p.embedDim,
            hidden_dims=tuple(p.hiddenDims),
            out_dim=p.outDim,
            learning_rate=p.learningRate,
            temperature=p.temperature,
            batch_size=p.batchSize,
            epochs=p.epochs,
            seed=p.seed if p.seed is not None else ctx.seed,
        )

    def _wrap(self, state: "tt_lib.TwoTowerState",
              cfg: tt_lib.TwoTowerConfig, user_index: BiMap,
              item_index: BiMap, n_examples: int) -> TwoTowerModelWrapper:
        user_vecs = np.asarray(
            tt_lib.encode_users(state.params, jnp.arange(cfg.n_users)))
        item_vecs = np.asarray(
            tt_lib.encode_items(state.params, jnp.arange(cfg.n_items)))
        # Train-time coarse index (policy-gated: PIO_IVF /
        # PIO_IVF_MIN_ITEMS) — the normalized tower outputs are the
        # IVF design target; serialized with the model so the
        # generation swap moves both atomically.
        ivf = build_train_index(item_vecs, name="twotower",
                                seed=cfg.seed)
        # Residual PQ codes (policy-gated: PIO_PQ / PIO_PQ_M /
        # PIO_PQ_MIN_ITEMS), built on top of the IVF coarse structure
        # and swapped with it.
        pq = build_train_pq(item_vecs, name="twotower", ivf=ivf,
                            seed=cfg.seed)
        return TwoTowerModelWrapper(
            user_vecs=user_vecs, item_vecs=item_vecs,
            user_index=user_index,
            item_index=item_index,
            ivf=ivf,
            pq=pq,
            # Quality baseline (ISSUE 11): top-K scores of a seeded user
            # sample against the full corpus — the same population
            # serving emits, so serve-time PSI compares like with like.
            quality=scorecard_from_matrix(user_vecs, item_vecs,
                                          seed=cfg.seed or 0,
                                          name="twotower"),
            # Expected-recall baseline (ISSUE 16): offline recall of the
            # structures just built, through the same search paths and
            # nprobe/rerank formulas serving will use.  None when
            # neither structure was built (exact serving — nothing to
            # monitor).
            recall=build_recall_scorecard(user_vecs, item_vecs, ivf=ivf,
                                          pq=pq, seed=cfg.seed or 0,
                                          name="twotower"),
            train_state=tt_lib.state_to_host(state),
            train_cfg=cfg,
            n_examples=int(n_examples))

    def train(self, ctx: RuntimeContext, prepared_data: InteractionData) -> TwoTowerModelWrapper:
        if len(prepared_data.user_ids) == 0:
            raise ValueError("No interaction events found — check appName.")
        cfg = self._config(ctx, len(prepared_data.user_index),
                           len(prepared_data.item_index))
        state = tt_lib.train(prepared_data.user_ids, prepared_data.item_ids,
                             cfg, mesh=ctx.mesh)
        return self._wrap(state, cfg, prepared_data.user_index,
                          prepared_data.item_index,
                          len(prepared_data.user_ids))

    def warm_start(self, ctx: RuntimeContext, prepared_delta: InteractionData,
                   prev_model: TwoTowerModelWrapper,
                   warm: Any) -> TwoTowerModelWrapper:
        """Delta warm-start (ISSUE 10 tentpole): restore the previous
        generation's carried train state, grow the embedding tables for
        entities first seen in the delta window, and CONTINUE training
        on the delta only — riding the same
        ``DevicePrefetcher``/fused-dispatch/supervision loop a full
        train uses.

        Falls back (``WarmStartFallback`` → full retrain in the same
        engine instance) when: the previous wrapper carries no train
        state (older generation), the algorithm config changed (shapes
        or optimizer semantics differ), the delta exceeds
        ``warm.max_delta_fraction`` of the previous corpus, or the
        continued model's loss on a fixed delta sample REGRESSES past
        ``warm.eval_tolerance`` vs the state it started from (a
        divergent continuation must never be promoted on the cheap
        path)."""
        log = logging.getLogger(__name__)
        snapshot = getattr(prev_model, "train_state", None)
        prev_cfg = getattr(prev_model, "train_cfg", None)
        if snapshot is None or prev_cfg is None:
            raise WarmStartFallback(
                "previous generation carries no train state")
        delta_n = len(prepared_delta.user_ids)
        prev_n = int(getattr(prev_model, "n_examples", 0))
        cfg_now = self._config(ctx, prev_cfg.n_users, prev_cfg.n_items)
        for f in ("embed_dim", "hidden_dims", "out_dim", "learning_rate",
                  "temperature", "batch_size", "seed"):
            if getattr(cfg_now, f) != getattr(prev_cfg, f):
                raise WarmStartFallback(
                    f"algorithm config changed ({f}: "
                    f"{getattr(prev_cfg, f)!r} → {getattr(cfg_now, f)!r})")
        max_frac = getattr(warm, "max_delta_fraction", 0.5)
        if prev_n <= 0 or delta_n > max_frac * prev_n:
            raise WarmStartFallback(
                f"delta window too large for continuation "
                f"({delta_n} events vs {prev_n} trained; "
                f"max fraction {max_frac:g})")
        # Merge the delta's entities into the previous index: existing
        # rows keep their ids (and factors); new entities append.
        user_index = _merge_index(prev_model.user_index,
                                  prepared_delta.user_index)
        item_index = _merge_index(prev_model.item_index,
                                  prepared_delta.item_index)
        uids = _remap_codes(prepared_delta.user_ids,
                            prepared_delta.user_index, user_index)
        iids = _remap_codes(prepared_delta.item_ids,
                            prepared_delta.item_index, item_index)
        cfg = dataclasses.replace(prev_cfg, n_users=len(user_index),
                                  n_items=len(item_index),
                                  epochs=self.params.epochs)
        state = tt_lib.grow_state(tt_lib.state_from_host(snapshot), cfg)
        if delta_n == 0:
            # Nothing new: re-land the carried state as a fresh
            # generation (its watermark still advances — staleness is
            # measured against the WINDOW, not the weights).
            return self._wrap(state, cfg, user_index, item_index, prev_n)
        # Regression gate sample: fixed (seeded) subset of the delta,
        # scored before and after continuation at the same temperature.
        rng = np.random.default_rng(cfg.seed)
        sample = rng.choice(delta_n, size=min(delta_n, 1024), replace=False)
        loss_before = tt_lib.eval_loss(state.params, uids[sample],
                                       iids[sample], cfg)
        trained = tt_lib.train(uids, iids, cfg, mesh=ctx.mesh,
                               warm_state=state)
        loss_after = tt_lib.eval_loss(trained.params, uids[sample],
                                      iids[sample], cfg)
        tol = getattr(warm, "eval_tolerance", 0.1)
        if not np.isfinite(loss_after) \
                or loss_after > loss_before * (1.0 + tol) + 1e-9:
            raise WarmStartFallback(
                f"warm-started eval regressed on the delta sample "
                f"({loss_before:.4f} → {loss_after:.4f}, "
                f"tolerance {tol:g})")
        log.info("two_tower warm-start: +%d events (%d new users, %d new "
                 "items), delta-sample loss %.4f → %.4f",
                 delta_n, len(user_index) - len(prev_model.user_index),
                 len(item_index) - len(prev_model.item_index),
                 loss_before, loss_after)
        return self._wrap(trained, cfg, user_index, item_index,
                          prev_n + delta_n)

    def predict(self, model: TwoTowerModelWrapper, query: Query) -> PredictedResult:
        # A batch of one: the facade's host fast path answers a lone
        # client in numpy (a B=1 matmul is orders of magnitude below one
        # device dispatch round-trip) — the same PIO_SERVE_HOST_MACS
        # threshold the ALS template uses, parity-tested.
        return self.batch_predict(model, [(0, query)])[0][1]

    def batch_predict(self, model: TwoTowerModelWrapper, queries):
        """Vectorized serving path for the continuous-batching scheduler:
        ONE retrieval-facade call for the whole cohort.

        All routing (host fast path, mesh-sharded / chunked device
        scoring, the train-time IVF index, pow2 batch + K-menu compile
        discipline) lives in :mod:`predictionio_tpu.retrieval` — this
        template only maps ids, a cohort at a time: the answers leave as
        :class:`~predictionio_tpu.controller.ItemScoreColumns`, and no
        ``ItemScore`` exists until somebody reads one.
        """
        known = [(i, q) for i, q in queries
                 if model.user_index.get(q.user) is not None]
        out = [(i, PredictedResult(itemScores=[])) for i, q in queries
               if model.user_index.get(q.user) is None]
        if not known:
            return out
        num = max(q.num for _, q in known)
        idxs = np.asarray([model.user_index[q.user] for _, q in known])
        scores, ids, _info = model.retriever().topk(
            model.user_vecs[idxs], num)
        keys_of = model.item_index.keys_of
        columns = hit_columns(scores, ids, [q.num for _, q in known])
        out.extend(
            (i, PredictedResult(itemScores=ItemScoreColumns(
                keys_of(item_ids), item_scores, ItemScore)))
            for (i, _), (item_ids, item_scores) in zip(known, columns))
        return out


def engine() -> Engine:
    return Engine(
        datasource_class=TwoTowerDataSource,
        preparator_class=IdentityPreparator,
        algorithm_classes={"twotower": TwoTowerAlgorithm},
        serving_class=FirstServing,
        query_class=Query,
    )
