"""Sequence template — "what next" from a user's event history.

A user's item events, in time order, are a sequence; the model scores
every item as the next one.  The algorithm's ``backbone`` picks it:
``lfm2`` (:mod:`predictionio_tpu.models.lfm2`: gated short convolutions,
grouped-query attention, routed experts; the default) or ``sala``
(:mod:`predictionio_tpu.models.sala`: block-selected sparse attention
beside lightning linear attention) or ``sambay``
(:mod:`predictionio_tpu.models.sambay`: Mamba and sliding-window layers
under one full-attention cache that the later layers read, with gated
memory units) or ``granite_h``
(:mod:`predictionio_tpu.models.granite_h`: Mamba-2 state-space layers
beside grouped-query attention with no positional encoding, under
Granite's four multipliers).
Serving keeps each user's state between queries in the
:class:`~predictionio_tpu.serving.state_cache.StateCache`, so a query
pays for the events it brings, not for the history behind them.

Query/result JSON::

    {"user": "u1", "num": 4, "events": ["i7", "i3"]}
        -> {"itemScores": [{"item", "score"}]}

``events``: the items of the user's events since their last query,
oldest first.  The engine appends them to the user's state and answers
at the last one; without ``events`` it answers from the state as it
stands.  A user the cache holds nothing for (never seen, evicted, or
after a ``/reload``) is first read back from the event store (the
engine's ``appName``), so the store has to hold the events of the
user's EARLIER turns and not yet those of this one: send a turn's events
to the event server after its query has been answered.  A store that
cannot answer fails the query (5xx) and moves no state: answering from
this turn's events alone would commit a truncated history that every
later turn would then hit.
A query with ``events`` changes what the next one sees, so its answers
are never served from the result cache.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, ClassVar, Dict, List, Optional, Sequence

import numpy as np

from predictionio_tpu.controller import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    ItemScoreColumns,
    Preparator,
    RuntimeContext,
)
from predictionio_tpu.controller.params import Params
from predictionio_tpu.data.event import BiMap
from predictionio_tpu.obs import dispatch_stage

logger = logging.getLogger(__name__)

# backbone -> (its module, its plain reference's module)
BACKBONES = {
    "lfm2": ("predictionio_tpu.models.lfm2",
             "predictionio_tpu.models.lfm2_reference"),
    "sala": ("predictionio_tpu.models.sala",
             "predictionio_tpu.models.sala_reference"),
    "sambay": ("predictionio_tpu.models.sambay",
               "predictionio_tpu.models.sambay_reference"),
    "granite_h": ("predictionio_tpu.models.granite_h",
                  "predictionio_tpu.models.granite_h_reference"),
}


def _backbone(name: str):
    import importlib

    try:
        module, reference = BACKBONES[name]
    except KeyError:
        raise ValueError(f"unknown backbone {name!r}; known: "
                         f"{sorted(BACKBONES)}") from None
    return importlib.import_module(module), importlib.import_module(
        reference)

__all__ = [
    "Query", "ItemScore", "PredictedResult", "Histories",
    "DataSourceParams", "SequenceDataSource", "PreparatorParams",
    "SequencePreparator", "SequenceAlgorithmParams", "SequenceModel",
    "SequenceAlgorithm", "engine",
]


@dataclasses.dataclass
class Query:
    user: str
    num: int = 10
    events: Optional[List[str]] = None
    # Answering advances the user's state: never a result-cache key.
    pio_stateful: ClassVar[bool] = True


@dataclasses.dataclass
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass
class PredictedResult:
    # From ``batch_predict`` an ItemScoreColumns: a list of ItemScore to
    # whoever reads it, two columns to the JSON.
    itemScores: Sequence[ItemScore]  # noqa: N815 — reference JSON field name


@dataclasses.dataclass
class Histories:
    """Every user's item events in time order, users back to back:
    user ``u``'s items are ``items[offsets[u]:offsets[u + 1]]``.  From
    the datasource ``items`` are item strings; the preparator turns them
    into ids below the vocabulary and fills ``item_index``."""

    offsets: np.ndarray
    items: np.ndarray
    item_index: Optional[BiMap] = None
    app_name: Optional[str] = None
    event_names: Sequence[str] = ()


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    appName: str  # noqa: N815 — engine.json key parity
    eventNames: Sequence[str] = ("view", "buy")  # noqa: N815


class SequenceDataSource(DataSource):
    """Reads each user's item events in time order."""

    params_class = DataSourceParams

    def read_training(self, ctx: RuntimeContext) -> Histories:
        from predictionio_tpu.data.columnar import encode_ids

        p: DataSourceParams = self.params
        table = ctx.event_store.find_columnar(
            p.appName, entity_type="user", target_entity_type="item",
            event_names=list(p.eventNames), ordered=True,
            columns=["entity_id", "target_entity_id"])
        users, user_index = encode_ids(table.column("entity_id"))
        items = np.asarray(table.column("target_entity_id").to_pylist(),
                           dtype=object)
        # A stable sort by user keeps each user's events in time order.
        order = np.argsort(users, kind="stable")
        counts = np.bincount(users, minlength=len(user_index))
        return Histories(
            offsets=np.concatenate([[0], np.cumsum(counts)]).astype(np.int64),
            items=items[order], app_name=p.appName,
            event_names=tuple(p.eventNames))


@dataclasses.dataclass(frozen=True)
class PreparatorParams(Params):
    vocabSize: int = 65536  # noqa: N815 — items the model can name


class SequencePreparator(Preparator):
    """Maps items to ids below the vocabulary: the ``vocabSize`` most
    frequent items, most frequent first; events on any other item are
    dropped from the histories."""

    params_class = PreparatorParams

    def prepare(self, ctx: RuntimeContext, td: Histories) -> Histories:
        vocab = int(self.params.vocabSize) if self.params else 65536
        names, inverse, counts = np.unique(
            td.items.astype(str), return_inverse=True, return_counts=True)
        rank = np.argsort(-counts, kind="stable")[:vocab]
        new_id = np.full(len(names), -1, np.int64)
        new_id[rank] = np.arange(len(rank))
        ids = new_id[inverse]
        keep = ids >= 0
        user_of = np.repeat(np.arange(len(td.offsets) - 1),
                            np.diff(td.offsets))
        counts_u = np.bincount(user_of[keep], minlength=len(td.offsets) - 1)
        return Histories(
            offsets=np.concatenate([[0], np.cumsum(counts_u)]).astype(
                np.int64),
            items=ids[keep].astype(np.int32),
            item_index=BiMap({str(names[j]): i for i, j in enumerate(rank)}),
            app_name=td.app_name, event_names=td.event_names)


@dataclasses.dataclass(frozen=True)
class SequenceAlgorithmParams(Params):
    backbone: str = "lfm2"
    hiddenSize: int = 64  # noqa: N815
    intermediateSize: int = 128  # noqa: N815
    moeIntermediateSize: int = 32  # noqa: N815
    numExperts: int = 8  # noqa: N815
    numExpertsPerTok: int = 2  # noqa: N815
    numAttentionHeads: int = 4  # noqa: N815
    numKeyValueHeads: int = 2  # noqa: N815
    layerTypes: Sequence[str] = (  # noqa: N815
        "conv", "full_attention", "conv", "conv")
    numDenseLayers: int = 1  # noqa: N815
    # The ``sala`` backbone: a mixer a layer, the heads' size, and what of
    # the block selection's sizes differs from the published ones.
    mixerTypes: Sequence[str] = (  # noqa: N815
        "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn")
    headDim: int = 16  # noqa: N815
    sparseConfig: Optional[Dict[str, int]] = None  # noqa: N815
    # The ``sambay`` backbone: its depth (a multiple of 4; the layer
    # pattern follows from it), the window layers' reach, and what of the
    # Mamba mixer's sizes (d_state, d_conv, expand, dt_rank) differs from
    # the modelling code's defaults.
    numHiddenLayers: int = 8  # noqa: N815
    slidingWindow: int = 16  # noqa: N815
    ssmConfig: Optional[Dict[str, int]] = None  # noqa: N815
    # The ``granite_h`` backbone: ``layerTypes`` of "mamba" and
    # "attention", heads of ``headDim``, the Mamba-2 sizes (mamba_n_heads,
    # mamba_d_head, mamba_d_state, mamba_d_conv, mamba_expand) under
    # ``ssmConfig``, and the published model's four multipliers.
    embeddingMultiplier: float = 12.0  # noqa: N815
    residualMultiplier: float = 0.22  # noqa: N815
    attentionMultiplier: float = 0.015625  # noqa: N815
    logitsScaling: float = 8.0  # noqa: N815
    # Training (next-item cross-entropy over windows of the histories).
    steps: int = 200
    batchSize: int = 16  # noqa: N815
    window: int = 32
    learningRate: float = 3e-3  # noqa: N815
    seed: Optional[int] = None
    # Serving state.
    stateBudgetMB: float = 64.0  # noqa: N815
    maxUsers: int = 1024  # noqa: N815


@dataclasses.dataclass(eq=False)
class SequenceModel:
    """Trained weights + the item index; serving state hangs off it
    (``state_cache``, keyed by user), so a model object IS a generation:
    a reload loads a new object with an empty cache, and the server
    frees this one's."""

    config: Any                       # the backbone's config
    params: Dict[str, Any]            # host arrays
    item_index: BiMap
    app_name: Optional[str] = None
    event_names: Sequence[str] = ()
    state_budget_bytes: int = 64 << 20
    max_users: int = 1024
    backbone: str = "lfm2"

    def __post_init__(self):
        self._init_transients()

    def _init_transients(self) -> None:
        self._runtime = None
        self._ctx: Optional[RuntimeContext] = None

    def __getstate__(self):
        d = dict(self.__dict__)
        for k in ("_runtime", "_ctx"):
            d.pop(k, None)
        d["params"] = _to_host(d["params"])
        return d

    def __setstate__(self, d):
        self.__dict__.update(d)
        self._init_transients()

    def post_load(self, ctx) -> None:
        self._ctx = ctx

    def runtime(self):
        """The device side, built on first use: serving-precision weights,
        the state cache within its budget, the compiled programs."""
        if self._runtime is None:
            # A model pickled before there were two has no such field.
            name = getattr(self, "backbone", "lfm2")
            self._runtime = _backbone(name)[0].make_runtime(
                self.config, self.params,
                budget_bytes=self.state_budget_bytes,
                max_users=self.max_users)
        return self._runtime

    @property
    def state_cache(self):
        """What the engine server holds a transaction on around a
        dispatch, and frees when this generation stops serving."""
        return self.runtime().cache

    def drop_serving_state(self) -> None:
        """Every user's state and the pools' device memory go (the
        server may keep this model for a rollback)."""
        if self._runtime is not None:
            self._runtime.cache.free()

    def stored_history(self, user: str, limit: int) -> np.ndarray:
        """The user's item ids from the event store, oldest first (at
        most the latest ``limit``); empty when the model names no app,
        the store holds no such app (a model deployed with no store
        behind it) or the user has no event.  Any other failure of the
        store is the caller's: a history that could not be read is not
        an empty one."""
        if not self.app_name:
            return np.zeros(0, np.int32)
        ctx = self._ctx
        if ctx is None:
            ctx = self._ctx = RuntimeContext.create()
        if ctx.storage.get_apps().get_by_name(self.app_name) is None:
            return np.zeros(0, np.int32)
        events = ctx.event_store.find_by_entity(
            self.app_name, "user", user,
            event_names=list(self.event_names) or None,
            target_entity_type="item", limit=limit, latest=True)
        ids = [self.item_index.get(e.target_entity_id)
               for e in reversed(events)]
        return np.asarray([i for i in ids if i is not None], np.int32)


def _to_host(tree):
    import jax

    return jax.tree_util.tree_map(np.asarray, tree)


class SequenceAlgorithm(Algorithm):
    params_class = SequenceAlgorithmParams

    def train(self, ctx: RuntimeContext, pd: Histories) -> SequenceModel:
        """Next-item cross-entropy over random windows of the histories,
        on the backbone's plain forward pass (tier-1 sizes: every expert
        runs on every token, the selection is a mask over all pairs)."""
        import jax
        import jax.numpy as jnp
        import optax

        p: SequenceAlgorithmParams = self.params
        if pd.item_index is None or len(pd.items) == 0:
            raise ValueError("No item events found — check appName and "
                             "eventNames.")
        backbone, reference = _backbone(p.backbone)
        cfg = backbone.config_from_params(p, len(pd.item_index))
        seed = p.seed if p.seed is not None else ctx.seed
        params = backbone.init_params(cfg, jax.random.PRNGKey(seed),
                                      jnp.float32)
        opt = optax.adam(p.learningRate)
        opt_state = opt.init(params)

        def loss_fn(params, tokens, mask):
            logits = jax.vmap(lambda t: reference.forward(
                params, cfg, t))(tokens[:, :-1])
            nll = optax.softmax_cross_entropy_with_integer_labels(
                logits, tokens[:, 1:])
            return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

        @jax.jit
        def step(params, opt_state, tokens, mask):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, mask)
            updates, opt_state = opt.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        rng = np.random.default_rng(seed)
        lengths = np.diff(pd.offsets)
        users = np.flatnonzero(lengths >= 2)
        if len(users) == 0:
            raise ValueError("No user has two item events to learn from.")
        w = int(p.window)
        loss = float("nan")
        for _ in range(int(p.steps)):
            tokens = np.zeros((p.batchSize, w + 1), np.int32)
            mask = np.zeros((p.batchSize, w), np.float32)
            for row, u in enumerate(rng.choice(users, p.batchSize)):
                n = min(int(lengths[u]), w + 1)
                start = pd.offsets[u] + rng.integers(
                    0, lengths[u] - n + 1)
                tokens[row, :n] = pd.items[start:start + n]
                mask[row, :n - 1] = 1.0
            params, opt_state, loss = step(params, opt_state,
                                           jnp.asarray(tokens),
                                           jnp.asarray(mask))
        logger.info("sequence model trained: %d steps, loss %.4f",
                    p.steps, float(loss))
        return SequenceModel(
            config=cfg, params=_to_host(params), item_index=pd.item_index,
            app_name=pd.app_name, event_names=tuple(pd.event_names),
            state_budget_bytes=int(p.stateBudgetMB * (1 << 20)),
            max_users=p.maxUsers, backbone=p.backbone)

    def predict(self, model: SequenceModel, query: Query) -> PredictedResult:
        return self.batch_predict(model, [(0, query)])[0][1]

    def batch_predict(self, model: SequenceModel, queries):
        """One cohort: every turn's new events through the backbone
        against its user's cached state, in arrival order (two turns of
        one user share a segment; the second sees the first).  The state
        moves only if the whole transaction does: inside the engine
        server that is the dispatch, here the call.  The answers leave
        as :class:`~predictionio_tpu.controller.ItemScoreColumns`: no
        ``ItemScore`` exists until somebody reads one."""
        from predictionio_tpu.models.seq_runtime import Turn
        from predictionio_tpu.retrieval import hit_columns

        runtime = model.runtime()
        cache = runtime.cache
        with cache.transaction():
            with dispatch_stage("predict.lookup", "lookup"):
                turns: List[Turn] = []
                prefill: Dict[Any, int] = {}
                seen = set()
                index = model.item_index
                for _, q in queries:
                    key = q.user
                    ids = [index.get(e) for e in (q.events or ())]
                    items = np.asarray([i for i in ids if i is not None],
                                       np.int32)
                    # A miss re-reads as much as one dispatch attends over.
                    room = cache.max_events - len(items)
                    if key not in seen and room > 0 \
                            and not cache.has(key, count=True):
                        history = model.stored_history(q.user, room)
                        if len(history):
                            prefill[key] = len(history)
                            items = np.concatenate([history, items])
                    seen.add(key)
                    turns.append(Turn(key, items, max(int(q.num), 1)))
            answers = runtime.extend(turns, prefill)
            with dispatch_stage("predict.assemble", "assemble"):
                # A turn's answer is as long as its own ``num`` (none
                # for a user with no event): one block, short rows
                # padded as the retrieval facade pads.
                k = max(len(s) for s, _ in answers)
                scores = np.full((len(answers), k), -np.inf, np.float32)
                ids = np.full((len(answers), k), -1, np.int32)
                for row, (s, i) in enumerate(answers):
                    scores[row, :len(s)] = s
                    ids[row, :len(i)] = i
                keys_of = model.item_index.keys_of
                columns = hit_columns(scores, ids,
                                      [q.num for _, q in queries])
                return [(i, PredictedResult(itemScores=ItemScoreColumns(
                    keys_of(item_ids), item_scores, ItemScore)))
                    for (i, _), (item_ids, item_scores)
                    in zip(queries, columns)]


def engine() -> Engine:
    return Engine(
        datasource_class=SequenceDataSource,
        preparator_class=SequencePreparator,
        algorithm_classes={"sequence": SequenceAlgorithm},
        serving_class=FirstServing,
        query_class=Query,
    )
