"""Engine Server — the `pio deploy` target.

Reference: core/.../workflow/CreateServer.scala (SURVEY.md §3.2): resolve
the latest COMPLETED engine instance, load its models, answer
``POST /queries.json`` through Algorithm.predict → Serving.serve, support
hot-reload after retrain (``POST /reload``), and a status page at ``GET /``.

The per-request path binds the query JSON to the engine's ``query_class``
dataclass (reference: JsonExtractor), runs every algorithm, and serializes
the served result back to JSON.  ``GET /metrics`` adds the rebuild's
latency histogram (SURVEY.md §5.5).
"""

from __future__ import annotations

import contextlib
import dataclasses
import datetime as _dt
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple
from urllib.parse import urlparse

from predictionio_tpu.controller import Engine, EngineVariant, RuntimeContext
from predictionio_tpu.controller.columns import dispatch_tally
from predictionio_tpu.controller.params import bind_params
from predictionio_tpu.data.storage import (
    Storage,
    StorageUnavailable,
    get_storage,
)
from predictionio_tpu.obs import (
    dispatch_stage,
    get_registry,
    publish_event,
    span,
    start_runtime_introspection,
)
from predictionio_tpu.obs import waterfall as _waterfall
from predictionio_tpu.obs.profiler import handle_http as profiler_http
from predictionio_tpu.obs.quality import SERVE_ID_HEADER, QualityMonitor
from predictionio_tpu.obs.recall import RecallMonitor
from predictionio_tpu.obs.slo import SLOConfig, SLOEngine
from predictionio_tpu.resilience import deadline as _deadline
from predictionio_tpu.resilience.deadline import DeadlineExceeded
from predictionio_tpu.resilience.faults import fault_point
from predictionio_tpu.resilience.policy import CircuitBreaker, CircuitOpenError
from predictionio_tpu.resilience.supervision import (
    ModelValidationError,
    validate_model_finite,
)
from predictionio_tpu.server.http import (
    BaseHandler,
    ThreadingHTTPServer,
    timeline_payload,
    traces_payload,
    param_bool,
)
from predictionio_tpu.config import env_bool
from predictionio_tpu.serving import (
    QueueFull,
    ResultCache,
    ResultCacheConfig,
    SchedulerClosed,
    SchedulerConfig,
    SchedulerStalled,
    ServingScheduler,
    canonical_query,
)
from predictionio_tpu.version import __version__
from predictionio_tpu.workflow.core_workflow import (
    WorkflowError,
    data_watermark,
    instance_engine_params,
    load_models,
)

logger = logging.getLogger(__name__)

__all__ = ["EngineServer", "QueryError"]

_DC_FIELDS: Dict[type, Tuple[str, ...]] = {}


def _dc_to_json(obj: Any) -> Any:
    """Shallow-recursive dataclass→dict for predicted results.

    Keeps ``dataclasses.asdict``'s JSON-visible contract (dataclasses
    nested in lists/tuples/dict values convert; tuples serialize as
    arrays) by a cached-field walk, without asdict's deep copy of every
    leaf value.  A value that has its own JSON form (``pio_json``: a
    template's :class:`~predictionio_tpu.controller.ItemScoreColumns`)
    is asked for it and not walked.
    """
    fields = _DC_FIELDS.get(type(obj))
    if fields is None:
        fields = tuple(f.name for f in dataclasses.fields(obj))
        _DC_FIELDS[type(obj)] = fields
    return {name: _val_to_json(getattr(obj, name)) for name in fields}


def _val_to_json(v: Any) -> Any:
    render = getattr(type(v), "pio_json", None)
    if render is not None:
        return render(v)
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return _dc_to_json(v)
    if isinstance(v, (list, tuple)):
        return [_val_to_json(x) for x in v]
    if isinstance(v, dict):
        return {k: _val_to_json(x) for k, x in v.items()}
    return v


def _by_query(answers: List[List[Tuple[int, Any]]], n: int
              ) -> List[List[Any]]:
    """``out[i]``: every algorithm's prediction for query ``i``, from
    each algorithm's ``batch_predict`` pairs.  Pairs that answer every
    index in order (no cold member was moved to the front) are read as
    they stand; any others go through a dict."""
    in_order = list(range(n))
    per_algo = []
    for pairs in answers:
        pairs = list(pairs)
        if [i for i, _ in pairs] == in_order:
            per_algo.append([p for _, p in pairs])
        else:
            by_index = dict(pairs)
            per_algo.append([by_index[i] for i in in_order])
    if not per_algo:   # an engine.json with no algorithm
        return [[] for _ in in_order]
    return [list(ps) for ps in zip(*per_algo)]


class QueryError(ValueError):
    pass


class _QueryMetrics:
    """Serving instruments over the shared registry; ``/metrics`` and
    ``/stats.json`` are views of these series."""

    def __init__(self, registry=None):
        self.registry = registry or get_registry()
        self.requests = self.registry.counter(
            "pio_query_requests_total", "Predict requests served.")
        self.errors = self.registry.counter(
            "pio_query_errors_total", "Predict requests that failed.")
        self.latency = self.registry.histogram(
            "pio_query_latency_ms", "Predict request latency.")
        self.shed = self.registry.counter(
            "pio_deadline_shed_total",
            "Requests shed with 504 because their deadline expired.",
            ("server",))
        self.items = self.registry.counter(
            "pio_dispatch_items_total",
            "Items of batched dispatches' answers: form=columns left as "
            "JSON rendered from the template's two columns, form=objects "
            "had an ItemScore object built on the way.", ("form",))

    def count_items(self, columns: int, objects: int) -> None:
        """One dispatch's answered items, by the form they left in."""
        if columns:
            self.items.inc(columns, form="columns")
        if objects:
            self.items.inc(objects, form="objects")

    def record(self, ms: float, ok: bool) -> None:
        self.requests.inc()
        if not ok:
            self.errors.inc()
        self.latency.observe(ms)

    def snapshot(self) -> Dict[str, Any]:
        return {"requestCount": int(self.requests.value()),
                "errorCount": int(self.errors.value()),
                "latencyMs": {"p50": self.latency.quantile(0.5),
                              "p95": self.latency.quantile(0.95),
                              "p99": self.latency.quantile(0.99)}}


class _Generation:
    """One immutable loaded-model generation (instance + built serving
    stack).  The server swaps whole generations under the lock and keeps
    the previous one for instant ``POST /admin/rollback``."""

    __slots__ = ("instance", "models", "algorithms", "serving", "loaded_at",
                 "number")

    def __init__(self, instance, models, algorithms, serving, loaded_at,
                 number):
        self.instance = instance
        self.models = models
        self.algorithms = algorithms
        self.serving = serving
        self.loaded_at = loaded_at
        self.number = number


class EngineServer:
    """Loads a trained engine instance and serves queries over HTTP.

    Reference roles: MasterActor (lifecycle/reload supervision) and
    ServerActor (request handling) collapse into this class.  The reload
    path is STAGED (the rebuild's answer to actor supervision — ISSUE 4):
    breaker-guarded storage reads, candidate built off to the side,
    validated (finite params + optional ``PIO_CANARY_QUERIES`` golden
    queries), then atomically swapped under the lock with the previous
    generation retained for ``POST /admin/rollback``.  A failed reload
    keeps serving the last-good model — ``pio_model_reload_total{result}``
    and ``pio_model_generation`` make the outcome observable.
    """

    def __init__(
        self,
        engine: Engine,
        variant: EngineVariant,
        storage: Optional[Storage] = None,
        host: str = "0.0.0.0",
        port: int = 8000,
        *,
        engine_id: Optional[str] = None,
        engine_version: str = __version__,
        instance_id: Optional[str] = None,
        mesh_spec: Optional[str] = None,
        plugins=None,
        breaker: Optional[CircuitBreaker] = None,
        scheduler_config: Optional[SchedulerConfig] = None,
    ):
        from predictionio_tpu.server.plugins import PluginManager

        self.engine = engine
        self.variant = variant
        self.storage = storage or get_storage()
        self.ctx = RuntimeContext.create(storage=self.storage, mesh_spec=mesh_spec)
        self.host = host
        self.port = port
        self.engine_id = engine_id or variant.engine_factory
        self.engine_version = engine_version
        self.requested_instance_id = instance_id
        self.stats = _QueryMetrics()
        # Runtime introspection: registers pio_xla_compile_* /
        # pio_device_mem_* so /metrics exposes them from t=0, and starts
        # the memory-sampler thread (jax is loaded here — models are).
        start_runtime_introspection()
        self._swap_lock = threading.Lock()
        self._stop_requested = threading.Event()  # POST /stop, see handle()
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._instance = None
        self._algorithms: List[Any] = []
        self._models: List[Any] = []
        self._serving = None
        self._loaded_at: Optional[_dt.datetime] = None
        self._init_lifecycle_state(breaker, scheduler_config)
        self.reload()
        # Server plugin seam (reference: EngineServerPlugin, SURVEY §5.1).
        # Started LAST — after reload() — so plugins see a fully
        # constructed server with a loaded instance.
        self.plugins = (plugins if plugins is not None
                        else PluginManager.from_env("PIO_ENGINESERVER_PLUGINS"))
        self.plugins.start(self)

    # -- model lifecycle ----------------------------------------------------

    def _init_lifecycle_state(
            self,
            breaker: Optional[CircuitBreaker] = None,
            scheduler_config: Optional[SchedulerConfig] = None) -> None:
        """Staged-reload state + the serving scheduler: lock, generations,
        breaker, instruments.  Factored out of ``__init__`` so test
        skeletons built with ``__new__`` (tests/test_resilience.py) stay
        in lock-step — their ``/queries.json`` calls ride the same
        admission queue + micro-batcher as production."""
        self._reload_lock = threading.Lock()  # serialize staged reloads
        self._generation = 0
        self._previous: Optional[_Generation] = None
        self._last_reload: Dict[str, Any] = {}
        # Breaker around reload()'s storage reads (ROADMAP resilience
        # follow-on (a)): a dead model store must shed fast with
        # Retry-After, not hang every /reload until TCP gives up.
        self._breaker = breaker or CircuitBreaker(
            "modeldata",
            failure_threshold=int(os.environ.get(
                "PIO_BREAKER_THRESHOLD", "5")),
            recovery_time_s=float(os.environ.get(
                "PIO_BREAKER_RECOVERY_S", "10")),
            failure_types=(StorageUnavailable, ConnectionError))
        self.retry_after_s = int(os.environ.get("PIO_RETRY_AFTER_S", "5"))
        # Retained-previous policy (ROADMAP carry-forward: the rollback
        # generation doubles model memory while it lives).  off = never
        # retain; TTL > 0 = drop it after the canary window.
        self._retain_previous = env_bool(
            os.environ.get("PIO_RETAIN_PREVIOUS"), True)
        try:
            self._retain_ttl_s = float(
                os.environ.get("PIO_RETAIN_PREVIOUS_TTL_S", "0") or 0)
        except ValueError:
            self._retain_ttl_s = 0.0
        self._evict_timer: Optional[threading.Timer] = None
        reg = self.stats.registry
        self._reload_total = reg.counter(
            "pio_model_reload_total",
            "Staged model reloads by outcome.", ("result",))
        self._gen_gauge = reg.gauge(
            "pio_model_generation",
            "Monotonic generation of the model currently serving "
            "(bumped by every successful reload or rollback).")
        self._prev_retained = reg.gauge(
            "pio_model_previous_retained",
            "1 while a rollback generation is held in memory.")
        self._prev_evicted = reg.counter(
            "pio_model_previous_evicted_total",
            "Rollback generations dropped by the PIO_RETAIN_PREVIOUS_TTL_S "
            "eviction timer.")
        # Serving scheduler (ISSUE 6): every /queries.json rides the
        # admission queue + micro-batcher; handlers never reach the
        # model directly (tools/lint_dispatch.py pins this).
        self.scheduler = ServingScheduler(
            config=scheduler_config or SchedulerConfig.from_env())
        self.scheduler.register("default", self._dispatch_batch)
        # SLO engine (ISSUE 9): multi-window burn rates over the serving
        # instruments + the autotuner's persistent-floor saturation
        # detector, combined into the /ready degradation verdict
        # (PIO_READY_SLO=off disables the flip, never the gauges).
        self.slo = SLOEngine(SLOConfig.from_env(),
                             registry=reg,
                             saturation_fn=self.scheduler.saturated)
        # Model-quality layer (ISSUE 11): sampled prediction stream +
        # drift detection + shadow-scored canary + feedback join, all
        # behind the PIO_QUALITY kill switch (off = inert no-op hooks).
        self.quality = QualityMonitor(registry=reg)
        # Retrieval-recall layer (ISSUE 16): sampled exact re-rank of
        # approximate-rung answers vs each generation's own baked recall
        # scorecard, folded into /quality.json's gate as a third
        # verdict.  PIO_RECALL=off registers zero instruments and can
        # never block a promotion.
        self.recall = RecallMonitor(registry=reg)
        # Serve-side result cache (ISSUE 20): the FIRST stop on the query
        # path, keyed by (generation fingerprint, canonical query) so every
        # reload/rollback invalidates by construction.  The optional fleet
        # tier rides the PR-13 shared KV; a missing/broken KV degrades to
        # the per-instance LRU, never fails construction.
        cache_cfg = ResultCacheConfig.from_env()
        cache_kv = None
        if cache_cfg.shared and getattr(self, "storage", None) is not None:
            try:
                cache_kv = self.storage.get_kv()
            except Exception:
                logger.warning("result cache: shared tier unavailable; "
                               "running local-only", exc_info=True)
        self.result_cache = ResultCache(cache_cfg, registry=reg,
                                        kv=cache_kv)

    def _load_candidate(self, target_instance_id: Optional[str] = None):
        """Storage-read phase of the staged reload (runs under the
        breaker): resolve the target instance and load its models.

        ``target_instance_id`` (ISSUE 15) pins the candidate explicitly
        — the fleet rollout controller names ONE instance id on every
        ``POST /reload`` so a newer COMPLETED train landing mid-wave can
        never split the fleet across generations."""
        instances = self.storage.get_engine_instances()
        requested = target_instance_id or self.requested_instance_id
        if requested:
            instance = instances.get(requested)
            if instance is None or instance.status != "COMPLETED":
                raise WorkflowError(
                    f"Engine instance {requested!r} not found "
                    "or not COMPLETED.")
        else:
            instance = instances.get_latest_completed(
                self.engine_id, self.engine_version, self.variant.variant_id)
            if instance is None:
                raise WorkflowError(
                    f"No COMPLETED engine instance for engine id "
                    f"{self.engine_id!r} variant {self.variant.variant_id!r} — "
                    "run `pio train` first.")
        models = load_models(self.engine, instance, self.ctx)
        return instance, models

    @staticmethod
    def _canary_queries() -> List[Any]:
        """Golden queries from ``PIO_CANARY_QUERIES``: inline JSON array,
        or a path to a JSON-array / NDJSON file.  Empty/unset disables
        the canary stage."""
        raw = os.environ.get("PIO_CANARY_QUERIES", "").strip()
        if not raw:
            return []
        if raw.startswith("["):
            return json.loads(raw)
        with open(raw, encoding="utf-8") as f:
            text = f.read().strip()
        if text.startswith("["):
            return json.loads(text)
        return [json.loads(line) for line in text.splitlines() if line.strip()]

    def _validate_candidate(self, instance, models, algorithms,
                            serving) -> None:
        """Validation stage: a candidate that cannot be trusted never
        reaches the swap.  Finite-params sanity over every array the
        models carry, then the optional golden-query canary — each
        PIO_CANARY_QUERIES entry must predict without raising."""
        for i, model in enumerate(models):
            validate_model_finite(model, name=f"models[{i}]")
        for qi, query_json in enumerate(self._canary_queries()):
            try:
                self._predict_with(algorithms, models, serving, query_json)
            except Exception as e:
                raise ModelValidationError(
                    f"candidate instance {instance.id} failed canary "
                    f"query #{qi} ({query_json!r}): "
                    f"{type(e).__name__}: {e}") from e

    def _record_reload(self, result: str, error: Optional[str] = None,
                       **extra) -> None:
        self._reload_total.inc(result=result)
        self._last_reload = {
            "result": result,
            "at": _dt.datetime.now(_dt.timezone.utc).isoformat(),
            **({"error": error} if error else {}),
        }
        publish_event("model.reload", result=result,
                      **({"error": error[:200]} if error else {}), **extra)

    def reload(self, target_instance_id: Optional[str] = None) -> str:
        """Staged reload of the latest COMPLETED instance — or, with
        ``target_instance_id``, of exactly THAT instance (the rollout
        controller's generation-atomic wave contract).

        read (breaker-guarded) → build → validate → swap; any failure
        keeps the last-good generation serving and raises.  The previous
        generation is retained for :meth:`rollback`."""
        with self._reload_lock:
            try:
                instance, models = self._breaker.call(
                    self._load_candidate, target_instance_id)
                engine_params = instance_engine_params(self.engine, instance)
                algorithms = self.engine.make_algorithms(engine_params)
                serving = self.engine.make_serving(engine_params)
                self._validate_candidate(instance, models, algorithms,
                                         serving)
            except Exception as e:
                self._record_reload("failed", error=str(e))
                logger.error("model reload failed (%s); %s", e,
                             "serving continues on the last-good model"
                             if self._instance is not None else
                             "no model is loaded yet")
                raise
            now = _dt.datetime.now(_dt.timezone.utc)
            with self._swap_lock:
                # PIO_RETAIN_PREVIOUS=off: never hold a second generation
                # in memory (large corpora double their footprint while a
                # rollback generation lives).
                self._previous = _Generation(
                    self._instance, self._models, self._algorithms,
                    self._serving, self._loaded_at, self._generation) \
                    if self._instance is not None and self._retain_previous \
                    else None
                outgoing = self._models
                self._instance = instance
                self._models = models
                self._algorithms = algorithms
                self._serving = serving
                self._loaded_at = now
                self._generation += 1
                gen = self._generation
                prev = self._previous
                retained = prev is not None
            self._drop_serving_state(outgoing)
            self._gen_gauge.set(gen)
            self._prev_retained.set(1 if retained else 0)
            # Quality re-anchor (ISSUE 11): the new generation's
            # scorecard becomes the drift baseline, and — while a
            # previous generation is retained for rollback — its predict
            # stack shadow-scores a sampled slice of live queries so the
            # canary window can judge old-vs-new divergence.  The
            # closure is dropped on rollback/eviction so the retained
            # generation's memory is actually freed.
            shadow_fn = None
            if retained and self.quality.enabled:
                def shadow_fn(q, _gen=prev):
                    return self._shadow_predict(_gen, q)
            self.quality.on_generation(
                gen, models, shadow_fn=shadow_fn,
                prev_generation=prev.number if retained else None)
            # Recall re-anchor (ISSUE 16): arm the NEW generation's
            # retriever hook and judge it against its own baked recall
            # scorecard (never the predecessor's).
            self.recall.on_generation(gen, models)
            # Result cache (ISSUE 20): the new instance id becomes the key
            # fingerprint — every pre-swap entry misses by construction.
            self.result_cache.on_generation(gen, instance.id)
            self._arm_eviction(gen)
            self._record_reload("ok", instance=instance.id, generation=gen)
            logger.info("Engine server loaded instance %s (generation %d)",
                        instance.id, gen)
            return instance.id

    def rollback(self) -> str:
        """Instant swap back to the retained previous generation
        (``POST /admin/rollback``).  The generations exchange places, so
        a second rollback returns; raises when none is retained."""
        with self._reload_lock:
            with self._swap_lock:
                prev = self._previous
                if prev is None:
                    raise WorkflowError(
                        "No previous model generation retained — nothing "
                        "to roll back to.")
                outgoing = self._models
                self._previous = _Generation(
                    self._instance, self._models, self._algorithms,
                    self._serving, self._loaded_at, self._generation)
                self._instance = prev.instance
                self._models = prev.models
                self._algorithms = prev.algorithms
                self._serving = prev.serving
                self._loaded_at = prev.loaded_at
                self._generation += 1
                gen = self._generation
                instance_id = prev.instance.id
                restored_models = prev.models
            self._drop_serving_state(outgoing)
            self._gen_gauge.set(gen)
            self._prev_retained.set(1)
            # Quality: the rollback ends any shadow session (the "new"
            # generation it was judging is out) and re-anchors drift on
            # the RESTORED generation's own scorecard.
            self.quality.on_generation(gen, restored_models)
            self.recall.on_generation(gen, restored_models)
            # Restoring the previous instance id revalidates its surviving
            # cache entries for free — the fingerprint IS the key.
            self.result_cache.on_generation(gen, instance_id)
            # The rolled-from generation now sits in the previous slot;
            # it ages out on the same TTL as any other retained one.
            self._arm_eviction(gen)
            self._record_reload("rollback", instance=instance_id,
                                generation=gen)
            logger.warning("Engine server rolled back to instance %s "
                           "(generation %d)", instance_id, gen)
            return instance_id

    @staticmethod
    def _drop_serving_state(models: List[Any]) -> None:
        """A generation that stops serving forgets its per-user serving
        state (``state_cache``) and gives that state's device memory
        back: while another generation answers, the state falls behind
        the users' events, so a rollback starts from misses (which
        re-read the history) and never from stale state."""
        for m in models or ():
            drop = getattr(m, "drop_serving_state", None)
            if callable(drop):
                drop()

    def _arm_eviction(self, generation: int) -> None:
        """(Re)start the retained-previous TTL timer for ``generation``.

        The timer carries the generation it was armed for: if a newer
        reload/rollback swapped again before it fires, the stale timer's
        eviction is a no-op (the new swap armed its own)."""
        timer, self._evict_timer = self._evict_timer, None
        if timer is not None:
            timer.cancel()
        if self._retain_ttl_s <= 0:
            return
        with self._swap_lock:
            if self._previous is None:
                return
        timer = threading.Timer(self._retain_ttl_s, self._evict_previous,
                                args=(generation,))
        timer.daemon = True
        timer.start()
        self._evict_timer = timer

    def _evict_previous(self, expected_generation: int) -> bool:
        """Drop the retained rollback generation (frees its model memory)
        — called by the TTL timer after the canary window, or directly.
        Returns False when a newer swap already owns the previous slot."""
        with self._swap_lock:
            if (self._generation != expected_generation
                    or self._previous is None):
                return False
            dropped = self._previous
            self._previous = None
        self._prev_retained.set(0)
        self._prev_evicted.inc()
        # The shadow session holds the evicted generation's predict
        # closure — drop it with the generation, or the eviction frees
        # nothing.
        self.quality.end_shadow("previous generation evicted")
        publish_event("model.previous_evicted",
                      generation=expected_generation,
                      evicted_generation=dropped.number)
        logger.info("Evicted retained previous model generation %d after "
                    "%.0fs TTL (rollback no longer available)",
                    dropped.number, self._retain_ttl_s)
        return True

    # -- query path ---------------------------------------------------------

    def _bind_query(self, obj: Any):
        if self.engine.query_class is None:
            return obj
        if dataclasses.is_dataclass(self.engine.query_class):
            try:
                return bind_params(self.engine.query_class, obj, _path="query")
            except TypeError as e:
                raise QueryError(str(e)) from e
        return self.engine.query_class(**obj)

    @staticmethod
    def _result_to_json(result: Any) -> Any:
        if dataclasses.is_dataclass(result) and not isinstance(result, type):
            return _dc_to_json(result)
        return result

    def _predict_with(self, algorithms, models, serving,
                      query_json: Any) -> Any:
        """bind → supplement → per-algorithm predict → serve against an
        EXPLICIT model set — the live generation (``query``) and the
        reload canary both ride this path."""
        with span("predict.bind"):
            q = self._bind_query(query_json)
        with span("predict.supplement"):
            q = serving.supplement(q)
        predictions = []
        for a, m in zip(algorithms, models):
            with span("predict.algorithm", algo=type(a).__name__):
                predictions.append(a.predict(m, q))
        with span("predict.serve"):
            return self._result_to_json(serving.serve(q, predictions))

    def _shadow_predict(self, gen: _Generation, q: Any) -> Any:
        """Score one BOUND query against a retained (non-serving)
        generation's full predict stack — the shadow-scoring canary's
        reference answer (ISSUE 11).  Runs on the shadow worker thread,
        never a handler thread."""
        q2 = gen.serving.supplement(q)
        preds = [a.predict(m, q2)
                 for a, m in zip(gen.algorithms, gen.models)]
        return self._result_to_json(gen.serving.serve(q2, preds))

    def query(self, query_json: Any) -> Any:
        """One predict round-trip (reference §3.2 hot path).

        Span-per-phase under an active trace: bind → supplement →
        per-algorithm predict → serve.  Outside a trace each ``span`` is
        two perf_counter calls — the hot path stays hot.
        """
        with self._swap_lock:
            algorithms, models, serving = (
                self._algorithms, self._models, self._serving)
        return self._predict_with(algorithms, models, serving, query_json)

    def _dispatch_batch(self, bound_queries: List[Any]
                        ) -> Tuple[List[Any], int]:
        """THE batched dispatch the serving scheduler drives: one
        ``batch_predict`` (vectorized XLA) call per algorithm for the
        whole cohort, against ONE generation snapshot taken under a
        single swap-lock acquisition — a reload/rollback landing
        mid-batch flips the next batch, never splits this one.

        Takes BOUND queries: binding is per-member, client-controlled
        failure, so it happens at admission (handler thread → its own
        400) and can never fail a cohort.  ``supplement`` stays here —
        it belongs to the generation's serving instance.

        The contract with ``batch_predict``: ``bound_queries`` are in
        ARRIVAL order and are handed over in that order, so an algorithm
        whose answers depend on earlier queries (a user's turns) applies
        them as they came, within a cohort as across cohorts.  An
        algorithm may hold state across calls ONLY through its model's
        ``state_cache`` (:mod:`predictionio_tpu.serving.state_cache`):
        the dispatch holds that cache's transaction from before the
        first ``batch_predict`` until ``serve`` has answered every
        member, so a dispatch that raises anywhere leaves every user's
        state as it was, and the batcher's member-by-member retry of a
        failed cohort applies no event twice.  (That holds for as many
        users a dispatch as the cache's write pool, which is the
        scheduler's largest cohort; a larger ``query_batch`` call
        commits device program by device program.)"""
        with self._swap_lock:
            algorithms, models, serving, generation = (
                self._algorithms, self._models, self._serving,
                self._generation)
        with dispatch_stage("dispatch.supplement", "supplement"):
            queries = [serving.supplement(q) for q in bound_queries]
            indexed = list(enumerate(queries))
        with contextlib.ExitStack() as held:
            for m in models:
                cache = getattr(m, "state_cache", None)
                if cache is not None:
                    held.enter_context(cache.transaction())
            tally = dispatch_tally()
            tally.columns = tally.objects = 0
            predictions = _by_query(
                [a.batch_predict(m, indexed)
                 for a, m in zip(algorithms, models)], len(indexed))
            with dispatch_stage("dispatch.serve", "serve"):
                out = [self._result_to_json(serving.serve(q, ps))
                       for q, ps in zip(queries, predictions)]
            self.stats.count_items(tally.columns, tally.objects)
            return out, generation

    def query_batch(self, query_jsons: List[Any]) -> List[Any]:
        """Batched predict (native frontend, ``pio batchpredict``): the
        scheduler's dispatch path without the generation tag."""
        with dispatch_stage("query_batch.bind", "bind"):
            bound = [self._bind_query(qj) for qj in query_jsons]
        return self._dispatch_batch(bound)[0]

    # -- HTTP ---------------------------------------------------------------

    def handle(self, method: str, path: str, body: bytes,
               params: Optional[Dict[str, List[str]]] = None
               ) -> Tuple[int, Any]:
        params = params or {}
        try:
            fault_point("http.engine")
            if path == "/" and method == "GET":
                # Lazy: the server package stays importable without jax
                # (`pio eventserver` and the storage tools never need it).
                from predictionio_tpu.backend import (
                    compile_stats,
                    describe_backend,
                )

                with self._swap_lock:
                    inst = self._instance
                    loaded = self._loaded_at
                    gen = self._generation
                    prev = self._previous
                wm = data_watermark(inst) if inst else None
                return 200, {
                    "status": "alive",
                    "engineFactory": self.variant.engine_factory,
                    "variant": self.variant.variant_id,
                    "engineInstanceId": inst.id if inst else None,
                    "modelLoadedAt": loaded.isoformat() if loaded else None,
                    "modelGeneration": gen,
                    # ISSUE 10: the served generation's data high-
                    # watermark — events before this instant are in the
                    # model; the gap to pio_events_latest_ts is the
                    # event→servable staleness.
                    "dataWatermark": wm.isoformat() if wm else None,
                    "refreshMode": (inst.env or {}).get("refreshMode")
                    if inst else None,
                    "lastReload": self._last_reload or None,
                    "rollbackAvailable": prev is not None,
                    "retainPreviousTtlS": self._retain_ttl_s or None,
                    "breaker": self._breaker.state,
                    "batcher": self.scheduler.snapshot(),
                    "resultCache": self.result_cache.snapshot(),
                    "slo": self.slo.snapshot(),
                    # The accelerator THIS process serves from, as jax
                    # reports it (the instance's env carries the one it
                    # was trained on).
                    "backend": {**describe_backend().as_json(),
                                **compile_stats()},
                    "version": __version__,
                }
            if path == "/ready" and method == "GET":
                # Readiness (vs "/" liveness): a model is loaded AND the
                # SLO/saturation signal is healthy — 503 rotates the
                # instance out of the LB pool (ISSUE 9: persistent-floor
                # saturation + burn rate flip this; PIO_READY_SLO=off is
                # the operator escape hatch; hysteresis in the engine).
                with self._swap_lock:
                    inst = self._instance
                    serving = self._serving
                loaded = inst is not None and serving is not None
                slo_ok, slo_state = self.slo.ready()
                ok = loaded and slo_ok
                status = "ready" if ok else (
                    "degraded" if loaded else "unavailable")
                return (200 if ok else 503), {
                    "status": status,
                    "engineInstanceId": inst.id if inst else None,
                    "slo": slo_state,
                }
            if path == "/metrics" and method == "GET":
                # THE process-wide exposition (shared registry render).
                # ?exemplars=1 appends the OpenMetrics trace-id suffixes
                # to waterfall buckets — opt-in, classic scrapers choke.
                return 200, self.stats.registry.render(
                    exemplars=param_bool(params, "exemplars"))
            if path == "/stats.json" and method == "GET":
                with self._swap_lock:
                    inst = self._instance
                wm = data_watermark(inst) if inst else None
                return 200, {**self.stats.snapshot(),
                             "batcher": self.scheduler.snapshot(),
                             "resultCache": self.result_cache.snapshot(),
                             "slo": self.slo.snapshot(),
                             "quality": self.quality.summary(),
                             "dataWatermark": wm.isoformat() if wm
                             else None}
            if path == "/quality.json" and method == "GET":
                # Model-quality document (ISSUE 11): drift vs the
                # training scorecard, shadow-canary divergence, online
                # hit-rate, and the promotion-gate verdict the refresh
                # daemon polls during the canary window.  The recall
                # layer (ISSUE 16) folds its verdict into the same gate
                # — the daemon/rollout read only gate.rollback, so a
                # recall regression rolls back through the existing path.
                return 200, self.recall.augment_quality(
                    self.quality.payload())
            if path == "/traces.json" and method == "GET":
                # ?request_id= resolves waterfall exemplars to ONE trace;
                # ?min_ms=/?limit= bound the view (shared helper).
                return 200, traces_payload(params)
            if path == "/timeline.json" and method == "GET":
                # Step-timeline ring: ?model=/?n=/?format=chrome for the
                # chrome://tracing / Perfetto export.
                return 200, timeline_payload(params)
            if path == "/reload" and method == "POST":
                # Optional target pin (ISSUE 15): the rollout controller
                # posts {"engineInstanceId": ...} so every instance in a
                # wave loads the SAME candidate.
                target = None
                if body:
                    try:
                        target = (json.loads(body.decode("utf-8"))
                                  or {}).get("engineInstanceId")
                    except (ValueError, AttributeError):
                        return 400, {"message": "reload body must be "
                                                "JSON"}
                try:
                    instance_id = self.reload(target)
                except ModelValidationError as e:
                    # Candidate rejected by the validation stage: the
                    # last-good model keeps serving — a client fault
                    # (bad train), not an availability failure.
                    return 409, {"message": str(e),
                                 "status": "rejected"}
                except WorkflowError as e:
                    if target:
                        # An explicitly named candidate this server
                        # cannot load (not COMPLETED / unknown): reject
                        # like a validation failure — the wave skips and
                        # reports, last-good keeps serving.
                        return 409, {"message": str(e),
                                     "status": "rejected"}
                    raise
                return 200, {"status": "reloaded",
                             "engineInstanceId": instance_id,
                             "generation": self._generation}
            if path.startswith("/admin/profile"):
                # The operator's capture of THIS process, the one that
                # holds the chip (`pio profile --url <engine> --out F`):
                # device ops beside the pio: host spans.
                out = profiler_http(method, path, params)
                if out is not None:
                    return out
            if path == "/admin/rollback" and method == "POST":
                try:
                    instance_id = self.rollback()
                except WorkflowError as e:
                    return 409, {"message": str(e)}
                return 200, {"status": "rolled_back",
                             "engineInstanceId": instance_id,
                             "generation": self._generation}
            if path == "/queries.json" and method == "POST":
                t0 = time.perf_counter()
                # Arm the latency waterfall (ISSUE 9): stages stamped
                # here (bind), by the batcher (queue/batch/dispatch/
                # retrieval), and by the transport driver (serialize/
                # shed_check), which also finalizes + publishes it after
                # the response is written.
                _waterfall.activate()
                try:
                    # Shed BEFORE admission: a request whose budget is
                    # spent must not occupy a queue slot.
                    _deadline.check("predict")
                    # Bind BEFORE admission: a malformed query 400s on
                    # this thread and never occupies a queue slot or
                    # fails the batch it would have ridden in.
                    tb = time.perf_counter()
                    # ingress: transport receipt → here (socket body
                    # read, trace setup, routing, the deadline check) —
                    # real wall the attestation contains, so the
                    # waterfall must bill it.
                    t0t = _waterfall.transport_start()
                    if t0t is not None and tb > t0t:
                        _waterfall.record_stage("ingress",
                                                (tb - t0t) * 1e3)
                    q = self._bind_query(json.loads(body.decode("utf-8")))
                    _waterfall.record_stage(
                        "bind", (time.perf_counter() - tb) * 1e3)
                    # The ONLY route to the model: admission queue →
                    # micro-batcher → vectorized dispatch (ISSUE 6; the
                    # lint forbids calling query/query_batch from here).
                    wf = _waterfall.current_waterfall()
                    # ONE uniform draw per request (ISSUE 11): the
                    # prediction record stream, shadow sampling, and the
                    # PIO_REQUEST_LOG_SAMPLE wide-event sampler all
                    # compare this same u against their own rates.
                    u = self.quality.draw() if self.quality.enabled \
                        else (self.recall.draw()
                              if self.recall.enabled else None)
                    if wf is not None and u is not None:
                        wf.sample_u = u
                    # Result cache (ISSUE 20): the first stop after bind.
                    # A hit bypasses admission/batching entirely but
                    # stamps the `cache` stage with the FILL generation —
                    # attribution and the serve-id describe the answer
                    # actually served — and rides the same quality record
                    # stream as a dispatched request, so a 95%-hit-rate
                    # drive still feeds the drift windows.  The lookup
                    # cost is stamped on misses too: it is real wall the
                    # attestation contains.
                    canon = None
                    if self.result_cache.enabled:
                        tc = time.perf_counter()
                        try:
                            canon = canonical_query(q)
                        except TypeError:
                            canon = None  # uncacheable query shape
                        hit = (self.result_cache.lookup(canon)
                               if canon is not None else None)
                        _waterfall.record_stage(
                            "cache", (time.perf_counter() - tc) * 1e3,
                            cacheHit=hit is not None)
                        if hit is not None:
                            if wf is not None:
                                wf.note(generation=hit.generation,
                                        cacheTier=hit.tier,
                                        cacheAgeS=round(hit.age_s, 3))
                                wf.mark("handler_done")
                            # Same never-late-200 gate as the dispatch
                            # path: a hit found past the budget still
                            # sheds.
                            _deadline.check("respond")
                            # Parse the document only when this request
                            # is quality-sampled (same gate observe
                            # applies): an unsampled hit serves the
                            # cached bytes untouched.
                            if (u is not None and self.quality.enabled
                                    and u < self.quality.config.sample):
                                sid = self.quality.observe(
                                    q, hit.result, hit.generation, u)
                                if sid is not None and wf is not None:
                                    wf.note(serveId=sid)
                            self.stats.record(
                                (time.perf_counter() - t0) * 1e3, True)
                            return (200, hit.result_bytes,
                                    "application/json; charset=UTF-8")
                    try:
                        result = self.scheduler.submit_and_wait(
                            "default", q)
                    finally:
                        # shed_check opens here: the transport stamps it
                        # from this mark so the span-unwind/stats segment
                        # between scheduler hand-back and the respond
                        # write is accounted, not lost.
                        if wf is not None:
                            wf.mark("handler_done")
                    # Cache fill at the scheduler hand-back, under the
                    # generation the batcher STAMPED at dispatch — never
                    # "current" — so a mid-flight swap can't cache
                    # generation A's answer under B's key.  Before the
                    # respond gate: a result that arrives past its budget
                    # still warms the cache for the retry.
                    if canon is not None:
                        self.result_cache.fill(
                            canon, result,
                            wf.attr("generation") if wf is not None
                            else None)
                    # Final gate: a result that arrived past its own
                    # deadline is never served as a slow 200 — the
                    # client's budget is spent, so it gets the same 504
                    # the waiter would have raised a tick later.
                    _deadline.check("respond")
                    # Quality record stream, at the scheduler hand-back
                    # (the request side of the dispatch boundary): one
                    # sampled append, attributed to the generation the
                    # batcher stamped on the dispatch.
                    sid = self.quality.observe(
                        q, result,
                        wf.attr("generation") if wf is not None else None,
                        u)
                    if sid is not None and wf is not None:
                        # Rides the waterfall into the wide event AND to
                        # the transport hook that echoes it as
                        # X-PIO-Serve-Id — a client that sends the id
                        # back on its buy/rate event
                        # (properties.pioServeId) closes the feedback
                        # join.
                        wf.note(serveId=sid)
                    self.stats.record((time.perf_counter() - t0) * 1e3, True)
                    return 200, result
                except QueueFull as e:
                    # Admission rejected: 429 + Retry-After (the handler
                    # adds the hint via retry_after_statuses) — back off,
                    # the requests already admitted keep their latency.
                    self.stats.record((time.perf_counter() - t0) * 1e3, False)
                    return 429, {"message": str(e)}
                except DeadlineExceeded as e:
                    self.stats.shed.inc(server="engine")
                    self.stats.record((time.perf_counter() - t0) * 1e3, False)
                    return 504, {"message": str(e)}
                except (SchedulerStalled, SchedulerClosed) as e:
                    self.stats.record((time.perf_counter() - t0) * 1e3, False)
                    return 503, {"message": f"Temporarily unavailable: {e}"}
                except (QueryError, json.JSONDecodeError) as e:
                    self.stats.record((time.perf_counter() - t0) * 1e3, False)
                    return 400, {"message": str(e)}
                except Exception:
                    self.stats.record((time.perf_counter() - t0) * 1e3, False)
                    logger.exception("query failed")
                    return 500, {"message": "Internal server error."}
            if path == "/stop" and method == "POST":
                # The transport stops the server AFTER this answer is on
                # the wire (Handler.do_POST): stopping from here raced the
                # response write, and `pio deploy` could exit with the
                # client still waiting for its 200.
                self._stop_requested.set()
                return 200, {"status": "stopping"}
            return 404, {"message": "Not Found"}
        except DeadlineExceeded as e:
            self.stats.shed.inc(server="engine")
            return 504, {"message": str(e)}
        except (ConnectionError, StorageUnavailable, CircuitOpenError) as e:
            # Injected faults, dead backends, and the reload breaker
            # shedding (CircuitOpenError) are availability failures: 503
            # + Retry-After, not a 500 bug report.  The last-good model
            # keeps serving throughout.
            return 503, {"message": f"Temporarily unavailable: {e}"}
        except Exception:
            logger.exception("engine server internal error")
            return 500, {"message": "Internal server error."}

    def _make_handler(server_self):
        class Handler(BaseHandler):
            server_log_name = "engine-server"
            trace_server_name = "engine"
            # Predicts are read-only: a 200 computed past its budget is
            # safely rewritten to 504 at the transport (never-late-200).
            shed_late_responses = True

            def pio_handle(self, method, path, params, body):
                return server_self.handle(method, path, body, params)

            def pio_shed(self):
                server_self.stats.shed.inc(server="engine")

            def pio_on_complete(self, method, path, status, ms, body,
                                params):
                extra = dict(server_self.plugins.on_request(
                    f"{method} {path}", status, ms) or {}) \
                    if server_self.plugins else {}
                # Serve-id echo (ISSUE 11): the quality layer noted the
                # sampled serve on the request's waterfall; surface it
                # as a response header so the client can echo it on its
                # feedback event.
                wf = _waterfall.current_waterfall()
                sid = wf.attr("serveId") if wf is not None else None
                if sid:
                    extra[SERVE_ID_HEADER] = str(sid)
                return extra or None

            def pio_retry_after_s(self):
                # Breaker-open reload shed carries the breaker's actual
                # recovery hint; other degraded answers the env default.
                open_in = server_self._breaker.retry_after_s()
                return max(1, int(open_in)) if open_in > 0 \
                    else server_self.retry_after_s

            def do_GET(self):  # noqa: N802
                self.dispatch("GET")

            def do_POST(self):  # noqa: N802
                self.dispatch("POST")
                if server_self._stop_requested.is_set():
                    server_self._stop_requested.clear()
                    threading.Thread(target=server_self.stop,
                                     daemon=True).start()

        return Handler

    def start(self, block: bool = False) -> None:
        self._httpd = ThreadingHTTPServer((self.host, self.port),
                                          self._make_handler())
        self.port = self._httpd.server_address[1]
        logger.info("Engine Server listening on %s:%d", self.host, self.port)
        if block:
            self._httpd.serve_forever()
        else:
            self._thread = threading.Thread(target=self._httpd.serve_forever,
                                            daemon=True)
            self._thread.start()

    def stop(self) -> None:
        # Safe to call twice and from two threads (POST /stop's thread and
        # the owner's own stop()): whoever takes the server shuts it down.
        with self._swap_lock:
            httpd, self._httpd = self._httpd, None
        if httpd:
            httpd.shutdown()
            httpd.server_close()
        if self._evict_timer is not None:
            self._evict_timer.cancel()
            self._evict_timer = None
        self.scheduler.close()
        self.quality.close()
        self.recall.close()
        self.plugins.stop()
