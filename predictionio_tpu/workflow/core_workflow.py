"""CoreWorkflow — train/eval runs with engine-instance lifecycle.

Reference: core/.../workflow/CoreWorkflow.scala (runTrain / runEvaluation)
and CreateWorkflow.scala (the spark-submit main).  Call stack parity with
SURVEY.md §3.1/§3.4:

    run_train: bind params → EngineInstance(TRAINING) → Engine.train
      → persist models → EngineInstance(COMPLETED | FAILED)
    run_evaluation: sweep EngineParamsGenerator candidates → Engine.eval
      → Metric.calculate → EvaluationInstance(EVALCOMPLETED)

Model persistence (reference §5.4): models implementing
:class:`~predictionio_tpu.controller.PersistentModel` save themselves (e.g.
orbax sharded checkpoints); everything else is pickled into the MODELDATA
blob store keyed by engine-instance id.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import json
import logging
import os
import pickle
import traceback
from typing import Any, List, Optional, Sequence, Tuple

from predictionio_tpu.controller import (
    Engine,
    EngineParams,
    EngineVariant,
    Evaluation,
    EngineParamsGenerator,
    MetricEvaluatorResult,
    PersistentModel,
    RuntimeContext,
    WarmStartFallback,
)
from predictionio_tpu.controller.params import params_to_dict
from predictionio_tpu.data.storage import (
    EngineInstance,
    EvaluationInstance,
    Model,
    Storage,
)
from predictionio_tpu.obs import (
    get_memory_sampler,
    phase as obs_phase,
    publish_event,
    trace as obs_trace,
)
from predictionio_tpu.resilience.supervision import TrainPreempted
from predictionio_tpu.version import __version__

logger = logging.getLogger(__name__)

__all__ = ["WorkflowError", "run_train", "load_models", "run_evaluation",
           "data_watermark", "DATA_WATERMARK_KEY"]

# EngineInstance.env keys of the online-refresh loop (ISSUE 10).  The env
# dict rides every backend's existing row format (JSON column / deepcopy /
# RPC), so the watermark needs no storage schema change.
DATA_WATERMARK_KEY = "dataWatermark"   # ISO-8601 until-bound of the data read
REFRESH_MODE_KEY = "refreshMode"       # "full" | "warm"
WARM_FROM_KEY = "warmStartFrom"        # parent COMPLETED instance id


def data_watermark(instance: EngineInstance) -> Optional[_dt.datetime]:
    """The data high-watermark recorded on a train run: every event with
    ``event_time < watermark`` was visible to (and bounded the read of)
    that generation.  None for instances written before ISSUE 10."""
    raw = (instance.env or {}).get(DATA_WATERMARK_KEY)
    if not raw:
        return None
    try:
        return _dt.datetime.fromisoformat(raw)
    except ValueError:
        return None


class WorkflowError(RuntimeError):
    pass


def _now() -> _dt.datetime:
    return _dt.datetime.now(_dt.timezone.utc)


def _class_path(obj: Any) -> str:
    cls = obj if isinstance(obj, type) else type(obj)
    return f"{cls.__module__}:{cls.__qualname__}"


def _engine_params_json(engine_params: EngineParams) -> dict:
    return engine_params.to_json_dict()


def run_train(
    engine: Engine,
    variant: EngineVariant,
    ctx: Optional[RuntimeContext] = None,
    *,
    engine_id: Optional[str] = None,
    engine_version: str = __version__,
    warm_from: Any = None,
) -> str:
    """Train an engine variant; returns the COMPLETED engine-instance id.

    Reference: CoreWorkflow.runTrain — including the FAILED-status write on
    error (§5.3 failure observation) which the caller relies on.

    Every run stamps a **data watermark** BEFORE the datasource reads and
    scopes the read to ``event_time < watermark`` (via
    :class:`~predictionio_tpu.data.store.WindowedEventStore`), recording
    the bound in ``instance.env[dataWatermark]`` — this is what makes
    consecutive refresh windows gap- and overlap-free (ISSUE 10): events
    landing mid-read belong to the NEXT generation, by construction.

    ``warm_from`` (a :class:`~predictionio_tpu.refresh.WarmStartContext`)
    switches the run to delta warm-start mode: the datasource reads only
    ``[previous watermark, new watermark)`` and each algorithm continues
    the previous generation's model.  Any
    :class:`~predictionio_tpu.controller.WarmStartFallback` (unsupported
    algorithm, oversized delta, regressed continuation) falls back to a
    full retrain over the complete window inside the SAME engine
    instance — a refresh cycle always lands one generation.
    """
    ctx = ctx or RuntimeContext.create()
    storage: Storage = ctx.storage
    engine_params = engine.bind_engine_params(variant.raw)
    ep_json = _engine_params_json(engine_params)
    # The watermark is pinned before ANY event is read; naive-free UTC ISO
    # so every backend and every host parses the same instant back.
    watermark = _now()
    env = {DATA_WATERMARK_KEY: watermark.isoformat(),
           REFRESH_MODE_KEY: "warm" if warm_from is not None else "full"}
    if warm_from is not None and getattr(warm_from, "instance", None):
        env[WARM_FROM_KEY] = warm_from.instance.id
    # Which accelerator trained this generation (platform / deviceKind /
    # deviceCount / pallas): a model trained on a CPU fallback says so.
    from predictionio_tpu.backend import describe_backend

    env.update(describe_backend().as_env())
    instance = EngineInstance(
        id=None,
        status="TRAINING",
        start_time=_now(),
        end_time=None,
        engine_id=engine_id or variant.engine_factory,
        engine_version=engine_version,
        engine_variant=variant.variant_id,
        engine_factory=variant.engine_factory,
        env=env,
        datasource_params=json.dumps(ep_json["datasource"]["params"]),
        preparator_params=json.dumps(ep_json["preparator"]["params"]),
        algorithms_params=json.dumps(ep_json["algorithms"]),
        serving_params=json.dumps(ep_json["serving"]["params"]),
    )
    instances = storage.get_engine_instances()
    instance_id = instances.insert(instance)
    logger.info("EngineInstance %s TRAINING (factory=%s)", instance_id, variant.engine_factory)
    # Per-train-run device-memory peak (obs.runtime): fresh peak window
    # at run start, the poll thread tracks the high-water mark, and the
    # final sample under the trace pins pio_device_mem_peak_bytes to THIS
    # run — surfaced by `pio status --metrics-url`.
    sampler = get_memory_sampler()
    sampler.reset_peak()
    sampler.start()

    def _windowed(start: Optional[_dt.datetime]) -> RuntimeContext:
        from predictionio_tpu.data.store import WindowedEventStore

        return dataclasses.replace(
            ctx, event_store=WindowedEventStore(storage, start, watermark))

    try:
        # One trace per training run: the DASE phases inside Engine.train
        # (datasource/prepare/algorithm) plus the persist phase below hang
        # off this root; recorded to the ring / PIO_TRACE_FILE on exit.
        with obs_trace("workflow.train",
                       engine_factory=variant.engine_factory,
                       instance=instance_id,
                       mode=env[REFRESH_MODE_KEY]):
            models = None
            if warm_from is not None:
                try:
                    wctx = _windowed(warm_from.start_time)
                    models = _maybe_profiled(
                        ctx, lambda: engine.train(wctx, engine_params,
                                                  warm=warm_from))
                except WarmStartFallback as e:
                    # The fallback is part of the contract, not a failure:
                    # retrain fully over the complete window, same
                    # instance, and record which road was taken.
                    logger.warning(
                        "EngineInstance %s: warm-start declined (%s) — "
                        "falling back to a full retrain", instance_id,
                        e.reason)
                    publish_event("refresh.warm_fallback",
                                  instance=instance_id,
                                  reason=e.reason[:200])
                    instance.env[REFRESH_MODE_KEY] = "full_fallback"
            if models is None:
                fctx = _windowed(None)
                models = _maybe_profiled(
                    ctx, lambda: engine.train(fctx, engine_params))
            with obs_phase("train.persist"):
                _persist_models(models, instance_id, ctx)
            sampler.sample_once()
        instance.status = "COMPLETED"
        instance.end_time = _now()
        instances.update(instance)
        logger.info(
            "EngineInstance %s COMPLETED in %.1fs",
            instance_id,
            (instance.end_time - instance.start_time).total_seconds(),
        )
        return instance_id
    except TrainPreempted as e:
        # SIGTERM preemption (resilience/supervision.py): a final
        # checkpoint was written, so the distinct status tells the
        # dashboard/supervisor this run resumes, not failed.
        instance.status = "PREEMPTED"
        instance.end_time = _now()
        instances.update(instance)
        logger.warning("EngineInstance %s PREEMPTED at step %d "
                       "(rerun resumes from the checkpoint)",
                       instance_id, e.step)
        raise
    except BaseException:
        # BaseException, not Exception: the step watchdog's abort raises
        # KeyboardInterrupt (interrupt_main) — that run must land as
        # FAILED, not sit in TRAINING forever as a phantom live train.
        instance.status = "FAILED"
        instance.end_time = _now()
        instances.update(instance)
        logger.error("EngineInstance %s FAILED:\n%s", instance_id, traceback.format_exc())
        raise


def _maybe_profiled(ctx: RuntimeContext, fn):
    """JAX profiler integration (SURVEY.md §5.1 rebuild note): set
    ``PIO_PROFILE_DIR`` (or workflow param ``profile_dir``) to capture an
    xplane trace of the training run, viewable in TensorBoard/XProf —
    the substrate's answer to the reference's Spark UI stage timings."""
    import os

    trace_dir = ctx.workflow_params.get("profile_dir") or os.environ.get(
        "PIO_PROFILE_DIR")
    if not trace_dir:
        return fn()
    import jax

    logger.info("Capturing JAX profiler trace to %s", trace_dir)
    with jax.profiler.trace(str(trace_dir)):
        return fn()


def _persist_models(models: Sequence[Any], instance_id: str, ctx: RuntimeContext) -> None:
    """One manifest blob per instance; each entry pickled or self-persisted."""
    entries: List[dict] = []
    payloads: List[Optional[bytes]] = []
    for i, model in enumerate(models):
        if isinstance(model, PersistentModel):
            saved = model.save(f"{instance_id}.{i}", ctx)
            if saved:
                entries.append({"kind": "persistent", "class": _class_path(model)})
                payloads.append(None)
                continue
        entries.append({"kind": "pickle", "class": _class_path(model)})
        payloads.append(pickle.dumps(model, protocol=pickle.HIGHEST_PROTOCOL))
    blob = pickle.dumps({"entries": entries, "payloads": payloads},
                        protocol=pickle.HIGHEST_PROTOCOL)
    ctx.storage.get_models().insert(Model(id=instance_id, models=blob))


def load_models(
    engine: Engine,
    instance: EngineInstance,
    ctx: Optional[RuntimeContext] = None,
) -> List[Any]:
    """Load the trained models of a COMPLETED instance (reference:
    CreateServer model loading / PersistentModelLoader)."""
    ctx = ctx or RuntimeContext.create()
    blob = ctx.storage.get_models().get(instance.id)
    if blob is None:
        raise WorkflowError(f"No model data for engine instance {instance.id}.")
    manifest = pickle.loads(blob.models)
    engine_params = _bind_instance_params(engine, instance)
    algo_params = dict(engine_params.algorithms_params)
    models: List[Any] = []
    for i, (entry, payload) in enumerate(zip(manifest["entries"], manifest["payloads"])):
        if entry["kind"] == "pickle":
            models.append(pickle.loads(payload))
        else:
            mod_name, _, qual = entry["class"].partition(":")
            import importlib

            cls = importlib.import_module(mod_name)
            for part in qual.split("."):
                cls = getattr(cls, part)
            name_i = list(algo_params)[i] if i < len(algo_params) else None
            models.append(cls.load(f"{instance.id}.{i}", algo_params.get(name_i), ctx))
    # Post-load re-parallelization hook (reference: SURVEY §3.2 — "P
    # models may re-parallelize" in CreateServer): a model that wants a
    # serving-time device layout (e.g. a corpus too large for one chip,
    # re-sharded over ctx.mesh) reshapes itself here.
    for m in models:
        hook = getattr(m, "post_load", None)
        if callable(hook):
            try:
                hook(ctx)
            except Exception:
                logger.exception("model post_load hook failed; serving "
                                 "continues with the loaded layout")
    return models


def _bind_instance_params(engine: Engine, instance: EngineInstance) -> EngineParams:
    """Rebind the params snapshot stored on the instance row."""
    variant_like = {
        "datasource": {"params": json.loads(instance.datasource_params)},
        "preparator": {"params": json.loads(instance.preparator_params)},
        "algorithms": json.loads(instance.algorithms_params),
        "serving": {"params": json.loads(instance.serving_params)},
    }
    return engine.bind_engine_params(variant_like)


def instance_engine_params(engine: Engine, instance: EngineInstance) -> EngineParams:
    """Public alias used by the serving layer."""
    return _bind_instance_params(engine, instance)


def run_evaluation(
    evaluation: Evaluation,
    params_generator: EngineParamsGenerator,
    ctx: Optional[RuntimeContext] = None,
    *,
    evaluation_class: str = "",
    params_generator_class: str = "",
    checkpoint_dir: Optional[str] = None,
) -> Tuple[str, MetricEvaluatorResult]:
    """Sweep engine-params candidates and score them (reference:
    CoreWorkflow.runEvaluation + MetricEvaluator.evaluateBase, §3.4).

    ``checkpoint_dir`` (ISSUE 15 satellite; default
    ``PIO_EVAL_CHECKPOINT_DIR``) makes the sweep preemption-safe: each
    completed (candidate, fold) unit persists as it finishes, a SIGTERM
    mid-sweep marks the instance EVALPREEMPTED and propagates
    ``TrainPreempted`` (the CLI exits 143, same contract as training),
    and rerunning the same command resumes from the completed units —
    which are cleared once the sweep lands."""
    from predictionio_tpu.controller.engine import EvalCheckpoint

    ctx = ctx or RuntimeContext.create()
    storage: Storage = ctx.storage
    ck_dir = checkpoint_dir or os.environ.get("PIO_EVAL_CHECKPOINT_DIR")
    checkpoint = EvalCheckpoint(ck_dir) if ck_dir else None
    instance = EvaluationInstance(
        id=None,
        status="EVALRUNNING",
        start_time=_now(),
        end_time=None,
        evaluation_class=evaluation_class or _class_path(evaluation.engine),
        engine_params_generator_class=params_generator_class or _class_path(params_generator),
    )
    instances = storage.get_evaluation_instances()
    instance_id = instances.insert(instance)
    try:
        engine = evaluation.engine
        candidates = list(params_generator.engine_params_list)
        if not candidates:
            raise WorkflowError("EngineParamsGenerator produced no candidates.")
        if checkpoint is not None and checkpoint.completed():
            logger.info("eval sweep resuming: %d completed "
                        "(candidate, fold) unit(s) found in %s",
                        checkpoint.completed(), ck_dir)
        scored: List[Tuple[EngineParams, float, List[float]]] = []
        # Shared-prep sweep: folds are read + prepared once per distinct
        # datasource/preparator config, not once per candidate.
        all_eval_data = engine.eval_multi(ctx, candidates,
                                          checkpoint=checkpoint)
        for i, (engine_params, eval_data) in enumerate(
                zip(candidates, all_eval_data)):
            score = evaluation.metric.calculate(eval_data)
            others = [m.calculate(eval_data) for m in evaluation.other_metrics]
            scored.append((engine_params, score, others))
            logger.info("eval candidate %d/%d: %s=%s", i + 1, len(candidates),
                        evaluation.metric.header, score)
        best_index = max(
            range(len(scored)),
            key=lambda i: (scored[i][1],),
        )
        result = MetricEvaluatorResult(
            best_score=scored[best_index][1],
            best_engine_params=scored[best_index][0],
            best_index=best_index,
            metric_header=evaluation.metric.header,
            other_metric_headers=[m.header for m in evaluation.other_metrics],
            candidate_scores=scored,
        )
        instance.status = "EVALCOMPLETED"
        instance.end_time = _now()
        instance.evaluator_results = result.summary()
        instance.evaluator_results_json = json.dumps(
            {
                "bestScore": result.best_score,
                "bestIndex": result.best_index,
                "metric": result.metric_header,
                "bestEngineParams": result.best_engine_params.to_json_dict(),
                "candidates": [
                    {"engineParams": p.to_json_dict(), "score": s, "others": o}
                    for p, s, o in scored
                ],
            }
        )
        instances.update(instance)
        if checkpoint is not None:
            checkpoint.clear()  # landed: a rerun is a fresh sweep
        return instance_id, result
    except TrainPreempted:
        # SIGTERM mid-sweep: the completed units are on disk and the CLI
        # owns the exit code — not a failed evaluation.
        instance.status = "EVALPREEMPTED"
        instance.end_time = _now()
        instances.update(instance)
        raise
    except Exception:
        instance.status = "EVALFAILED"
        instance.end_time = _now()
        instances.update(instance)
        raise
