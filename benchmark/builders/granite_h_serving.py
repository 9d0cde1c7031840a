"""An ``EngineServer`` of the sequence template on the Mamba-2 /
no-position attention backbone, over seeded weights at the configuration's
published widths and depth.  The model enters through the program's own
load path (``load_models`` -> a persistent model's ``load``), so reload,
the dispatch, the runtime and the state cache are the deployed ones; only
a 6.4 GB pickle is skipped.  No per-user state is built here: the drive's
``warm`` sends every user's history through the engine, as a first visit
would."""

from __future__ import annotations

import datetime as _dt
import json
import pickle
import sys
import time
from typing import Any, Dict

import jax

from benchmark import datagen_granite_h
from benchmark.builders import sala_serving

BACKBONE = "granite_h"
# model id -> spec of the seeded model the program's loader asks for
_SPECS: Dict[str, Dict[str, Any]] = {}


class SeededGraniteHModel:
    """The persistent-model hook ``load_models`` calls: the template's own
    ``SequenceModel`` on the ``granite_h`` backbone over weights made on
    the device from the seed, a layer at a time."""

    @classmethod
    def load(cls, model_id: str, params, ctx):
        from predictionio_tpu.data.event import BiMap
        from predictionio_tpu.models.granite_h import GraniteHConfig
        from predictionio_tpu.templates.sequence import SequenceModel

        spec = _SPECS[model_id.rsplit(".", 1)[0]]
        cfg, seed, split = spec["config"], spec["seed"], spec["split"]
        t0 = time.perf_counter()
        weights = {
            "embed": datagen_granite_h.embedding(cfg, seed),
            "final_norm": datagen_granite_h.final_norm(cfg, seed),
            "layers": [datagen_granite_h.layer_weights(cfg, seed, layer)
                       for layer in range(int(cfg["num_hidden_layers"]))]}
        jax.block_until_ready(weights)
        split["weights_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        items = BiMap({f"i{j}": j for j in range(int(cfg["vocab_size"]))})
        split["id_maps_s"] = time.perf_counter() - t0
        return SequenceModel(
            config=GraniteHConfig.from_published(cfg),
            params=weights, item_index=items, app_name="benchmark",
            event_names=("view",), backbone=BACKBONE,
            state_budget_bytes=int(cfg["state"]["budget_bytes"]),
            max_users=int(cfg["n_users"]))


class GraniteHServingSystem(sala_serving.SalaServingSystem):
    """A deployed sequence engine on the ``granite_h`` backbone:
    ``query_batch`` (and ``POST /queries.json`` on ``port``); stopped and
    freed as its parent is."""

    def __init__(self, config: Dict[str, Any], seed: int,
                 split: Dict[str, float]):
        from predictionio_tpu.controller import EngineVariant
        from predictionio_tpu.data.storage import (
            EngineInstance, Model, get_storage,
        )
        from predictionio_tpu.server import EngineServer
        from predictionio_tpu.templates.sequence import engine
        from predictionio_tpu.templates.sequence.engine import _backbone

        # A program without the backbone says so here, before anything
        # is built.
        _backbone(BACKBONE)
        self.config, self.seed, self.split = config, seed, split
        self.population = int(config["n_users"])
        storage = get_storage()
        variant = EngineVariant.from_dict({
            "engineFactory": "predictionio_tpu.templates.sequence:engine",
            "datasource": {"params": {"appName": "benchmark"}},
            "algorithms": [{"name": "sequence",
                            "params": {"backbone": BACKBONE}}],
        })
        now = _dt.datetime.now(_dt.timezone.utc)
        iid = storage.get_engine_instances().insert(EngineInstance(
            id=None, status="COMPLETED", start_time=now, end_time=now,
            engine_id=variant.engine_factory, engine_version="benchmark",
            engine_variant=variant.variant_id,
            engine_factory=variant.engine_factory,
            datasource_params=json.dumps({"appName": "benchmark"}),
            algorithms_params=json.dumps(variant.raw["algorithms"])))
        _SPECS[iid] = {"config": config, "seed": seed, "split": split}
        storage.get_models().insert(Model(id=iid, models=pickle.dumps({
            "entries": [{"kind": "persistent",
                         "class": f"{__name__}:SeededGraniteHModel"}],
            "payloads": [None]})))
        t0 = time.perf_counter()
        self.server = EngineServer(
            engine(), variant, storage, host="127.0.0.1", port=0,
            engine_version="benchmark", instance_id=iid)
        split["load_s"] = (time.perf_counter() - t0
                           - split["weights_s"] - split["id_maps_s"])
        del _SPECS[iid]
        self.server.start()
        self.port = self.server.port


def build(config: Dict[str, Any], seed: int, split: Dict[str, float]):
    return GraniteHServingSystem(config, seed, split)


def controls(config: Dict[str, Any], seed: int, samples) -> Dict[str, Any]:
    """Name -> what the reference is told to leave out or round."""
    import jax.numpy as jnp

    from benchmark import datagen_seq

    lengths = datagen_seq.history_lengths(config, seed)
    users = sorted({u for u, _, _ in samples})
    # A turn starts where the history, or the turn before it, ended.
    starts = [[int(lengths[u])] + [c for v, c, _ in samples if v == u][:-1]
              for u in users]
    return {"float8_weights": {"weight_dtype": jnp.float8_e4m3fn},
            "state_zeroed_each_turn": {"turn_starts": starts},
            "attention_scaled_by_rsqrt_head": {
                "attention_multiplier":
                    (int(config["hidden_size"])
                     // int(config["num_attention_heads"])) ** -0.5},
            "residual_multiplier_1": {"residual_multiplier": 1.0}}


def control(config: Dict[str, Any], seed: int, n_users: int = 0,
            n_answers: int = 0) -> Dict[str, float]:
    """Each negative control in the program's place (the reference with
    float8_e4m3 weights; with the Mamba-2 state zeroed at every turn's
    start; with the attention scaled by ``1 / sqrt(head size)`` = 1 / 8
    where the config says 0.015625; with ``residual_multiplier`` 1),
    compared as a run's answers are.  Every control's numbers are
    printed; what is handed back is those of the control that came
    CLOSEST to passing (the smallest widest value-over-limit), so that
    ``control.py``'s "refused" means: each of them was."""
    from benchmark import compare, compare_granite_h

    samples = sala_serving.control_samples(
        config, seed, n_users or int(config["control_users"]),
        n_answers or int(config["control_answers"]))
    pairs = [(u, c) for u, c, _ in samples]
    truth = compare_granite_h.reference_logits(config, seed, pairs)
    closest = None
    for name, variant in controls(config, seed, samples).items():
        logits = compare_granite_h.reference_logits(config, seed, pairs,
                                                    **variant)
        got = compare_granite_h.numbers(
            config, seed, compare_granite_h.as_answers(samples, logits),
            logits=truth)
        ok, _ = compare.verdict(got, {k: config["limits"][k] for k in got})
        over = max((v / config["limits"][k] if config["limits"][k]
                    else float(v > 0) * 1e9) for k, v in got.items())
        print(f"control {name} seed {seed}: refused {not ok}, widest "
              f"value/limit {over:.3f}: {got}", file=sys.stderr, flush=True)
        if closest is None or over < closest[0]:
            closest = (over, got)
    return closest[1]
