"""An ``EngineServer`` of the sequence template over seeded weights at
the configuration's published widths.  The model enters through the
program's own load path (``load_models`` -> a persistent model's
``load``), so reload, validation, the scheduler and the state cache are
the deployed ones; only a 10 GB pickle is skipped.  No per-user state is
built here: the drive's ``warm`` sends every user's history through the
engine, as a first visit would."""

from __future__ import annotations

import datetime as _dt
import json
import pickle
import time
from pathlib import Path
from typing import Any, Dict, List

import jax

from benchmark import datagen_seq

# model id -> spec of the seeded model the program's loader asks for
_SPECS: Dict[str, Dict[str, Any]] = {}


class SeededSequenceModel:
    """The persistent-model hook ``load_models`` calls: returns the
    template's own ``SequenceModel`` over weights made on the device from
    the seed, a held layer at a time."""

    @classmethod
    def load(cls, model_id: str, params, ctx):
        from predictionio_tpu.data.event import BiMap
        from predictionio_tpu.models.lfm2 import LFM2Config
        from predictionio_tpu.templates.sequence import SequenceModel

        spec = _SPECS[model_id.rsplit(".", 1)[0]]
        cfg, seed, split = spec["config"], spec["seed"], spec["split"]
        t0 = time.perf_counter()
        held = datagen_seq.held_layers(cfg)
        weights = {
            "embed": datagen_seq.embedding(cfg, seed),
            "final_norm": datagen_seq.final_norm(cfg, seed),
            "layers": [datagen_seq.layer_weights(cfg, seed, layer)
                       for layer in held]}
        jax.block_until_ready(weights)
        split["weights_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        items = BiMap({f"i{j}": j for j in range(int(cfg["vocab_size"]))})
        split["id_maps_s"] = time.perf_counter() - t0
        state = cfg["state"]
        return SequenceModel(
            config=LFM2Config.from_published(cfg, held), params=weights,
            item_index=items, app_name="benchmark", event_names=("view",),
            state_budget_bytes=int(state["budget_bytes"]),
            max_users=int(cfg["n_users"]) + int(state["warm_users"]))


class SequenceServingSystem:
    """A deployed sequence engine: ``query_batch`` and ``POST
    /queries.json`` on ``port``."""

    def __init__(self, config: Dict[str, Any], seed: int,
                 split: Dict[str, float]):
        from predictionio_tpu.controller import EngineVariant
        from predictionio_tpu.data.storage import (
            EngineInstance, Model, get_storage,
        )
        from predictionio_tpu.server import EngineServer
        from predictionio_tpu.templates.sequence import engine

        self.config, self.seed, self.split = config, seed, split
        self.population = int(config["n_users"])
        storage = get_storage()
        variant = EngineVariant.from_dict({
            "engineFactory": "predictionio_tpu.templates.sequence:engine",
            "datasource": {"params": {"appName": "benchmark"}},
            "algorithms": [{"name": "sequence", "params": {}}],
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
                         "class": f"{__name__}:SeededSequenceModel"}],
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

    def query_batch(self, queries: List[Dict[str, Any]]) -> List[Any]:
        return self.server.query_batch(queries)

    def free(self) -> None:
        """Stop the server and drop every device array it held: weights,
        the state cache's slots and pages, the programs."""
        models = list(self.server._models)
        self.server.stop()
        self.server = None
        for m in models:
            m.state_cache.free()
            m.params = None
            m._runtime = None


def build(config: Dict[str, Any], seed: int, split: Dict[str, float]):
    return SequenceServingSystem(config, seed, split)


def control(config: Dict[str, Any], seed: int, n: int = 64, num: int = 10,
            mix=None) -> Dict[str, float]:
    """The reference in the program's place with its weights rounded to
    float8_e4m3 where the configuration states bfloat16, compared as a
    run's answers are, on what a run checks: ``n`` answers of a window of
    the configuration's ``control_traffic``, each after its user's
    seeded history and the turns before (``mix``: tests' smaller one)."""
    import jax.numpy as jnp

    from benchmark import compare_seq, manifest
    from benchmark.drives import http_sessions_open_loop as drive
    from benchmark.drives import sample

    if mix is None:
        with open(Path(__file__).resolve().parents[1] / "traffic"
                  / f"{config['control_traffic']}.json",
                  encoding="utf-8") as f:
            mix = json.load(f)
    _, users, sizes = drive.schedule(
        mix, config, seed, float(manifest.load()["run_seconds"]))
    after = drive.events_after(config, seed, users, sizes)
    samples = [(int(users[i]), int(after[i]), num)
               for i in sample(seed, len(users), n)]
    answers = compare_seq.control_answers(config, seed, samples,
                                          jnp.float8_e4m3fn)
    return compare_seq.numbers(config, seed, answers)
