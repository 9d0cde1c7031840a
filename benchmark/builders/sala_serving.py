"""An ``EngineServer`` of the sequence template on the block-selected /
lightning backbone, over seeded weights at the configuration's published
widths.  The model enters through the program's own load path
(``load_models`` -> a persistent model's ``load``), so reload, the
dispatch, the runtime and the state cache are the deployed ones; only a
6 GB pickle is skipped.  No per-user state is built here: the drive's
``warm`` sends every user's history through the engine, as a first visit
would."""

from __future__ import annotations

import datetime as _dt
import json
import pickle
import sys
import time
from typing import Any, Dict, List

import jax

from benchmark import datagen_sala

# model id -> spec of the seeded model the program's loader asks for
_SPECS: Dict[str, Dict[str, Any]] = {}


class SeededSalaModel:
    """The persistent-model hook ``load_models`` calls: the template's own
    ``SequenceModel`` on the ``sala`` backbone over weights made on the
    device from the seed, a held layer at a time."""

    @classmethod
    def load(cls, model_id: str, params, ctx):
        from predictionio_tpu.data.event import BiMap
        from predictionio_tpu.models.sala import SALAConfig
        from predictionio_tpu.templates.sequence import SequenceModel

        spec = _SPECS[model_id.rsplit(".", 1)[0]]
        cfg, seed, split = spec["config"], spec["seed"], spec["split"]
        t0 = time.perf_counter()
        held = datagen_sala.held_layers(cfg)
        weights = {
            "embed": datagen_sala.vocab_matrix(cfg, seed, "embed"),
            "head": datagen_sala.vocab_matrix(cfg, seed, "head"),
            "final_norm": datagen_sala.final_norm(cfg, seed),
            "layers": [datagen_sala.layer_weights(cfg, seed, layer)
                       for layer in held]}
        jax.block_until_ready(weights)
        split["weights_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        items = BiMap({f"i{j}": j for j in range(int(cfg["vocab_size"]))})
        split["id_maps_s"] = time.perf_counter() - t0
        state = cfg["state"]
        return SequenceModel(
            config=SALAConfig.from_published(cfg, held,
                                             **cfg["sparse_config"]),
            params=weights, item_index=items, app_name="benchmark",
            event_names=("view",), backbone="sala",
            state_budget_bytes=int(state["budget_bytes"]),
            max_users=int(cfg["n_users"]))


class SalaServingSystem:
    """A deployed sequence engine on the ``sala`` backbone:
    ``query_batch`` (and ``POST /queries.json`` on ``port``)."""

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
            "algorithms": [{"name": "sequence",
                            "params": {"backbone": "sala"}}],
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
                         "class": f"{__name__}:SeededSalaModel"}],
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
        the state cache's slots, pages and tables, the programs."""
        models = list(self.server._models)
        self.server.stop()
        self.server = None
        for m in models:
            m.state_cache.free()
            m.params = None
            m._runtime = None


def build(config: Dict[str, Any], seed: int, split: Dict[str, float]):
    return SalaServingSystem(config, seed, split)


def controls() -> Dict[str, Dict[str, Any]]:
    """Name -> what the reference is told to leave out or round."""
    import jax.numpy as jnp

    return {"float8_weights": {"weight_dtype": jnp.float8_e4m3fn},
            "forced_blocks_only": {"forced_only": True},
            "no_decay": {"no_decay": True}}


def control_samples(config: Dict[str, Any], seed: int, n_users: int,
                    n_answers: int) -> List[tuple]:
    """(user, events, num) of answers as a window would sample them:
    ``n_users`` residents drawn by the seed, each after its seeded history
    and a few turns of the mix's sizes."""
    from benchmark import datagen_seq
    from benchmark.drives import sample

    lengths = datagen_seq.history_lengths(config, seed)
    out = []
    for u in sample(seed, len(lengths), n_users):
        count = int(lengths[u])
        for step in range(n_answers // n_users):
            count += 1 + (int(u) + 3 * step) % 5
            out.append((int(u), count, 10))
    return out


def control(config: Dict[str, Any], seed: int, n_users: int = 0,
            n_answers: int = 0) -> Dict[str, float]:
    """Each negative control in the program's place (the reference with
    float8_e4m3 weights; with the forced blocks alone; with no decay),
    compared as a run's answers are.  Every control's numbers are
    printed; what is handed back is those of the control that came
    CLOSEST to passing (the smallest widest value-over-limit), so that
    ``control.py``'s "refused" means: each of them was."""
    from benchmark import compare, compare_sala

    samples = control_samples(
        config, seed, n_users or int(config["control_users"]),
        n_answers or int(config["control_answers"]))
    pairs = [(u, c) for u, c, _ in samples]
    truth = compare_sala.reference_logits(config, seed, pairs)
    closest = None
    for name, variant in controls().items():
        logits = compare_sala.reference_logits(config, seed, pairs,
                                               **variant)
        got = compare_sala.numbers(
            config, seed, compare_sala.as_answers(samples, logits),
            logits=truth)
        ok, _ = compare.verdict(got, {k: config["limits"][k] for k in got})
        over = max((v / config["limits"][k] if config["limits"][k]
                    else float(v > 0) * 1e9) for k, v in got.items())
        print(f"control {name} seed {seed}: refused {not ok}, widest "
              f"value/limit {over:.3f}: {got}", file=sys.stderr, flush=True)
        if closest is None or over < closest[0]:
            closest = (over, got)
    return closest[1]
