"""An ``EngineServer`` of the recommendation template over seeded
factors.  The model enters through the program's own load path
(``load_models`` -> a persistent model's ``load``), so reload,
validation, the retriever cache, the result cache and the scheduler are
the deployed ones; only the 5.8 GB pickle is skipped."""

from __future__ import annotations

import datetime as _dt
import json
import pickle
import threading
import time
from typing import Any, Dict, List

import jax

from benchmark import datagen

# model id -> spec of the seeded model the program's loader asks for
_SPECS: Dict[str, Dict[str, Any]] = {}


class SeededALSModel:
    """The persistent-model hook ``load_models`` calls: returns the
    template's own ``ALSModelWrapper`` over factors made on the device
    from the seed."""

    @classmethod
    def load(cls, model_id: str, params, ctx):
        from predictionio_tpu.data.event import BiMap
        from predictionio_tpu.models.als import ALSModel
        from predictionio_tpu.templates.recommendation.engine import (
            ALSModelWrapper,
        )

        spec = _SPECS[model_id.rsplit(".", 1)[0]]
        cfg, seed, split = spec["config"], spec["seed"], spec["split"]
        maps: Dict[str, Any] = {}

        def build_maps():
            # String ids as a deployment has them; pure host work, so it
            # overlaps the device calls below.
            for kind, letter in (("item", "i"), ("user", "u")):
                n = cfg[f"n_{kind}s"]
                maps[kind] = BiMap(dict(zip(
                    [f"{letter}{j}" for j in range(n)], range(n))))

        t0 = time.perf_counter()
        th = threading.Thread(target=build_maps)
        th.start()
        rank, rows = cfg["rank"], cfg["factor_block_rows"]
        items = datagen.make_factors(
            datagen.seed_key(seed, 1), n_rows=cfg["n_items"], dim=rank,
            block_rows=rows)
        users = datagen.make_factors(
            datagen.seed_key(seed, 2), n_rows=cfg["n_users"], dim=rank,
            block_rows=rows)
        jax.block_until_ready((items, users))
        split["weights_s"] = time.perf_counter() - t0
        th.join()
        split["id_maps_s"] = time.perf_counter() - t0 - split["weights_s"]
        return ALSModelWrapper(
            model=ALSModel(user_factors=users, item_factors=items,
                           rank=rank, implicit=False),
            user_index=maps["user"], item_index=maps["item"])


class ServingSystem:
    """A deployed engine: ``query_batch`` (what ``pio batchpredict``
    calls) and ``POST /queries.json`` on ``port``."""

    def __init__(self, config: Dict[str, Any], seed: int,
                 split: Dict[str, float]):
        from predictionio_tpu.controller import EngineVariant
        from predictionio_tpu.data.storage import (
            EngineInstance, Model, get_storage,
        )
        from predictionio_tpu.server import EngineServer
        from predictionio_tpu.templates.recommendation import engine

        self.config = config
        self.population = int(config["n_users"])
        storage = get_storage()
        variant = EngineVariant.from_dict({
            "engineFactory":
                "predictionio_tpu.templates.recommendation:engine",
            "datasource": {"params": {"appName": "benchmark"}},
            "algorithms": [{"name": "als",
                            "params": {"rank": config["rank"]}}],
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
                         "class": f"{__name__}:SeededALSModel"}],
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
        """Stop the server and drop every device array it held."""
        self.server.stop()
        self.server = None


def build(config: Dict[str, Any], seed: int, split: Dict[str, float]):
    return ServingSystem(config, seed, split)


def control(config: Dict[str, Any], seed: int, n: int = 256, num: int = 10,
            precision: str = "high", operand_dtype=None
            ) -> Dict[str, float]:
    """The reference in the program's place, its scores computed at
    ``high`` (three bfloat16 passes) where the configuration states
    ``highest``, shaped as served answers and compared as a run's are."""
    from benchmark import compare, traffic

    users = traffic.rng_for(seed, 4).choice(config["n_users"], n, False)
    answers = compare.control_answers(config, seed, users, num, precision,
                                      operand_dtype)
    return compare.serving_numbers(config, seed, answers)
