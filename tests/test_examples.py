"""Every shipped example engine.json binds against its engine factory."""

import json
from pathlib import Path

import pytest

from predictionio_tpu.controller import EngineVariant, load_engine_factory

EXAMPLES = sorted(
    (Path(__file__).resolve().parents[1] / "examples").glob("*/engine.json"))


@pytest.mark.parametrize("path", EXAMPLES, ids=lambda p: p.parent.name)
def test_example_binds(path):
    variant = EngineVariant.from_file(path)
    engine = load_engine_factory(variant.engine_factory)()
    params = engine.bind_engine_params(variant.raw)
    assert params.algorithms_params
    assert engine.query_class is not None


def test_examples_cover_all_templates():
    names = {p.parent.name for p in EXAMPLES}
    assert names == {"recommendation", "classification", "similarproduct",
                     "ecommerce", "twotower", "dlrm", "sequence"}
