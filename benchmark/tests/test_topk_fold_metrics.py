"""The two per-layer metrics that read the fused top-k kernel's round
counter (PR 26): additions only, an existing reader, a series the
program really observes, and nothing where it does not.  Run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import pytest

from benchmark import manifest, prom
from benchmark.readers import prom_mean
from benchmark.tests.test_benchmark import (BULK, SERVE, _run, device_rung,
                                            doc, tiny_cell)

__all__ = ["device_rung", "doc"]          # fixtures, used by name

SERIES = "pio_topk_fold_rounds_per_tile"
NEW = {"topk_fold_rounds_per_tile.serve": (SERVE, "query_p50_ms"),
       "topk_fold_rounds_per_tile.bulk": (BULK, "queries_per_s")}


@pytest.fixture
def interpreted_kernel(monkeypatch):
    """The chunked rung's Pallas arm on the CPU: the kernel interpreted."""
    from predictionio_tpu.ops import pallas_kernels as pk
    from predictionio_tpu.retrieval import exact

    monkeypatch.setattr(exact, "pallas_supported", lambda: True)
    monkeypatch.setattr(
        exact, "fused_topk_pallas",
        lambda *a, **kw: pk.fused_topk_pallas(*a, **kw, interpret=True))


def test_the_two_metrics_are_appended_and_name_what_exists(doc):
    assert [m["name"] for m in doc["per_layer"]][-2:] == list(NEW)
    for name, (cell, moves) in NEW.items():
        (entry,) = [m for m in manifest.cell(doc, cell).per_layer
                    if m["name"] == name]
        assert entry["workloads"] == [cell]
        assert (entry["moves"], entry["better"]) == (moves, "lower")
        assert (entry["layer"], entry["source"]) == ("serving kernel",
                                                     "program_counter")
        spec = manifest.layer_metric_spec(name)
        # The reader PR 24 wrote; one family, every label.
        assert spec["reader"] == "prom_mean"
        assert spec["args"] == {"terms": [{"family": SERIES}]}


def test_the_reader_reads_the_series_the_program_observes(
        interpreted_kernel):
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.retrieval import exact

    rng = np.random.default_rng(3)
    items = jnp.asarray(rng.normal(size=(3000, 16)).astype(np.float32))
    q = rng.normal(size=(4, 16)).astype(np.float32)
    cache = {}
    before = prom.snapshot()
    for _ in range(3):
        exact.exact_chunked(q, items, 3000, 10, jit_cache=cache)
    ctx = {"before": before, "after": prom.snapshot()}
    assert prom.delta(before, ctx["after"], SERIES + "_count") == 3
    value = prom_mean.read(ctx, **manifest.layer_metric_spec(
        "topk_fold_rounds_per_tile.bulk")["args"])
    assert 10 / 3 <= value <= 10      # three tiles, k = 10 in the first
    # A program without the series (the parent commit): nothing.
    assert prom_mean.read({"before": {}, "after": {}},
                          terms=[{"family": SERIES}]) is None


def test_a_traced_run_prints_it_where_the_kernel_ran(doc, device_rung,
                                                     interpreted_kernel):
    res = _run(tiny_cell(doc, BULK), trace=True)
    assert res["correct"], res["compared"]
    # 4,096 items are four tiles; the first runs k = 10 rounds.
    assert 2.5 <= res["metrics"]["topk_fold_rounds_per_tile.bulk"][
        "value"] <= 10


def test_a_traced_run_without_the_kernel_leaves_it_out(doc, device_rung):
    # The XLA scan answers on the CPU, as the parent's kernel answers
    # without the series: the line has no such metric, and no error.
    res = _run(tiny_cell(doc, BULK), trace=True)
    assert res["correct"], res["compared"]
    assert "topk_fold_rounds_per_tile.bulk" not in res["metrics"]
    assert "retrieval_ms.bulk" in res["metrics"]
