"""The per-layer metrics that read the program's host spans (PR 25):
the three readers on hand-made snapshots, the manifest with the new
entries, and tiny traced runs on the CPU that print them.  Run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import pytest

from benchmark import manifest
from benchmark.readers import prom_mean_diff, prom_share, prom_total
from benchmark.tests.test_benchmark import (BULK, SERVE, TRAIN, _run,
                                            device_rung, doc, tiny_cell)

__all__ = ["device_rung", "doc"]          # fixtures, used by name

NEW = {
    SERVE: {"batcher_wait_empty_pct", "batcher_finish_ms",
            "dispatch_lookup_ms.serve", "dispatch_assemble_ms.serve",
            "retrieval_host_ms.serve"},
    BULK: {"dispatch_lookup_ms.bulk", "dispatch_assemble_ms.bulk",
           "retrieval_host_ms.bulk", "bulk_bind_ms"},
    TRAIN: {"train_prep_upload_s", "train_prep_plan_s",
            "train_prep_lower_s", "train_prep_compile_wait_s"},
}


def _thread(phase, total, count):
    key = f'pio_batcher_thread_ms_%s{{model="default",phase="{phase}"}}'
    return {key % "sum": total, key % "count": count}


def test_share_of_the_window_of_a_tiled_family():
    before = {**_thread("wait_empty", 1000.0, 2),
              **_thread("dispatch", 500.0, 2)}
    after = {**_thread("wait_empty", 4000.0, 50),
             **_thread("dispatch", 25500.0, 700),
             **_thread("finish", 3500.0, 700)}
    # The phases grew by 31,500 ms between the snapshots; the traced
    # window inside them is 30,000 ms long.
    ctx = {"before": before, "after": after, "trace": {"window_s": 30.0}}
    family, part = "pio_batcher_thread_ms", {"phase": "wait_empty"}
    assert prom_share.read(ctx, family, part) == pytest.approx(10.0)
    # The 1,500 ms outside the window were idle moments of the harness.
    assert prom_share.read(ctx, family, part, outside_window_is_part=True
                           ) == pytest.approx(5.0)
    assert prom_share.read(ctx, family, {"phase": "finish"},
                           outside_window_is_part=True
                           ) == pytest.approx(100 * 2000.0 / 30000.0)
    # The parent commit has no such family, an untraced run no window:
    # nothing, and no error.
    assert prom_share.read({"before": {}, "after": {},
                            "trace": {"window_s": 30.0}},
                           family, part) is None
    assert prom_share.read({**ctx, "trace": None}, family, part) is None


def test_parent_minus_child_per_parent_call():
    wait = 'pio_dispatch_stage_ms_%s{stage="wait"}'
    other = 'pio_dispatch_stage_ms_%s{stage="h2d"}'
    parent = 'pio_retrieval_ms_%s{rung="chunked"}'
    before = {parent % "sum": 100.0, parent % "count": 2,
              wait % "sum": 90.0, wait % "count": 2}
    after = {parent % "sum": 4100.0, parent % "count": 102,
             wait % "sum": 3790.0, wait % "count": 102,
             other % "sum": 55.0, other % "count": 100}
    args = {"parent": {"family": "pio_retrieval_ms"},
            "child": {"family": "pio_dispatch_stage_ms",
                      "match": {"stage": "wait"}}}
    # (4,000 - 3,700) ms over 100 calls.
    assert prom_mean_diff.read({"before": before, "after": after},
                               **args) == pytest.approx(3.0)
    # No child series (the parent commit), or no call in the window.
    no_child = {k: v for k, v in after.items() if "retrieval" in k}
    assert prom_mean_diff.read({"before": {}, "after": no_child},
                               **args) is None
    assert prom_mean_diff.read({"before": after, "after": after},
                               **args) is None


def test_total_as_it_stands_after_the_window():
    series = 'pio_train_phase_ms_%s{phase="%s"}'
    after = {series % ("sum", "prep.lower_build"): 4000.0,
             series % ("count", "prep.lower_build"): 1,
             series % ("sum", "prep.lower_loop"): 29000.0,
             series % ("count", "prep.lower_loop"): 1,
             series % ("sum", "train.dispatch"): 31000.0,
             series % ("count", "train.dispatch"): 19}
    # The window's own growth is not the question: prep ran in set-up.
    ctx = {"before": dict(after), "after": after}
    terms = [{"family": "pio_train_phase_ms", "match": {"phase": p}}
             for p in ("prep.lower_build", "prep.lower_loop")]
    assert prom_total.read(ctx, terms, scale=0.001) == pytest.approx(33.0)
    assert prom_total.read(ctx, terms[:1], scale=0.001) \
        == pytest.approx(4.0)
    missing = [{"family": "pio_train_phase_ms",
                "match": {"phase": "prep.compile_wait"}}]
    assert prom_total.read(ctx, missing) is None


def test_manifest_holds_the_new_metrics_where_they_apply(doc):
    for name, new in NEW.items():
        cell = manifest.cell(doc, name)
        listed = {m["name"]: m for m in cell.per_layer}
        assert new <= set(listed)
        for metric in new:
            assert listed[metric]["source"] == "program_span"
            assert listed[metric]["workloads"] == [name]
            spec = manifest.layer_metric_spec(metric)
            assert (manifest.ROOT / "readers"
                    / f"{spec['reader']}.py").exists()
    # Appended: the 17 metrics the benchmark had come first, untouched.
    assert [m["name"] for m in doc["per_layer"]][16] \
        == "device_idle_pct.train"
    assert len(doc["per_layer"]) == 17 + sum(map(len, NEW.values()))


@pytest.mark.parametrize("name, cpu_cannot", [
    (SERVE, set()), (BULK, set()),
    # No Pallas on the CPU, so prep takes the host path: no lowering of
    # its own, no build program to wait for.
    (TRAIN, {"train_prep_lower_s", "train_prep_compile_wait_s"}),
])
def test_a_traced_run_prints_the_new_metrics(doc, device_rung, name,
                                             cpu_cannot):
    res = _run(tiny_cell(doc, name), trace=True)
    assert NEW[name] - cpu_cannot <= set(res["metrics"])
    assert not cpu_cannot & set(res["metrics"])
    for metric in NEW[name] - cpu_cannot:
        assert res["metrics"][metric]["value"] >= 0
    if name == SERVE:
        assert 0 < res["metrics"]["batcher_wait_empty_pct"]["value"] < 100
        assert 0 < res["metrics"]["batcher_finish_ms"]["value"] < 50
