"""The sequence cell's own files at tiny sizes on the CPU: the
configuration against the published keys, the schedule, the builder and
the drive through a whole run (``require_chip=False``), the readers on
hand-made snapshots, the reference's control, and the manifest.  Run
with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import dataclasses
import json

import numpy as np
import pytest

from benchmark import (compare_seq, datagen_seq, manifest, reference_seq,
                       rooflines_seq)
from benchmark.drives import http_sessions_open_loop as drive
from benchmark.readers import (moe_roofline, op_ms_per_unit, prom_ratio,
                               seq_mfu)
from benchmark.tests.test_benchmark import _run, doc

__all__ = ["doc"]                         # fixture, used by name

CELL = "lfm2-24b-a2b-l9.serve-sessions"
TINY = dict(hidden_size=64, vocab_size=512, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=96,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            n_users=24,
            history={"median": 40, "sigma": 0.87, "min": 8, "max": 300},
            state={"budget_bytes": 6_000_000, "warm_users": 64})
NEW = {"seq_extend_ms", "seq_new_tokens_per_dispatch",
       "moe_experts_touched_pct", "moe_expert_load_max_over_mean",
       "seq_state_hit_pct", "state_cache_build_s", "seq_compile_s"}
DEVICE_ONLY = {"moe_experts_ms", "moe_experts_roofline", "seq_step_mfu"}


def tiny(doc):
    cell = manifest.cell(doc, CELL)
    config = dict(cell.config, **TINY)
    # Tiny widths put bfloat16 noise and turned expert picks well above
    # the full-size limits; the run's arithmetic is what is under test.
    config["limits"] = dict(config["limits"], score_abs_err_p50=1.5,
                            score_abs_err_p90=3.0, rank_gap_p90=3.0,
                            score_abs_err_max=4.0, rank_gap_max=4.0)
    mix = dict(cell.traffic, arrival={"rate_per_s": 20}, connections=4,
               check_answers=6, prefill_users_per_call=8)
    return dataclasses.replace(cell, config=config, traffic=mix)


def test_the_configuration_keeps_every_published_number():
    row = None
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl",
                  encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
    except OSError:
        pytest.skip("no catalog here")
    cfg = manifest.config(manifest.load(), "lfm2-24b-a2b-l9")
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    held = datagen_seq.held_layers(cfg)
    assert len(held) == cfg["num_hidden_layers"] == 9
    kinds = [cfg["layer_types"][i] for i in held]
    assert kinds == ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    assert [datagen_seq.is_dense(cfg, i) for i in held] \
        == [True] + [False] * 8
    # 647M parameters a token (8 x (4 experts + mixer), the dense layer,
    # the head) and 1M of routers.
    assert round(rooflines_seq.params_per_token(cfg) / 1e6) == 648


def test_schedule_is_the_same_work_for_every_seed(doc):
    cell = manifest.cell(doc, CELL)
    a = drive.schedule(cell.traffic, cell.config, 3, 10.0)
    b = drive.schedule(cell.traffic, cell.config, 2 ** 31 + 3, 10.0)
    n = int(cell.traffic["arrival"]["rate_per_s"] * 10)
    assert len(a[0]) == len(b[0]) == n
    assert sorted(a[2]) == sorted(b[2]) and not np.array_equal(a[2], b[2])
    assert a[2].min() >= 1 and a[2].max() <= 64
    assert 5 <= np.median(a[2]) <= 7
    for due, users, _ in (a, b):
        last = {}
        for t, u in zip(due, users):
            assert t - last.get(u, -9.0) >= 1.0
            last[u] = t
    lengths = datagen_seq.history_lengths(cell.config, 3)
    assert sorted(lengths) == sorted(
        datagen_seq.history_lengths(cell.config, 4))
    assert lengths.min() >= 128 and lengths.max() <= 8192
    assert np.median(lengths) == 1024
    events = datagen_seq.Events(cell.config, 3)
    np.testing.assert_array_equal(events.of(7, 50)[:20], events.of(7, 20))


def test_tiny_cell_runs_and_is_correct(doc):
    res = _run(tiny(doc), seconds=3.0)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] == 60
    assert set(res["metrics"]) == {"query_p50_ms", "query_p95_ms",
                                   "setup_s"}
    compared = res["compared"]
    assert compared["state_misses_in_window"]["value"] == 0
    assert compared["compiles_in_window"]["value"] == 0
    assert 0 < compared["score_abs_err_p50"]["value"] < 1.5
    assert {"state_cache_build_s", "seq_compile_s"} <= set(
        res["setup_split_s"])


def test_a_traced_run_prints_the_new_metrics(doc):
    res = _run(tiny(doc), seconds=3.0, trace=True)
    assert res["correct"], res["compared"]
    got = set(res["metrics"])
    assert NEW <= got and not DEVICE_ONLY & got
    listed = {m["name"] for m in manifest.cell(doc, CELL).per_layer}
    assert NEW | DEVICE_ONLY <= listed
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["seq_state_hit_pct"] == 100.0
    assert 1 <= m["seq_new_tokens_per_dispatch"] <= 64 * 64
    assert 0 < m["moe_experts_touched_pct"] <= 100
    assert m["moe_expert_load_max_over_mean"] >= 1
    assert {"serve_batch_size", "dispatch_lookup_ms.serve",
            "dispatch_assemble_ms.serve", "batcher_finish_ms",
            "compile_s"} <= got


def test_the_control_is_refused_at_tiny_size(doc):
    cell = tiny(doc)
    from benchmark.builders import seq_serving

    numbers = seq_serving.control(
        cell.config, 2 ** 31 + 5, n=4,
        # 24 users a second apart cannot take 20/s for a whole window.
        mix=dict(cell.traffic, arrival={"rate_per_s": 10}))
    # float8 weights move logits of order 1 by tenths.
    assert numbers["score_abs_err_p50"] > 0.3 and numbers["malformed"] == 0
    assert numbers["score_abs_err_max"] >= numbers["score_abs_err_p90"]


def test_leaving_the_bias_out_moves_every_answer(doc):
    """The seeded bias (width 0.05) changes which experts are picked, so
    a program that left it out would answer as the reference does with
    ``use_expert_bias`` off: off by tenths in the MEDIAN answer, where a
    sound run at this size is off by hundredths."""
    cfg = tiny(doc).config
    seed = 2 ** 31 + 9
    events = datagen_seq.Events(cfg, seed)
    seqs = [events.of(u, 40 + u) for u in range(8)]
    no_bias = reference_seq.logits_at_end(
        dict(cfg, use_expert_bias=False), seed, seqs)
    served = []
    for u, row in enumerate(no_bias):
        top = np.argsort(-row)[:10]
        served.append((u, 40 + u, 10, {"itemScores": [
            {"item": f"i{int(i)}", "score": float(row[i])} for i in top]}))
    numbers = compare_seq.numbers(cfg, seed, served)
    assert numbers["score_abs_err_p50"] > 0.2
    assert numbers["malformed"] == numbers["unordered"] == 0


def test_wrong_items_are_caught(doc):
    cfg = tiny(doc).config
    seed = 9
    events = datagen_seq.Events(cfg, seed)
    logits = reference_seq.logits_at_end(cfg, seed, [events.of(0, 30)])[0]
    top = np.argsort(-logits)[:10]

    def answer(ids, shift=0.0):
        return {"itemScores": [{"item": f"i{int(i)}",
                                "score": float(logits[i]) + shift}
                               for i in ids]}

    good = compare_seq.numbers(cfg, seed, [(0, 30, 10, answer(top))])
    assert good["score_abs_err_p90"] < 1e-5 and good["rank_gap_p90"] == 0
    assert good["malformed"] == good["unordered"] == 0
    worse = np.argsort(-logits)[5:15]
    off = compare_seq.numbers(cfg, seed, [(0, 30, 10, answer(worse))])
    assert off["rank_gap_p90"] == pytest.approx(
        float(logits[top[9]] - logits[worse[-1]]), abs=1e-5)
    # One event more of history is another answer.
    late = compare_seq.numbers(cfg, seed, [(0, 31, 10, answer(top))])
    assert late["score_abs_err_p50"] > 0.1
    bad = compare_seq.numbers(cfg, seed, [(0, 30, 10, {"itemScores": []})])
    assert bad["malformed"] == 1


def test_one_answer_at_another_event_is_caught_by_the_widest(doc):
    """One user's lost turn among eight answers moves no quantile that
    carries a limit; the widest error and rank gap show it."""
    cfg = tiny(doc).config
    seed = 12
    events = datagen_seq.Events(cfg, seed)
    counts = [30 + u for u in range(8)]
    logits = reference_seq.logits_at_end(
        cfg, seed, [events.of(u, n) for u, n in enumerate(counts)])

    def served(rows):
        return [(u, n, 10, {"itemScores": [
            {"item": f"i{int(i)}", "score": float(row[i])}
            for i in np.argsort(-row)[:10]]})
            for (u, n), row in zip(enumerate(counts), rows)]

    good = compare_seq.numbers(cfg, seed, served(logits))
    assert good["score_abs_err_max"] < 1e-5 and good["rank_gap_max"] == 0
    lost = logits.copy()      # user 3's last turn (five events) is lost
    lost[3] = reference_seq.logits_at_end(
        cfg, seed, [events.of(3, counts[3] - 5)])[0]
    bad = compare_seq.numbers(cfg, seed, served(lost))
    assert bad["score_abs_err_p50"] < 1e-5
    assert bad["score_abs_err_max"] > 0.1 and bad["rank_gap_max"] > 0.05
    assert bad["score_abs_err_max"] > bad["score_abs_err_p90"]


# -- readers on hand-made snapshots -----------------------------------------

def test_ratio_of_counters():
    hit = 'pio_seq_state_total{result="hit"}'
    miss = 'pio_seq_state_total{result="miss"}'
    ctx = {"before": {hit: 10.0}, "after": {hit: 40.0, miss: 10.0},
           "config": {"num_experts": 64}}
    terms = lambda r: [{"family": "pio_seq_state_total",  # noqa: E731
                        "match": {"result": r}}]
    assert prom_ratio.read(ctx, terms("hit"), terms("hit") + terms("miss"),
                           scale=100.0) == pytest.approx(75.0)
    assert prom_ratio.read(ctx, terms("miss"), terms("hit"),
                           scale_config="num_experts") \
        == pytest.approx(64 / 3)
    # The parent commit has no such series.
    assert prom_ratio.read({"before": {}, "after": {}, "config": {}},
                           terms("hit"), terms("miss")) is None


def test_device_readers_of_the_expert_products(doc):
    cfg = manifest.cell(doc, CELL).config
    runs = "pio_seq_dispatches_total"
    after = {runs: 100.0,
             'pio_moe_assignments_total{layer="0"}': 100 * 8 * 160.0,
             'pio_moe_experts_touched_total{layer="0"}': 100 * 8 * 58.0,
             "pio_seq_tokens_total{kind=\"new\"}": 4000.0,
             "pio_seq_attended_keys_total": 4000 * 1500.0}
    trace = {"window_s": 30.0, "busy_s": 2.0, "chips_traced": 1,
             "op_s": {"gmm": 1.4, "fusion": 0.5}, "gap_s": {}}
    class _Window:
        extras = {"seq_dispatches": 100.0}

    ctx = {"before": {}, "after": after, "trace": trace, "config": cfg,
           "device_kind": "TPU v5 lite", "window": _Window}
    assert op_ms_per_unit.read(ctx, "^gmm", "seq_dispatches") \
        == pytest.approx(14.0)
    flops, nbytes = rooflines_seq.moe_counts(cfg, 100 * 8 * 160, 100 * 8 * 58)
    assert flops == 2 * 3 * 2048 * 1536 * 128000
    share = moe_roofline.read(ctx, "gmm")
    # Memory-bound: the touched experts' weights at 819 GB/s.
    assert share == pytest.approx(100 * (nbytes / 819e9) / 1.4)
    assert 60 < share < 100
    mfu = seq_mfu.read(ctx)
    assert mfu == pytest.approx(100 * rooflines_seq.step_flops(
        cfg, 4000, 6e6) / (30 * 197e12))
    # No kernel time (the CPU, the parent commit): nothing, no error.
    none = {**ctx, "trace": {**trace, "op_s": {}, "chips_traced": 0}}
    assert op_ms_per_unit.read(none, "^gmm", "seq_dispatches") is None
    assert moe_roofline.read(none, "gmm") is None
    assert seq_mfu.read(none) is None
    assert seq_mfu.read({**ctx, "after": {}}) is None


def test_manifest_holds_the_new_cell_and_its_metrics(doc):
    cell = manifest.cell(doc, CELL)
    assert cell.chips == 1 and cell.config["builder"] == "seq_serving"
    assert [m["name"] for m in cell.end_to_end] == [
        "query_p50_ms", "query_p95_ms", "setup_s"]
    for m in cell.per_layer:
        spec = manifest.layer_metric_spec(m["name"])
        assert (manifest.ROOT / "readers" / f"{spec['reader']}.py").exists()
    # Appended: what the benchmark had comes first, untouched but for the
    # serving metrics' lists, which gained the cell at their end.
    assert [w["name"] for w in doc["workloads"]][-1] == CELL
    for m in doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL
    assert "workloads" not in next(m for m in doc["per_layer"]
                                   if m["name"] == "compile_s")
