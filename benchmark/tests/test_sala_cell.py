"""The block-selected / lightning cell's own files at tiny sizes on the
CPU: the configuration against the published keys, the schedule, the
builder and the drive through a whole run (``require_chip=False``), the
three negative controls, the readers on hand-made snapshots, and the
manifest.  Run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import dataclasses
import json

import numpy as np
import pytest

from benchmark import (compare_sala, datagen_sala, datagen_seq, manifest,
                       reference_sala, rooflines_sala)
from benchmark.builders import sala_serving
from benchmark.drives import seq_bulk_closed_loop as drive
from benchmark.readers import op_ms_per_unit, sala_mfu, sala_roofline
from benchmark.tests.test_benchmark import _run, doc

__all__ = ["doc"]                         # fixture, used by name

CELL = "minicpm-sala-l8.bulk-turns"
TINY = dict(hidden_size=64, vocab_size=512, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, lightning_nh=4,
            lightning_head_dim=16, intermediate_size=96, n_users=8,
            history={"median": 60, "sigma": 0.6, "min": 30, "max": 140},
            sparse_config={"kernel_size": 4, "kernel_stride": 2,
                           "block_size": 8, "topk": 4, "init_blocks": 1,
                           "window_size": 16, "dense_len": 24},
            state={"budget_bytes": 40_000_000},
            control_users=2, control_answers=4)
NEW = {"sparse_selected_path_pct", "sparse_keys_per_query",
       "seq_extend_ms.bulk", "seq_new_tokens_per_dispatch.bulk",
       "state_cache_build_s", "seq_compile_s"}
DEVICE_ONLY = {"sala_step_mfu", "sparse_attn_ms", "sparse_attn_roofline",
               "lightning_ms", "lightning_roofline", "device_idle_pct.bulk"}
# Tiny widths put bfloat16 noise well above the full-size limits (logits
# here are of order 1); the controls move answers by more still.
LIMITS = dict(score_abs_err_p50=0.1, score_abs_err_p90=0.2,
              score_abs_err_max=0.4, rank_gap_p90=0.2, rank_gap_max=0.4)


def tiny(doc):
    cell = manifest.cell(doc, CELL)
    config = dict(cell.config, **TINY)
    config["limits"] = dict(config["limits"], **LIMITS)
    mix = dict(cell.traffic, chunk=8, max_calls=400, check_users=2,
               check_answers=6, prefill_users_per_call=4)
    return dataclasses.replace(cell, config=config, traffic=mix)


def test_the_configuration_keeps_every_published_number():
    row = None
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl",
                  encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiniCPM-SALA")
    except OSError:
        pytest.skip("no catalog here")
    cfg = manifest.config(manifest.load(), "minicpm-sala-l8")
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 32
    held = datagen_sala.held_layers(cfg)
    assert held == list(range(9, 17)) and len(held) == 8
    kinds = [cfg["mixer_types"][i] for i in held]
    assert kinds == ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    # 2 x 253.8M + 6 x 285.2M of layers and the 300.8M head a token.
    assert round(rooflines_sala.params_per_token(cfg) / 1e6, 1) == 2519.6
    assert cfg["sparse_config"]["topk"] == 64 and cfg["assumed"]["qk_gain"]
    assert set(cfg["limits"]) >= set(compare_sala.EMPTY)


def test_a_pass_is_the_same_work_for_every_seed(doc):
    cell = manifest.cell(doc, CELL)
    mix = dict(cell.traffic, max_calls=40)
    ua, sa = drive.schedule(mix, cell.config, 3)
    ub, sb = drive.schedule(mix, cell.config, 2 ** 31 + 3)
    assert ua.shape == sa.shape == (40, 128)
    for users, sizes in ((ua, sa), (ub, sb)):
        for c in range(40):                # a call: every resident once
            assert sorted(users[c]) == list(range(128))
            assert sorted(sizes[c]) == sorted(sa[0])
    assert not np.array_equal(ua, ub)
    assert sa.min() >= 1 and sa.max() <= 16 and np.median(sa) == 2
    lengths = datagen_seq.history_lengths(cell.config, 3)
    assert lengths.min() >= 8192 and lengths.max() <= 65536
    assert 2.4e6 < lengths.sum() < 2.7e6
    with pytest.raises(ValueError, match="does not divide"):
        drive.schedule(dict(mix, chunk=48), cell.config, 3)


def test_tiny_cell_runs_and_is_correct(doc):
    res = _run(tiny(doc), seconds=3.0)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] % 8 == 0
    assert set(res["metrics"]) == {"queries_per_s", "setup_s"}
    compared = res["compared"]
    assert compared["state_misses_in_window"]["value"] == 0
    assert compared["compiles_in_window"]["value"] == 0
    assert 0 < compared["score_abs_err_p50"]["value"] < 0.1
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
    assert m["sparse_selected_path_pct"] == 100.0
    # 3 whole blocks of 8 and the query's own, up to itself.
    assert 25 <= m["sparse_keys_per_query"] <= 32
    assert 4 <= m["seq_new_tokens_per_dispatch.bulk"] <= 64
    assert {"dispatch_lookup_ms.bulk", "dispatch_assemble_ms.bulk",
            "bulk_bind_ms", "compile_s"} <= got


def test_the_three_controls_are_refused_at_tiny_size(doc, capsys):
    cell = tiny(doc)
    numbers = sala_serving.control(cell.config, 2 ** 31 + 5)
    from benchmark import compare

    ok, compared = compare.verdict(
        numbers, {k: cell.config["limits"][k] for k in numbers})
    assert not ok, compared                 # the closest to passing failed
    err = capsys.readouterr().err
    for name in sala_serving.controls():
        assert f"control {name} seed {2 ** 31 + 5}: refused True" in err


def test_answers_at_another_event_are_caught(doc):
    cfg = tiny(doc).config
    seed = 9
    samples = [(0, 50, 10), (0, 55, 10), (1, 40, 10)]
    logits = compare_sala.reference_logits(
        cfg, seed, [(u, c) for u, c, _ in samples])
    good = compare_sala.numbers(cfg, seed,
                                compare_sala.as_answers(samples, logits))
    assert good["score_abs_err_max"] < 1e-5 and good["rank_gap_max"] == 0
    assert good["malformed"] == good["unordered"] == 0
    # One event fewer of history is another answer.
    late = [(u, c - 1, n, a) for u, c, n, a in
            compare_sala.as_answers(samples, logits)]
    off = compare_sala.numbers(cfg, seed, late)
    assert off["score_abs_err_p50"] > 0.1
    bad = compare_sala.numbers(cfg, seed, [(0, 50, 10, {"itemScores": []})])
    assert bad["malformed"] == 1
    events = datagen_seq.Events(cfg, seed)
    once = reference_sala.logits_at(cfg, seed, [events.of(0, 50)], [[49]])
    np.testing.assert_allclose(once[0][0], logits[0], atol=1e-5)


# -- readers on hand-made snapshots -----------------------------------------

def test_device_readers_of_the_two_kernels(doc):
    cfg = manifest.cell(doc, CELL).config
    after = {"pio_seq_dispatches_total": 1000.0,
             'pio_seq_tokens_total{kind="new"}': 176_000.0,
             "pio_seq_sparse_keys_total": 176_000 * 4 * 4064.0,
             "pio_seq_index_pairs_total": 176_000 * 4 * 1250.0,
             "pio_seq_recurrent_updates_total": 64_000 * 6.0}
    trace = {"window_s": 30.0, "busy_s": 20.0, "chips_traced": 1,
             "op_s": {"sala_sparse_attention": 4.0, "sala_lightning": 3.0,
                      "fusion": 9.0}, "gap_s": {}}

    class _Window:
        extras = {"seq_dispatches": 1000.0}

    ctx = {"before": {}, "after": after, "trace": trace, "config": cfg,
           "device_kind": "TPU v5 lite", "window": _Window}
    assert op_ms_per_unit.read(ctx, "^sala_sparse_attention",
                               "seq_dispatches") == pytest.approx(4.0)
    flops, nbytes = rooflines_sala.sparse_attention_counts(
        cfg, 176_000 * 4 * 4064, 64_000)
    assert nbytes == 64_000 * 2 * 2 * 64 * 64 * 2 * 128 * 2
    share = sala_roofline.read(ctx, "sparse", "^sala_sparse_attention")
    assert share == pytest.approx(100 * max(nbytes / 819e9, flops / 197e12)
                                  / 4.0)
    assert 0 < share < 100
    flops, nbytes = rooflines_sala.lightning_counts(cfg, 176_000, 384_000)
    share = sala_roofline.read(ctx, "lightning", "^sala_lightning")
    assert share == pytest.approx(100 * (nbytes / 819e9) / 3.0)
    assert 0 < share < 100
    mfu = sala_mfu.read(ctx)
    assert mfu == pytest.approx(100 * rooflines_sala.step_flops(
        cfg, 176_000, 176_000 * 4 * 4064, 176_000 * 4 * 1250)
        / (30 * 197e12))
    assert 0 < mfu < 100
    # No kernel time (the CPU), no counters (the parent commit): nothing.
    none = {**ctx, "trace": {**trace, "op_s": {}, "chips_traced": 0}}
    assert sala_roofline.read(none, "sparse", "^sala_sparse") is None
    assert sala_mfu.read(none) is None
    assert sala_mfu.read({**ctx, "after": {}}) is None
    assert sala_roofline.read({**ctx, "after": {}}, "lightning",
                              "^sala_lightning") is None


def test_manifest_holds_the_new_cell_and_its_metrics(doc):
    cell = manifest.cell(doc, CELL)
    assert cell.chips == 1 and cell.config["builder"] == "sala_serving"
    assert [m["name"] for m in cell.end_to_end] == ["queries_per_s",
                                                    "setup_s"]
    for m in cell.per_layer:
        spec = manifest.layer_metric_spec(m["name"])
        assert (manifest.ROOT / "readers" / f"{spec['reader']}.py").exists()
    assert (manifest.ROOT / "drives"
            / f"{cell.traffic['drive']}.py").exists()
    # Looked up by name, not by place: the next cell is appended after
    # this one and must not turn this test red.
    assert CELL in [w["name"] for w in doc["workloads"]]
    assert "minicpm-sala-l8" in [c["name"] for c in doc["configs"]]
    listed = {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert NEW | DEVICE_ONLY | {"queries_per_s", "bulk_bind_ms"} <= listed
    assert manifest.cell(doc, CELL).chips == 1
