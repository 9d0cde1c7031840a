"""The Mamba-2 / no-position attention cell's own files at tiny sizes on
the CPU: the configuration against the published keys, the schedule, the
builder and the drive through a whole run (``require_chip=False``), the
four negative controls, the readers on hand-made snapshots, and the
manifest (entries looked up by name, never by place).  Run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import dataclasses
import json

import numpy as np
import pytest

from benchmark import (compare_granite_h, datagen_granite_h, datagen_seq,
                       manifest, reference_granite_h, rooflines_granite_h)
from benchmark.builders import granite_h_serving
from benchmark.drives import seq_bulk_turns as drive
from benchmark.readers import granite_h_mfu, granite_h_roofline, \
    op_ms_per_unit, prom_ratio
from benchmark.tests.test_benchmark import _run, doc

__all__ = ["doc"]                         # fixture, used by name

CELL = "granite-4.0-h-micro-l40.bulk-turns-32"
CONFIG = "granite-4.0-h-micro-l40"
# Six layers, one of them attention; every published ratio kept: heads x
# P = 2 d, N = 2 P, one group, 2 query heads a kv head.
TINY = dict(hidden_size=64, vocab_size=512, num_attention_heads=4,
            num_key_value_heads=2, shared_intermediate_size=96,
            intermediate_size=96, num_hidden_layers=6,
            layer_types=["mamba", "mamba", "attention", "mamba", "mamba",
                         "mamba"],
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=32, n_users=8,
            history={"median": 200, "sigma": 0.6, "min": 140, "max": 400},
            state={"budget_bytes": 40_000_000, "page": 128},
            control_users=2, control_answers=4)
NEW = {"ssd_updates_per_dispatch", "seq_extend_ms.bulk",
       "seq_new_tokens_per_dispatch.bulk", "state_cache_build_s",
       "seq_compile_s"}
DEVICE_ONLY = {"granite_h_step_mfu", "ssd_update_ms", "ssd_update_roofline",
               "gqa_attn_ms", "gqa_attn_roofline", "device_idle_pct.bulk"}
# At tiny widths the input embedding (times 12) outweighs the six layers'
# branches (times 0.22), so sound answers read 0.0004-0.0006 and the
# closest controls 0.01 (the logits' spread is 0.13): limits between.
LIMITS = dict(score_abs_err_p50=0.002, score_abs_err_p90=0.003,
              score_abs_err_max=0.004, rank_gap_p90=0.002,
              rank_gap_max=0.003)


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
                       if r["name"] == "granite-4.0-h-micro")
    except OSError:
        pytest.skip("no catalog here")
    cfg = manifest.config(manifest.load(), CONFIG)
    assert cfg["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if cfg.get(k) != v] == []
    assert cfg["reduced"] == []
    kinds = cfg["layer_types"]
    assert [i for i, k in enumerate(kinds) if k == "attention"] \
        == [5, 15, 25, 35] and kinds.count("mamba") == 36
    assert datagen_granite_h.sizes(cfg) == {
        "d": 2048, "f": 8192, "e": 4096, "n": 128, "h": 64, "p": 64,
        "w": 4, "heads": 32, "kv": 8, "hd": 64}
    per_kind = {k: sum(int(np.prod(s)) for s in
                       datagen_granite_h.layer_shapes(cfg, layer).values())
                for layer, k in ((0, "mamba"), (5, "attention"))}
    mlp = 2048 * 16384 + 8192 * 2048
    assert round((per_kind["mamba"] - mlp) / 1e6, 2) == 25.85
    assert round((per_kind["attention"] - mlp) / 1e6, 2) == 10.49
    total = 36 * per_kind["mamba"] + 4 * per_kind["attention"] \
        + 100352 * 2048 + 2048
    assert round(total / 1e6, 1) == 3191.4
    assert round(rooflines_granite_h.matrix_params(cfg) / 1e6, 1) == 2984.8
    assert set(cfg["limits"]) >= set(compare_granite_h.EMPTY)
    assert cfg["limits_why"] and cfg["precision"]
    assert set(cfg["trace_spans"]) == {"dispatch_batch", "seq_extend"}
    state = cfg["state"]
    assert state["slot_bytes"] == 36 * (64 * 64 * 128 + 3 * 4352) * 4 \
        + 2048 * 4 == 77_385_728
    assert state["budget_bytes"] == 66 * state["slot_bytes"] \
        + 4 * 33 * 256 + 2901 * state["page_bytes"]


def test_the_program_reads_the_weights_the_reference_makes():
    """The builder's model and the reference's generator name and shape
    every layer's weights alike (they share no code), and the cache the
    program builds is the one the configuration states."""
    from predictionio_tpu.models import granite_h

    cfg = manifest.config(manifest.load(), CONFIG)
    model = granite_h.GraniteHConfig.from_published(cfg)
    assert list(model.layer_types) == cfg["layer_types"]
    for layer in range(40):
        assert granite_h.layer_shapes(model, layer) \
            == datagen_granite_h.layer_shapes(cfg, layer)
    layout = granite_h.state_layout(model, 128)
    assert layout["fixed_bytes"] == cfg["state"]["slot_bytes"]
    assert layout["paged_bytes"] == cfg["state"]["page_bytes"]
    assert layout["table_len"] == 256
    assert granite_h.READ_BUCKETS[-1] == cfg["n_users"] == 32


def test_a_pass_is_the_same_work_for_every_seed(doc):
    cell = manifest.cell(doc, CELL)
    mix = dict(cell.traffic, max_calls=40)
    assert (mix["chunk"], mix["max_calls"], mix["prefill_users_per_call"],
            mix["check_users"], mix["check_answers"]) == (32, 40, 8, 4, 32)
    assert cell.traffic["max_calls"] == 3000
    ua, sa = drive.schedule(mix, cell.config, 3)
    ub, sb = drive.schedule(mix, cell.config, 2 ** 31 + 3)
    assert ua.shape == sa.shape == (40, 32)
    for users, sizes in ((ua, sa), (ub, sb)):
        for c in range(40):                # a call: every resident once
            assert sorted(users[c]) == list(range(32))
            assert sorted(sizes[c]) == sorted(sa[0])
    assert not np.array_equal(ua, ub)
    assert sa.min() >= 1 and sa.max() <= 16 and sa[0].sum() == 87
    lengths = datagen_seq.history_lengths(cell.config, 3)
    assert (lengths.min(), lengths.max(), lengths.sum()) \
        == (731, 16384, 170_697)
    assert int((-(-lengths // 128)).sum()) == 1349


def test_tiny_cell_runs_and_is_correct(doc):
    res = _run(tiny(doc), seconds=3.0)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] % 8 == 0
    assert set(res["metrics"]) == {"queries_per_s", "setup_s"}
    compared = res["compared"]
    assert compared["state_misses_in_window"]["value"] == 0
    assert compared["compiles_in_window"]["value"] == 0
    assert 0 < compared["score_abs_err_p50"]["value"] < 0.002
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
    # 8 users a call x 5 Mamba-2 layers, while a call is one program.
    assert m["ssd_updates_per_dispatch"] == 40
    assert 8 <= m["seq_new_tokens_per_dispatch.bulk"] <= 64
    assert {"dispatch_lookup_ms.bulk", "dispatch_assemble_ms.bulk",
            "bulk_bind_ms", "compile_s"} <= got


def test_the_four_controls_are_refused_at_tiny_size(doc, capsys):
    cell = tiny(doc)
    seed = 2 ** 31 + 5
    numbers = granite_h_serving.control(cell.config, seed)
    from benchmark import compare

    ok, compared = compare.verdict(
        numbers, {k: cell.config["limits"][k] for k in numbers})
    assert not ok, compared                 # the closest to passing failed
    err = capsys.readouterr().err
    for name in ("float8_weights", "state_zeroed_each_turn",
                 "attention_scaled_by_rsqrt_head", "residual_multiplier_1"):
        assert f"control {name} seed {seed}: refused True" in err


def test_answers_at_another_event_are_caught(doc):
    cfg = tiny(doc).config
    seed = 9
    samples = [(0, 150, 10), (0, 155, 10), (1, 140, 10)]
    logits = compare_granite_h.reference_logits(
        cfg, seed, [(u, c) for u, c, _ in samples])
    good = compare_granite_h.numbers(
        cfg, seed, compare_granite_h.as_answers(samples, logits))
    assert good["score_abs_err_max"] < 1e-5 and good["rank_gap_max"] == 0
    assert good["malformed"] == good["unordered"] == 0
    # One event fewer of history is another answer.
    late = [(u, c - 1, n, a) for u, c, n, a in
            compare_granite_h.as_answers(samples, logits)]
    off = compare_granite_h.numbers(cfg, seed, late)
    assert off["score_abs_err_p50"] > 0.02
    bad = compare_granite_h.numbers(cfg, seed,
                                    [(0, 150, 10, {"itemScores": []})])
    assert bad["malformed"] == 1
    events = datagen_seq.Events(cfg, seed)
    once = reference_granite_h.logits_at(cfg, seed, [events.of(0, 150)],
                                         [[149]])
    np.testing.assert_allclose(once[0][0], logits[0], atol=1e-5)


def test_the_benchmarks_reference_is_the_programs(doc):
    """Two independent writings of the equations, on the same seeded
    weights: the benchmark's reference in blocks and the program's plain
    event-by-event reference agree to float32 rounding, the controls'
    variants too."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import granite_h, granite_h_reference

    cfg = tiny(doc).config
    seed = 5
    model = granite_h.GraniteHConfig.from_published(cfg)
    params = {"embed": datagen_granite_h.embedding(cfg, seed),
              "final_norm": datagen_granite_h.final_norm(cfg, seed),
              "layers": [datagen_granite_h.layer_weights(cfg, seed, layer)
                         for layer in range(6)]}
    tokens = datagen_seq.Events(cfg, seed).of(3, 300)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(granite_h_reference.forward(
            params, model, jnp.asarray(tokens)))
    got = reference_granite_h.logits_at(cfg, seed, [tokens],
                                        [[10, 50, 255, 256, 299]])
    np.testing.assert_allclose(got[0], want[[10, 50, 255, 256, 299]],
                               atol=5e-5)
    starts = np.zeros(300, bool)
    starts[[40, 256, 290]] = True
    other = dataclasses.replace(model, attention_multiplier=0.25,
                                residual_multiplier=1.0)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(granite_h_reference.forward(
            params, other, jnp.asarray(tokens),
            state_resets=jnp.asarray(starts)))
    got = reference_granite_h.logits_at(
        cfg, seed, [tokens], [[255, 299]], attention_multiplier=0.25,
        residual_multiplier=1.0, turn_starts=[[40, 256, 290]])
    np.testing.assert_allclose(got[0], want[[255, 299]], atol=5e-5)


# -- readers and counts on hand-made snapshots ------------------------------

def test_rooflines_on_hand_counted_sizes(doc):
    cfg = manifest.cell(doc, CELL).config
    # One dispatch of the cell: 32 users, 87 new events, 36 layers.
    flops, nbytes = rooflines_granite_h.ssd_counts(cfg, 87, 32 * 36)
    assert nbytes == 32 * 36 * 2 * (64 * 64 * 128 * 4) \
        + 87 * 36 * (2 * 4096 + 2 * 128 + 64) * 4
    assert flops == 87 * 36 * (5 * 4096 * 128 + 64)
    flops, nbytes = rooflines_granite_h.attention_counts(cfg, 1000.0, 400.0)
    assert (flops, nbytes) == (1000 * 32 * 4 * 64, 400 * 2 * 8 * 64 * 2)
    assert rooflines_granite_h.step_flops(cfg, 87, 32, 1000.0) \
        == 2 * rooflines_granite_h.matrix_params(cfg) * 87 \
        + 2 * 100352 * 2048 * 32 + 1000 * 32 * 4 * 64 \
        + 87 * 36 * (5 * 4096 * 128 + 64)


def test_device_readers_of_the_two_kernels(doc):
    cfg = manifest.cell(doc, CELL).config
    calls, events = 1200.0, 1200 * 87.0
    keys, rows = events * 4 * 7000.0, calls * 32 * 4 * 7000.0
    after = {"pio_seq_dispatches_total": calls,
             'pio_seq_tokens_total{kind="new"}': events,
             "pio_seq_attended_keys_total": keys,
             "pio_seq_attention_rows_total": rows,
             "pio_seq_recurrent_updates_total": calls * 32 * 36}
    trace = {"window_s": 30.0, "busy_s": 26.0, "chips_traced": 1,
             "op_s": {"granite_h_ssd_update": 10.0,
                      "granite_h_gqa_attention": 4.0, "fusion": 12.0},
             "gap_s": {}}

    class _Window:
        extras = {"seq_dispatches": calls}
        attempted, failed = int(calls) * 32, 0

    ctx = {"before": {}, "after": after, "trace": trace, "config": cfg,
           "device_kind": "TPU v5 lite", "window": _Window}
    assert op_ms_per_unit.read(ctx, "^granite_h_ssd_update",
                               "seq_dispatches") == pytest.approx(1e4 / calls)
    flops, nbytes = rooflines_granite_h.ssd_counts(cfg, events,
                                                   calls * 32 * 36)
    share = granite_h_roofline.read(ctx, "ssd", "^granite_h_ssd_update")
    assert share == pytest.approx(100 * (nbytes / 819e9) / 10.0)
    assert 0 < share < 100
    flops, nbytes = rooflines_granite_h.attention_counts(cfg, keys, rows)
    share = granite_h_roofline.read(ctx, "attention",
                                    "^granite_h_gqa_attention")
    assert share == pytest.approx(
        100 * max(nbytes / 819e9, flops / 197e12) / 4.0)
    assert 0 < share < 100
    mfu = granite_h_mfu.read(ctx)
    assert mfu == pytest.approx(100 * rooflines_granite_h.step_flops(
        cfg, events, calls * 32, keys) / (30 * 197e12))
    assert 0 < mfu < 100
    spec = manifest.layer_metric_spec("ssd_updates_per_dispatch")
    assert prom_ratio.read(ctx, **spec["args"]) == 32 * 36
    # No kernel time (the CPU), no counters (the parent commit): nothing.
    none = {**ctx, "trace": {**trace, "op_s": {}, "chips_traced": 0}}
    assert granite_h_roofline.read(none, "ssd", "^granite_h_ssd") is None
    assert granite_h_mfu.read(none) is None
    assert granite_h_mfu.read({**ctx, "after": {}}) is None
    assert granite_h_roofline.read({**ctx, "after": {}}, "ssd",
                                   "^granite_h_ssd_update") is None
    assert prom_ratio.read({**ctx, "after": {}}, **spec["args"]) is None


def test_manifest_holds_the_new_cell_and_its_metrics(doc):
    cell = manifest.cell(doc, CELL)
    assert cell.chips == 1
    assert cell.config["builder"] == "granite_h_serving"
    assert cell.traffic_name == "bulk-turns-32"
    assert [m["name"] for m in cell.end_to_end] == ["queries_per_s",
                                                    "setup_s"]
    for m in cell.per_layer:
        spec = manifest.layer_metric_spec(m["name"])
        assert (manifest.ROOT / "readers" / f"{spec['reader']}.py").exists()
    assert (manifest.ROOT / "drives"
            / f"{cell.traffic['drive']}.py").exists()
    assert (manifest.ROOT / f"{cell.config['compare']}.py").exists()
    # Looked up by name, not by place: the next cell is appended after
    # this one and must not turn this test red.
    assert CELL in [w["name"] for w in doc["workloads"]]
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] \
        and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    listed = {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert NEW | DEVICE_ONLY | {"queries_per_s", "bulk_bind_ms"} <= listed
    own = [m for m in doc["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in own} == DEVICE_ONLY - {
        "device_idle_pct.bulk"} | {"ssd_updates_per_dispatch"}
    assert all(m["moves"] == "queries_per_s" for m in own)
