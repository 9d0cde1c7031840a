"""The Mamba / sliding-window / shared-cache cell's own files at tiny
sizes on the CPU: the configuration against the published keys, the
schedule, the builder and the drive through a whole run
(``require_chip=False``), the four negative controls, the readers on
hand-made snapshots, and the manifest (entries looked up by name).  Run
with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import dataclasses
import json

import numpy as np
import pytest

from benchmark import (compare_sambay, datagen_sambay, datagen_seq,
                       manifest, reference_sambay, rooflines_sambay)
from benchmark.builders import sambay_serving
from benchmark.drives import seq_bulk_turns as drive
from benchmark.readers import (op_ms_per_unit, prom_gauge_ratio, sambay_mfu,
                               sambay_roofline)
from benchmark.tests.test_benchmark import _run, doc

__all__ = ["doc"]                         # fixture, used by name

CELL = "phi4-mini-flash-l32.bulk-turns-64"
CONFIG = "phi4-mini-flash-l32"
# Eight layers (Mamba, window, Mamba, window, Mamba, full, GMU, cross), a
# window of 24 over pages of 128: the window pool's lists are exercised,
# its release only in the tier-1 tests (pages of 8 there).
TINY = dict(hidden_size=64, vocab_size=512, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=96,
            num_hidden_layers=8, sliding_window=24, n_users=8,
            history={"median": 200, "sigma": 0.6, "min": 140, "max": 400},
            assumed={"d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 4},
            state={"budget_bytes": 60_000_000, "page": 128,
                   "window_page_bytes": 2 * 128 * 64 * 2},
            control_users=2, control_answers=4)
NEW = {"window_pages_per_user", "cross_rows_pct", "seq_extend_ms.bulk",
       "seq_new_tokens_per_dispatch.bulk", "state_cache_build_s",
       "seq_compile_s"}
DEVICE_ONLY = {"sambay_step_mfu", "shared_attn_ms", "shared_attn_roofline",
               "window_attn_ms", "window_attn_roofline", "ssm_scan_ms",
               "ssm_scan_roofline", "device_idle_pct.bulk"}
# Tiny widths put bfloat16 noise above the full-size limits; the controls
# move answers by more still.
LIMITS = dict(score_abs_err_p50=0.12, score_abs_err_p90=0.2,
              score_abs_err_max=0.3, rank_gap_p90=0.2, rank_gap_max=0.3)


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
                       if r["name"] == "Phi-4-mini-flash-reasoning")
    except OSError:
        pytest.skip("no catalog here")
    cfg = manifest.config(manifest.load(), CONFIG)
    assert cfg["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if cfg.get(k) != v] == []
    assert cfg["reduced"] == []
    kinds = datagen_sambay.kinds(cfg)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "cross",
                                     "gmu")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    assert datagen_sambay.sizes(cfg) == {
        "d": 2560, "f": 10240, "e": 5120, "n": 16, "r": 160, "w": 4,
        "heads": 40, "kv": 20, "hd": 64}
    every, read = rooflines_sambay.params_per_token(cfg)
    assert (round(every / 1e6, 1), round(read / 1e6, 1)) == (1870.9, 1980.2)
    total = sum(int(np.prod(s)) for layer in range(32) for s in
                datagen_sambay.layer_shapes(cfg, layer).values()) \
        + 200064 * 2560 + 2 * 2560
    assert round(total / 1e6, 1) == 3852.6
    assert set(cfg["limits"]) >= set(compare_sambay.EMPTY)
    assert cfg["limits_why"] and cfg["precision"] and cfg["assumed"]["ssm"]
    assert cfg["state"]["window_page_bytes"] == 128 * 8 * 5120


def test_the_program_reads_the_weights_the_reference_makes():
    """The builder's model and the reference's generator name and shape
    every layer's weights alike (they share no code)."""
    from predictionio_tpu.models import sambay

    cfg = manifest.config(manifest.load(), CONFIG)
    a = cfg["assumed"]
    model = sambay.SambaYConfig.from_published(
        cfg, **{k: a[k] for k in ("d_state", "d_conv", "expand", "dt_rank")})
    assert list(model.kinds) == datagen_sambay.kinds(cfg)
    for layer in range(32):
        assert sambay.layer_shapes(model, layer) \
            == datagen_sambay.layer_shapes(cfg, layer)
    layout = sambay.state_layout(model, 128)
    assert layout["window_bytes"] == cfg["state"]["window_page_bytes"]


def test_a_pass_is_the_same_work_for_every_seed(doc):
    cell = manifest.cell(doc, CELL)
    mix = dict(cell.traffic, max_calls=40)
    ua, sa = drive.schedule(mix, cell.config, 3)
    ub, sb = drive.schedule(mix, cell.config, 2 ** 31 + 3)
    assert ua.shape == sa.shape == (40, 64)
    for users, sizes in ((ua, sa), (ub, sb)):
        for c in range(40):                # a call: every resident once
            assert sorted(users[c]) == list(range(64))
            assert sorted(sizes[c]) == sorted(sa[0])
    assert not np.array_equal(ua, ub)
    assert sa.min() >= 1 and sa.max() <= 16 and sa[0].sum() == 178
    lengths = datagen_seq.history_lengths(cell.config, 3)
    assert (lengths.min(), lengths.max(), lengths.sum()) \
        == (1184, 32768, 682_651)
    assert int((-(-lengths // 128)).sum()) == 5366


def test_tiny_cell_runs_and_is_correct(doc):
    res = _run(tiny(doc), seconds=3.0)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] % 8 == 0
    assert set(res["metrics"]) == {"queries_per_s", "setup_s"}
    compared = res["compared"]
    assert compared["state_misses_in_window"]["value"] == 0
    assert compared["compiles_in_window"]["value"] == 0
    assert 0 < compared["score_abs_err_p50"]["value"] < 0.12
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
    assert 0 < m["window_pages_per_user"] <= 2
    # One read row a query; 1-16 events a query.
    assert 100 / 16 <= m["cross_rows_pct"] <= 100
    assert 4 <= m["seq_new_tokens_per_dispatch.bulk"] <= 64
    assert {"dispatch_lookup_ms.bulk", "dispatch_assemble_ms.bulk",
            "bulk_bind_ms", "compile_s"} <= got


def test_the_four_controls_are_refused_at_tiny_size(doc, capsys):
    cell = tiny(doc)
    seed = 2 ** 31 + 5
    numbers = sambay_serving.control(cell.config, seed)
    from benchmark import compare

    ok, compared = compare.verdict(
        numbers, {k: cell.config["limits"][k] for k in numbers})
    assert not ok, compared                 # the closest to passing failed
    err = capsys.readouterr().err
    for name in ("float8_weights", "state_zeroed_each_turn", "zero_lambda",
                 "window_a_page_short"):
        assert f"control {name} seed {seed}: refused True" in err


def test_answers_at_another_event_are_caught(doc):
    cfg = tiny(doc).config
    seed = 9
    samples = [(0, 150, 10), (0, 155, 10), (1, 140, 10)]
    logits = compare_sambay.reference_logits(
        cfg, seed, [(u, c) for u, c, _ in samples])
    good = compare_sambay.numbers(
        cfg, seed, compare_sambay.as_answers(samples, logits))
    assert good["score_abs_err_max"] < 1e-5 and good["rank_gap_max"] == 0
    assert good["malformed"] == good["unordered"] == 0
    # One event fewer of history is another answer.
    late = [(u, c - 1, n, a) for u, c, n, a in
            compare_sambay.as_answers(samples, logits)]
    off = compare_sambay.numbers(cfg, seed, late)
    assert off["score_abs_err_p50"] > 0.1
    bad = compare_sambay.numbers(cfg, seed,
                                 [(0, 150, 10, {"itemScores": []})])
    assert bad["malformed"] == 1
    events = datagen_seq.Events(cfg, seed)
    once = reference_sambay.logits_at(cfg, seed, [events.of(0, 150)],
                                      [[149]])
    np.testing.assert_allclose(once[0][0], logits[0], atol=1e-5)


def test_the_benchmarks_reference_is_the_programs(doc):
    """Two independent writings of the equations, on the same seeded
    weights: the benchmark's reference in blocks and the program's plain
    reference agree to float32 rounding."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import sambay, sambay_reference

    cfg = tiny(doc).config
    seed = 5
    a = cfg["assumed"]
    model = sambay.SambaYConfig.from_published(
        cfg, **{k: a[k] for k in ("d_state", "d_conv", "expand", "dt_rank")})
    params = {"embed": datagen_sambay.embedding(cfg, seed),
              **datagen_sambay.final_norm(cfg, seed),
              "layers": [datagen_sambay.layer_weights(cfg, seed, layer)
                         for layer in range(8)]}
    tokens = datagen_seq.Events(cfg, seed).of(3, 90)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(sambay_reference.forward(
            params, model, jnp.asarray(tokens)))
    got = reference_sambay.logits_at(cfg, seed, [tokens], [[10, 50, 89]])
    np.testing.assert_allclose(got[0], want[[10, 50, 89]], atol=2e-4)
    starts = np.zeros(90, bool)
    starts[[40, 80]] = True
    with jax.default_matmul_precision("highest"):
        want = np.asarray(sambay_reference.forward(
            params, model, jnp.asarray(tokens), zero_lambda=True, window=9,
            state_resets=jnp.asarray(starts)))
    got = reference_sambay.logits_at(
        cfg, seed, [tokens], [[89]], zero_lambda=True, window=9,
        turn_starts=[[40, 80]])
    np.testing.assert_allclose(got[0][0], want[89], atol=2e-4)


# -- readers on hand-made snapshots -----------------------------------------

def test_device_readers_of_the_three_kernels(doc):
    cfg = manifest.cell(doc, CELL).config
    shared_keys = 64_000 * 8 * 10_700.0
    window_keys, window_rows = 178_000 * 8 * 512.0, 64_000 * 8 * 514.0
    after = {"pio_seq_dispatches_total": 1000.0,
             'pio_seq_tokens_total{kind="new"}': 178_000.0,
             "pio_seq_cross_rows_total": 64_000.0,
             "pio_seq_shared_keys_total": shared_keys,
             "pio_seq_window_keys_total": window_keys,
             "pio_seq_window_rows_total": window_rows,
             "pio_seq_recurrent_updates_total": 64_000 * 9.0,
             'pio_seq_state_bytes{kind="window"}': 300 * 5242880.0,
             'pio_seq_state_bytes{kind="full"}': 5.0e9,
             "pio_seq_state_users": 64.0}
    trace = {"window_s": 60.0, "busy_s": 50.0, "chips_traced": 1,
             "op_s": {"sambay_shared_attention": 40.0,
                      "sambay_window_attention": 4.0,
                      "sambay_selective_scan": 3.0, "fusion": 9.0},
             "gap_s": {}}

    class _Window:
        extras = {"seq_dispatches": 1000.0}

    ctx = {"before": {}, "after": after, "trace": trace, "config": cfg,
           "device_kind": "TPU v5 lite", "window": _Window}
    assert op_ms_per_unit.read(ctx, "^sambay_shared_attention",
                               "seq_dispatches") == pytest.approx(40.0)
    flops, nbytes = rooflines_sambay.shared_counts(cfg, shared_keys)
    assert nbytes == shared_keys * 5120 and flops == shared_keys * 40 * 384
    share = sambay_roofline.read(ctx, "shared", "^sambay_shared_attention")
    assert share == pytest.approx(100 * (nbytes / 819e9) / 40.0)
    assert 0 < share < 100
    flops, nbytes = rooflines_sambay.window_counts(cfg, window_keys,
                                                   window_rows)
    share = sambay_roofline.read(ctx, "window", "^sambay_window_attention")
    assert share == pytest.approx(
        100 * max(nbytes / 819e9, flops / 197e12) / 4.0)
    assert 0 < share < 100
    flops, nbytes = rooflines_sambay.scan_counts(cfg, 178_000, 576_000)
    assert nbytes == 576_000 * 2 * 327_680 + 178_000 * 9 * (
        3 * 5120 + 32) * 4
    share = sambay_roofline.read(ctx, "scan", "^sambay_selective_scan")
    assert share == pytest.approx(100 * (nbytes / 819e9) / 3.0)
    assert 0 < share < 100
    mfu = sambay_mfu.read(ctx)
    assert mfu == pytest.approx(100 * rooflines_sambay.step_flops(
        cfg, 178_000, 64_000, window_keys, shared_keys) / (60 * 197e12))
    assert 0 < mfu < 100
    spec = manifest.layer_metric_spec("window_pages_per_user")
    assert prom_gauge_ratio.read(ctx, **spec["args"]) \
        == pytest.approx(300 / 64)
    # No kernel time (the CPU), no counters (the parent commit): nothing.
    none = {**ctx, "trace": {**trace, "op_s": {}, "chips_traced": 0}}
    assert sambay_roofline.read(none, "shared", "^sambay_shared") is None
    assert sambay_mfu.read(none) is None
    assert sambay_mfu.read({**ctx, "after": {}}) is None
    assert sambay_roofline.read({**ctx, "after": {}}, "scan",
                                "^sambay_selective_scan") is None
    parent = {k: v for k, v in after.items() if "window" not in k}
    parent['pio_seq_state_bytes{kind="paged"}'] = 1.0e9
    assert prom_gauge_ratio.read({**ctx, "after": parent},
                                 **spec["args"]) is None


def test_manifest_holds_the_new_cell_and_its_metrics(doc):
    cell = manifest.cell(doc, CELL)
    assert cell.chips == 1 and cell.config["builder"] == "sambay_serving"
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
    assert CONFIG in [c["name"] for c in doc["configs"]]
    listed = {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert NEW | DEVICE_ONLY | {"queries_per_s", "bulk_bind_ms"} <= listed
    assert all(m["moves"] == "queries_per_s" for m in doc["per_layer"]
               if m.get("workloads") == [CELL])
