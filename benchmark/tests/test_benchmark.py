"""Tests of the benchmark's own files: CPU, tiny sizes, Pallas
interpreted.  Run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They are not part of the repo's tier-1 suite (``tests/``)."""

import copy
import dataclasses
import http.server
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (builders, compare, drives, loadgen, manifest,
                       ratings, rooflines, trace_reduce, traffic)

CHECKOUT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SERVE = "als-amazon14-r128-exact.serve-steady"
BULK = "als-amazon14-r128-exact.bulk-score"
TRAIN = "als-netflix-r64.retrain"
TINY_SERVING = dict(n_items=4096, n_users=512, rank=16,
                    factor_block_rows=512)
TINY_TRAIN = dict(
    n_users=300, n_items=40, n_ratings=3000, rank=8, check_items=8,
    ratings={"user_degrees": {"median": 7, "min": 1, "max": 30},
             "item_degrees": {"median": 50, "min": 3, "max": 250},
             "stars": [1, 2, 6, 7, 5]})


@pytest.fixture(scope="module")
def doc():
    return manifest.load()


def tiny_cell(doc, name):
    cell = manifest.cell(doc, name)
    config = dict(cell.config)
    mix = dict(cell.traffic)
    if name == TRAIN:
        config.update(TINY_TRAIN)
    else:
        config.update(TINY_SERVING)
        mix.update(check_answers=32)
        if name == SERVE:
            mix.update(arrival={"rate_per_s": 50}, connections=8)
        else:
            mix.update(chunk=16, max_queries=200_000)
    return dataclasses.replace(cell, config=config, traffic=mix)


@pytest.fixture
def device_rung(monkeypatch):
    # A 4,096-item corpus would be answered by the host rung; send it
    # where the full-size cell goes.
    monkeypatch.setenv("PIO_SERVE_CHUNK_ABOVE", "0")
    monkeypatch.setenv("PIO_SERVE_HOST_MACS", "0")


# -- manifest ---------------------------------------------------------------

def test_manifest_loads_and_every_file_is_there(doc):
    for w in doc["workloads"]:
        cell = manifest.cell(doc, w["name"])
        assert cell.per_layer and len(cell.end_to_end) >= 2
        for m in cell.per_layer:
            spec = manifest.layer_metric_spec(m["name"])
            assert (manifest.ROOT / "readers"
                    / f"{spec['reader']}.py").exists()


@pytest.mark.parametrize("edit", [
    lambda d: d.update(extra=1),
    lambda d: d["workloads"][0].update(note="x"),
    lambda d: d["end_to_end"][0].update(why="x"),
    lambda d: d["workloads"][0].update(name="has space"),
    lambda d: d["per_layer"][0].update(name="a/b"),
    lambda d: d["end_to_end"][0].update(unit="queries per second"),
    lambda d: d["end_to_end"][0].update(unit="µs"),
    lambda d: d["per_layer"][0].update(moves="no_such_metric"),
    lambda d: d["per_layer"][0].update(source="guess"),
    lambda d: d["workloads"][0].update(chips=2),
    lambda d: d["end_to_end"][0].update(bound=0.5),
    lambda d: d["workloads"].append(dict(d["workloads"][0], name="again")),
], ids=["top-key", "workload-key", "metric-key", "name-space",
        "name-slash", "unit-words", "unit-greek", "moves", "source",
        "chips", "bound", "pair-twice"])
def test_manifest_refuses(doc, edit):
    bad = copy.deepcopy(doc)
    edit(bad)
    with pytest.raises(manifest.ManifestError):
        manifest.validate(bad)


# -- traffic and the open-loop generator ------------------------------------

def test_every_seed_offers_the_same_work_in_another_order():
    mix = {"arrival": {"rate_per_s": 40}, "num": [[10, 0.75], [100, 0.25]]}
    a = traffic.serving_requests(mix, 3, 5.0, 1000)
    b = traffic.serving_requests(mix, 2 ** 31 + 3, 5.0, 1000)
    assert len(a[0]) == len(b[0]) == 200
    assert a[0][-1] < 5.0 and np.all(np.diff(a[0]) > 0)
    gaps = [np.sort(np.diff(x[0], prepend=0.0)) for x in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9)
    assert not np.array_equal(a[1], b[1])
    assert sorted(a[2]) == sorted(b[2]) and (a[2] == 100).sum() == 50
    assert len(set(a[1])) == 200          # distinct users
    again = traffic.serving_requests(mix, 3, 5.0, 1000)
    assert all(np.array_equal(x, y) for x, y in zip(a, again))


def test_bursts_keep_the_rate():
    steady = traffic.arrival_times({"arrival": {"rate_per_s": 100}}, 1, 10.0)
    bursty = traffic.arrival_times({"arrival": {
        "rate_per_s": 100, "burst_share": 0.25, "burst_factor": 8}}, 1, 10.0)
    assert len(steady) == len(bursty) == 1000
    assert np.diff(bursty).std() > 1.1 * np.diff(steady).std()


def test_user_draws():
    n, pop = 20_000, 5_000
    uniform = traffic.draw_users({"users": {"draw": "uniform"}}, 1, n, pop)
    zipf = traffic.draw_users({"users": {"draw": "zipf", "zipf_s": 1.1}},
                              1, n, pop)
    for users in (uniform, zipf):
        assert len(users) == n and users.min() >= 0 and users.max() < pop
    hot = np.sort(np.bincount(zipf, minlength=pop))[::-1]
    flat = np.sort(np.bincount(uniform, minlength=pop))[::-1]
    # The k-th hottest of a Zipf(1.1) asks k^-1.1 / sum of the time;
    # uniform gives each about n / pop = 4.
    w = np.arange(1, pop + 1) ** -1.1
    np.testing.assert_allclose(hot[:5] / n, (w / w.sum())[:5], rtol=0.15)
    assert flat[0] < 20
    other = traffic.draw_users({"users": {"draw": "zipf"}}, 2, n, pop)
    assert np.bincount(other).argmax() != np.bincount(zipf).argmax()
    with pytest.raises(ValueError, match="users.draw"):
        traffic.draw_users({"users": {"draw": "pareto"}}, 1, n, pop)


def test_builders_and_drives_are_found_by_name():
    assert drives.load("http_open_loop").run
    assert builders.load("als_serving").build
    with pytest.raises(ValueError, match="no benchmark/drives/replay.py"):
        drives.load("replay")
    with pytest.raises(ValueError, match="no benchmark/builders/dlrm.py"):
        builders.load("dlrm")


# -- seeded ratings ---------------------------------------------------------

def test_ratings_keep_the_degrees_and_repeat_no_pair():
    cfg = dict(TINY_TRAIN)
    user_deg, item_deg = ratings.degree_sequences(cfg)
    law = cfg["ratings"]
    for deg, side, n in ((user_deg, "user_degrees", cfg["n_users"]),
                         (item_deg, "item_degrees", cfg["n_items"])):
        assert len(deg) == n and deg.sum() == cfg["n_ratings"]
        assert deg[0] == law[side]["max"] and deg[-1] == law[side]["min"]
        assert abs(np.median(deg) - law[side]["median"]) <= 0.05 * \
            law[side]["median"] + 1
    coos = [tuple(np.asarray(a) for a in ratings.ratings_coo(seed, cfg))
            for seed in (3, 2 ** 31 + 3)]
    for users, items, stars in coos:
        assert len(set(zip(users.tolist(), items.tolist()))) == \
            cfg["n_ratings"]
        # Every seed: the same degrees under other names.
        assert np.array_equal(np.sort(np.bincount(
            users, minlength=cfg["n_users"]))[::-1], user_deg)
        assert np.array_equal(np.sort(np.bincount(
            items, minlength=cfg["n_items"]))[::-1], item_deg)
        assert set(np.unique(stars)) == {1.0, 2.0, 3.0, 4.0, 5.0}
        share = np.bincount(stars.astype(int), minlength=6)[1:] / len(stars)
        np.testing.assert_allclose(
            share, np.array(law["stars"]) / sum(law["stars"]), atol=0.03)
    # Another seed: other pairs and stars, every id's degree as it was.
    assert not np.array_equal(coos[0][0], coos[1][0])
    for side, n in ((0, cfg["n_users"]), (1, cfg["n_items"])):
        assert np.array_equal(np.bincount(coos[0][side], minlength=n),
                              np.bincount(coos[1][side], minlength=n))
    again = ratings.ratings_coo(3, cfg)
    assert all(np.array_equal(a, b) for a, b in zip(coos[0], again))


def test_a_class_the_items_cannot_serve_is_an_error():
    with pytest.raises(ValueError, match="fewer left"):
        ratings.plan(np.array([3, 3]), np.array([3, 1, 1, 1]))


class _SlowFirst(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    stall_s = 0.3
    seen = 0

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers["Content-Length"]))
        type(self).seen += 1
        if type(self).seen == 1:
            time.sleep(self.stall_s)
        body = b'{"ok": true}'
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def test_loadgen_times_from_due_and_reports_lateness():
    _SlowFirst.seen = 0
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _SlowFirst)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    try:
        # One connection: the second request is due at 0.05 s but cannot
        # be sent until the stalled first one (0.3 s) has answered.
        res = loadgen.run({"port": srv.server_address[1],
                           "due_s": [0.0, 0.05, 0.5],
                           "bodies": ["{}", "{}", "{}"], "connections": 1})
    finally:
        srv.shutdown()
        srv.server_close()
        th.join(timeout=5)
    assert not th.is_alive()
    assert res["status"] == [200, 200, 200]
    due, sent, done = (np.array(res[k]) for k in
                       ("due_s", "sent_s", "done_s"))
    late = sent - due
    assert late[1] > 0.2 and late[0] < 0.05 and late[2] < 0.05
    # Latency from the due time holds the stall; from the send it hides.
    assert done[1] - due[1] > 0.2 > done[1] - sent[1]


# -- trace reduction --------------------------------------------------------

@pytest.mark.parametrize("recorded", ["small_trace_serve.json",
                                      "small_trace_train.json"])
def test_trace_reduction_on_the_recorded_trace(recorded):
    with open(DATA / recorded, encoding="utf-8") as f:
        rec = json.load(f)
    red = trace_reduce.reduce(rec["trace"])
    want = rec["hand_computed"]
    assert red["chips_traced"] == want["chips_traced"]
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for name, seconds in want["op_s"].items():
        assert red["op_s"][name] == pytest.approx(seconds, rel=1e-9), name
    assert set(red["op_s"]) == set(want["op_s"])
    for name, seconds in want["gap_s"].items():
        assert red["gap_s"][name] == pytest.approx(seconds, rel=1e-9), name
    assert sum(red["gap_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    for pattern, seconds in want["kernel_s"].items():
        assert trace_reduce.kernel_seconds(red, pattern) == pytest.approx(
            seconds, rel=1e-9)
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0][0] == want["top_op"]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


# -- rooflines --------------------------------------------------------------

def test_count_functions_give_hand_computed_numbers():
    assert rooflines.fused_topk_counts(2, 1000, 8, 10) == (
        2 * 2 * 1000 * 8, 1000 * 8 * 4 + 2 * 8 * 4 + 2 * 10 * 8)
    assert rooflines.als_gram_counts(10, 4) == (
        2 * 10 * 16 + 2 * 10 * 4, 10 * (4 * 2 + 4 + 4))
    flops, nbytes = rooflines.als_solve_counts(3, 4)
    assert flops == pytest.approx(3 * (64 / 3 + 32))
    assert nbytes == 3 * (16 + 8) * 4


@pytest.mark.parametrize("counts", [
    rooflines.fused_topk_counts(64, 9_400_000, 128, 10),
    rooflines.fused_topk_counts(4096, 9_400_000, 128, 10),
    rooflines.als_gram_counts(200_961_014, 64),
    rooflines.als_solve_counts(497_959, 64),
], ids=["topk-memory", "topk-compute", "gram", "solve"])
def test_share_cannot_pass_100_on_a_consistent_input(counts):
    pk = rooflines.peaks("TPU v5 lite")
    flops, nbytes = counts
    least = max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    # A kernel cannot run faster than the least time...
    at = rooflines.roofline_share(flops, nbytes, least, "TPU v5 lite")
    assert at["pct"] == pytest.approx(100.0)
    # ...and any real time is longer.
    assert rooflines.roofline_share(flops, nbytes, 3 * least,
                                    "TPU v5 lite")["pct"] < 100.0
    assert rooflines.roofline_share(flops, nbytes, 0.0,
                                    "TPU v5 lite") is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no peaks on record"):
        rooflines.peaks("cpu")


# -- the harness ------------------------------------------------------------

@pytest.mark.parametrize("cell", [SERVE, BULK, TRAIN])
def test_a_cpu_run_exits_nonzero_without_a_result(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def _run(cell, seed=2 ** 31 + 11, seconds=1.5, trace=False):
    from benchmark import run

    return run.run_cell(cell, seed, seconds, trace, require_chip=False)


@pytest.mark.parametrize("name", [SERVE, BULK, TRAIN])
def test_tiny_cell_runs_and_is_correct(doc, device_rung, name):
    res = _run(tiny_cell(doc, name))
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    assert set(res["metrics"]) == {
        m["name"] for m in manifest.cell(doc, name).end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_traced_run_reports_layer_metrics_it_can_read(doc, device_rung):
    res = _run(tiny_cell(doc, SERVE), trace=True)
    # No device plane on the CPU: the device readers return nothing and
    # their metrics stay out of the line; the host ones are there.
    assert {"loadgen_late_ms", "http_ingress_ms", "serve_queue_wait_ms",
            "serve_batch_size", "retrieval_ms.serve",
            "compile_s"} == set(res["metrics"])
    assert res["device"]["window_s"] > 1.0


def test_wrong_items_served_are_refused(doc, device_rung, monkeypatch):
    """The timed path broken underneath: the retrieval facade returns
    each row's neighbours' ids, shifted by one."""
    from predictionio_tpu.retrieval import Retriever

    inner = Retriever.topk

    def shifted(self, queries, num, **kw):
        s, i, info = inner(self, queries, num, **kw)
        return s, (i + 1) % self.n_items, info

    monkeypatch.setattr(Retriever, "topk", shifted)
    res = _run(tiny_cell(doc, BULK))
    assert not res["correct"]
    c = res["compared"]["rank_gap"]
    assert c["value"] > c["limit"]


def test_a_sweep_that_returns_its_state_unchanged_is_refused(
        doc, monkeypatch):
    from predictionio_tpu.models import als

    def unchanged(inputs, config, **kw):
        return als.ALSModel(user_factors=inputs.uf0,
                            item_factors=inputs.itf0, rank=config.rank,
                            implicit=False)

    monkeypatch.setattr(als, "train_als_prepared", unchanged)
    res = _run(tiny_cell(doc, TRAIN))
    assert not res["correct"]
    c = res["compared"]["user_rows_mean"]
    assert c["value"] > c["limit"]


# -- the control: one precision step down must be refused -------------------

def test_serving_control_is_refused(doc):
    import jax.numpy as jnp

    from benchmark.builders import als_serving

    config = tiny_cell(doc, BULK).config
    for seed in (5, 6, 2 ** 31 + 7):
        sound = als_serving.control(config, seed, n=32,
                                    precision="highest")
        ok, _ = compare.verdict(sound, config["limits"] | {})
        assert ok, sound
        # The CPU's matmul has one precision, so the step down is taken
        # on the operands (on the chip: precision "high").
        low = als_serving.control(config, seed, n=32,
                                  operand_dtype=jnp.bfloat16)
        ok, compared = compare.verdict(
            low, {k: config["limits"][k] for k in low})
        assert not ok, compared


def test_training_control_is_refused(doc):
    from benchmark.builders import als_retrain

    config = tiny_cell(doc, TRAIN).config
    for seed in (5, 6, 2 ** 31 + 7):
        low = als_retrain.control(config, seed)
        ok, compared = compare.verdict(
            low, {k: config["limits"][k] for k in low})
        assert not ok, compared
