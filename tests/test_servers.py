"""Event Server + Engine Server over real HTTP (reference §3.2/§3.3 parity)."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.controller import EngineVariant, RuntimeContext
from predictionio_tpu.data.storage import AccessKey, App, Channel, get_storage
from predictionio_tpu.server import EngineServer, EventServer
from predictionio_tpu.templates.recommendation import engine
from predictionio_tpu.workflow.core_workflow import run_train


def _req(method, url, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        payload = e.read()
        return e.code, json.loads(payload) if payload else None


@pytest.fixture()
def event_server(pio_home):
    storage = get_storage()
    app_id = storage.get_apps().insert(App(id=None, name="app1"))
    storage.get_events().init(app_id)
    key = storage.get_access_keys().insert(AccessKey(key="", app_id=app_id))
    srv = EventServer(storage=storage, host="127.0.0.1", port=0)
    srv.start()
    yield srv, key, storage, app_id
    srv.stop()


class TestEventServer:
    def test_alive(self, event_server):
        srv, *_ = event_server
        status, body = _req("GET", f"http://127.0.0.1:{srv.port}/")
        assert (status, body) == (200, {"status": "alive"})

    def test_ingest_and_query_roundtrip(self, event_server):
        srv, key, *_ = event_server
        base = f"http://127.0.0.1:{srv.port}"
        ev = {"event": "rate", "entityType": "user", "entityId": "u1",
              "targetEntityType": "item", "targetEntityId": "i1",
              "properties": {"rating": 4.5},
              "eventTime": "2026-01-02T03:04:05.000Z"}
        status, body = _req("POST", f"{base}/events.json?accessKey={key}", ev)
        assert status == 201 and body["eventId"]
        event_id = body["eventId"]

        status, one = _req("GET", f"{base}/events/{event_id}.json?accessKey={key}")
        assert status == 200
        assert one["event"] == "rate"
        assert one["properties"]["rating"] == 4.5
        assert one["eventTime"].startswith("2026-01-02T03:04:05")

        status, found = _req(
            "GET", f"{base}/events.json?accessKey={key}&entityId=u1")
        assert status == 200 and len(found) == 1

        status, _ = _req("DELETE", f"{base}/events/{event_id}.json?accessKey={key}")
        assert status == 200
        status, _ = _req("GET", f"{base}/events/{event_id}.json?accessKey={key}")
        assert status == 404

    def test_batch_ingest(self, event_server):
        srv, key, *_ = event_server
        base = f"http://127.0.0.1:{srv.port}"
        batch = [
            {"event": "buy", "entityType": "user", "entityId": f"u{i}",
             "targetEntityType": "item", "targetEntityId": "i1"}
            for i in range(3)
        ] + [{"entityType": "user", "entityId": "broken"}]  # missing "event"
        status, results = _req("POST", f"{base}/batch/events.json?accessKey={key}", batch)
        assert status == 200
        assert [r["status"] for r in results] == [201, 201, 201, 400]

    def test_batch_size_limit(self, event_server):
        srv, key, *_ = event_server
        base = f"http://127.0.0.1:{srv.port}"
        batch = [{"event": "e", "entityType": "t", "entityId": "x"}] * 51
        status, _ = _req("POST", f"{base}/batch/events.json?accessKey={key}", batch)
        assert status == 400

    def test_auth_rejected(self, event_server):
        srv, *_ = event_server
        base = f"http://127.0.0.1:{srv.port}"
        ev = {"event": "rate", "entityType": "user", "entityId": "u1"}
        assert _req("POST", f"{base}/events.json?accessKey=WRONG", ev)[0] == 401
        assert _req("POST", f"{base}/events.json", ev)[0] == 401

    def test_event_allowlist(self, event_server):
        srv, _, storage, app_id = event_server
        limited = storage.get_access_keys().insert(
            AccessKey(key="", app_id=app_id, events=("view",)))
        base = f"http://127.0.0.1:{srv.port}"
        ok = {"event": "view", "entityType": "user", "entityId": "u1"}
        bad = {"event": "rate", "entityType": "user", "entityId": "u1"}
        assert _req("POST", f"{base}/events.json?accessKey={limited}", ok)[0] == 201
        assert _req("POST", f"{base}/events.json?accessKey={limited}", bad)[0] == 403

    def test_channel_ingest(self, event_server):
        srv, key, storage, app_id = event_server
        chan_id = storage.get_channels().insert(
            Channel(id=None, name="mobile", app_id=app_id))
        storage.get_events().init(app_id, chan_id)  # as `pio app channel-new` does
        base = f"http://127.0.0.1:{srv.port}"
        ev = {"event": "view", "entityType": "user", "entityId": "u9"}
        s, _ = _req("POST", f"{base}/events.json?accessKey={key}&channel=mobile", ev)
        assert s == 201
        # Default channel read does NOT see it (empty match = 200 []);
        # channel read does.
        s, none = _req("GET", f"{base}/events.json?accessKey={key}&entityId=u9")
        assert s == 200 and none == []
        s, found = _req(
            "GET", f"{base}/events.json?accessKey={key}&entityId=u9&channel=mobile")
        assert s == 200 and len(found) == 1
        s, _ = _req("POST", f"{base}/events.json?accessKey={key}&channel=nope", ev)
        assert s == 400

    def test_stats_and_metrics(self, event_server):
        srv, key, *_ = event_server
        base = f"http://127.0.0.1:{srv.port}"
        ev = {"event": "view", "entityType": "user", "entityId": "u1"}
        _req("POST", f"{base}/events.json?accessKey={key}", ev)
        status, stats = _req("GET", f"{base}/stats.json")
        assert status == 200 and stats["eventCounts"].get("view") == 1
        req = urllib.request.Request(f"{base}/metrics")
        with urllib.request.urlopen(req, timeout=10) as resp:
            text = resp.read().decode()
            assert resp.headers["Content-Type"].startswith("text/plain")
        assert "pio_event_requests_total" in text
        # the exposition must be valid Prometheus text (strict parser)
        from tests.test_obs import parse_prometheus

        samples = parse_prometheus(text)
        assert ({"status": "201"}, 1.0) in samples["pio_event_requests_total"]
        assert ({"event": "view"}, 1.0) in samples["pio_event_events_total"]
        assert samples["pio_event_request_latency_ms_count"][0][1] >= 1

    def test_request_id_round_trips(self, event_server):
        srv, *_ = event_server
        base = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(f"{base}/",
                                     headers={"X-Request-ID": "client-id-42"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.headers["X-Request-ID"] == "client-id-42"
        # absent → server generates one and still returns it
        with urllib.request.urlopen(f"{base}/", timeout=10) as resp:
            gen = resp.headers["X-Request-ID"]
        assert gen and len(gen) == 32 and gen != "client-id-42"
        # hostile ids are sanitized, not echoed raw
        req = urllib.request.Request(
            f"{base}/", headers={"X-Request-ID": "a\tb c"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.headers["X-Request-ID"] == "abc"

    def test_traces_json_records_requests(self, event_server):
        import time

        srv, key, *_ = event_server
        base = f"http://127.0.0.1:{srv.port}"
        ev = {"event": "view", "entityType": "user", "entityId": "u1"}
        _req("POST", f"{base}/events.json?accessKey={key}&", ev)
        # per-request traces require auth (unlike the aggregate /metrics)
        assert _req("GET", f"{base}/traces.json")[0] == 401
        # the trace is recorded just AFTER the response bytes go out
        posts = []
        for _ in range(50):
            status, body = _req("GET", f"{base}/traces.json?accessKey={key}")
            assert status == 200
            posts = [t for t in body["traces"]
                     if t["attrs"].get("path") == "/events.json"]
            if posts:
                break
            time.sleep(0.02)
        assert posts, "POST /events.json trace never reached the ring"
        t = posts[0]
        assert t["name"] == "http.request"
        assert t["attrs"]["server"] == "event"
        assert t["attrs"]["status"] == 201
        names = [s["name"] for s in t["spans"]]
        assert names == ["http.read", "http.handle", "http.respond"]


@pytest.fixture()
def deployed(pio_home):
    storage = get_storage()
    ctx = RuntimeContext.create(storage=storage)
    app_id = storage.get_apps().insert(App(id=None, name="testapp"))
    storage.get_events().init(app_id)
    from predictionio_tpu.data.event import DataMap, Event

    rng = np.random.default_rng(0)
    for u in range(10):
        for i in range(8):
            if i % 2 == u % 2 and rng.random() < 0.95:
                storage.get_events().insert(
                    Event(event="rate", entity_type="user", entity_id=f"u{u}",
                          target_entity_type="item", target_entity_id=f"i{i}",
                          properties=DataMap({"rating": 4.0})), app_id)
    variant = EngineVariant.from_dict({
        "engineFactory": "predictionio_tpu.templates.recommendation:engine",
        "datasource": {"params": {"appName": "testapp"}},
        "algorithms": [{"name": "als", "params": {"rank": 4, "numIterations": 5}}],
    })
    eng = engine()
    run_train(eng, variant, ctx)
    srv = EngineServer(eng, variant, storage, host="127.0.0.1", port=0)
    srv.start()
    yield srv, storage, ctx, eng, variant
    srv.stop()


class TestEngineServer:
    def test_status_page(self, deployed):
        srv, *_ = deployed
        status, body = _req("GET", f"http://127.0.0.1:{srv.port}/")
        assert status == 200
        assert body["status"] == "alive" and body["engineInstanceId"]

    def test_status_page_names_the_backend(self, deployed):
        srv, storage, *_ = deployed
        _, body = _req("GET", f"http://127.0.0.1:{srv.port}/")
        b = body["backend"]
        assert b["platform"] == "cpu" and b["pallas"] == "interpret"
        assert b["deviceCount"] >= 1 and "compileSeconds" in b
        # ...and the generation says what it was trained on
        inst = storage.get_engine_instances().get(body["engineInstanceId"])
        assert inst.env["platform"] == "cpu"
        assert inst.env["deviceCount"] == str(b["deviceCount"])

    def test_stop_answers_before_it_stops(self, deployed):
        """POST /stop used to start the shutdown from inside the handler,
        racing its own response: `pio deploy` could exit with the client
        still waiting for the 200."""
        srv, *_ = deployed
        thread = srv._thread
        status, body = _req("POST", f"http://127.0.0.1:{srv.port}/stop", {})
        assert status == 200 and body == {"status": "stopping"}
        thread.join(timeout=10)
        assert not thread.is_alive()

    def test_query(self, deployed):
        srv, *_ = deployed
        status, body = _req("POST", f"http://127.0.0.1:{srv.port}/queries.json",
                            {"user": "u0", "num": 3})
        assert status == 200
        assert len(body["itemScores"]) == 3
        items = [s["item"] for s in body["itemScores"]]
        assert all(int(i[1:]) % 2 == 0 for i in items)  # u0 is even-clique

    def test_query_binding_error(self, deployed):
        srv, *_ = deployed
        status, body = _req("POST", f"http://127.0.0.1:{srv.port}/queries.json",
                            {"nope": 1})
        assert status == 400

    def test_reload_picks_up_retrain(self, deployed):
        srv, storage, ctx, eng, variant = deployed
        old = srv._instance.id
        run_train(eng, variant, ctx)
        status, body = _req("POST", f"http://127.0.0.1:{srv.port}/reload")
        assert status == 200
        assert body["engineInstanceId"] != old

    def test_metrics_track_queries(self, deployed):
        srv, *_ = deployed
        _req("POST", f"http://127.0.0.1:{srv.port}/queries.json",
             {"user": "u0", "num": 2})
        req = urllib.request.Request(f"http://127.0.0.1:{srv.port}/metrics")
        with urllib.request.urlopen(req, timeout=10) as resp:
            text = resp.read().decode()
        assert "pio_query_requests_total 1" in text
        from tests.test_obs import parse_prometheus

        samples = parse_prometheus(text)
        assert samples["pio_query_latency_ms_count"][0][1] == 1
        # the registry is process-wide: training-phase series from the
        # fixture's run_train surface in the SERVING exposition too
        assert any(lb.get("phase") == "train.algorithm"
                   for lb, _ in samples.get("pio_train_phase_ms_count", []))

    def test_metrics_expose_runtime_introspection(self, deployed):
        """ISSUE 3 acceptance: a live engine server's /metrics carries
        the compile-tracking and device-memory instrument families."""
        srv, *_ = deployed
        req = urllib.request.Request(f"http://127.0.0.1:{srv.port}/metrics")
        with urllib.request.urlopen(req, timeout=10) as resp:
            text = resp.read().decode()
        assert "pio_xla_compile_total" in text
        assert "pio_device_mem_bytes" in text
        from tests.test_obs import parse_prometheus

        samples = parse_prometheus(text)
        # CPU backend has no allocator stats, but the live-array
        # fallback gives real series (the loaded model's arrays).
        assert any(lb.get("kind") == "live_bytes" and v > 0
                   for lb, v in samples.get("pio_device_mem_bytes", []))

    def test_timeline_endpoint(self, deployed):
        from predictionio_tpu.obs import get_timeline

        srv, *_ = deployed
        get_timeline().record("toy", host_wait_ms=1, h2d_ms=2,
                              device_wait_ms=3, device_step_ms=4,
                              examples=8)
        base = f"http://127.0.0.1:{srv.port}"
        status, body = _req("GET", f"{base}/timeline.json")
        assert status == 200 and body["steps"][0]["model"] == "toy"
        status, body = _req("GET",
                            f"{base}/timeline.json?format=summary&model=toy")
        assert status == 200
        assert body["models"]["toy"]["phase_ms"]["h2d"] == 2
        status, chrome = _req("GET", f"{base}/timeline.json?format=chrome")
        assert status == 200
        assert any(e["ph"] == "X" for e in chrome["traceEvents"])

    def test_stats_json_view(self, deployed):
        srv, *_ = deployed
        _req("POST", f"http://127.0.0.1:{srv.port}/queries.json",
             {"user": "u0", "num": 2})
        status, stats = _req("GET",
                             f"http://127.0.0.1:{srv.port}/stats.json")
        assert status == 200
        assert stats["requestCount"] == 1 and stats["errorCount"] == 0
        assert stats["latencyMs"]["p50"] >= 0

    def test_query_trace_covers_wall_time(self, deployed, tmp_path,
                                          monkeypatch):
        """Acceptance: a served query's trace decomposes into spans with
        no large unattributed gap, and exports as JSONL.  Judged on the
        spans' own numbers, not on wall-clock ratios a busy machine
        moves."""
        import json as _json
        import time

        trace_file = tmp_path / "traces.jsonl"
        monkeypatch.setenv("PIO_TRACE_FILE", str(trace_file))
        srv, *_ = deployed
        # Coverage is about the DISPATCH path's spans: cache hits on the
        # repeated query answer in sub-millisecond walls where fixed
        # inter-span gaps dominate the ratio, so bypass the cache here.
        srv.result_cache.set_enabled(False)
        n_queries = 12
        for _ in range(n_queries):
            status, _ = _req("POST",
                             f"http://127.0.0.1:{srv.port}/queries.json",
                             {"user": "u0", "num": 3})
            assert status == 200
        docs = []
        for _ in range(50):
            if trace_file.exists():
                docs = [_json.loads(line) for line in
                        trace_file.read_text().strip().splitlines()]
                if sum(d["attrs"].get("path") == "/queries.json"
                       for d in docs) >= n_queries:
                    break
            time.sleep(0.02)
        traces = [d for d in docs
                  if d["attrs"].get("path") == "/queries.json"]
        assert traces, "no /queries.json trace reached PIO_TRACE_FILE"
        t = traces[-1]
        assert t["attrs"]["server"] == "engine"
        # The span tree covers the request: read, handle, respond (and the
        # zero-length waterfall event) in that order, every time.
        assert {tuple(s["name"] for s in d["spans"]) for d in traces} == {
            ("http.read", "http.handle", "http.respond", "waterfall")}
        # No large unattributed gap, judged from the spans' own start and
        # duration with the scheduler taken out: a code section that no
        # span covers opens the SAME gap (root start -> first child,
        # child -> next child, last child -> root end) in every request,
        # while a lost timeslice (busy neighbours, a GIL hand-off to the
        # client thread) widens one gap of one request.  So take each
        # gap's smallest reading over the requests and hold their sum
        # against the fastest request: fixed inter-span code is ~150 us
        # of a ~1.4 ms request on a quiet CPU, and one more millisecond
        # that no span covers fails this whatever the machine is doing.
        def gaps_ms(d):
            edges = [(d["startS"], d["startS"])]
            edges += [(s["startS"], s["startS"] + s["durationMs"] / 1e3)
                      for s in d["spans"]]
            end = d["startS"] + d["durationMs"] / 1e3
            edges.append((end, end))
            return [max(b[0] - a[1], 0.0) * 1e3
                    for a, b in zip(edges, edges[1:])]

        systemic_ms = sum(map(min, zip(*map(gaps_ms, traces))))
        fastest_ms = min(d["durationMs"] for d in traces)
        assert systemic_ms <= 0.2 * fastest_ms, (
            f"{systemic_ms:.3f} ms of a {fastest_ms:.3f} ms request is "
            f"covered by no span: {[gaps_ms(d) for d in traces]}")
        # ISSUE 6: the predict itself runs on the batcher thread; the
        # request's span tree carries the batcher.dispatch JOIN event,
        # and the dispatch is its own root trace keyed by batch_id.
        handle = next(s for s in t["spans"] if s["name"] == "http.handle")
        joins = [s for s in handle.get("spans", [])
                 if s["name"] == "batcher.dispatch"]
        assert joins, "request span lost its batcher.dispatch join event"
        ev = joins[0]["attrs"]
        assert ev["batch_size"] >= 1 and ev["generation"] >= 1
        dispatches = [d for d in docs if d.get("name") == "batcher.dispatch"
                      and d["attrs"].get("batch_id") == ev["batch_id"]]
        assert dispatches, "no batcher.dispatch root trace for the batch"
        assert dispatches[0]["attrs"]["model"] == "default"

    @pytest.mark.parametrize("rung, device_stages", [
        ("device", 1), ("host", 0)])
    def test_query_batch_counts_each_dispatch_stage_once(
            self, deployed, monkeypatch, rung, device_stages):
        """``pio batchpredict``'s call opens no trace: the stages of a
        dispatch reach ``pio_dispatch_stage_ms`` all the same, each once
        per call (h2d/launch/wait only where a device rung answers)."""
        from predictionio_tpu.obs import current_span, get_registry

        monkeypatch.setenv("PIO_RETRIEVAL_RUNG", rung)
        srv, *_ = deployed
        queries = [{"user": f"u{u}", "num": 3} for u in range(4)]
        srv.query_batch(queries)          # compile, load host factors
        stages = get_registry().get("pio_dispatch_stage_ms")
        expect = {"bind": 1, "supplement": 1, "lookup": 1, "assemble": 1,
                  "serve": 1, "h2d": device_stages,
                  "launch": device_stages, "wait": device_stages}
        before = {s: stages.count(stage=s) for s in expect}
        assert current_span() is None
        out = srv.query_batch(queries)
        assert len(out) == 4 and all(r["itemScores"] for r in out)
        assert {s: stages.count(stage=s) - before[s]
                for s in expect} == expect

    def test_engine_answers_the_profile_routes(self, deployed, tmp_path):
        """Only the process that holds the chip can trace it, so the
        engine server answers ``pio profile --url``'s three routes
        itself: arm, status, artifact."""
        from predictionio_tpu.obs import profiler as profiler_mod

        started = []
        session = profiler_mod.ProfilerSession(
            start_fn=started.append, stop_fn=lambda: None)
        prev = profiler_mod.set_profiler(session)
        srv, *_ = deployed
        base = f"http://127.0.0.1:{srv.port}"
        out = tmp_path / "prof"
        out.mkdir()
        (out / "host.xplane.pb").write_bytes(b"capture")
        try:
            status, body = _req(
                "POST", f"{base}/admin/profile?duration_ms=60000&out={out}")
            assert status == 200 and body["status"] == "profiling"
            assert started == [str(out)]
            status, body = _req("GET", f"{base}/admin/profile")
            assert status == 200 and body["active"] is True
            assert session.stop() == str(out)
            with urllib.request.urlopen(f"{base}/admin/profile/artifact",
                                        timeout=10) as resp:
                assert resp.headers["Content-Type"] == "application/gzip"
                assert "prof.tar.gz" in resp.headers["Content-Disposition"]
                assert resp.read()[:2] == b"\x1f\x8b"
        finally:
            session.stop()
            profiler_mod.set_profiler(prev)

    def test_engine_request_id_round_trips(self, deployed):
        srv, *_ = deployed
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/",
            headers={"X-Request-ID": "q-7"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.headers["X-Request-ID"] == "q-7"


def test_dc_to_json_matches_asdict_on_wire():
    """The serving fast converter must keep dataclasses.asdict's JSON
    contract for nested dataclasses in lists, tuples and dict values
    (tuples become JSON arrays either way)."""
    import dataclasses
    import json
    from typing import Any, Dict, List, Tuple

    from predictionio_tpu.server.engine_server import _dc_to_json

    @dataclasses.dataclass
    class Inner:
        a: int

    @dataclasses.dataclass
    class Outer:
        xs: Tuple[Inner, ...]
        ys: List[Inner]
        d: Dict[str, Inner]
        n: Inner
        s: str

    o = Outer(xs=(Inner(1), Inner(2)), ys=[Inner(5)], d={"k": Inner(3)},
              n=Inner(4), s="z")
    assert json.dumps(_dc_to_json(o), sort_keys=True) == \
        json.dumps(dataclasses.asdict(o), sort_keys=True)

    # A value with a JSON form of its own (a template's item columns) is
    # asked for it wherever it sits: as a field, in a list, as a dict
    # value.  The wire is the list of dataclasses it stands for.
    from predictionio_tpu.controller import ItemScoreColumns

    @dataclasses.dataclass
    class Hit:
        item: str
        score: float

    @dataclasses.dataclass
    class Shelf:
        top: Any
        rows: List[Any]
        by_name: Dict[str, Any]

    def shelf(hits):
        return Shelf(top=hits(), rows=[hits(), hits()],
                     by_name={"k": hits()})

    columns = shelf(lambda: ItemScoreColumns(["a", "b"], [1.0, 0.5], Hit))
    plain = shelf(lambda: [Hit("a", 1.0), Hit("b", 0.5)])
    assert json.dumps(_dc_to_json(columns)) == \
        json.dumps(dataclasses.asdict(plain))


class TestServerPluginSeam:
    """SURVEY §5.1: EngineServerPlugin/EventServerPlugin equivalents —
    env-discovered request instrumentation invoked per request with
    (route, status, ms), able to inject response headers, active over
    the python HTTP transport (native covered in test_native.py)."""

    def test_event_server_plugin_counts_and_injects(self, pio_home,
                                                    monkeypatch):
        import urllib.request

        import tests.plugin_fixture as pf
        from predictionio_tpu.data.storage import get_storage
        from predictionio_tpu.data.storage.base import AccessKey, App
        from predictionio_tpu.server.event_server import EventServer

        monkeypatch.setenv("PIO_EVENTSERVER_PLUGINS",
                           "tests.plugin_fixture:make_plugin")
        storage = get_storage()
        app_id = storage.get_apps().insert(App(id=None, name="plugapp"))
        storage.get_events().init(app_id)
        key = storage.get_access_keys().insert(AccessKey.generate(app_id))
        srv = EventServer(storage, host="127.0.0.1", port=0)
        plugin = pf.LAST
        assert plugin is not None and plugin.started_with is srv
        srv.start(block=False)
        try:
            ev = {"event": "rate", "entityType": "user", "entityId": "u1"}
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/events.json?accessKey={key}",
                data=json.dumps(ev).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as r:
                assert r.status == 201
                assert r.headers["X-Plugin-Count"] == "1"
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/", timeout=10) as r:
                assert r.headers["X-Plugin-Count"] == "2"
            routes = [r[0] for r in plugin.requests]
            assert routes == ["POST /events.json", "GET /"]
            assert all(isinstance(r[2], float) for r in plugin.requests)
        finally:
            srv.stop()
        # stop() runs the plugin's shutdown hook (lifecycle contract)
        assert plugin.started_with is None

    def test_metrics_plugin_matches_builtin_counters(self, pio_home):
        """The MetricsPlugin exemplar and the built-in instrumentation
        feed the SAME registry and must agree on totals — proving the
        plugin path reports identically to the built-in path."""
        from predictionio_tpu.data.storage import get_storage
        from predictionio_tpu.obs import get_registry
        from predictionio_tpu.server.event_server import EventServer
        from predictionio_tpu.server.plugins import (
            MetricsPlugin, PluginManager,
        )

        srv = EventServer(get_storage(), host="127.0.0.1", port=0,
                          plugins=PluginManager([MetricsPlugin()]))
        srv.start(block=False)
        try:
            for _ in range(3):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/", timeout=10)
            _req("GET", f"http://127.0.0.1:{srv.port}/nope.json")
        finally:
            srv.stop()
        reg = get_registry()
        builtin = reg.get("pio_event_requests_total")
        plugin = reg.get("pio_plugin_requests_total")
        assert builtin.total() == plugin.total() == 4
        assert plugin.value(route="GET /", status="200") == 3
        assert plugin.value(route="GET /nope.json", status="401") == 1
        # one exposition carries both
        from tests.test_obs import parse_prometheus

        samples = parse_prometheus(reg.render())
        assert "pio_plugin_requests_total" in samples
        assert "pio_event_requests_total" in samples

    def test_plugin_header_with_a_name_that_is_no_token_is_dropped(
            self, caplog):
        """CR/LF were blanked already; a name that is empty or holds a
        ':' or a space still came out as a malformed header line, on the
        Python path (``send_header``) and the native one
        (``header_block``)."""
        from predictionio_tpu.server.plugins import (
            PluginManager, ServerPlugin,
        )

        class Sloppy(ServerPlugin):
            name = "sloppy"

            def on_request(self, route, status, ms):
                return {"": "empty", "X-A: b": "colon", "X B": "space",
                        "X-Bad\r\nX-Evil": "crlf", "X-Good_1.a": "kept\r\n"}

        pm = PluginManager([Sloppy()])
        with caplog.at_level("WARNING",
                             logger="predictionio_tpu.server.plugins"):
            assert pm.on_request("GET /", 200, 1.0) == {"X-Good_1.a": "kept  "}
        dropped = [r.getMessage() for r in caplog.records
                   if "invalid name" in r.getMessage()]
        assert pm.header_block("GET /", 200, 1.0) == "X-Good_1.a: kept  \r\n"
        assert len(dropped) == 4 and all("sloppy" in m for m in dropped)
        assert any("'X-A: b'" in m for m in dropped)

    def test_plugin_failure_does_not_break_requests(self, pio_home,
                                                    monkeypatch):
        import urllib.request

        from predictionio_tpu.data.storage import get_storage
        from predictionio_tpu.server.event_server import EventServer
        from predictionio_tpu.server.plugins import (
            PluginManager, ServerPlugin,
        )

        class Exploding(ServerPlugin):
            def on_request(self, route, status, ms):
                raise RuntimeError("boom")

        class Injecting(ServerPlugin):
            def on_request(self, route, status, ms):
                # CRLF in values must not smuggle extra headers
                return {"X-Safe": "a\r\nX-Evil: yes"}

        srv = EventServer(get_storage(), host="127.0.0.1", port=0,
                          plugins=PluginManager([Exploding(), Injecting()]))
        srv.start(block=False)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/", timeout=10) as r:
                assert r.status == 200
                assert "X-Evil" not in r.headers
                assert r.headers["X-Safe"] == "a  X-Evil: yes"
        finally:
            srv.stop()
