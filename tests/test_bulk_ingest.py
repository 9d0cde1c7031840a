"""Bulk ingest data plane (ISSUE 17): ``POST /batch/events.json`` —
NDJSON bodies, per-item status, client batch-token exactly-once,
write-path admission (429 + Retry-After), disk-pressure degradation, and
spill-replay of a partially-landed batch."""

import json
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import (
    AccessKey,
    App,
    StorageUnavailable,
    get_storage,
    reset_storage,
)
from predictionio_tpu.resilience import faults
from predictionio_tpu.server.event_server import EventServer, max_batch_size


def _stack(pio_home, **server_kw):
    storage = get_storage()
    app_id = storage.get_apps().insert(App(id=None, name="bulk"))
    storage.get_events().init(app_id)
    key = storage.get_access_keys().insert(AccessKey(key="", app_id=app_id))
    srv = EventServer(storage=storage, host="127.0.0.1", port=0, **server_kw)
    return srv, key, storage, app_id


def _post(srv, key, path, payload, params=None):
    p = {"accessKey": [key]}
    for k, v in (params or {}).items():
        p[k] = [v]
    body = payload if isinstance(payload, bytes) \
        else json.dumps(payload).encode()
    return srv.handle("POST", path, p, body)


def _http_post(url, body, ctype="application/json"):
    req = urllib.request.Request(
        url, data=body, method="POST", headers={"Content-Type": ctype})
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, dict(resp.headers), \
                json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        payload = e.read()
        return e.code, dict(e.headers), \
            (json.loads(payload) if payload else None)


def _ev(i, name="view"):
    return {"event": name, "entityType": "user", "entityId": f"u{i}",
            "targetEntityType": "item", "targetEntityId": f"i{i}"}


# --------------------------------------------------------------------------
# Batch bodies and per-item status
# --------------------------------------------------------------------------


def test_json_array_batch_per_item_status(pio_home):
    srv, key, storage, app_id = _stack(pio_home)
    try:
        status, results = _post(srv, key, "/batch/events.json",
                                [_ev(0), _ev(1), _ev(2)])
        assert status == 200
        assert [r["status"] for r in results] == [201, 201, 201]
        assert all(r["eventId"] for r in results)
        assert len(list(storage.get_events().find(app_id))) == 3
    finally:
        srv.stop()


def test_ndjson_batch_malformed_line_never_fails_cohort(pio_home):
    """One torn/garbage NDJSON line answers ITS OWN 400; every other
    line still lands 201 — per-item isolation is the whole point of the
    per-line framing."""
    srv, key, storage, app_id = _stack(pio_home)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        nd = "\n".join([json.dumps(_ev(0)), "{not json", json.dumps(_ev(1)),
                        "", json.dumps(_ev(2))])
        status, _, results = _http_post(
            f"{base}/batch/events.json?accessKey={key}", nd.encode(),
            ctype="application/x-ndjson")
        assert status == 200
        assert [r["status"] for r in results] == [201, 400, 201, 201]
        assert "line 2" in results[1]["message"]
        assert len(list(storage.get_events().find(app_id))) == 3
    finally:
        srv.stop()


def test_ndjson_sniffed_without_content_type(pio_home):
    # first non-space byte != "[" → NDJSON even under a generic type
    srv, key, storage, app_id = _stack(pio_home)
    try:
        nd = json.dumps(_ev(0)) + "\n" + json.dumps(_ev(1))
        status, results = _post(srv, key, "/batch/events.json", nd.encode())
        assert status == 200
        assert [r["status"] for r in results] == [201, 201]
    finally:
        srv.stop()


def test_invalid_item_isolated_valid_cohort_lands(pio_home):
    srv, key, storage, app_id = _stack(pio_home)
    try:
        batch = [_ev(0), {"entityType": "user", "entityId": "nope"}, _ev(1)]
        status, results = _post(srv, key, "/batch/events.json", batch)
        assert status == 200
        assert [r["status"] for r in results] == [201, 400, 201]
        assert len(list(storage.get_events().find(app_id))) == 2
    finally:
        srv.stop()


def test_batch_cap_enforced(pio_home, monkeypatch):
    monkeypatch.setenv("PIO_MAX_BATCH_SIZE", "3")
    assert max_batch_size() == 3
    srv, key, *_ = _stack(pio_home)
    try:
        status, payload = _post(srv, key, "/batch/events.json",
                                [_ev(i) for i in range(4)])
        assert status == 400 and "limit of 3" in payload["message"]
    finally:
        srv.stop()


# --------------------------------------------------------------------------
# Client batch token: exactly-once across retries
# --------------------------------------------------------------------------


def test_batch_token_retry_dedups_row_by_row(pio_home):
    """A client retry with the SAME batchToken (reply lost) re-derives
    the same sub-tokens → same event ids → zero duplicates."""
    srv, key, storage, app_id = _stack(pio_home)
    try:
        batch = [_ev(0), _ev(1), _ev(2)]
        s1, r1 = _post(srv, key, "/batch/events.json", batch,
                       params={"batchToken": "client-tok-1"})
        s2, r2 = _post(srv, key, "/batch/events.json", batch,
                       params={"batchToken": "client-tok-1"})
        assert s1 == s2 == 200
        assert [r["eventId"] for r in r1] == [r["eventId"] for r in r2]
        assert len(list(storage.get_events().find(app_id))) == 3
    finally:
        srv.stop()


_KILL9_BATCHES, _KILL9_PER = 300, 8
_KILL9_LOADER = """
import sys
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import get_storage
ev = get_storage().get_events()
app_id, batches, per = (int(a) for a in sys.argv[1:4])
for b in range(batches):
    evs = [Event(event='view', entity_type='user', entity_id=f'ku{b}_{j}',
                 target_entity_type='item', target_entity_id=f'ki{j}')
           for j in range(per)]
    ev.create_batch(evs, app_id, tokens=[f'kill{b}.{j}' for j in range(per)])
    print(b, flush=True)
"""


def test_kill9_mid_stream_then_token_replay_loses_and_doubles_nothing(
        pio_home, kill9_after):
    """A REAL ``kill -9`` of a bulk loader between two of its batches,
    then every batch re-sent with its tokens (the crashed one included):
    the ids the tokens derive are the dedup keys, so the replay lands
    exactly the missing rows."""
    storage = get_storage()
    app_id = storage.get_apps().insert(App(id=None, name="kill9"))
    storage.get_events().init(app_id)
    reset_storage()  # the child owns the store until it dies
    seen = kill9_after(_KILL9_LOADER, (app_id, _KILL9_BATCHES, _KILL9_PER),
                       lambda b: b >= 10)  # provably mid-stream
    assert 10 <= seen < _KILL9_BATCHES - 1
    events = get_storage().get_events()
    landed = sum(1 for _ in events.find(app_id))
    assert (seen + 1) * _KILL9_PER <= landed < _KILL9_BATCHES * _KILL9_PER
    for b in range(_KILL9_BATCHES):
        events.create_batch(
            [Event(event="view", entity_type="user", entity_id=f"ku{b}_{j}",
                   target_entity_type="item", target_entity_id=f"ki{j}")
             for j in range(_KILL9_PER)],
            app_id, tokens=[f"kill{b}.{j}" for j in range(_KILL9_PER)])
    rows = [e.entity_id for e in events.find(app_id)]
    assert len(rows) == len(set(rows)) == _KILL9_BATCHES * _KILL9_PER


def test_bad_batch_token_rejected(pio_home):
    srv, key, *_ = _stack(pio_home)
    try:
        status, payload = _post(srv, key, "/batch/events.json", [_ev(0)],
                                params={"batchToken": "bad token!"})
        assert status == 400 and "batchToken" in payload["message"]
        status, _ = _post(srv, key, "/batch/events.json", [_ev(0)],
                          params={"batchToken": "x" * 121})
        assert status == 400
    finally:
        srv.stop()


def test_spill_replay_of_partially_landed_batch_exactly_once(pio_home):
    """The crash-consistency core: storage 'fails' a batch AFTER
    committing part of it (lost reply).  The spill record carries the
    per-item sub-tokens, so replay re-issues the identical create_batch
    and the already-committed rows dedup away — zero lost, zero
    duplicated."""
    srv, key, storage, app_id = _stack(pio_home, replay_interval_s=3600,
                                       replay_wait=lambda ev, t: ev.wait())
    try:
        events_repo = storage.get_events()
        real = type(events_repo).create_batch
        calls = {"n": 0}

        def flaky(self, evs, app_id_, channel_id=None, tokens=None):
            calls["n"] += 1
            if calls["n"] == 1:
                # commit the FIRST HALF, then "crash" before replying
                real(self, evs[: len(evs) // 2], app_id_, channel_id,
                     tokens=list(tokens)[: len(evs) // 2]
                     if tokens else None)
                raise StorageUnavailable("crashed mid-batch")
            return real(self, evs, app_id_, channel_id, tokens=tokens)

        import unittest.mock as mock

        with mock.patch.object(type(events_repo), "create_batch", flaky):
            status, results = _post(srv, key, "/batch/events.json",
                                    [_ev(i) for i in range(4)],
                                    params={"batchToken": "crashy"})
            assert status == 200
            assert [r["status"] for r in results] == [202] * 4
            assert srv.spill is not None and srv.spill.depth() == 4
            # half landed before the "crash"
            assert len(list(events_repo.find(app_id))) == 2
            assert srv._replay.drain_once() == 4
        landed = list(events_repo.find(app_id))
        assert len(landed) == 4, "replay must fill ONLY the missing rows"
        assert {e.entity_id for e in landed} == {f"u{i}" for i in range(4)}
    finally:
        srv.stop()


# --------------------------------------------------------------------------
# Write-path admission + disk pressure
# --------------------------------------------------------------------------


def test_saturated_plane_answers_429_with_retry_after(pio_home, monkeypatch):
    monkeypatch.setenv("PIO_INGEST_QUEUE_BUDGET", "2")
    srv, key, storage, app_id = _stack(pio_home)
    srv.start()
    try:
        assert srv.ingest_budget == 2
        base = f"http://127.0.0.1:{srv.port}"
        body = json.dumps([_ev(i) for i in range(5)]).encode()
        status, headers, payload = _http_post(
            f"{base}/batch/events.json?accessKey={key}", body)
        assert status == 429
        assert "Retry-After" in headers
        assert float(headers["Retry-After"]) > 0
        assert "PIO_INGEST_QUEUE_BUDGET" in payload["message"]
        # nothing landed, nothing leaked: inflight back to 0 and a batch
        # UNDER budget still goes through
        assert srv._inflight == 0
        status, _, results = _http_post(
            f"{base}/batch/events.json?accessKey={key}",
            json.dumps([_ev(0)]).encode())
        assert status == 200 and results[0]["status"] == 201
    finally:
        srv.stop()


def test_single_event_admission_429(pio_home, monkeypatch):
    """The budget is shared with the spill backlog: a deep journal
    starves single-row admission too (backpressure reaches every write
    entry point)."""
    monkeypatch.setenv("PIO_INGEST_QUEUE_BUDGET", "3")
    srv, key, *_ = _stack(pio_home, replay_interval_s=3600,
                          replay_wait=lambda ev, t: ev.wait())
    try:
        faults.install("storage.create:error:1.0")
        for i in range(3):  # fill the journal to the budget
            status, payload = _post(srv, key, "/events.json", _ev(i))
            assert status == 202
        status, payload = _post(srv, key, "/events.json", _ev(9))
        assert status == 429
        assert "retry later" in payload["message"]
    finally:
        faults.clear()
        srv.stop()


def test_disk_pressure_degrades_ready_not_ingest(pio_home, monkeypatch):
    """PIO_DISK_MIN_FREE_BYTES above the disk's free space: segment tee
    flips off and /ready says degraded — but the PRIMARY ingest path
    keeps answering 201 (segments are derived data)."""
    monkeypatch.setenv("PIO_DISK_MIN_FREE_BYTES", str(1 << 60))
    srv, key, storage, app_id = _stack(pio_home)
    try:
        assert srv.segments is not None
        status, r = _post(srv, key, "/events.json", _ev(0))
        assert status == 201  # ingest unaffected
        status, ready = srv.handle("GET", "/ready", {}, b"")
        assert status == 200  # still routable — only coverage stopped
        assert ready["status"] == "degraded"
        assert ready["diskDegraded"] is True
    finally:
        srv.stop()


def test_ready_reports_segment_counts(pio_home):
    srv, key, storage, app_id = _stack(pio_home)
    try:
        _post(srv, key, "/batch/events.json", [_ev(i) for i in range(3)])
        assert srv.segments is not None
        srv.segments.seal_all()
        status, ready = srv.handle("GET", "/ready", {}, b"")
        assert status == 200 and ready["status"] == "ready"
        assert ready["segmentDirs"] == 1
        assert ready["segmentCount"] == 1
        assert ready["ingestBudget"] == 0 and ready["ingestInflight"] == 0
    finally:
        srv.stop()


def test_ingest_faults_seam_drillable(pio_home):
    """`ingest.*` PIO_FAULTS points: admission and the batch fold are
    drill-able without monkeypatching server internals."""
    srv, key, storage, app_id = _stack(pio_home, spill_dir="off")
    try:
        faults.install("ingest.batch:error:1.0")
        status, results = _post(srv, key, "/batch/events.json", [_ev(0)])
        assert status == 503  # ConnectionError → availability, not a bug
        faults.clear()
        faults.install("ingest.admit:error:1.0")
        status, _ = _post(srv, key, "/events.json", _ev(1))
        assert status == 503
        faults.clear()
        status, results = _post(srv, key, "/batch/events.json", [_ev(2)])
        assert status == 200 and results[0]["status"] == 201
    finally:
        faults.clear()
        srv.stop()
