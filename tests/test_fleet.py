"""ISSUE 15 — fleet-scale serving plane.

Three tentpole pieces under test:

- **Shared spill backplane**: the storage-backed queue's lease/ack
  contract (pinned identical across sqlite / memory / pioserver), the
  drainer-crash chaos spine (a peer replays an expired lease with zero
  lost and zero duplicated events, by idempotency token), the PIO_FAULTS
  ``spillq.*`` seams, and the event server's shared-first /
  local-journal-fallback spill routing.
- **Rollout controller**: wave parsing, live multi-server wave
  promotion, halt-on-fleet-burn with WHOLE-fleet rollback, dead-instance
  and 409 skip-and-report, and deterministic resume/unwind from the
  journaled wave state.
- **Durable fold-in cache**: instance B answers a visitor instance A
  solved, without touching the event store; plus the item-side fold-in
  satellite and the eval-sweep preemption-resume satellite.

Fake clocks drive every lease-expiry and bake-window path — no wall
sleeps anywhere but the live-HTTP server round-trips themselves.
"""

import json
import os
import pickle
import threading
from urllib.request import Request, urlopen

import numpy as np
import pytest

from predictionio_tpu.controller import EngineVariant, RuntimeContext
from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.json_support import event_from_json
from predictionio_tpu.data.storage import App, get_storage
from predictionio_tpu.resilience import faults, idempotency_key
from predictionio_tpu.resilience.shared_spill import (
    LeaseDrainer,
    SharedSpillQueue,
    resolve_spill_backend,
)
from predictionio_tpu.workflow.core_workflow import load_models, run_train


# ==========================================================================
# Shared queue contract — identical semantics across backends
# ==========================================================================


def _sqlite_queues(tmp_path):
    from predictionio_tpu.data.storage.sqlite import SQLiteClient

    return SQLiteClient(str(tmp_path / "q.db")).spill_queues()


def _memory_queues(tmp_path):
    from predictionio_tpu.data.storage.memory import MemorySpillQueues

    return MemorySpillQueues()


@pytest.fixture(params=["sqlite", "memory"])
def queues(request, tmp_path, pio_home):
    return {"sqlite": _sqlite_queues,
            "memory": _memory_queues}[request.param](tmp_path)


class TestQueueContract:
    def test_enqueue_is_token_idempotent(self, queues):
        a = queues.enqueue("events", {"token": "t1"}, token="t1",
                           events=2, now_s=10.0)
        b = queues.enqueue("events", {"token": "t1"}, token="t1",
                           events=2, now_s=11.0)
        assert a == b
        st = queues.stats("events", now_s=12.0)
        assert st["pending"] == 1 and st["pendingEvents"] == 2

    def test_lease_is_exclusive_until_expiry(self, queues):
        queues.enqueue("events", {"token": "t1"}, token="t1", now_s=10.0)
        got = queues.lease("events", "A", 5, ttl_s=30, now_s=11.0)
        assert len(got) == 1 and got[0].attempts == 1
        # B cannot claim under A's unexpired lease
        assert queues.lease("events", "B", 5, ttl_s=30, now_s=20.0) == []
        # past expiry B takes over, bumping attempts
        stolen = queues.lease("events", "B", 5, ttl_s=30, now_s=42.0)
        assert len(stolen) == 1 and stolen[0].attempts == 2
        # A's ack now reports the lost lease instead of deleting B's work
        assert queues.ack("events", [got[0].id], "A") == 0
        assert queues.ack("events", [stolen[0].id], "B") == 1
        assert queues.stats("events", now_s=43.0)["pending"] == 0

    def test_nack_releases_immediately(self, queues):
        queues.enqueue("events", {"token": "t1"}, token="t1", now_s=1.0)
        got = queues.lease("events", "A", 5, ttl_s=1000, now_s=2.0)
        assert queues.nack("events", [got[0].id], "A") == 1
        # pending again without waiting out the (long) TTL
        assert len(queues.lease("events", "B", 5, ttl_s=10,
                                now_s=3.0)) == 1

    def test_dead_letter_and_requeue(self, queues):
        queues.enqueue("events", {"token": "t1"}, token="t1",
                       events=3, now_s=1.0)
        got = queues.lease("events", "A", 5, ttl_s=30, now_s=2.0)
        assert queues.dead_letter("events", got[0].id, "A", "poison")
        st = queues.stats("events", now_s=3.0)
        assert st["dead"] == 1 and st["deadEvents"] == 3
        assert queues.peek("events", state="dead")[0].reason == "poison"
        assert queues.requeue_dead("events") == 3
        st = queues.stats("events", now_s=4.0)
        assert st["pending"] == 1 and st["dead"] == 0

    def test_fifo_order_and_expired_stat(self, queues):
        for i in range(3):
            queues.enqueue("events", {"i": i}, token=f"t{i}",
                           now_s=float(i))
        got = queues.lease("events", "A", 2, ttl_s=5, now_s=10.0)
        assert [r.payload["i"] for r in got] == [0, 1]
        st = queues.stats("events", now_s=100.0)
        assert st["expired"] == 2 and st["pending"] == 1


class _HostedBackplane:
    """Minimal storage façade for StorageServer: events + spill queue +
    KV, all memory-backed (the server-side half of the chaos tests)."""

    def __init__(self):
        from predictionio_tpu.data.storage import memory as m

        self._events = m.MemoryEvents()
        self._queues = m.MemorySpillQueues()
        self._kv = m.MemoryKV()

    def get_events(self):
        return self._events

    def get_spill_queues(self):
        return self._queues

    def get_kv(self):
        return self._kv

    def __getattr__(self, name):
        if name.startswith("get_"):
            return lambda: None
        raise AttributeError(name)


@pytest.fixture()
def remote_backplane(pio_home):
    from predictionio_tpu.data.storage.remote import (
        RemoteClient,
        StorageServer,
    )

    hosted = _HostedBackplane()
    srv = StorageServer(hosted, host="127.0.0.1", port=0)
    srv.start()
    client = RemoteClient("127.0.0.1", srv.port)
    client.events().init(1)
    yield hosted, client
    client.close()
    srv.stop()


class TestQueueContractRemote:
    def test_lease_ack_round_trip_over_rpc(self, remote_backplane):
        _, client = remote_backplane
        q = client.spill_queues()
        q.enqueue("events", {"token": "t1", "events": [{"x": 1}]},
                  token="t1", events=1, now_s=5.0)
        got = q.lease("events", "A", 5, ttl_s=30, now_s=6.0)
        assert len(got) == 1 and got[0].payload["events"] == [{"x": 1}]
        assert q.ack("events", [got[0].id], "A") == 1
        assert q.stats("events", now_s=7.0)["pending"] == 0


# ==========================================================================
# Chaos spine: drainer crash mid-lease → peer replays exactly once
# ==========================================================================


def _record(i, n_events=1):
    evs = [{"event": "rate", "entityType": "user", "entityId": f"u{i}",
            "targetEntityType": "item", "targetEntityId": f"i{k}",
            "properties": {"rating": 4}} for k in range(n_events)]
    return {"token": f"tok{i}", "appId": 1, "channelId": None,
            "events": evs}


def _rpc_insert_fn(client):
    """The replay write, exactly as the event server issues it: the
    record's pinned token + the original event set, over RPC — the
    server-side dedup window is what turns redelivery into
    exactly-once."""
    repo = client.events()

    def insert(payload):
        evs = [event_from_json(e) for e in payload["events"]]
        with idempotency_key(payload["token"]):
            repo.insert_batch(evs, payload["appId"],
                              payload.get("channelId"))
    return insert


class _QueueView:
    """A SharedSpillQueue whose clock a test advances by hand.  The stub
    storage wraps the repo through the fault seam exactly like
    ``Storage.get_spill_queues`` does, so ``spillq.*`` rules fire."""

    def __init__(self, client, now=1000.0):
        from predictionio_tpu.resilience.faults import wrap_spill_queues

        class _S:
            def get_spill_queues(self_inner):
                return wrap_spill_queues(client.spill_queues())

        self.now = [now]
        self.q = SharedSpillQueue(_S(), clock=lambda: self.now[0])


class TestDrainerCrashChaos:
    def test_peer_replays_expired_lease_exactly_once(self,
                                                     remote_backplane):
        """THE acceptance e2e (1): drainer A crashes mid-lease after
        landing PART of its batch; B takes the expired lease over and
        replays everything — every event in the store exactly once,
        because B's re-inserts carry A's pinned tokens and the RPC dedup
        window answers them without re-executing."""
        hosted, client = remote_backplane
        view = _QueueView(client)
        q = view.q
        for i in range(6):
            q.append(_record(i)["events"], 1, None, token=f"tok{i}")
        assert q.depth() == 6

        insert = _rpc_insert_fn(client)
        # Drainer A leases everything, lands records 0-2, then "crashes"
        # (no ack, no nack — the lease just stops being renewed).
        leased = q.lease("A", 100, ttl_s=30)
        assert len(leased) == 6
        for rec in leased[:3]:
            insert(rec.payload)
        assert len(list(client.events().find(1))) == 3

        # B before expiry: nothing claimable.
        assert q.lease("B", 100, ttl_s=30) == []

        # Lease expires; B drains the whole batch — including the three
        # records A already landed.
        view.now[0] += 31.0
        drainer_b = LeaseDrainer(q, insert, owner="B", lease_ttl_s=30)
        landed = drainer_b.drain_once()
        assert landed == 6
        assert q.depth() == 0

        evs = list(client.events().find(1))
        assert len(evs) == 6, "zero lost AND zero duplicated"
        assert sorted(e.entity_id for e in evs) == \
            sorted(f"u{i}" for i in range(6))

    def test_storage_error_mid_ack_is_replayed_not_lost(
            self, remote_backplane):
        """PIO_FAULTS spillq.ack:error — the drainer's ack fails AFTER
        the inserts landed; the records stay leased, expire, and the
        next drain re-replays them (dedup'd) instead of losing or
        double-counting them."""
        hosted, client = remote_backplane
        view = _QueueView(client)
        q = view.q
        q.append(_record(0)["events"], 1, None, token="tok0")
        insert = _rpc_insert_fn(client)
        drainer = LeaseDrainer(q, insert, owner="A", lease_ttl_s=30)

        faults.install("spillq.ack:error:1.0:1")
        try:
            drainer.drain_once()
        finally:
            faults.clear()
        # landed but still queued (leased) — not lost
        assert len(list(client.events().find(1))) == 1
        assert q.depth() == 1
        view.now[0] += 31.0
        assert drainer.drain_once() == 1
        assert q.depth() == 0
        assert len(list(client.events().find(1))) == 1  # no duplicate

    def test_lease_steal_fault_point_fires(self, remote_backplane):
        _, client = remote_backplane
        view = _QueueView(client)
        view.q.append(_record(0)["events"], 1, None, token="tok0")
        faults.install("spillq.lease:error:1.0:1")
        try:
            with pytest.raises(ConnectionError):
                view.q.lease("A", 5, 30)
        finally:
            faults.clear()

    def test_poison_record_dead_letters_without_wedging(
            self, remote_backplane):
        _, client = remote_backplane
        view = _QueueView(client)
        q = view.q
        q.append([{"not": "an event"}], 1, None, token="bad")
        q.append(_record(1)["events"], 1, None, token="tok1")
        drainer = LeaseDrainer(q, _rpc_insert_fn(client), owner="A",
                               lease_ttl_s=30)
        assert drainer.drain_once() == 1  # good record landed
        st = q.stats()
        assert st["dead"] == 1 and q.depth() == 0
        # operator requeues after fixing the cause
        assert q.requeue_dead() == 1


# ==========================================================================
# Event server routing: shared-first, local journal as spill-of-the-spill
# ==========================================================================


def _event_stack(shared: bool):
    from predictionio_tpu.data.storage import AccessKey
    from predictionio_tpu.server.event_server import EventServer

    storage = get_storage()
    app_id = storage.get_apps().insert(App(id=None, name="spillapp"))
    storage.get_events().init(app_id)
    key = storage.get_access_keys().insert(
        AccessKey(key="", app_id=app_id))
    srv = EventServer(
        storage=storage, host="127.0.0.1", port=0,
        spill_backend="shared" if shared else "local",
        replay_wait=lambda ev, t: ev.wait(0.01) or True,   # parked
        drain_wait=lambda ev, t: ev.wait(0.01) or True)    # parked
    return srv, key, app_id, storage


def _post_event(srv, key, user="u1"):
    return srv.handle(
        "POST", "/events.json", {"accessKey": [key]},
        json.dumps({"event": "rate", "entityType": "user",
                    "entityId": user, "targetEntityType": "item",
                    "targetEntityId": "i1",
                    "properties": {"rating": 3}}).encode())


class TestEventServerSharedSpill:
    def test_resolve_backend_precedence(self, pio_home, monkeypatch):
        assert resolve_spill_backend(None, "sqlite") == "local"
        assert resolve_spill_backend(None, "pioserver") == "shared"
        assert resolve_spill_backend("shared", "sqlite") == "shared"
        assert resolve_spill_backend("local", "pioserver") == "local"
        monkeypatch.setenv("PIO_SPILL_BACKEND", "shared")
        assert resolve_spill_backend(None, "sqlite") == "shared"
        assert resolve_spill_backend("bogus", "sqlite") == "local"

    def test_outage_spills_shared_then_drains(self, pio_home):
        srv, key, app_id, storage = _event_stack(shared=True)
        try:
            faults.install("storage.create:error:1.0")
            st, body = _post_event(srv, key)
            assert st == 202 and body["token"]
            assert srv.shared_spill.depth() == 1
            assert srv.spill.depth() == 0  # shared took it
            faults.clear()
            assert srv._lease_drainer.drain_once() == 1
            assert srv.shared_spill.depth() == 0
            assert len(list(storage.get_events().find(app_id))) == 1
        finally:
            faults.clear()
            srv.stop()

    def test_storage_outage_degrades_to_local_journal(self, pio_home):
        """When storage ITSELF is the outage the shared enqueue fails
        too — the record must land in the local journal, never vanish."""
        srv, key, app_id, storage = _event_stack(shared=True)
        try:
            faults.install(
                "storage.create:error:1.0,spillq.enqueue:error:1.0")
            st, body = _post_event(srv, key)
            assert st == 202
            assert srv.spill.depth() == 1  # the spill-of-the-spill
            faults.clear()
            assert srv._replay.drain_once() == 1
            assert len(list(storage.get_events().find(app_id))) == 1
        finally:
            faults.clear()
            srv.stop()

    def test_ready_reports_both_depths(self, pio_home):
        srv, key, *_ = _event_stack(shared=True)
        try:
            st, body = srv.handle("GET", "/ready", {}, b"")
            assert body["spillBackend"] == "shared"
            assert body["sharedSpillDepth"] == 0
            assert body["spillQueueDepth"] == 0
        finally:
            srv.stop()

    def test_cached_depth_converges_after_peer_drains(self, pio_home):
        """A's /ready depth is cached (never a storage RPC on the probe
        path) and must RECONCILE at A's next drainer tick after a PEER
        drained the queue — no phantom backlog forever."""
        srv_a, key, app_id, storage = _event_stack(shared=True)
        srv_b = None
        try:
            faults.install("storage.create:error:1.0")
            assert _post_event(srv_a, key)[0] == 202
            faults.clear()
            _, body = srv_a.handle("GET", "/ready", {}, b"")
            assert body["sharedSpillDepth"] == 1  # incremental bump
            from predictionio_tpu.server.event_server import EventServer

            srv_b = EventServer(
                storage=storage, host="127.0.0.1", port=0,
                spill_backend="shared",
                replay_wait=lambda ev, t: ev.wait(0.01) or True,
                drain_wait=lambda ev, t: ev.wait(0.01) or True)
            assert srv_b._lease_drainer.drain_once() == 1  # peer drains
            # A's next tick leases nothing but still refreshes the view
            assert srv_a._lease_drainer.drain_once() == 0
            _, body = srv_a.handle("GET", "/ready", {}, b"")
            assert body["sharedSpillDepth"] == 0
        finally:
            faults.clear()
            srv_a.stop()
            if srv_b is not None:
                srv_b.stop()

    def test_any_instance_drains_a_crashed_peers_spill(self, pio_home):
        """Two event servers, one shared queue: A spills and 'crashes'
        (stops); B's drainer replays A's events."""
        srv_a, key, app_id, storage = _event_stack(shared=True)
        faults.install("storage.create:error:1.0")
        try:
            st, _ = _post_event(srv_a, key, user="uA")
            assert st == 202 and srv_a.shared_spill.depth() == 1
        finally:
            faults.clear()
        srv_a.stop()  # crash: the record is in the SHARED queue
        from predictionio_tpu.server.event_server import EventServer

        srv_b = EventServer(
            storage=storage, host="127.0.0.1", port=0,
            spill_backend="shared",
            replay_wait=lambda ev, t: ev.wait(0.01) or True,
            drain_wait=lambda ev, t: ev.wait(0.01) or True)
        try:
            assert srv_b._lease_drainer.drain_once() == 1
            evs = list(storage.get_events().find(app_id))
            assert [e.entity_id for e in evs] == ["uA"]
        finally:
            srv_b.stop()


# ==========================================================================
# Rollout controller
# ==========================================================================


from predictionio_tpu.fleet import (  # noqa: E402
    FleetPromoter,
    RolloutConfig,
    RolloutController,
    parse_waves,
)


class TestWaveParsing:
    def test_mixed_counts_and_percentages(self):
        assert parse_waves("1,25%,100%", 8) == [1, 2, 8]
        assert parse_waves("1,25%,100%", 3) == [1, 3]
        assert parse_waves("2,50%", 10) == [2, 5, 10]

    def test_appends_full_fleet_wave(self):
        assert parse_waves("1", 4) == [1, 4]

    def test_monotonic_and_clamped(self):
        assert parse_waves("3,1,2,100%", 4) == [3, 4]
        assert parse_waves("99", 4) == [4]

    def test_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_waves("0", 4)
        with pytest.raises(ValueError):
            parse_waves("150%", 4)
        with pytest.raises(ValueError):
            parse_waves("abc", 4)


ALS_VARIANT = {
    "engineFactory": "predictionio_tpu.templates.recommendation:engine",
    "datasource": {"params": {"appName": "fleetapp"}},
    "algorithms": [{"name": "als",
                    "params": {"rank": 8, "numIterations": 2,
                               "seed": 3}}],
}


def _trained_fleet_stack(n_generations=1):
    from predictionio_tpu.templates.recommendation import engine

    storage = get_storage()
    ctx = RuntimeContext.create(storage=storage)
    app_id = storage.get_apps().insert(App(id=None, name="fleetapp"))
    storage.get_events().init(app_id)
    rng = np.random.default_rng(0)
    evs = [Event(event="rate", entity_type="user", entity_id=f"u{u}",
                 target_entity_type="item", target_entity_id=f"i{i}",
                 properties=DataMap({"rating": float(r)}))
           for u, i, r in zip(rng.integers(0, 30, 1200),
                              rng.integers(0, 40, 1200),
                              rng.integers(1, 6, 1200))]
    storage.get_events().insert_batch(evs, app_id)
    eng = engine()
    variant = EngineVariant.from_dict(ALS_VARIANT)
    iids = [run_train(eng, variant, ctx) for _ in range(n_generations)]
    return eng, variant, ctx, app_id, iids


def _fleet_servers(eng, variant, storage, n=3):
    from predictionio_tpu.server import EngineServer

    servers = [EngineServer(eng, variant, storage, host="127.0.0.1",
                            port=0) for _ in range(n)]
    for s in servers:
        s.start(block=False)
    return servers, [f"http://127.0.0.1:{s.port}" for s in servers]


def _cfg(tmp_path, **kw):
    kw.setdefault("waves", "1,100%")
    kw.setdefault("bake_s", 0.2)
    kw.setdefault("poll_s", 0.02)
    kw.setdefault("state_path", str(tmp_path / "rollout.json"))
    return RolloutConfig(**kw)


class TestRolloutE2E:
    def test_wave_promotes_whole_fleet_generation_atomically(
            self, pio_home, tmp_path):
        eng, variant, ctx, _, (i1,) = _trained_fleet_stack(1)
        servers, urls = _fleet_servers(eng, variant, ctx.storage)
        i2 = run_train(eng, variant, ctx)  # candidate generation
        try:
            ctl = RolloutController(urls, _cfg(tmp_path))
            state = ctl.run()
            assert state["status"] == "promoted"
            assert state["target"] == i2
            assert state["waveCounts"] == [1, 3]
            for u in urls:
                assert ctl.served_instance(u) == i2
            # journal is terminal + readable
            saved = json.loads((tmp_path / "rollout.json").read_text())
            assert saved["status"] == "promoted"
            assert saved["preRollout"][urls[0]] == i1  # pre-swap snapshot
        finally:
            for s in servers:
                s.stop()

    def test_halt_on_canary_burn_rolls_back_every_promoted_instance(
            self, pio_home, tmp_path):
        """THE acceptance e2e (2): wave 1 promotes the canary; its SLO
        degrades; the controller halts BEFORE wave 2 and rolls the
        canary back — pre-promotion generation serving everywhere, the
        other instances never touched."""
        from predictionio_tpu.obs.fleet import FleetAggregator

        eng, variant, ctx, _, (i1,) = _trained_fleet_stack(1)
        servers, urls = _fleet_servers(eng, variant, ctx.storage)
        i2 = run_train(eng, variant, ctx)  # candidate generation
        promoted_urls = []

        def fetch(url):
            base = url.rsplit("/", 1)[0]
            with urlopen(url, timeout=10) as r:
                text = r.read().decode()
            if url.endswith("/stats.json") and base in promoted_urls:
                doc = json.loads(text)
                doc.setdefault("slo", {})["degraded"] = True
                return json.dumps(doc)
            return text

        class Ctl(RolloutController):
            def _promote_instance(self, url, target):
                out = super()._promote_instance(url, target)
                if out[0] == "ok":
                    promoted_urls.append(url)
                return out

        try:
            ctl = Ctl(urls, _cfg(tmp_path),
                      aggregator=FleetAggregator(urls, fetch=fetch))
            state = ctl.run(i2)
            assert state["status"] == "rolled_back"
            assert state["promoted"] == [urls[0]]
            assert state["rolledBack"] == [urls[0]]
            assert "slo burn" in state["haltReason"]
            # the whole fleet serves the pre-promotion generation
            for u in urls:
                assert ctl.served_instance(u) == i1
            assert state["postRollback"] == {u: i1 for u in urls}
        finally:
            for s in servers:
                s.stop()

    def test_bad_candidate_burns_the_real_gate_and_the_rest_answer_on(
            self, pio_home, tmp_path, monkeypatch):
        """The halt above with nothing faked between the candidate and
        the gate: a candidate that passes validation and fails every
        predict is promoted to the canary only, its own 500s burn the
        SLO the controller scrapes, the wave halts and rolls back, and
        a client of each instance NOT yet promoted saw nothing but 200s
        for the whole episode.  (The three servers share one process and
        one registry, so the burn is process-wide; what is told apart
        per instance is what each one's client received.)"""
        from urllib.error import HTTPError

        from predictionio_tpu.server import engine_server as es_mod

        eng, variant, ctx, _, (i1,) = _trained_fleet_stack(1)
        servers, urls = _fleet_servers(eng, variant, ctx.storage)
        bad = run_train(eng, variant, ctx)
        real_load = es_mod.load_models

        class Unservable:
            """No arrays for the finite check to refuse, no predict."""

        monkeypatch.setattr(
            es_mod, "load_models",
            lambda e, inst, c=None: [Unservable()] if inst.id == bad
            else real_load(e, inst, c))
        stop = threading.Event()
        seen = {u: {} for u in urls}

        def drive(url):
            k = 0
            while not stop.is_set():
                req = Request(
                    url + "/queries.json",
                    data=json.dumps({"user": f"u{k % 30}",
                                     "num": 3}).encode(),
                    headers={"Content-Type": "application/json"})
                try:
                    with urlopen(req, timeout=30) as resp:
                        status = resp.status
                except HTTPError as e:
                    status = e.code
                except OSError:
                    status = -1
                seen[url][status] = seen[url].get(status, 0) + 1
                k += 1

        drivers = [threading.Thread(target=drive, args=(u,), daemon=True)
                   for u in urls]
        try:
            for t in drivers:
                t.start()
            ctl = RolloutController(
                urls, _cfg(tmp_path, bake_s=30.0, poll_s=0.05))
            state = ctl.run(bad)
            assert state["status"] == "rolled_back"
            assert state["promoted"] == state["rolledBack"] == [urls[0]]
            assert "slo burn" in state["haltReason"]
            for u in urls:
                assert ctl.served_instance(u) == i1
        finally:
            stop.set()
            for t in drivers:
                t.join(timeout=30)
            for s in servers:
                s.stop()
        assert seen[urls[0]].get(500, 0) > 0, seen  # the canary did burn
        for u in urls[1:]:
            assert set(seen[u]) == {200}, seen

    def test_dead_instance_and_409_skip_and_report(self, pio_home,
                                                   tmp_path):
        eng, variant, ctx, _, (i1,) = _trained_fleet_stack(1)
        servers, urls = _fleet_servers(eng, variant, ctx.storage, n=2)
        i2 = run_train(eng, variant, ctx)
        dead = "http://127.0.0.1:9"  # discard port: never connects
        try:
            ctl = RolloutController(
                urls + [dead], _cfg(tmp_path, waves="100%",
                                    reload_timeout_s=5.0))
            state = ctl.run(i2)
            assert state["status"] == "promoted"
            assert sorted(state["promoted"]) == sorted(urls)
            assert "unreachable" in state["skipped"][dead]
            # an unknown target on live servers → 409 skip, not a wedge
            state2 = ctl.run("no-such-instance")
            assert state2["status"] == "failed"
            assert all("rejected" in v
                       for u, v in state2["skipped"].items()
                       if u != dead)
            for u in urls:  # nobody loaded anything new
                assert ctl.served_instance(u) == i2
        finally:
            for s in servers:
                s.stop()

    def test_preempted_controller_resumes_deterministically(
            self, pio_home, tmp_path):
        """Kill the controller after wave 1; a fresh controller resumes
        from the journal, re-verifies served instances, and finishes the
        remaining waves without re-promoting the canary."""
        eng, variant, ctx, _, (i1,) = _trained_fleet_stack(1)
        servers, urls = _fleet_servers(eng, variant, ctx.storage)
        i2 = run_train(eng, variant, ctx)

        class Preempted(RuntimeError):
            pass

        class DiesAfterWave1(RolloutController):
            def _bake(self, state):
                raise Preempted()  # killed mid-bake, journal on disk

        try:
            ctl = DiesAfterWave1(urls, _cfg(tmp_path))
            with pytest.raises(Preempted):
                ctl.run(i2)
            saved = json.loads((tmp_path / "rollout.json").read_text())
            assert saved["status"] == "in_progress"
            assert saved["promoted"] == [urls[0]]

            reload_counts = {}

            class Counting(RolloutController):
                def _promote_instance(self, url, target):
                    reload_counts[url] = reload_counts.get(url, 0) + 1
                    return super()._promote_instance(url, target)

            ctl2 = Counting(urls, _cfg(tmp_path))
            state = ctl2.resume()
            assert state["status"] == "promoted"
            assert reload_counts.get(urls[0]) is None  # not re-promoted
            for u in urls:
                assert ctl2.served_instance(u) == i2
        finally:
            for s in servers:
                s.stop()

    def test_preempted_controller_unwinds_on_request(self, pio_home,
                                                     tmp_path):
        eng, variant, ctx, _, (i1,) = _trained_fleet_stack(1)
        servers, urls = _fleet_servers(eng, variant, ctx.storage)
        i2 = run_train(eng, variant, ctx)

        class Preempted(RuntimeError):
            pass

        class DiesAfterWave1(RolloutController):
            def _bake(self, state):
                raise Preempted()

        try:
            with pytest.raises(Preempted):
                DiesAfterWave1(urls, _cfg(tmp_path)).run(i2)
            state = RolloutController(urls, _cfg(tmp_path)).resume(
                unwind=True)
            assert state["status"] == "rolled_back"
            for u in urls:
                assert RolloutController(
                    urls, _cfg(tmp_path)).served_instance(u) == i1
        finally:
            for s in servers:
                s.stop()

    def test_fleet_promoter_drives_rollout_for_the_daemon(
            self, pio_home, tmp_path):
        from predictionio_tpu.refresh import RefreshConfig
        from predictionio_tpu.refresh.daemon import RefreshDaemon

        eng, variant, ctx, _, (i1,) = _trained_fleet_stack(1)
        servers, urls = _fleet_servers(eng, variant, ctx.storage, n=2)
        try:
            # multi-URL promote_url → the daemon builds a FleetPromoter
            d = RefreshDaemon(
                eng, variant, ctx,
                config=RefreshConfig(interval_s=0.01,
                                     promote_url=",".join(urls)))
            assert isinstance(d.promoter, FleetPromoter)
            d.promoter.config = _cfg(tmp_path)
            d.promoter.canary_window_s = 0.2
            d.promoter._factory = lambda: RolloutController(
                urls, _cfg(tmp_path))
            out = d.run_once()
            assert out["promotion"] == "promoted"
            i3 = out["instance"]
            for u in urls:
                assert RolloutController(
                    urls, _cfg(tmp_path)).served_instance(u) == i3
            # the staleness anchor: oldest served watermark is readable
            assert d.promoter.served_watermark() is not None
        finally:
            for s in servers:
                s.stop()


class TestReloadTarget:
    def test_reload_accepts_explicit_instance_id(self, pio_home):
        eng, variant, ctx, _, (i1, i2) = _trained_fleet_stack(2)
        servers, urls = _fleet_servers(eng, variant, ctx.storage, n=1)
        try:
            # pin BACK to the older instance explicitly
            req = Request(urls[0] + "/reload",
                          data=json.dumps(
                              {"engineInstanceId": i1}).encode(),
                          method="POST",
                          headers={"Content-Type": "application/json"})
            with urlopen(req, timeout=60) as resp:
                body = json.loads(resp.read())
            assert body["engineInstanceId"] == i1
            # unknown target → 409 rejected, last-good keeps serving
            from urllib.error import HTTPError

            req = Request(urls[0] + "/reload",
                          data=b'{"engineInstanceId": "nope"}',
                          method="POST",
                          headers={"Content-Type": "application/json"})
            with pytest.raises(HTTPError) as ei:
                urlopen(req, timeout=60)
            assert ei.value.code == 409
            with urlopen(urls[0] + "/", timeout=10) as resp:
                assert json.loads(
                    resp.read())["engineInstanceId"] == i1
        finally:
            for s in servers:
                s.stop()


# ==========================================================================
# Durable fold-in cache (tentpole c) + item-side fold-in satellite
# ==========================================================================


class TestDurableFoldInCache:
    def _stack_with_new_user(self):
        eng, variant, ctx, app_id, (iid,) = _trained_fleet_stack(1)
        ctx.storage.get_events().insert_batch(
            [Event(event="rate", entity_type="user", entity_id="newuser",
                   target_entity_type="item", target_entity_id=f"i{i}",
                   properties=DataMap({"rating": 5.0}))
             for i in range(5)], app_id)
        inst = ctx.storage.get_engine_instances().get(iid)
        return eng, ctx, inst

    @staticmethod
    def _metric(result):
        from predictionio_tpu.obs import get_registry

        c = get_registry().get("pio_fold_in_total")
        return c.series().get((result,), 0) if c else 0

    def test_instance_b_hits_what_instance_a_solved(self, pio_home):
        """THE acceptance e2e (3), wrapper level: A solves, B answers
        from the shared KV — even with B's event store broken."""
        eng, ctx, inst = self._stack_with_new_user()
        wrap_a = load_models(eng, inst, ctx)[0]
        wrap_b = load_models(eng, inst, ctx)[0]
        assert wrap_a._shared_kv is not None

        vec_a = wrap_a.fold_in_user("newuser")
        assert vec_a is not None and self._metric("solved") == 1

        class Boom:
            def find_by_entity(self, *a, **k):
                raise AssertionError("B must not read the event store")

        wrap_b._event_store = Boom()
        vec_b = wrap_b.fold_in_user("newuser")
        assert vec_b is not None and np.allclose(vec_a, vec_b)
        assert self._metric("shared") == 1

    def test_shared_cache_survives_instance_restart(self, pio_home):
        """A restarted instance (fresh wrapper) warms from the fleet's
        work instead of re-solving."""
        eng, ctx, inst = self._stack_with_new_user()
        load_models(eng, inst, ctx)[0].fold_in_user("newuser")
        fresh = load_models(eng, inst, ctx)[0]  # "restart"
        assert fresh.fold_in_user("newuser") is not None
        assert self._metric("shared") == 1
        assert self._metric("solved") == 1  # solved exactly once

    def test_different_factors_never_share(self, pio_home):
        """Entries are fingerprint-keyed: a different generation's
        factors must miss and re-solve."""
        eng, variant, ctx, app_id, (i1,) = _trained_fleet_stack(1)
        ctx.storage.get_events().insert_batch(
            [Event(event="rate", entity_type="user", entity_id="newuser",
                   target_entity_type="item", target_entity_id="i1",
                   properties=DataMap({"rating": 5.0}))], app_id)
        inst1 = ctx.storage.get_engine_instances().get(i1)
        w1 = load_models(eng, inst1, ctx)[0]
        assert w1.fold_in_user("newuser") is not None
        i2 = run_train(eng, variant, ctx)  # retrain → new factors
        inst2 = ctx.storage.get_engine_instances().get(i2)
        w2 = load_models(eng, inst2, ctx)[0]
        assert w2._fold_ns() != w1._fold_ns()
        assert w2.fold_in_user("newuser") is not None
        assert self._metric("solved") == 2 and self._metric("shared") == 0

    def test_kill_switch_and_kv_blip_degrade_cleanly(self, pio_home,
                                                     monkeypatch):
        eng, ctx, inst = self._stack_with_new_user()
        wrap = load_models(eng, inst, ctx)[0]
        monkeypatch.setenv("PIO_FOLD_IN_SHARED", "off")
        assert wrap.fold_in_user("newuser") is not None
        assert self._metric("solved") == 1
        # fresh wrapper: with sharing off it must re-solve, not hit
        wrap2 = load_models(eng, inst, ctx)[0]
        assert wrap2.fold_in_user("newuser") is not None
        assert self._metric("solved") == 2 and self._metric("shared") == 0
        monkeypatch.delenv("PIO_FOLD_IN_SHARED")

        class BoomKV:
            def get(self, *a):
                raise RuntimeError("kv down")

            def put(self, *a):
                raise RuntimeError("kv down")

        wrap3 = load_models(eng, inst, ctx)[0]
        wrap3._shared_kv = BoomKV()
        assert wrap3.fold_in_user("newuser") is not None  # still answers

    def test_max_age_gate_re_solves_stale_entries(self, pio_home,
                                                  monkeypatch):
        """The stored solve time is load-bearing: with
        PIO_FOLD_IN_SHARED_MAX_AGE_S set, an entry solved longer ago
        reads as a miss and the visitor re-solves (anchor is SOLVE age —
        an idle user's old events must not permanently expire their
        entry)."""
        eng, ctx, inst = self._stack_with_new_user()
        wrap_a = load_models(eng, inst, ctx)[0]
        assert wrap_a.fold_in_user("newuser") is not None
        # the solve just happened: a generous age accepts, a tiny one
        # rejects
        monkeypatch.setenv("PIO_FOLD_IN_SHARED_MAX_AGE_S", "3600")
        wrap_b = load_models(eng, inst, ctx)[0]
        assert wrap_b.fold_in_user("newuser") is not None
        assert self._metric("shared") == 1
        monkeypatch.setenv("PIO_FOLD_IN_SHARED_MAX_AGE_S", "0.000001")
        wrap_c = load_models(eng, inst, ctx)[0]
        assert wrap_c.fold_in_user("newuser") is not None
        assert self._metric("shared") == 1    # gate rejected the entry
        assert self._metric("solved") == 2    # ...so C re-solved

    def test_negative_outcomes_are_not_shared(self, pio_home):
        eng, variant, ctx, app_id, (iid,) = _trained_fleet_stack(1)
        inst = ctx.storage.get_engine_instances().get(iid)
        wrap = load_models(eng, inst, ctx)[0]
        assert wrap.fold_in_user("ghost") is None
        kv = ctx.storage.get_kv()
        assert kv.get(wrap._fold_ns(), "ghost") is None

    def test_live_http_fold_in_shared_across_two_servers(self, pio_home):
        """Live-HTTP flavor of acceptance e2e (3): query the new user on
        server A, then on server B — B's answer comes from the shared
        cache (counter), and both rank identically."""
        eng, variant, ctx, app_id, (iid,) = _trained_fleet_stack(1)
        ctx.storage.get_events().insert_batch(
            [Event(event="rate", entity_type="user", entity_id="newuser",
                   target_entity_type="item", target_entity_id=f"i{i}",
                   properties=DataMap({"rating": 5.0}))
             for i in range(5)], app_id)
        servers, urls = _fleet_servers(eng, variant, ctx.storage, n=2)

        def query(base):
            req = Request(base + "/queries.json",
                          data=json.dumps({"user": "newuser",
                                           "num": 3}).encode(),
                          headers={"Content-Type": "application/json"})
            with urlopen(req, timeout=30) as resp:
                return json.loads(resp.read())

        try:
            ra = query(urls[0])
            assert ra["itemScores"], "fold-in must answer, not cold-start"
            assert self._metric("solved") == 1
            rb = query(urls[1])
            assert [s["item"] for s in rb["itemScores"]] == \
                [s["item"] for s in ra["itemScores"]]
            assert self._metric("shared") == 1
            assert self._metric("solved") == 1  # B did NOT re-solve
        finally:
            for s in servers:
                s.stop()


class TestItemSideFoldIn:
    def _stack(self):
        from predictionio_tpu.templates.similarproduct import engine

        storage = get_storage()
        ctx = RuntimeContext.create(storage=storage)
        app_id = storage.get_apps().insert(App(id=None, name="spapp"))
        storage.get_events().init(app_id)
        # clique: even users view even items, odd view odd
        evs = [Event(event="view", entity_type="user",
                     entity_id=f"u{u}", target_entity_type="item",
                     target_entity_id=f"i{i}")
               for u in range(10) for i in range(8) if i % 2 == u % 2]
        storage.get_events().insert_batch(evs, app_id)
        variant = EngineVariant.from_dict({
            "engineFactory":
                "predictionio_tpu.templates.similarproduct:engine",
            "datasource": {"params": {"appName": "spapp"}},
            "algorithms": [{"name": "als",
                            "params": {"rank": 8, "numIterations": 6,
                                       "seed": 3}}],
        })
        eng = engine()
        iid = run_train(eng, variant, ctx)
        return eng, variant, ctx, app_id, iid

    @staticmethod
    def _metric(result):
        from predictionio_tpu.obs import get_registry

        c = get_registry().get("pio_fold_in_items_total")
        return c.series().get((result,), 0) if c else 0

    def test_new_item_folds_in_and_ranks_its_cohort(self, pio_home):
        eng, variant, ctx, app_id, iid = self._stack()
        # new item i100, viewed by EVEN (cohort-0) users
        ctx.storage.get_events().insert_batch(
            [Event(event="view", entity_type="user", entity_id=f"u{u}",
                   target_entity_type="item", target_entity_id="i100")
             for u in (0, 2, 4, 6)], app_id)
        inst = ctx.storage.get_engine_instances().get(iid)
        model = load_models(eng, inst, ctx)[0]
        algo = eng.algorithm_classes["als"](None)
        from predictionio_tpu.templates.similarproduct.engine import Query

        res = algo.predict(model, Query(items=["i100"], num=4))
        assert res.itemScores, "a viewed new item must not stay cold"
        assert self._metric("solved") == 1
        # the folded factor lands in the even cohort
        top = [s.item for s in res.itemScores]
        even_hits = sum(1 for it in top if int(it[1:]) % 2 == 0)
        assert even_hits >= 3, top
        # repeat query rides the bounded cache
        algo.predict(model, Query(items=["i100"], num=4))
        assert self._metric("cached") >= 1
        assert self._metric("solved") == 1

    def test_unknown_item_without_views_stays_cold(self, pio_home):
        eng, variant, ctx, app_id, iid = self._stack()
        inst = ctx.storage.get_engine_instances().get(iid)
        model = load_models(eng, inst, ctx)[0]
        algo = eng.algorithm_classes["als"](None)
        from predictionio_tpu.templates.similarproduct.engine import Query

        res = algo.predict(model, Query(items=["i999"], num=4))
        assert res.itemScores == []
        assert self._metric("no_events") == 1

    def test_kill_switch_disables_item_fold_in(self, pio_home,
                                               monkeypatch):
        eng, variant, ctx, app_id, iid = self._stack()
        ctx.storage.get_events().insert_batch(
            [Event(event="view", entity_type="user", entity_id="u0",
                   target_entity_type="item",
                   target_entity_id="i100")], app_id)
        monkeypatch.setenv("PIO_FOLD_IN", "off")
        inst = ctx.storage.get_engine_instances().get(iid)
        model = load_models(eng, inst, ctx)[0]
        algo = eng.algorithm_classes["als"](None)
        from predictionio_tpu.templates.similarproduct.engine import Query

        res = algo.predict(model, Query(items=["i100"], num=4))
        assert res.itemScores == []

    def test_old_pickle_backfills_and_declines(self, pio_home):
        """A pre-ISSUE-15 pickle (no user factors) loads and simply
        declines item fold-in."""
        eng, variant, ctx, app_id, iid = self._stack()
        inst = ctx.storage.get_engine_instances().get(iid)
        model = load_models(eng, inst, ctx)[0]
        state = model.__getstate__()
        for k in ("user_factors", "user_index", "app_name",
                  "fold_event_names", "reg", "alpha"):
            state.pop(k, None)
        old = pickle.loads(pickle.dumps(state))
        revived = type(model).__new__(type(model))
        revived.__setstate__(old)
        assert revived.user_factors is None
        assert revived.fold_in_item("i100") is None


# ==========================================================================
# Eval-sweep preemption resume (satellite)
# ==========================================================================


class TestEvalCheckpointResume:
    def _eval_pieces(self):
        from predictionio_tpu.templates.recommendation import engine

        storage = get_storage()
        ctx = RuntimeContext.create(storage=storage)
        app_id = storage.get_apps().insert(App(id=None, name="evapp"))
        storage.get_events().init(app_id)
        rng = np.random.default_rng(0)
        evs = [Event(event="rate", entity_type="user",
                     entity_id=f"u{u}", target_entity_type="item",
                     target_entity_id=f"i{i}",
                     properties=DataMap({"rating": float(r)}))
               for u, i, r in zip(rng.integers(0, 20, 600),
                                  rng.integers(0, 25, 600),
                                  rng.integers(1, 6, 600))]
        storage.get_events().insert_batch(evs, app_id)
        eng = engine()
        candidates = [
            eng.bind_engine_params({
                "datasource": {"params": {"appName": "evapp",
                                          "evalK": 2}},
                "algorithms": [{"name": "als",
                                "params": {"rank": r, "numIterations": 2,
                                           "seed": 3}}]})
            for r in (4, 6)
        ]
        return eng, ctx, candidates

    def test_preempted_sweep_resumes_from_completed_units(
            self, pio_home, tmp_path, monkeypatch):
        from predictionio_tpu.controller.engine import EvalCheckpoint
        from predictionio_tpu.resilience import supervision
        from predictionio_tpu.resilience.supervision import TrainPreempted

        eng, ctx, candidates = self._eval_pieces()
        baseline = eng.eval_multi(ctx, candidates)

        ck = EvalCheckpoint(tmp_path / "evalck")
        calls = {"n": 0}

        def preempt_after_two():
            calls["n"] += 1
            return calls["n"] > 2

        monkeypatch.setattr(supervision, "preemption_requested",
                            preempt_after_two)
        with pytest.raises(TrainPreempted):
            eng.eval_multi(ctx, candidates, checkpoint=ck)
        done_before = ck.completed()
        assert 0 < done_before < 4  # partial progress persisted

        monkeypatch.setattr(supervision, "preemption_requested",
                            lambda: False)
        trains = {"n": 0}
        from predictionio_tpu.templates.recommendation.engine import (
            ALSAlgorithm,
        )

        real_train = ALSAlgorithm.train

        def counting_train(self, ctx_, pd):
            trains["n"] += 1
            return real_train(self, ctx_, pd)

        monkeypatch.setattr(ALSAlgorithm, "train", counting_train)
        resumed = eng.eval_multi(ctx, candidates, checkpoint=ck)
        # only the un-checkpointed units retrained
        assert trains["n"] == 4 - done_before
        assert ck.completed() == 4
        # scores from the resumed sweep match the uninterrupted one
        from predictionio_tpu.templates.recommendation.evaluation import (
            PrecisionAtK,
        )

        metric = PrecisionAtK(k=3)
        for cand in range(2):
            assert metric.calculate(resumed[cand]) == pytest.approx(
                metric.calculate(baseline[cand]))

    def test_run_evaluation_marks_preempted_and_resumes(
            self, pio_home, tmp_path, monkeypatch):
        from predictionio_tpu.resilience import supervision
        from predictionio_tpu.resilience.supervision import TrainPreempted
        from predictionio_tpu.templates.recommendation.evaluation import (
            ParamsList,
            RecommendationEvaluation,
        )
        from predictionio_tpu.workflow.core_workflow import run_evaluation

        eng, ctx, candidates = self._eval_pieces()
        evaluation = RecommendationEvaluation(k=3)
        generator = ParamsList(candidates)
        ck_dir = str(tmp_path / "evalck2")

        calls = {"n": 0}
        monkeypatch.setattr(supervision, "preemption_requested",
                            lambda: (calls.__setitem__("n",
                                                       calls["n"] + 1)
                                     or calls["n"] > 1))
        with pytest.raises(TrainPreempted):
            run_evaluation(evaluation, generator, ctx,
                           checkpoint_dir=ck_dir)
        rows = ctx.storage.get_evaluation_instances().get_all()
        assert any(r.status == "EVALPREEMPTED" for r in rows)

        monkeypatch.setattr(supervision, "preemption_requested",
                            lambda: False)
        iid, result = run_evaluation(evaluation, generator, ctx,
                                     checkpoint_dir=ck_dir)
        assert result.best_score is not None
        # checkpoint cleared once the sweep landed
        from predictionio_tpu.controller.engine import EvalCheckpoint

        assert EvalCheckpoint(ck_dir).completed() == 0
        inst = ctx.storage.get_evaluation_instances().get(iid)
        assert inst.status == "EVALCOMPLETED"
