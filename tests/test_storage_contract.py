"""Storage contract tests — one spec, every backend.

Reference: data/.../storage/LEventsSpec / PEventsSpec run against multiple
backends via env selection (SURVEY.md §4 "storage-contract tests").  Here
pytest parametrization replaces env selection.
"""

import datetime as dt
import json

import pytest

from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage import StorageError
from predictionio_tpu.data.storage.base import (
    AccessKey,
    App,
    Channel,
    EngineInstance,
    Model,
)

UTC = dt.timezone.utc


def ts(s):
    return dt.datetime.fromisoformat(s).replace(tzinfo=UTC)


def _hosted(client):
    """Storage-like adapter exposing one SQLiteClient's repositories to a
    StorageServer (shared by the remote-backend tests)."""

    class Hosted:
        get_events = staticmethod(client.events)
        get_apps = staticmethod(client.apps)
        get_access_keys = staticmethod(client.access_keys)
        get_channels = staticmethod(client.channels)
        get_engine_instances = staticmethod(client.engine_instances)
        get_evaluation_instances = staticmethod(client.evaluation_instances)
        get_models = staticmethod(client.models)

    return Hosted


# --------------------------------------------------------------------------
# Events contract
# --------------------------------------------------------------------------

def _remote_pair(tmp_path):
    """An OUT-OF-PROCESS-shaped backend: sqlite behind the TCP storage
    server, reached through RemoteClient — the same traits over the wire
    (round-2 verdict item 4: pluggability proven by a second
    process-external backend)."""
    from predictionio_tpu.data.storage.remote import RemoteClient, StorageServer
    from predictionio_tpu.data.storage.sqlite import SQLiteClient

    client = SQLiteClient(str(tmp_path / "served.db"))

    Hosted = _hosted(client)

    srv = StorageServer(Hosted, host="127.0.0.1", port=0)
    srv.start()
    remote = RemoteClient("127.0.0.1", srv.port)

    def cleanup():
        remote.close()
        srv.stop()
        client.close()

    return remote, cleanup


@pytest.fixture(params=["memory", "sqlite", "parquetlog", "pioserver"])
def events_backend(request, tmp_path):
    if request.param == "memory":
        from predictionio_tpu.data.storage.memory import MemoryEvents

        yield MemoryEvents()
    elif request.param == "sqlite":
        from predictionio_tpu.data.storage.sqlite import SQLiteClient

        client = SQLiteClient(str(tmp_path / "ev.db"))
        yield client.events()
        client.close()
    elif request.param == "pioserver":
        remote, cleanup = _remote_pair(tmp_path)
        yield remote.events()
        cleanup()
    else:
        from predictionio_tpu.data.storage.parquet_events import ParquetEvents

        yield ParquetEvents(str(tmp_path / "events"))


def _mk(event, eid, t, etype="user", target=None, props=None):
    return Event(
        event=event,
        entity_type=etype,
        entity_id=eid,
        target_entity_type="item" if target else None,
        target_entity_id=target,
        properties=DataMap(props or {}),
        event_time=ts(t),
    )


APP = 7


class TestEventsContract:
    def test_requires_init(self, events_backend):
        with pytest.raises(StorageError):
            list(events_backend.find(APP))

    def test_insert_get_delete(self, events_backend):
        ev = events_backend
        ev.init(APP)
        eid = ev.insert(_mk("rate", "u1", "2026-01-01T00:00:00", target="i1",
                            props={"rating": 4.5}), APP)
        got = ev.get(eid, APP)
        assert got is not None
        assert got.event == "rate" and got.entity_id == "u1"
        assert got.target_entity_id == "i1"
        assert got.properties.get_double("rating") == 4.5
        assert got.event_time == ts("2026-01-01T00:00:00")
        assert ev.delete(eid, APP) is True
        assert ev.get(eid, APP) is None
        assert ev.delete(eid, APP) is False

    def test_find_filters_and_order(self, events_backend):
        ev = events_backend
        ev.init(APP)
        ev.insert_batch(
            [
                _mk("view", "u1", "2026-01-01T00:00:00", target="i1"),
                _mk("buy", "u1", "2026-01-02T00:00:00", target="i2"),
                _mk("view", "u2", "2026-01-03T00:00:00", target="i1"),
                _mk("view", "u1", "2026-01-04T00:00:00", target="i3"),
            ],
            APP,
        )
        all_ev = list(ev.find(APP))
        assert [e.event_time for e in all_ev] == sorted(e.event_time for e in all_ev)
        assert len(all_ev) == 4
        u1 = list(ev.find(APP, entity_type="user", entity_id="u1"))
        assert len(u1) == 3
        views = list(ev.find(APP, event_names=["view"]))
        assert len(views) == 3
        window = list(
            ev.find(APP, start_time=ts("2026-01-02T00:00:00"),
                    until_time=ts("2026-01-04T00:00:00"))
        )
        assert [e.event for e in window] == ["buy", "view"]
        tgt = list(ev.find(APP, target_entity_type="item", target_entity_id="i1"))
        assert len(tgt) == 2
        newest = list(ev.find(APP, limit=2, reversed=True))
        assert [e.event_time for e in newest] == [ts("2026-01-04T00:00:00"),
                                                  ts("2026-01-03T00:00:00")]

    def test_time_window_boundary_inclusivity(self, events_backend):
        """ISSUE 10 satellite: the refresh loop's gap/overlap-free window
        contract — ``start_time`` INCLUSIVE, ``until_time`` EXCLUSIVE —
        pinned identical across every backend.  A generation trained
        with ``until_time=W`` plus a delta trained with
        ``start_time=W`` must cover every event exactly once, including
        one stamped exactly at W."""
        ev = events_backend
        ev.init(APP)
        ev.insert_batch(
            [
                _mk("a", "u1", "2026-01-01T00:00:00"),
                _mk("b", "u1", "2026-01-02T00:00:00"),   # exactly at W
                _mk("c", "u1", "2026-01-03T00:00:00"),
            ],
            APP,
        )
        w = ts("2026-01-02T00:00:00")
        before = [e.event for e in ev.find(APP, until_time=w)]
        after = [e.event for e in ev.find(APP, start_time=w)]
        assert before == ["a"], "until_time must be EXCLUSIVE"
        assert after == ["b", "c"], "start_time must be INCLUSIVE"
        assert sorted(before + after) == ["a", "b", "c"]  # no gap/overlap
        # the columnar (training) read follows the same contract
        tbl = ev.find_columnar(APP, start_time=w)
        assert tbl.num_rows == 2
        tbl = ev.find_columnar(APP, until_time=w)
        assert tbl.num_rows == 1

    # -- bulk-ingest create_batch contract (ISSUE 17) ------------------

    def test_create_batch_lands_all_rows(self, events_backend):
        ev = events_backend
        ev.init(APP)
        ids = ev.create_batch(
            [
                _mk("view", "u1", "2026-01-01T00:00:00", target="i1"),
                _mk("buy", "u2", "2026-01-02T00:00:00", target="i2"),
            ],
            APP,
            tokens=["tokA.0", "tokA.1"],
        )
        assert len(ids) == 2 and len(set(ids)) == 2
        got = [ev.get(i, APP) for i in ids]
        assert [g.event for g in got] == ["view", "buy"]
        assert len(list(ev.find(APP))) == 2

    def test_create_batch_replay_is_idempotent(self, events_backend):
        """The exactly-once core: replaying the SAME sub-tokens (a client
        retry after a crashed reply, a journal replay after restart)
        lands each row at most once and returns the same ids."""
        ev = events_backend
        ev.init(APP)
        events = [
            _mk("view", "u1", "2026-01-01T00:00:00", target="i1"),
            _mk("buy", "u2", "2026-01-02T00:00:00", target="i2"),
        ]
        toks = ["replay.0", "replay.1"]
        first = ev.create_batch(events, APP, tokens=toks)
        second = ev.create_batch(events, APP, tokens=toks)
        assert first == second
        assert len(list(ev.find(APP))) == 2

    def test_create_batch_partial_landing_replays_per_item(
            self, events_backend):
        """A crash can leave HALF a batch committed (the reply was lost
        either way).  Dedup is per-item, not per-batch: the replay must
        fill in only the missing rows."""
        ev = events_backend
        ev.init(APP)
        events = [
            _mk("view", "u1", "2026-01-01T00:00:00", target="i1"),
            _mk("buy", "u2", "2026-01-02T00:00:00", target="i2"),
        ]
        toks = ["part.0", "part.1"]
        # simulate the partial landing: only item 0 committed
        ev.create_batch(events[:1], APP, tokens=toks[:1])
        assert len(list(ev.find(APP))) == 1
        ids = ev.create_batch(events, APP, tokens=toks)
        assert len(ids) == 2
        all_ev = list(ev.find(APP))
        assert len(all_ev) == 2, "replay must add ONLY the missing row"
        assert sorted(e.event for e in all_ev) == ["buy", "view"]

    def test_create_batch_without_tokens_still_lands(self, events_backend):
        # tokens are optional — an untokened call degrades to plain
        # multi-row insert semantics (at-least-once, server-generated ids)
        ev = events_backend
        ev.init(APP)
        ids = ev.create_batch(
            [_mk("view", "u1", "2026-01-01T00:00:00", target="i1")], APP)
        assert len(ids) == 1
        assert ev.get(ids[0], APP).event == "view"

    def test_time_window_naive_bounds_mean_utc(self, events_backend):
        """A NAIVE window bound means the same instant as the aware-UTC
        stamp on every backend (the shared epoch_us rule) — a daemon
        passing datetime.utcnow() must not shift or crash anywhere."""
        ev = events_backend
        ev.init(APP)
        ev.insert_batch(
            [
                _mk("a", "u1", "2026-01-01T00:00:00"),
                _mk("b", "u1", "2026-01-02T00:00:00"),
            ],
            APP,
        )
        naive = dt.datetime(2026, 1, 2)  # no tzinfo → means UTC
        assert [e.event for e in ev.find(APP, start_time=naive)] == ["b"]
        assert [e.event for e in ev.find(APP, until_time=naive)] == ["a"]

    def test_equal_event_times_order_by_creation(self, events_backend):
        """Ties on event_time order by creation_time everywhere — the
        watermark contract needs ONE deterministic order, not a
        per-backend one."""
        ev = events_backend
        ev.init(APP)
        t = ts("2026-01-01T00:00:00")
        for name, created in (("first", "2026-01-01T10:00:00"),
                              ("second", "2026-01-01T11:00:00")):
            ev.insert(Event(event=name, entity_type="user", entity_id="u1",
                            event_time=t, creation_time=ts(created)), APP)
        assert [e.event for e in ev.find(APP)] == ["first", "second"]
        assert [e.event for e in ev.find(APP, reversed=True)] == \
            ["second", "first"]

    def test_latest_event_time(self, events_backend):
        """Ingest high-watermark (ISSUE 10): max event_time, None when
        empty, channel-scoped — every backend."""
        ev = events_backend
        ev.init(APP)
        assert ev.latest_event_time(APP) is None
        ev.insert_batch(
            [
                _mk("a", "u1", "2026-01-02T00:00:00"),
                _mk("b", "u1", "2026-01-05T00:00:00"),
                _mk("c", "u1", "2026-01-03T00:00:00"),
            ],
            APP,
        )
        assert ev.latest_event_time(APP) == ts("2026-01-05T00:00:00")
        ev.init(APP, channel_id=2)
        assert ev.latest_event_time(APP, 2) is None
        ev.insert(_mk("d", "u1", "2026-02-01T00:00:00"), APP, channel_id=2)
        assert ev.latest_event_time(APP, 2) == ts("2026-02-01T00:00:00")
        assert ev.latest_event_time(APP) == ts("2026-01-05T00:00:00")

    def test_channel_isolation(self, events_backend):
        ev = events_backend
        ev.init(APP)
        ev.init(APP, channel_id=2)
        ev.insert(_mk("view", "u1", "2026-01-01T00:00:00"), APP)
        ev.insert(_mk("buy", "u1", "2026-01-02T00:00:00"), APP, channel_id=2)
        assert [e.event for e in ev.find(APP)] == ["view"]
        assert [e.event for e in ev.find(APP, channel_id=2)] == ["buy"]

    def test_remove(self, events_backend):
        ev = events_backend
        ev.init(APP)
        ev.insert(_mk("view", "u1", "2026-01-01T00:00:00"), APP)
        assert ev.remove(APP) is True
        with pytest.raises(StorageError):
            list(ev.find(APP))

    def test_find_columnar(self, events_backend):
        ev = events_backend
        ev.init(APP)
        ev.insert_batch(
            [
                _mk("rate", "u1", "2026-01-01T00:00:00", target="i1", props={"r": 1.0}),
                _mk("rate", "u2", "2026-01-02T00:00:00", target="i2", props={"r": 2.0}),
            ],
            APP,
        )
        table = ev.find_columnar(APP, event_names=["rate"])
        assert table.num_rows == 2
        assert table.column("entity_id").to_pylist() == ["u1", "u2"]
        props = [json.loads(p) for p in table.column("properties_json").to_pylist()]
        assert [p["r"] for p in props] == [1.0, 2.0]

    def test_find_columnar_unordered_and_projected(self, events_backend):
        ev = events_backend
        ev.init(APP)
        ev.insert_batch(
            [
                _mk("rate", "u2", "2026-01-02T00:00:00", target="i2", props={"r": 2.0}),
                _mk("rate", "u1", "2026-01-01T00:00:00", target="i1", props={"r": 1.0}),
                _mk("view", "u3", "2026-01-03T00:00:00", target="i1"),
            ],
            APP,
        )
        # projection returns exactly the named columns (in that order)
        t = ev.find_columnar(APP, event_names=["rate"],
                             columns=["entity_id", "properties_json"])
        assert t.column_names == ["entity_id", "properties_json"]
        assert sorted(t.column("entity_id").to_pylist()) == ["u1", "u2"]
        # unordered returns the same ROWS, any order
        t2 = ev.find_columnar(APP, event_names=["rate"], ordered=False,
                              columns=["entity_id"])
        assert sorted(t2.column("entity_id").to_pylist()) == ["u1", "u2"]
        # ordered remains the default and sorts by event time
        t3 = ev.find_columnar(APP, event_names=["rate"])
        assert t3.column("entity_id").to_pylist() == ["u1", "u2"]

    def test_insert_columnar(self, events_backend):
        import pyarrow as pa

        ev = events_backend
        ev.init(APP)
        n = ev.insert_columnar(
            pa.table({
                "event": ["rate", "rate", "buy"],
                "entity_type": ["user"] * 3,
                "entity_id": ["u1", "u2", "u1"],
                "target_entity_type": ["item"] * 3,
                "target_entity_id": ["i1", "i2", "i3"],
                "properties_json": ['{"rating": 4.5}', '{"rating": 3.0}', None],
                "event_time_us": [1_700_000_000_000_000 + i for i in range(3)],
            }),
            APP,
        )
        assert n == 3
        got = list(ev.find(APP))
        assert len(got) == 3
        assert sorted(e.event for e in got) == ["buy", "rate", "rate"]
        rate1 = next(e for e in got if e.entity_id == "u1" and e.event == "rate")
        assert rate1.properties.get_double("rating") == 4.5
        assert rate1.event_time is not None
        # ids are store-assigned, unique, and get() resolves them
        ids = {e.event_id for e in got}
        assert len(ids) == 3 and None not in ids
        some = next(iter(ids))
        assert ev.get(some, APP) is not None
        # the bulk rows coexist with row-path inserts on the same scan
        ev.insert(_mk("rate", "u9", "2026-01-05T00:00:00", target="i9",
                      props={"rating": 1.0}), APP)
        t = ev.find_columnar(APP, event_names=["rate"], ordered=False,
                             columns=["entity_id", "properties_json"])
        assert sorted(t.column("entity_id").to_pylist()) == ["u1", "u2", "u9"]
        from predictionio_tpu.data.columnar import numeric_property
        vals = numeric_property(t, "rating")
        assert sorted(vals.tolist()) == [1.0, 3.0, 4.5]

    def test_insert_columnar_validates(self, events_backend):
        import pyarrow as pa

        ev = events_backend
        ev.init(APP)
        with pytest.raises(StorageError):
            ev.insert_columnar(pa.table({"event": ["x"]}), APP)
        with pytest.raises(StorageError):
            ev.insert_columnar(
                pa.table({"event": ["x"], "entity_type": ["u"],
                          "entity_id": ["1"], "bogus": ["y"]}), APP)
        # nulls in a required column are rejected per the event contract
        with pytest.raises(StorageError):
            ev.insert_columnar(
                pa.table({"event": ["x", None], "entity_type": ["u", "u"],
                          "entity_id": ["1", "2"]}), APP)
        # per-row null event times get the server-clock default
        n = ev.insert_columnar(
            pa.table({"event": ["x", "y"], "entity_type": ["u", "u"],
                      "entity_id": ["1", "2"],
                      "event_time_us": pa.array([1_700_000_000_000_000,
                                                 None])}), APP)
        assert n == 2
        assert all(e.event_time is not None for e in ev.find(APP))

    def test_insert_columnar_null_event_time_is_the_rows_creation_time(
            self, events_backend):
        """A null ``event_time_us`` takes its own row's creation time, as a
        missing column does, not the server clock at insert."""
        import pyarrow as pa

        ev = events_backend
        ev.init(APP)
        old = 1_600_000_000_000_000  # 2020: far from any "now"
        ev.insert_columnar(
            pa.table({"event": ["x", "y", "z"], "entity_type": ["u"] * 3,
                      "entity_id": ["1", "2", "3"],
                      "event_time_us": pa.array([old + 7, None, None]),
                      "creation_time_us": pa.array([old, old + 1, None])}),
            APP)
        got = {e.entity_id: e for e in ev.find(APP)}
        assert got["1"].event_time == got["1"].creation_time \
            + dt.timedelta(microseconds=7)
        assert got["2"].event_time == got["2"].creation_time
        assert got["2"].creation_time.year == 2020
        # no creation time either: both are the one server-clock reading
        assert got["3"].event_time == got["3"].creation_time
        assert got["3"].creation_time.year > 2020

    def test_find_columnar_refuses_an_unknown_column(self, events_backend):
        ev = events_backend
        ev.init(APP)
        ev.insert(_mk("rate", "u1", "2026-01-01T00:00:00", target="i1"), APP)
        with pytest.raises(StorageError, match="unknown column.*'rating'"):
            ev.find_columnar(APP, columns=["entity_id", "rating"])
        with pytest.raises(StorageError, match="unknown column"):
            ev.find_columnar(APP, columns=["entity_id", "rating"],
                             ordered=False, event_names=["none-such"])

    def test_aggregate_properties(self, events_backend):
        ev = events_backend
        ev.init(APP)
        ev.insert_batch(
            [
                _mk("$set", "i1", "2026-01-01T00:00:00", etype="item",
                    props={"cat": "a", "price": 10}),
                _mk("$set", "i1", "2026-01-02T00:00:00", etype="item", props={"price": 12}),
                _mk("$set", "i2", "2026-01-01T00:00:00", etype="item", props={"cat": "b"}),
                _mk("$delete", "i2", "2026-01-03T00:00:00", etype="item"),
                _mk("view", "u1", "2026-01-02T00:00:00"),
            ],
            APP,
        )
        props = ev.aggregate_properties(APP, entity_type="item")
        assert set(props) == {"i1"}
        assert props["i1"].to_dict() == {"cat": "a", "price": 12}


# --------------------------------------------------------------------------
# Metadata contract
# --------------------------------------------------------------------------

@pytest.fixture(params=["memory", "sqlite", "pioserver"])
def meta_backend(request, tmp_path):
    if request.param == "pioserver":
        remote, cleanup = _remote_pair(tmp_path)

        class B:
            apps = remote.apps()
            keys = remote.access_keys()
            channels = remote.channels()
            instances = remote.engine_instances()
            models = remote.models()

        yield B
        cleanup()
    elif request.param == "memory":
        from predictionio_tpu.data.storage import memory as m

        class B:
            apps = m.MemoryApps()
            keys = m.MemoryAccessKeys()
            channels = m.MemoryChannels()
            instances = m.MemoryEngineInstances()
            models = m.MemoryModels()

        yield B
    else:
        from predictionio_tpu.data.storage.sqlite import SQLiteClient

        client = SQLiteClient(str(tmp_path / "meta.db"))

        class B:
            apps = client.apps()
            keys = client.access_keys()
            channels = client.channels()
            instances = client.engine_instances()
            models = client.models()

        yield B
        client.close()


class TestMetadataContract:
    def test_apps_crud(self, meta_backend):
        apps = meta_backend.apps
        aid = apps.insert(App(id=None, name="myapp", description="d"))
        assert aid is not None
        assert apps.get(aid).name == "myapp"
        assert apps.get_by_name("myapp").id == aid
        assert apps.insert(App(id=None, name="myapp")) is None  # duplicate name
        assert apps.update(App(id=aid, name="renamed", description=None))
        assert apps.get(aid).name == "renamed"
        assert [a.id for a in apps.get_all()] == [aid]
        assert apps.delete(aid) is True
        assert apps.get(aid) is None

    def test_access_keys(self, meta_backend):
        keys = meta_backend.keys
        k = keys.insert(AccessKey(key="", app_id=3, events=("view",)))
        assert k
        got = keys.get(k)
        assert got.app_id == 3 and got.events == ("view",)
        assert keys.get_by_app_id(3)[0].key == k
        assert keys.delete(k) is True
        assert keys.get(k) is None

    def test_channels(self, meta_backend):
        ch = meta_backend.channels
        cid = ch.insert(Channel(id=None, name="live", app_id=3))
        assert cid is not None
        assert ch.get(cid).name == "live"
        # invalid name (too long / bad chars) rejected
        assert ch.insert(Channel(id=None, name="x" * 17, app_id=3)) is None
        assert ch.insert(Channel(id=None, name="bad name", app_id=3)) is None
        # duplicate per app rejected
        assert ch.insert(Channel(id=None, name="live", app_id=3)) is None
        assert [c.id for c in ch.get_by_app_id(3)] == [cid]
        assert ch.delete(cid) is True

    def test_engine_instances_lifecycle(self, meta_backend):
        insts = meta_backend.instances

        def mk(status, t):
            return EngineInstance(
                id=None, status=status, start_time=ts(t), end_time=None,
                engine_id="e1", engine_version="v1", engine_variant="default",
                engine_factory="my.Factory",
                algorithms_params='[{"name":"als","params":{"rank":8}}]',
            )

        i1 = insts.insert(mk("TRAINING", "2026-01-01T00:00:00"))
        i2 = insts.insert(mk("COMPLETED", "2026-01-02T00:00:00"))
        i3 = insts.insert(mk("COMPLETED", "2026-01-03T00:00:00"))
        assert insts.get_latest_completed("e1", "v1", "default").id == i3
        assert [i.id for i in insts.get_completed("e1", "v1", "default")] == [i3, i2]
        inst = insts.get(i1)
        inst.status = "FAILED"
        inst.end_time = ts("2026-01-01T01:00:00")
        assert insts.update(inst)
        assert insts.get(i1).status == "FAILED"
        assert insts.get(i1).end_time == ts("2026-01-01T01:00:00")
        assert json.loads(insts.get(i2).algorithms_params)[0]["params"]["rank"] == 8
        assert insts.delete(i1)

    def test_models_blob(self, meta_backend):
        models = meta_backend.models
        models.insert(Model(id="m1", models=b"\x00\x01binary"))
        assert models.get("m1").models == b"\x00\x01binary"
        models.insert(Model(id="m1", models=b"v2"))  # overwrite
        assert models.get("m1").models == b"v2"
        assert models.delete("m1") is True
        assert models.get("m1") is None


# --------------------------------------------------------------------------
# localfs models + registry
# --------------------------------------------------------------------------

def test_localfs_models(tmp_path):
    from predictionio_tpu.data.storage.localfs_models import LocalFSModels

    m = LocalFSModels(str(tmp_path / "models"))
    m.insert(Model(id="engine/inst1", models=b"blob"))
    assert m.get("engine/inst1").models == b"blob"
    assert m.delete("engine/inst1") is True
    assert m.get("engine/inst1") is None


def test_storage_registry_defaults(pio_home):
    from predictionio_tpu.data.storage import Storage

    s = Storage()
    assert s.verify() == {
        "METADATA": "sqlite", "EVENTDATA": "sqlite", "MODELDATA": "localfs"
    }
    apps = s.get_apps()
    aid = apps.insert(App(id=None, name="regapp"))
    ev = s.get_events()
    ev.init(aid)
    ev.insert(_mk("view", "u1", "2026-01-01T00:00:00"), aid)
    assert len(list(ev.find(aid))) == 1
    s.close()


def test_storage_registry_parquet_eventdata(pio_home, monkeypatch):
    from predictionio_tpu.data.storage import Storage

    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE", "PARQUET")
    s = Storage()
    assert s.verify()["EVENTDATA"] == "parquetlog"
    s.close()


def test_storage_registry_unknown_type(pio_home, monkeypatch):
    from predictionio_tpu.data.storage import Storage

    monkeypatch.setenv("PIO_STORAGE_SOURCES_BOGUS_TYPE", "nosuch")
    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_METADATA_SOURCE", "BOGUS")
    s = Storage()
    with pytest.raises(StorageError):
        s.get_apps()


def test_pioserver_selected_by_env_alone(pio_home, monkeypatch, tmp_path):
    """The reference's defining storage property: swap to an
    out-of-process backend purely via PIO_STORAGE_* env config."""
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.remote import StorageServer
    from predictionio_tpu.data.storage.sqlite import SQLiteClient

    client = SQLiteClient(str(tmp_path / "served.db"))

    Hosted = _hosted(client)

    srv = StorageServer(Hosted, host="127.0.0.1", port=0)
    srv.start()
    try:
        monkeypatch.setenv("PIO_STORAGE_SOURCES_REMOTE_TYPE", "pioserver")
        monkeypatch.setenv("PIO_STORAGE_SOURCES_REMOTE_HOSTS", "127.0.0.1")
        monkeypatch.setenv("PIO_STORAGE_SOURCES_REMOTE_PORTS", str(srv.port))
        monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE",
                           "REMOTE")
        monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_METADATA_SOURCE",
                           "REMOTE")
        s = Storage()
        app_id = s.get_apps().insert(App(id=None, name="remoteapp"))
        assert s.get_apps().get_by_name("remoteapp").id == app_id
        ev = s.get_events()
        ev.init(app_id)
        eid = ev.insert(_mk("rate", "u1", "2024-01-01T00:00:00",
                            target="i1", props={"rating": 5}), app_id)
        got = ev.get(eid, app_id)
        assert got.properties["rating"] == 5
        # Data really lives in the SERVED sqlite, not in-process.
        direct = client.events()
        assert direct.get(eid, app_id) is not None
        s.close()
    finally:
        srv.stop()
        client.close()


def test_event_server_over_remote_storage(pio_home, monkeypatch, tmp_path):
    """Deployment-shaped composition: the EVENT server process keeps its
    data in a separate STORAGE server process (upstream: event server ->
    HBase/JDBC).  Ingest over HTTP, verify the bytes landed in the served
    store, then read back through the event server."""
    import urllib.request

    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import AccessKey
    from predictionio_tpu.data.storage.remote import StorageServer
    from predictionio_tpu.data.storage.sqlite import SQLiteClient
    from predictionio_tpu.server.event_server import EventServer

    backing = SQLiteClient(str(tmp_path / "backing.db"))

    Hosted = _hosted(backing)

    ss = StorageServer(Hosted, host="127.0.0.1", port=0)
    ss.start()
    try:
        monkeypatch.setenv("PIO_STORAGE_SOURCES_REMOTE_TYPE", "pioserver")
        monkeypatch.setenv("PIO_STORAGE_SOURCES_REMOTE_HOSTS", "127.0.0.1")
        monkeypatch.setenv("PIO_STORAGE_SOURCES_REMOTE_PORTS", str(ss.port))
        monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE",
                           "REMOTE")
        monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_METADATA_SOURCE",
                           "REMOTE")
        storage = Storage()
        from predictionio_tpu.data.storage.base import App

        app_id = storage.get_apps().insert(App(id=None, name="viaremote"))
        storage.get_events().init(app_id)
        key = storage.get_access_keys().insert(AccessKey.generate(app_id))
        es = EventServer(storage, host="127.0.0.1", port=0)
        es.start()
        try:
            url = (f"http://127.0.0.1:{es.port}/events.json"
                   f"?accessKey={key}")
            req = urllib.request.Request(
                url, data=json.dumps({
                    "event": "rate", "entityType": "user", "entityId": "u1",
                    "targetEntityType": "item", "targetEntityId": "i1",
                    "properties": {"rating": 5}}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=20) as r:
                eid = json.loads(r.read())["eventId"]
            # The event physically lives in the BACKING sqlite.
            assert backing.events().get(eid, app_id) is not None
            with urllib.request.urlopen(url + "&limit=-1", timeout=20) as r:
                evs = json.loads(r.read())
            assert len(evs) == 1 and evs[0]["properties"]["rating"] == 5
        finally:
            es.stop()
        storage.close()
    finally:
        ss.stop()
        backing.close()


# --------------------------------------------------------------------------
# Remote streaming + auth (round-4: cursor-paginated scans, shared secret)
# --------------------------------------------------------------------------

class TestRemoteStreaming:
    def test_scan_streams_past_the_reply_cap(self, tmp_path, monkeypatch):
        """A scan bigger than the per-message cap succeeds because it is
        cursor-paginated — the legacy one-shot find RPC on the same data
        blows the cap (round-3 weakness: find materialized everything)."""
        from predictionio_tpu.data.storage import remote as remote_mod

        remote, cleanup = _remote_pair(tmp_path)
        try:
            events = remote.events()
            events.init(APP)
            n = 500
            events.insert_batch(
                [_mk("rate", f"u{j}", "2024-01-01T00:00:00", target=f"i{j}",
                     props={"rating": float(j % 5), "pad": "x" * 200})
                 for j in range(n)], APP)
            # Cap a message at 64 KB: 500 padded events in one reply far
            # exceed it, single 50-event pages (~20 KB) do not.
            monkeypatch.setattr(remote_mod, "_MAX_MESSAGE", 64 << 10)
            got = list(remote.stream_find(APP, _batch=50))
            assert len(got) == n
            assert {e.entity_id for e in got} == {f"u{j}" for j in range(n)}
            with pytest.raises(StorageError):
                remote.call("events.find", APP)  # one-shot blows the cap
        finally:
            monkeypatch.undo()
            cleanup()

    def test_abandoned_scan_frees_the_connection(self, tmp_path):
        remote, cleanup = _remote_pair(tmp_path)
        try:
            events = remote.events()
            events.init(APP)
            events.insert_batch(
                [_mk("view", f"u{j}", "2024-01-01T00:00:00")
                 for j in range(50)], APP)
            it = remote.stream_find(APP, _batch=10)
            next(it), next(it)
            it.close()  # break out mid-scan → find_close + conn back to pool
            # The pinned connection really went back: the idle pool is full
            # again (a leak would pass a weaker serve-more-RPCs check,
            # since _lease mints overflow connections on demand).
            assert len(remote._idle) == remote._pool_size
            assert len(list(events.find(APP))) == 50
            assert len(remote._idle) == remote._pool_size
        finally:
            cleanup()


class TestRemoteAuth:
    def _secure_pair(self, tmp_path, server_secret, client_secret):
        from predictionio_tpu.data.storage.remote import (
            RemoteClient, StorageServer)
        from predictionio_tpu.data.storage.sqlite import SQLiteClient

        client = SQLiteClient(str(tmp_path / "served.db"))
        srv = StorageServer(_hosted(client), host="127.0.0.1", port=0,
                            secret=server_secret)
        srv.start()
        remote = RemoteClient("127.0.0.1", srv.port, secret=client_secret)

        def cleanup():
            remote.close()
            srv.stop()
            client.close()

        return remote, cleanup

    def test_matching_secret_round_trips(self, tmp_path):
        remote, cleanup = self._secure_pair(tmp_path, "hunter2", "hunter2")
        try:
            events = remote.events()
            events.init(APP)
            eid = events.insert(
                _mk("rate", "u1", "2024-01-01T00:00:00", target="i1",
                    props={"rating": 4}), APP)
            assert events.get(eid, APP).properties["rating"] == 4
        finally:
            cleanup()

    def test_client_secret_against_unsecured_server(self, tmp_path):
        # Misconfiguration (server started without --secret) must not
        # produce cryptic RPC failures: the server acks the handshake.
        remote, cleanup = self._secure_pair(tmp_path, None, "hunter2")
        try:
            events = remote.events()
            events.init(APP)
            eid = events.insert(
                _mk("rate", "u1", "2024-01-01T00:00:00", target="i1",
                    props={"rating": 3}), APP)
            assert events.get(eid, APP).properties["rating"] == 3
        finally:
            cleanup()

    def test_wrong_secret_rejected(self, tmp_path):
        from predictionio_tpu.data.storage.remote import RemoteBackendError

        remote, cleanup = self._secure_pair(tmp_path, "hunter2", "wrong")
        try:
            with pytest.raises(RemoteBackendError, match="auth"):
                remote.events().get("nope", APP)
        finally:
            cleanup()

    def test_missing_secret_rejected(self, tmp_path):
        from predictionio_tpu.data.storage.remote import RemoteBackendError

        remote, cleanup = self._secure_pair(tmp_path, "hunter2", None)
        try:
            with pytest.raises(RemoteBackendError):
                remote.events().get("nope", APP)
        finally:
            cleanup()
