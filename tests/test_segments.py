"""Columnar segment store (ISSUE 17): CRC-framed blocks, torn-tail
recovery, crash-safe seal/compaction, coverage honesty, and the
WindowedEventStore delta read that rides it."""

import datetime as dt
import json
from types import SimpleNamespace

import pyarrow as pa
import pytest

from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.columnar import (
    SEGMENT_SUFFIX,
    SegmentDiskPressure,
    SegmentStore,
    _payloads_to_table,
    recover_segment_tail,
    resolve_segment_root,
)
from predictionio_tpu.resilience import faults
from predictionio_tpu.resilience.faults import FaultInjected

UTC = dt.timezone.utc
APP = 7


def _ev(i, t_s, name="view"):
    return Event(
        event=name,
        entity_type="user",
        entity_id=f"u{i}",
        target_entity_type="item",
        target_entity_id=f"i{i}",
        properties=DataMap({}),
        event_time=dt.datetime.fromtimestamp(t_s, UTC),
    )


def _store(root, clk, **kw):
    kw.setdefault("roll_bytes", 1 << 30)
    kw.setdefault("roll_s", 1e9)
    kw.setdefault("grace_s", 0.0)
    kw.setdefault("compact_trigger", 0)  # tests drive compaction directly
    return SegmentStore(root, clock=lambda: clk.t, **kw)


def _seg_files(root):
    return sorted(p.name for p in (root / "app_7" / "default").iterdir()
                  if p.suffix == SEGMENT_SUFFIX)


def _manifest(root):
    return json.loads((root / "app_7" / "default" / "manifest.json")
                      .read_text())


@pytest.fixture()
def clk():
    return SimpleNamespace(t=1000.0)


# --------------------------------------------------------------------------
# Roundtrip + coverage honesty
# --------------------------------------------------------------------------


def test_append_seal_read_roundtrip(tmp_path, clk):
    st = _store(tmp_path, clk)
    st.append_events(APP, None, [_ev(0, 1001), _ev(1, 1002)])
    st.append_events(APP, None, [_ev(2, 1003, name="buy")])
    clk.t = 1100.0
    st.seal_all()
    got = st.read_window(APP, None, int(1000e6), 1 << 62)
    assert got is not None
    table, covered = got
    assert covered == int(1100e6)
    assert table.num_rows == 3
    # filters are find_columnar parity
    table, _ = st.read_window(APP, None, int(1000e6), 1 << 62,
                              event_names=["buy"])
    assert table.num_rows == 1
    table, _ = st.read_window(APP, None, int(1000e6), 1 << 62,
                              entity_id="u0")
    assert table.num_rows == 1
    # a read starting BELOW the floor cannot be proven — full fallback
    assert st.read_window(APP, None, int(900e6), 1 << 62) is None


def test_unsealed_rows_are_never_claimed(tmp_path, clk):
    st = _store(tmp_path, clk)
    st.append_events(APP, None, [_ev(0, 1001)])
    clk.t = 1100.0
    st.seal_all()
    st.append_events(APP, None, [_ev(1, 1150)])  # active, not sealed
    got = st.read_window(APP, None, int(1000e6), 1 << 62)
    table, covered = got
    assert covered == int(1100e6)  # coverage stops at the active window
    assert table.num_rows == 1  # the active row is the PRIMARY's to serve


def test_late_event_ratchets_floor(tmp_path, clk):
    """An event older than the open window would falsify the sealed
    ranges' completeness claim — the floor ratchets up (coverage wiped)
    rather than lie; reads fall back to the primary store."""
    st = _store(tmp_path, clk)
    st.append_events(APP, None, [_ev(0, 1001)])
    clk.t = 1100.0
    st.seal_all()
    assert st.read_window(APP, None, int(1000e6), 1 << 62) is not None
    st.append_events(APP, None, [_ev(1, 1050)])  # 1050 < window start 1100
    assert _manifest(tmp_path)["floorUs"] == int(1100e6)
    assert st.read_window(APP, None, int(1000e6), 1 << 62) is None


def test_straggler_teed_into_next_window_is_still_found(tmp_path, clk):
    """Rows land by DATA range, not window label: an event teed slightly
    after its stamp (but still >= window start) seals into the next
    window; the read must overlap by min/max, not the label."""
    st = _store(tmp_path, clk)
    st.append_events(APP, None, [_ev(0, 1001)])
    clk.t = 1100.0
    st.seal_all()
    # stamped inside window 2, sealed in window 2 — plus one stamped
    # EXACTLY at a boundary the first window claimed up to
    st.append_events(APP, None, [_ev(1, 1100), _ev(2, 1150)])
    clk.t = 1200.0
    st.seal_all()
    table, covered = st.read_window(APP, None, int(1000e6), 1 << 62)
    assert covered == int(1200e6) and table.num_rows == 3


# --------------------------------------------------------------------------
# Torn tails + CRC
# --------------------------------------------------------------------------


def test_torn_tail_truncated_counted_idempotent(tmp_path, clk):
    st = _store(tmp_path, clk)
    st.append_events(APP, None, [_ev(0, 1001)])
    st.append_events(APP, None, [_ev(1, 1002)])
    clk.t = 1100.0
    st.seal_all()
    seg = tmp_path / "app_7" / "default" / _seg_files(tmp_path)[0]
    good = seg.read_bytes()
    # a torn write: half the last block's bytes survived the crash
    seg.write_bytes(good[: len(good) - 7])
    rec = recover_segment_tail(seg)
    assert rec["blocks"] == 1 and rec["rows"] == 1
    assert rec["torn_bytes"] > 0
    assert seg.stat().st_size == rec["valid_bytes"]
    rec2 = recover_segment_tail(seg)  # second pass: clean, no-op
    assert rec2["torn_bytes"] == 0 and rec2["blocks"] == 1


def test_corrupt_crc_stops_scan(tmp_path, clk):
    st = _store(tmp_path, clk)
    st.append_events(APP, None, [_ev(0, 1001)])
    st.append_events(APP, None, [_ev(1, 1002)])
    clk.t = 1100.0
    st.seal_all()
    seg = tmp_path / "app_7" / "default" / _seg_files(tmp_path)[0]
    raw = bytearray(seg.read_bytes())
    raw[-3] ^= 0xFF  # flip a bit inside the LAST block's crc
    seg.write_bytes(bytes(raw))
    rec = recover_segment_tail(seg, truncate=False)
    assert rec["blocks"] == 1  # scan stopped at the bad CRC


def test_damaged_sealed_segment_means_full_fallback(tmp_path, clk):
    """A sealed file whose recoverable rows disagree with the manifest is
    a broken completeness claim — the reader answers None (primary-store
    fallback), never a silently short slice."""
    st = _store(tmp_path, clk)
    st.append_events(APP, None, [_ev(0, 1001), _ev(1, 1002)])
    st.append_events(APP, None, [_ev(2, 1003)])
    clk.t = 1100.0
    st.seal_all()
    seg = tmp_path / "app_7" / "default" / _seg_files(tmp_path)[0]
    seg.write_bytes(seg.read_bytes()[:-5])
    assert st.read_window(APP, None, int(1000e6), 1 << 62) is None


def test_crashed_active_window_is_discarded_at_open(tmp_path, clk):
    """kill -9 with an open active window: the tail is recovered and
    MEASURED, then discarded — its window was never claimed and the
    primary store is authoritative, so salvaging rows that raced the
    crash could break a later seal's completeness claim."""
    st = _store(tmp_path, clk)
    st.append_events(APP, None, [_ev(0, 1001)])
    clk.t = 1100.0
    st.seal_all()
    st.append_events(APP, None, [_ev(1, 1150)])
    # simulate kill -9: no seal, no close — reopen the dir cold
    st2 = _store(tmp_path, clk)
    st2._dir(APP, None)  # triggers _load_and_recover
    leftovers = [p for p in (tmp_path / "app_7" / "default").iterdir()
                 if p.suffix == ".tmp"]
    assert leftovers == []
    table, covered = st2.read_window(APP, None, int(1000e6), 1 << 62)
    assert table.num_rows == 1 and covered == int(1100e6)


_KILL9_WRITER = """
import sys, time
from predictionio_tpu.data.columnar import SegmentStore
from predictionio_tpu.data.event import Event
st = SegmentStore(sys.argv[1], roll_bytes=1 << 20, roll_s=0.05, grace_s=0.0)
b = 0
while True:
    st.append_events(7, None, [
        Event(event='view', entity_type='user', entity_id=f'su{b}_{j}',
              target_entity_type='item', target_entity_id=f'si{j}')
        for j in range(50)])
    b += 1
    print(b, flush=True)
    time.sleep(0.005)
"""


def test_kill9_of_a_live_writer_leaves_every_sealed_claim_readable(
        tmp_path, kill9_after):
    """A REAL ``kill -9`` of a process that appends and seals on the wall
    clock (the simulated crashes above reopen a store that stopped at a
    chosen line): reopening sweeps the torn active tail, every sealed
    file is CRC-clean and holds the rows its manifest entry claims, and
    the window read returns every row inside coverage."""
    # 40 appends at 5 ms apart, windows of 50 ms: several have sealed
    kill9_after(_KILL9_WRITER, (tmp_path,), lambda b: b >= 40)
    st = SegmentStore(tmp_path)
    st._dir(APP, None)  # reopening is the recovery
    status, = st.status()
    assert status["segments"] >= 2
    seg_dir = tmp_path / "app_7" / "default"
    assert [p for p in seg_dir.iterdir() if p.suffix == ".tmp"] == []
    manifest = _manifest(tmp_path)
    file_rows = below_floor = 0
    for seg in manifest["segments"]:
        info = recover_segment_tail(seg_dir / seg["file"], truncate=False)
        assert info["rows"] == seg["rows"], seg["file"]
        assert info["torn_bytes"] == 0, seg["file"]
        file_rows += info["rows"]
        # rows stamped before the first window opened lie under floorUs:
        # in the file, outside coverage (the primary store has them)
        below_floor += sum(
            1 for t in _payloads_to_table(info["payloads"])
            .column("event_time_us").to_pylist() if t < manifest["floorUs"])
    assert file_rows == status["rows"] > 0
    table, covered = st.read_window(APP, None, status["floorUs"],
                                    status["coveredUntilUs"])
    assert covered == status["coveredUntilUs"]
    assert table.num_rows == file_rows - below_floor
    st.close()


# --------------------------------------------------------------------------
# Compaction: merge + crash at every commit boundary
# --------------------------------------------------------------------------


def _three_small_segments(tmp_path, clk):
    st = _store(tmp_path, clk)
    for k in range(3):
        st.append_events(APP, None,
                         [_ev(2 * k, 1001 + 100 * k),
                          _ev(2 * k + 1, 1002 + 100 * k)])
        clk.t = 1100.0 + 100 * k
        st.seal_all()
    assert len(_seg_files(tmp_path)) == 3
    return st


def test_compaction_merges_and_preserves_reads(tmp_path, clk):
    st = _three_small_segments(tmp_path, clk)
    before, cov_before = st.read_window(APP, None, int(1000e6), 1 << 62)
    stats = st.compact(APP, None)
    assert stats == {"runs": 1, "segments_in": 3, "segments_out": 1}
    assert len(_seg_files(tmp_path)) == 1
    m = _manifest(tmp_path)
    assert [e["file"] for e in m["segments"]] == _seg_files(tmp_path)
    after, cov_after = st.read_window(APP, None, int(1000e6), 1 << 62)
    assert cov_after == cov_before
    assert after.sort_by("event_time_us").equals(
        before.sort_by("event_time_us"))


@pytest.mark.parametrize("point", ["segment.compact",
                                   "segment.compact.commit",
                                   "segment.compact.cleanup"])
def test_compaction_crash_leaves_one_readable_set(tmp_path, clk, point):
    """Kill compaction at each boundary: after 'restart' (fresh store →
    orphan sweep) the manifest references exactly the files on disk and
    the read answers ALL six rows — old set or new set, never both,
    never neither."""
    st = _three_small_segments(tmp_path, clk)
    try:
        faults.install(f"{point}:error:1.0")
        with pytest.raises(FaultInjected):
            st.compact(APP, None)
    finally:
        faults.clear()
    st2 = _store(tmp_path, clk)
    st2._dir(APP, None)  # restart: sweep whatever the crash stranded
    m = _manifest(tmp_path)
    assert [e["file"] for e in m["segments"]] == _seg_files(tmp_path)
    table, covered = st2.read_window(APP, None, int(1000e6), 1 << 62)
    assert table.num_rows == 6, f"rows lost after crash at {point}"
    assert sorted(table.column("entity_id").to_pylist()) == \
        [f"u{i}" for i in range(6)]


# --------------------------------------------------------------------------
# Kill at every fsync boundary of the append/seal path
# --------------------------------------------------------------------------


@pytest.mark.parametrize("point", ["segment.append", "segment.seal",
                                   "segment.manifest"])
def test_seal_path_crash_never_overclaims(tmp_path, clk, point):
    """Crash the writer at each append/seal/manifest boundary.  The
    invariant is HONESTY, not durability: whatever survived, a reopened
    store either proves coverage (and then has every claimed row) or
    declines — the sealed generation A stays intact either way."""
    st = _store(tmp_path, clk)
    st.append_events(APP, None, [_ev(0, 1001)])  # generation A
    clk.t = 1100.0
    st.seal_all()
    try:
        faults.install(f"{point}:error:1.0")
        with pytest.raises(FaultInjected):
            st.append_events(APP, None, [_ev(1, 1150)])
            clk.t = 1200.0
            st.seal_all()
    finally:
        faults.clear()
    st2 = _store(tmp_path, clk)
    st2._dir(APP, None)
    m = _manifest(tmp_path)
    assert [e["file"] for e in m["segments"]] == _seg_files(tmp_path)
    got = st2.read_window(APP, None, int(1000e6), 1 << 62)
    assert got is not None
    table, covered = got
    claimed = table.filter(
        pa.compute.less(table.column("event_time_us"), int(1100e6)))
    assert claimed.num_rows == 1  # generation A never lost or duplicated
    # and nothing beyond what the manifest claims is served
    assert covered <= m["activeStartUs"]


def test_disk_pressure_raises_before_write(tmp_path, clk):
    st = _store(tmp_path, clk, min_free_bytes=1 << 60)
    with pytest.raises(SegmentDiskPressure):
        st.append_events(APP, None, [_ev(0, 1001)])
    st2 = _store(tmp_path, clk, min_free_bytes=1)
    st2.append_events(APP, None, [_ev(0, 1001)])  # plenty free → fine


def test_resolve_segment_root_env(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_SEGMENT_DIR", str(tmp_path / "x"))
    assert resolve_segment_root() == tmp_path / "x"
    monkeypatch.setenv("PIO_SEGMENTS", "off")
    assert resolve_segment_root() is None
    monkeypatch.delenv("PIO_SEGMENTS")
    monkeypatch.delenv("PIO_SEGMENT_DIR")
    monkeypatch.setenv("PIO_HOME", str(tmp_path / "home"))
    assert resolve_segment_root() == tmp_path / "home" / "segments"


# --------------------------------------------------------------------------
# The delta read that rides it (WindowedEventStore)
# --------------------------------------------------------------------------


def test_windowed_delta_read_serves_covered_prefix_from_segments(
        pio_home, tmp_path, monkeypatch, clk):
    """End-to-end read path: primary store + teed segments.  The
    windowed read must return EXACTLY what a pure primary read returns —
    segment slice for the covered prefix, primary tail for the rest."""
    from predictionio_tpu.data.storage import App, get_storage
    from predictionio_tpu.data.store import EventStore, WindowedEventStore

    seg_root = tmp_path / "segs"
    monkeypatch.setenv("PIO_SEGMENT_DIR", str(seg_root))
    storage = get_storage()
    app_id = storage.get_apps().insert(App(id=None, name="segapp"))
    storage.get_events().init(app_id)
    covered = [_ev(i, 1001 + i) for i in range(10)]
    tail = [_ev(100 + i, 2010 + i) for i in range(3)]
    storage.get_events().insert_batch(covered + tail, app_id)
    # tee ONLY the covered prefix (the tail is "younger than the last
    # seal" — exactly the real server's steady state)
    st = _store(seg_root, clk)
    st.append_events(app_id, None, covered)
    clk.t = 2000.0
    st.seal_all()

    start = dt.datetime.fromtimestamp(1000, UTC)
    windowed = WindowedEventStore(storage, start, None)
    got = windowed.find_columnar("segapp")
    want = EventStore(storage).find_columnar("segapp", start_time=start)
    assert got.num_rows == want.num_rows == 13
    assert got.column("entity_id").to_pylist() == \
        want.column("entity_id").to_pylist()
    # prove the slice actually came from segments: poison the primary
    # window the segments cover and read again — identical rows
    sliced = windowed._segment_slice(
        "segapp", None, {"start_time": start, "until_time": None})
    assert sliced is not None and sliced[0].num_rows == 10

    # and with segments disabled the same read falls back cleanly
    monkeypatch.setenv("PIO_SEGMENTS", "off")
    fallback = WindowedEventStore(storage, start, None)
    tbl = fallback.find_columnar("segapp")
    assert tbl.num_rows == 13
