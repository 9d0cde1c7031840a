"""Append-only Parquet event log — the batch-training-optimized event store.

Reference analogue: storage/hbase/ (HBPEvents' full-scan RDD reads) —
SURVEY.md §2.1.  Where HBase serves Spark `newAPIHadoopRDD` scans, this
backend serves columnar `pyarrow` scans that feed host-sharded `jax.Array`
construction directly (zero row materialization on the training path).

Layout: ``<root>/app_<id>/<channel|default>/part-<uuid>.parquet``; one file
per flushed batch.  Deletion of single events rewrites the owning part file
(rare path); `remove` drops the directory.
"""

from __future__ import annotations

import datetime as _dt
import json
import threading
import uuid
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import base
from predictionio_tpu.data.storage.base import EVENT_ARROW_SCHEMA

__all__ = ["ParquetEvents"]


def _us(dt: _dt.datetime) -> int:
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=_dt.timezone.utc)
    return int(dt.timestamp() * 1_000_000)


class ParquetEvents(base.Events):
    """Single-event inserts are buffered in memory and flushed as one part
    file per :data:`FLUSH_THRESHOLD` events (or on any read/close) — an
    event-per-file layout would make every scan O(#events) file opens."""

    FLUSH_THRESHOLD = 256

    def __init__(self, root: str):
        self.root = Path(root)
        self._lock = threading.RLock()
        self._pending: Dict[tuple, List[Event]] = {}
        # Bulk-ingest dedup index (ISSUE 17): token-derived event ids
        # already on disk, per (app, channel).  Seeded lazily with ONE
        # projected scan of the event_id column, then maintained
        # incrementally — parquet has no primary key to conflict on, so
        # create_batch's per-item exactly-once lives here.
        self._batch_ids: Dict[tuple, set] = {}

    def _dir(self, app_id: int, channel_id: Optional[int]) -> Path:
        chan = "default" if channel_id is None else str(channel_id)
        return self.root / f"app_{app_id}" / chan

    def init(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        self._dir(app_id, channel_id).mkdir(parents=True, exist_ok=True)
        return True

    def remove(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        import shutil

        with self._lock:
            self._pending.pop((app_id, channel_id), None)
            self._batch_ids.pop((app_id, channel_id), None)
            d = self._dir(app_id, channel_id)
            if not d.exists():
                return False
            shutil.rmtree(d)
            return True

    def close(self) -> None:
        self.flush()

    def _check_init(self, app_id: int, channel_id: Optional[int]) -> Path:
        d = self._dir(app_id, channel_id)
        if not d.is_dir():
            raise base.StorageError(
                f"Events store for app {app_id} channel {channel_id} not initialized."
            )
        return d

    def insert(self, event: Event, app_id: int, channel_id: Optional[int] = None) -> str:
        self._check_init(app_id, channel_id)
        eid = uuid.uuid4().hex  # store-assigned, any client id ignored
        with self._lock:
            pending = self._pending.setdefault((app_id, channel_id), [])
            pending.append(event.with_event_id(eid))
            if len(pending) >= self.FLUSH_THRESHOLD:
                self._flush(app_id, channel_id)
        return eid

    def insert_batch(
        self, events: Sequence[Event], app_id: int, channel_id: Optional[int] = None
    ) -> List[str]:
        d = self._check_init(app_id, channel_id)
        stamped = []
        ids = []
        for ev in events:
            eid = uuid.uuid4().hex
            ids.append(eid)
            stamped.append(ev.with_event_id(eid))
        table = base.events_to_arrow(stamped)
        with self._lock:
            pq.write_table(table, d / f"part-{uuid.uuid4().hex}.parquet")
        return ids

    def _seen_batch_ids(self, d: Path, app_id: int,
                        channel_id: Optional[int]) -> set:
        """Token-derived ids already stored (caller holds the lock)."""
        key = (app_id, channel_id)
        seen = self._batch_ids.get(key)
        if seen is None:
            table = self._scan(d, app_id, channel_id, columns=["event_id"])
            seen = set()
            if table is not None:
                for eid in table["event_id"].to_pylist():
                    if eid and eid.startswith("bt"):
                        seen.add(eid)
            self._batch_ids[key] = seen
        return seen

    def create_batch(
        self, events: Sequence[Event], app_id: int,
        channel_id: Optional[int] = None,
        tokens: Optional[Sequence[str]] = None,
    ) -> List[str]:
        """One part file for the not-yet-landed rows; rows whose derived
        id is already on disk (prior partial landing) are skipped, so a
        replayed batch never duplicates."""
        d = self._check_init(app_id, channel_id)
        if tokens is None:
            # One uuid4 per BATCH, not per event (see sqlite.create_batch).
            pre = uuid.uuid4().hex
            tokens = [f"{pre}{i:x}" for i in range(len(events))]
        else:
            tokens = list(tokens)
        if len(tokens) != len(events):
            raise base.StorageError(
                f"create_batch: {len(events)} events but {len(tokens)} "
                "tokens")
        ids = [base.batch_event_id(t) for t in tokens]
        with self._lock:
            seen = self._seen_batch_ids(d, app_id, channel_id)
            fresh = [ev.with_event_id(eid)
                     for ev, eid in zip(events, ids) if eid not in seen]
            if fresh:
                pq.write_table(base.events_to_arrow(fresh),
                               d / f"part-{uuid.uuid4().hex}.parquet")
                seen.update(ev.event_id for ev in fresh)
        return ids

    def insert_columnar(
        self, table: pa.Table, app_id: int, channel_id: Optional[int] = None
    ) -> int:
        """Bulk columnar ingest: normalize, stamp ids with one Arrow
        kernel, write ONE part file — no per-event Python object is ever
        created.  This is the write half of the north-star data path
        (25M events land at parquet-writer speed, not event-loop speed).

        Dictionary encoding is parquet's default for strings, so
        low-cardinality columns (entity ids, the ~10 distinct rating
        property bags of ML-25M) compress to their index width on disk
        and come back dictionary-encoded on the training scan."""
        d = self._check_init(app_id, channel_id)
        table = base.stamp_event_ids(
            base.normalize_event_table(table),
            prefix=f"blk{uuid.uuid4().hex[:12]}-")
        with self._lock:
            pq.write_table(table, d / f"part-{uuid.uuid4().hex}.parquet")
        return table.num_rows

    def _flush(self, app_id: int, channel_id: Optional[int]) -> None:
        """Write buffered single-event inserts as one part file. Caller holds
        the lock (RLock: safe from both insert and the read paths)."""
        pending = self._pending.pop((app_id, channel_id), None)
        if not pending:
            return
        d = self._dir(app_id, channel_id)
        pq.write_table(base.events_to_arrow(pending),
                       d / f"part-{uuid.uuid4().hex}.parquet")

    def flush(self) -> None:
        with self._lock:
            for app_id, channel_id in list(self._pending):
                self._flush(app_id, channel_id)

    # Parquet stores low-cardinality strings dictionary-encoded anyway;
    # reading them back AS dictionary arrays keeps the training scan at
    # index width (int32 per row instead of a materialized string) and
    # hands `data.columnar.encode_ids` its O(unique) fast path.
    _DICT_COLS = ["event", "entity_type", "entity_id", "target_entity_type",
                  "target_entity_id", "properties_json", "pr_id"]

    def _scan(self, d: Path, app_id: int, channel_id: Optional[int],
              columns: Optional[Sequence[str]] = None) -> Optional[pa.Table]:
        """Caller holds the lock; flushes the write buffer first so reads
        always see every insert.  ``columns`` projects the read — parquet
        is columnar, unread columns cost nothing."""
        self._flush(app_id, channel_id)
        parts = sorted(d.glob("part-*.parquet"))
        if not parts:
            return None
        read_cols = list(columns) if columns is not None else None
        tabs = [pq.read_table(p, columns=read_cols,
                              read_dictionary=self._DICT_COLS)
                for p in parts]
        return tabs[0] if len(tabs) == 1 else pa.concat_tables(tabs)

    def _filtered(
        self, app_id, channel_id, start_time, until_time, entity_type, entity_id,
        event_names, target_entity_type, target_entity_id,
        ordered: bool = True, columns: Optional[Sequence[str]] = None,
    ) -> pa.Table:
        d = self._check_init(app_id, channel_id)
        read_cols = None
        if columns is not None:
            # filters need their columns read even when projected away
            need = set(columns)
            for col, active in (
                ("event_time_us", start_time is not None
                 or until_time is not None),
                ("creation_time_us", ordered),
                ("event_time_us", ordered),
                ("entity_type", entity_type is not None),
                ("entity_id", entity_id is not None),
                ("event", event_names is not None),
                ("target_entity_type", target_entity_type is not None),
                ("target_entity_id", target_entity_id is not None),
            ):
                if active:
                    need.add(col)
            read_cols = [f.name for f in EVENT_ARROW_SCHEMA
                         if f.name in need]
        with self._lock:
            table = self._scan(d, app_id, channel_id, columns=read_cols)
        if table is None:
            empty = EVENT_ARROW_SCHEMA.empty_table()
            return empty.select(list(columns)) if columns is not None \
                else empty
        mask = None

        def _and(m, cond):
            if cond is None:  # condition passes every row
                return m
            return cond if m is None else pc.and_(m, cond)

        def _value_mask(col, pred):
            """Row mask from a VALUE-level predicate.  For dictionary
            columns the predicate runs over the dictionary (O(unique))
            and fans out by index; ``None`` short-circuits "every row
            passes" so the common full-scan filter costs O(unique)."""
            arr = (col.combine_chunks()
                   if isinstance(col, pa.ChunkedArray) else col)
            if not pa.types.is_dictionary(arr.type):
                return pred(arr)
            if len(arr.dictionary) == 0:  # all-null column: no row matches
                import numpy as np

                return pa.array(np.zeros(len(arr), bool))
            from predictionio_tpu.data.columnar import dict_take

            vm = pred(arr.dictionary).to_numpy(zero_copy_only=False)
            if arr.null_count == 0 and vm.all():
                return None
            return pa.array(dict_take(vm, arr, False))

        if start_time is not None:
            mask = _and(mask, pc.greater_equal(table["event_time_us"], _us(start_time)))
        if until_time is not None:
            mask = _and(mask, pc.less(table["event_time_us"], _us(until_time)))
        if entity_type is not None:
            mask = _and(mask, _value_mask(
                table["entity_type"], lambda a: pc.equal(a, entity_type)))
        if entity_id is not None:
            mask = _and(mask, _value_mask(
                table["entity_id"], lambda a: pc.equal(a, entity_id)))
        if event_names is not None:
            vs = pa.array(list(event_names), type=pa.string())
            mask = _and(mask, _value_mask(
                table["event"], lambda a: pc.is_in(a, value_set=vs)))
        if target_entity_type is not None:
            mask = _and(mask, _value_mask(
                table["target_entity_type"],
                lambda a: pc.equal(a, target_entity_type)))
        if target_entity_id is not None:
            mask = _and(mask, _value_mask(
                table["target_entity_id"],
                lambda a: pc.equal(a, target_entity_id)))
        if mask is not None and not (
                mask.null_count == 0 and pc.all(mask).as_py()):
            # all-true masks (the common full-training scan) skip the
            # 25M-row copy a filter() would pay; a null in the mask means
            # "drop" (Arrow filter semantics), so it never skips
            table = table.filter(mask)
        if ordered:
            table = table.sort_by([("event_time_us", "ascending"),
                                   ("creation_time_us", "ascending")])
        if columns is not None:
            table = table.select(list(columns))
        return table

    def latest_event_time(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> Optional[_dt.datetime]:
        """Ingest high-watermark: columnar MAX over the projected
        event_time_us column — no row materialization, no sort."""
        d = self._check_init(app_id, channel_id)
        with self._lock:
            table = self._scan(d, app_id, channel_id,
                               columns=["event_time_us"])
        if table is None or table.num_rows == 0:
            return None
        us = pc.max(table["event_time_us"]).as_py()
        if us is None:
            return None
        return _dt.datetime.fromtimestamp(us / 1_000_000,
                                          tz=_dt.timezone.utc)

    def get(self, event_id: str, app_id: int, channel_id: Optional[int] = None):
        d = self._check_init(app_id, channel_id)
        with self._lock:
            table = self._scan(d, app_id, channel_id)
        if table is None:
            return None
        hit = table.filter(pc.equal(table["event_id"], event_id))
        if hit.num_rows == 0:
            return None
        return base.arrow_to_events(hit)[0]

    def delete(self, event_id: str, app_id: int, channel_id: Optional[int] = None) -> bool:
        d = self._check_init(app_id, channel_id)
        with self._lock:
            self._flush(app_id, channel_id)
            for p in sorted(d.glob("part-*.parquet")):
                t = pq.read_table(p)
                mask = pc.equal(t["event_id"], event_id)
                if pc.any(mask).as_py():
                    kept = t.filter(pc.invert(mask))
                    if kept.num_rows:
                        pq.write_table(kept, p)
                    else:
                        p.unlink()
                    # keep the bulk-ingest dedup index truthful: a deleted
                    # token-derived row may legitimately be re-created
                    seen = self._batch_ids.get((app_id, channel_id))
                    if seen is not None:
                        seen.discard(event_id)
                    return True
        return False

    def find(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        *,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        entity_id: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type: Optional[str] = None,
        target_entity_id: Optional[str] = None,
        limit: Optional[int] = None,
        reversed: bool = False,
    ) -> Iterator[Event]:
        table = self._filtered(
            app_id, channel_id, start_time, until_time, entity_type, entity_id,
            event_names, target_entity_type, target_entity_id,
        )
        events = base.arrow_to_events(table)
        if reversed:
            events.reverse()
        if limit is not None and limit >= 0:
            events = events[:limit]
        return iter(events)

    def find_columnar(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        *,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        entity_id: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type: Optional[str] = None,
        target_entity_id: Optional[str] = None,
        ordered: bool = True,
        columns: Optional[Sequence[str]] = None,
    ) -> pa.Table:
        base.check_event_columns(columns)
        return self._filtered(
            app_id, channel_id, start_time, until_time, entity_type, entity_id,
            event_names, target_entity_type, target_entity_id,
            ordered=ordered, columns=columns,
        )
