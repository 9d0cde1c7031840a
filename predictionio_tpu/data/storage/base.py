"""Storage abstraction: metadata records + repository traits.

Reference: data/src/main/scala/org/apache/predictionio/data/storage/ —
the ``LEvents`` / ``PEvents`` / ``Models`` / ``Apps`` / ``AccessKeys`` /
``Channels`` / ``EngineInstances`` / ``EvaluationInstances`` traits that every
backend plugin implements (SURVEY.md §1 L2).

Design departure from the reference (deliberate, TPU-first): the reference
splits event reads into ``LEvents`` (iterator, serving path) and ``PEvents``
(RDD, training path).  Here a single :class:`Events` trait carries both:
``find`` yields :class:`Event` objects (the L path) and ``find_columnar``
returns a ``pyarrow.Table`` (the P path) — columnar batches are what feeds
host-sharded ``jax.Array`` construction, replacing RDD partitions.
"""

from __future__ import annotations

import abc
import datetime as _dt
import secrets
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence

import numpy as np
import pyarrow as pa

from predictionio_tpu.data.event import Event, PropertyMap

__all__ = [
    "App",
    "AccessKey",
    "Channel",
    "EngineInstance",
    "EvaluationInstance",
    "Model",
    "QueueRecord",
    "Apps",
    "AccessKeys",
    "Channels",
    "EngineInstances",
    "EvaluationInstances",
    "Models",
    "Events",
    "SpillQueues",
    "KV",
    "EVENT_ARROW_SCHEMA",
    "StorageError",
    "StorageUnavailable",
    "normalize_event_table",
    "stamp_event_ids",
    "batch_event_id",
]


def batch_event_id(token: str) -> str:
    """Deterministic event id for a bulk-ingest item from its idempotency
    sub-token.  The id IS the dedup key: every backend's ``create_batch``
    keys its conflict-ignoring insert on it, so a replayed batch (same
    tokens) lands each row at most once — even when a crash left the
    first attempt partially committed."""
    return f"bt{token}"


class StorageError(RuntimeError):
    pass


class StorageUnavailable(StorageError):
    """The backend is unreachable / timing out — an AVAILABILITY failure,
    distinct from a bad request: retriable, counted by circuit breakers,
    and mapped to 503 (or a spill-journal 202) by the servers instead of
    a client-fault 400."""

    retriable = True


# --------------------------------------------------------------------------
# Metadata records (reference: App.scala, AccessKey.scala, Channel.scala,
# EngineInstance.scala, EvaluationInstance.scala, Model.scala)
# --------------------------------------------------------------------------


@dataclass
class App:
    id: Optional[int]
    name: str
    description: Optional[str] = None


@dataclass
class AccessKey:
    key: str
    app_id: int
    events: Sequence[str] = ()          # allowlist; empty = all events permitted

    @staticmethod
    def generate(app_id: int, events: Sequence[str] = ()) -> "AccessKey":
        return AccessKey(key=secrets.token_urlsafe(48), app_id=app_id, events=tuple(events))


@dataclass
class Channel:
    id: Optional[int]
    name: str
    app_id: int

    NAME_MAX = 16

    @staticmethod
    def is_valid_name(name: str) -> bool:
        # Reference: Channel.isValidName — [a-zA-Z0-9-] and 1..16 chars.
        return (
            0 < len(name) <= Channel.NAME_MAX
            and all((c.isascii() and c.isalnum()) or c == "-" for c in name)
        )


@dataclass
class EngineInstance:
    """One row per train run (reference: EngineInstance.scala)."""

    id: Optional[str]
    status: str                                  # INIT | TRAINING | COMPLETED | FAILED
    start_time: _dt.datetime
    end_time: Optional[_dt.datetime]
    engine_id: str
    engine_version: str
    engine_variant: str
    engine_factory: str
    env: Dict[str, str] = field(default_factory=dict)
    runtime_conf: Dict[str, Any] = field(default_factory=dict)   # reference: sparkConf
    datasource_params: str = "{}"
    preparator_params: str = "{}"
    algorithms_params: str = "[]"
    serving_params: str = "{}"


@dataclass
class EvaluationInstance:
    """One row per `pio eval` run (reference: EvaluationInstance.scala)."""

    id: Optional[str]
    status: str
    start_time: _dt.datetime
    end_time: Optional[_dt.datetime]
    evaluation_class: str
    engine_params_generator_class: str
    env: Dict[str, str] = field(default_factory=dict)
    evaluator_results: str = ""                  # pretty text summary
    evaluator_results_html: str = ""
    evaluator_results_json: str = "{}"


@dataclass
class Model:
    """Binary model blob (reference: Model.scala / Models trait)."""

    id: str
    models: bytes


@dataclass
class QueueRecord:
    """One record of a shared spill queue (ISSUE 15).

    ``payload`` is the journal-record JSON object (token/appId/channelId/
    events); ``state`` walks pending → leased → (acked = deleted | dead).
    ``lease_expires_s`` is epoch seconds — lease math is done against a
    CALLER-supplied ``now_s`` so tests (and clock-skewed fleets) reason
    about expiry explicitly instead of trusting each backend's wall
    clock."""

    id: str
    payload: Dict[str, Any]
    token: Optional[str] = None
    events: int = 1
    attempts: int = 0
    state: str = "pending"            # pending | leased | dead
    lease_owner: Optional[str] = None
    lease_expires_s: Optional[float] = None
    reason: Optional[str] = None      # dead-letter reason
    enqueued_s: float = 0.0


# --------------------------------------------------------------------------
# Repository traits
# --------------------------------------------------------------------------


class Apps(abc.ABC):
    @abc.abstractmethod
    def insert(self, app: App) -> Optional[int]: ...

    @abc.abstractmethod
    def get(self, app_id: int) -> Optional[App]: ...

    @abc.abstractmethod
    def get_by_name(self, name: str) -> Optional[App]: ...

    @abc.abstractmethod
    def get_all(self) -> List[App]: ...

    @abc.abstractmethod
    def update(self, app: App) -> bool: ...

    @abc.abstractmethod
    def delete(self, app_id: int) -> bool: ...


class AccessKeys(abc.ABC):
    @abc.abstractmethod
    def insert(self, access_key: AccessKey) -> Optional[str]: ...

    @abc.abstractmethod
    def get(self, key: str) -> Optional[AccessKey]: ...

    @abc.abstractmethod
    def get_all(self) -> List[AccessKey]: ...

    @abc.abstractmethod
    def get_by_app_id(self, app_id: int) -> List[AccessKey]: ...

    @abc.abstractmethod
    def update(self, access_key: AccessKey) -> bool: ...

    @abc.abstractmethod
    def delete(self, key: str) -> bool: ...


class Channels(abc.ABC):
    def insert(self, channel: Channel) -> Optional[int]:
        """Validate then store; name rules enforced here so every backend —
        including ones registered via ``register_backend`` — gets them."""
        if not Channel.is_valid_name(channel.name):
            return None
        return self._insert(channel)

    @abc.abstractmethod
    def _insert(self, channel: Channel) -> Optional[int]: ...

    @abc.abstractmethod
    def get(self, channel_id: int) -> Optional[Channel]: ...

    @abc.abstractmethod
    def get_by_app_id(self, app_id: int) -> List[Channel]: ...

    @abc.abstractmethod
    def delete(self, channel_id: int) -> bool: ...


class EngineInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, instance: EngineInstance) -> str: ...

    @abc.abstractmethod
    def get(self, instance_id: str) -> Optional[EngineInstance]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EngineInstance]: ...

    @abc.abstractmethod
    def get_latest_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> Optional[EngineInstance]: ...

    @abc.abstractmethod
    def get_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> List[EngineInstance]: ...

    @abc.abstractmethod
    def update(self, instance: EngineInstance) -> bool: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> bool: ...


class EvaluationInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, instance: EvaluationInstance) -> str: ...

    @abc.abstractmethod
    def get(self, instance_id: str) -> Optional[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_completed(self) -> List[EvaluationInstance]: ...

    @abc.abstractmethod
    def update(self, instance: EvaluationInstance) -> bool: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> bool: ...


class Models(abc.ABC):
    @abc.abstractmethod
    def insert(self, model: Model) -> None: ...

    @abc.abstractmethod
    def get(self, model_id: str) -> Optional[Model]: ...

    @abc.abstractmethod
    def delete(self, model_id: str) -> bool: ...


class SpillQueues(abc.ABC):
    """Shared durable work queue with lease/ack semantics (ISSUE 15).

    The fleet-scale replacement for the per-instance JSONL spill journal:
    N event servers enqueue failed writes into ONE storage-backed queue,
    and any instance's drainer may lease a batch, replay it, and ack.  A
    crashed drainer's lease expires (``lease_expires_s`` vs the caller's
    ``now_s``) and another instance re-leases the batch — replay stays
    idempotent because each record carries the ORIGINAL write's
    idempotency token, so the at-least-once redelivery dedups into
    exactly-once against dedup-capable backends (pioserver).

    Contract pinned by tests/test_fleet.py across sqlite/memory/remote:

    - :meth:`enqueue` is token-idempotent — re-enqueueing a token already
      queued (lost-reply retry) returns the existing record's id.
    - :meth:`lease` atomically claims up to ``n`` records that are
      pending OR whose lease expired before ``now_s``, oldest first,
      bumping ``attempts`` — two concurrent drainers never hold the same
      record under an unexpired lease.
    - :meth:`ack` deletes ONLY records still leased by ``owner`` — an
      acker whose lease was stolen learns it from the return count.
    - :meth:`nack` releases records back to pending (transient replay
      failure: storage still down, retry next tick).
    - :meth:`dead_letter` parks a permanently unreplayable record (state
      ``dead``) where :meth:`requeue_dead` can resurrect it after the
      operator fixes the cause.
    """

    @abc.abstractmethod
    def enqueue(self, queue: str, payload: Dict[str, Any],
                token: Optional[str] = None, events: int = 1,
                now_s: Optional[float] = None) -> str: ...

    @abc.abstractmethod
    def lease(self, queue: str, owner: str, n: int, ttl_s: float,
              now_s: Optional[float] = None) -> List["QueueRecord"]: ...

    @abc.abstractmethod
    def ack(self, queue: str, ids: Sequence[str], owner: str) -> int: ...

    @abc.abstractmethod
    def nack(self, queue: str, ids: Sequence[str], owner: str) -> int: ...

    @abc.abstractmethod
    def dead_letter(self, queue: str, record_id: str, owner: str,
                    reason: str) -> bool: ...

    @abc.abstractmethod
    def requeue_dead(self, queue: str) -> int:
        """Move every dead record back to pending; returns EVENTS
        requeued (the operator-facing unit, matching the journal)."""

    @abc.abstractmethod
    def stats(self, queue: str, now_s: Optional[float] = None
              ) -> Dict[str, Any]:
        """``{"pending","leased","expired","dead"}`` record counts plus
        ``*Events`` sums — ``expired`` counts leased records whose lease
        already lapsed at ``now_s`` (re-leasable work)."""

    @abc.abstractmethod
    def peek(self, queue: str, n: int = 5, state: str = "pending"
             ) -> List["QueueRecord"]:
        """Read-only oldest-first view for ``pio spill inspect`` — takes
        no lease, never mutates."""


class KV(abc.ABC):
    """Namespaced shared key-value store (ISSUE 15: the durable fold-in
    cache).  Values are opaque bytes; ``prune`` bounds a namespace by
    dropping the least-recently-written entries, so N instances can share
    a cache without any one of them owning an eviction thread."""

    @abc.abstractmethod
    def put(self, ns: str, key: str, value: bytes) -> None: ...

    @abc.abstractmethod
    def get(self, ns: str, key: str) -> Optional[bytes]: ...

    @abc.abstractmethod
    def delete(self, ns: str, key: str) -> bool: ...

    @abc.abstractmethod
    def count(self, ns: str) -> int: ...

    @abc.abstractmethod
    def prune(self, ns: str, keep: int) -> int:
        """Drop all but the ``keep`` most-recently-written entries of
        ``ns``; returns the number deleted."""


# --------------------------------------------------------------------------
# Events trait — unified L+P event store
# --------------------------------------------------------------------------

# Columnar schema for the P (training) read path; feeds host-sharded arrays.
EVENT_ARROW_SCHEMA = pa.schema(
    [
        pa.field("event_id", pa.string()),
        pa.field("event", pa.string()),
        pa.field("entity_type", pa.string()),
        pa.field("entity_id", pa.string()),
        pa.field("target_entity_type", pa.string()),
        pa.field("target_entity_id", pa.string()),
        pa.field("properties_json", pa.string()),
        pa.field("event_time_us", pa.int64()),      # epoch micros UTC
        pa.field("pr_id", pa.string()),
        pa.field("creation_time_us", pa.int64()),
    ]
)


class Events(abc.ABC):
    """Unified event store trait (reference: LEvents + PEvents).

    All methods take ``app_id`` and optional ``channel_id`` (None = default
    channel), matching the reference's partitioning.
    """

    @abc.abstractmethod
    def init(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        """Create per-app/channel structures (reference: LEvents.init)."""

    @abc.abstractmethod
    def remove(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        """Drop all events of the app/channel (reference: LEvents.remove)."""

    @abc.abstractmethod
    def close(self) -> None: ...

    @abc.abstractmethod
    def insert(self, event: Event, app_id: int, channel_id: Optional[int] = None) -> str:
        """Insert one event; the store ALWAYS assigns a fresh event id
        (any ``event.event_id`` present is ignored), matching the
        reference's server-generated ids.  Returns the assigned id."""

    def insert_batch(
        self, events: Sequence[Event], app_id: int, channel_id: Optional[int] = None
    ) -> List[str]:
        return [self.insert(e, app_id, channel_id) for e in events]

    def create_batch(
        self, events: Sequence[Event], app_id: int,
        channel_id: Optional[int] = None,
        tokens: Optional[Sequence[str]] = None,
    ) -> List[str]:
        """One multi-row write with PER-ITEM exactly-once semantics
        (ISSUE 17: the bulk-ingest data plane's storage contract).

        ``tokens`` are the batch's per-item idempotency sub-tokens; each
        item's event id is derived deterministically from its sub-token
        (:func:`batch_event_id`), so a replay of the same batch after a
        crashed reply — possibly after a PARTIAL landing — skips the rows
        that already committed and re-inserts only the missing ones,
        returning the same ids either way.  Without tokens a fresh set is
        minted, which degrades to plain at-least-once ``insert_batch``
        behavior.

        The base default delegates to :meth:`insert_batch` (store-assigned
        ids, at-least-once on replay — it cannot force ids on a backend it
        knows nothing about); sqlite/memory/parquet override with a
        genuinely single-round-trip conflict-ignoring write keyed on the
        derived ids, and the pioserver backend forwards the call (token
        set included) over one RPC.
        """
        if tokens is not None and len(tokens) != len(events):
            raise StorageError(
                f"create_batch: {len(events)} events but {len(tokens)} "
                "tokens")
        return self.insert_batch(events, app_id, channel_id)

    @abc.abstractmethod
    def get(
        self, event_id: str, app_id: int, channel_id: Optional[int] = None
    ) -> Optional[Event]: ...

    @abc.abstractmethod
    def delete(
        self, event_id: str, app_id: int, channel_id: Optional[int] = None
    ) -> bool: ...

    @abc.abstractmethod
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
        """Time/entity-filtered scan (reference: LEvents.find).

        ``limit=None`` means no limit; ``reversed=True`` returns newest first
        (only valid when filtering, per reference semantics — here always
        honored).  Results are ordered by event time.
        """

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
        """Columnar scan for the training path (reference: PEvents.find).

        ``ordered=False`` lets the backend skip the event-time sort —
        training reads are order-independent (the reference's RDD scans
        come back in HBase rowkey-hash order, not time order), and at the
        ML-25M north star the sort alone costs seconds.  ``columns``
        projects the result to the named :data:`EVENT_ARROW_SCHEMA`
        fields; columnar backends then avoid materializing the others at
        all (the 32-char ``event_id`` strings are the widest column in
        the store and no trainer reads them).

        Default implementation converts the iterator; columnar backends
        override with a zero-copy path.
        """
        check_event_columns(columns)
        table = events_to_arrow(
            self.find(
                app_id,
                channel_id,
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                entity_id=entity_id,
                event_names=event_names,
                target_entity_type=target_entity_type,
                target_entity_id=target_entity_id,
            )
        )
        if columns is not None:
            table = table.select(list(columns))
        return table

    def insert_columnar(
        self, table: pa.Table, app_id: int, channel_id: Optional[int] = None
    ) -> int:
        """Bulk columnar ingest (reference analogue: HBase bulk import /
        ``pio import`` at scale — SURVEY §2.1).

        ``table`` carries :data:`EVENT_ARROW_SCHEMA` columns (``event_id``
        is ignored — the store assigns ids, same rule as :meth:`insert`;
        missing nullable columns default to null, a missing
        ``creation_time_us`` defaults to now).  Returns the number of
        events ingested instead of per-row id strings: materializing 25M
        Python strings would defeat the point of the columnar path.

        Default implementation chunks through :meth:`insert_batch` so
        row-oriented backends stay correct without bulk-specific code.
        """
        table = normalize_event_table(table)
        n = 0
        for start in range(0, table.num_rows, 65536):
            chunk = table.slice(start, 65536)
            n += len(self.insert_batch(arrow_to_events(chunk),
                                       app_id, channel_id))
        return n

    def latest_event_time(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> Optional[_dt.datetime]:
        """The ingest high-watermark: the newest ``event_time`` stored for
        the app/channel, or None when empty.

        This is THE freshness anchor of the online-learning loop
        (ISSUE 10): the event server exports it as
        ``pio_events_latest_ts{app}`` and the refresh daemon compares it
        against the serving generation's data watermark to compute
        event→servable staleness.  Default implementation reads one
        event via the reversed ordered scan; backends override with an
        O(1)/indexed query.
        """
        for ev in self.find(app_id, channel_id, limit=1, reversed=True):
            return ev.event_time
        return None

    def aggregate_properties(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        *,
        entity_type: str,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        required: Optional[Sequence[str]] = None,
    ) -> Dict[str, PropertyMap]:
        """Aggregate ``$set``/``$unset``/``$delete`` into per-entity state.

        Reference: PEventStore.aggregateProperties / LEventAggregator.
        """
        from predictionio_tpu.data.event import aggregate_properties as _agg

        by_entity: Dict[str, List[Event]] = {}
        for ev in self.find(
            app_id,
            channel_id,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            event_names=["$set", "$unset", "$delete"],
        ):
            by_entity.setdefault(ev.entity_id, []).append(ev)
        out: Dict[str, PropertyMap] = {}
        for eid, evs in by_entity.items():
            pm = _agg(evs)
            if pm is None:
                continue
            if required and not all(k in pm for k in required):
                continue
            out[eid] = pm
        return out


# --------------------------------------------------------------------------
# Timestamp + Arrow conversion helpers (shared by all backends — keep the
# naive-datetime-is-UTC rule in exactly one place)
# --------------------------------------------------------------------------


def epoch_us(dt: Optional[_dt.datetime]) -> Optional[int]:
    if dt is None:
        return None
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=_dt.timezone.utc)
    return int(dt.timestamp() * 1_000_000)


def from_epoch_us(us: Optional[int]) -> Optional[_dt.datetime]:
    if us is None:
        return None
    return _dt.datetime.fromtimestamp(us / 1_000_000, tz=_dt.timezone.utc)


# Backwards-compat private aliases used inside this module.
_epoch_us = epoch_us
_from_epoch_us = from_epoch_us


def events_to_arrow(events: Iterable[Event]) -> pa.Table:
    import json

    cols: Dict[str, list] = {f.name: [] for f in EVENT_ARROW_SCHEMA}
    for e in events:
        cols["event_id"].append(e.event_id)
        cols["event"].append(e.event)
        cols["entity_type"].append(e.entity_type)
        cols["entity_id"].append(e.entity_id)
        cols["target_entity_type"].append(e.target_entity_type)
        cols["target_entity_id"].append(e.target_entity_id)
        cols["properties_json"].append(json.dumps(e.properties.to_dict()))
        cols["event_time_us"].append(_epoch_us(e.event_time))
        cols["pr_id"].append(e.pr_id)
        cols["creation_time_us"].append(_epoch_us(e.creation_time))
    return pa.table(cols, schema=EVENT_ARROW_SCHEMA)


def check_event_columns(columns: Optional[Sequence[str]]) -> None:
    """``find_columnar``'s ``columns`` must name :data:`EVENT_ARROW_SCHEMA`
    fields: an unknown one is the caller's error, said as such."""
    known = {f.name for f in EVENT_ARROW_SCHEMA}
    unknown = [c for c in columns or () if c not in known]
    if unknown:
        raise StorageError(
            f"find_columnar: unknown column(s) {unknown}; "
            f"the event schema has {sorted(known)}")


def normalize_event_table(table: pa.Table) -> pa.Table:
    """Validate/complete a caller-supplied columnar event batch against
    :data:`EVENT_ARROW_SCHEMA` for :meth:`Events.insert_columnar`.

    Required: ``event``, ``entity_type``, ``entity_id``.  ``event_id`` is
    dropped (store-assigned).  Missing nullable columns become null;
    a missing ``creation_time_us`` is stamped now; a missing
    ``event_time_us`` defaults to creation time (reference rule: an event
    without an explicit eventTime gets the server clock).
    """
    names = set(table.column_names)
    for req in ("event", "entity_type", "entity_id"):
        if req not in names:
            raise StorageError(f"insert_columnar: missing column {req!r}")
        nc = table.column(req).null_count
        if nc:
            raise StorageError(
                f"insert_columnar: column {req!r} has {nc} null value(s) "
                "— required per event (reference: EventJson4sSupport "
                "validation)")
    unknown = names - {f.name for f in EVENT_ARROW_SCHEMA}
    if unknown:
        raise StorageError(
            f"insert_columnar: unknown column(s) {sorted(unknown)}")
    n = table.num_rows
    now_us = epoch_us(_dt.datetime.now(_dt.timezone.utc))

    def _conform(col: "pa.ChunkedArray", typ: pa.DataType):
        # A dictionary column with the right value type passes through
        # untouched — casting it dense would materialize 25M strings and
        # defeat the columnar bulk path (parquet stores dictionary pages
        # either way; row backends densify per-chunk at insert).
        if pa.types.is_dictionary(col.type) and col.type.value_type == typ:
            return col
        return col.cast(typ)

    # creation time as every row will carry it: given, else the server
    # clock.  event_time_us (earlier in the schema) defaults to it.
    import pyarrow.compute as pc

    if "creation_time_us" in names:
        creation = pc.fill_null(
            table.column("creation_time_us").cast(pa.int64()), now_us)
    else:
        creation = pa.chunked_array([np.full(n, now_us, np.int64)])

    cols = []
    for field in EVENT_ARROW_SCHEMA:
        if field.name == "event_id":
            cols.append(pa.nulls(n, field.type))
        elif field.name == "properties_json":
            # the row path always serializes a DataMap ('{}' minimum);
            # null here would violate that invariant (and sqlite's schema)
            if field.name in names:
                col = _conform(table.column(field.name), field.type)
                if col.null_count:
                    if pa.types.is_dictionary(col.type):
                        col = col.cast(field.type)  # rare: nulls in dict col
                    col = pc.fill_null(col, "{}")
                cols.append(col)
            else:
                cols.append(pa.repeat(pa.scalar("{}", field.type), n))
        elif field.name == "creation_time_us":
            cols.append(creation)
        elif field.name == "event_time_us":
            # per row, the same rule whether the column is missing or the
            # value null: the row's creation time (a null must not leak —
            # sqlite's eventtime is NOT NULL and readers assume every
            # Event has a time)
            if field.name in names:
                cols.append(pc.coalesce(
                    _conform(table.column(field.name), field.type),
                    creation))
            else:
                cols.append(creation)
        elif field.name in names:
            cols.append(_conform(table.column(field.name), field.type))
        else:
            cols.append(pa.nulls(n, field.type))
    fields = [pa.field(f.name, col.type, nullable=True)
              for f, col in zip(EVENT_ARROW_SCHEMA, cols)]
    return pa.table(cols, schema=pa.schema(fields))


def stamp_event_ids(table: pa.Table, prefix: str) -> pa.Table:
    """Replace ``event_id`` with ``<prefix><row>`` — unique ids from one
    cast+concat Arrow kernel pair instead of 25M Python ``uuid4`` calls
    (measured ~1 µs each; the columnar bulk path cannot afford them)."""
    import pyarrow.compute as pc

    seq = pc.cast(pa.array(np.arange(table.num_rows, dtype=np.int64)),
                  pa.string())
    ids = pc.binary_join_element_wise(pa.scalar(prefix), seq, "")
    return table.set_column(
        table.schema.get_field_index("event_id"),
        EVENT_ARROW_SCHEMA.field("event_id"), ids)


def arrow_to_events(table: pa.Table) -> List[Event]:
    import json

    from predictionio_tpu.data.event import DataMap

    out: List[Event] = []
    d = table.to_pydict()
    n = table.num_rows
    for i in range(n):
        out.append(
            Event(
                event_id=d["event_id"][i],
                event=d["event"][i],
                entity_type=d["entity_type"][i],
                entity_id=d["entity_id"][i],
                target_entity_type=d["target_entity_type"][i],
                target_entity_id=d["target_entity_id"][i],
                properties=DataMap(json.loads(d["properties_json"][i] or "{}")),
                event_time=_from_epoch_us(d["event_time_us"][i]),
                pr_id=d["pr_id"][i],
                creation_time=_from_epoch_us(d["creation_time_us"][i]),
            )
        )
    return out
