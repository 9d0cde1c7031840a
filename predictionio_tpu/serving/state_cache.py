"""Per-user serving state of a sequence model, of up to four kinds in
one manager, keyed by user (a loaded model owns its cache, so a cache IS
a model generation's state: ``/reload`` frees the outgoing one's):

* a FIXED slot per user, which never grows: the short-convolution
  layers' last rows (a few KB a layer) or a linear-attention layer's
  recurrent matrices (MBs a layer), and the last hidden row;
* PAGED rows that grow with the user's history: the attention layers'
  keys and values, ``PAGE_SIZE`` events a page; and
* INDEX rows that ride with the pages (the pooled keys a block selection
  scores): allotted, evicted and rolled back with the page they sit in;
* WINDOW pages of a pool of their own (a layout with ``window_bytes``:
  the keys and values of sliding-window attention layers), which a user
  holds only while an event in them lies within ``window_events`` of the
  user's last one: see **Window pages** below.  Beside them the paged
  rows are the pool that grows with the history (the gauge calls it
  ``full`` there).

All live in device arrays the cache owns (``arrays``); a model's device
program reads and writes them at the slots and rows a :class:`Plan`
names.  The cache knows sizes, not models: a model hands it a ``layout``
(the bytes of a slot and of a page by kind, and ``allocate(n_slots,
n_pages)``, which makes the arrays as the model's programs index them).

**Transactions.**  Every change belongs to a :meth:`StateCache.transaction`
(re-entrant; the engine server holds one around a whole dispatch).  A
program writes a user's fixed state into ANOTHER slot than the committed
one and new paged rows beyond the user's committed length, so until
:meth:`commit` makes the written slot the user's and moves the length,
every committed state is intact: a failed dispatch rolls back to exactly
what was there, for every kind.  The other slot comes from a POOL of
``write_slots`` spare ones, as many as the users ONE device program can
touch, so the write side is sized by the dispatch and not by the
population (a slot is MBs where the state is a recurrent matrix); a
commit hands the user's old slot back.  A transaction that touches more
users than that (a bulk call of several programs) COMMITS IN PARTS: when
the pool is dry, the users whose programs have run and who are not in
the one being planned are committed there and then, which frees their
old slots.  So a roll-back is exact for a call of up to ``write_slots``
users (every cohort the scheduler forms), and program by program beyond:
a failed program leaves every state whole, the earlier programs' users
advanced, and no turn applied in part (a turn split over programs is in
each of their plans).  Evictions made to find room are not undone (an
evicted user is a later miss, which re-reads the history, never a wrong
answer).

**Page lists.**  A dispatch names its users' pages either as ONE flat
list of ``PAGE_LIST_LEN`` pages (few users with long histories fill it),
or, with ``table_len``, through a page table a user that lives on the
device (``arrays["table"]``, a row a user; the plan names the entries a
dispatch's new pages add, and the program writes them).

**Window pages.**  A query of a window layer reads itself and the
``window_events - 1`` events before it, so of a user's window pages only
those that hold one of the last ``window_events - 1`` events, or will
hold the next, are ever read again: at most ``window_pages_per_user``.
A page that has fallen behind goes back to the pool AT COMMIT, never
before, so a rolled-back turn still finds the window it started from;
only a page that the open transaction itself handed out (a long history
read in several programs of one call) is taken back as soon as a later
program of the transaction has passed it, since no committed state
names it.  A user's window pages are a short list on the host
(``Plan.seg_wpages``, the first at index ``Plan.seg_wbase`` of the
history), beside the long page table of the pool that grows.  The
window pool holds ``window_pages_per_user`` pages a user and what one
program can add (a page a user of the write pool, and
``window_spare_pages``); it is taken off the budget first.

**Budget.**  ``budget_bytes`` of device memory: the fixed slots (a user
each, the pool and two the cache keeps), the window pool where the
layout has one, and as many pages as the rest holds.  When rows or pages run out the least recently used user outside
the open transaction is evicted
(``pio_seq_state_total{result="evicted"}``).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
from typing import Any, Dict, Hashable, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.obs import get_registry

__all__ = ["StateCache", "StateCacheFull", "Plan"]

# Events a page of paged state holds (tests pass the constructor a
# smaller one to cross page borders with short histories).
PAGE_SIZE = 128
# Longest history, in pages, that one dispatch can attend over; the page
# list of a dispatch has this length (a multiple of the attention loop's
# block).  1,024 pages of 128 events = 131,072 events.
PAGE_LIST_LEN = 1024


class StateCacheFull(RuntimeError):
    """The dispatch's own users need more slots or pages than the budget
    holds."""


@dataclasses.dataclass
class _Entry:
    row: int                  # the user's number: its row of the table
    slot: Optional[int]       # the slot that holds the committed state
    length: int = 0           # committed events
    pages: List[int] = dataclasses.field(default_factory=list)
    # Window pages, oldest first, and the index in the history of the
    # first (a layout with a window pool).
    wpages: List[int] = dataclasses.field(default_factory=list)
    wbase: int = 0


@dataclasses.dataclass
class _Staged:
    length: int
    pages: List[int]
    slot: Optional[int] = None  # where the transaction writes the key
    wrote: bool = False         # a program has written it
    wpages: List[int] = dataclasses.field(default_factory=list)
    wbase: int = 0


@dataclasses.dataclass
class Plan:
    """Where one dispatch's segments read and write."""

    keys: List[Hashable]
    seg_start: List[int]        # events the user has before this dispatch
    seg_len: List[int]
    read_slot: List[int]
    write_slot: List[int]
    seg_pages: List[List[int]]  # the user's pages once the rows are added
    page_size: int
    # With a page table a user: each segment's row of it, and (row, index,
    # pool page) of every page this plan handed out.
    table_row: List[int] = dataclasses.field(default_factory=list)
    new_pages: List[Tuple[int, int, int]] = dataclasses.field(
        default_factory=list)
    # With a window pool: each segment's window pages as the program
    # needs them (those the window behind its first new event reaches,
    # and those its new events fill), and the index in the user's
    # history of the first.
    seg_wpages: List[List[int]] = dataclasses.field(default_factory=list)
    seg_wbase: List[int] = dataclasses.field(default_factory=list)

    def rows_of(self, tok_seg: np.ndarray, tok_pos: np.ndarray
                ) -> np.ndarray:
        """Pool row of each token's key and value."""
        return self._rows(self.seg_pages, [0] * len(self.seg_pages),
                          tok_seg, tok_pos)

    def window_rows_of(self, tok_seg: np.ndarray, tok_pos: np.ndarray
                       ) -> np.ndarray:
        """Window-pool row of each token's key and value."""
        return self._rows(self.seg_wpages, self.seg_wbase, tok_seg, tok_pos)

    def _rows(self, seg_pages, seg_base, tok_seg, tok_pos) -> np.ndarray:
        rows = np.zeros(len(tok_seg), np.int64)
        for s, (pages, base) in enumerate(zip(seg_pages, seg_base)):
            sel = tok_seg == s
            pos = tok_pos[sel]
            rows[sel] = (np.asarray(pages, np.int64)[
                pos // self.page_size - base] * self.page_size
                + pos % self.page_size)
        return rows

    def page_list(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(pool page, segment, position of its first row) of every page
        the dispatch's users hold."""
        ids = [p for pages in self.seg_pages for p in pages]
        seg = [s for s, pages in enumerate(self.seg_pages) for _ in pages]
        base = [i * self.page_size for pages in self.seg_pages
                for i in range(len(pages))]
        return (np.asarray(ids, np.int32), np.asarray(seg, np.int32),
                np.asarray(base, np.int32))


class StateCache:
    ZERO_SLOT = 0    # all zeros, never written: a user with no history
    SCRAP_SLOT = 1   # takes the writes of a program's padding
    SCRAP_PAGE = 0

    def __init__(self, layout: Dict[str, Any], *, budget_bytes: int,
                 max_users: int, write_slots: int,
                 page_size: int = PAGE_SIZE, registry=None):
        self.layout = layout
        self.page_size = int(page_size)
        self.max_users = int(max_users)
        self.write_slots = int(write_slots)
        self.table_len = layout.get("table_len")
        self.page_list_len = None if self.table_len else PAGE_LIST_LEN
        self.n_slots = 2 + self.max_users + self.write_slots
        self.slot_bytes = int(layout["fixed_bytes"])
        self.kind_bytes = {"fixed": self.slot_bytes,
                           "paged": int(layout.get("paged_bytes", 0)),
                           "index": int(layout.get("index_bytes", 0))}
        self.page_bytes = self.kind_bytes["paged"] + self.kind_bytes["index"]
        # A window pool: its page's bytes, the window's reach in events,
        # the most pages a committed user holds, the pool's size.
        self.window_bytes = int(layout.get("window_bytes", 0))
        self.window_events = int(layout.get("window_events", 0)) \
            if self.window_bytes else 0
        self.window_pages_per_user = self.n_wpages = 0
        if self.window_bytes:
            self.window_pages_per_user = \
                -(-(self.window_events - 1) // self.page_size) + 1
            self.n_wpages = (
                self.max_users * self.window_pages_per_user
                + self.write_slots
                + int(layout.get("window_spare_pages", 0)))
        fixed = self.n_slots * self.slot_bytes \
            + 4 * (1 + self.max_users) * int(self.table_len or 0)
        if self.window_bytes:
            fixed += (1 + self.n_wpages) * self.window_bytes
        self.n_pages = int((int(budget_bytes) - fixed - self.page_bytes)
                           // max(self.page_bytes, 1)) \
            if self.page_bytes else 0
        if self.page_bytes and self.n_pages < 1:
            raise ValueError(
                f"a budget of {budget_bytes} bytes holds {self.n_slots} "
                f"slots of {self.slot_bytes} bytes and no page of "
                f"{self.page_bytes}")
        self._lock = threading.RLock()
        self._depth = 0
        self._entries: "collections.OrderedDict[Hashable, _Entry]" = \
            collections.OrderedDict()
        self._staged: Dict[Hashable, _Staged] = {}
        self._txn_pages: List[int] = []
        self._txn_wpages: List[int] = []
        self._txn_created: List[Hashable] = []
        self._fresh_lists()
        self.arrays: Dict[str, Any] = {}
        self._allocate()
        reg = registry or get_registry()
        self._m_state = reg.counter(
            "pio_seq_state_total",
            "Turns by what the state cache held for their user (hit, "
            "miss) and users evicted to make room (evicted).", ("result",))
        self._m_users = reg.gauge(
            "pio_seq_state_users", "Users with state in the cache.")
        self._m_pages = reg.gauge(
            "pio_seq_state_pages_used", "Pages of paged state in use.")
        self._m_bytes = reg.gauge(
            "pio_seq_state_bytes",
            "Device bytes of per-user state by kind: fixed (every slot); "
            "paged and index (the pages in use); or, of a model with a "
            "window pool, window (its pages in use) and full (the pages "
            "in use of the pool that grows with the history).", ("kind",))
        self._m_wpages = reg.counter(
            "pio_seq_window_pages_total",
            "Window pages handed to users (taken) and taken back once "
            "every event in them lay behind the user's window (released; "
            "an evicted user's are not counted).", ("event",))
        self._gauges()

    def _fresh_lists(self) -> None:
        self._free_rows = list(range(self.max_users - 1, -1, -1))
        self._free_pages = list(range(self.n_pages, 0, -1))
        self._free_slots = list(range(self.n_slots - 1, 1, -1))
        self._free_wpages = list(range(self.n_wpages, 0, -1))

    # -- device arrays -------------------------------------------------------

    def _allocate(self) -> None:
        import jax.numpy as jnp

        sizes = (self.n_slots, self.n_pages) + (
            (self.n_wpages,) if self.window_bytes else ())
        self.arrays = dict(self.layout["allocate"](*sizes))
        if self.table_len:
            self.arrays["table"] = jnp.zeros(
                (1 + self.max_users, int(self.table_len)), jnp.int32)

    def run(self, fn, params, batch):
        """``fn(params, arrays, batch) -> (arrays, *rest)`` with the
        arrays donated: keeps what comes back, returns ``rest``.  If the
        call dies after taking the arrays, every user's state went with
        them: the cache starts empty again (all later turns miss)."""
        import jax

        try:
            out = fn(params, self.arrays, batch)
        except Exception:
            leaves = jax.tree_util.tree_leaves(self.arrays)
            if any(getattr(a, "is_deleted", lambda: False)()
                   for a in leaves):
                self.reset()
            raise
        self.arrays = out[0]
        return out

    def bytes_in_use(self) -> int:
        import jax

        return sum(a.nbytes for a in jax.tree_util.tree_leaves(self.arrays))

    def free(self) -> None:
        """Forget every user and give the device arrays back: what
        ``/reload`` and a rollback do to the outgoing model's state (the
        server may keep that model for a rollback; its pools must not
        stay beside the new one's).  The next transaction allocates
        them anew."""
        with self._lock:
            self.arrays = {}
            self._entries.clear()
            self._staged.clear()
            self._txn_pages.clear()
            self._txn_wpages.clear()
            self._txn_created.clear()
            self._fresh_lists()
            self._gauges()

    def reset(self) -> None:
        with self._lock:
            self.free()
            self._allocate()

    # -- transactions --------------------------------------------------------

    @contextlib.contextmanager
    def transaction(self):
        """Hold the cache for one dispatch: commit when the block ends,
        roll back if it raises.  Re-entrant; the outermost decides."""
        with self._lock:
            if not self.arrays:
                self._allocate()
            self._depth += 1
            try:
                yield self
            except BaseException:
                if self._depth == 1:
                    self.rollback()
                raise
            else:
                if self._depth == 1:
                    self.commit()
            finally:
                self._depth -= 1

    def commit(self) -> None:
        with self._lock:
            self._commit(list(self._staged))
            self._staged.clear()
            self._txn_pages.clear()
            self._txn_wpages.clear()
            self._txn_created.clear()
            self._gauges()

    def _commit(self, keys: Sequence[Hashable]) -> None:
        """Make what is staged for ``keys`` theirs; a slot nobody's state
        is in any more goes back to the pool."""
        for key in keys:
            st = self._staged.pop(key)
            e = self._entries.get(key)
            if e is None:
                continue
            e.length, e.pages = st.length, st.pages
            if self.window_bytes:
                # The window pages that fell behind go back only now.
                self._release_window(set(e.wpages) - set(st.wpages))
                e.wpages, e.wbase = st.wpages, st.wbase
            if st.wrote:
                self._release(e.slot)
                e.slot = st.slot
            else:
                self._release(st.slot)
            self._entries.move_to_end(key)

    def _commit_early(self, keep) -> bool:
        """The pool is dry: commit the users whose programs have run and
        who are not in the plan being made (``keep``).  Whether any slot
        came back."""
        done = {key for key, st in self._staged.items()
                if st.wrote and key not in keep}
        self._commit(done)
        # Their pages stay in ``_txn_pages`` (and ``_txn_wpages``): a
        # roll-back frees only those of them that no entry holds.
        self._txn_created = [k for k in self._txn_created if k not in done]
        return bool(self._free_slots)

    def _release(self, slot: Optional[int]) -> None:
        if slot is not None:
            self._free_slots.append(slot)

    def _release_window(self, pages) -> None:
        """Window pages every event of which lies behind their user's
        window go back to the pool."""
        if pages:
            self._free_wpages.extend(pages)
            self._txn_wpages = [p for p in self._txn_wpages
                                if p not in pages]
            self._m_wpages.inc(len(pages), event="released")

    def rollback(self) -> None:
        with self._lock:
            live = {p for e in self._entries.values() for p in e.pages}
            self._free_pages.extend(p for p in self._txn_pages
                                    if p not in live)
            if self._txn_wpages:
                held = {p for e in self._entries.values() for p in e.wpages}
                self._free_wpages.extend(p for p in self._txn_wpages
                                         if p not in held)
            for st in self._staged.values():
                self._release(st.slot)
            for key in self._txn_created:
                e = self._entries.pop(key, None)
                if e is not None:
                    self._free_rows.append(e.row)
            self._staged.clear()
            self._txn_pages.clear()
            self._txn_wpages.clear()
            self._txn_created.clear()
            self._gauges()

    # -- what the model asks -------------------------------------------------

    def has(self, key: Hashable, count: bool = False) -> bool:
        """Whether ``key`` has state (committed, or staged in the open
        transaction); ``count`` records the turn as a hit or a miss."""
        with self._lock:
            found = key in self._staged or (
                key in self._entries and self._entries[key].length > 0)
            if count:
                self._m_state.inc(result="hit" if found else "miss")
            return found

    def length(self, key: Hashable) -> int:
        with self._lock:
            st = self._staged.get(key)
            if st is not None:
                return st.length
            e = self._entries.get(key)
            return e.length if e is not None else 0

    @property
    def max_pages(self) -> int:
        """Pages one user's history can hold: what a dispatch's flat list,
        or the user's page table, has room for."""
        return int(self.table_len or self.page_list_len)

    @property
    def max_events(self) -> int:
        """The longest history one dispatch can attend over."""
        return self.max_pages * self.page_size

    def pages_after(self, key: Hashable, n_new: int) -> int:
        return -(-(self.length(key) + int(n_new)) // self.page_size)

    def read_slot(self, key: Hashable) -> int:
        """The slot that holds ``key``'s fixed state as the open
        transaction sees it (``ZERO_SLOT`` for a key with none)."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return self.ZERO_SLOT
            st = self._staged.get(key)
            if st is not None and st.wrote:
                return st.slot
            if e.length == 0:
                return self.ZERO_SLOT
            return e.slot

    def table_row(self, key: Hashable) -> int:
        """The key's row of the device page table (row 0 is nobody's)."""
        with self._lock:
            e = self._entries.get(key)
            return 1 + e.row if e is not None else 0

    def plan(self, keys: Sequence[Hashable], seg_len: Sequence[int]
             ) -> Plan:
        """Slots and pages for a dispatch that adds ``seg_len[i]`` events
        to ``keys[i]``.  When the pool of slots is dry, the transaction's
        earlier programs are committed; when rows or pages run short, the
        least recently used users outside the open transaction are
        evicted."""
        with self._lock:
            if self._depth == 0:
                raise RuntimeError("plan() outside a transaction")
            keep = set(keys) | set(self._staged)
            plan = Plan(list(keys), [], [int(n) for n in seg_len], [], [],
                        [], self.page_size)
            for key, n in zip(keys, plan.seg_len):
                e = self._entries.get(key)
                if e is None:
                    if not self._free_rows:
                        self._evict_one(keep)
                    row = self._free_rows.pop()
                    e = self._entries[key] = _Entry(row, None)
                    self._txn_created.append(key)
                st = self._staged.get(key)
                if st is None:
                    st = _Staged(e.length, list(e.pages),
                                 wpages=list(e.wpages), wbase=e.wbase)
                if st.slot is None:
                    if not self._free_slots \
                            and not self._commit_early(set(keys)):
                        self._evict_one(keep)
                    st.slot = self._free_slots.pop()
                start = st.length
                need = -(-(start + n) // self.page_size) - len(st.pages) \
                    if self.page_bytes else 0
                pages = list(st.pages)
                for _ in range(max(need, 0)):
                    if not self._free_pages:
                        self._evict_one(keep)
                    page = self._free_pages.pop()
                    self._txn_pages.append(page)
                    plan.new_pages.append((1 + e.row, len(pages), page))
                    pages.append(page)
                wpages = list(st.wpages)
                if self.window_bytes:
                    reach = -(-(start + n) // self.page_size)
                    for _ in range(reach - st.wbase - len(wpages)):
                        wpages.append(self._take_window_page(keep, keys))
                    plan.seg_wpages.append(wpages)
                    plan.seg_wbase.append(st.wbase)
                # The pages and the slot are the key's from now on, so
                # that a later segment's eviction cannot hand them out
                # again.
                self._staged[key] = _Staged(st.length, pages, st.slot,
                                            st.wrote, wpages, st.wbase)
                if len(pages) > self.max_pages:
                    raise StateCacheFull(
                        f"{key!r} would hold {len(pages)} pages; a "
                        f"dispatch attends over {self.max_pages}")
                plan.seg_start.append(start)
                plan.read_slot.append(self.read_slot(key))
                plan.write_slot.append(st.slot)
                plan.seg_pages.append(pages)
                plan.table_row.append(1 + e.row)
            return plan

    def _take_window_page(self, keep, keys) -> int:
        """A window page for the plan being made: from the pool; else by
        committing the transaction's earlier programs, whose users then
        give back what fell behind their windows; else by an eviction."""
        if not self._free_wpages:
            self._commit_early(set(keys))
        while not self._free_wpages:
            self._evict_one(keep)
        page = self._free_wpages.pop()
        self._txn_wpages.append(page)
        self._m_wpages.inc(event="taken")
        return page

    def stage(self, plan: Plan) -> None:
        """The program of ``plan`` ran: its rows are there to commit.  Of
        a user's window pages the transaction keeps those the NEXT event's
        window reaches; of the others, those it handed out itself go back
        to the pool now (no committed state names them), the committed
        ones when it commits."""
        with self._lock:
            for i, (key, start, n, pages, slot) in enumerate(zip(
                    plan.keys, plan.seg_start, plan.seg_len, plan.seg_pages,
                    plan.write_slot)):
                st = _Staged(start + n, pages, slot, True)
                if self.window_bytes:
                    wpages, wbase = plan.seg_wpages[i], plan.seg_wbase[i]
                    first = max(start + n - (self.window_events - 1), 0) \
                        // self.page_size
                    drop = max(min(first - wbase, len(wpages)), 0)
                    committed = set(self._entries[key].wpages)
                    self._release_window(
                        set(wpages[:drop]) - committed)
                    st.wpages, st.wbase = wpages[drop:], wbase + drop
                self._staged[key] = st

    # -- eviction -------------------------------------------------------------

    def _evict_one(self, keep) -> None:
        for key in self._entries:
            if key not in keep:
                self.evict(key)
                return
        raise StateCacheFull(
            f"{len(keep)} users of one transaction need more than the "
            f"cache's {self.max_users} users, {self.n_slots - 2} slots "
            f"and {self.n_pages} pages")

    def evict(self, key: Hashable) -> bool:
        with self._lock:
            e = self._entries.pop(key, None)
            if e is None:
                return False
            self._forget(key, e)
            self._m_state.inc(result="evicted")
            self._gauges()
            return True

    def _forget(self, key: Hashable, e: _Entry) -> None:
        """Hand back the slot and every page of a key that has left
        ``_entries``, those staged in the open transaction too."""
        st = self._staged.pop(key, None)
        pages = set(e.pages) | set(st.pages if st is not None else ())
        self._txn_pages = [p for p in self._txn_pages if p not in pages]
        wpages = set(e.wpages) | set(st.wpages if st is not None else ())
        self._txn_wpages = [p for p in self._txn_wpages if p not in wpages]
        self._free_wpages.extend(wpages)
        if key in self._txn_created:
            self._txn_created.remove(key)
        self._free_pages.extend(pages)
        self._free_rows.append(e.row)
        self._release(e.slot)
        if st is not None:
            self._release(st.slot)

    def _gauges(self) -> None:
        used = self.n_pages - len(self._free_pages)
        self._m_users.set(len(self._entries))
        self._m_pages.set(used)
        held = bool(self.arrays)
        self._m_bytes.set(held * self.n_slots * self.kind_bytes["fixed"],
                          kind="fixed")
        if self.window_bytes:
            self._m_bytes.set(used * self.page_bytes, kind="full")
            self._m_bytes.set(
                (self.n_wpages - len(self._free_wpages)) * self.window_bytes,
                kind="window")
            return
        for kind in ("paged", "index"):
            self._m_bytes.set(used * self.kind_bytes[kind], kind=kind)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            out = {"users": len(self._entries),
                   "pagesUsed": self.n_pages - len(self._free_pages),
                   "pages": self.n_pages, "slots": self.max_users,
                   "bytes": self.bytes_in_use()}
            if self.window_bytes:
                out["windowPagesUsed"] = \
                    self.n_wpages - len(self._free_wpages)
                out["windowPages"] = self.n_wpages
            return out
