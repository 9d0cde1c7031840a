"""Per-user serving state of a sequence model, of two kinds in one
manager, keyed by user (a loaded model owns its cache, so a cache IS a
model generation's state: ``/reload`` frees the outgoing one's):

* a FIXED slot per user: the short-convolution layers' last rows and the
  last hidden row (a few KB a layer; never grows), and
* PAGED rows that grow with the user's history: the attention layers'
  keys and values, ``PAGE_SIZE`` events a page.

Both live in device arrays the cache owns (``arrays``); a model's device
program reads and writes them at the slots and rows a :class:`Plan`
names.  The cache knows shapes, not models.

**Transactions.**  Every change belongs to a :meth:`StateCache.transaction`
(re-entrant; the engine server holds one around a whole dispatch).  A
program writes a user's fixed state into the slot's TWIN and new paged
rows beyond the user's committed length, so until :meth:`commit` flips
the twin and moves the length, every committed state is intact: a failed
dispatch rolls back to exactly what was there.  Evictions made to find
room are not undone (an evicted user is a later miss, which re-reads the
history, never a wrong answer).

**Budget.**  ``budget_bytes`` of device memory: ``max_users`` fixed slots
(two twins each) and as many pages as the rest holds.  When slots or
pages run out the least recently used user outside the open transaction
is evicted (``pio_seq_state_total{result="evicted"}``).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
from typing import Any, Dict, Hashable, List, Sequence, Tuple

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
    pair: int                 # slot pair; flat slots 2 + 2*pair (+ 1)
    twin: int = 0             # which of the pair holds the committed state
    length: int = 0           # committed events
    pages: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Staged:
    length: int
    pages: List[int]
    wrote: bool = False       # a program has written the twin


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

    def rows_of(self, tok_seg: np.ndarray, tok_pos: np.ndarray
                ) -> np.ndarray:
        """Pool row of each token's key and value."""
        rows = np.zeros(len(tok_seg), np.int64)
        for s, pages in enumerate(self.seg_pages):
            sel = tok_seg == s
            pos = tok_pos[sel]
            rows[sel] = (np.asarray(pages, np.int64)[pos // self.page_size]
                         * self.page_size + pos % self.page_size)
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

    def __init__(self, *, n_fixed_layers: int, n_paged_layers: int,
                 width: int, paged_width: int, budget_bytes: int,
                 max_users: int, page_size: int = PAGE_SIZE, registry=None):
        import jax.numpy as jnp

        self.n_fixed_layers = int(n_fixed_layers)
        self.n_paged_layers = int(n_paged_layers)
        self.width = int(width)
        self.paged_width = int(paged_width)
        self.page_size = int(page_size)
        self.page_list_len = PAGE_LIST_LEN
        self.max_users = int(max_users)
        self.dtype = jnp.bfloat16
        self.slot_bytes = 2 * (2 * self.n_fixed_layers + 1) * self.width * 2
        self.page_bytes = (2 * self.n_paged_layers * self.page_size
                           * self.paged_width * 2)
        fixed = (self.max_users + 1) * self.slot_bytes
        self.n_pages = int((int(budget_bytes) - fixed - self.page_bytes)
                           // max(self.page_bytes, 1)) \
            if self.n_paged_layers else 0
        if self.n_paged_layers and self.n_pages < 1:
            raise ValueError(
                f"a budget of {budget_bytes} bytes holds {self.max_users} "
                f"slots of {self.slot_bytes} bytes and no page of "
                f"{self.page_bytes}")
        self._lock = threading.RLock()
        self._depth = 0
        self._entries: "collections.OrderedDict[Hashable, _Entry]" = \
            collections.OrderedDict()
        self._staged: Dict[Hashable, _Staged] = {}
        self._txn_pages: List[int] = []
        self._txn_created: List[Hashable] = []
        self._free_pairs = list(range(self.max_users - 1, -1, -1))
        self._free_pages = list(range(self.n_pages, 0, -1))
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

    # -- device arrays -------------------------------------------------------

    def _allocate(self) -> None:
        import jax.numpy as jnp

        slots = 2 + 2 * self.max_users
        pool = (1 + self.n_pages, self.page_size, self.paged_width)
        self.arrays = {
            "conv": jnp.zeros((self.n_fixed_layers, slots, 2, self.width),
                              self.dtype),
            "h_last": jnp.zeros((slots, self.width), self.dtype),
            "k": [jnp.zeros(pool, self.dtype)
                  for _ in range(self.n_paged_layers)],
            "v": [jnp.zeros(pool, self.dtype)
                  for _ in range(self.n_paged_layers)],
        }

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
            self._txn_created.clear()
            self._free_pairs = list(range(self.max_users - 1, -1, -1))
            self._free_pages = list(range(self.n_pages, 0, -1))
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
            for key, st in self._staged.items():
                e = self._entries.get(key)
                if e is None:
                    continue
                e.length, e.pages = st.length, st.pages
                if st.wrote:
                    e.twin ^= 1
                self._entries.move_to_end(key)
            self._staged.clear()
            self._txn_pages.clear()
            self._txn_created.clear()
            self._gauges()

    def rollback(self) -> None:
        with self._lock:
            live = {p for e in self._entries.values() for p in e.pages}
            self._free_pages.extend(p for p in self._txn_pages
                                    if p not in live)
            for key in self._txn_created:
                e = self._entries.pop(key, None)
                if e is not None:
                    self._free_pairs.append(e.pair)
            self._staged.clear()
            self._txn_pages.clear()
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
    def max_events(self) -> int:
        """The longest history one dispatch can attend over."""
        return self.page_list_len * self.page_size

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
                return 2 + 2 * e.pair + (e.twin ^ 1)
            if e.length == 0:
                return self.ZERO_SLOT
            return 2 + 2 * e.pair + e.twin

    def plan(self, keys: Sequence[Hashable], seg_len: Sequence[int]
             ) -> Plan:
        """Slots and pages for a dispatch that adds ``seg_len[i]`` events
        to ``keys[i]``; evicts least recently used users outside the
        open transaction when slots or pages run short."""
        with self._lock:
            if self._depth == 0:
                raise RuntimeError("plan() outside a transaction")
            keep = set(keys) | set(self._staged)
            plan = Plan(list(keys), [], [int(n) for n in seg_len], [], [],
                        [], self.page_size)
            for key, n in zip(keys, plan.seg_len):
                e = self._entries.get(key)
                if e is None:
                    if not self._free_pairs:
                        self._evict_one(keep)
                    e = self._entries[key] = _Entry(self._free_pairs.pop())
                    self._txn_created.append(key)
                st = self._staged.get(key)
                if st is None:
                    st = _Staged(e.length, list(e.pages))
                start = st.length
                need = -(-(start + n) // self.page_size) - len(st.pages) \
                    if self.n_paged_layers else 0
                pages = list(st.pages)
                for _ in range(max(need, 0)):
                    if not self._free_pages:
                        self._evict_one(keep)
                    page = self._free_pages.pop()
                    self._txn_pages.append(page)
                    pages.append(page)
                if len(pages) > self.page_list_len:
                    raise StateCacheFull(
                        f"{key!r} would hold {len(pages)} pages; a "
                        f"dispatch attends over {self.page_list_len}")
                plan.seg_start.append(start)
                plan.read_slot.append(self.read_slot(key))
                plan.write_slot.append(2 + 2 * e.pair + (e.twin ^ 1))
                plan.seg_pages.append(pages)
                # The pages are the key's from now on, so that a later
                # segment's eviction cannot hand them out again.
                self._staged[key] = _Staged(st.length, pages, st.wrote)
            return plan

    def stage(self, plan: Plan) -> None:
        """The program of ``plan`` ran: its rows are there to commit."""
        with self._lock:
            for key, start, n, pages in zip(plan.keys, plan.seg_start,
                                            plan.seg_len, plan.seg_pages):
                self._staged[key] = _Staged(start + n, pages, True)

    # -- eviction -------------------------------------------------------------

    def _evict_one(self, keep) -> None:
        for key in self._entries:
            if key not in keep:
                self.evict(key)
                return
        raise StateCacheFull(
            f"{len(keep)} users of one dispatch need more than the "
            f"cache's {self.max_users} slots and {self.n_pages} pages")

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
        if key in self._txn_created:
            self._txn_created.remove(key)
        self._free_pages.extend(pages)
        self._free_pairs.append(e.pair)

    def _gauges(self) -> None:
        self._m_users.set(len(self._entries))
        self._m_pages.set(self.n_pages - len(self._free_pages))

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"users": len(self._entries),
                    "pagesUsed": self.n_pages - len(self._free_pages),
                    "pages": self.n_pages, "slots": self.max_users,
                    "bytes": self.bytes_in_use()}
