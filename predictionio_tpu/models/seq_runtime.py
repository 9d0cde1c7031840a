"""Turns -> dispatches, for any backbone of the sequence engine.

:class:`SequenceRuntime` packs turns into token buckets
(:func:`predictionio_tpu.ops.ragged.pack_turns`), plans slots and pages
with the :class:`~predictionio_tpu.serving.state_cache.StateCache`, and
runs the backbone's device programs; a turn longer than a bucket is taken
in chunks.  What is the backbone's own comes from its STEP object
(``models.lfm2.LFM2Step``, ``models.sala.SALAStep``,
``models.sambay.SambaYStep``, ``models.granite_h.GraniteHStep``):

    step.cfg, step.token_buckets, step.read_buckets
    step.program(cache, t, r, k)   a jitted ``fn(params, arrays, vec) ->
                                   (arrays, out)`` with the arrays donated
    step.batch_vector(pack, plan, t, r, cache)   the dispatch's int32
                                   arrays as one vector
    step.read_out(out, r, k, plan) (scores [r, k], ids [r, k]) of what
                                   came back; moves the backbone's counters

One upload and one download a dispatch: every hand-over between the
batcher's thread and the runtime lets the server's handler threads take
the interpreter, so the int32 arrays of a batch travel as one vector and
the answers come back as one.
"""

from __future__ import annotations

import dataclasses
import gc
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from predictionio_tpu.obs import dispatch_stage, get_registry
from predictionio_tpu.ops.ragged import TurnPack, pack_turns

__all__ = ["SequenceRuntime", "Turn", "K_MENU"]

# The answer's widths a program is compiled for.
K_MENU = (16, 128, 1024)


@dataclasses.dataclass
class Turn:
    """One query's part of a dispatch: the user's new item ids (oldest
    first; may be empty) and how many answers it wants."""

    key: Any
    items: np.ndarray
    num: int = 10


def _bucket(n: int, menu: Sequence[int]) -> int:
    for b in menu:
        if n <= b:
            return b
    raise ValueError(f"{n} is over the largest bucket {menu[-1]}")


def _settle_heap() -> None:
    """After a program's first run.  Tracing and compiling leave about
    100k objects that live as long as the runtime, beside the 170k of
    the imports and the model, and a full pass of the cycle collector
    over them stops every thread: 105 ms once in ~4,000 requests on the
    chip (``PERF.md``, finding 8 of PR 28).  One pass now, on a call
    that has just paid a compile, then ``gc.freeze`` keeps later passes
    to what requests leave behind.  The unfreeze first lets the pass
    after a reload take the model before."""
    gc.unfreeze()
    gc.collect()
    gc.freeze()


class SequenceRuntime:
    """Runs turns against a :class:`StateCache`.  One per loaded model;
    callers hold the cache's transaction around :meth:`extend` (the
    engine server does, for a whole dispatch).  The cache's write pool
    holds ``read_buckets[-1]`` slots, the most users one program
    touches, so a call of more users runs within it, committed program
    by program."""

    def __init__(self, step, params: Dict[str, Any], cache):
        self.step = step
        self.cfg = step.cfg
        self.params = params
        self.cache = cache
        # The program shapes (tests set smaller ones to split a turn
        # over dispatches with a short history).
        self.token_buckets = step.token_buckets
        self.read_buckets = step.read_buckets
        self._programs: Dict[Tuple[int, int, int], Any] = {}
        reg = get_registry()
        self._m_tokens = reg.counter(
            "pio_seq_tokens_total",
            "Events run through the sequence backbone, by kind: new (a "
            "turn's own) or prefill (a history re-read after a miss).",
            ("kind",))
        self._m_dispatches = reg.counter(
            "pio_seq_dispatches_total",
            "Device programs the sequence runtime launched.")

    def program(self, t: int, r: int, k: int):
        key = (t, r, k)
        fn = self._programs.get(key)
        if fn is None:
            fn = self._programs[key] = self.step.program(self.cache, t, r, k)
        return fn

    def extend(self, turns: Sequence[Turn], prefill: Optional[Dict[Any, int]]
               = None) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Apply ``turns`` in order and answer each: ``(scores, item
        ids)`` of its top ``num``, at its last event (a turn with no
        event answers from the user's state as it stands; a user with no
        event at all gets empty arrays).  ``prefill[key]``: how many of
        the key's first items are a re-read history, for the counters."""
        k = min(_bucket(max([t.num for t in turns] + [1]), K_MENU),
                self.cfg.vocab_size)
        out: List[Tuple[np.ndarray, np.ndarray]] = [
            (np.zeros(0, np.float32), np.zeros(0, np.int32))] * len(turns)
        # A turn has an answer if its user has any event by then: in the
        # cache, or brought by this or an earlier turn of the call.
        known = set()
        pending = []
        for i, turn in enumerate(turns):
            if len(turn.items) or turn.key in known \
                    or self.cache.length(turn.key):
                known.add(turn.key)
                pending.append((i, turn.key,
                                np.asarray(turn.items, np.int32)))
        for pack in pack_turns(pending, max_tokens=self.token_buckets[-1],
                               max_reads=self.read_buckets[-1],
                               max_pages=self.cache.page_list_len,
                               pages_of=self.cache.pages_after):
            scores, ids = self._run(pack, k)
            for row, i in enumerate(pack.read_turn):
                n = turns[i].num
                out[i] = (scores[row, :n], ids[row, :n])
        n_pre = sum((prefill or {}).values())
        n_new = sum(len(items) for _, _, items in pending) - n_pre
        if n_pre:
            self._m_tokens.inc(n_pre, kind="prefill")
        if n_new:
            self._m_tokens.inc(n_new, kind="new")
        return out

    def _run(self, pack: TurnPack, k: int
             ) -> Tuple[np.ndarray, np.ndarray]:
        cache, step = self.cache, self.step
        t = _bucket(pack.n_tokens, self.token_buckets)
        r = _bucket(len(pack.read_turn), self.read_buckets)
        with dispatch_stage("seq.extend", "seq_extend"):
            with dispatch_stage("seq.extend.h2d", "seq_h2d"):
                plan = cache.plan(pack.seg_key, pack.seg_len)
                batch = jax.device_put(
                    step.batch_vector(pack, plan, t, r, cache))
            with dispatch_stage("seq.extend.launch", "seq_launch"):
                first_run = (t, r, k) not in self._programs
                fn = self.program(t, r, k)
                _, out = cache.run(fn, self.params, batch)
            with dispatch_stage("seq.extend.wait", "seq_wait"):
                out = np.asarray(jax.device_get(out))
            cache.stage(plan)
        if first_run:
            _settle_heap()
        self._m_dispatches.inc()
        return step.read_out(out, r, k, plan)
