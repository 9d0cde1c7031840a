"""A ranked item list carried as two columns.

A template's ``batch_predict`` answers a cohort from two arrays (item
ids and scores).  What leaves the server is the JSON value
``[{"item": ..., "score": ...}, ...]``; between the two nothing has to
exist per item.  :class:`ItemScoreColumns` is what a template puts into
its ``PredictedResult.itemScores``: it holds the item strings and the
scores as two lists, renders the JSON value from them when the engine
server asks (:meth:`ItemScoreColumns.pio_json`), and is the list of the
template's ``ItemScore`` objects for whoever reads it as one (a custom
``Serving``, an evaluation metric, a test), building them on that first
read.

The engine server counts, once a dispatch, how many items left from the
columns and for how many the objects were built
(``pio_dispatch_items_total{form}``); :func:`dispatch_tally` is the
calling thread's tally it reads.
"""

from __future__ import annotations

import threading
from collections.abc import Sequence
from typing import Any, Callable, List

__all__ = ["ItemScoreColumns", "dispatch_tally"]


class _Tally(threading.local):
    """Items of this thread's results since its owner last cleared it."""

    columns = 0   # rendered to JSON straight from the columns
    objects = 0   # had their ItemScore objects built


_TALLY = _Tally()


def dispatch_tally() -> _Tally:
    """The calling thread's tally; a dispatch zeroes it before
    ``batch_predict`` and reads it after the last answer is rendered
    (predict, ``serve`` and the rendering all run on that thread)."""
    return _TALLY


class ItemScoreColumns(Sequence):
    """``items[j]`` scored ``scores[j]``, best first; reads as
    ``[make(items[0], scores[0]), ...]``.

    ``make`` is the template's own ``ItemScore`` class, a dataclass of
    ``(item, score)``.  The objects are built on the first read that
    needs one, all at once, and kept: a ``Serving`` that changes one and
    hands the prediction on is served what it changed.
    """

    __slots__ = ("items", "scores", "_make", "_objs")

    def __init__(self, items: List[Any], scores: List[float],
                 make: Callable[[Any, float], Any]):
        self.items = items
        self.scores = scores
        self._make = make
        self._objs = None

    def _objects(self) -> List[Any]:
        objs = self._objs
        if objs is None:
            objs = self._objs = list(map(self._make, self.items,
                                         self.scores))
            _TALLY.objects += len(objs)
        return objs

    def pio_json(self) -> List[dict]:
        """The JSON value of the list: what the generic dataclass walk
        gives for a list of ``ItemScore``, key for key."""
        objs = self._objs
        if objs is not None:   # already counted, and possibly changed
            return [{"item": o.item, "score": o.score} for o in objs]
        _TALLY.columns += len(self.items)
        return [{"item": i, "score": s}
                for i, s in zip(self.items, self.scores)]

    def __len__(self) -> int:
        return len(self.items)

    def __getitem__(self, index):
        return self._objects()[index]

    def __iter__(self):
        return iter(self._objects())

    def __eq__(self, other) -> bool:
        if isinstance(other, ItemScoreColumns):
            other = other._objects()
        elif not isinstance(other, list):
            return NotImplemented
        return self._objects() == other

    __hash__ = None

    def __add__(self, other) -> List[Any]:
        return self._objects() + list(other)

    def __radd__(self, other) -> List[Any]:
        return list(other) + self._objects()

    def __repr__(self) -> str:
        return (f"ItemScoreColumns(items={self.items!r}, "
                f"scores={self.scores!r})")
