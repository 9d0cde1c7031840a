"""Profiler trace -> numbers.  The only trace reduction in the repo.

Two steps, so the second can be checked on a small recorded trace
(``tests/data/small_trace.json``) without a chip:

``extract``  reads an ``.xplane.pb`` with nothing but jax
             (``jax.profiler.ProfileData``) into plain lists:
             ``{"device": {plane: [[name, start_ns, dur_ns], ...]},
                "host": [[name, start_ns, dur_ns], ...]}``
             device events from each ``/device:TPU:n`` plane's "XLA Ops"
             line, host events whose name starts with ``bench:`` (the
             harness's ``TraceAnnotation`` spans, same clock).
``reduce``   busy seconds (union of device-op intervals inside the
             window, averaged over the chips), the window, per-op summed
             seconds, and the idle time split by what the host was doing:
             each instant of a gap goes to the innermost ``bench:`` span
             open at that instant, or to ``unannotated``.

The window is the ``bench:window`` span the harness opens around the
measured loop.  Container events (a ``while`` and the ``jit_`` module
around its body) cover their children: they count for the union, not
for an op's own time.
"""

from __future__ import annotations

import glob
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench:window"
_CONTAINER = re.compile(r"^(%?while|jit_|%?call|%?conditional)")


def extract(trace_dir: str) -> Dict[str, Any]:
    import jax

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    device: Dict[str, List[List[Any]]] = {}
    host: List[List[Any]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"device": device, "host": host}


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(events, lo: float, hi: float):
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def _label_gap(gs: float, ge: float, marks, gaps: Dict[str, float]) -> None:
    """Split the idle gap [gs, ge) among the spans open during it: every
    piece goes to the shortest (innermost) span that covers it."""
    over = [(n, max(s, gs), min(e, ge), e - s) for n, s, e in marks
            if s < ge and e > gs]
    cuts = sorted({gs, ge, *(t for _, s, e, _ in over for t in (s, e))})
    for a, b in zip(cuts, cuts[1:]):
        inner = min((m for m in over if m[1] <= a and m[2] >= b),
                    key=lambda m: m[3], default=None)
        label = inner[0] if inner else "unannotated"
        gaps[label] = gaps.get(label, 0.0) + (b - a)


def op_key(name: str) -> str:
    """An op's stable name.  The trace names an op by its whole HLO line
    (``%fusion.123 = bf16[...] fusion(...)``); the key is the instruction
    name without ``%`` and numeric suffix (``fusion``,
    ``fused_topk_pallas``), so runs, seeds and shapes agree."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def reduce(trace: Dict[str, Any],
           window: Optional[Tuple[float, float]] = None) -> Dict[str, Any]:
    """Busy/idle/op seconds of ``trace`` inside the window."""
    host = trace["host"]
    if window is None:
        spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace holds no {WINDOW_SPAN} span")
        window = max(spans, key=lambda w: w[1] - w[0])
    lo, hi = window
    planes = trace["device"]
    busy_ns = 0.0
    ops: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    marks = [(n, s, s + d) for n, s, d in host if n != WINDOW_SPAN]
    for events in planes.values():
        inside = list(_clip(events, lo, hi))
        merged = _union((s, e) for _, s, e in inside)
        busy_ns += sum(e - s for s, e in merged)
        for name, s, e in inside:
            if not _CONTAINER.match(name):
                key = op_key(name)
                ops[key] = ops.get(key, 0.0) + (e - s)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                _label_gap(gs, ge, marks, gaps)
    chips = max(len(planes), 1)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / chips / 1e9,
        "chips_traced": len(planes),
        "op_s": {k: v / chips / 1e9 for k, v in ops.items()},
        "gap_s": {k: v / chips / 1e9 for k, v in gaps.items()},
    }


def kernel_seconds(reduced: Dict[str, Any], pattern: str) -> float:
    """Summed device seconds of the ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["op_s"].items() if rx.search(k))


def breakdown(reduced: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    def first(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": first(reduced["op_s"]),
            "idle_gaps": first(reduced["gap_s"])}
