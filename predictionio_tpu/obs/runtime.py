"""Runtime introspection: XLA compile tracking, device-memory telemetry,
and the per-step pipeline timeline ring.

PR 1 made requests and training phases observable; this module opens the
layer *below* — the JAX runtime — following the always-on/low-overhead
model of Google-Wide Profiling (Ren et al., IEEE Micro 2010):

- :class:`CompileTracker` wraps jit entry points and exports
  ``pio_xla_compile_total{fn}`` / ``pio_xla_compile_ms{fn}``; a function
  that compiles more than ``PIO_COMPILE_WARN_THRESHOLD`` times (default
  3) logs a structured shape-churn warning.  Every compile also lands in
  the PR-1 trace ring (:func:`publish_event`), so a slow request or
  training step can be explained by "recompiled here".
- :class:`DeviceMemorySampler` polls ``device.memory_stats()`` (and a
  ``jax.live_arrays()`` fallback for backends like CPU that report no
  allocator stats) into ``pio_device_mem_bytes{device,kind}`` gauges with
  per-train-run peak tracking (``pio_device_mem_peak_bytes{device}``),
  surfaced by ``pio status``.  The clock/devices are injectable (same
  discipline as ``resilience/policy.py``) so tests run on fakes with no
  wall sleeps.
- :class:`StepTimeline` is a process-wide ring of per-step pipeline phase
  decompositions (host_wait / h2d / device_wait / device_step, fed by
  ``obs.pipeline.PipelineProbe``), served at ``/timeline.json``,
  exportable as Chrome-trace JSON; its per-model summary is what the
  fleet aggregator (``obs/fleet.py``) merges into ``/fleet.json``.

Like the rest of ``obs``, importing this module never imports jax: all
jax touches are lazy and degrade to no-ops when jax is absent — the
event server keeps its jax-free footprint.
"""

from __future__ import annotations

import collections
import functools
import logging
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from predictionio_tpu.obs.host import get_host_ledger, register_thread
from predictionio_tpu.obs.metrics import MetricsRegistry, get_registry
from predictionio_tpu.obs.trace import (
    Span,
    TraceRecorder,
    current_span,
    current_trace_id,
    get_recorder,
    new_trace_id,
    span,
)

logger = logging.getLogger(__name__)

__all__ = [
    "publish_event",
    "CompileTracker",
    "get_compile_tracker",
    "track_compiles",
    "DeviceMemorySampler",
    "get_memory_sampler",
    "StepTimeline",
    "get_timeline",
    "set_timeline",
    "start_runtime_introspection",
    "reset_runtime",
]


# -- trace-ring events -------------------------------------------------------

def publish_event(name: str, *, recorder: Optional[TraceRecorder] = None,
                  **attrs) -> None:
    """Publish a zero-duration annotation into the trace ring.

    Inside an active trace the event attaches as a child span of the
    innermost open span — a request that triggered a recompile (or hit a
    breaker transition, or spilled) carries the evidence in its own span
    tree.  Outside any trace it records as a standalone single-span trace
    so the ring still shows runtime incidents with their wall time.
    """
    ev = Span(name, attrs)
    ev.duration_ms = 0.0
    parent = current_span()
    if parent is not None:
        parent.children.append(ev)
        return
    (recorder or get_recorder()).record(
        current_trace_id() or new_trace_id(), ev)


# -- XLA compile tracking ----------------------------------------------------

def _jit_cache_size(jitted: Any) -> Optional[int]:
    """Compiled-variant count of a ``jax.jit`` wrapper (None: unknowable)."""
    f = getattr(jitted, "_cache_size", None)
    if f is None:
        return None
    try:
        return int(f())
    except Exception:
        return None


_trace_state_clean: Optional[Callable[[], bool]] = None


def _outside_jax_trace() -> bool:
    """True unless we are inside jax tracing (a wrapped jit called from an
    outer jit inlines — its cache growth is not an independent compile)."""
    global _trace_state_clean
    if _trace_state_clean is None:
        try:
            from jax.core import trace_state_clean as f  # type: ignore
        except Exception:
            def f() -> bool:
                return True
        _trace_state_clean = f
    try:
        return _trace_state_clean()
    except Exception:
        return True


class CompileTracker:
    """Counts XLA compilations per tracked jit entry point.

    Instruments resolve from the process registry at record time (not
    construction), so a test-isolation registry reset never strands the
    tracker on unregistered series.  Detection is cache-growth across a
    call: a call after which the jit wrapper holds more compiled variants
    than before paid a compilation, and the call's wall time bounds the
    compile time (trace+lower+compile dominate such calls).
    """

    def __init__(self, warn_threshold: Optional[int] = None,
                 registry: Optional[MetricsRegistry] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self._registry = registry
        self._clock = clock
        self._env_threshold = warn_threshold is None
        self.warn_threshold = (self._read_threshold()
                               if warn_threshold is None
                               else int(warn_threshold))

    @staticmethod
    def _read_threshold() -> int:
        try:
            return int(os.environ.get("PIO_COMPILE_WARN_THRESHOLD", "3"))
        except ValueError:
            return 3

    def _reg(self) -> MetricsRegistry:
        return self._registry or get_registry()

    def _counter(self):
        return self._reg().counter(
            "pio_xla_compile_total",
            "XLA compilations observed per tracked jit entry point.",
            ("fn",))

    def _hist(self):
        return self._reg().histogram(
            "pio_xla_compile_ms",
            "Wall time of calls that triggered an XLA compilation.",
            ("fn",))

    def touch(self) -> None:
        """Register the instruments so ``/metrics`` exposes them from t=0."""
        self._counter()
        self._hist()

    def record(self, fn: str, duration_ms: float) -> None:
        """One observed compilation of ``fn`` taking ``duration_ms``."""
        c = self._counter()
        c.inc(fn=fn)
        self._hist().observe(duration_ms, fn=fn)
        publish_event("xla.compile", fn=fn,
                      durationMs=round(float(duration_ms), 3))
        n = int(c.value(fn=fn))
        threshold = (self._read_threshold() if self._env_threshold
                     else self.warn_threshold)
        if n > threshold:
            # Shape churn: the same function keeps recompiling — varying
            # shapes or unhashed static args defeat the jit cache.
            logger.warning(
                "shape churn: jit fn %r compiled %d times "
                "(threshold %d, PIO_COMPILE_WARN_THRESHOLD); recurring "
                "recompilation usually means varying input shapes or "
                "non-canonical static args", fn, n, threshold)

    def wrap(self, fn_name: str, jitted: Callable) -> Callable:
        """Proxy a jitted callable; cache growth across a call = compile."""
        tracker = self

        @functools.wraps(jitted)
        def wrapper(*args, **kwargs):
            if not _outside_jax_trace():
                return jitted(*args, **kwargs)
            before = _jit_cache_size(jitted)
            t0 = tracker._clock()
            out = jitted(*args, **kwargs)
            if before is not None:
                after = _jit_cache_size(jitted)
                if after is not None and after > before:
                    tracker.record(fn_name, (tracker._clock() - t0) * 1e3)
            return out

        wrapper.__wrapped__ = jitted
        return wrapper


_compile_tracker = CompileTracker()


def get_compile_tracker() -> CompileTracker:
    """THE process compile tracker (models wrap their jit steps on it)."""
    return _compile_tracker


def track_compiles(fn_name: str) -> Callable[[Callable], Callable]:
    """Decorator form: ``step = track_compiles("model.step")(jax.jit(f))``."""
    def deco(jitted: Callable) -> Callable:
        return get_compile_tracker().wrap(fn_name, jitted)
    return deco


# -- device-memory telemetry -------------------------------------------------

def _default_devices() -> Sequence[Any]:
    """Local jax devices — ONLY when jax is already loaded in this process
    (a jax-free event server must not pay a jax import for telemetry)."""
    if "jax" not in sys.modules:
        return ()
    import jax

    return jax.local_devices()


def _default_live_arrays() -> Sequence[Any]:
    if "jax" not in sys.modules:
        return ()
    import jax

    return jax.live_arrays()


class DeviceMemorySampler:
    """Background device-memory poller over the shared registry.

    Exports every numeric key of ``device.memory_stats()`` as
    ``pio_device_mem_bytes{device,kind}`` (kind = the stats key, e.g.
    ``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit``) plus a
    ``live_bytes`` / ``live_arrays`` aggregate from ``jax.live_arrays()``
    for backends whose allocator reports nothing (CPU).  Tracks the peak
    ``bytes_in_use`` per device since the last :meth:`reset_peak` —
    ``run_train`` resets at run start, so the gauge IS the train run's
    peak.  ``devices_fn`` / ``live_arrays_fn`` / ``clock`` are injectable
    so tests sample fakes with no wall sleeps; the poll thread is started
    only via :meth:`start` and ticks every ``interval_s`` (env
    ``PIO_MEM_SAMPLE_INTERVAL_S``, default 10; <= 0 disables the thread,
    :meth:`sample_once` stays callable).
    """

    def __init__(self, interval_s: Optional[float] = None,
                 registry: Optional[MetricsRegistry] = None,
                 devices_fn: Callable[[], Sequence[Any]] = _default_devices,
                 live_arrays_fn: Callable[[], Sequence[Any]]
                 = _default_live_arrays,
                 clock: Callable[[], float] = time.monotonic):
        if interval_s is None:
            try:
                interval_s = float(
                    os.environ.get("PIO_MEM_SAMPLE_INTERVAL_S", "10"))
            except ValueError:
                interval_s = 10.0
        self.interval_s = float(interval_s)
        self._registry = registry
        self._devices_fn = devices_fn
        self._live_arrays_fn = live_arrays_fn
        self._clock = clock
        self._lock = threading.Lock()
        self._peaks: Dict[str, float] = {}
        self._peak_since: float = clock()
        # HBM headroom guardrail (one warning per device per peak window):
        # prefetch depth x donated buffers changes the training memory
        # profile, so the per-run peak is checked against the device
        # bytes limit every sample.
        self._hbm_warned: set = set()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _reg(self) -> MetricsRegistry:
        return self._registry or get_registry()

    @staticmethod
    def _warn_fraction() -> float:
        """``PIO_HBM_WARN_FRACTION`` (default 0.9): warn when a train
        run's peak ``bytes_in_use`` exceeds this fraction of the device
        ``bytes_limit``.  <= 0 disables the check."""
        try:
            return float(os.environ.get("PIO_HBM_WARN_FRACTION", "0.9"))
        except ValueError:
            return 0.9

    def _headroom_counter(self):
        return self._reg().counter(
            "pio_hbm_headroom_warn_total",
            "Times a train-run memory peak crossed the HBM headroom "
            "warning fraction (PIO_HBM_WARN_FRACTION of bytes_limit).",
            ("device",))

    def _gauges(self):
        reg = self._reg()
        return (reg.gauge(
            "pio_device_mem_bytes",
            "Device memory by device and kind (memory_stats keys; "
            "live_bytes/live_arrays fall back to jax.live_arrays()).",
            ("device", "kind")),
            reg.gauge(
            "pio_device_mem_peak_bytes",
            "Peak bytes_in_use per device since the last peak reset "
            "(run_train resets at run start).", ("device",)))

    def touch(self) -> None:
        self._gauges()
        self._headroom_counter()

    @staticmethod
    def _label(device: Any) -> str:
        return f"{getattr(device, 'platform', 'dev')}:" \
               f"{getattr(device, 'id', 0)}"

    def sample_once(self) -> Dict[str, Dict[str, float]]:
        """Poll every device once; returns {device: {kind: value}}."""
        gauge, peak_gauge = self._gauges()
        out: Dict[str, Dict[str, float]] = {}
        try:
            devices = list(self._devices_fn())
        except Exception:
            logger.debug("device enumeration failed", exc_info=True)
            return out
        for d in devices:
            label = self._label(d)
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            row: Dict[str, float] = {}
            for k, v in (stats or {}).items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    gauge.set(float(v), device=label, kind=str(k))
                    row[str(k)] = float(v)
            if row:
                out[label] = row
        # live-array fallback ONLY for devices whose allocator reported
        # nothing (CPU): a TPU train process with tens of thousands of
        # live arrays must not pay an O(arrays) walk per tick on top of
        # memory_stats().
        if len(out) < len(devices):
            self._sample_live_arrays(gauge, out,
                                     skip=frozenset(out))
        frac = self._warn_fraction()
        warn: List[tuple] = []
        with self._lock:
            for label, row in out.items():
                in_use = row.get("bytes_in_use", row.get("live_bytes"))
                if in_use is None:
                    continue
                # Deliberately NOT folding the allocator's
                # peak_bytes_in_use in: that key is monotone since
                # allocator creation and would defeat reset_peak() —
                # this window is the max of OUR samples (it can
                # undershoot a spike between ticks; the lifetime peak
                # stays visible as its own kind gauge).
                peak = max(self._peaks.get(label, 0.0), in_use)
                self._peaks[label] = peak
                peak_gauge.set(peak, device=label)
                # HBM headroom guardrail: the peak against the allocator
                # limit, once per device per peak window (run_train's
                # reset_peak re-arms it).
                limit = row.get("bytes_limit")
                if (frac > 0 and limit and peak > frac * limit
                        and label not in self._hbm_warned):
                    self._hbm_warned.add(label)
                    warn.append((label, peak, limit))
        for label, peak, limit in warn:
            self._headroom_counter().inc(device=label)
            logger.warning(
                "HBM headroom: device %s train-run peak %.0f MiB is "
                "%.1f%% of its %.0f MiB limit (warn fraction %.2f, "
                "PIO_HBM_WARN_FRACTION) — reduce PIO_PREFETCH_DEPTH, "
                "the batch size, or the model/table sharding footprint "
                "before the allocator OOMs",
                label, peak / 2**20, 100.0 * peak / limit,
                limit / 2**20, frac)
        return out

    def _sample_live_arrays(self, gauge, out, skip=frozenset()) -> None:
        """live-array aggregate per device (the stats-less-backend
        fallback); ``skip`` holds devices the allocator already covered."""
        try:
            arrays = self._live_arrays_fn()
        except Exception:
            return
        agg: Dict[str, List[float]] = {}
        for a in arrays:
            try:
                devs = a.devices() if callable(getattr(a, "devices", None)) \
                    else [getattr(a, "device", None)]
                nbytes = float(getattr(a, "nbytes", 0) or 0)
            except Exception:
                continue
            for d in devs or ():
                if d is None:
                    continue
                label = self._label(d)
                if label not in skip:
                    row = agg.setdefault(label, [0.0, 0.0])
                    row[0] += nbytes
                    row[1] += 1
                break  # attribute fully-replicated arrays once
        for label, (nbytes, count) in agg.items():
            gauge.set(nbytes, device=label, kind="live_bytes")
            gauge.set(count, device=label, kind="live_arrays")
            row = out.setdefault(label, {})
            row["live_bytes"] = nbytes
            row["live_arrays"] = count

    def peaks(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._peaks)

    def headroom_exceeded(self, fraction: Optional[float] = None) -> bool:
        """Train-run PEAK ``bytes_in_use`` (since ``reset_peak``, folded
        with one fresh sample) checked against the HBM headroom
        guardrail: True when any device's peak crosses ``fraction``
        (default ``PIO_HBM_WARN_FRACTION``) of its allocator
        ``bytes_limit``.  The fusion/batch autotuner's probe — it
        decides at round boundaries, i.e. in the trough BETWEEN windows
        (and the host runs ahead of the device), so the instantaneous
        sample routinely misses the mid-scan peak the background
        sampler saw; deciding on the trough would grow straight past
        the limit into an OOM.  Backends reporting no limit (CPU
        live-array fallback) can never push back and return False."""
        frac = self._warn_fraction() if fraction is None else float(fraction)
        if frac <= 0:
            return False
        rows = self.sample_once()  # also folds this sample into _peaks
        with self._lock:
            peaks = dict(self._peaks)
        for label, row in rows.items():
            in_use = row.get("bytes_in_use")
            limit = row.get("bytes_limit")
            peak = max(in_use or 0.0, peaks.get(label, 0.0))
            if peak and limit and peak > frac * limit:
                return True
        return False

    def reset_peak(self) -> None:
        """Start a fresh peak window (run_train calls this at run start)."""
        with self._lock:
            self._peaks.clear()
            self._hbm_warned.clear()
            self._peak_since = self._clock()

    # -- background thread --------------------------------------------------

    def start(self) -> bool:
        """Start the poll thread (idempotent); False when disabled."""
        if self.interval_s <= 0:
            return False
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return True
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="pio-mem-sampler", daemon=True)
            self._thread.start()
        return True

    def _tick(self) -> span:
        """One tick as a span: ``pio:mem_sampler.sample`` on a capture's
        clock, ``pio_mem_sampler_ms`` and its CPU twin.  Wall far over
        CPU says ``memory_stats()`` waited for the runtime (and whether
        it held the interpreter meanwhile shows in what else stalled
        under the annotation)."""
        reg = self._reg()
        return span(
            "mem_sampler.sample", annotate=True,
            hist=reg.histogram(
                "pio_mem_sampler_ms",
                "One tick of the device-memory sampler."),
            cpu_hist=reg.histogram(
                "pio_mem_sampler_cpu_ms",
                "CPU time the sampler's thread ran inside one tick."))

    def _run(self) -> None:
        register_thread("sampler")
        while not self._stop.wait(self.interval_s):
            try:
                with self._tick():
                    self.sample_once()
            except Exception:
                logger.exception("device-memory sample failed")

    def stop(self, timeout: float = 2.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout)
        self._thread = None


_memory_sampler = DeviceMemorySampler()


def get_memory_sampler() -> DeviceMemorySampler:
    """THE process device-memory sampler."""
    return _memory_sampler


# -- step timeline ring ------------------------------------------------------

class StepTimeline:
    """Ring of per-step pipeline phase decompositions, per model.

    Each record is one training iteration's wall decomposition as
    measured by ``PipelineProbe`` (host_wait → h2d → device_wait on the
    host lane; device_step overlapped on the device lane).  Served at
    ``/timeline.json`` and exportable as Chrome-trace JSON (load in
    ``chrome://tracing`` / Perfetto).  Ring size: ``PIO_TIMELINE_RING``
    (records, default 2048).
    """

    PHASES = ("host_wait", "h2d", "h2d_overlap", "dispatch",
              "device_wait", "device_step")
    # host-lane phases whose sum approximates the iteration's wall time.
    # h2d_overlap is deliberately NOT here: prefetched staging runs under
    # device compute (data/prefetch.py) and costs the step loop nothing.
    # dispatch IS here: the step call's own wall — on synchronous-
    # dispatch backends (CPU with donated buffers) it carries the
    # execution itself, and before ISSUE 7 it hid between probe points.
    WALL_PHASES = ("host_wait", "h2d", "dispatch", "device_wait")

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            try:
                capacity = int(os.environ.get("PIO_TIMELINE_RING", "2048"))
            except ValueError:
                capacity = 2048
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=max(int(capacity), 1))
        self._seq = 0

    def record(self, model: str, *, host_wait_ms: float = 0.0,
               h2d_ms: float = 0.0, h2d_overlap_ms: float = 0.0,
               dispatch_ms: float = 0.0,
               device_wait_ms: float = 0.0,
               device_step_ms: float = 0.0, examples: int = 0,
               start_s: Optional[float] = None,
               dispatch_s: Optional[float] = None,
               staged_s: Optional[float] = None,
               step: Optional[int] = None,
               fused_steps: int = 1) -> None:
        if start_s is None:
            start_s = time.time()
        rec = {
            "model": model,
            "startS": round(float(start_s), 6),
            "hostWaitMs": round(float(host_wait_ms), 4),
            "h2dMs": round(float(h2d_ms), 4),
            "h2dOverlapMs": round(float(h2d_overlap_ms), 4),
            "dispatchMs": round(float(dispatch_ms), 4),
            "deviceWaitMs": round(float(device_wait_ms), 4),
            "deviceStepMs": round(float(device_step_ms), 4),
            "examples": int(examples),
            # Optimizer steps this ONE record (= one dispatch) covers: a
            # K-fused lax.scan window writes K — the per-dispatch wall is
            # attributable to K steps, and the summary's ``fuse_steps``
            # is their mean per dispatch.
            "fusedSteps": max(int(fused_steps), 1),
        }
        # True dispatch / staging-end wall clocks (when known): the
        # Chrome export draws the device and prefetch lanes from these
        # instead of approximating from the step start.
        if dispatch_s is not None:
            rec["dispatchS"] = round(float(dispatch_s), 6)
        if staged_s is not None:
            rec["stagedS"] = round(float(staged_s), 6)
        with self._lock:
            self._seq += 1
            rec["step"] = int(step if step is not None else self._seq)
            self._ring.append(rec)

    def recent(self, n: int = 256,
               model: Optional[str] = None) -> List[Dict[str, Any]]:
        """Last ``n`` records, most recent first (optionally one model)."""
        with self._lock:
            items = list(self._ring)
        if model is not None:
            items = [r for r in items if r["model"] == model]
        return items[::-1][:max(n, 0)]

    def models(self) -> List[str]:
        with self._lock:
            return sorted({r["model"] for r in self._ring})

    def summary(self, model: Optional[str] = None) -> Dict[str, Any]:
        """Aggregate phase totals/shares, served by
        ``/timeline.json?format=summary`` and merged per instance into
        ``/fleet.json`` (``obs/fleet.py``).

        ``phase_share`` is each host-lane phase's share of the summed
        host-lane wall (host_wait + h2d + device_wait): the decomposition
        of where the training loop's time actually went.
        """
        with self._lock:
            items = [r for r in self._ring
                     if model is None or r["model"] == model]
        totals = {p: 0.0 for p in self.PHASES}
        examples = 0
        steps = 0
        for r in items:
            totals["host_wait"] += r["hostWaitMs"]
            totals["h2d"] += r["h2dMs"]
            totals["h2d_overlap"] += r.get("h2dOverlapMs", 0.0)
            totals["dispatch"] += r.get("dispatchMs", 0.0)
            totals["device_wait"] += r["deviceWaitMs"]
            totals["device_step"] += r["deviceStepMs"]
            examples += r["examples"]
            steps += max(int(r.get("fusedSteps", 1)), 1)
        wall = sum(totals[p] for p in self.WALL_PHASES)
        shares = {p: (totals[p] / wall if wall > 0 else 0.0)
                  for p in self.WALL_PHASES}
        return {
            "model": model,
            # Optimizer steps vs dispatches: with K-step fusion one
            # record covers K steps, so the pair exposes the mean
            # fusion depth.
            "steps": steps,
            "dispatches": len(items),
            "fuse_steps": round(steps / len(items), 2) if items else 0.0,
            "examples": examples,
            "phase_ms": {p: round(v, 3) for p, v in totals.items()},
            "phase_share": {p: round(v, 4) for p, v in shares.items()},
        }

    def to_chrome_trace(self, n: int = 2048,
                        model: Optional[str] = None) -> Dict[str, Any]:
        """Chrome-trace-format export (``?format=chrome``).

        Host-lane phases lay out sequentially from each step's start.
        The device step rides a second lane from the recorded dispatch
        timestamp (``dispatchS``) when present — the true h2d/compute
        overlap — falling back to the step start for records written
        before dispatch stamping.  Prefetched staging (``h2dOverlapMs``)
        draws on a third lane, ending when the batch left the prep
        thread (``stagedS``), so the overlap with the previous step's
        device lane is visible rather than inferred.
        """
        records = self.recent(n, model=model)[::-1]  # chronological
        pids = {m: i + 1 for i, m in
                enumerate(sorted({r["model"] for r in records}))}
        has_prefetch = {r["model"] for r in records
                        if r.get("h2dOverlapMs", 0) > 0}
        events: List[Dict[str, Any]] = []
        for m, pid in pids.items():
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "tid": 0, "args": {"name": m}})
            lanes = [(0, "host"), (1, "device")]
            if m in has_prefetch:
                lanes.append((2, "prefetch"))
            for tid, lane in lanes:
                events.append({"name": "thread_name", "ph": "M", "pid": pid,
                               "tid": tid, "args": {"name": lane}})
        for r in records:
            pid = pids[r["model"]]
            ts = r["startS"] * 1e6
            for key, name in (("hostWaitMs", "host_wait"),
                              ("h2dMs", "h2d"),
                              ("dispatchMs", "dispatch"),
                              ("deviceWaitMs", "device_wait")):
                dur = r.get(key, 0.0) * 1e3
                if dur <= 0:
                    continue
                events.append({"name": name, "ph": "X", "pid": pid,
                               "tid": 0, "ts": round(ts, 3),
                               "dur": round(dur, 3),
                               "args": {"step": r["step"]}})
                ts += dur
            if r["deviceStepMs"] > 0:
                dev_ts = r.get("dispatchS", r["startS"]) * 1e6
                events.append({"name": "device_step", "ph": "X", "pid": pid,
                               "tid": 1, "ts": round(dev_ts, 3),
                               "dur": round(r["deviceStepMs"] * 1e3, 3),
                               "args": {"step": r["step"],
                                        "examples": r["examples"]}})
            overlap = r.get("h2dOverlapMs", 0.0)
            if overlap > 0:
                dur = overlap * 1e3
                end = r.get("stagedS")
                if end is None:  # staging ended when the queue get returned
                    end = r["startS"] + r["hostWaitMs"] / 1e3
                events.append({"name": "h2d_overlap", "ph": "X", "pid": pid,
                               "tid": 2, "ts": round(end * 1e6 - dur, 3),
                               "dur": round(dur, 3),
                               "args": {"step": r["step"]}})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._seq = 0


_timeline = StepTimeline()
_timeline_lock = threading.Lock()


def get_timeline() -> StepTimeline:
    """THE process step-timeline ring (probe writes, servers serve)."""
    return _timeline


def set_timeline(timeline: StepTimeline) -> StepTimeline:
    """Swap the process timeline (tests); returns the previous one."""
    global _timeline
    with _timeline_lock:
        prev, _timeline = _timeline, timeline
    return prev


# -- process wiring ----------------------------------------------------------

def start_runtime_introspection(*, sample: bool = True) -> None:
    """Idempotent per-process bring-up, called by the servers: register
    the compile/memory instruments (so ``/metrics`` exposes the names
    before the first event), start the memory-sampler thread and hang
    the host ledger (``obs.host``: threads by role, the cycle
    collector's pauses, CPU throttling) on the registry's render."""
    get_host_ledger().install()
    get_compile_tracker().touch()
    sampler = get_memory_sampler()
    sampler.touch()
    sampler.start()
    if sample:
        try:
            sampler.sample_once()
        except Exception:
            logger.debug("initial device-memory sample failed",
                         exc_info=True)


def reset_runtime() -> None:
    """Test isolation: empty timeline + fresh peak window."""
    get_timeline().clear()
    get_memory_sampler().reset_peak()
