"""Unified observability layer (SURVEY §5.5 rebuild addition).

Three parts, one process-wide state:

- :mod:`predictionio_tpu.obs.metrics` — thread-safe Counter / Gauge /
  Histogram registry with label support and THE Prometheus text renderer
  behind every server's ``GET /metrics``.
- :mod:`predictionio_tpu.obs.trace` — span/trace API with per-request
  trace ids (``X-Request-ID``), a last-N ring buffer (``GET
  /traces.json``), JSONL export (``PIO_TRACE_FILE``), and slow-request
  logging (``PIO_SLOW_REQUEST_MS``).
- :mod:`predictionio_tpu.obs.pipeline` — training-loop probe decomposing
  the feeder→device pipeline into host-wait / H2D / device-step.
- :mod:`predictionio_tpu.obs.runtime` — runtime introspection below the
  request/training layer: XLA compile tracking, device-memory telemetry,
  the per-step timeline ring, and trace-ring event publication.
- :mod:`predictionio_tpu.obs.host` — the host's own ledger: threads by
  role from ``schedstat``, the cycle collector's pauses, the cgroup's
  CPU throttling, read when the registry renders.
- :mod:`predictionio_tpu.obs.profiler` — on-demand bounded
  ``jax.profiler`` capture behind ``POST /admin/profile`` and
  ``pio profile``.
- :mod:`predictionio_tpu.obs.waterfall` — per-request serving stage
  decomposition (``pio_serve_stage_ms{stage}`` + exemplars + the
  ``PIO_REQUEST_LOG`` wide-event JSONL).
- :mod:`predictionio_tpu.obs.slo` — availability/latency SLOs,
  multi-window burn rates, the ``/ready`` degradation verdict.
- :mod:`predictionio_tpu.obs.fleet` — Prometheus-text parsing and the
  type-correct multi-instance merge behind ``/fleet.json`` /
  ``pio status --fleet``.
- :mod:`predictionio_tpu.obs.quality` — model-quality observability:
  sampled prediction stream, scorecard drift (PSI/KL), shadow-scored
  canaries, feedback-joined online hit-rate, and the ``/quality.json``
  promotion gate.

stdlib-only on import: safe from the CLI, the servers, and the data layer
without touching jax/numpy.
"""

from predictionio_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    set_registry,
)
from predictionio_tpu.obs.pipeline import PipelineProbe
from predictionio_tpu.obs.runtime import (
    CompileTracker,
    DeviceMemorySampler,
    StepTimeline,
    get_compile_tracker,
    get_memory_sampler,
    get_timeline,
    publish_event,
    reset_runtime,
    set_timeline,
    start_runtime_introspection,
    track_compiles,
)
from predictionio_tpu.obs.trace import (
    Span,
    TraceRecorder,
    attach_event,
    current_span,
    current_trace_id,
    get_recorder,
    new_trace_id,
    sanitize_trace_id,
    set_recorder,
    slow_request_ms,
    span,
    trace,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "set_registry",
    "PipelineProbe",
    "CompileTracker",
    "DeviceMemorySampler",
    "StepTimeline",
    "get_compile_tracker",
    "get_memory_sampler",
    "get_timeline",
    "publish_event",
    "set_timeline",
    "start_runtime_introspection",
    "track_compiles",
    "Span",
    "TraceRecorder",
    "attach_event",
    "current_span",
    "current_trace_id",
    "get_recorder",
    "new_trace_id",
    "sanitize_trace_id",
    "set_recorder",
    "slow_request_ms",
    "span",
    "trace",
    "phase",
    "dispatch_stage",
    "reset_observability",
]

def phase(name: str, **attrs) -> span:
    """Span + per-phase duration histogram + ``pio:<name>`` profiler
    annotation: a thin use of :class:`span`'s two trace-independent
    sinks.

    The workflow's named phases (datasource / prepare / train / persist,
    and the ``prep.*`` / ``train.*`` phases inside ALS prep and train)
    show up in the trace tree, as ``pio_train_phase_ms{phase=...}``
    series with the CPU twin ``pio_train_phase_cpu_ms`` (what the
    phase's own thread ran of that wall), and on a profiler capture's
    host timeline — crashed phases too, the runs most worth seeing.
    (The metric names are literals by design — tools/lint_metrics.py
    keeps every registered name statically checkable.)
    """
    reg = get_registry()
    hist = reg.histogram(
        "pio_train_phase_ms", "Workflow phase duration by phase name.",
        ("phase",))
    cpu_hist = reg.histogram(
        "pio_train_phase_cpu_ms",
        "CPU time the phase's own thread ran, by phase name.", ("phase",))
    return span(name, hist=hist, labels={"phase": name}, annotate=True,
                cpu_hist=cpu_hist, **attrs)


def dispatch_stage(name: str, stage: str, **attrs) -> span:
    """:func:`phase`'s serving twin: one host stage of a batched
    dispatch (bind, supplement, lookup, h2d, launch, wait, assemble,
    serve) as span ``name``, ``pio_dispatch_stage_ms{stage}`` with its
    CPU twin ``pio_dispatch_stage_cpu_ms{stage}`` and a ``pio:<name>``
    annotation.  Per dispatch, never per request."""
    reg = get_registry()
    hist = reg.histogram(
        "pio_dispatch_stage_ms",
        "Host stages of one batched dispatch, by stage.", ("stage",))
    cpu_hist = reg.histogram(
        "pio_dispatch_stage_cpu_ms",
        "CPU time the dispatching thread ran inside each host stage of "
        "one batched dispatch, by stage.", ("stage",))
    return span(name, hist=hist, labels={"stage": stage}, annotate=True,
                cpu_hist=cpu_hist, **attrs)


def reset_observability() -> None:
    """Fresh registry + empty trace ring + empty timeline/peaks (test
    isolation; see conftest)."""
    get_registry().reset()
    get_recorder().clear()
    reset_runtime()
    # A test that drives the engine's pio_handle directly (no transport
    # driver) arms the request waterfall but nothing finalizes it — drop
    # the leaked collector so the NEXT test's contextvar view is clean.
    from predictionio_tpu.obs import waterfall as _waterfall
    _waterfall.deactivate()
    # The feedback joiner is process-global (engine notes serves, event
    # server joins) — drop it with the registry its counters lived in.
    from predictionio_tpu.obs.quality import reset_quality
    reset_quality()
