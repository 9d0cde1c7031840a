"""Shared HTTP plumbing for every predictionio_tpu server.

One place for the transport knobs the Event Server, Engine Server,
Dashboard, and Admin server previously each copy-pasted, plus the
request-id glue every frontend speaks:

- :class:`ThreadingHTTPServer` — stdlib ``ThreadingHTTPServer`` with a
  128-deep accept backlog (the default of 5 resets connections under
  load bursts; measured on the event server).
- ``X-Request-ID`` handling: :func:`incoming_request_id` pulls and
  sanitizes the client-supplied id (or None → the tracer generates one);
  every response carries the effective id back, so a client (or an
  upstream proxy) can join its logs to the server's trace/JSONL records.
- :class:`BaseHandler` — the per-request handler skeleton: HTTP/1.1
  keep-alive, Nagle off (Nagle + delayed-ACK between our multi-write
  responses and a keep-alive client stalls every request ~40 ms —
  measured: 44 ms/req persistent vs 0.9 ms without), debug-level access
  logs, and a :meth:`BaseHandler.respond` helper that writes a JSON or
  Prometheus-text payload with Content-Length and the request-id header.
- :meth:`BaseHandler.dispatch` — THE request driver every frontend used
  to copy-paste (with intentional-but-drifting differences; ROADMAP
  resilience follow-on (d)): trace root + ``http.read`` /
  ``http.handle`` / ``http.respond`` spans, deadline scope with optional
  pre-handle shedding, per-server completion hook (stats + plugins), and
  the ``Retry-After`` hint on degraded answers.  Subclasses implement
  :meth:`BaseHandler.pio_handle` and override the small hooks below it.
"""

from __future__ import annotations

import json
import logging
import time
from http.server import (
    BaseHTTPRequestHandler,
    ThreadingHTTPServer as _ThreadingHTTPServer,
)
from typing import Any, Dict, List, Optional, Tuple, Union
from urllib.parse import parse_qs, urlparse

from predictionio_tpu.obs import waterfall as _waterfall
from predictionio_tpu.obs.host import register_thread, retire_thread
from predictionio_tpu.obs.trace import (
    attach_event,
    current_trace_id,
    get_recorder,
    sanitize_trace_id,
    slow_request_ms,
    span,
    trace,
)
from predictionio_tpu.resilience import deadline as _deadline
from predictionio_tpu.resilience.deadline import DEADLINE_HEADER

logger = logging.getLogger(__name__)

__all__ = [
    "ThreadingHTTPServer",
    "BaseHandler",
    "REQUEST_ID_HEADER",
    "DEADLINE_HEADER",
    "PROMETHEUS_CTYPE",
    "incoming_request_id",
    "incoming_deadline_ms",
    "payload_bytes",
    "timeline_payload",
    "traces_payload",
]

REQUEST_ID_HEADER = "X-Request-ID"
PROMETHEUS_CTYPE = "text/plain; version=0.0.4"


class ThreadingHTTPServer(_ThreadingHTTPServer):
    # Default accept backlog (5) resets connections under load bursts.
    request_queue_size = 128

    def process_request_thread(self, request, client_address):
        """A connection's thread, start to end: its CPU time counts as
        the ``handler`` role's in the host ledger (``obs.host``)."""
        register_thread("handler")
        try:
            super().process_request_thread(request, client_address)
        finally:
            retire_thread()


def incoming_request_id(headers) -> Optional[str]:
    """Sanitized client-supplied ``X-Request-ID`` (None → generate one)."""
    if headers is None:
        return None
    return sanitize_trace_id(headers.get(REQUEST_ID_HEADER))


def incoming_deadline_ms(headers) -> Optional[float]:
    """Client-declared time budget (``X-PIO-Deadline-Ms``); None when
    absent or unparseable — a garbage header must not 500 the request."""
    if headers is None:
        return None
    raw = headers.get(DEADLINE_HEADER)
    if not raw:
        return None
    try:
        budget = float(raw)
    except ValueError:
        return None
    return budget if budget >= 0 else None


def payload_bytes(payload: Any) -> Tuple[bytes, str]:
    """(body, content-type) for a handler payload: ``str`` means
    Prometheus text exposition, anything else is JSON."""
    if isinstance(payload, str):
        return payload.encode(), PROMETHEUS_CTYPE
    return json.dumps(payload).encode(), "application/json; charset=UTF-8"


def timeline_payload(params: Dict[str, List[str]]) -> Dict[str, Any]:
    """The shared ``GET /timeline.json`` view over the process step
    timeline.  ``?model=`` filters, ``?n=`` bounds the record count, and
    ``?format=chrome`` returns Chrome-trace JSON (chrome://tracing /
    Perfetto); ``?format=summary`` returns only the per-model phase
    aggregation, which the fleet aggregator (``obs/fleet.py``) merges
    into ``/fleet.json``."""
    from predictionio_tpu.obs.runtime import get_timeline

    tl = get_timeline()
    model = params.get("model", [None])[0]
    try:
        n = int(params.get("n", ["256"])[0])
    except ValueError:
        n = 256
    fmt = params.get("format", ["raw"])[0]
    if fmt == "chrome":
        return tl.to_chrome_trace(max(n, 1), model=model)
    models = tl.models() if model is None else [model]
    summaries = {m: tl.summary(m) for m in models}
    if fmt == "summary":
        return {"models": summaries}
    return {"steps": tl.recent(n, model=model), "models": summaries}


def param_bool(params: Optional[Dict[str, List[str]]], key: str,
               default: bool = False) -> bool:
    """Boolean query param in the same dialect as env_bool — so
    ``?exemplars=0`` / ``?exemplars=off`` actually means OFF (a bare
    presence check would read an explicit opt-out as opt-in)."""
    from predictionio_tpu.config import env_bool

    vals = (params or {}).get(key) or [""]
    return env_bool(vals[0], default)


def traces_payload(params: Dict[str, List[str]]) -> Dict[str, Any]:
    """The shared ``GET /traces.json`` view (every frontend).

    ``?request_id=`` resolves one exact trace (exemplar links from the
    ``pio_serve_stage_ms`` waterfall buckets land here), ``?min_ms=``
    keeps only traces at least that slow, ``?limit=`` bounds the count
    (default 50, clamped to the ring)."""
    request_id = sanitize_trace_id(params.get("request_id", [None])[0])
    try:
        limit = int(params.get("limit", ["50"])[0])
    except ValueError:
        limit = 50
    min_ms: Optional[float] = None
    raw = params.get("min_ms", [None])[0]
    if raw:
        try:
            min_ms = float(raw)
        except ValueError:
            min_ms = None
    return {"traces": get_recorder().recent(
        limit, request_id=request_id, min_ms=min_ms)}


# A handler hook's result: (status, payload) with the content type
# inferred by payload_bytes, (status, payload, ctype) when the frontend
# picks its own (the dashboard's HTML pages), or
# (status, payload, ctype, headers) when it also sets response headers
# (the profiler artifact's Content-Disposition).
HandlerResult = Union[Tuple[int, Any], Tuple[int, Any, str],
                      Tuple[int, Any, str, Dict[str, str]]]


class BaseHandler(BaseHTTPRequestHandler):
    """Shared request-handler skeleton; subclasses implement
    :meth:`pio_handle` and route their do_* methods through
    :meth:`dispatch` (or keep replying directly through :meth:`respond`).
    """

    protocol_version = "HTTP/1.1"
    # See module docstring: keep-alive + Nagle stalls every request ~40 ms.
    disable_nagle_algorithm = True
    server_log_name = "server"
    # Short server tag used in traces and the shed counter ("event", ...).
    trace_server_name = "server"
    # Shed with 504 BEFORE pio_handle when the deadline is already spent
    # (the event server's pre-auth shed; the engine server sheds inside
    # its handler, right before the expensive predict, instead).
    shed_pre_handle = False
    # Rewrite a 2xx whose budget ran out DURING handling into a 504
    # (ISSUE 6: an expired request gets 504, never a slow 200).  The
    # verdict and the X-PIO-Deadline-Remaining-Ms attestation are ONE
    # measurement, so a 200 always attests positive remaining budget.
    # Only safe on non-mutating frontends — the engine server opts in;
    # an event-server write that SUCCEEDED must report its success.
    shed_late_responses = False
    # Degraded answers that carry the Retry-After backoff hint: spill
    # accepts (202), admission rejections (429), and unavailability
    # (503) all want the client to come back, just later.
    retry_after_statuses = (202, 429, 503)

    # -- per-frontend hooks --------------------------------------------------

    def pio_handle(self, method: str, path: str,
                   params: Dict[str, List[str]], body: bytes) -> HandlerResult:
        """Handle one request; runs inside the trace + deadline scope."""
        raise NotImplementedError

    def pio_on_complete(self, method: str, path: str, status: int,
                        ms: float, body: bytes,
                        params: Dict[str, List[str]]
                        ) -> Optional[Dict[str, str]]:
        """Post-handle hook (stats recording, plugins); runs BEFORE the
        response is written — a client reading /stats.json right after
        its own request completes must see it counted.  May return extra
        response headers."""
        return None

    def pio_retry_after_s(self) -> Optional[int]:
        """Backoff hint attached to ``retry_after_statuses`` answers."""
        return None

    def pio_shed(self) -> None:
        """Count a transport-level deadline shed (pre-handle 504)."""

    # -- THE request driver --------------------------------------------------

    def dispatch(self, method: str) -> None:
        t0 = time.perf_counter()
        # Receipt wall for the waterfall's ingress stage (the engine
        # handler arms the collector mid-handle, after body read+routing
        # already happened — it reads this to bill them).
        _waterfall.note_transport_start(t0)
        with trace("http.request",
                   trace_id=incoming_request_id(self.headers),
                   slow_ms=slow_request_ms(),
                   server=self.trace_server_name, method=method) as troot:
            parsed = urlparse(self.path)
            troot.set(path=parsed.path)
            params = parse_qs(parsed.query)
            with span("http.read"):
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
            remaining: Optional[float] = None
            with _deadline.deadline_scope(
                    incoming_deadline_ms(self.headers)):
                if self.shed_pre_handle and _deadline.exceeded():
                    # A request whose budget is already gone must not
                    # queue behind auth/storage.
                    self.pio_shed()
                    out: HandlerResult = (504, {"message":
                                                "Deadline exceeded."})
                else:
                    with span("http.handle"):
                        out = self.pio_handle(method, parsed.path, params,
                                              body)
                    remaining = _deadline.remaining_ms()
            t_shed = time.perf_counter()
            handler_headers: Dict[str, str] = {}
            if len(out) == 4:
                status, payload, ctype, handler_headers = out  # type: ignore[misc]
            elif len(out) == 3:
                status, payload, ctype = out  # type: ignore[misc]
            else:
                status, payload = out  # type: ignore[misc]
                ctype = None
            if (self.shed_late_responses and remaining is not None
                    and remaining <= 0 and 200 <= status < 300):
                # The handler answered, but past its budget: the client
                # stopped waiting — 504, not a slow 2xx (see class attr).
                self.pio_shed()
                status, payload, ctype = 504, {
                    "message": "Deadline exceeded before response."}, None
            troot.set(status=status)
            ms = (time.perf_counter() - t0) * 1e3
            extra = dict(self.pio_on_complete(method, parsed.path, status,
                                              ms, body, params) or {})
            for k, v in handler_headers.items():
                extra.setdefault(k, v)
            # The server's own read+handle wall time: clients use it to
            # attribute client-vs-server latency drift and to ATTEST
            # deadline compliance — a 200 whose
            # X-PIO-Server-Ms is inside the sent budget was served in
            # time by the server's clock, whatever transport queueing
            # added around it.
            extra.setdefault("X-PIO-Server-Ms", f"{ms:.1f}")
            if remaining is not None:
                # Deadline attestation: the SAME reading the late-shed
                # verdict used — a 200 always carries remaining > 0
                # (though formatting may floor a sliver to 0.00, so
                # verifiers must treat only NEGATIVE values as late).
                extra.setdefault("X-PIO-Deadline-Remaining-Ms",
                                 f"{remaining:.2f}")
            retry_after = self.pio_retry_after_s()
            if retry_after is not None and status in self.retry_after_statuses:
                extra.setdefault("Retry-After", str(retry_after))
            wf = _waterfall.current_waterfall()
            if wf is not None:
                # shed_check: scheduler hand-back → the respond write —
                # the handler's span unwind + stats hooks (from the
                # handler_done mark when the engine set one), the
                # late-shed verdict, and response-header assembly.  Small,
                # but the waterfall must account for it so the stage sum
                # reconciles with X-PIO-Server-Ms.
                t_fin = wf.take_mark("handler_done") or t_shed
                wf.stamp("shed_check",
                         (time.perf_counter() - t_fin) * 1e3)
            with span("http.respond") as rspan:
                if ctype is None:
                    data, ctype = payload_bytes(payload)
                else:
                    data = (payload.encode() if isinstance(payload, str)
                            else payload)
                self.respond(status, data, ctype, extra,
                             request_id=current_trace_id())
            if wf is not None:
                # serialize: result → JSON bytes + the socket write.
                wf.stamp("serialize", rspan.duration_ms or 0.0)
                doc = wf.finalize(
                    trace_id=current_trace_id(), status=status,
                    total_ms=(time.perf_counter() - t0) * 1e3,
                    attested_ms=ms)
                if doc:
                    attach_event(troot, "waterfall",
                                 **{k: v for k, v in doc.items()
                                    if k not in ("ts", "traceId")})
                _waterfall.deactivate()

    def respond(self, status: int, data: bytes, ctype: str,
                extra_headers: Optional[Dict[str, str]] = None,
                request_id: Optional[str] = None) -> None:
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        if request_id:
            self.send_header(REQUEST_ID_HEADER, request_id)
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, fmt, *args):
        logger.debug("%s %s", self.server_log_name, fmt % args)
