"""Process-wide fault-tolerance layer (tail-at-scale machinery).

The paper positions predictionio_tpu as a production ML *server*: ingest
must not lose events and queries must degrade gracefully under partial
failure.  This package is the one home for that machinery, wired through
every network hop (SDK → event/engine servers → RemoteClient):

- :mod:`predictionio_tpu.resilience.policy` — :class:`RetryPolicy`
  (jittered exponential backoff, ``Retry-After``-aware) and
  :class:`CircuitBreaker` (closed/open/half-open, exported as
  ``pio_breaker_state`` gauges).
- :mod:`predictionio_tpu.resilience.deadline` — ``X-PIO-Deadline-Ms``
  budget propagation; a request that cannot finish in budget sheds early
  with 504 instead of queueing.
- :mod:`predictionio_tpu.resilience.faults` — env-driven fault injection
  (``PIO_FAULTS="storage.create:error:0.3,storage.find:delay:200ms"``)
  hooked into the storage base layer, the JSON-RPC framing, and the HTTP
  handlers; used by tests.
- :mod:`predictionio_tpu.resilience.spill` — storage-outage spill
  journal: a durable append-only JSONL file the event server degrades
  into (202 + ``Retry-After``) plus the background replay worker that
  drains it on recovery.
- :mod:`predictionio_tpu.resilience.supervision` — run supervision for
  the model lifecycle: step watchdog (``PIO_STEP_TIMEOUT_S``),
  divergence rollback (``PIO_DIVERGENCE_RETRIES``), SIGTERM preemption
  (``pio train`` exits :data:`~supervision.PREEMPTED_EXIT_CODE` after a
  final checkpoint), and the finite-model validation behind the engine
  server's staged reload.

Idempotency tokens make remote-storage writes *safely* retriable: the
JSON-RPC client stamps every write with a client-generated token, the
server keeps a bounded dedup window, and :func:`idempotency_key` lets
the spill-replay path pin a persisted token so a crashed replay never
double-inserts.

stdlib-only on import (same constraint as :mod:`predictionio_tpu.obs`).
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, Optional

from predictionio_tpu.resilience.deadline import (
    DEADLINE_HEADER,
    DeadlineExceeded,
    deadline_scope,
    remaining_ms,
)
from predictionio_tpu.resilience.faults import (
    FaultInjected,
    FaultPlan,
    fault_point,
)
from predictionio_tpu.resilience.policy import (
    CircuitBreaker,
    CircuitOpenError,
    RetryPolicy,
)
from predictionio_tpu.resilience.spill import ReplayWorker, SpillJournal
from predictionio_tpu.resilience.supervision import (
    PREEMPTED_EXIT_CODE,
    DivergenceGuard,
    ModelValidationError,
    StepWatchdog,
    TrainDiverged,
    TrainPreempted,
    install_preemption_handler,
    preemption_requested,
    request_preemption,
    validate_model_finite,
)

__all__ = [
    "CircuitBreaker",
    "CircuitOpenError",
    "RetryPolicy",
    "DEADLINE_HEADER",
    "DeadlineExceeded",
    "deadline_scope",
    "remaining_ms",
    "FaultInjected",
    "FaultPlan",
    "fault_point",
    "ReplayWorker",
    "SpillJournal",
    "idempotency_key",
    "current_idempotency_key",
    "PREEMPTED_EXIT_CODE",
    "DivergenceGuard",
    "ModelValidationError",
    "StepWatchdog",
    "TrainDiverged",
    "TrainPreempted",
    "install_preemption_handler",
    "preemption_requested",
    "request_preemption",
    "validate_model_finite",
]


# -- idempotency-token plumbing --------------------------------------------
#
# The JSON-RPC client (data/storage/remote.py) stamps every write with a
# fresh client-generated token unless one is pinned here.  The spill
# replay worker pins the token PERSISTED in the journal so that a replay
# retried after a lost reply (or a process crash between insert and
# journal compaction) dedups server-side instead of double-inserting.

_IDEM_TOKEN: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "pio_idempotency_token", default=None)


@contextlib.contextmanager
def idempotency_key(token: str) -> Iterator[str]:
    """Pin the idempotency token used by the NEXT remote-storage write on
    this thread/context (nested scopes override)."""
    tok = _IDEM_TOKEN.set(token)
    try:
        yield token
    finally:
        _IDEM_TOKEN.reset(tok)


def current_idempotency_key() -> Optional[str]:
    return _IDEM_TOKEN.get()
