"""On-demand JAX profiler capture (Dapper-style sample-on-demand).

Continuous xplane capture is too heavy to leave on, so capture is armed
on demand — ``POST /admin/profile?duration_ms=`` on the admin server or
the ``pio profile`` CLI verb — runs for a bounded window, and stops
itself.  One capture at a time per process (the underlying
``jax.profiler`` session is a process singleton).

The start/stop callables are injectable so tests exercise the whole
state machine — busy, finished, platform-can't-capture — with fakes and
no real profiler artifacts; the HTTP layer maps
:class:`ProfilerUnavailable` to a clear **501** instead of crashing when
the platform cannot capture (no jax, no profiler plugin).
"""

from __future__ import annotations

import logging
import os
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from predictionio_tpu.obs.runtime import publish_event

logger = logging.getLogger(__name__)

__all__ = [
    "ProfilerUnavailable",
    "ProfilerBusy",
    "ProfilerSession",
    "get_profiler",
    "set_profiler",
    "capture",
    "handle_http",
]

# Hard ceiling on a requested capture window: an unattended multi-minute
# xplane capture can fill a disk.
MAX_CAPTURE_MS = 600_000.0


class ProfilerUnavailable(RuntimeError):
    """This platform/process cannot capture a profile (mapped to 501)."""


class ProfilerBusy(RuntimeError):
    """A capture is already running (mapped to 409)."""


def _default_start(path: str) -> None:
    try:
        import jax
    except Exception as e:  # pragma: no cover - jax is present in CI
        raise ProfilerUnavailable(f"jax unavailable: {e}") from e
    try:
        # The Python tracer stays off: it hooks every Python call of
        # every thread, and a served engine then stops being the system
        # one wanted a picture of (on one v5e at 200 queries/s: 14
        # dispatches in 2.7 s, p50 353 ms against 73).  The host side of
        # the timeline is the program's own pio: spans (obs.trace) and
        # jax's TraceMe events.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(path, profiler_options=options)
    except ProfilerUnavailable:
        raise
    except Exception as e:
        raise ProfilerUnavailable(
            f"profiler capture unsupported here: {e}") from e


def _default_stop() -> None:
    import jax

    jax.profiler.stop_trace()


class ProfilerSession:
    """One-at-a-time timed profiler capture with injectable backend.

    ``start(duration_ms)`` arms the capture and schedules the stop on a
    timer thread; ``stop()`` is idempotent and safe to call early.  The
    artifact directory defaults to a fresh ``pio_profile_*`` temp dir
    (override per call or via ``PIO_PROFILE_OUT``).
    """

    def __init__(self,
                 start_fn: Callable[[str], None] = _default_start,
                 stop_fn: Callable[[], None] = _default_stop,
                 clock: Callable[[], float] = time.monotonic,
                 timer_factory: Callable[..., threading.Timer]
                 = threading.Timer):
        self._start_fn = start_fn
        self._stop_fn = stop_fn
        self._clock = clock
        self._timer_factory = timer_factory
        self._lock = threading.Lock()
        # Serializes in-memory artifact tar builds (see artifact()).
        self._artifact_lock = threading.Lock()
        self._active_path: Optional[str] = None
        self._started_at: Optional[float] = None
        self._duration_ms: float = 0.0
        self._timer: Optional[threading.Timer] = None
        self._last_path: Optional[str] = None

    def start(self, duration_ms: float,
              out_dir: Optional[str] = None) -> Dict[str, Any]:
        """Arm a capture; returns {"path", "durationMs"}.

        Raises :class:`ProfilerBusy` when a capture is running and
        :class:`ProfilerUnavailable` when the platform cannot capture.
        """
        try:
            duration_ms = float(duration_ms)
        except (TypeError, ValueError):
            raise ValueError(f"bad duration_ms: {duration_ms!r}") from None
        if not duration_ms > 0:
            raise ValueError("duration_ms must be > 0")
        duration_ms = min(duration_ms, MAX_CAPTURE_MS)
        path = (out_dir or os.environ.get("PIO_PROFILE_OUT")
                or tempfile.mkdtemp(prefix="pio_profile_"))
        with self._lock:
            if self._active_path is not None:
                raise ProfilerBusy(
                    f"capture already running to {self._active_path}")
            self._start_fn(path)  # ProfilerUnavailable propagates un-armed
            self._active_path = path
            self._started_at = self._clock()
            self._duration_ms = duration_ms
            self._timer = self._timer_factory(duration_ms / 1e3, self.stop)
            self._timer.daemon = True
            self._timer.start()
        publish_event("profiler.start", path=path,
                      durationMs=round(duration_ms, 1))
        logger.info("profiler capture started: %s (%.0f ms)", path,
                    duration_ms)
        return {"path": path, "durationMs": duration_ms}

    def stop(self) -> Optional[str]:
        """Finish the active capture; returns its path (None if idle)."""
        with self._lock:
            path = self._active_path
            if path is None:
                return None
            timer, self._timer = self._timer, None
            self._active_path = None
            self._started_at = None
            self._last_path = path
            try:
                self._stop_fn()
            except Exception:
                # the capture window still produced whatever landed on
                # disk before the stop failed — report the path anyway
                logger.exception("profiler stop failed (artifacts may be "
                                 "partial): %s", path)
        if timer is not None:
            timer.cancel()
        publish_event("profiler.stop", path=path)
        logger.info("profiler capture finished: %s", path)
        return path

    def status(self) -> Dict[str, Any]:
        with self._lock:
            if self._active_path is None:
                return {"active": False, "lastPath": self._last_path}
            elapsed_ms = (self._clock() - (self._started_at or 0.0)) * 1e3
            return {"active": True, "path": self._active_path,
                    "durationMs": self._duration_ms,
                    "remainingMs": max(self._duration_ms - elapsed_ms, 0.0)}

    def artifact(self) -> Optional[Tuple[bytes, str]]:
        """(tar.gz bytes, filename) of the LAST finished capture — the
        download behind ``GET /admin/profile/artifact`` (ISSUE 9
        satellite: captures returned server-local paths since PR 3, so
        remote/fleet operation needed box access to retrieve them).

        Only the session's own ``_last_path`` is ever archived — the
        endpoint can not be steered at arbitrary server paths.  Returns
        None when no finished capture exists (HTTP 404 upstream); raises
        :class:`ProfilerBusy` while one is running (the artifact is
        still being written).

        The archive is built in memory (the handler plumbing answers
        with payload bytes either way); concurrent downloads serialize
        on a build lock so N clients cost ONE archive's peak at a time,
        not N."""
        import io
        import tarfile

        with self._artifact_lock:
            # Busy-check INSIDE the build lock: a waiter that queued
            # behind another download must re-validate, or a capture
            # armed meanwhile (same PIO_PROFILE_OUT dir) gets archived
            # while being written.
            with self._lock:
                if self._active_path is not None:
                    raise ProfilerBusy(
                        f"capture still running to {self._active_path}")
                path = self._last_path
            if not path or not os.path.isdir(path):
                return None
            buf = io.BytesIO()
            base = os.path.basename(os.path.normpath(path)) or "pio_profile"
            try:
                with tarfile.open(fileobj=buf, mode="w:gz") as tar:
                    tar.add(path, arcname=base)
            except OSError as e:
                # Files vanished/changed mid-walk: a capture started into
                # this directory after the busy-check — same verdict as
                # catching it before (409), never a truncated archive.
                raise ProfilerBusy(
                    f"capture artifacts changed while archiving: {e}")
            return buf.getvalue(), f"{base}.tar.gz"


_profiler = ProfilerSession()
_profiler_lock = threading.Lock()


def get_profiler() -> ProfilerSession:
    """THE process profiler session (admin server + CLI)."""
    return _profiler


def set_profiler(session: ProfilerSession) -> ProfilerSession:
    """Swap the process session (tests); returns the previous one."""
    global _profiler
    with _profiler_lock:
        prev, _profiler = _profiler, session
    return prev


def capture(duration_ms: float, out_dir: Optional[str] = None,
            sleep: Callable[[float], None] = time.sleep) -> str:
    """Blocking capture (the local ``pio profile`` path): start, wait the
    window out, stop, return the artifact path."""
    session = get_profiler()
    info = session.start(duration_ms, out_dir)
    # start() caps the window at MAX_CAPTURE_MS — wait out the CAPPED
    # duration, not the raw request, or an over-asked CLI blocks long
    # after the timer already stopped the capture.
    sleep(info["durationMs"] / 1e3)
    return session.stop() or info["path"]


def handle_http(method: str, path: str, params: Dict[str, list]
                ) -> Optional[tuple]:
    """The profiler's HTTP routes, for every server whose process an
    operator may want on a device timeline (the admin server, and the
    engine server: only the process that holds the chip can trace it).
    ``POST /admin/profile?duration_ms=[&out=DIR]`` arms a capture, ``GET
    /admin/profile`` reports status, ``GET /admin/profile/artifact``
    downloads the last finished capture as a tar.gz.  Returns the
    handler tuple, or None where ``path`` is not one of these."""
    if path == "/admin/profile/artifact" and method == "GET":
        try:
            art = get_profiler().artifact()
        except ProfilerBusy as e:
            return 409, {"message": str(e)}
        if art is None:
            return 404, {"message": "no finished profiler capture "
                                    "in this process"}
        data, filename = art
        return 200, data, "application/gzip", {
            "Content-Disposition": f'attachment; filename="{filename}"'}
    if path != "/admin/profile":
        return None
    profiler = get_profiler()
    if method == "GET":
        return 200, profiler.status()
    if method != "POST":
        return 404, {"message": "Not Found"}
    raw = params.get("duration_ms", ["2000"])[0]
    try:
        duration_ms = float(raw)
        if not duration_ms > 0:
            raise ValueError
    except ValueError:
        return 400, {"message": f"bad duration_ms: {raw!r}"}
    out_dir = params.get("out", [None])[0]
    try:
        info = profiler.start(duration_ms, out_dir)
    except ProfilerBusy as e:
        return 409, {"message": str(e)}
    except ProfilerUnavailable as e:
        # The clear degrade: this platform/process cannot capture (no
        # jax, no profiler plugin) — a 501 the caller can act on, never
        # a crash/500.
        return 501, {"message": f"profiler capture unavailable: {e}"}
    return 200, {"status": "profiling", **info}
