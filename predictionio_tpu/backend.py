"""Which accelerator this process runs on, and where its compiles go.

One process owns one chip.  ``pio train`` / ``deploy`` / ``eval`` /
``batchpredict`` call :func:`resolve_backend` once at start-up: it places
the persistent compile cache, asks jax which platform it got, refuses a
CPU that jax fell back to behind the operator's back, and logs the answer.
Everything downstream (``ops.pallas_kernels.pallas_supported``, the ALS
``auto`` settings) keys off ``jax.default_backend()`` with no fallback of
its own, so what this module logged is what runs.
"""

from __future__ import annotations

import dataclasses
import glob
import logging
import os
import threading
from pathlib import Path
from typing import Any, Dict

import jax

logger = logging.getLogger(__name__)

__all__ = ["Backend", "BackendError", "configure_compile_cache",
           "describe_backend", "resolve_backend", "attached_tpu_chips",
           "compile_stats"]

_CHECKOUT = Path(__file__).resolve().parents[1]
_GOOGLE_PCI_VENDOR = "0x1ae0"


class BackendError(RuntimeError):
    """The process did not get the accelerator the host has."""


@dataclasses.dataclass(frozen=True)
class Backend:
    platform: str        # jax.devices()[0].platform
    device_kind: str     # jax.devices()[0].device_kind
    device_count: int    # len(jax.devices())
    # How the Pallas kernels run here: Mosaic-compiled on TPU, the Pallas
    # interpreter anywhere else (the CPU test path).
    pallas: str          # "compiled" | "interpret"

    def as_json(self) -> Dict[str, Any]:
        """The ``backend`` block of ``GET /``."""
        return {"platform": self.platform, "deviceKind": self.device_kind,
                "deviceCount": self.device_count, "pallas": self.pallas}

    def as_env(self) -> Dict[str, str]:
        """The same keys for ``EngineInstance.env`` (string-valued)."""
        return {k: str(v) for k, v in self.as_json().items()}


def configure_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax has already read it and
    nothing here sets another.  Otherwise the cache is
    ``<checkout>/.jax_cache`` — a fixed path (the path is part of the
    cache key), never derived from ``PIO_HOME``, a pid or the time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class _CompileClock:
    """Seconds this process spent in XLA backend compiles, and how many
    of them the persistent cache answered (``jax.monitoring`` events —
    process-wide by nature: jax has one listener registry)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._installed = False
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def install(self) -> None:
        with self._lock:
            if self._installed:
                return
            self._installed = True
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += secs
                self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"compileSeconds": round(self.seconds, 3),
                    "compiles": self.compiles,
                    "compileCacheHits": self.cache_hits}


_COMPILE_CLOCK = _CompileClock()


def compile_stats() -> Dict[str, float]:
    """Backend-compile seconds / count / persistent-cache hits since
    :func:`resolve_backend` (zeros in a process that never called it)."""
    return _COMPILE_CLOCK.snapshot()


def attached_tpu_chips() -> int:
    """TPU chips on this host's PCI bus (Google vendor id), whether or
    not this process could take them."""
    n = 0
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            if Path(vendor).read_text().strip() == _GOOGLE_PCI_VENDOR:
                n += 1
        except OSError:
            continue
    return n


def describe_backend() -> Backend:
    """The backend jax resolved, as jax reports it.  Raises whatever jax
    raises when the platform ``JAX_PLATFORMS`` names cannot start."""
    devs = jax.devices()
    platform = devs[0].platform
    return Backend(platform=platform, device_kind=devs[0].device_kind,
                   device_count=len(devs),
                   pallas="compiled" if platform == "tpu" else "interpret")


_ONE_PROCESS = (
    "one process per chip: `pio deploy`, `pio train --follow` and `pio "
    "train` each need their own. Stop the other process, give this one "
    "another chip, or set JAX_PLATFORMS=cpu to run on the CPU on purpose")


def resolve_backend() -> Backend:
    """Start-up backend resolution for the CLI verbs that compute.

    A chip belongs to one process.  What a second process gets, measured
    on a v5e host with jax 0.9.0 / libtpu 0.0.34 while ``pio deploy``
    held the chip: jax finds the TPU on the PCI bus, pins
    ``jax_platforms`` to ``tpu,cpu`` itself, and raises within seconds
    ("Unable to initialize backend 'tpu': ABORTED: ... libtpu
    multi-process lockfile") — an error, but one that names a lock file
    instead of the cause, and whose own advice (``JAX_PLATFORMS=''``)
    turns it into a silent CPU fallback.  Both ends are closed here: the
    first is re-raised naming the cause, the second is refused.
    """
    cache = configure_compile_cache()
    _COMPILE_CLOCK.install()
    try:
        backend = describe_backend()
    except RuntimeError as e:
        if "Unable to initialize backend" not in str(e):
            raise
        raise BackendError(
            f"jax could not take the accelerator ({e}). If another process "
            f"on this host is using the TPU, that is the cause — "
            f"{_ONE_PROCESS}.") from e
    if backend.platform == "cpu" and not os.environ.get("JAX_PLATFORMS"):
        chips = attached_tpu_chips()
        if chips:
            raise BackendError(
                f"{chips} TPU chip(s) are attached to this host but jax "
                f"fell back to the CPU: another process most likely holds "
                f"the chip — {_ONE_PROCESS}.")
    logger.info("backend: platform=%s device_kind=%s devices=%d pallas=%s "
                "compile_cache=%s", backend.platform, backend.device_kind,
                backend.device_count, backend.pallas, cache)
    return backend
