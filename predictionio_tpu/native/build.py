"""Compile-on-first-use loader for the C++ components.

Keeps the build chain dependency-free: one ``g++ -O2 -shared`` invocation
per translation unit.  The output is named by a hash of the source it was
built from and of the compile command, so the only library that can load
is one built from the tracked ``.cc`` as it stands — a stale or foreign
``lib*.so`` lying in ``native/`` is never opened by name.  (The
reference's equivalent is sbt/assembly — SURVEY.md §2.1 build glue.)
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path
from typing import Dict, Optional

logger = logging.getLogger(__name__)

__all__ = ["load_library", "native_available", "NATIVE_DIR"]

NATIVE_DIR = Path(__file__).resolve().parents[2] / "native"
_CXX = ["g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-pthread"]
_cache: Dict[str, ctypes.CDLL] = {}
_lock = threading.Lock()


def _library_path(src: Path) -> Path:
    h = hashlib.sha256(" ".join(_CXX).encode())
    h.update(b"\0")
    h.update(src.read_bytes())
    return NATIVE_DIR / f"lib{src.stem}-{h.hexdigest()[:16]}.so"


def _build(src: Path, out: Path) -> bool:
    # Build beside the target and rename into place: a concurrent process
    # never dlopens a half-written file.
    tmp = out.with_name(f".{out.name}.{os.getpid()}.tmp")
    try:
        subprocess.run(_CXX + [str(src), "-o", str(tmp)], check=True,
                       capture_output=True, timeout=300)
        os.replace(tmp, out)
        return True
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError) as e:
        err = getattr(e, "stderr", b"") or b""
        logger.warning("native build failed for %s: %s", src.name,
                       err.decode(errors="replace")[:2000])
        tmp.unlink(missing_ok=True)
        return False


def load_library(name: str) -> Optional[ctypes.CDLL]:
    """Load ``native/<name>.cc`` as a shared library, building it unless
    a library with this exact source + command hash is already there."""
    with _lock:
        if name in _cache:
            return _cache[name]
        src = NATIVE_DIR / f"{name}.cc"
        if not src.exists():
            logger.warning("native source %s missing", src)
            return None
        out = _library_path(src)
        if not out.exists():
            if not _build(src, out):
                return None
            for old in NATIVE_DIR.glob(f"lib{name}-*.so"):
                if old != out:  # builds of earlier revisions of the source
                    old.unlink(missing_ok=True)
        try:
            lib = ctypes.CDLL(str(out))
        except OSError as e:
            logger.warning("cannot dlopen %s: %s", out, e)
            return None
        _cache[name] = lib
        return lib


def native_available(name: str) -> bool:
    return load_library(name) is not None
