"""Test bootstrap: force an 8-device virtual CPU mesh before JAX initializes.

This is the rebuild's analogue of the reference's Spark ``local[n]`` test
substrate (SURVEY.md §4): real sharding/collective semantics, one process,
no accelerator.  Must run before any ``import jax`` resolves a backend.
"""

import os

# Set before the first ``import jax``: that is all it takes here.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Tests compile what they run: the CLI verbs place a persistent compile
# cache (backend.configure_compile_cache) and an in-process `pio train`
# would otherwise leave it on for the rest of the session.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402


@pytest.fixture()
def pio_home(tmp_path, monkeypatch):
    """Isolated PIO_HOME per test (fresh storage singleton both sides).

    Also resets the process-wide observability state (metrics registry +
    trace ring): servers share ONE registry by design, so without a reset
    each test would see the previous tests' counts.
    """
    from predictionio_tpu.data.storage import reset_storage
    from predictionio_tpu.obs import reset_observability

    home = tmp_path / "pio_home"
    home.mkdir()
    monkeypatch.setenv("PIO_HOME", str(home))
    for k in list(os.environ):
        if k.startswith("PIO_STORAGE_"):
            monkeypatch.delenv(k, raising=False)
    reset_storage()
    reset_observability()
    yield home
    reset_storage()
    reset_observability()
    # Pay the GC debt at the TEST boundary, deterministically: live-HTTP
    # tests (fleet, refresh, servers) churn whole server stacks + model
    # arrays, and an automatic collection landing mid-request in a LATER
    # timing-sensitive test (e.g. the 95%-trace-coverage pin) reads as a
    # phantom unattributed gap on this 1-core box.
    import gc

    gc.collect()


@pytest.fixture()
def kill9_after():
    """Start ``python -c source *argv``, read the integers it prints one a
    line until ``reached(n)``, then ``kill -9`` it while it still runs.
    Returns the last integer read.  The child has a time limit of its
    own (60 s), whatever the test around it does."""
    import signal
    import subprocess
    import sys
    import threading

    def run(source, argv, reached):
        child = subprocess.Popen(
            [sys.executable, "-c", source, *map(str, argv)],
            env={**os.environ, "PYTHONPATH": os.pathsep.join(sys.path)},
            stdout=subprocess.PIPE, text=True)
        limit = threading.Timer(60.0, child.kill)
        limit.start()
        try:
            seen = None
            for line in child.stdout:
                seen = int(line)
                if reached(seen):
                    break
            assert child.poll() is None, "the child ended before the kill"
            os.kill(child.pid, signal.SIGKILL)
            child.wait(timeout=30)
        finally:
            limit.cancel()
            child.kill()
            child.stdout.close()
        return seen

    return run
