"""The host's own ledger (ISSUE 40, ``obs/host.py``): threads by role
from ``schedstat``, the cycle collector's pauses, the cgroup's
throttling, all published when the registry renders.

The kernel's files are a fake tree under ``tmp_path`` and the clocks are
dials, so every equality is exact; the few tests on the real ``/proc``
compare two of the kernel's own accounts with each other, never with the
wall.
"""

import gc
import importlib
import os
import sys
import threading
import urllib.request
from http.server import BaseHTTPRequestHandler
from pathlib import Path

import pytest

from predictionio_tpu.obs import (
    get_registry,
    reset_observability,
    start_runtime_introspection,
)
from predictionio_tpu.obs.host import HostLedger, get_host_ledger
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.obs.runtime import DeviceMemorySampler
from predictionio_tpu.server.http import ThreadingHTTPServer

host_mod = importlib.import_module("predictionio_tpu.obs.host")
trace_mod = importlib.import_module("predictionio_tpu.obs.trace")
runtime_mod = importlib.import_module("predictionio_tpu.obs.runtime")

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "tools"))

import lint_metrics  # noqa: E402

MAIN = os.getpid()


@pytest.fixture(autouse=True)
def fresh_registry(monkeypatch):
    # The twins below are observed whatever this host's thread clock is.
    monkeypatch.setattr(trace_mod, "_thread_clock_fine", True)
    reset_observability()
    yield
    reset_observability()


class FakeHost:
    """A ``/proc`` and a cgroup mount under ``tmp_path``, a ledger that
    reads them on two dials, and a registry of its own."""

    def __init__(self, root):
        self.proc, self.cgroup = root / "proc", root / "cgroup"
        (self.proc / "self" / "task").mkdir(parents=True)
        self.cgroup.mkdir()
        self.wall, self.cpu = 1000.0, 5.0
        self.ledger = HostLedger(str(self.proc), str(self.cgroup),
                                 clock=lambda: self.wall,
                                 cpu_clock=lambda: self.cpu)
        self.registry = MetricsRegistry()
        self.registry.add_collector(self.ledger.collect)

    def task(self, tid, run_ns, wait_ns):
        d = self.proc / "self" / "task" / str(tid)
        d.mkdir(exist_ok=True)
        (d / "schedstat").write_text(f"{run_ns} {wait_ns} 17\n")

    def end(self, tid):
        d = self.proc / "self" / "task" / str(tid)
        (d / "schedstat").unlink()
        d.rmdir()

    def register(self, monkeypatch, tid, role):
        monkeypatch.setattr(host_mod.threading, "get_native_id",
                            lambda: tid)
        self.ledger.register_thread(role)

    def write(self, relative, text):
        path = self.proc / relative if relative.startswith(
            ("self/", "pressure/")) else self.cgroup / relative
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)

    def series(self):
        out = {}
        for line in self.registry.render().splitlines():
            if line and not line.startswith("#"):
                key, _, value = line.rpartition(" ")
                out[key] = float(value)
        return out


@pytest.fixture()
def fake(tmp_path):
    return FakeHost(tmp_path)


def run_s(role):
    return f'pio_host_thread_run_seconds_total{{role="{role}"}}'


def wait_s(role):
    return f'pio_host_thread_runq_wait_seconds_total{{role="{role}"}}'


def threads(role):
    return f'pio_host_threads{{role="{role}"}}'


# -- threads by role --------------------------------------------------------


def test_the_collector_adds_each_tasks_growth_to_its_role(fake, monkeypatch):
    fake.task(MAIN, 2_000_000_000, 10_000_000)
    fake.task(501, 500_000_000, 40_000_000)
    fake.task(502, 250_000_000, 0)
    fake.task(601, 125_000_000, 5_000_000)
    fake.task(777, 3_000_000_000, 1_000_000_000)   # nobody claimed it
    fake.register(monkeypatch, 501, "handler")
    fake.register(monkeypatch, 502, "handler")
    fake.register(monkeypatch, 601, "batcher")
    first = fake.series()
    assert first[run_s("main")] == pytest.approx(2.0)
    assert first[run_s("handler")] == pytest.approx(0.75)
    assert first[run_s("batcher")] == pytest.approx(0.125)
    assert first[run_s("runtime")] == pytest.approx(3.0)
    assert first[wait_s("handler")] == pytest.approx(0.04)
    assert first[wait_s("runtime")] == pytest.approx(1.0)
    assert [first[threads(r)] for r in
            ("main", "handler", "batcher", "runtime")] == [1, 2, 1, 1]
    # Only growth is added at the next render.
    fake.task(501, 700_000_000, 40_000_000)
    fake.task(601, 125_000_000, 9_000_000)
    second = fake.series()
    assert second[run_s("handler")] == pytest.approx(0.95)
    assert second[wait_s("batcher")] == pytest.approx(0.009)
    assert second[run_s("runtime")] == pytest.approx(3.0)


def test_a_thread_that_ended_keeps_what_it_ran(fake, monkeypatch):
    fake.task(MAIN, 1_000_000, 0)
    fake.task(501, 400_000_000, 2_000_000)
    fake.register(monkeypatch, 501, "handler")
    fake.series()
    fake.end(501)
    after = fake.series()
    assert after[run_s("handler")] == pytest.approx(0.4)
    assert after[wait_s("handler")] == pytest.approx(0.002)
    assert after[threads("handler")] == 0
    # ... and its id went with it: the kernel hands it to a pool thread.
    fake.task(501, 30_000_000, 0)
    later = fake.series()
    assert later[run_s("handler")] == pytest.approx(0.4)
    assert later[run_s("runtime")] == pytest.approx(0.03)


def test_a_reused_id_starts_from_zero(fake, monkeypatch):
    fake.task(MAIN, 1_000_000, 0)
    fake.task(601, 900_000_000, 50_000_000)
    fake.register(monkeypatch, 601, "batcher")
    fake.series()
    # Between two renders the batcher ended and a new one got its id:
    # the reading went DOWN, so all of it is the new thread's.
    fake.task(601, 100_000_000, 1_000_000)
    fake.register(monkeypatch, 601, "batcher")
    after = fake.series()
    assert after[run_s("batcher")] == pytest.approx(1.0)
    assert after[wait_s("batcher")] == pytest.approx(0.051)
    assert after[threads("batcher")] == 1


def test_a_retiring_thread_books_its_last_reading_and_frees_its_id(
        fake, monkeypatch):
    fake.task(MAIN, 1_000_000, 0)
    fake.task(501, 10_000_000, 0)
    fake.register(monkeypatch, 501, "handler")
    fake.series()
    fake.task(501, 60_000_000, 3_000_000)     # ran on, then closes
    fake.ledger.retire_thread()
    fake.end(501)
    assert 501 not in fake.ledger._roles and 501 not in fake.ledger._last
    after = fake.series()
    assert after[run_s("handler")] == pytest.approx(0.06)
    assert after[wait_s("handler")] == pytest.approx(0.003)
    # A thread nobody registered retires without a trace.
    monkeypatch.setattr(host_mod.threading, "get_native_id", lambda: 999)
    fake.ledger.retire_thread()
    assert fake.series()[run_s("handler")] == pytest.approx(0.06)


def test_two_renders_are_monotonic_and_the_clocks_are_the_renders(fake,
                                                                 monkeypatch):
    fake.task(MAIN, 5_000_000, 1_000)
    fake.task(601, 1_000_000, 0)
    fake.register(monkeypatch, 601, "batcher")
    fake.write("cpu.stat", "usage_usec 9\nnr_periods 4\nnr_throttled 1\n"
                           "throttled_usec 20000\n")
    fake.write("pressure/cpu", "some avg10=0.00 avg60=0.00 avg300=0.00 "
                               "total=1500000\nfull avg10=0.00 total=0\n")
    first = fake.series()
    assert first["pio_host_clock_seconds_total"] == 1000.0
    assert first["pio_host_process_cpu_seconds_total"] == 5.0
    fake.wall += 30.0
    fake.cpu += 12.5
    fake.task(MAIN, 6_000_000, 2_000)
    fake.write("cpu.stat", "nr_throttled 3\nthrottled_usec 170000\n")
    second = fake.series()
    assert second["pio_host_clock_seconds_total"] == 1030.0
    assert second["pio_host_process_cpu_seconds_total"] == 17.5
    for key, value in first.items():
        if key.startswith(("pio_host_", "pio_gc_")) \
                and not key.startswith("pio_host_threads"):
            assert second[key] >= value, key
    assert second["pio_host_cpu_throttled_seconds_total"] \
        == pytest.approx(0.17)
    assert second["pio_host_cpu_throttled_periods_total"] == 3
    assert second["pio_host_cpu_pressure_seconds_total"] \
        == pytest.approx(1.5)


def test_a_reset_registry_keeps_the_collector_and_starts_from_zero(
        fake, monkeypatch):
    fake.task(MAIN, 5_000_000_000, 0)
    fake.series()
    fake.registry.add_collector(fake.ledger.collect)    # twice is once
    assert len(fake.registry._collectors) == 1
    fake.registry.reset()
    fake.task(MAIN, 5_250_000_000, 0)
    fake.wall += 2.0
    after = fake.series()
    assert after[run_s("main")] == pytest.approx(0.25)
    assert after["pio_host_clock_seconds_total"] == pytest.approx(2.0)


def test_a_failing_collector_does_not_fail_the_scrape(fake):
    def broken(registry):
        raise OSError("no /proc today")

    fake.registry.add_collector(broken)
    fake.task(MAIN, 1_000_000_000, 0)
    assert fake.series()[run_s("main")] == pytest.approx(1.0)


# -- what a host may lack ---------------------------------------------------


def test_no_schedstat_no_thread_series(fake, monkeypatch):
    (fake.proc / "self" / "task" / str(MAIN)).mkdir()   # a task, no file
    fake.register(monkeypatch, 601, "batcher")
    got = fake.series()
    assert not [k for k in got if k.startswith("pio_host_thread")]
    assert "pio_host_clock_seconds_total" in got
    # No task directory at all (not Linux) reads the same.
    bare = HostLedger(str(fake.proc / "nowhere"), str(fake.cgroup))
    registry = MetricsRegistry()
    bare.collect(registry)
    assert registry.get("pio_host_thread_run_seconds_total") is None


def test_without_schedstat_the_tasks_stat_gives_run_time_and_no_wait(
        fake, monkeypatch):
    """A sandboxed kernel: no ``schedstat``, utime and stime in ticks."""
    def stat(tid, utime, stime):
        d = fake.proc / "self" / "task" / str(tid)
        d.mkdir(exist_ok=True)
        (d / "stat").write_text(
            f"{tid} (pio batcher (x) S 1 1 1 0 -1 4194304 9 0 0 0 "
            f"{utime} {stime} 0 0 20 0 140 0 12345 1 1\n")

    tick_s = 1.0 / os.sysconf("SC_CLK_TCK")
    stat(MAIN, 150, 50)
    stat(601, 30, 10)
    stat(777, 500, 0)
    fake.register(monkeypatch, 601, "batcher")
    first = fake.series()
    assert first[run_s("main")] == pytest.approx(200 * tick_s)
    assert first[run_s("batcher")] == pytest.approx(40 * tick_s)
    assert first[run_s("runtime")] == pytest.approx(500 * tick_s)
    assert first[threads("batcher")] == 1
    stat(601, 35, 15)
    second = fake.series()
    assert second[run_s("batcher")] == pytest.approx(50 * tick_s)
    # What was not measured is not published as zero.
    assert not [k for k in second if "runq_wait" in k]


def test_no_cgroup_file_no_throttling_series(fake):
    fake.task(MAIN, 1, 0)
    got = fake.series()
    assert not [k for k in got if "throttled" in k or "pressure" in k]
    # The root group of a v2 tree has a cpu.stat that counts no
    # throttling: no series either.
    fake.write("cpu.stat", "usage_usec 5\nuser_usec 3\nsystem_usec 2\n")
    assert not [k for k in fake.series() if "throttled" in k]


@pytest.mark.parametrize("cgroup_line, relative, text, seconds", [
    ("0::/", "cpu.stat",
     "nr_periods 9\nnr_throttled 2\nthrottled_usec 250000\n", 0.25),
    ("0::/kubepods/pod1", "kubepods/pod1/cpu.stat",
     "nr_throttled 2\nthrottled_usec 1000000\n", 1.0),
    ("3:cpu,cpuacct:/", "cpu/cpu.stat",
     "nr_periods 9\nnr_throttled 2\nthrottled_time 750000000\n", 0.75),
    ("3:cpu,cpuacct:/jobs/a", "cpu,cpuacct/jobs/a/cpu.stat",
     "nr_throttled 2\nthrottled_time 500000000\n", 0.5),
])
def test_throttling_is_read_from_either_cgroup_version(
        fake, cgroup_line, relative, text, seconds):
    fake.task(MAIN, 1, 0)
    fake.write("self/cgroup", f"7:memory:/x\n{cgroup_line}\n")
    fake.write(relative, text)
    got = fake.series()
    assert got["pio_host_cpu_throttled_seconds_total"] \
        == pytest.approx(seconds)
    assert got["pio_host_cpu_throttled_periods_total"] == 2


# -- the cycle collector ----------------------------------------------------


def _gc_entries():
    return [c for c in gc.callbacks
            if getattr(c, "__self__", None) is get_host_ledger()]


def test_reset_observability_leaves_one_gc_callback_never_two():
    start_runtime_introspection(sample=False)
    start_runtime_introspection(sample=False)
    assert len(_gc_entries()) == 1
    reset_observability()
    start_runtime_introspection(sample=False)
    assert len(_gc_entries()) == 1
    assert get_registry()._collectors.count(get_host_ledger().collect) == 1


def _rendered(name):
    for line in get_registry().render().splitlines():
        if line.startswith(name + " "):
            return float(line.rpartition(" ")[2])
    return 0.0


def test_a_forced_full_pass_advances_both_series_by_one():
    start_runtime_introspection(sample=False)
    gc.collect()            # whatever the heap owed is paid here
    passes = 'pio_gc_collections_total{generation="2"}'
    before = (_rendered(passes), _rendered("pio_gc_full_pause_ms_count"),
              _rendered('pio_gc_pause_ms_total{generation="2"}'))
    was_enabled = gc.isenabled()
    gc.disable()            # no automatic pass between the two renders
    try:
        gc.collect()
        after = (_rendered(passes),
                 _rendered("pio_gc_full_pause_ms_count"),
                 _rendered('pio_gc_pause_ms_total{generation="2"}'))
    finally:
        if was_enabled:
            gc.enable()
    assert after[0] - before[0] == 1
    assert after[1] - before[1] == 1
    assert after[2] > before[2]


def test_a_full_pass_is_held_open_as_an_annotation(monkeypatch):
    events = []

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("open", self.name))
            return self

        def __exit__(self, *exc):
            events.append(("close", self.name))
            return False

    monkeypatch.setattr(trace_mod, "_TraceAnnotation", Recording)
    ticks = iter(range(100))
    ledger = HostLedger(clock=lambda: next(ticks) * 0.004)
    ledger._on_gc("start", {"generation": 0})
    ledger._on_gc("stop", {"generation": 0, "collected": 0})
    assert events == []
    ledger._on_gc("start", {"generation": 2})
    ledger._on_gc("stop", {"generation": 2, "collected": 5})
    assert events == [("open", "pio:gc.full"), ("close", "pio:gc.full")]
    assert ledger._gc[0] == [1, pytest.approx(4.0)]
    assert ledger._gc[2] == [1, pytest.approx(4.0)]
    assert ledger._gc_full == [pytest.approx(4.0)]


# -- the real /proc ---------------------------------------------------------


def test_on_the_real_proc_the_roles_add_up_to_the_process():
    """Two accounts of one thing by the same kernel: the tasks' run time
    summed by role, and the process's CPU clock."""
    ledger = HostLedger()
    if ledger._task_times(MAIN) is None:
        pytest.skip("this /proc counts no task's CPU time")
    registry = MetricsRegistry()
    registry.add_collector(ledger.collect)

    def reading():
        out = {}
        for line in registry.render().splitlines():
            if line.startswith(("pio_host_thread_run_seconds_total",
                                "pio_host_process_cpu_seconds_total")):
                key, _, value = line.rpartition(" ")
                out[key] = float(value)
        process = out.pop("pio_host_process_cpu_seconds_total")
        return sum(out.values()), process

    before = reading()
    n = 0
    while reading()[1] - before[1] < 0.2:      # 200 ms of CPU, no clock
        n += sum(range(20000))
    after = reading()
    by_role, process = after[0] - before[0], after[1] - before[1]
    assert by_role == pytest.approx(process, rel=0.05, abs=0.005)


def test_a_connections_thread_is_a_handler_until_it_closes():
    seen = []

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            seen.append(get_host_ledger()._roles.get(
                threading.get_native_id()))
            self.send_response(204)
            self.end_headers()

        def log_message(self, *args):
            pass

    server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    serving = threading.Thread(target=server.serve_forever, daemon=True)
    serving.start()
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/",
                                    timeout=10) as response:
            assert response.status == 204
    finally:
        server.shutdown()
        server.server_close()       # joins the connection's thread
        serving.join(timeout=10)
    assert not serving.is_alive()
    assert seen == ["handler"]
    assert "handler" not in get_host_ledger()._roles.values()


# -- the sampler's tick -----------------------------------------------------


def test_a_failing_sample_still_closes_the_ticks_span(monkeypatch):
    closed = []

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, exc_type, exc, tb):
            closed.append((self.name, exc_type))
            return False

    monkeypatch.setattr(trace_mod, "_TraceAnnotation", Recording)
    sampler = DeviceMemorySampler(interval_s=0.0)
    ticked = []

    def wait(timeout):          # the stop event: two ticks, then stop
        ticked.append(timeout)
        return len(ticked) > 2

    def sample_once():
        raise RuntimeError("the runtime went away")

    roles = []
    monkeypatch.setattr(runtime_mod, "register_thread", roles.append)
    monkeypatch.setattr(sampler._stop, "wait", wait)
    monkeypatch.setattr(sampler, "sample_once", sample_once)
    sampler._run()              # returns: the failure did not kill it
    assert roles == ["sampler"]
    assert closed == [("pio:mem_sampler.sample", RuntimeError)] * 2
    reg = get_registry()
    assert reg.get("pio_mem_sampler_ms").count() == 2
    assert reg.get("pio_mem_sampler_cpu_ms").count() == 2
    assert reg.get("pio_mem_sampler_cpu_ms").sum() \
        <= reg.get("pio_mem_sampler_ms").sum()


def test_a_devices_fn_that_raises_is_swallowed_inside_the_span(monkeypatch):
    def no_devices():
        raise RuntimeError("no backend")

    sampler = DeviceMemorySampler(interval_s=0.0, devices_fn=no_devices)
    with sampler._tick():
        assert sampler.sample_once() == {}
    assert get_registry().get("pio_mem_sampler_ms").count() == 1


# -- the names --------------------------------------------------------------


NEW_SERIES = {
    "pio_dispatch_stage_cpu_ms": "histogram",
    "pio_batcher_thread_cpu_ms": "histogram",
    "pio_train_phase_cpu_ms": "histogram",
    "pio_mem_sampler_ms": "histogram",
    "pio_mem_sampler_cpu_ms": "histogram",
    "pio_gc_full_pause_ms": "histogram",
    "pio_gc_collections_total": "counter",
    "pio_gc_pause_ms_total": "counter",
    "pio_host_thread_run_seconds_total": "counter",
    "pio_host_thread_runq_wait_seconds_total": "counter",
    "pio_host_threads": "gauge",
    "pio_host_clock_seconds_total": "counter",
    "pio_host_process_cpu_seconds_total": "counter",
    "pio_host_cpu_throttled_seconds_total": "counter",
    "pio_host_cpu_throttled_periods_total": "counter",
    "pio_host_cpu_pressure_seconds_total": "counter",
}


@pytest.fixture(scope="module")
def linted():
    registered = {}
    violations = []
    for path in sorted((REPO / "predictionio_tpu").rglob("*.py")):
        violations.extend(lint_metrics.check_source(
            path.read_text(encoding="utf-8"), str(path), registered))
    return violations, registered


@pytest.mark.parametrize("name", sorted(NEW_SERIES))
def test_the_metrics_lint_passes_on_the_new_names(linted, name):
    violations, registered = linted
    assert violations == []
    assert registered[name]["kind"] == NEW_SERIES[name]
    twin = name.replace("_cpu_ms", "_ms")
    if twin != name:
        assert registered[twin]["labels"] == registered[name]["labels"]


def test_the_module_names_no_setting():
    text = (REPO / "predictionio_tpu" / "obs" / "host.py").read_text()
    assert "PIO_" not in text and "environ" not in text
