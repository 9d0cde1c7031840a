"""The host's own ledger: which threads ran, which waited for a CPU, and
the stalls the process suffers as a whole.

``obs.trace.span`` books a stage's wall and, since this module, the CPU
its thread ran of that wall; the difference is time off the CPU.  For a
stage that blocks on nothing by design that is time the thread wanted to
run and did not, and it has two causes this module tells apart:

- **the OS** took the CPU away (descheduling, a cgroup's CFS quota): the
  thread sits RUNNABLE on a run queue, and the kernel counts that per
  task in ``/proc/self/task/<tid>/schedstat`` (``run_ns wait_ns
  slices``; a sandboxed kernel that keeps none still counts utime and
  stime in the task's ``stat``, so run time is known there and the wait
  is not);
- **the GIL**: a thread waiting for the interpreter sleeps on a
  condition variable and is NOT runnable, so it counts as neither run
  nor run-queue wait.  Off-CPU time with no run-queue wait beside it is
  the GIL's (or a wait the stage asked for).

Nothing here runs on a timer and nothing has a switch.  The registry
calls :meth:`HostLedger.collect` when it renders (``GET /metrics``, a
harness's snapshot), which reads ``/proc`` and the cgroup files then and
only then; between renders the ledger costs a dictionary entry a
registered thread and two clock readings a cycle collection.

Threads count by ROLE: ``batcher``, ``handler`` and ``sampler`` threads
say so themselves (:func:`register_thread`), the process's first thread
is ``main``, and every other task of the process is ``runtime`` (XLA's
and the TPU runtime's pools, the profiler).  Series (all absent where
the kernel file behind them is):

- ``pio_host_thread_run_seconds_total{role}``,
  ``pio_host_thread_runq_wait_seconds_total{role}``,
  ``pio_host_threads{role}``;
- ``pio_host_clock_seconds_total`` (``perf_counter`` at render: the
  denominator that turns two renders into a rate) and
  ``pio_host_process_cpu_seconds_total`` (``process_time`` at render:
  what the roles' run seconds must add up to);
- ``pio_gc_collections_total{generation}``,
  ``pio_gc_pause_ms_total{generation}``, ``pio_gc_full_pause_ms`` and a
  ``pio:gc.full`` annotation around every full pass;
- ``pio_host_cpu_throttled_seconds_total``,
  ``pio_host_cpu_throttled_periods_total`` (the cgroup's ``cpu.stat``),
  ``pio_host_cpu_pressure_seconds_total`` (``/proc/pressure/cpu``).

stdlib-only on import, like the rest of ``obs``.
"""

from __future__ import annotations

import gc
import os
import re
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from predictionio_tpu.obs.metrics import MetricsRegistry, get_registry
from predictionio_tpu.obs.trace import _open_annotation

__all__ = [
    "HostLedger",
    "get_host_ledger",
    "register_thread",
    "retire_thread",
]

# A full pass of the cycle collector: a millisecond on a settled heap,
# hundreds over a few hundred thousand tracked objects.
GC_FULL_PAUSE_BUCKETS_MS = (1, 2.5, 5, 10, 25, 50, 100, 250, 500)

# PSI: "some avg10=0.00 avg60=0.00 avg300=0.00 total=<microseconds>".
_PSI_SOME_TOTAL = re.compile(r"^some .*\btotal=(\d+)", re.MULTILINE)

# A task's ``stat`` counts utime and stime in clock ticks.
_TICK_NS = 10 ** 9 // os.sysconf("SC_CLK_TCK")


def _read(path: str) -> Optional[str]:
    try:
        with open(path, encoding="ascii") as f:
            return f.read()
    except (OSError, ValueError):
        return None


def _fields(text: str) -> Dict[str, float]:
    """``key value`` lines (a cgroup's ``cpu.stat``) as a dict."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        key, _, value = line.partition(" ")
        try:
            out[key] = float(value)
        except ValueError:
            continue
    return out


class HostLedger:
    """Threads by role, the cycle collector's pauses and the cgroup's
    throttling, published into a registry when it renders.

    ``proc_root`` / ``cgroup_root`` / the clocks are injectable, so the
    tests read a fake tree on a dial.
    """

    def __init__(self, proc_root: str = "/proc",
                 cgroup_root: str = "/sys/fs/cgroup",
                 clock: Callable[[], float] = time.perf_counter,
                 cpu_clock: Callable[[], float] = time.process_time):
        self._proc = proc_root
        self._cgroup = cgroup_root
        self._clock = clock
        self._cpu_clock = cpu_clock
        self._lock = threading.Lock()
        self._roles: Dict[int, str] = {}              # native tid -> role
        self._last: Dict[int, Tuple[int, int]] = {}   # tid -> (run, wait) ns
        # role -> [run ns, run-queue wait ns] of every thread it ever had
        self._by_role: Dict[str, List[int]] = {}
        # series -> the total it was last advanced to
        self._published: Dict[tuple, float] = {}
        self._runq_known = False    # some task's schedstat has been read
        # The collector's callback runs with the GIL held on whichever
        # thread collects, one collection at a time: plain lists, no
        # lock, no registry call (a histogram's lock may be held by the
        # very allocation that started the collection).
        self._gc = [[0, 0.0], [0, 0.0], [0, 0.0]]     # [passes, ms] a gen
        self._gc_full: List[float] = []               # not yet observed
        self._gc_t0 = 0.0
        self._gc_ann = None

    # -- threads ------------------------------------------------------------

    def register_thread(self, role: str) -> None:
        """The calling thread is one of ``role``'s, from now on."""
        tid = threading.get_native_id()
        with self._lock:
            self._roles[tid] = role

    def retire_thread(self) -> None:
        """The calling thread is about to end: book what it ran since the
        last render and forget its id.  For threads that come and go by
        the thousand (a handler a connection); a long-lived thread that
        ends without it is dropped at the next render with what was last
        read."""
        tid = threading.get_native_id()
        with self._lock:
            if tid in self._roles:
                reading = self._task_times(tid)
                if reading is not None:
                    self._book(tid, self._roles[tid], reading)
                del self._roles[tid]
            self._last.pop(tid, None)

    def _task_times(self, tid: int) -> Optional[Tuple[int, Optional[int]]]:
        """(run ns, run-queue wait ns) of one task from its ``schedstat``;
        where the kernel keeps none (a sandboxed kernel), the task's
        ``stat`` still has utime + stime in clock ticks, and the wait
        is not known (None)."""
        task = f"{self._proc}/self/task/{tid}"
        text = _read(f"{task}/schedstat")
        try:
            if text is not None:
                run, wait = text.split()[:2]
                return int(run), int(wait)
            # "pid (comm) state ...": utime and stime are fields 14, 15.
            fields = _read(f"{task}/stat").rsplit(")", 1)[1].split()
            return (int(fields[11]) + int(fields[12])) * _TICK_NS, None
        except (AttributeError, IndexError, ValueError):
            return None

    def _book(self, tid: int, role: str,
              reading: Tuple[int, Optional[int]]) -> None:
        run, wait = reading
        if wait is None:
            wait = 0
        else:
            self._runq_known = True
        last = self._last.get(tid)
        if last is None or run < last[0] or wait < last[1]:
            last = (0, 0)       # a new thread, or the id of a dead one
        total = self._by_role.setdefault(role, [0, 0])
        total[0] += run - last[0]
        total[1] += wait - last[1]
        self._last[tid] = (run, wait)

    def _read_threads(self) -> Optional[Dict[str, int]]:
        """Book every task's growth to its role; live threads by role, or
        None where ``/proc`` has neither file of :meth:`_task_times`."""
        try:
            names = os.listdir(f"{self._proc}/self/task")
        except OSError:
            return None
        main = os.getpid()
        live: Dict[str, int] = {}
        seen = set()
        for name in names:
            try:
                tid = int(name)
            except ValueError:
                continue
            reading = self._task_times(tid)
            if reading is None:
                continue        # ended since the listing
            seen.add(tid)
            role = self._roles.get(tid) or (
                "main" if tid == main else "runtime")
            self._book(tid, role, reading)
            live[role] = live.get(role, 0) + 1
        if not seen:
            return None
        # Threads that ended keep what they ran (it is in their role's
        # total) and lose their id, which the kernel hands out again.
        for table in (self._last, self._roles):
            for tid in [t for t in table if t not in seen]:
                del table[tid]
        return live

    # -- the cycle collector ------------------------------------------------

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            if info["generation"] == 2:
                self._gc_ann = _open_annotation("gc.full")
            self._gc_t0 = self._clock()
            return
        ms = (self._clock() - self._gc_t0) * 1e3
        generation = info["generation"]
        row = self._gc[generation]
        row[0] += 1
        row[1] += ms
        if generation == 2:
            ann, self._gc_ann = self._gc_ann, None
            if ann is not None:
                ann.__exit__(None, None, None)
            self._gc_full.append(ms)

    def install(self) -> None:
        """Idempotent: one ``gc.callbacks`` entry, one collector on the
        process's registry."""
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        get_registry().add_collector(self.collect)

    # -- the cgroup ---------------------------------------------------------

    def _cpu_stat_paths(self) -> Iterator[str]:
        """Where this process's ``cpu.stat`` can be: cgroup v2 (the
        unified tree), then v1's ``cpu`` controller, each under the
        path ``/proc/self/cgroup`` gives and at the mount's root (a
        container sees its own group there)."""
        v2, v1 = "", ""
        for line in (_read(f"{self._proc}/self/cgroup") or "").splitlines():
            _, _, rest = line.partition(":")
            controllers, _, path = rest.partition(":")
            if not controllers:
                v2 = path.rstrip("/")
            elif "cpu" in controllers.split(","):
                v1 = path.rstrip("/")
        for sub in (v2, ""):
            yield f"{self._cgroup}{sub}/cpu.stat"
        for mount in ("cpu", "cpu,cpuacct"):
            for sub in (v1, ""):
                yield f"{self._cgroup}/{mount}{sub}/cpu.stat"

    def _throttled(self) -> Optional[Tuple[float, float]]:
        """(seconds throttled, periods throttled) of this process's
        cgroup; None where no ``cpu.stat`` counts them."""
        for path in self._cpu_stat_paths():
            stat = _fields(_read(path) or "")
            if "nr_throttled" not in stat:
                continue
            if "throttled_usec" in stat:                    # v2
                return stat["throttled_usec"] / 1e6, stat["nr_throttled"]
            if "throttled_time" in stat:                    # v1, ns
                return stat["throttled_time"] / 1e9, stat["nr_throttled"]
        return None

    def _pressure(self) -> Optional[float]:
        """Seconds some task was stalled for a CPU (PSI ``some total``)."""
        found = _PSI_SOME_TOTAL.search(
            _read(f"{self._proc}/pressure/cpu") or "")
        return float(found.group(1)) / 1e6 if found else None

    # -- publication --------------------------------------------------------

    def _advance(self, counter, total: float, **labels) -> None:
        """Bring a counter series up by what its source grew since it was
        last published: a registry that was reset starts it from zero,
        a source that went down (another cgroup) re-bases it."""
        key = (counter.name, tuple(sorted(labels.items())))
        grown = total - self._published.get(key, 0.0)
        self._published[key] = total
        counter.inc(max(grown, 0.0), **labels)

    def collect(self, registry: MetricsRegistry) -> None:
        """Read everything once and publish it; called by the registry
        before it renders."""
        with self._lock:
            self._collect(registry)

    def _collect(self, reg: MetricsRegistry) -> None:
        self._advance(reg.counter(
            "pio_host_clock_seconds_total",
            "time.perf_counter() when the registry last rendered: the "
            "seconds between two renders, for rates of the series "
            "beside it."), self._clock())
        self._advance(reg.counter(
            "pio_host_process_cpu_seconds_total",
            "CPU time of the whole process (time.process_time()) when "
            "the registry last rendered."), self._cpu_clock())

        live = self._read_threads()
        if live is not None:
            run = reg.counter(
                "pio_host_thread_run_seconds_total",
                "CPU time the threads of a role ran (schedstat, or the "
                "tasks' stat in clock ticks), threads that have ended "
                "included.", ("role",))
            threads = reg.gauge(
                "pio_host_threads", "Live threads by role.", ("role",))
            for role, (run_ns, wait_ns) in self._by_role.items():
                self._advance(run, run_ns / 1e9, role=role)
                threads.set(live.get(role, 0), role=role)
                if self._runq_known:
                    self._advance(reg.counter(
                        "pio_host_thread_runq_wait_seconds_total",
                        "Time the threads of a role were runnable and not "
                        "on a CPU (schedstat): the OS's doing, never the "
                        "GIL's.", ("role",)), wait_ns / 1e9, role=role)

        passes = reg.counter(
            "pio_gc_collections_total",
            "Passes of the cycle collector, by generation.",
            ("generation",))
        pause = reg.counter(
            "pio_gc_pause_ms_total",
            "Time inside the cycle collector (every thread stands "
            "still), by generation.", ("generation",))
        for generation, (count, ms) in enumerate(self._gc):
            self._advance(passes, count, generation=str(generation))
            self._advance(pause, ms, generation=str(generation))
        full = reg.histogram(
            "pio_gc_full_pause_ms",
            "One full (generation 2) pass of the cycle collector.",
            buckets=GC_FULL_PAUSE_BUCKETS_MS)
        done, self._gc_full = self._gc_full, []
        for ms in done:
            full.observe(ms)

        throttled = self._throttled()
        if throttled is not None:
            self._advance(reg.counter(
                "pio_host_cpu_throttled_seconds_total",
                "Time this process's cgroup was held off the CPU by its "
                "quota (cpu.stat)."), throttled[0])
            self._advance(reg.counter(
                "pio_host_cpu_throttled_periods_total",
                "Quota periods in which this process's cgroup was "
                "throttled (cpu.stat nr_throttled)."), throttled[1])
        pressure = self._pressure()
        if pressure is not None:
            self._advance(reg.counter(
                "pio_host_cpu_pressure_seconds_total",
                "Time some task was stalled waiting for a CPU "
                "(/proc/pressure/cpu, some total)."), pressure)


_ledger = HostLedger()


def get_host_ledger() -> HostLedger:
    """THE process's host ledger."""
    return _ledger


def register_thread(role: str) -> None:
    """The calling thread's CPU time counts under ``role`` from now on
    (``batcher``, ``handler``, ``sampler``)."""
    _ledger.register_thread(role)


def retire_thread() -> None:
    _ledger.retire_thread()
