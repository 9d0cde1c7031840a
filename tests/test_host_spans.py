"""The host spans where the program feeds the chip (ISSUE 25): the span
primitive's two trace-independent sinks, the batcher thread's ledger,
the inside of ``retrieval`` and the phases of ALS prep and train.

Everything that is an equality runs on an injected clock
(``obs.trace._now``), so no assertion depends on the scheduler.
"""

import glob
import importlib
import threading

import numpy as np
import pytest

from predictionio_tpu.obs import (
    current_span,
    dispatch_stage,
    get_registry,
    phase,
    reset_observability,
    span,
    trace,
)
from predictionio_tpu.serving.batcher import (
    IDLE_SLICE_S,
    THREAD_PHASES,
    MicroBatcher,
)
from predictionio_tpu.serving.queue import ModelQueue, Pending

# The module, not the ``trace`` function the package re-exports.
trace_mod = importlib.import_module("predictionio_tpu.obs.trace")


class Dial:
    """A clock that moves only when a test moves it."""

    def __init__(self, t=100.0):
        self.t = t

    def now(self):
        return self.t


class RecordingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps (name,
    start, end, exception type) of every annotation, on ``clock``."""

    events = []
    clock = None

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.start = self.clock() if self.clock else None
        return self

    def __exit__(self, exc_type, exc, tb):
        end = self.clock() if self.clock else None
        type(self).events.append((self.name, self.start, end, exc_type))
        return False


@pytest.fixture(autouse=True)
def fresh_registry():
    """Counts below are exact: start every test from an empty registry."""
    reset_observability()
    yield
    reset_observability()


@pytest.fixture()
def annotations(monkeypatch):
    RecordingAnnotation.events = []
    RecordingAnnotation.clock = None
    monkeypatch.setattr(trace_mod, "_TraceAnnotation", RecordingAnnotation)
    return RecordingAnnotation


def _stage_counts():
    hist = get_registry().get("pio_dispatch_stage_ms")
    stages = ("bind", "supplement", "lookup", "h2d", "launch", "wait",
              "assemble", "serve")
    return {s: hist.count(stage=s) for s in stages} if hist else {}


# -- the primitive ----------------------------------------------------------


def test_span_observes_its_histogram_with_no_trace_open(monkeypatch):
    dial = Dial()
    monkeypatch.setattr(trace_mod, "_now", dial.now)
    hist = get_registry().histogram("pio_test_span_ms", "t", ("part",))
    assert current_span() is None
    with span("work", hist=hist, labels={"part": "a"}) as s:
        dial.t += 0.25
    assert s.duration_ms == pytest.approx(250.0)
    assert hist.count(part="a") == 1
    assert hist.sum(part="a") == pytest.approx(250.0)
    # ... and joins the tree as before when one is open.
    with trace("root") as root:
        with span("work", hist=hist, labels={"part": "a"}):
            dial.t += 0.5
    assert [c.name for c in root.children] == ["work"]
    assert hist.sum(part="a") == pytest.approx(750.0)


class TwoDials:
    """The wall clock and the thread's CPU clock, each moved by hand;
    ``reads`` is the order in which a span read them."""

    def __init__(self):
        self.wall, self.cpu, self.reads = 100.0, 7.0, []

    def now(self):
        self.reads.append("wall")
        return self.wall

    def thread_now(self):
        self.reads.append("cpu")
        return self.cpu

    def run(self, wall_s, cpu_s):
        self.wall += wall_s
        self.cpu += cpu_s


@pytest.fixture(autouse=True)
def fine_thread_clock(monkeypatch):
    """The host's thread clock is taken as fine (Linux); the tests of
    the probe itself say otherwise."""
    monkeypatch.setattr(trace_mod, "_thread_clock_fine", True)


@pytest.fixture()
def dials(monkeypatch):
    d = TwoDials()
    monkeypatch.setattr(trace_mod, "_now", d.now)
    monkeypatch.setattr(trace_mod, "_thread_now", d.thread_now)
    return d


def test_a_span_books_its_threads_cpu_beside_its_wall(dials):
    reg = get_registry()
    wall = reg.histogram("pio_test_span_ms", "t", ("part",))
    cpu = reg.histogram("pio_test_span_cpu_ms", "t", ("part",))
    with span("work", hist=wall, cpu_hist=cpu, labels={"part": "a"}):
        dials.run(0.25, 0.1)
    assert wall.sum(part="a") == pytest.approx(250.0)
    assert cpu.sum(part="a") == pytest.approx(100.0)
    assert cpu.count(part="a") == wall.count(part="a") == 1
    # The thread clock is read INSIDE the wall clock's two readings, so
    # on real clocks a span's CPU time cannot pass its wall.
    assert dials.reads == ["wall", "cpu", "cpu", "wall"]
    # The root of a trace books it the same way, and a nested trace().
    with trace("root", hist=wall, cpu_hist=cpu, labels={"part": "r"}):
        dials.run(0.5, 0.5)
        with trace("nested", hist=wall, cpu_hist=cpu,
                   labels={"part": "n"}):
            dials.run(0.125, 0.0)
    assert (wall.sum(part="r"), cpu.sum(part="r")) == pytest.approx(
        (625.0, 500.0))
    assert (wall.sum(part="n"), cpu.sum(part="n")) == pytest.approx(
        (125.0, 0.0))


@pytest.mark.parametrize("opener", ["span", "trace"])
def test_the_cpu_observation_is_made_on_the_exception_path(dials, opener):
    reg = get_registry()
    wall = reg.histogram("pio_test_span_ms", "t", ("part",))
    cpu = reg.histogram("pio_test_span_cpu_ms", "t", ("part",))
    open_ = span if opener == "span" else trace
    with pytest.raises(KeyError):
        with open_("work", hist=wall, cpu_hist=cpu, labels={"part": "a"}):
            dials.run(0.004, 0.003)
            raise KeyError("boom")
    assert cpu.count(part="a") == 1
    assert cpu.sum(part="a") == pytest.approx(3.0)
    assert wall.sum(part="a") == pytest.approx(4.0)


def test_a_span_without_a_cpu_histogram_reads_no_thread_clock(monkeypatch):
    def no_thread_clock():
        raise AssertionError("a span with no cpu_hist read thread_time")

    monkeypatch.setattr(trace_mod, "_thread_now", no_thread_clock)
    wall = get_registry().histogram("pio_test_span_ms", "t", ("part",))
    with trace("http.request"):
        with span("http.read"):
            pass
        with span("work", hist=wall, labels={"part": "a"}, annotate=True):
            pass
    assert wall.count(part="a") == 1


@pytest.mark.parametrize("step_s, reads_until_it_moves, fine", [
    (1e-6, 1, True),        # Linux: a microsecond a reading
    (1e-2, 40, False),      # a sandboxed kernel: one 10 ms tick
    (0.0, 0, False),        # never moves within the 2 ms
])
def test_the_thread_clock_is_probed_once_and_a_coarse_one_gets_no_twin(
        monkeypatch, step_s, reads_until_it_moves, fine):
    state = {"reads": 0, "wall": 50.0}

    def thread_time():
        state["reads"] += 1
        moved = reads_until_it_moves and \
            state["reads"] > reads_until_it_moves
        return 3.0 + (step_s if moved else 0.0)

    def perf_counter():
        state["wall"] += 1e-5
        return state["wall"]

    monkeypatch.setattr(trace_mod, "_thread_clock_fine", None)
    monkeypatch.setattr(trace_mod.time, "thread_time", thread_time)
    monkeypatch.setattr(trace_mod.time, "perf_counter", perf_counter)
    reg = get_registry()
    wall = reg.histogram("pio_test_span_ms", "t", ("part",))
    cpu = reg.histogram("pio_test_span_cpu_ms", "t", ("part",))
    with span("work", hist=wall, cpu_hist=cpu, labels={"part": "a"}):
        pass
    assert trace_mod._thread_clock_fine is fine
    probed = state["reads"]
    assert probed <= 1 + (reads_until_it_moves or 200)
    with span("work", hist=wall, cpu_hist=cpu, labels={"part": "a"}):
        pass
    assert state["reads"] == probed         # asked once a process
    assert wall.count(part="a") == 2
    assert cpu.count(part="a") == (2 if fine else 0)


def test_on_the_real_clocks_cpu_is_at_most_wall():
    for _ in range(50):
        with dispatch_stage("predict.assemble", "assemble"):
            sum(range(2000))
    reg = get_registry()
    wall = reg.get("pio_dispatch_stage_ms").sum(stage="assemble")
    cpu = reg.get("pio_dispatch_stage_cpu_ms").sum(stage="assemble")
    assert 0.0 < cpu <= wall


def _open_dispatch_stages():
    for stage in ("bind", "supplement", "lookup", "h2d", "launch", "wait",
                  "assemble", "serve", "seq_extend", "seq_h2d",
                  "seq_launch", "seq_wait"):
        with dispatch_stage("stage." + stage, stage):
            pass


def _open_train_phases():
    for name in ("train.prepare", "prep.plan", "prep.lower_loop"):
        with phase(name):
            pass


def _run_a_batcher_turn():
    queue = ModelQueue("m", depth=4)
    clock = Dial()
    clock.wait = lambda cond, timeout: False
    batcher = MicroBatcher(
        "m", queue, lambda qs: ([{"ok": True}] * len(qs), 1),
        window_s=0.0, max_size=4, clock=clock)
    queue.put(Pending({"q": 1}, clock.now()))
    assert batcher.run_once() == 1


@pytest.mark.parametrize("wall_family, cpu_family, drive", [
    ("pio_dispatch_stage_ms", "pio_dispatch_stage_cpu_ms",
     _open_dispatch_stages),
    ("pio_train_phase_ms", "pio_train_phase_cpu_ms", _open_train_phases),
    ("pio_batcher_thread_ms", "pio_batcher_thread_cpu_ms",
     _run_a_batcher_turn),
])
def test_a_twin_family_carries_its_wall_familys_label_sets(
        wall_family, cpu_family, drive):
    drive()
    reg = get_registry()
    wall, cpu = reg.get(wall_family), reg.get(cpu_family)
    assert wall.labelnames == cpu.labelnames
    series = {k: s.count for k, s in wall._series.items()}
    assert series and series == {k: s.count
                                 for k, s in cpu._series.items()}
    for key, s in cpu._series.items():
        assert s.sum <= wall._series[key].sum


def test_annotation_carries_the_pio_name_and_closes_on_the_exception_path(
        annotations):
    with pytest.raises(KeyError):
        with dispatch_stage("retrieval.wait", "wait"):
            raise KeyError("boom")
    with pytest.raises(ValueError):
        with trace("batcher.dispatch", annotate=True):
            raise ValueError("boom")
    with span("not.annotated"):
        pass
    assert [(n, t) for n, _, _, t in annotations.events] == [
        ("pio:retrieval.wait", KeyError),
        ("pio:batcher.dispatch", ValueError)]
    # The crashed stage was observed too: the runs most worth seeing.
    assert _stage_counts()["wait"] == 1


def test_phase_is_a_span_with_both_sinks(annotations):
    assert isinstance(phase("train.prepare"), span)
    with trace("workflow.train") as root:
        with phase("train.prepare", algo="als") as s:
            pass
    assert root.children == [s] and s.attrs == {"algo": "als"}
    hist = get_registry().get("pio_train_phase_ms")
    assert hist.count(phase="train.prepare") == 1
    assert annotations.events[0][0] == "pio:train.prepare"


def test_annotation_is_a_no_op_in_a_process_without_jax(monkeypatch):
    monkeypatch.setattr(trace_mod, "_TraceAnnotation", None)
    monkeypatch.delitem(trace_mod.sys.modules, "jax", raising=False)
    assert trace_mod._open_annotation("x") is None
    with dispatch_stage("dispatch.serve", "serve") as s:
        pass
    assert s.duration_ms is not None


def test_a_real_capture_holds_the_pio_spans(tmp_path):
    """The same spans through jax's own profiler on the CPU, read back
    with ``ProfileData``: nested per thread, on the capture's clock."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((8, 8))
    (x @ x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with phase("prep.plan"):
            with dispatch_stage("retrieval.wait", "wait"):
                (x @ x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb",
                            recursive=True))[-1]
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("pio:"):
                    found[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert set(found) == {"pio:prep.plan", "pio:retrieval.wait"}
    outer, inner = found["pio:prep.plan"], found["pio:retrieval.wait"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]


# -- the batcher thread's ledger --------------------------------------------


class ArrivalClock(Dial):
    """The batcher's clock: the queue stays empty for ``idle_s`` (the
    batcher waits it out in slices), then a request arrives; a window
    wait passes its timeout with no arrival."""

    def __init__(self, queue, make_entry, idle_s=0.125):
        super().__init__()
        self.queue, self.make_entry, self.idle_s = queue, make_entry, idle_s
        self.quiet_s = idle_s

    def wait(self, cond, timeout):
        if timeout < IDLE_SLICE_S:          # the batch window
            self.t += timeout
            return False
        if self.quiet_s > timeout:          # one more slice of quiet
            self.t += timeout
            self.quiet_s -= timeout
            return False
        self.t += self.quiet_s
        self.quiet_s = self.idle_s
        self.queue.put(self.make_entry())
        return True


def test_the_five_batcher_phases_tile_the_threads_wall(monkeypatch,
                                                       annotations):
    queue = ModelQueue("m", depth=16)
    clock = ArrivalClock(queue, lambda: SlowPending())

    class SlowPending(Pending):
        """Claiming and waking a member cost time on the dial."""

        def __init__(self):
            super().__init__({"q": 1}, clock.now())

        def claim(self):
            clock.t += 0.001
            return super().claim()

        def finish(self, result=None, error=None):
            clock.t += 0.002
            super().finish(result, error)

    def dispatch_fn(queries):
        clock.t += 0.037
        return [{"ok": True}] * len(queries), 1

    monkeypatch.setattr(trace_mod, "_now", clock.now)
    annotations.clock = clock.now
    batcher = MicroBatcher("m", queue, dispatch_fn, window_s=0.002,
                           max_size=8, clock=clock)
    start = clock.now()
    turns = 4
    for _ in range(turns):
        assert batcher.run_once() == 1
    wall = clock.now() - start

    ledger = [(n, s, e) for n, s, e, _ in annotations.events
              if n.startswith("pio:batcher.")]
    # 125 ms of quiet are three slices of at most IDLE_SLICE_S.
    assert [n for n, _, _ in ledger] == (
        ["pio:batcher.wait_empty"] * 2
        + [f"pio:batcher.{p}" for p in THREAD_PHASES]) * turns
    # No gap, no overlap: each phase starts where the last one ended,
    # from the first turn's start to the last turn's end.
    assert ledger[0][1] == start and ledger[-1][2] == clock.now()
    for (_, _, end), (_, nxt, _) in zip(ledger, ledger[1:]):
        assert nxt == end
    hist = get_registry().get("pio_batcher_thread_ms")
    by_phase = {p: hist.sum(model="m", phase=p) for p in THREAD_PHASES}
    assert [hist.count(model="m", phase=p) for p in THREAD_PHASES] \
        == [3 * turns, turns, turns, turns, turns]
    assert sum(by_phase.values()) == pytest.approx(wall * 1e3)
    # The first two turns wait out the 2 ms window; after two lone
    # batches the batcher stops waiting for company.
    assert by_phase == pytest.approx({
        "wait_empty": turns * 125.0, "wait_window": 2 * 2.0,
        "shed": turns * 1.0, "dispatch": turns * 37.0,
        "finish": turns * 2.0})
    # pio_batch_dispatch_ms stays, beside the ledger's dispatch phase.
    assert get_registry().get("pio_batch_dispatch_ms").sum(
        model="m") == pytest.approx(turns * 37.0)


def test_a_failed_dispatch_is_finished_inside_the_finish_phase(
        monkeypatch, annotations):
    queue = ModelQueue("m", depth=16)
    clock = Dial()
    clock.wait = lambda cond, timeout: False
    calls = []

    def dispatch_fn(queries):
        calls.append(len(queries))
        if len(queries) > 1:
            raise RuntimeError("poisoned cohort")
        clock.t += 0.010
        return [{"ok": True}], 1

    monkeypatch.setattr(trace_mod, "_now", clock.now)
    batcher = MicroBatcher("m", queue, dispatch_fn, window_s=0.0,
                           max_size=8, clock=clock)
    entries = [Pending({"q": i}, clock.now()) for i in range(2)]
    for e in entries:
        queue.put(e)
    assert batcher.run_once() == 2
    assert calls == [2, 1, 1] and all(e.error is None for e in entries)
    assert [n for n, *_ in annotations.events] == [
        "pio:batcher.wait_empty", "pio:batcher.wait_window",
        "pio:batcher.shed", "pio:batcher.dispatch", "pio:batcher.finish"]
    hist = get_registry().get("pio_batcher_thread_ms")
    assert hist.sum(model="m", phase="finish") == pytest.approx(20.0)


# -- the inside of retrieval ------------------------------------------------


def _retriever(n=64, d=8):
    from predictionio_tpu.retrieval import Retriever

    rng = np.random.default_rng(0)
    return Retriever(rng.normal(size=(n, d)).astype(np.float32),
                     name="spans")


@pytest.mark.parametrize("rung", ["device", "chunked"])
def test_retrieval_self_time_plus_children_is_the_retrieval_span(
        monkeypatch, rung):
    monkeypatch.setenv("PIO_RETRIEVAL_RUNG", rung)
    r = _retriever()
    q = np.ones((3, 8), np.float32)
    r.topk(q, 5)                      # compile outside the ticking clock
    reg = get_registry()
    stages = reg.get("pio_dispatch_stage_ms")
    children = ("h2d", "launch", "wait")

    def readings():
        return (reg.get("pio_retrieval_ms").sum(rung=rung),
                [stages.count(stage=s) for s in children],
                sum(stages.sum(stage=s) for s in children))

    before = readings()

    ticks = iter(range(10 ** 6))
    lock = threading.Lock()

    def tick():                       # every reading is 1 ms later
        with lock:
            return next(ticks) * 1e-3

    monkeypatch.setattr(trace_mod, "_now", tick)
    with trace("batcher.dispatch") as root:
        scores, ids, info = r.topk(q, 5)
    assert info["rung"] == rung
    (sp,) = root.children
    assert sp.name == "retrieval"
    assert [c.name for c in sp.children] == [
        "retrieval.h2d", "retrieval.launch", "retrieval.wait"]
    self_ms = sp.duration_ms - sp.children_ms()
    assert self_ms > 0
    # Children lie inside the parent, in order, without overlap.
    edge = sp._t0
    for c in sp.children:
        assert c._t0 >= edge
        edge = c._t0 + c.duration_ms / 1e3
    assert edge <= sp._t0 + sp.duration_ms / 1e3
    # The series tell the same story: pio_retrieval_ms is the span,
    # the three stages are its children, each counted once.
    after = readings()
    assert after[0] - before[0] == pytest.approx(sp.duration_ms)
    assert [a - b for a, b in zip(after[1], before[1])] == [1, 1, 1]
    assert after[2] - before[2] == pytest.approx(sp.children_ms())
    assert info["ms"] == pytest.approx(sp.duration_ms)


def test_the_host_rung_keeps_the_bare_retrieval_span(monkeypatch,
                                                     annotations):
    monkeypatch.setenv("PIO_RETRIEVAL_RUNG", "host")
    with trace("batcher.dispatch") as root:
        _retriever().topk(np.ones((2, 8), np.float32), 5)
    (sp,) = root.children
    assert sp.name == "retrieval" and sp.children == []
    assert [n for n, *_ in annotations.events] == ["pio:retrieval"]
    assert not any(_stage_counts().values())


# -- ALS prep and train -----------------------------------------------------


def _phase_counts():
    hist = get_registry().get("pio_train_phase_ms")
    names = ("prep.upload", "prep.plan", "prep.lower_build",
             "prep.lower_loop", "prep.init_factors", "prep.compile_wait",
             "prep.build_run", "train.loop_wait", "train.dispatch")
    return {n: hist.count(phase=n) for n in names if hist.count(phase=n)}


@pytest.mark.parametrize("device_prep, expected", [
    (False, {"prep.init_factors": 1, "prep.plan": 2, "prep.upload": 2,
             "train.dispatch": 1}),
    (True, {"prep.upload": 1, "prep.plan": 1, "prep.lower_build": 1,
            "prep.lower_loop": 1, "prep.init_factors": 1,
            "prep.compile_wait": 1, "prep.build_run": 1,
            "train.loop_wait": 1, "train.dispatch": 1}),
])
def test_prep_and_train_phases_are_observed(device_prep, expected):
    from predictionio_tpu.models.als import (
        ALSConfig, prepare_als_inputs, train_als_prepared,
    )

    # A shape no other test prepares: the build and loop programs are
    # memoized per plan for the life of the process.
    rng = np.random.default_rng(25)
    n_users, n_items, nnz = 37, 23, 411
    users = rng.integers(0, n_users, nnz).astype(np.int32)
    items = rng.integers(0, n_items, nnz).astype(np.int32)
    stars = rng.integers(1, 6, nnz).astype(np.float32)
    config = ALSConfig(rank=8, iterations=1, reg=0.1, seed=25,
                       device_prep=device_prep)
    inputs = prepare_als_inputs(users, items, stars, n_users, n_items,
                                config)
    prep_only = {k: v for k, v in expected.items()
                 if k.startswith("prep.")}
    assert _phase_counts() == prep_only
    model = train_als_prepared(inputs, config)
    assert np.isfinite(np.asarray(model.user_factors)).all()
    assert _phase_counts() == expected
