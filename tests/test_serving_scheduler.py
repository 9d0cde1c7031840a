"""Serving scheduler (ISSUE 6): admission, micro-batching, autotune.

The deadline-window unit tests drive the batcher's gather/dispatch logic
directly with an injectable clock and a fake engine — zero wall sleeps,
the same discipline as tests/test_supervision.py.  One threaded
integration class exercises the real dispatcher thread and the engine
server's HTTP surface (429 + Retry-After, batcher metrics, retained-
previous eviction).
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.obs import get_registry
from predictionio_tpu.resilience.deadline import (
    DeadlineExceeded,
    deadline_scope,
)
from predictionio_tpu.serving import (
    MicroBatcher,
    ModelQueue,
    Pending,
    QueueFull,
    SchedulerClosed,
    SchedulerConfig,
    ServingScheduler,
    WindowAutotuner,
)


class FakeClock:
    """now() is a dial; wait() advances it by the timeout and reports
    'no arrival' — a gather window passes with zero wall time."""

    def __init__(self, t=0.0):
        self.t = t
        self.waits = []

    def now(self):
        return self.t

    def wait(self, cond, timeout):
        self.waits.append(timeout)
        if timeout is not None:
            self.t += timeout
        return False


class FakeEngine:
    """Records each dispatch (queries, generation at snapshot).  The
    generation is snapshotted ONCE per call under a lock — the same
    contract as EngineServer._dispatch_batch — and ``swap_mid_dispatch``
    simulates a staged reload landing while the batch is in flight."""

    def __init__(self):
        self.generation = 1
        self.calls = []
        self.swap_mid_dispatch = False
        self._lock = threading.Lock()

    def dispatch(self, queries):
        with self._lock:
            gen = self.generation
            if self.swap_mid_dispatch:
                self.generation += 1  # the "reload" lands mid-batch
        self.calls.append((list(queries), gen))
        return [(q, gen) for q in queries], gen


def _batcher(engine=None, clock=None, depth=16, window_s=0.010,
             max_size=8, autotuner=None):
    engine = engine or FakeEngine()
    clock = clock or FakeClock()
    q = ModelQueue("m", depth)
    b = MicroBatcher("m", q, engine.dispatch, window_s=window_s,
                     max_size=max_size, clock=clock, autotuner=autotuner)
    return engine, clock, q, b


class TestDeadlineAwareWindow:
    def test_window_closes_early_under_deadline_pressure(self, pio_home):
        """A member with little slack pulls the close forward: the batch
        dispatches while the constrained request can still answer in
        time, instead of holding it for the full window."""
        engine, clock, q, b = _batcher(window_s=0.010)
        b._est_dispatch_s = 0.004  # EWMA: dispatch costs ~4ms
        tight = Pending("tight", clock.now(), deadline_s=0.006)
        loose = Pending("loose", clock.now(), deadline_s=None)
        q.put(tight)
        q.put(loose)
        batch = b.gather()
        assert {e.query for e in batch} == {"tight", "loose"}
        # window must have closed at deadline-est (6-4=2ms), NOT at 10ms
        assert clock.t == pytest.approx(0.002)
        n = b.dispatch(batch)
        assert n == 2
        assert len(engine.calls) == 1  # ONE coalesced dispatch
        assert tight.result == ("tight", 1)
        assert tight.error is None  # answered inside its budget

    def test_no_deadline_runs_the_full_window(self, pio_home):
        engine, clock, q, b = _batcher(window_s=0.010)
        q.put(Pending("a", clock.now()))
        batch = b.gather()
        assert clock.t == pytest.approx(0.010)
        assert len(batch) == 1

    def test_full_batch_skips_the_window(self, pio_home):
        engine, clock, q, b = _batcher(window_s=0.010, max_size=3)
        for i in range(3):
            q.put(Pending(i, clock.now()))
        batch = b.gather()
        assert len(batch) == 3
        assert clock.t == 0.0  # max_size reached: no window wait at all

    def test_lone_client_stream_stops_paying_the_window(self, pio_home):
        """Two consecutive singleton gathers prove the stream is a lone
        client: further singles dispatch immediately (no window tax), and
        the first multi-entry scoop re-arms the window."""
        engine, clock, q, b = _batcher(window_s=0.010)
        for _ in range(2):  # singles pay the window while streak builds
            q.put(Pending("s", clock.now()))
            t0 = clock.t
            b.gather()
            assert clock.t == pytest.approx(t0 + 0.010)
        q.put(Pending("s", clock.now()))
        t0 = clock.t
        assert len(b.gather()) == 1
        assert clock.t == t0  # streak >= 2: no window wait
        q.put(Pending("a", clock.now()))
        q.put(Pending("b", clock.now()))
        assert len(b.gather()) == 2  # scoop still coalesces concurrency
        q.put(Pending("s", clock.now()))
        t0 = clock.t
        b.gather()
        assert clock.t == pytest.approx(t0 + 0.010)  # window re-armed

    def test_zero_window_still_coalesces_the_backlog(self, pio_home):
        """Entries already queued batch for free — a zero window means
        'never WAIT for arrivals', not 'never batch': under overload the
        backlog coalesces with no added latency."""
        engine, clock, q, b = _batcher(window_s=0.0, max_size=8)
        for i in range(5):
            q.put(Pending(i, clock.now()))
        batch = b.gather()
        assert len(batch) == 5
        assert clock.t == 0.0  # zero wall/window time spent

    def test_expired_entries_shed_before_device_work(self, pio_home):
        """An entry whose deadline passed while queued is 504-shed pre-
        dispatch: the engine never sees it, the live cohort still runs."""
        engine, clock, q, b = _batcher()
        clock.t = 1.0
        dead = Pending("dead", 0.0, deadline_s=0.5)     # expired at t=1
        live = Pending("live", 0.9, deadline_s=None)
        b.dispatch([dead, live])
        assert isinstance(dead.error, DeadlineExceeded)
        assert live.result == ("live", 1)
        assert engine.calls == [(["live"], 1)]
        shed = get_registry().get("pio_queue_shed_total")
        assert shed.value(model="m", reason="expired") == 1

    def test_abandoned_entries_dropped_silently(self, pio_home):
        engine, clock, q, b = _batcher()
        gone = Pending("gone", 0.0)
        assert gone.abandon()  # the waiter walked (its deadline fired)
        b.dispatch([gone])
        assert engine.calls == []  # nothing live: no dispatch at all

    def test_failed_singleton_is_not_dispatched_twice(self, pio_home):
        """A failed batch of ONE must answer with the original error —
        re-dispatching the identical call would double the device work
        for the same outcome (and every inline-mode error with it)."""

        class Boom:
            calls = 0

            def dispatch(self, queries):
                Boom.calls += 1
                raise ValueError("kaput")

        q = ModelQueue("m", 4)
        b = MicroBatcher("m", q, Boom().dispatch, clock=FakeClock())
        solo = Pending("q", 0.0)
        b.dispatch([solo])
        assert isinstance(solo.error, ValueError)
        assert Boom.calls == 1

    def test_batch_error_isolates_per_member(self, pio_home):
        """One poisoned query 400s itself, not its cohort."""

        class Picky:
            def __init__(self):
                self.calls = 0

            def dispatch(self, queries):
                self.calls += 1
                if "bad" in queries:
                    raise ValueError("cannot bind 'bad'")
                return [q.upper() for q in queries], 3

        eng = Picky()
        clock = FakeClock()
        q = ModelQueue("m", 8)
        b = MicroBatcher("m", q, eng.dispatch, clock=clock)
        good, bad = Pending("ok", 0.0), Pending("bad", 0.0)
        b.dispatch([good, bad])
        assert good.result == "OK"
        assert isinstance(bad.error, ValueError)
        assert eng.calls == 3  # 1 batch attempt + 2 isolated retries


class TestGenerationAtomicity:
    def test_batch_never_spans_a_mid_flight_swap(self, pio_home):
        """A reload landing mid-dispatch must not split the batch: every
        member is answered by the ONE generation snapshotted at dispatch
        entry, and the NEXT batch picks up the new generation."""
        engine, clock, q, b = _batcher()
        engine.swap_mid_dispatch = True
        first = [Pending(f"a{i}", 0.0) for i in range(4)]
        b.dispatch(first)
        gens = {e.result[1] for e in first}
        assert gens == {1}, f"batch split across generations: {gens}"
        second = [Pending(f"b{i}", 0.0) for i in range(4)]
        b.dispatch(second)
        assert {e.result[1] for e in second} == {2}
        assert [g for _, g in engine.calls] == [1, 2]

    def test_concurrent_reloads_never_split_any_batch(self, pio_home):
        """Threaded version: submitters + a reload thread against the
        real dispatcher thread; every recorded dispatch must be answered
        by exactly one generation (consistency, not timing, is asserted)."""
        engine = FakeEngine()
        sched = ServingScheduler(SchedulerConfig(
            window_ms=2.0, max_batch=8, queue_depth=64, autotune=False))
        sched.register("m", engine.dispatch)
        stop = threading.Event()

        def reloader():
            while not stop.is_set():
                with engine._lock:
                    engine.generation += 1

        results = []
        res_lock = threading.Lock()

        def submitter(base):
            for i in range(16):
                r = sched.submit_and_wait("m", f"{base}-{i}")
                with res_lock:
                    results.append(r)

        rt = threading.Thread(target=reloader)
        rt.start()
        threads = [threading.Thread(target=submitter, args=(t,))
                   for t in range(4)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            stop.set()
            rt.join()
            sched.close()
        assert len(results) == 64
        for queries, gen in engine.calls:
            answered = [g for rq, g in results if rq in queries]
            assert set(answered) == {gen}, \
                f"batch {queries} answered by generations {set(answered)}"


class TestAdmission:
    def test_queue_full_rejects(self, pio_home):
        q = ModelQueue("m", 2)
        q.put(Pending("a", 0.0))
        q.put(Pending("b", 0.0))
        with pytest.raises(QueueFull):
            q.put(Pending("c", 0.0))

    def test_abandoned_corpses_free_admission_slots(self, pio_home):
        """Entries whose waiter walked (deadline) must not hold queue
        slots against live traffic while a slow dispatch is in flight:
        a full-looking queue of corpses compacts at admission."""
        q = ModelQueue("m", 2)
        dead1, dead2 = Pending("d1", 0.0), Pending("d2", 0.0)
        q.put(dead1)
        q.put(dead2)
        assert dead1.abandon() and dead2.abandon()
        live = Pending("live", 0.0)
        q.put(live)  # corpses swept, slot freed — no QueueFull
        assert len(q) == 1

    def test_batch_retry_sheds_expired_members(self, pio_home):
        """The per-member retry after a failed batch re-checks budgets:
        a member that expired during the failed attempt sheds 504
        instead of burning a doomed device dispatch."""

        clock = FakeClock()
        calls = []

        def flaky(queries):
            calls.append(list(queries))
            if len(calls) == 1:
                clock.t = 1.0  # the failed attempt burns doomed's budget
                raise ConnectionError("backend blip")
            return [q.upper() for q in queries], 1

        q = ModelQueue("m", 8)
        b = MicroBatcher("m", q, flaky, clock=clock)
        doomed = Pending("dead", 0.0, deadline_s=0.5)
        alive = Pending("ok", 0.0, deadline_s=None)
        clock.t = 0.3  # doomed still in budget when the batch forms
        b.dispatch([doomed, alive])
        assert isinstance(doomed.error, DeadlineExceeded)
        assert alive.result == "OK"
        assert calls == [["dead", "ok"], ["ok"]]  # no doomed re-dispatch

    def test_per_model_isolation(self, pio_home):
        """Model A at capacity must not poison model B's admission."""
        qa, qb = ModelQueue("a", 1), ModelQueue("b", 1)
        qa.put(Pending("x", 0.0))
        with pytest.raises(QueueFull):
            qa.put(Pending("y", 0.0))
        qb.put(Pending("z", 0.0))  # unaffected
        assert len(qb) == 1

    def test_scheduler_per_model_isolation_end_to_end(self, pio_home):
        engine = FakeEngine()
        sched = ServingScheduler(SchedulerConfig(
            enabled=False, queue_depth=0))  # depth 0: reject everything
        sched.register("full", engine.dispatch)
        sched2 = ServingScheduler(SchedulerConfig(enabled=False,
                                                  queue_depth=4))
        sched2.register("open", engine.dispatch)
        with pytest.raises(QueueFull):
            sched.submit_and_wait("full", "q")
        assert sched2.submit_and_wait("open", "q") == ("q", 1)

    def test_inline_mode_dispatches_and_counts(self, pio_home):
        """PIO_BATCH_ENABLED=off: same scheduler surface, caller-thread
        dispatch, admission + metrics still live."""
        engine = FakeEngine()
        sched = ServingScheduler(SchedulerConfig(enabled=False,
                                                 queue_depth=4))
        sched.register("m", engine.dispatch)
        assert sched.submit_and_wait("m", "q1") == ("q1", 1)
        snap = sched.snapshot()["m"]
        assert snap["batching"] is False
        assert snap["requests"] == 1 and snap["dispatches"] == 1
        sched.close()

    def test_inline_expired_deadline_sheds_504(self, pio_home):
        engine = FakeEngine()
        sched = ServingScheduler(SchedulerConfig(enabled=False,
                                                 queue_depth=4))
        sched.register("m", engine.dispatch)
        with deadline_scope(0):
            with pytest.raises(DeadlineExceeded):
                sched.submit_and_wait("m", "q")
        assert engine.calls == []  # shed BEFORE the engine
        sched.close()

    def test_closed_scheduler_rejects(self, pio_home):
        engine = FakeEngine()
        sched = ServingScheduler(SchedulerConfig(enabled=False))
        sched.register("m", engine.dispatch)
        sched.close()
        with pytest.raises(SchedulerClosed):
            sched.submit_and_wait("m", "q")

    def test_config_from_env(self, pio_home, monkeypatch):
        monkeypatch.setenv("PIO_BATCH_ENABLED", "off")
        monkeypatch.setenv("PIO_QUEUE_DEPTH", "7")
        monkeypatch.setenv("PIO_BATCH_WINDOW_MS", "3.5")
        monkeypatch.setenv("PIO_BATCH_MAX", "bogus")  # falls to default
        cfg = SchedulerConfig.from_env()
        assert (cfg.enabled, cfg.queue_depth, cfg.window_ms,
                cfg.max_batch) == (False, 7, 3.5, 64)
        # flag overrides beat env
        cfg = SchedulerConfig.from_env(queue_depth=9)
        assert cfg.queue_depth == 9


class TestAutotuner:
    def _pair(self):
        engine, clock, q, b = _batcher(window_s=0.004, max_size=8)
        tuner = WindowAutotuner("m", 100.0, window_max_s=0.020,
                                max_size_cap=64)
        return b, tuner

    def test_over_target_shrinks_window_then_batch(self, pio_home):
        b, tuner = self._pair()
        tuner.retune(b, p99_ms=400.0)
        assert b.window_s == pytest.approx(0.002)
        tuner.retune(b, p99_ms=400.0)
        assert b.window_s == pytest.approx(0.001)
        for _ in range(8):  # halving must SNAP to the floor, not decay
            tuner.retune(b, p99_ms=400.0)
            if b.window_s == 0.0:
                break
        assert b.window_s == 0.0    # window at floor: batch is next...
        b._est_dispatch_s = 0.050   # ...and the dispatch IS slow (50ms)
        tuner.retune(b, p99_ms=400.0)
        assert b.max_size == 4

    def test_backlog_latency_never_shrinks_the_batch(self, pio_home):
        """Over-target p99 with a FAST dispatch means offered load >
        capacity — shrinking the batch would cut throughput and make the
        backlog worse, so the tuner floors instead."""
        b, tuner = self._pair()
        b.set_knobs(window_s=0.0)
        b._est_dispatch_s = 0.003  # 3ms dispatch << 100ms target
        tuner.retune(b, p99_ms=400.0)
        assert b.max_size == 8  # untouched
        acts = get_registry().get("pio_batch_autotune_total")
        assert acts.value(model="m", action="floor") == 1

    def test_under_target_grows_batch_then_window(self, pio_home):
        b, tuner = self._pair()
        tuner.retune(b, p99_ms=10.0)
        assert b.max_size == 16  # restore batching headroom first
        b.set_knobs(max_size=64)
        w0 = b.window_s
        tuner.retune(b, p99_ms=10.0)
        assert b.window_s > w0

    def test_hysteresis_band_holds(self, pio_home):
        b, tuner = self._pair()
        w0, m0 = b.window_s, b.max_size
        tuner.retune(b, p99_ms=80.0)  # between 60 and 100
        assert (b.window_s, b.max_size) == (w0, m0)
        acts = get_registry().get("pio_batch_autotune_total")
        assert acts.value(model="m", action="hold") == 1

    def test_after_dispatch_retunes_on_interval(self, pio_home):
        engine, clock, q, b = _batcher(window_s=0.004)
        tuner = WindowAutotuner("m", 100.0, interval=4)
        b.autotuner = tuner
        for _ in range(400):
            tuner.observe(500.0)  # way over target
        for _ in range(4):
            tuner.after_dispatch(b)
        assert b.window_s < 0.004
        assert tuner.last_p99_ms == pytest.approx(500.0)


@pytest.fixture()
def trained(pio_home):
    """A small trained ALS engine + its storage (the HTTP integration
    substrate; mirrors test_servers.deployed but keeps server
    construction in the tests so they can pass scheduler configs/env)."""
    from predictionio_tpu.controller import EngineVariant, RuntimeContext
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage import App, get_storage
    from predictionio_tpu.templates.recommendation import engine
    from predictionio_tpu.workflow.core_workflow import run_train

    storage = get_storage()
    ctx = RuntimeContext.create(storage=storage)
    app_id = storage.get_apps().insert(App(id=None, name="schedapp"))
    storage.get_events().init(app_id)
    rng = np.random.default_rng(0)
    for u in range(8):
        for i in range(6):
            if rng.random() < 0.8:
                storage.get_events().insert(
                    Event(event="rate", entity_type="user",
                          entity_id=f"u{u}", target_entity_type="item",
                          target_entity_id=f"i{i}",
                          properties=DataMap({"rating": 4.0})), app_id)
    variant = EngineVariant.from_dict({
        "engineFactory": "predictionio_tpu.templates.recommendation:engine",
        "datasource": {"params": {"appName": "schedapp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 4, "numIterations": 3}}],
    })
    eng = engine()
    run_train(eng, variant, ctx)
    return eng, variant, storage, ctx


def _post(url, body):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, dict(resp.headers), \
                json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        payload = e.read()
        return e.code, dict(e.headers), \
            json.loads(payload) if payload else None


class TestEngineServerIntegration:
    def test_queries_coalesce_over_http(self, trained, monkeypatch):
        """Concurrent POST /queries.json share dispatches: requests >
        dispatches once clients overlap (the tentpole, end to end).

        The result cache is disabled: this test pins the BATCHER path
        (repeated users would otherwise hit the cache and never reach
        the scheduler's admission)."""
        from predictionio_tpu.server import EngineServer

        monkeypatch.setenv("PIO_RESULT_CACHE", "0")
        eng, variant, storage, _ = trained
        srv = EngineServer(eng, variant, storage, host="127.0.0.1", port=0,
                           scheduler_config=SchedulerConfig(
                               window_ms=10.0, max_batch=16,
                               queue_depth=64, autotune=False))
        srv.start()
        try:
            statuses = []
            lock = threading.Lock()

            def one(i):
                s, _, body = _post(
                    f"http://127.0.0.1:{srv.port}/queries.json",
                    {"user": f"u{i % 8}", "num": 2})
                with lock:
                    statuses.append((s, body))

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert all(s == 200 for s, _ in statuses)
            snap = srv.scheduler.snapshot()["default"]
            assert snap["requests"] == 12
            assert snap["dispatches"] < 12, \
                "no coalescing happened at 12-way concurrency"
        finally:
            srv.stop()

    def test_late_2xx_rewritten_to_504_with_attestation(self, trained):
        """The transport's late-response shed (never-late-200): a
        handler that answers 200 past its budget is rewritten to 504,
        and the X-PIO-Deadline-Remaining-Ms attestation carries the
        same reading the verdict used."""
        import time as _time

        from predictionio_tpu.server import EngineServer

        eng, variant, storage, _ = trained
        srv = EngineServer(eng, variant, storage, host="127.0.0.1", port=0)
        srv.start()
        try:
            real_handle = srv.handle

            def slow_handle(method, path, body, params=None):
                _time.sleep(0.05)  # blows the 20ms budget below
                return 200, {"ok": 1}

            srv.handle = slow_handle
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/queries.json", data=b"{}",
                method="POST", headers={"X-PIO-Deadline-Ms": "20"})
            try:
                with urllib.request.urlopen(req, timeout=10) as resp:
                    status, headers = resp.status, resp.headers
            except urllib.error.HTTPError as e:
                status, headers = e.code, e.headers
            assert status == 504
            assert float(headers["X-PIO-Deadline-Remaining-Ms"]) <= 0
            assert get_registry().get("pio_deadline_shed_total").value(
                server="engine") >= 1
            # no deadline header → no gate, no attestation
            srv.handle = real_handle
            status, headers, _body = _post(
                f"http://127.0.0.1:{srv.port}/queries.json",
                {"user": "u0", "num": 2})
            assert status == 200
            assert "X-PIO-Deadline-Remaining-Ms" not in headers
        finally:
            srv.stop()

    def test_faulted_drive_sheds_504_and_never_serves_a_late_200(
            self, trained, monkeypatch):
        """Concurrent clients, a quarter of them on a budget the
        injected handler delay overruns: what comes back is a 200 the
        server attests as in time (remaining budget >= 0) or a 504,
        through the real batcher and transport, never a late 200."""
        from predictionio_tpu.resilience import faults
        from predictionio_tpu.server import EngineServer

        monkeypatch.setenv("PIO_RESULT_CACHE", "0")
        eng, variant, storage, _ = trained
        srv = EngineServer(eng, variant, storage, host="127.0.0.1", port=0)
        srv.start()
        url = f"http://127.0.0.1:{srv.port}/queries.json"
        outcomes = []  # (status, budget sent, the server's remaining ms)

        def client(i):
            for k in range(10):
                budget = 15 if (i + k) % 4 == 0 else 5000
                req = urllib.request.Request(
                    url, method="POST",
                    data=json.dumps({"user": f"u{(i + k) % 8}",
                                     "num": 2}).encode(),
                    headers={"Content-Type": "application/json",
                             "X-PIO-Deadline-Ms": str(budget)})
                try:
                    with urllib.request.urlopen(req, timeout=30) as resp:
                        status, headers = resp.status, resp.headers
                except urllib.error.HTTPError as e:
                    status, headers = e.code, e.headers
                outcomes.append((status, budget, float(
                    headers["X-PIO-Deadline-Remaining-Ms"])))

        _post(url, {"user": "u0", "num": 2})  # the first query's warm-up
        faults.install("http.engine:delay:40ms")
        try:
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            faults.clear()
            srv.stop()
        assert len(outcomes) == 40
        assert all(rem >= 0 for s, _, rem in outcomes if s == 200), outcomes
        assert sorted((s, b) for s, b, _ in outcomes) \
            == [(200, 5000)] * 30 + [(504, 15)] * 10, outcomes

    def test_admission_full_answers_429_with_retry_after(self, trained):
        from predictionio_tpu.server import EngineServer

        eng, variant, storage, _ = trained
        srv = EngineServer(eng, variant, storage, host="127.0.0.1", port=0,
                           scheduler_config=SchedulerConfig(
                               enabled=False, queue_depth=0))
        srv.start()
        try:
            status, headers, body = _post(
                f"http://127.0.0.1:{srv.port}/queries.json",
                {"user": "u0", "num": 2})
            assert status == 429
            assert "Retry-After" in headers
            assert "full" in body["message"] or "limit" in body["message"]
            assert get_registry().get(
                "pio_queue_rejected_total").value(model="default") == 1
        finally:
            srv.stop()

    def test_batcher_surfaces_in_metrics_stats_and_status(self, trained):
        from predictionio_tpu.server import EngineServer

        eng, variant, storage, _ = trained
        srv = EngineServer(eng, variant, storage, host="127.0.0.1", port=0)
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            assert _post(f"{base}/queries.json",
                         {"user": "u0", "num": 2})[0] == 200
            with urllib.request.urlopen(f"{base}/metrics",
                                        timeout=10) as resp:
                text = resp.read().decode()
            for family in ("pio_batch_size_bucket", "pio_queue_wait_ms",
                           "pio_batch_dispatch_total",
                           "pio_batch_window_ms", "pio_queue_depth"):
                assert family in text, f"{family} missing from /metrics"
            with urllib.request.urlopen(f"{base}/stats.json",
                                        timeout=10) as resp:
                stats = json.loads(resp.read())
            assert stats["batcher"]["default"]["requests"] >= 1
            with urllib.request.urlopen(base, timeout=10) as resp:
                front = json.loads(resp.read())
            assert front["batcher"]["default"]["queueLimit"] >= 1
        finally:
            srv.stop()


class TestRetainedPreviousEviction:
    def _reloaded_server(self, trained, monkeypatch, **env):
        from predictionio_tpu.server import EngineServer
        from predictionio_tpu.workflow.core_workflow import run_train

        for k, v in env.items():
            monkeypatch.setenv(k, v)
        eng, variant, storage, ctx = trained
        srv = EngineServer(eng, variant, storage, host="127.0.0.1", port=0)
        run_train(eng, variant, ctx)   # a second instance to reload into
        srv.reload()
        return srv

    def test_retain_off_never_holds_a_second_generation(self, trained,
                                                        monkeypatch):
        srv = self._reloaded_server(trained, monkeypatch,
                                    PIO_RETAIN_PREVIOUS="off")
        try:
            assert srv._previous is None
            status, payload = srv.handle("GET", "/", b"")
            assert payload["rollbackAvailable"] is False
            status, payload = srv.handle("POST", "/admin/rollback", b"")
            assert status == 409
        finally:
            srv.stop()

    def test_rollback_inside_ttl_then_eviction_after(self, trained,
                                                     monkeypatch):
        """The satellite's pin: within the TTL the canary window is
        intact (rollback works); once the timer fires the previous
        generation is dropped and rollback answers 409."""
        srv = self._reloaded_server(trained, monkeypatch,
                                    PIO_RETAIN_PREVIOUS_TTL_S="300")
        try:
            assert srv._previous is not None
            assert srv._evict_timer is not None  # TTL armed
            gen_before = srv._generation
            # INSIDE the TTL: rollback still works (and re-arms)
            status, _ = srv.handle("POST", "/admin/rollback", b"")
            assert status == 200
            assert srv._generation == gen_before + 1
            # the timer fires (driven directly — no wall wait)
            assert srv._evict_previous(srv._generation) is True
            assert srv._previous is None
            reg = get_registry()
            assert reg.get(
                "pio_model_previous_evicted_total").value() == 1
            assert reg.get("pio_model_previous_retained").value() == 0
            # AFTER eviction: nothing to roll back to
            status, _ = srv.handle("POST", "/admin/rollback", b"")
            assert status == 409
        finally:
            srv.stop()

    def test_stale_eviction_timer_is_a_noop(self, trained, monkeypatch):
        """A timer armed for generation N must not evict the previous
        slot after a newer swap owns it."""
        srv = self._reloaded_server(trained, monkeypatch,
                                    PIO_RETAIN_PREVIOUS_TTL_S="300")
        try:
            stale_gen = srv._generation
            srv.rollback()  # newer swap: previous slot re-owned
            assert srv._evict_previous(stale_gen) is False
            assert srv._previous is not None
        finally:
            srv.stop()
