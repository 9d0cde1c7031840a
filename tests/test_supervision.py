"""Run supervision & crash-safe model lifecycle (ISSUE 4).

Training side: fake-clock watchdog firing, NaN-injected loss → rollback
→ converges, preemption mid-train → resumed ALS run bitwise-equal to an
uninterrupted one.  Serving side: reload under 100% storage faults fails
closed (last-good keeps serving, /ready stays 200, the failure and the
breaker transitions are observable), canary validation, and the instant
rollback endpoint.  CPU-only, fake clocks, no real sleeps — same
discipline as tests/test_resilience.py.
"""

import json

import numpy as np
import pytest

from predictionio_tpu.resilience import faults
from predictionio_tpu.resilience.supervision import (
    PREEMPTED_EXIT_CODE,
    DivergenceGuard,
    ModelValidationError,
    RollbackRequested,
    StepWatchdog,
    TrainDiverged,
    TrainPreempted,
    clear_preemption,
    install_preemption_handler,
    preemption_requested,
    request_preemption,
    validate_model_finite,
)

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _clean_supervision_state():
    clear_preemption()
    faults.clear()
    yield
    clear_preemption()
    faults.clear()


# -- step watchdog (fake clock, no sleeps) -----------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_watchdog_fires_once_with_metrics_event_and_checkpoint(pio_home):
    from predictionio_tpu.obs import get_recorder, get_registry
    from predictionio_tpu.obs.runtime import StepTimeline

    clock = FakeClock()
    tl = StepTimeline(capacity=8)
    tl.record("two_tower", host_wait_ms=1.0, device_step_ms=5.0, step=41)
    actions = []
    wd = StepWatchdog("two_tower", timeout_s=30.0, clock=clock,
                      checkpoint_fn=lambda: actions.append("ckpt"),
                      abort_fn=lambda: actions.append("abort"),
                      poll_interval_s=0, timeline=tl)
    assert wd.enabled
    wd.arm(42)
    assert wd.poll() is False  # not yet expired
    clock.t += 31.0
    assert wd.poll() is True
    # checkpoint flushed BEFORE abort
    assert actions == ["ckpt", "abort"]
    assert wd.poll() is False, "fires exactly once per armed step"
    counter = get_registry().counter(
        "pio_watchdog_fired_total", "", ("fn",))
    assert counter.value(fn="two_tower") == 1
    # trace-ring event carries the last step-timeline entry (published
    # outside any trace → standalone single-span trace doc)
    traces = get_recorder().recent(10)
    fired = [t for t in traces if t["name"] == "watchdog.fired"]
    assert fired and fired[0]["attrs"]["step"] == 42
    assert json.loads(fired[0]["attrs"]["lastStep"])["step"] == 41


def test_watchdog_disarm_prevents_firing(pio_home):
    clock = FakeClock()
    fired = []
    wd = StepWatchdog("als", timeout_s=10.0, clock=clock,
                      abort_fn=lambda: fired.append(1), poll_interval_s=0)
    wd.arm(1)
    wd.disarm()
    clock.t += 1000.0
    assert wd.poll() is False and not fired


def test_watchdog_disabled_without_env(pio_home, monkeypatch):
    monkeypatch.delenv("PIO_STEP_TIMEOUT_S", raising=False)
    wd = StepWatchdog("dlrm", poll_interval_s=0)
    assert not wd.enabled
    wd.arm(1)  # no-op
    assert wd.poll() is False


# -- PIO_STEP_TIMEOUT_KILL hard escalation (ISSUE 10 satellite) --------------

def test_kill_escalates_when_abort_cannot_unwind(pio_home):
    """A fired watchdog whose abort never unwinds (runtime wedged in a C
    call) hard-kills after the grace period — exactly once, with the
    metric and trace event."""
    from predictionio_tpu.obs import get_recorder, get_registry

    clock = FakeClock()
    actions = []
    wd = StepWatchdog("als", timeout_s=10.0, kill_grace_s=20.0, clock=clock,
                      abort_fn=lambda: actions.append("abort"),
                      kill_fn=lambda: actions.append("KILL"),
                      poll_interval_s=0)
    wd.arm(5)
    clock.t += 11.0
    assert wd.poll() is True          # soft fire
    assert actions == ["abort"]
    clock.t += 19.0                    # inside the grace window
    assert wd.poll() is False
    assert actions == ["abort"]
    clock.t += 2.0                     # grace expired, still not unwound
    assert wd.poll() is True
    assert actions == ["abort", "KILL"]
    assert wd.poll() is False, "kills exactly once"
    assert actions == ["abort", "KILL"]
    counter = get_registry().counter(
        "pio_watchdog_killed_total", "", ("fn",))
    assert counter.value(fn="als") == 1
    killed = [t for t in get_recorder().recent(10)
              if t["name"] == "watchdog.killed"]
    assert killed and killed[0]["attrs"]["graceS"] == 20.0


def test_kill_stands_down_when_run_unwinds(pio_home):
    """stop() (the training loop's finally) IS the unwind signal: a run
    the soft abort successfully tore down never escalates."""
    clock = FakeClock()
    actions = []
    wd = StepWatchdog("als", timeout_s=10.0, kill_grace_s=20.0, clock=clock,
                      abort_fn=lambda: actions.append("abort"),
                      kill_fn=lambda: actions.append("KILL"),
                      poll_interval_s=0)
    wd.arm(5)
    clock.t += 11.0
    assert wd.poll() is True
    wd.stop()                          # the abort unwound the loop
    clock.t += 1000.0
    assert wd.poll() is False
    assert actions == ["abort"]


def test_kill_disabled_by_default(pio_home, monkeypatch):
    """No PIO_STEP_TIMEOUT_KILL → never escalates, however long the
    wedge lasts (the pre-ISSUE-10 behavior is the default)."""
    monkeypatch.delenv("PIO_STEP_TIMEOUT_KILL", raising=False)
    clock = FakeClock()
    actions = []
    wd = StepWatchdog("als", timeout_s=10.0, clock=clock,
                      abort_fn=lambda: actions.append("abort"),
                      kill_fn=lambda: actions.append("KILL"),
                      poll_interval_s=0)
    assert wd.kill_grace_s == 0.0
    wd.arm(5)
    clock.t += 11.0
    assert wd.poll() is True
    clock.t += 1e6
    assert wd.poll() is False
    assert actions == ["abort"]


def test_kill_grace_reads_env(pio_home, monkeypatch):
    monkeypatch.setenv("PIO_STEP_TIMEOUT_KILL", "45")
    wd = StepWatchdog("als", timeout_s=1.0, poll_interval_s=0)
    assert wd.kill_grace_s == 45.0
    monkeypatch.setenv("PIO_STEP_TIMEOUT_KILL", "nonsense")
    wd = StepWatchdog("als", timeout_s=1.0, poll_interval_s=0)
    assert wd.kill_grace_s == 0.0


# -- divergence guard --------------------------------------------------------

def test_guard_allows_finite_and_bounds_rollbacks(pio_home):
    g = DivergenceGuard("tt", max_rollbacks=2)
    g.check(0.5, 1)  # finite: silent
    with pytest.raises(RollbackRequested):
        g.check(float("nan"), 2)
    with pytest.raises(RollbackRequested):
        g.check(float("inf"), 3)
    with pytest.raises(TrainDiverged) as ei:
        g.check(float("nan"), 4)
    assert "rollback" in str(ei.value)
    from predictionio_tpu.obs import get_registry

    c = get_registry().counter("pio_train_divergence_total", "", ("fn",))
    assert c.value(fn="tt") == 3


def test_validate_model_finite_walks_wrapper_objects(pio_home):
    class Wrapper:
        def __init__(self, arr):
            self.nested = {"factors": [arr]}

    validate_model_finite(Wrapper(np.ones((3, 2), np.float32)))
    bad = np.ones((3, 2), np.float32)
    bad[1, 1] = np.nan
    with pytest.raises(ModelValidationError, match="non-finite"):
        validate_model_finite(Wrapper(bad))
    # integer arrays are exempt (nothing to be non-finite)
    validate_model_finite(Wrapper(np.ones((2,), np.int32)))


# -- NaN injection → rollback → converges ------------------------------------

def _tt_data():
    rng = np.random.default_rng(0)
    return rng.integers(0, 16, 200), rng.integers(0, 8, 200)


def _tt_cfg():
    from predictionio_tpu.models import two_tower as tt

    return tt.TwoTowerConfig(n_users=16, n_items=8, embed_dim=8,
                             hidden_dims=(16,), out_dim=8, batch_size=32,
                             epochs=2, seed=7)


def test_nan_injected_loss_rolls_back_and_converges(pio_home, tmp_path,
                                                    monkeypatch):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import two_tower as tt

    users, items = _tt_data()
    cfg = _tt_cfg()
    clean = tt.train(users, items, cfg)

    real_step = tt.train_step
    state_counter = {"n": 0, "injected": False}

    def nan_once(state, u, i, w, c):
        s2, loss = real_step(state, u, i, w, c)
        state_counter["n"] += 1
        if state_counter["n"] == 5 and not state_counter["injected"]:
            state_counter["injected"] = True
            poisoned = jax.tree.map(lambda x: x * jnp.nan, s2.params)
            return (tt.TwoTowerState(poisoned, s2.opt_state, s2.step),
                    jnp.float32(jnp.nan))
        return s2, loss

    monkeypatch.setattr(tt, "train_step", nan_once)
    out = tt.train(users, items, cfg, checkpoint_dir=tmp_path / "ck",
                   save_every=3)
    # The run completed, the model is finite, and the replayed steps
    # reproduce the clean result — the NaN state was never kept.
    assert np.isfinite(np.asarray(out.params["user_embed"])).all()
    np.testing.assert_allclose(np.asarray(clean.params["user_embed"]),
                               np.asarray(out.params["user_embed"]),
                               rtol=1e-5, atol=1e-6)


def test_persistent_divergence_raises_without_persisting(pio_home,
                                                         monkeypatch):
    import jax.numpy as jnp

    from predictionio_tpu.models import two_tower as tt

    users, items = _tt_data()
    cfg = _tt_cfg()
    real_step = tt.train_step

    def always_nan(state, u, i, w, c):
        s2, _ = real_step(state, u, i, w, c)
        return s2, jnp.float32(jnp.nan)

    monkeypatch.setattr(tt, "train_step", always_nan)
    with pytest.raises(TrainDiverged):
        tt.train(users, items, cfg)


def test_als_divergence_without_checkpoints_is_terminal(pio_home,
                                                        monkeypatch):
    from predictionio_tpu.models import als as als_lib

    rng = np.random.default_rng(3)
    users = rng.integers(0, 20, 400)
    items = rng.integers(0, 15, 400)
    ratings = rng.integers(1, 6, 400).astype(np.float32)
    cfg = als_lib.ALSConfig(rank=4, iterations=2, seed=4, split_above=64)

    real_loop = als_lib._train_loop

    def nan_loop(uf0, itf0, *a, **k):
        uf, itf = real_loop(uf0, itf0, *a, **k)
        return uf * np.nan, itf

    monkeypatch.setattr(als_lib, "_train_loop", nan_loop)
    with pytest.raises(TrainDiverged):
        als_lib.train_als(users, items, ratings, 20, 15, cfg)


# -- preemption --------------------------------------------------------------

def test_sigterm_handler_sets_preemption_flag(pio_home):
    import os
    import signal

    installed = install_preemption_handler()
    assert installed
    try:
        assert not preemption_requested()
        os.kill(os.getpid(), signal.SIGTERM)
        # synchronous on the main thread: the handler ran on return
        assert preemption_requested()
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        clear_preemption()
    assert PREEMPTED_EXIT_CODE == 143


def test_preempted_als_resumes_bitwise_equal(pio_home, tmp_path,
                                             monkeypatch):
    from predictionio_tpu.models import als as als_lib

    rng = np.random.default_rng(3)
    users = rng.integers(0, 40, 1200)
    items = (rng.zipf(1.4, 1200) % 30).astype(np.int64)
    ratings = rng.integers(1, 6, 1200).astype(np.float32)
    cfg = als_lib.ALSConfig(rank=8, iterations=6, reg=0.05, seed=4,
                            split_above=64)
    expected = als_lib.train_als(users, items, ratings, 40, 30, cfg)

    # "SIGTERM" lands between sweep chunks: the flag is what the signal
    # handler sets; raising it from inside the loop is the same path
    # without the cross-test hazard of a real signal.
    real_loop = als_lib._train_loop
    calls = {"n": 0}

    def preempting_loop(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            request_preemption()
        return real_loop(*a, **k)

    ck = tmp_path / "als"
    monkeypatch.setattr(als_lib, "_train_loop", preempting_loop)
    with pytest.raises(TrainPreempted) as ei:
        als_lib.train_als(users, items, ratings, 40, 30, cfg,
                          checkpoint_dir=ck, save_every=2)
    assert ei.value.checkpointed
    monkeypatch.setattr(als_lib, "_train_loop", real_loop)
    clear_preemption()

    resumed = als_lib.train_als(users, items, ratings, 40, 30, cfg,
                                checkpoint_dir=ck, save_every=2)
    np.testing.assert_array_equal(np.asarray(expected.user_factors),
                                  np.asarray(resumed.user_factors))
    np.testing.assert_array_equal(np.asarray(expected.item_factors),
                                  np.asarray(resumed.item_factors))


def test_preempted_run_marks_instance_preempted(pio_home, tmp_path,
                                                monkeypatch):
    """run_train records status=PREEMPTED (not FAILED) and the CLI's
    documented exit code is distinct from failure."""
    import os

    from predictionio_tpu.controller import EngineVariant, RuntimeContext
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage import App, get_storage
    from predictionio_tpu.models import als as als_lib
    from predictionio_tpu.templates.recommendation import engine
    from predictionio_tpu.workflow.core_workflow import run_train

    storage = get_storage()
    ctx = RuntimeContext.create(storage=storage)
    app_id = storage.get_apps().insert(App(id=None, name="papp"))
    storage.get_events().init(app_id)
    rng = np.random.default_rng(0)
    storage.get_events().insert_batch(
        [Event(event="rate", entity_type="user", entity_id=f"u{u}",
               target_entity_type="item", target_entity_id=f"i{i}",
               properties=DataMap({"rating": float(r)}))
         for u, i, r in zip(rng.integers(0, 20, 300),
                            rng.integers(0, 15, 300),
                            rng.integers(1, 6, 300))], app_id)
    variant = EngineVariant.from_dict({
        "engineFactory": "predictionio_tpu.templates.recommendation:engine",
        "datasource": {"params": {"appName": "papp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 4, "numIterations": 4}}],
    })
    monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(tmp_path / "ck"))
    monkeypatch.setenv("PIO_CHECKPOINT_EVERY", "1")

    real_loop = als_lib._train_loop

    def preempting_loop(*a, **k):
        request_preemption()
        return real_loop(*a, **k)

    monkeypatch.setattr(als_lib, "_train_loop", preempting_loop)
    with pytest.raises(TrainPreempted):
        run_train(engine(), variant, ctx)
    rows = storage.get_engine_instances().get_all()
    assert [r.status for r in rows] == ["PREEMPTED"]
    assert os.path.isdir(tmp_path / "ck" / "als")


# -- serving: staged reload / fail-closed / rollback -------------------------

def _trained_server(storage, n_events=400, breaker=None):
    from predictionio_tpu.controller import EngineVariant, RuntimeContext
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.server import EngineServer
    from predictionio_tpu.templates.recommendation import engine
    from predictionio_tpu.workflow.core_workflow import run_train

    ctx = RuntimeContext.create(storage=storage)
    app_id = storage.get_apps().insert(App(id=None, name="sapp"))
    storage.get_events().init(app_id)
    rng = np.random.default_rng(1)
    storage.get_events().insert_batch(
        [Event(event="rate", entity_type="user", entity_id=f"u{u}",
               target_entity_type="item", target_entity_id=f"i{i}",
               properties=DataMap({"rating": float(r)}))
         for u, i, r in zip(rng.integers(0, 30, n_events),
                            rng.integers(0, 20, n_events),
                            rng.integers(1, 6, n_events))], app_id)
    variant = EngineVariant.from_dict({
        "engineFactory": "predictionio_tpu.templates.recommendation:engine",
        "datasource": {"params": {"appName": "sapp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 4, "numIterations": 2}}],
    })
    eng = engine()
    iid = run_train(eng, variant, ctx)
    srv = EngineServer(eng, variant, storage, host="127.0.0.1", port=0,
                       breaker=breaker)
    return srv, eng, variant, ctx, iid


def test_reload_under_total_storage_outage_serves_last_good(pio_home):
    """ISSUE 4 acceptance: storage 100% faulted → reload fails closed,
    /queries.json answers from the last-good model with zero non-2xx,
    /ready stays 200, pio_model_reload_total{result="failed"} and the
    breaker transition are observable."""
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.obs import get_registry
    from predictionio_tpu.resilience.policy import CircuitBreaker
    from predictionio_tpu.data.storage import (
        StorageUnavailable,
    )

    breaker = CircuitBreaker(
        "modeldata", failure_threshold=2, recovery_time_s=60.0,
        failure_types=(StorageUnavailable, ConnectionError))
    srv, *_ = _trained_server(get_storage(), breaker=breaker)
    gen0 = srv._generation
    faults.install("storage.find:error:1.0")
    try:
        st, _body = srv.handle("POST", "/reload", b"")
        assert st == 503
        # predicts never touch storage: zero non-2xx during the outage
        for u in range(10):
            st, body = srv.handle(
                "POST", "/queries.json",
                json.dumps({"user": f"u{u}", "num": 3}).encode())
            assert st == 200 and "itemScores" in body
        st, body = srv.handle("GET", "/ready", b"")
        assert st == 200 and body["status"] == "ready"
        # second failure trips the threshold-2 breaker → open, and the
        # next reload sheds WITHOUT touching storage
        st, _ = srv.handle("POST", "/reload", b"")
        assert st == 503
        assert breaker.state == "open"
        st, _ = srv.handle("POST", "/reload", b"")
        assert st == 503
    finally:
        faults.clear()
    assert srv._generation == gen0, "failed reloads must not bump the gen"
    reg = get_registry()
    c = reg.counter("pio_model_reload_total", "", ("result",))
    assert c.value(result="failed") >= 2
    t = reg.counter("pio_breaker_transitions_total", "", ("breaker", "to"))
    assert t.value(breaker="modeldata", to="open") == 1
    st, body = srv.handle("GET", "/", b"")
    assert body["breaker"] == "open"
    assert body["lastReload"]["result"] == "failed"


def test_reload_mid_drive_under_storage_outage_answers_every_query(pio_home):
    """The outage above with clients on the wire: concurrent HTTP
    predicts keep their 200s while a ``POST /reload`` lands among them
    and fails closed, and the generation they are served from does not
    move."""
    import threading
    import urllib.error
    import urllib.request

    from predictionio_tpu.data.storage import get_storage

    srv, *_ = _trained_server(get_storage())
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    gen0 = srv._generation
    stop = threading.Event()
    statuses = []

    def post(path, payload):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(), method="POST",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status
        except urllib.error.HTTPError as e:
            return e.code

    def drive(i):
        k = i
        while not stop.is_set():
            statuses.append(post("/queries.json",
                                 {"user": f"u{k % 30}", "num": 3}))
            k += 1

    drivers = [threading.Thread(target=drive, args=(i,), daemon=True)
               for i in range(3)]
    faults.install("storage.find:error:1.0")
    try:
        for t in drivers:
            t.start()
        reloads = []
        for _ in range(3):
            seen = len(statuses)
            reloads.append(post("/reload", {}))
            while len(statuses) < seen + 6:  # predicts after each reload
                stop.wait(0.005)
    finally:
        stop.set()
        for t in drivers:
            t.join(timeout=30)
        faults.clear()
        srv.stop()
    assert reloads == [503, 503, 503]
    assert srv._generation == gen0
    assert len(statuses) >= 18 and set(statuses) == {200}, statuses


def test_reload_swaps_and_rollback_restores_previous_generation(pio_home):
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.workflow.core_workflow import run_train

    srv, eng, variant, ctx, iid1 = _trained_server(get_storage())
    iid2 = run_train(eng, variant, ctx)
    st, body = srv.handle("POST", "/reload", b"")
    assert st == 200 and body["engineInstanceId"] == iid2
    assert body["generation"] == 2
    st, body = srv.handle("POST", "/admin/rollback", b"")
    assert st == 200 and body["engineInstanceId"] == iid1
    assert body["generation"] == 3
    # rollback of the rollback returns to iid2
    st, body = srv.handle("POST", "/admin/rollback", b"")
    assert st == 200 and body["engineInstanceId"] == iid2
    # queries keep working on the rolled-to generation
    st, body = srv.handle("POST", "/queries.json",
                          json.dumps({"user": "u1", "num": 2}).encode())
    assert st == 200


def test_rollback_without_previous_generation_409s(pio_home):
    from predictionio_tpu.data.storage import get_storage

    srv, *_ = _trained_server(get_storage())
    st, body = srv.handle("POST", "/admin/rollback", b"")
    assert st == 409 and "roll back" in body["message"]


def test_canary_query_gates_reload(pio_home, monkeypatch):
    """A candidate that cannot answer the golden queries is rejected
    (409) and the last-good generation keeps serving."""
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.workflow.core_workflow import run_train

    srv, eng, variant, ctx, iid1 = _trained_server(get_storage())
    run_train(eng, variant, ctx)
    # a malformed canary (missing required "user" field) fails binding
    monkeypatch.setenv("PIO_CANARY_QUERIES",
                       json.dumps([{"nope": True}]))
    st, body = srv.handle("POST", "/reload", b"")
    assert st == 409 and "canary" in body["message"]
    assert srv._instance.id == iid1, "last-good must keep serving"
    # a valid canary passes
    monkeypatch.setenv("PIO_CANARY_QUERIES",
                       json.dumps([{"user": "u1", "num": 2}]))
    st, body = srv.handle("POST", "/reload", b"")
    assert st == 200


def test_finite_validation_rejects_nan_model(pio_home, monkeypatch):
    """A persisted model with NaN factors never reaches the swap."""
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.workflow import core_workflow
    from predictionio_tpu.workflow.core_workflow import run_train

    srv, eng, variant, ctx, iid1 = _trained_server(get_storage())
    run_train(eng, variant, ctx)

    real_load = core_workflow.load_models

    def poisoned_load(engine, instance, c=None):
        models = real_load(engine, instance, c)
        m = models[0]
        uf = np.asarray(m.model.user_factors).copy()
        uf[0, 0] = np.nan
        m.model.user_factors = uf
        return models

    # engine_server imported load_models by name — patch it there
    from predictionio_tpu.server import engine_server as es_mod

    monkeypatch.setattr(es_mod, "load_models", poisoned_load)
    st, body = srv.handle("POST", "/reload", b"")
    assert st == 409 and "non-finite" in body["message"]
    assert srv._instance.id == iid1


def test_status_page_reports_generation_and_reload(pio_home):
    from predictionio_tpu.data.storage import get_storage

    srv, *_ = _trained_server(get_storage())
    st, body = srv.handle("GET", "/", b"")
    assert st == 200
    assert body["modelGeneration"] == 1
    assert body["lastReload"]["result"] == "ok"
    assert body["rollbackAvailable"] is False
    assert body["breaker"] == "closed"


def test_pio_status_serving_snapshot_parses_metrics(capsys):
    from predictionio_tpu.cli.main import _print_serving_snapshot

    _print_serving_snapshot([
        "# HELP pio_model_generation gen",
        "pio_model_generation 4",
        'pio_model_reload_total{result="ok"} 3',
        'pio_model_reload_total{result="failed"} 2',
        'pio_breaker_state{breaker="modeldata"} 2',
        'pio_breaker_state{breaker="eventdata"} 0',
        'pio_watchdog_fired_total{fn="als"} 1',
    ])
    out = capsys.readouterr().out
    assert "model generation 4" in out
    assert "failed=2, ok=3" in out
    assert "breaker [modeldata]: open" in out
    assert "breaker [eventdata]: closed" in out
    assert "watchdog fired [als]: 1" in out
