"""ISSUE 9: per-request latency waterfall, SLO burn-rate engine wired to
/ready, and fleet-aggregated telemetry.

Three layers under test, all on injectable clocks (ZERO wall sleeps in
the SLO/overload paths — acceptance requirement):

- **waterfall** (obs.waterfall + metrics exemplars): per-stage stamps
  ride the ``Pending`` hand-off across the handler/batcher threads, land
  in ``pio_serve_stage_ms{stage}`` with exemplar trace ids, and the
  stage sum reconciles with the server-attested ``X-PIO-Server-Ms``.
- **SLO engine** (obs.slo): multi-window burn rates over the process
  registry, the saturation+burn trip, asymmetric hysteresis, and the
  live ``/ready`` 503 flip.
- **fleet** (obs.fleet): type-correct multi-instance merge (counters
  sum, histogram buckets add, gauges keep an ``instance`` label),
  counter-reset survival, dead-instance staleness, and the dashboard's
  ``/fleet.json`` aggregating two LIVE engine servers.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.obs import get_registry
from predictionio_tpu.obs.fleet import (
    CounterResetTracker,
    FleetAggregator,
    histogram_quantile,
    merge_histogram_buckets,
    merge_samples,
    parse_exposition,
)
from predictionio_tpu.obs.slo import SLOConfig, SLOEngine
from predictionio_tpu.obs.waterfall import (
    Waterfall,
    begin_request,
    current_waterfall,
    dispatch_sink,
    record_stage,
)


# --------------------------------------------------------------------------
# Metrics additions: exemplars + count_le (the SLO's "good events" read)
# --------------------------------------------------------------------------

class TestHistogramAdditions:
    def test_exemplar_stored_and_rendered_openmetrics_style(self, pio_home):
        h = get_registry().histogram("pio_x_ms", "h", ("stage",))
        h.observe(3.0, exemplar="abc123", stage="bind")
        h.observe(4.0, stage="bind")  # no exemplar: previous one survives
        ex = h.exemplars(stage="bind")
        assert ex[5] == ("abc123", 3.0)  # the le=5 bucket holds 2.5<v<=5
        text = get_registry().render(exemplars=True)
        line = next(l for l in text.splitlines()
                    if l.startswith('pio_x_ms_bucket{stage="bind",le="5"'))
        assert '# {trace_id="abc123"} 3' in line
        # the DEFAULT exposition stays classic-0.0.4 clean — a strict
        # Prometheus scraper rejects exemplar suffixes wholesale
        assert "# {" not in get_registry().render()
        # downstream parsers must tolerate the suffix
        types, samples = parse_exposition(text)
        assert ("pio_x_ms_bucket", {"stage": "bind", "le": "5"}, 2.0) \
            in samples

    def test_count_le_interpolates_and_undercounts_inf(self, pio_home):
        h = get_registry().histogram("pio_y_ms", "h",
                                     buckets=(10.0, 100.0))
        for v in (5.0, 50.0, 99.0, 5000.0):
            h.observe(v)
        # at a bucket bound: everything in buckets up to it
        assert h.count_le(100.0) == pytest.approx(3.0)
        assert h.count_le(10.0) == pytest.approx(1.0)
        # inside (10,100]: 1 + interpolated share of that bucket's 2 obs
        assert h.count_le(55.0) == pytest.approx(1 + 2 * 0.5)
        # past the top finite bound: +Inf observations count as NOT good
        assert h.count_le(9999.0) == pytest.approx(3.0)
        assert h.count_le(0.0, ) == 0.0


# --------------------------------------------------------------------------
# Waterfall collector
# --------------------------------------------------------------------------

class TestWaterfall:
    def test_stamps_accumulate_and_merge(self, pio_home):
        wf = Waterfall()
        wf.stamp("dispatch", 5.0)
        wf.stamp("dispatch", 2.0, batchSize=4)   # retry bills both
        sink = Waterfall()
        with dispatch_sink(sink):
            record_stage("retrieval", 3.0, rung="host")
        stages, attrs = sink.export()
        wf.merge(stages, **attrs)
        snap = wf.snapshot()
        assert snap["dispatch"] == pytest.approx(7.0)
        assert snap["retrieval"] == pytest.approx(3.0)
        assert wf.attrs["rung"] == "host"

    def test_record_stage_prefers_sink_then_request_then_noop(self,
                                                             pio_home):
        record_stage("bind", 1.0)  # no collector anywhere: no crash
        with begin_request() as wf:
            record_stage("bind", 1.0)
            sink = Waterfall()
            with dispatch_sink(sink):
                record_stage("retrieval", 2.0)
            record_stage("serialize", 3.0)
        assert current_waterfall() is None
        assert wf.snapshot() == {"bind": 1.0, "serialize": 3.0}
        assert sink.snapshot() == {"retrieval": 2.0}

    def test_finalize_publishes_once_then_drops_late_stamps(
            self, pio_home, tmp_path, monkeypatch):
        log = tmp_path / "req.jsonl"
        monkeypatch.setenv("PIO_REQUEST_LOG", str(log))
        wf = Waterfall()
        for s, ms in (("queue_wait", 1.0), ("batch_wait", 2.0),
                      ("bind", 0.5), ("dispatch", 10.0),
                      ("retrieval", 6.0), ("serialize", 1.0),
                      ("shed_check", 0.1)):
            wf.stamp(s, ms)
        doc = wf.finalize(trace_id="t1", status=200, total_ms=15.0,
                          attested_ms=13.7)
        assert doc["stages"]["dispatch"] == 10.0
        # retrieval ⊂ dispatch: excluded from the reconciliation sum
        assert doc["stageSumMs"] == pytest.approx(14.6)
        # serialize lies outside the attested wall by construction
        assert doc["attestedSumMs"] == pytest.approx(13.6)
        assert doc["serverMs"] == 13.7
        # close-once: a walked waiter / double-finalize publishes nothing
        wf.stamp("dispatch", 99.0)
        assert wf.finalize(trace_id="t1", status=200, total_ms=15.0) == {}
        hist = get_registry().get("pio_serve_stage_ms")
        assert hist.count(stage="dispatch") == 1
        assert hist.exemplars(stage="dispatch")[10] == ("t1", 10.0)
        rows = [json.loads(l) for l in log.read_text().splitlines()]
        assert len(rows) == 1 and rows[0]["traceId"] == "t1"
        assert rows[0]["stages"]["retrieval"] == 6.0


# --------------------------------------------------------------------------
# SLO engine (fake clock; no wall sleeps anywhere)
# --------------------------------------------------------------------------

class _Tick:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _instruments():
    reg = get_registry()
    return (reg.counter("pio_query_requests_total",
                        "Predict requests served."),
            reg.counter("pio_query_errors_total",
                        "Predict requests that failed."),
            reg.histogram("pio_query_latency_ms",
                          "Predict request latency."))


def _engine(clock, saturation=None, **cfg):
    defaults = dict(fast_window_s=300.0, slow_window_s=3600.0,
                    burn_threshold=14.4, min_requests=10,
                    recovery_s=60.0, latency_target_ms=100.0)
    defaults.update(cfg)
    return SLOEngine(SLOConfig(**defaults), clock=clock,
                     saturation_fn=saturation)


def _traffic(req, err, lat, n_good=0, n_bad=0, slow_ms=None):
    req.inc(n_good + n_bad)
    err.inc(n_bad)
    for _ in range(n_good):
        lat.observe(slow_ms if slow_ms is not None else 5.0)


class TestSLOEngine:
    def test_healthy_traffic_never_burns(self, pio_home):
        req, err, lat = _instruments()
        clock = _Tick()
        slo = _engine(clock)
        for _ in range(10):
            _traffic(req, err, lat, n_good=50)
            clock.t += 60
            state = slo.tick(force=True)
        assert state["degraded"] is False
        assert state["burn"]["fast"]["availability"] == 0.0
        assert state["burn"]["fast"]["latency"] == 0.0
        ok, _ = slo.ready()
        assert ok

    def test_fast_spike_alone_does_not_trip(self, pio_home):
        """A single error burst burns the fast window hot while an hour
        of good history keeps the slow window cold — no flip (the
        classic multi-window guard against paging on blips)."""
        req, err, lat = _instruments()
        clock = _Tick()
        slo = _engine(clock)
        for _ in range(60):                      # 1h of clean traffic
            _traffic(req, err, lat, n_good=100)
            clock.t += 60
            slo.tick(force=True)
        _traffic(req, err, lat, n_bad=50)        # 100%-error blip
        clock.t += 30
        state = slo.tick(force=True)
        assert state["burn"]["fast"]["availability"] > 14.4
        assert state["burn"]["slow"]["availability"] < 14.4
        assert state["degraded"] is False

    def test_sustained_burn_trips_then_recovers_with_hysteresis(
            self, pio_home):
        req, err, lat = _instruments()
        clock = _Tick()
        slo = _engine(clock)
        state = None
        for _ in range(70):                      # >1h of 20% errors
            _traffic(req, err, lat, n_good=80, n_bad=20)
            clock.t += 60
            state = slo.tick(force=True)
        assert state["degraded"] is True
        assert "sustained_burn" in state["tripReasons"]
        ok, _ = slo.ready()
        assert not ok
        # errors stop; burn decays as the windows slide past the bad era
        recovered_at = None
        for minute in range(90):
            _traffic(req, err, lat, n_good=100)
            clock.t += 60
            state = slo.tick(force=True)
            if not state["degraded"]:
                recovered_at = minute
                break
        assert recovered_at is not None, "never recovered"
        # hysteresis: clearing needed the trip condition false for a
        # recovery_s dwell, not just one good tick
        assert recovered_at >= 1

    def test_flap_resets_the_recovery_dwell(self, pio_home):
        req, err, lat = _instruments()
        clock = _Tick()
        slo = _engine(clock, fast_window_s=60.0, slow_window_s=120.0,
                      recovery_s=300.0)
        for _ in range(5):
            _traffic(req, err, lat, n_good=10, n_bad=90)
            clock.t += 30
            slo.tick(force=True)
        assert slo.tick(force=True)["degraded"] is True
        # burn clears (windows slide past the errors)...
        clock.t += 150
        _traffic(req, err, lat, n_good=200)
        state = slo.tick(force=True)
        assert state["degraded"] is True          # dwell started, not done
        assert state["recoveringForS"] is not None
        # ...but a fresh burst inside the dwell resets it
        _traffic(req, err, lat, n_good=10, n_bad=90)
        clock.t += 10
        state = slo.tick(force=True)
        assert state["recoveringForS"] is None
        # finally: quiet for the whole dwell → ready again
        for _ in range(16):
            clock.t += 30
            _traffic(req, err, lat, n_good=50)
            slo.tick(force=True)
        assert slo.tick(force=True)["degraded"] is False

    def test_latency_burn_uses_target_threshold(self, pio_home):
        req, err, lat = _instruments()
        clock = _Tick()
        slo = _engine(clock, latency_objective=0.99,
                      latency_target_ms=100.0,
                      fast_window_s=60.0, slow_window_s=120.0)
        for _ in range(6):   # every request answers, but SLOW (500ms)
            _traffic(req, err, lat, n_good=50, slow_ms=500.0)
            clock.t += 30
            state = slo.tick(force=True)
        assert state["burn"]["fast"]["latency"] > 14.4
        assert state["burn"]["fast"]["availability"] == 0.0
        assert state["degraded"] is True

    def test_min_requests_floor_prevents_flapping(self, pio_home):
        req, err, lat = _instruments()
        clock = _Tick()
        slo = _engine(clock, min_requests=10)
        _traffic(req, err, lat, n_bad=3)  # 100% errors... of 3 requests
        clock.t += 30
        assert slo.tick(force=True)["degraded"] is False

    def test_saturation_plus_fast_burn_trips_without_slow_window(
            self, pio_home):
        """The ROADMAP rung: persistent-floor saturation supplies the
        "it's sustained" evidence, so a fast-window burn ≥1 flips /ready
        immediately instead of waiting for the slow window to heat."""
        req, err, lat = _instruments()
        clock = _Tick()
        saturated = {"v": False}
        slo = _engine(clock, saturation=lambda: saturated["v"])
        for _ in range(60):                  # 1h of clean history keeps
            _traffic(req, err, lat, n_good=100)   # the slow window cold
            clock.t += 60
            slo.tick(force=True)
        _traffic(req, err, lat, n_good=80, n_bad=20)   # fast burn hot
        clock.t += 30
        state = slo.tick(force=True)
        assert state["burn"]["fast"]["availability"] > 1.0
        assert state["degraded"] is False              # burn alone: no
        saturated["v"] = True
        _traffic(req, err, lat, n_good=80, n_bad=20)
        clock.t += 30
        state = slo.tick(force=True)
        assert state["degraded"] is True
        assert state["tripReasons"] == ["saturation_with_burn"]
        assert state["saturated"] is True

    def test_saturation_alone_with_slo_met_stays_ready(self, pio_home):
        req, err, lat = _instruments()
        clock = _Tick()
        slo = _engine(clock, saturation=lambda: True)
        _traffic(req, err, lat, n_good=100)
        clock.t += 30
        state = slo.tick(force=True)
        assert state["saturated"] is True
        assert state["degraded"] is False   # the batcher is coping

    def test_ready_slo_off_escape_hatch_reports_but_never_flips(
            self, pio_home):
        req, err, lat = _instruments()
        clock = _Tick()
        slo = _engine(clock, ready_slo=False,
                      saturation=lambda: True)
        slo.tick(force=True)          # baseline snapshot at t=0
        _traffic(req, err, lat, n_good=10, n_bad=90)
        clock.t += 30
        ok, state = slo.ready()
        assert state["degraded"] is True    # the signal still reports
        assert ok is True                   # ...but /ready ignores it
        assert get_registry().get("pio_slo_degraded").value() == 1

    def test_gauges_exported(self, pio_home):
        req, err, lat = _instruments()
        clock = _Tick()
        slo = _engine(clock)
        slo.tick(force=True)          # baseline snapshot at t=0
        _traffic(req, err, lat, n_good=50, n_bad=50)
        clock.t += 30
        slo.tick(force=True)
        reg = get_registry()
        assert reg.get("pio_slo_burn_rate").value(
            slo="availability", window="fast") > 0
        assert reg.get("pio_slo_objective").value(
            slo="availability") == pytest.approx(0.999)
        assert reg.get("pio_slo_latency_target_ms").value() == 100.0

    def test_tick_coalescing_bounds_the_snapshot_ring(self, pio_home):
        _instruments()
        clock = _Tick()
        slo = _engine(clock)
        for _ in range(50):                 # an LB polling at 10 Hz
            clock.t += 0.1
            slo.tick()
        assert len(slo._snaps) <= 6         # ~1 real tick per second

    def test_config_from_env(self, pio_home, monkeypatch):
        monkeypatch.setenv("PIO_BATCH_P99_TARGET_MS", "250")
        monkeypatch.setenv("PIO_SLO_BURN_THRESHOLD", "6")
        monkeypatch.setenv("PIO_READY_SLO", "off")
        cfg = SLOConfig.from_env()
        assert cfg.latency_target_ms == 250.0   # defaults from the
        assert cfg.burn_threshold == 6.0        # autotuner's target
        assert cfg.ready_slo is False
        monkeypatch.setenv("PIO_SLO_LATENCY_TARGET_MS", "80")
        assert SLOConfig.from_env().latency_target_ms == 80.0


class TestSaturationDetector:
    def _floor_pair(self):
        from predictionio_tpu.serving import WindowAutotuner

        class _B:
            window_s = 0.0
            window_min_s = 0.0
            max_size = 8
            _est_dispatch_s = 0.003   # fast dispatch: over-target p99
                                      # means backlog, i.e. load>capacity

            def set_knobs(self, **kw):
                for k, v in kw.items():
                    setattr(self, k, v)

        return _B(), WindowAutotuner("m", 100.0, saturation_streak=3)

    def test_floor_streak_reports_saturated(self, pio_home):
        b, tuner = self._floor_pair()
        for i in range(3):
            assert tuner.saturated() is False, f"tripped at {i}"
            tuner.retune(b, p99_ms=400.0)
        assert tuner.saturated() is True
        assert get_registry().get("pio_batch_saturated").value(
            model="m") == 1

    def test_any_other_action_clears_the_streak(self, pio_home):
        b, tuner = self._floor_pair()
        for _ in range(3):
            tuner.retune(b, p99_ms=400.0)
        assert tuner.saturated() is True
        tuner.retune(b, p99_ms=80.0)      # hold: capacity returned
        assert tuner.saturated() is False
        assert get_registry().get("pio_batch_saturated").value(
            model="m") == 0


# --------------------------------------------------------------------------
# /traces.json filters (exemplar-link resolver)
# --------------------------------------------------------------------------

class TestShedAttribution:
    """Every batcher finish path stamps queue_wait/batch_wait — a 504's
    wall must read as queueing (scale out), never leak into the waiter's
    resume residual (GIL contention): the attribution verdict matters
    most under exactly that overload."""

    def _batcher(self, dispatch_fn, clock):
        from predictionio_tpu.serving.batcher import MicroBatcher
        from predictionio_tpu.serving.queue import ModelQueue
        q = ModelQueue("m", 4)
        return MicroBatcher("m", q, dispatch_fn, clock=clock)

    def test_queue_expired_504_bills_waits_not_resume(self, pio_home):
        from predictionio_tpu.serving.queue import Pending

        class Clock:
            t = 1.0

            def now(self):
                return self.t

        b = self._batcher(lambda qs: ([0] * len(qs), 1), Clock())
        wf = Waterfall()
        dead = Pending("dead", 0.0, deadline_s=0.5, waterfall=wf)
        dead.gathered_s = 0.2
        b.dispatch([dead])
        stages = wf.snapshot()
        assert stages["queue_wait"] == pytest.approx(200.0)
        assert stages["batch_wait"] == pytest.approx(800.0)
        assert "resume" not in stages
        assert "dispatch" not in stages  # no device work happened

    def test_failed_batch_bills_waits_and_dispatch(self, pio_home):
        from predictionio_tpu.serving.queue import Pending

        class Clock:
            t = 1.0

            def now(self):
                Clock.t += 0.010
                return Clock.t

        def boom(qs):
            raise RuntimeError("dead backend")

        b = self._batcher(boom, Clock())
        wf = Waterfall()
        p = Pending("q", 0.5, deadline_s=None, waterfall=wf)
        b.dispatch([p])
        assert isinstance(p.error, RuntimeError)
        stages = wf.snapshot()
        # the waits and the FAILED attempt's wall are both attributed
        assert stages["queue_wait"] > 0
        assert "batch_wait" in stages
        assert stages["dispatch"] > 0


class TestTraceFilters:
    def _ring(self):
        from predictionio_tpu.obs import get_recorder
        from predictionio_tpu.obs.trace import trace

        ids = []
        for i in range(5):
            with trace("req", trace_id=f"{i:032x}") as root:
                root.set(i=i)
            ids.append(f"{i:032x}")
        return get_recorder(), ids

    def test_request_id_resolves_one_trace(self, pio_home):
        rec, ids = self._ring()
        out = rec.recent(50, request_id=ids[2])
        assert len(out) == 1 and out[0]["traceId"] == ids[2]
        assert rec.recent(50, request_id="f" * 32) == []

    def test_min_ms_and_limit(self, pio_home):
        rec, ids = self._ring()
        assert len(rec.recent(2)) == 2
        assert rec.recent(50, min_ms=1e9) == []
        assert len(rec.recent(50, min_ms=0.0)) == 5

    def test_http_params_view(self, pio_home):
        from predictionio_tpu.server.http import traces_payload

        _, ids = self._ring()
        doc = traces_payload({"request_id": [ids[1]]})
        assert [t["traceId"] for t in doc["traces"]] == [ids[1]]
        doc = traces_payload({"limit": ["3"]})
        assert len(doc["traces"]) == 3
        # junk params degrade to defaults, never 500
        doc = traces_payload({"limit": ["x"], "min_ms": ["y"],
                              "request_id": ["../etc"]})
        assert len(doc["traces"]) <= 50


# --------------------------------------------------------------------------
# Fleet merge (unit)
# --------------------------------------------------------------------------

def _expo(counter=0.0, gen=1.0, buckets=(1, 2, 3)):
    b1, b2, b3 = buckets
    return (
        "# TYPE pio_query_requests_total counter\n"
        f"pio_query_requests_total {counter}\n"
        "# TYPE pio_model_generation gauge\n"
        f"pio_model_generation {gen}\n"
        "# TYPE pio_query_latency_ms histogram\n"
        f'pio_query_latency_ms_bucket{{le="10"}} {b1}\n'
        f'pio_query_latency_ms_bucket{{le="100"}} {b2}\n'
        f'pio_query_latency_ms_bucket{{le="+Inf"}} {b3}\n'
        f"pio_query_latency_ms_sum {b3 * 5.0}\n"
        f"pio_query_latency_ms_count {b3}\n")


class TestFleetMerge:
    def test_parse_tolerates_exemplars_and_junk(self, pio_home):
        text = ('# TYPE pio_a_ms histogram\n'
                'pio_a_ms_bucket{le="5"} 2 # {trace_id="abc"} 3.0\n'
                'garbage !!! line\n'
                '{not even a name} 4\n'
                'pio_a_ms_count 2\n')
        types, samples = parse_exposition(text)
        assert types == {"pio_a_ms": "histogram"}
        assert ("pio_a_ms_bucket", {"le": "5"}, 2.0) in samples
        assert ("pio_a_ms_count", {}, 2.0) in samples

    def test_counters_sum_and_gauges_keep_instance_label(self, pio_home):
        merged = merge_samples({
            "http://a": parse_exposition(_expo(counter=10, gen=3)),
            "http://b": parse_exposition(_expo(counter=32, gen=7)),
        })
        assert merged["counters"]["pio_query_requests_total"] == 42.0
        # gauges never sum — and the two instances never collide
        assert merged["gauges"][
            'pio_model_generation{instance="http://a"}'] == 3.0
        assert merged["gauges"][
            'pio_model_generation{instance="http://b"}'] == 7.0
        assert "pio_model_generation" not in merged["counters"]

    def test_histogram_buckets_add_and_quantile_reads_merged(self,
                                                             pio_home):
        merged = merge_samples({
            "a": parse_exposition(_expo(buckets=(1, 2, 4))),
            "b": parse_exposition(_expo(buckets=(0, 6, 8))),
        })
        series = merged["histograms"]["pio_query_latency_ms"]
        row = series["pio_query_latency_ms"]
        assert row["buckets"] == {"10": 1.0, "100": 8.0, "+Inf": 12.0}
        assert row["count"] == 12.0
        q50 = histogram_quantile(row["buckets"], 0.5)
        assert 10.0 < q50 <= 100.0

    def test_bucket_merge_is_associative_and_sum_preserving(self,
                                                            pio_home):
        rng = np.random.default_rng(9)
        parts = [{le: float(rng.integers(0, 100))
                  for le in ("10", "100", "+Inf")} for _ in range(3)]
        a, b, c = parts
        left = merge_histogram_buckets(
            [merge_histogram_buckets([a, b]), c])
        right = merge_histogram_buckets(
            [a, merge_histogram_buckets([b, c])])
        flat = merge_histogram_buckets(parts)
        assert left == right == flat
        for le in ("10", "100", "+Inf"):
            assert flat[le] == a[le] + b[le] + c[le]

    def test_counter_sums_survive_an_instance_restart(self, pio_home):
        """Reset detection: instance b restarts (its raw series drops to
        near zero); the fleet sum must keep the pre-restart total as an
        offset instead of going backwards."""
        tracker = CounterResetTracker()
        m1 = merge_samples({"a": parse_exposition(_expo(counter=100)),
                            "b": parse_exposition(_expo(counter=50))},
                           tracker)
        assert m1["counters"]["pio_query_requests_total"] == 150.0
        # b restarts and serves 7 new requests: raw 50 → 7
        m2 = merge_samples({"a": parse_exposition(_expo(counter=110)),
                            "b": parse_exposition(_expo(counter=7))},
                           tracker)
        assert m2["counters"]["pio_query_requests_total"] == 167.0
        # monotonic from then on
        m3 = merge_samples({"a": parse_exposition(_expo(counter=110)),
                            "b": parse_exposition(_expo(counter=9))},
                           tracker)
        assert m3["counters"]["pio_query_requests_total"] == 169.0

    def test_dead_instance_degrades_to_marked_stale_entry(self, pio_home):
        calls = {"n": 0}

        def fetch(url):
            if url.startswith("http://dead"):
                raise OSError("connection refused")
            calls["n"] += 1
            if url.endswith("/metrics"):
                return _expo(counter=5)
            raise OSError("no stats here")   # stats/timeline optional

        agg = FleetAggregator(["http://live:1", "http://dead:2"],
                              fetch=fetch, clock=_Tick(100.0))
        doc = agg.scrape()
        rows = {r["instance"]: r for r in doc["instances"]}
        assert rows["http://live:1"]["stale"] is False
        assert rows["http://dead:2"]["stale"] is True
        assert "error" in rows["http://dead:2"]
        assert doc["merged"]["counters"][
            "pio_query_requests_total"] == 5.0

    def test_dead_instance_keeps_contributing_last_known_counters(
            self, pio_home):
        """A scrape failure must not make fleet sums dip: the dead
        instance's last-good counters stay in the merge, marked stale."""
        alive = {"v": True}

        def fetch(url):
            if url.startswith("http://b") and not alive["v"]:
                raise OSError("down")
            n = 50 if url.startswith("http://b") else 100
            if url.endswith("/metrics"):
                return _expo(counter=n)
            raise OSError("optional")

        agg = FleetAggregator(["http://a", "http://b"], fetch=fetch,
                              clock=_Tick(0.0))
        assert agg.scrape()["merged"]["counters"][
            "pio_query_requests_total"] == 150.0
        alive["v"] = False
        doc = agg.scrape()
        assert doc["merged"]["counters"][
            "pio_query_requests_total"] == 150.0   # no dip
        rows = {r["instance"]: r for r in doc["instances"]}
        assert rows["http://b"]["stale"] is True


# --------------------------------------------------------------------------
# End-to-end over live servers
# --------------------------------------------------------------------------

@pytest.fixture()
def trained(pio_home):
    """A small trained ALS engine + storage (same substrate as
    test_serving_scheduler's HTTP integration tests)."""
    from predictionio_tpu.controller import EngineVariant, RuntimeContext
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage import App, get_storage
    from predictionio_tpu.templates.recommendation import engine
    from predictionio_tpu.workflow.core_workflow import run_train

    storage = get_storage()
    ctx = RuntimeContext.create(storage=storage)
    app_id = storage.get_apps().insert(App(id=None, name="sloapp"))
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
        "datasource": {"params": {"appName": "sloapp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 4, "numIterations": 3}}],
    })
    eng = engine()
    run_train(eng, variant, ctx)
    return eng, variant, storage, ctx


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def _read_rows(log, n, timeout_s=10.0):
    """Wide-event rows, polled until ``n`` arrive: the JSONL line lands
    AFTER the response bytes reach the client (the serialize stage wraps
    the respond write), so a client that just got its 200 may race the
    server thread's finalize by a few ms."""
    import time as _time

    deadline = _time.monotonic() + timeout_s
    while _time.monotonic() < deadline:
        if log.exists():
            rows = []
            for line in log.read_text().splitlines():
                try:
                    rows.append(json.loads(line))
                except json.JSONDecodeError:
                    pass   # torn tail mid-write: next poll sees it whole
            if len(rows) >= n:
                return rows
        _time.sleep(0.01)
    raise AssertionError(f"request log never reached {n} rows")


def _post_query(port, user="u0"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json",
        data=json.dumps({"user": user, "num": 2}).encode(),
        method="POST", headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, dict(resp.headers), json.loads(resp.read())


class TestWaterfallEndToEnd:
    def test_stages_reconcile_with_server_attested_total(
            self, trained, tmp_path, monkeypatch):
        """Acceptance pin: every stage lands on a live /queries.json
        request; the wide-event stage sum reconciles with the server's
        own X-PIO-Server-Ms within 10% at p50; the bucket exemplar
        resolves to ONE trace via /traces.json?request_id=."""
        from predictionio_tpu.server import EngineServer

        log = tmp_path / "requests.jsonl"
        monkeypatch.setenv("PIO_REQUEST_LOG", str(log))
        # This pin is about the DISPATCH-path decomposition (queue_wait/
        # batch_wait/dispatch on every row): repeated users would hit the
        # result cache and legitimately skip those stages, so bypass it.
        monkeypatch.setenv("PIO_RESULT_CACHE", "0")
        eng, variant, storage, _ = trained
        srv = EngineServer(eng, variant, storage, host="127.0.0.1",
                           port=0)
        srv.start()
        try:
            server_ms = {}   # traceId -> X-PIO-Server-Ms
            lock = threading.Lock()

            def one(i):
                s, headers, _body = _post_query(srv.port, f"u{i % 8}")
                assert s == 200
                with lock:
                    server_ms[headers["X-Request-ID"]] = \
                        float(headers["X-PIO-Server-Ms"])

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            rows = _read_rows(log, 16)
            assert len(rows) == 16
            # every request carries the full decomposition
            for doc in rows:
                for stage in ("queue_wait", "batch_wait", "bind",
                              "dispatch", "serialize", "shed_check"):
                    assert stage in doc["stages"], doc
                assert "retrieval" in doc["stages"]   # rung-tagged
                assert doc.get("rung")
            # per-request reconciliation at p50 (acceptance: within 10%)
            # — the attested-stage sum vs the SAME X-PIO-Server-Ms
            # reading, which each wide event records as serverMs (pinned
            # here to equal the header the client saw).
            for doc in rows:
                assert doc["serverMs"] == pytest.approx(
                    server_ms[doc["traceId"]], abs=0.06)
            ratios = sorted(
                doc["attestedSumMs"] / doc["serverMs"] for doc in rows)
            assert len(ratios) == 16
            p50 = ratios[len(ratios) // 2]
            assert 0.9 <= p50 <= 1.1, f"stage sum vs server wall: {p50}"
            # the histogram family is live on /metrics; the exemplar
            # suffixes ride only the opt-in view (classic scrapers choke)
            _, _, body = _get(f"http://127.0.0.1:{srv.port}/metrics")
            text = body.decode()
            assert 'pio_serve_stage_ms_bucket{stage="dispatch"' in text
            assert 'trace_id="' not in text
            _, _, body = _get(f"http://127.0.0.1:{srv.port}"
                              f"/metrics?exemplars=1")
            assert 'trace_id="' in body.decode()
            # ...and an exemplar id resolves to exactly one trace
            hist = get_registry().get("pio_serve_stage_ms")
            ex = hist.exemplars(stage="dispatch")
            assert ex, "dispatch bucket carries no exemplar"
            tid = next(iter(ex.values()))[0]
            _, _, body = _get(f"http://127.0.0.1:{srv.port}"
                              f"/traces.json?request_id={tid}")
            traces = json.loads(body)["traces"]
            assert len(traces) == 1
            assert traces[0]["traceId"] == tid
            # the waterfall event rides the request's own span tree
            assert '"waterfall"' in json.dumps(traces[0])
        finally:
            srv.stop()

    def test_unbatched_inline_path_still_stamps_stages(
            self, trained, tmp_path, monkeypatch):
        from predictionio_tpu.server import EngineServer
        from predictionio_tpu.serving import SchedulerConfig

        log = tmp_path / "requests.jsonl"
        monkeypatch.setenv("PIO_REQUEST_LOG", str(log))
        eng, variant, storage, _ = trained
        srv = EngineServer(eng, variant, storage, host="127.0.0.1",
                           port=0,
                           scheduler_config=SchedulerConfig.from_env(
                               enabled=False))
        srv.start()
        try:
            s, _, _ = _post_query(srv.port)
            assert s == 200
            doc = _read_rows(log, 1)[0]
            assert doc["stages"]["dispatch"] > 0
            assert "bind" in doc["stages"]
        finally:
            srv.stop()


class TestReadySLOFlip:
    def _server_with_fake_clock_slo(self, trained, **cfg):
        from predictionio_tpu.server import EngineServer

        eng, variant, storage, _ = trained
        srv = EngineServer(eng, variant, storage, host="127.0.0.1",
                           port=0)
        clock = _Tick()
        saturated = {"v": False}
        defaults = dict(fast_window_s=300.0, slow_window_s=3600.0,
                        min_requests=10, recovery_s=60.0)
        defaults.update(cfg)
        srv.slo = SLOEngine(SLOConfig(**defaults),
                            clock=clock,
                            saturation_fn=lambda: saturated["v"])
        return srv, clock, saturated

    def test_overload_flips_ready_503_and_recovers_with_hysteresis(
            self, trained):
        """Acceptance pin: synthetic overload (autotuner pinned at floor
        + fast burn over threshold) flips /ready to 503; healing holds
        through the recovery dwell before 200 returns.  Fake clock, no
        wall sleeps."""
        srv, clock, saturated = self._server_with_fake_clock_slo(trained)
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            req, err, lat = _instruments()
            _traffic(req, err, lat, n_good=100)
            clock.t += 30
            s, _, body = _get(f"{base}/ready")
            assert s == 200
            assert json.loads(body)["status"] == "ready"
            # synthetic overload: saturation + queue sheds burning the
            # availability SLO in the fast window
            saturated["v"] = True
            _traffic(req, err, lat, n_good=50, n_bad=50)
            clock.t += 30
            s, _, body = _get(f"{base}/ready")
            doc = json.loads(body)
            assert s == 503
            assert doc["status"] == "degraded"
            assert "saturation_with_burn" in doc["slo"]["tripReasons"]
            assert doc["slo"]["saturated"] is True
            # overload clears: burn still in-window keeps it degraded
            saturated["v"] = False
            clock.t += 300          # errors slide out of the fast window
            _traffic(req, err, lat, n_good=200)
            clock.t += 30
            s, _, body = _get(f"{base}/ready")
            assert s == 503         # hysteresis dwell running
            assert json.loads(body)["slo"]["recoveringForS"] is not None
            clock.t += 61           # dwell (60s) elapses, still healthy
            s, _, body = _get(f"{base}/ready")
            assert s == 200
            assert json.loads(body)["status"] == "ready"
            # the /stats.json + status page carry the same state doc
            _, _, body = _get(f"{base}/stats.json")
            assert json.loads(body)["slo"]["degraded"] is False
        finally:
            srv.stop()

    def test_escape_hatch_keeps_ready_200_while_reporting(self, trained):
        srv, clock, saturated = self._server_with_fake_clock_slo(
            trained, ready_slo=False)
        srv.start()
        try:
            req, err, lat = _instruments()
            _get(f"http://127.0.0.1:{srv.port}/ready")  # baseline tick
            saturated["v"] = True
            _traffic(req, err, lat, n_good=10, n_bad=90)
            clock.t += 30
            s, _, body = _get(f"http://127.0.0.1:{srv.port}/ready")
            doc = json.loads(body)
            assert s == 200                       # hatch holds it in
            assert doc["slo"]["degraded"] is True  # signal still honest
        finally:
            srv.stop()


class TestFleetEndToEnd:
    def test_fleet_json_aggregates_two_live_instances(self, trained):
        """Acceptance pin: /fleet.json merges ≥2 live instances —
        merged counters equal the per-instance sums, per-instance SLO
        state is visible, and a stopped instance degrades to a marked
        stale row (its counters still contributing)."""
        from predictionio_tpu.server import EngineServer
        from predictionio_tpu.server.dashboard import DashboardServer

        eng, variant, storage, _ = trained
        srv1 = EngineServer(eng, variant, storage, host="127.0.0.1",
                            port=0)
        srv2 = EngineServer(eng, variant, storage, host="127.0.0.1",
                            port=0)
        srv1.start()
        srv2.start()
        dash = DashboardServer(
            storage=storage, host="127.0.0.1", port=0,
            fleet=[f"http://127.0.0.1:{srv1.port}",
                   f"http://127.0.0.1:{srv2.port}"])
        dash.start(block=False)
        try:
            for port, n in ((srv1.port, 3), (srv2.port, 2)):
                for i in range(n):
                    assert _post_query(port, f"u{i}")[0] == 200
            # ground truth: each instance's own exposition
            per_instance = []
            for srv in (srv1, srv2):
                _, _, body = _get(
                    f"http://127.0.0.1:{srv.port}/metrics")
                _, samples = parse_exposition(body.decode())
                per_instance.append(sum(
                    v for name, labels, v in samples
                    if name == "pio_query_requests_total"))
            s, _, body = _get(
                f"http://127.0.0.1:{dash.port}/fleet.json")
            assert s == 200
            doc = json.loads(body)
            assert len(doc["instances"]) == 2
            for row in doc["instances"]:
                assert row["stale"] is False
                assert "slo" in row       # per-instance SLO state
                assert "degraded" in row["slo"]
            assert doc["merged"]["counters"][
                "pio_query_requests_total"] == sum(per_instance)
            # per-instance gauges never collide
            gen_keys = [k for k in doc["merged"]["gauges"]
                        if k.startswith("pio_model_generation{")]
            assert len(gen_keys) == 2
            # merged latency histogram carries fleet quantiles
            q = doc["merged"]["histogramQuantiles"][
                "pio_query_latency_ms"]["pio_query_latency_ms"]
            # NOTE: both live instances share this test process's ONE
            # metrics registry, so each reports the same totals; the
            # aggregator's contract (merged == sum of what each
            # instance reported) is what's pinned here.
            assert q["count"] == sum(per_instance)
            assert q["p99"] > 0
            # one instance dies: stale row, sums keep last-known value
            srv2.stop()
            s, _, body = _get(
                f"http://127.0.0.1:{dash.port}/fleet.json")
            doc = json.loads(body)
            rows = {r["instance"]: r for r in doc["instances"]}
            assert rows[f"http://127.0.0.1:{srv2.port}"]["stale"] is True
            assert doc["merged"]["counters"][
                "pio_query_requests_total"] == sum(per_instance)
        finally:
            try:
                srv1.stop()
            finally:
                try:
                    srv2.stop()
                except Exception:
                    pass
                dash.stop()

    def test_dashboard_without_fleet_config_says_so(self, pio_home):
        from predictionio_tpu.server.dashboard import DashboardServer

        dash = DashboardServer(host="127.0.0.1", port=0)
        dash.start(block=False)
        try:
            s, _, body = _get(
                f"http://127.0.0.1:{dash.port}/fleet.json")
            doc = json.loads(body)
            assert s == 200
            assert doc["instances"] == []
            assert "PIO_FLEET_INSTANCES" in doc["message"]
        finally:
            dash.stop()

    def test_pio_status_fleet_summary(self, trained, capsys):
        from predictionio_tpu.cli.main import _print_fleet_status
        from predictionio_tpu.server import EngineServer

        eng, variant, storage, _ = trained
        srv = EngineServer(eng, variant, storage, host="127.0.0.1",
                           port=0)
        srv.start()
        try:
            assert _post_query(srv.port)[0] == 200
            _print_fleet_status(f"http://127.0.0.1:{srv.port}")
            out = capsys.readouterr().out
            assert "fleet: 1 instance(s)" in out
            assert "healthy" in out
            assert "pio_query_requests_total" in out
            assert "p99" in out
        finally:
            srv.stop()
