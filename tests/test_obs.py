"""obs/ unit tests: registry semantics, renderer validity, tracing,
pipeline probe.

``parse_prometheus`` doubles as the suite's Prometheus text-format
validator (no prometheus_client in the image): strict line grammar,
TYPE-before-samples, cumulative ``le`` buckets, ``+Inf`` == ``_count``.
test_servers.py imports it to validate live ``/metrics`` output.
"""

import json
import math
import re
import threading

import pytest

from predictionio_tpu.obs import (
    CompileTracker,
    DeviceMemorySampler,
    MetricsRegistry,
    PipelineProbe,
    StepTimeline,
    TraceRecorder,
    get_recorder,
    get_registry,
    get_timeline,
    phase,
    publish_event,
    reset_observability,
    sanitize_trace_id,
    set_timeline,
    span,
    trace,
)

# -- Prometheus text-format parser/validator --------------------------------

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_SAMPLE_RE = re.compile(
    rf"^({_NAME})(?:\{{(.*)\}})? (-?(?:[0-9.]+(?:[eE][+-]?[0-9]+)?|Inf)|\+Inf|NaN)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"(?:,|$)')
_VALUE = r"(?:-?(?:[0-9.]+(?:[eE][+-]?[0-9]+)?|Inf)|\+Inf|NaN)"
# OpenMetrics exemplar suffix: ` # {labels} value` (ISSUE 9: histogram
# buckets carry the trace id of the last observation that landed there).
_EXEMPLAR_RE = re.compile(rf" # \{{((?:[^\"}}]|\"(?:[^\"\\]|\\.)*\")*)\}} ({_VALUE})$")


def _parse_value(s: str) -> float:
    if s == "+Inf":
        return math.inf
    if s == "-Inf":
        return -math.inf
    if s == "NaN":
        return math.nan
    return float(s)


def parse_prometheus(text: str):
    """Validate + parse exposition text → {name: [(labels_dict, value)]}.

    Raises AssertionError on any malformed line, samples without a
    preceding # TYPE, non-cumulative histogram buckets, or +Inf bucket
    disagreeing with _count.
    """
    samples = {}
    types = {}
    assert text.endswith("\n"), "exposition must end with a newline"
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            assert kind in ("counter", "gauge", "histogram", "summary",
                            "untyped"), f"bad TYPE line: {line!r}"
            types[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        em = _EXEMPLAR_RE.search(line)
        if em:
            line = line[:em.start()]
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, labels_raw, value = m.group(1), m.group(2), m.group(3)
        if em:
            # Exemplars are legal only on histogram bucket samples, and
            # their labelset must itself be well-formed.
            assert name.endswith("_bucket"), \
                f"exemplar on non-bucket sample: {line!r}"
            ex_labels = em.group(1)
            consumed = sum(len(mm.group(0)) for mm in
                           _LABEL_RE.finditer(ex_labels))
            assert consumed == len(ex_labels), \
                f"malformed exemplar labels: {ex_labels!r}"
            _parse_value(em.group(2))
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert base in types or name in types, \
            f"sample {name!r} has no # TYPE"
        labels = {}
        if labels_raw:
            consumed = sum(len(mm.group(0)) for mm in
                           _LABEL_RE.finditer(labels_raw))
            assert consumed == len(labels_raw), \
                f"malformed labels: {labels_raw!r}"
            for mm in _LABEL_RE.finditer(labels_raw):
                labels[mm.group(1)] = mm.group(2)
        samples.setdefault(name, []).append((labels, _parse_value(value)))
    # histogram invariants
    for name, kind in types.items():
        if kind != "histogram":
            continue
        series = {}
        for labels, v in samples.get(f"{name}_bucket", []):
            key = tuple(sorted((k, lv) for k, lv in labels.items()
                               if k != "le"))
            le = math.inf if labels["le"] == "+Inf" else float(labels["le"])
            series.setdefault(key, []).append((le, v))
        counts = {tuple(sorted(labels.items())): v
                  for labels, v in samples.get(f"{name}_count", [])}
        for key, bs in series.items():
            bs.sort()
            cums = [v for _, v in bs]
            assert cums == sorted(cums), f"{name}{key}: buckets not cumulative"
            assert bs[-1][0] == math.inf, f"{name}{key}: no +Inf bucket"
            assert bs[-1][1] == counts[key], \
                f"{name}{key}: +Inf bucket != _count"
    return samples


# -- registry ---------------------------------------------------------------

class TestRegistry:
    def test_counter_labels_and_values(self):
        reg = MetricsRegistry()
        c = reg.counter("pio_t_total", "t", ("status",))
        c.inc(status="200")
        c.inc(2, status="404")
        assert c.value(status="200") == 1
        assert c.value(status="404") == 2
        assert c.total() == 3
        with pytest.raises(ValueError):
            c.inc(status="200", extra="nope")
        with pytest.raises(ValueError):
            c.inc(-1, status="200")

    def test_get_or_create_and_mismatch(self):
        reg = MetricsRegistry()
        a = reg.counter("pio_x_total", "x")
        assert reg.counter("pio_x_total") is a
        with pytest.raises(ValueError):
            reg.gauge("pio_x_total")
        with pytest.raises(ValueError):
            reg.counter("pio_x_total", labelnames=("other",))
        with pytest.raises(ValueError):
            reg.counter("0bad name")
        h = reg.histogram("pio_x_ms", buckets=(1, 10))
        assert reg.histogram("pio_x_ms", buckets=(1, 10)) is h
        with pytest.raises(ValueError):
            reg.histogram("pio_x_ms", buckets=(5, 50))

    def test_phase_records_even_on_exception(self):
        reset_observability()
        with pytest.raises(RuntimeError):
            with trace("workflow.train"):
                with phase("train.datasource"):
                    raise RuntimeError("boom")
        h = get_registry().get("pio_train_phase_ms")
        assert h.count(phase="train.datasource") == 1

    def test_label_escaping_round_trips(self):
        reg = MetricsRegistry()
        c = reg.counter("pio_esc_total", "h", ("route",))
        nasty = 'a"b\\c\nd'
        c.inc(route=nasty)
        text = reg.render()
        assert '\\"' in text and "\\\\" in text and "\\n" in text
        samples = parse_prometheus(text)
        (labels, value), = samples["pio_esc_total"]
        assert value == 1
        # unescape what the renderer escaped — must round-trip
        unescaped = (labels["route"].replace("\\\\", "\x00")
                     .replace('\\"', '"').replace("\\n", "\n")
                     .replace("\x00", "\\"))
        assert unescaped == nasty

    def test_histogram_buckets_and_quantiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("pio_h_ms", "h", buckets=(1, 10, 100))
        for v in (0.5, 5, 5, 50, 500):
            h.observe(v)
        assert h.count() == 5
        assert h.sum() == 560.5
        samples = parse_prometheus(reg.render())
        le_counts = {labels["le"]: v
                     for labels, v in samples["pio_h_ms_bucket"]}
        assert le_counts == {"1": 1, "10": 3, "100": 4, "+Inf": 5}
        # interpolated median lands inside the (1, 10] bucket
        assert 1 <= h.quantile(0.5) <= 10
        # +Inf-bucket quantiles report the top finite bound
        assert h.quantile(0.999) == 100

    def test_concurrent_increments_lose_nothing(self):
        reg = MetricsRegistry()
        c = reg.counter("pio_c_total", "c", ("worker",))
        h = reg.histogram("pio_ch_ms", "h")
        n_threads, per = 8, 500

        def work(i):
            for _ in range(per):
                c.inc(worker=str(i % 2))
                h.observe(1.0)

        ts = [threading.Thread(target=work, args=(i,))
              for i in range(n_threads)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert c.total() == n_threads * per
        assert h.count() == n_threads * per

    def test_unlabelled_counter_renders_bare(self):
        reg = MetricsRegistry()
        reg.counter("pio_bare_total", "b").inc()
        assert "pio_bare_total 1\n" in reg.render()

    def test_gauge_set_inc_dec(self):
        reg = MetricsRegistry()
        g = reg.gauge("pio_g", "g")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value() == 6

    def test_render_is_valid_when_empty_and_after_reset(self):
        reg = MetricsRegistry()
        reg.counter("pio_a_total", "a", ("x",))
        parse_prometheus(reg.render())
        reg.reset()
        assert reg.render() == "\n" or parse_prometheus(reg.render()) == {}


# -- tracing ----------------------------------------------------------------

class TestTracing:
    def setup_method(self):
        reset_observability()

    def test_span_tree_and_ring(self):
        with trace("root", trace_id="tid-1", a=1) as t:
            with span("child1"):
                with span("grand"):
                    pass
            with span("child2", algo="als"):
                pass
        assert t.duration_ms is not None
        docs = get_recorder().recent(5)
        assert docs and docs[0]["traceId"] == "tid-1"
        names = [s["name"] for s in docs[0]["spans"]]
        assert names == ["child1", "child2"]
        assert docs[0]["spans"][0]["spans"][0]["name"] == "grand"
        assert docs[0]["spans"][1]["attrs"] == {"algo": "als"}

    def test_span_outside_trace_records_nothing(self):
        with span("orphan") as s:
            pass
        assert s.duration_ms is not None
        assert get_recorder().recent(5) == []

    def test_nested_trace_degrades_to_span(self):
        with trace("outer"):
            with trace("inner"):
                pass
        docs = get_recorder().recent(5)
        assert len(docs) == 1
        assert [s["name"] for s in docs[0]["spans"]] == ["inner"]

    def test_jsonl_export(self, tmp_path, monkeypatch):
        out = tmp_path / "traces.jsonl"
        monkeypatch.setenv("PIO_TRACE_FILE", str(out))
        with trace("one"):
            pass
        with trace("two"):
            with span("s"):
                pass
        lines = [json.loads(line) for line in
                 out.read_text().strip().splitlines()]
        assert [d["name"] for d in lines] == ["one", "two"]
        assert all("traceId" in d and "durationMs" in d for d in lines)

    def test_ring_is_bounded(self):
        rec = TraceRecorder(ring_size=3)
        for i in range(5):
            with trace(f"t{i}", recorder=rec):
                pass
        assert [d["name"] for d in rec.recent(10)] == ["t4", "t3", "t2"]

    def test_slow_trace_logs_warning(self, caplog):
        import logging

        with caplog.at_level(logging.WARNING,
                             logger="predictionio_tpu.obs.trace"):
            with trace("fast", slow_ms=10000):
                pass
            assert not caplog.records
            with trace("slow", slow_ms=0.0000001):
                pass
        assert any("slow" in r.message for r in caplog.records)

    def test_sanitize_trace_id(self):
        assert sanitize_trace_id(None) is None
        assert sanitize_trace_id("") is None
        assert sanitize_trace_id("ab-c_1.2:3") == "ab-c_1.2:3"
        # CRLF and header-splitting characters are stripped
        assert sanitize_trace_id("a\r\nSet-Cookie: x") == "aSet-Cookie:x"
        assert sanitize_trace_id("\r\n") is None
        assert len(sanitize_trace_id("x" * 500)) == 128

    def test_phase_records_span_and_histogram(self):
        reset_observability()
        with trace("workflow.train"):
            with phase("train.datasource"):
                pass
        h = get_registry().get("pio_train_phase_ms")
        assert h.count(phase="train.datasource") == 1
        doc = get_recorder().recent(1)[0]
        assert doc["spans"][0]["name"] == "train.datasource"


# -- pipeline probe ---------------------------------------------------------

class TestPipelineProbe:
    def test_decomposition_counts(self):
        reg = MetricsRegistry()
        probe = PipelineProbe("toy", registry=reg,
                              timeline=StepTimeline(capacity=16))
        batches = [([1, 2], [3, 4]), ([5], [6])]
        seen = []
        for b in probe.iter_host(iter(batches)):
            with probe.h2d():
                staged = b
            probe.sync()
            seen.append(staged)
            probe.dispatched({"step": len(seen)}, examples=len(b[0]))
        probe.finish()
        assert seen == batches
        assert reg.get("pio_train_steps_total").value(model="toy") == 2
        assert reg.get("pio_train_examples_total").value(model="toy") == 3
        assert reg.get("pio_train_host_wait_ms").count(model="toy") == 2
        assert reg.get("pio_train_h2d_ms").count(model="toy") == 2
        # one-step lag: first sync is a no-op, finish drains the last
        assert reg.get("pio_train_device_wait_ms").count(model="toy") == 2
        parse_prometheus(reg.render())

    def test_probe_feeds_timeline_per_step(self):
        reg = MetricsRegistry()
        tl = StepTimeline(capacity=16)
        probe = PipelineProbe("toy", registry=reg, timeline=tl)
        for b in probe.iter_host(iter([([1, 2],), ([3],)])):
            with probe.h2d():
                pass
            probe.sync()
            probe.dispatched({"x": 1}, examples=len(b[0]))
        probe.finish()
        steps = tl.recent(10, model="toy")
        assert len(steps) == 2
        # most recent first; step ids increase; every phase recorded
        assert [r["step"] for r in steps] == [2, 1]
        assert steps[0]["examples"] == 1 and steps[1]["examples"] == 2
        for r in steps:
            for k in ("hostWaitMs", "h2dMs", "deviceWaitMs",
                      "deviceStepMs", "startS"):
                assert r[k] >= 0


# -- runtime introspection ---------------------------------------------------

class _FakeJit:
    """Stands in for a jax.jit wrapper: compiles (cache grows) whenever
    called with an unseen arg 'shape'."""

    def __init__(self):
        self.cache = set()
        self.calls = 0

    def _cache_size(self):
        return len(self.cache)

    def __call__(self, x):
        self.calls += 1
        self.cache.add(x)
        return x * 2


class TestCompileTracker:
    def setup_method(self):
        reset_observability()

    def test_counts_only_compiling_calls(self):
        reg = get_registry()
        tracker = CompileTracker(warn_threshold=99)
        fn = tracker.wrap("toy.step", _FakeJit())
        assert fn(1) == 2
        assert fn(1) == 2    # cache hit: no compile
        assert fn(2) == 4    # new "shape": compile
        c = reg.get("pio_xla_compile_total")
        assert c.value(fn="toy.step") == 2
        assert reg.get("pio_xla_compile_ms").count(fn="toy.step") == 2
        parse_prometheus(reg.render())

    def test_compile_event_lands_in_trace_ring(self):
        tracker = CompileTracker(warn_threshold=99)
        fn = tracker.wrap("toy.step", _FakeJit())
        fn(1)
        docs = get_recorder().recent(5)
        assert docs and docs[0]["name"] == "xla.compile"
        assert docs[0]["attrs"]["fn"] == "toy.step"

    def test_compile_inside_open_trace_attaches_to_request(self):
        tracker = CompileTracker(warn_threshold=99)
        fn = tracker.wrap("toy.step", _FakeJit())
        with trace("http.request", trace_id="req-9"):
            fn(1)
        doc, = get_recorder().recent(5)
        assert doc["traceId"] == "req-9"
        names = [s["name"] for s in doc.get("spans", [])]
        assert "xla.compile" in names  # "recompiled here"

    def test_shape_churn_warning_past_threshold(self, caplog):
        import logging

        tracker = CompileTracker(warn_threshold=2)
        fn = tracker.wrap("churny.step", _FakeJit())
        with caplog.at_level(logging.WARNING,
                             logger="predictionio_tpu.obs.runtime"):
            fn(1)
            fn(2)
            assert not caplog.records  # at threshold: still quiet
            fn(3)
        assert any("shape churn" in r.message and "churny.step" in r.message
                   for r in caplog.records)

    def test_unwrappable_fn_passes_through(self):
        tracker = CompileTracker(warn_threshold=99)
        fn = tracker.wrap("plain", lambda x: x + 1)  # no _cache_size
        assert fn(1) == 2
        c = get_registry().get("pio_xla_compile_total")
        assert c is None or c.value(fn="plain") == 0


class _FakeDevice:
    def __init__(self, platform, id, stats):
        self.platform = platform
        self.id = id
        self._stats = stats

    def memory_stats(self):
        return self._stats


class _FakeArray:
    def __init__(self, nbytes, device):
        self.nbytes = nbytes
        self._device = device

    def devices(self):
        return {self._device}


class TestDeviceMemorySampler:
    def setup_method(self):
        reset_observability()

    def test_sample_exports_gauges_and_tracks_peak(self):
        t = [100.0]
        stats = {"bytes_in_use": 1000, "peak_bytes_in_use": 1500,
                 "bytes_limit": 4000}
        dev = _FakeDevice("tpu", 0, stats)
        sampler = DeviceMemorySampler(
            interval_s=0, devices_fn=lambda: [dev],
            live_arrays_fn=lambda: [], clock=lambda: t[0])
        out = sampler.sample_once()
        assert out["tpu:0"]["bytes_in_use"] == 1000
        g = get_registry().get("pio_device_mem_bytes")
        assert g.value(device="tpu:0", kind="bytes_in_use") == 1000
        assert g.value(device="tpu:0", kind="bytes_limit") == 4000
        peak = get_registry().get("pio_device_mem_peak_bytes")
        # the window peaks over OUR bytes_in_use samples; the allocator's
        # monotone peak_bytes_in_use must NOT leak in (it would defeat
        # reset_peak) — it stays visible as its own kind gauge
        assert peak.value(device="tpu:0") == 1000
        assert g.value(device="tpu:0", kind="peak_bytes_in_use") == 1500
        # memory falls; the peak gauge must NOT fall with it
        stats["bytes_in_use"] = 200
        stats["peak_bytes_in_use"] = 0
        sampler.sample_once()
        assert peak.value(device="tpu:0") == 1000
        # fresh train run: window resets, next sample re-establishes
        sampler.reset_peak()
        sampler.sample_once()
        assert peak.value(device="tpu:0") == 200
        parse_prometheus(get_registry().render())

    def test_live_array_fallback_for_statless_backends(self):
        dev = _FakeDevice("cpu", 0, None)
        arrays = [_FakeArray(64, dev), _FakeArray(36, dev)]
        sampler = DeviceMemorySampler(
            interval_s=0, devices_fn=lambda: [dev],
            live_arrays_fn=lambda: arrays)
        out = sampler.sample_once()
        assert out["cpu:0"]["live_bytes"] == 100
        g = get_registry().get("pio_device_mem_bytes")
        assert g.value(device="cpu:0", kind="live_bytes") == 100
        assert g.value(device="cpu:0", kind="live_arrays") == 2
        # live_bytes stands in for bytes_in_use in the peak window
        assert get_registry().get(
            "pio_device_mem_peak_bytes").value(device="cpu:0") == 100

    def test_interval_zero_disables_thread(self):
        sampler = DeviceMemorySampler(interval_s=0,
                                      devices_fn=lambda: [])
        assert sampler.start() is False

    def test_device_enumeration_failure_is_quiet(self):
        def boom():
            raise RuntimeError("backend down")

        sampler = DeviceMemorySampler(interval_s=0, devices_fn=boom,
                                      live_arrays_fn=lambda: [])
        assert sampler.sample_once() == {}


class TestStepTimeline:
    def test_ring_bounds_and_summary_shares(self):
        tl = StepTimeline(capacity=3)
        for i in range(5):
            tl.record("m", host_wait_ms=10, h2d_ms=30, device_wait_ms=60,
                      device_step_ms=70, examples=8, start_s=1000.0 + i)
        assert len(tl.recent(10)) == 3  # bounded
        s = tl.summary("m")
        assert s["steps"] == 3 and s["examples"] == 24
        assert s["phase_ms"]["h2d"] == 90
        assert abs(s["phase_share"]["host_wait"] - 0.1) < 1e-6
        assert abs(s["phase_share"]["device_wait"] - 0.6) < 1e-6
        # device_step is overlapped: tracked in phase_ms, not in shares
        assert "device_step" not in s["phase_share"]

    def test_models_filter(self):
        tl = StepTimeline(capacity=8)
        tl.record("a", host_wait_ms=1)
        tl.record("b", h2d_ms=2)
        assert tl.models() == ["a", "b"]
        assert [r["model"] for r in tl.recent(10, model="a")] == ["a"]

    def test_chrome_trace_export(self):
        tl = StepTimeline(capacity=8)
        tl.record("m", host_wait_ms=1.0, h2d_ms=2.0, device_wait_ms=3.0,
                  device_step_ms=4.0, start_s=123.0, examples=8)
        doc = tl.to_chrome_trace()
        events = doc["traceEvents"]
        assert {e["ph"] for e in events} == {"M", "X"}
        xs = [e for e in events if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"host_wait", "h2d",
                                           "device_wait", "device_step"}
        # host-lane phases tile sequentially from the step start
        by_name = {e["name"]: e for e in xs}
        assert by_name["h2d"]["ts"] == pytest.approx(
            by_name["host_wait"]["ts"] + by_name["host_wait"]["dur"])
        assert by_name["device_step"]["tid"] != by_name["host_wait"]["tid"]
        json.dumps(doc)  # must be directly serializable

    def test_process_timeline_swap(self):
        prev = set_timeline(StepTimeline(capacity=4))
        try:
            get_timeline().record("x", host_wait_ms=1)
            assert get_timeline().models() == ["x"]
        finally:
            set_timeline(prev)


class TestPublishEvent:
    def setup_method(self):
        reset_observability()

    def test_standalone_event_records_trace(self):
        publish_event("breaker.transition", breaker="b", to="open")
        doc, = get_recorder().recent(5)
        assert doc["name"] == "breaker.transition"
        assert doc["attrs"]["to"] == "open"

    def test_event_inside_trace_attaches_as_child(self):
        with trace("http.request", trace_id="t1"):
            publish_event("spill.append", token="tok", events=3)
        doc, = get_recorder().recent(5)
        assert doc["traceId"] == "t1"
        assert [s["name"] for s in doc["spans"]] == ["spill.append"]


# -- overlapped input pipeline (ISSUE 5): probe + timeline + HBM guard ------

class _FakePrefetched:
    """Stands in for data.prefetch.PrefetchedBatch (duck-typed)."""

    def __init__(self, step, args, examples, h2d_ms, staged_s):
        self.step = step
        self.args = args
        self.examples = examples
        self.h2d_ms = h2d_ms
        self.staged_s = staged_s


class TestPrefetchedProbe:
    def test_overlap_attribution_and_dispatch_stamp(self):
        reg = MetricsRegistry()
        tl = StepTimeline(capacity=16)
        probe = PipelineProbe("toy", registry=reg, timeline=tl)
        batches = [_FakePrefetched(k, ("a",), 4, 12.5, 1000.0 + k)
                   for k in (1, 2)]
        for b in probe.iter_prefetched(iter(batches)):
            probe.sync()
            probe.dispatched({"s": b.step}, examples=b.examples)
        probe.finish()
        # staging lands in the overlap window, not the h2d wall component
        assert reg.get("pio_train_h2d_overlap_ms").count(model="toy") == 2
        assert reg.get("pio_train_h2d_ms").count(model="toy") == 0
        recs = tl.recent(10, model="toy")
        assert len(recs) == 2
        for r in recs:
            assert r["h2dOverlapMs"] == pytest.approx(12.5)
            assert r["h2dMs"] == 0.0
            assert r["dispatchS"] > 0          # true dispatch wall clock
            assert r["stagedS"] >= 1000.0
        s = tl.summary("toy")
        assert s["phase_ms"]["h2d_overlap"] == pytest.approx(25.0)
        # overlapped staging is excluded from the wall decomposition
        assert "h2d_overlap" not in s["phase_share"]
        parse_prometheus(reg.render())

    def test_chrome_export_uses_dispatch_and_prefetch_lane(self):
        tl = StepTimeline(capacity=8)
        tl.record("m", host_wait_ms=1.0, h2d_overlap_ms=4.0,
                  device_wait_ms=3.0, device_step_ms=9.0,
                  start_s=100.0, dispatch_s=100.005, staged_s=99.999,
                  examples=8)
        doc = tl.to_chrome_trace()
        xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        # the device lane starts at the recorded dispatch, not the
        # step start
        assert xs["device_step"]["ts"] == pytest.approx(100.005e6)
        # overlapped staging draws on its own lane, ending at stagedS
        pf = xs["h2d_overlap"]
        assert pf["tid"] == 2
        assert pf["ts"] + pf["dur"] == pytest.approx(99.999e6)
        lanes = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["name"] == "thread_name"}
        assert lanes == {"host", "device", "prefetch"}
        json.dumps(doc)

    def test_chrome_export_without_dispatch_falls_back(self):
        tl = StepTimeline(capacity=8)
        tl.record("m", host_wait_ms=1.0, device_step_ms=2.0, start_s=50.0)
        doc = tl.to_chrome_trace()
        xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        assert xs["device_step"]["ts"] == pytest.approx(50.0e6)
        lanes = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["name"] == "thread_name"}
        assert lanes == {"host", "device"}  # no prefetch lane if unused


class TestHbmHeadroomWarning:
    def setup_method(self):
        reset_observability()

    def _sampler(self, stats):
        dev = _FakeDevice("tpu", 0, stats)
        return DeviceMemorySampler(interval_s=0, devices_fn=lambda: [dev],
                                   live_arrays_fn=lambda: [])

    def test_warns_once_per_window_above_fraction(self, caplog):
        stats = {"bytes_in_use": 950, "bytes_limit": 1000}
        sampler = self._sampler(stats)
        with caplog.at_level("WARNING"):
            sampler.sample_once()
            sampler.sample_once()  # second crossing must not re-warn
        warns = [r for r in caplog.records if "HBM headroom" in r.message]
        assert len(warns) == 1
        assert "PIO_PREFETCH_DEPTH" in warns[0].message
        c = get_registry().get("pio_hbm_headroom_warn_total")
        assert c.value(device="tpu:0") == 1

    def test_below_fraction_is_silent(self, caplog):
        sampler = self._sampler({"bytes_in_use": 500, "bytes_limit": 1000})
        with caplog.at_level("WARNING"):
            sampler.sample_once()
        assert not [r for r in caplog.records
                    if "HBM headroom" in r.message]

    def test_reset_peak_rearms_the_warning(self, caplog):
        stats = {"bytes_in_use": 950, "bytes_limit": 1000}
        sampler = self._sampler(stats)
        with caplog.at_level("WARNING"):
            sampler.sample_once()
            sampler.reset_peak()  # new train run -> fresh guard
            sampler.sample_once()
        warns = [r for r in caplog.records if "HBM headroom" in r.message]
        assert len(warns) == 2
        assert get_registry().get(
            "pio_hbm_headroom_warn_total").value(device="tpu:0") == 2

    def test_fraction_env_override_and_disable(self, caplog, monkeypatch):
        stats = {"bytes_in_use": 700, "bytes_limit": 1000}
        monkeypatch.setenv("PIO_HBM_WARN_FRACTION", "0.5")
        with caplog.at_level("WARNING"):
            self._sampler(stats).sample_once()
        assert [r for r in caplog.records if "HBM headroom" in r.message]
        caplog.clear()
        monkeypatch.setenv("PIO_HBM_WARN_FRACTION", "0")  # disabled
        with caplog.at_level("WARNING"):
            self._sampler(stats).sample_once()
        assert not [r for r in caplog.records
                    if "HBM headroom" in r.message]

    def test_no_limit_no_warning(self, caplog):
        # CPU live-array fallback has no bytes_limit: never warns
        dev = _FakeDevice("cpu", 0, None)
        sampler = DeviceMemorySampler(
            interval_s=0, devices_fn=lambda: [dev],
            live_arrays_fn=lambda: [_FakeArray(900, dev)])
        with caplog.at_level("WARNING"):
            sampler.sample_once()
        assert not [r for r in caplog.records
                    if "HBM headroom" in r.message]

    def test_headroom_exceeded_latches_the_run_peak(self):
        # The fusion autotuner probes BETWEEN windows — in the memory
        # trough.  headroom_exceeded must answer from the run PEAK the
        # sampler observed (here: a mid-window sample), not the
        # instantaneous trough, or the tuner grows straight past the
        # limit into an OOM.  reset_peak (a new train run) re-arms it.
        stats = {"bytes_in_use": 950, "bytes_limit": 1000}
        sampler = self._sampler(stats)
        sampler.sample_once()  # mid-window: the peak
        stats["bytes_in_use"] = 100  # trough at the round boundary
        assert sampler.headroom_exceeded() is True
        sampler.reset_peak()
        assert sampler.headroom_exceeded() is False
        assert sampler.headroom_exceeded(fraction=0.05) is True
