"""The `pio` command-line console.

Reference: tools/.../tools/console/Console.scala (scopt verb dispatch) and
tools/.../commands/{App,AccessKey,...} — SURVEY.md §2.1 "Tools/CLI" and
Appendix A's CLI verb list.  Verbs:

    pio status
    pio app new <name> | list | delete <name> | data-delete <name>
    pio app channel-new <app> <channel> | channel-delete <app> <channel>
    pio accesskey new <appname> [event ...] | list [appname] | delete <key>
    pio train   --engine-json engine.json [--seed N]
    pio import  --appid N --input events.ndjson
    pio export  --appid N --output events.ndjson
    pio eval    <EvaluationClass> <EngineParamsGeneratorClass>
    pio eventserver --port 7070        (added with the server layer)
    pio deploy  --engine-json ... --port 8000
    pio profile [--url http://HOST:7071] [--duration-ms N]

Where the reference's `pio train`/`pio deploy` shell out to spark-submit,
these run the workflow in-process — there is no cluster-manager boundary on
a TPU slice; multi-host launch is `pio train` once per host with
PIO_COORDINATOR_ADDRESS set (parallel/distributed.py).
"""

from __future__ import annotations

import argparse
import datetime as _dt
import os
import json
import logging
import re
import sys
from pathlib import Path
from typing import List, Optional

from predictionio_tpu.version import __version__

logger = logging.getLogger(__name__)


def _storage():
    from predictionio_tpu.data.storage import get_storage

    return get_storage()


def _die(msg: str, code: int = 1) -> "NoReturn":  # noqa: F821
    print(f"[error] {msg}", file=sys.stderr)
    raise SystemExit(code)


# --------------------------------------------------------------------------
# pio status
# --------------------------------------------------------------------------

def cmd_status(args) -> int:
    from predictionio_tpu.config import load_config

    cfg = load_config()
    print(f"predictionio_tpu {__version__}")
    print(f"PIO_HOME: {cfg.home}")
    try:
        repo_types = _storage().verify()
    except Exception as e:
        _die(f"storage verification failed: {e}")
    for repo, t in repo_types.items():
        src = cfg.source_for(repo)
        print(f"  {repo}: type={t} path={src.path or '-'}")
    _print_segment_status()
    devices_ok = True
    try:
        from predictionio_tpu.backend import describe_backend

        b = describe_backend()
        print(f"devices: {b.device_count} x {b.platform} ({b.device_kind})")
        _print_device_memory()
    except RuntimeError as e:  # storage status is still worth printing
        devices_ok = False
        print(f"devices: unavailable ({e})")
    fleet = getattr(args, "fleet", None)
    metrics_url = getattr(args, "metrics_url", None)
    # An explicit --metrics-url outranks the ambient PIO_FLEET_INSTANCES:
    # the operator asked about ONE process, not the fleet the env
    # happens to describe.  --fleet (also explicit) still wins over it.
    if fleet is not None or (metrics_url is None
                             and os.environ.get("PIO_FLEET_INSTANCES")):
        _print_fleet_status(fleet)
    else:
        _print_metrics_snapshot(metrics_url)
    if not devices_ok:
        print("(storage OK; NO DEVICE — see the devices line)")
        return 1
    print("(sanity check OK)")
    return 0


def _print_segment_status() -> None:
    """ISSUE 17 lines for `pio status`: the columnar segment store (read
    straight from the on-disk manifests — works with no server running)
    and the write-path admission knobs."""
    from predictionio_tpu.data.columnar import resolve_segment_root

    seg_root = resolve_segment_root()
    if seg_root is None:
        print("segments: off (PIO_SEGMENTS=off)")
    else:
        entries = []
        for mpath in sorted(seg_root.glob("app_*/*/manifest.json")):
            try:
                man = json.loads(mpath.read_text())
            except (OSError, ValueError):
                continue
            segs = man.get("segments", [])
            entries.append((str(mpath.parent.relative_to(seg_root)),
                            len(segs), sum(e["rows"] for e in segs),
                            sum(e["bytes"] for e in segs)))
        print(f"segments: root={seg_root} dirs={len(entries)} "
              f"sealed={sum(e[1] for e in entries)} "
              f"rows={sum(e[2] for e in entries)}")
        for d, s, r, b in entries:
            print(f"  {d}: segments={s} rows={r} bytes={b}")
    budget = os.environ.get("PIO_INGEST_QUEUE_BUDGET") or "unbounded"
    min_free = os.environ.get("PIO_DISK_MIN_FREE_BYTES") or "0"
    print(f"ingest: admission budget={budget} "
          f"max batch={os.environ.get('PIO_MAX_BATCH_SIZE', '50')} "
          f"disk min free bytes={min_free}")


def _print_fleet_status(fleet_arg: Optional[str]) -> None:
    """`pio status --fleet URL,URL` (ISSUE 9): scrape every instance's
    /metrics + SLO state, merge type-correctly (obs.fleet), and print
    the operator summary — per-instance readiness next to fleet-summed
    traffic counters."""
    from predictionio_tpu.obs.fleet import (
        FleetAggregator,
        fleet_instances_from_env,
    )

    urls = ([u.strip().rstrip("/") for u in fleet_arg.split(",")
             if u.strip()] if fleet_arg else fleet_instances_from_env())
    if not urls:
        print("fleet: no instances configured (--fleet URL,URL or "
              "PIO_FLEET_INSTANCES)")
        return
    agg = FleetAggregator(urls)
    doc = agg.scrape()
    print(f"fleet: {len(urls)} instance(s)")
    for row in doc["instances"]:
        state = "STALE" if row["stale"] else "up"
        parts = [state]
        slo = row.get("slo")
        if slo:
            parts.append("degraded" if slo.get("degraded") else "healthy")
            if slo.get("saturated"):
                parts.append("saturated")
            fast = slo.get("burn", {}).get("fast", {})
            parts.append(f"burn fast a={fast.get('availability', 0):g}"
                         f"/l={fast.get('latency', 0):g}")
        if row.get("error"):
            parts.append(row["error"])
        print(f"  {row['instance']}: {', '.join(parts)}")
    counters = doc["merged"]["counters"]
    interesting = ("pio_query_requests_total", "pio_query_errors_total",
                   "pio_event_requests_total", "pio_queue_rejected_total",
                   "pio_deadline_shed_total")
    shown = {k: v for k, v in counters.items()
             if any(k.startswith(p) for p in interesting)}
    if shown:
        print("  fleet totals:")
        for k, v in sorted(shown.items()):
            print(f"    {k} {v:g}")
    q = doc["merged"]["histogramQuantiles"].get("pio_query_latency_ms", {})
    for key, row in sorted(q.items()):
        print(f"  fleet {key}: p50 {row['p50']:g}ms p99 {row['p99']:g}ms "
              f"over {row['count']:g} requests")
    _print_fleet_plane(doc)


def _print_fleet_plane(doc) -> None:
    """ISSUE 15 lines for `pio status --fleet`: shared spill-queue depth
    (scraped gauges first, storage second) and the journaled rollout
    wave state."""
    gauges = doc["merged"].get("gauges", {})
    shared = {k: v for k, v in gauges.items()
              if k.startswith("pio_spill_shared_depth")}
    if shared:
        print(f"  shared spill queue: {max(shared.values()):g} event(s) "
              "pending/leased (per-instance view of one fleet queue)")
    else:
        # No event server in the scraped set — best-effort direct read
        # of THIS process's configured storage.
        try:
            from predictionio_tpu.resilience.shared_spill import (
                SharedSpillQueue,
            )

            st = SharedSpillQueue(_storage()).stats()
            print(f"  shared spill queue: {st.get('pendingEvents', 0)} "
                  f"pending / {st.get('leasedEvents', 0)} leased / "
                  f"{st.get('deadEvents', 0)} dead event(s)")
        except Exception:
            pass
    try:
        from predictionio_tpu.fleet import rollout_state_path

        state = json.loads(rollout_state_path().read_text())
    except Exception:
        return
    line = (f"  rollout [{state.get('rolloutId')}]: "
            f"{state.get('status')} — wave {state.get('wave')} of "
            f"{len(state.get('waveCounts') or [])}, "
            f"{len(state.get('promoted') or [])} promoted, "
            f"{len(state.get('skipped') or {})} skipped")
    if state.get("haltReason"):
        line += f", halt: {state['haltReason']}"
    print(line)


def _print_device_memory() -> None:
    """Device-memory snapshot (obs.runtime sampler): live allocator stats
    for this process, plus any per-train-run peaks a local run recorded.
    A remote server's peaks arrive via --metrics-url (the sampler exports
    pio_device_mem_bytes / pio_device_mem_peak_bytes there)."""
    from predictionio_tpu.obs import get_memory_sampler

    sampler = get_memory_sampler()
    try:
        sample = sampler.sample_once()
    except Exception as e:
        print(f"device memory: unavailable ({e})")
        return
    if not sample:
        print("device memory: no allocator stats on this backend")
        return
    peaks = sampler.peaks()
    for dev, row in sorted(sample.items()):
        parts = []
        for kind in ("bytes_in_use", "bytes_limit", "live_bytes",
                     "live_arrays"):
            if kind in row:
                v = row[kind]
                parts.append(f"{kind}={int(v):,}" if kind != "live_arrays"
                             else f"{kind}={int(v)}")
        if dev in peaks:
            parts.append(f"peak={int(peaks[dev]):,}")
        print(f"device memory [{dev}]: {' '.join(parts) or '(empty)'}")


def _print_metrics_snapshot(metrics_url: Optional[str]) -> None:
    """Metrics view for `pio status`: scrape a running server's /metrics
    when --metrics-url is given, else render this process's registry (the
    sanity checks above already touched storage, so it is non-empty only
    if instrumented code ran — say so rather than print nothing)."""
    if metrics_url:
        from urllib.request import urlopen

        url = metrics_url.rstrip("/")
        if not url.endswith("/metrics"):
            url += "/metrics"
        try:
            with urlopen(url, timeout=10) as resp:
                text = resp.read().decode()
        except Exception as e:
            print(f"metrics: cannot scrape {url} ({e})")
            return
        _print_serving_snapshot(text.splitlines())
        print(f"metrics (scraped from {url}):")
        for line in text.splitlines():
            if line and not line.startswith("#"):
                print(f"  {line}")
        return
    from predictionio_tpu.obs import get_registry

    metrics = get_registry().metrics()
    samples = [line for m in metrics for line in m.render()]
    if not samples:
        print("metrics: none recorded in this process "
              "(use --metrics-url http://HOST:PORT to scrape a server)")
        return
    _print_serving_snapshot(samples)
    print("metrics (this process):")
    for line in samples:
        print(f"  {line}")


_BREAKER_STATES = {0: "closed", 1: "half-open", 2: "open"}
_METRIC_LINE = None  # compiled lazily (keep the import-light CLI startup)


def _parse_metric_lines(lines):
    """(name, labels-dict, value) triples from Prometheus text lines."""
    import re

    global _METRIC_LINE
    if _METRIC_LINE is None:
        _METRIC_LINE = re.compile(
            r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
            r'(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)$')
    for line in lines:
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        # Strip OpenMetrics exemplar suffixes (pio_serve_stage_ms buckets
        # carry ` # {trace_id="..."} v` after the sample value).
        line = line.split(" # ", 1)[0].rstrip()
        m = _METRIC_LINE.match(line)
        if not m:
            continue
        labels = {}
        for part in (m.group("labels") or "").split(","):
            if "=" in part:
                k, _, v = part.partition("=")
                labels[k.strip()] = v.strip().strip('"')
        try:
            yield m.group("name"), labels, float(m.group("value"))
        except ValueError:
            continue


def _print_serving_snapshot(lines) -> None:
    """Model-lifecycle view for `pio status` (ISSUE 4 satellite): the
    serving generation, reload outcomes, and breaker states out of a
    metrics exposition — printed alongside the device-memory snapshot so
    one `pio status --metrics-url` answers "what model is live and is
    its storage healthy"."""
    generation = None
    reloads = {}
    breakers = {}
    watchdog = {}
    batcher = {}
    latest_ts = {}
    staleness = None
    refresh_runs = {}
    quality = {}
    recall = {}
    rcache = {}

    def _b(model):
        return batcher.setdefault(model, {})

    for name, labels, value in _parse_metric_lines(lines):
        if name == "pio_model_generation":
            generation = int(value)
        elif name == "pio_events_latest_ts":
            latest_ts[labels.get("app", "?")] = value
        elif name == "pio_refresh_staleness_s":
            staleness = value
        elif name == "pio_refresh_runs_total" and value > 0:
            refresh_runs[labels.get("result", "?")] = int(value)
        elif name == "pio_quality_drift":
            quality.setdefault("drift", {})[
                f"{labels.get('metric', '?')}_{labels.get('window', '?')}"
            ] = value
        elif name == "pio_quality_drift_tripped":
            quality["tripped"] = bool(value)
        elif name == "pio_quality_reporting_only" and value > 0:
            quality["reporting_only"] = True
        elif name == "pio_quality_shadow_overlap":
            quality["shadow_overlap"] = value
        elif name == "pio_quality_online_hit_rate":
            quality["hit_rate"] = value
        elif name == "pio_quality_gate_rollback":
            quality["gate_rollback"] = bool(value)
        elif name == "pio_quality_sampled_total" and value > 0:
            quality["sampled"] = int(value)
        elif name == "pio_retrieval_recall":
            if labels.get("window") == "fast":
                recall.setdefault("rungs", {})[
                    labels.get("rung", "?")] = value
                recall["k"] = labels.get("k", "?")
        elif name == "pio_retrieval_recall_baseline":
            recall.setdefault("baselines", {})[
                labels.get("rung", "?")] = value
        elif name == "pio_retrieval_recall_tripped" and value > 0:
            recall["tripped"] = True
        elif name == "pio_retrieval_recall_reporting_only" and value > 0:
            recall["reporting_only"] = True
        elif name == "pio_result_cache_hits_total":
            rcache["hits"] = rcache.get("hits", 0) + int(value)
        elif name == "pio_result_cache_misses_total":
            rcache["misses"] = int(value)
        elif name == "pio_result_cache_hit_rate":
            rcache["hit_rate"] = value
        elif name == "pio_result_cache_entries":
            rcache["entries"] = int(value)
        elif name == "pio_result_cache_bytes":
            rcache["bytes"] = int(value)
        elif name == "pio_result_cache_evictions_total" and value > 0:
            rcache["evictions"] = int(value)
        elif name == "pio_result_cache_shared_errors_total" and value > 0:
            rcache["shared_errors"] = int(value)
        elif name == "pio_model_reload_total":
            reloads[labels.get("result", "?")] = int(value)
        elif name == "pio_breaker_state":
            breakers[labels.get("breaker", "?")] = \
                _BREAKER_STATES.get(int(value), str(value))
        elif name == "pio_watchdog_fired_total" and value > 0:
            watchdog[labels.get("fn", "?")] = int(value)
        elif name == "pio_batch_window_ms":
            _b(labels.get("model", "?"))["window_ms"] = value
        elif name == "pio_batch_max_size":
            _b(labels.get("model", "?"))["max"] = int(value)
        elif name == "pio_queue_depth":
            _b(labels.get("model", "?"))["queued"] = int(value)
        elif name == "pio_batch_dispatch_total":
            _b(labels.get("model", "?"))["dispatches"] = int(value)
        elif name == "pio_batch_requests_total":
            _b(labels.get("model", "?"))["requests"] = int(value)
        elif name == "pio_queue_rejected_total" and value > 0:
            _b(labels.get("model", "?"))["rejected"] = int(value)
        elif name == "pio_queue_shed_total" and value > 0:
            shed = _b(labels.get("model", "?")).setdefault("shed", {})
            shed[labels.get("reason", "?")] = int(value)
    if generation is None and not reloads and not breakers and not batcher \
            and not latest_ts and not refresh_runs and staleness is None \
            and not quality and not recall and not rcache:
        return
    if generation is not None:
        print(f"serving: model generation {generation}")
    # Freshness (ISSUE 10): ingest high-watermark per app + the refresh
    # loop's event→servable staleness, when the scraped process runs it.
    for app, ts in sorted(latest_ts.items()):
        iso = _dt.datetime.fromtimestamp(
            ts, tz=_dt.timezone.utc).isoformat(timespec="seconds")
        print(f"  events latest [app {app}]: {iso}")
    if staleness is not None:
        print(f"  refresh staleness: {staleness:g}s event→servable")
    if refresh_runs:
        parts = ", ".join(f"{k}={v}" for k, v in sorted(refresh_runs.items()))
        print(f"  refresh runs: {parts}")
    # Model quality (ISSUE 11): drift vs the training scorecard, shadow
    # canary overlap, online hit-rate, and the promotion-gate verdict.
    if quality:
        parts = []
        drift = quality.get("drift", {})
        if "psi_fast" in drift or "psi_slow" in drift:
            parts.append(f"psi fast={drift.get('psi_fast', 0):.3f}"
                         f"/slow={drift.get('psi_slow', 0):.3f}")
        if quality.get("tripped"):
            parts.append("DRIFT TRIPPED")
        if quality.get("reporting_only"):
            parts.append("reporting-only (no trusted scorecard)")
        if "shadow_overlap" in quality:
            parts.append(f"shadow overlap {quality['shadow_overlap']:.2f}")
        if "hit_rate" in quality:
            parts.append(f"online hit-rate {quality['hit_rate']:.3f}")
        if quality.get("gate_rollback"):
            parts.append("GATE=ROLLBACK")
        if "sampled" in quality:
            parts.append(f"sampled {quality['sampled']}")
        if parts:
            print(f"  quality: {', '.join(parts)}")
    # Retrieval recall (ISSUE 16): live sampled recall@k per approximate
    # rung vs the generation's own baked baseline.
    if recall:
        parts = []
        baselines = recall.get("baselines", {})
        for rung, v in sorted(recall.get("rungs", {}).items()):
            b = baselines.get(rung)
            parts.append(f"{rung} {v:.3f}"
                         + (f" (baseline {b:.3f})" if b is not None
                            else ""))
        if recall.get("tripped"):
            parts.append("RECALL TRIPPED")
        if recall.get("reporting_only"):
            parts.append("reporting-only (no trusted recall scorecard)")
        if parts:
            k = recall.get("k", "?")
            print(f"  recall@{k}: {', '.join(parts)}")
    # Result cache (ISSUE 20): the serve fast path — hit rate, residency,
    # and whether the shared tier is degrading to local-only.
    if rcache:
        parts = []
        if "hit_rate" in rcache:
            parts.append(f"hit-rate {rcache['hit_rate']:.3f}")
        if "hits" in rcache or "misses" in rcache:
            parts.append(f"hits {rcache.get('hits', 0)}"
                         f"/misses {rcache.get('misses', 0)}")
        if "entries" in rcache:
            parts.append(f"entries {rcache['entries']}")
        if "bytes" in rcache:
            parts.append(f"{rcache['bytes'] / 1024:.0f}KiB")
        if rcache.get("evictions"):
            parts.append(f"evictions {rcache['evictions']}")
        if rcache.get("shared_errors"):
            parts.append(f"SHARED-TIER ERRORS {rcache['shared_errors']}")
        if parts:
            print(f"  result cache: {', '.join(parts)}")
    if reloads:
        parts = ", ".join(f"{k}={v}" for k, v in sorted(reloads.items()))
        print(f"  model reloads: {parts}")
    for b, st in sorted(breakers.items()):
        print(f"  breaker [{b}]: {st}")
    for fn, n in sorted(watchdog.items()):
        print(f"  watchdog fired [{fn}]: {n}")
    # Batcher snapshot (ISSUE 6): coalescing health per model lane.
    for model, row in sorted(batcher.items()):
        disp, reqs = row.get("dispatches", 0), row.get("requests", 0)
        parts = [f"window {row.get('window_ms', 0):g}ms",
                 f"max {row.get('max', '?')}",
                 f"queued {row.get('queued', 0)}",
                 f"requests {reqs}", f"dispatches {disp}"]
        if disp:
            parts.append(f"mean batch {reqs / disp:.2f}")
        if row.get("rejected"):
            parts.append(f"rejected(429) {row['rejected']}")
        for reason, n in sorted(row.get("shed", {}).items()):
            parts.append(f"shed[{reason}] {n}")
        print(f"  batcher [{model}]: {', '.join(parts)}")


# --------------------------------------------------------------------------
# pio app ...
# --------------------------------------------------------------------------

def cmd_app_new(args) -> int:
    from predictionio_tpu.data.storage import AccessKey, App

    s = _storage()
    app_id = s.get_apps().insert(App(id=None, name=args.name, description=args.description))
    if app_id is None:
        _die(f"App {args.name!r} already exists.")
    s.get_events().init(app_id)
    key = s.get_access_keys().insert(AccessKey(key=args.access_key or "", app_id=app_id))
    print("Created a new app:")
    print(f"      Name: {args.name}")
    print(f"        ID: {app_id}")
    print(f"Access Key: {key}")
    return 0


def cmd_app_list(args) -> int:
    s = _storage()
    apps = s.get_apps().get_all()
    keys = s.get_access_keys()
    print(f"{'Name':20} {'ID':>4}  Access Key")
    for app in apps:
        ks = keys.get_by_app_id(app.id)
        first = ks[0].key if ks else "-"
        print(f"{app.name:20} {app.id:>4}  {first}")
    print(f"Finished listing {len(apps)} app(s).")
    return 0


def cmd_app_delete(args) -> int:
    s = _storage()
    app = s.get_apps().get_by_name(args.name)
    if app is None:
        _die(f"App {args.name!r} does not exist.")
    if not args.force:
        ans = input(f"Delete app {args.name!r} and ALL its data? (YES to confirm): ")
        if ans.strip() != "YES":
            print("Aborted.")
            return 1
    for ch in s.get_channels().get_by_app_id(app.id):
        s.get_events().remove(app.id, ch.id)
        s.get_channels().delete(ch.id)
    s.get_events().remove(app.id)
    for k in s.get_access_keys().get_by_app_id(app.id):
        s.get_access_keys().delete(k.key)
    s.get_apps().delete(app.id)
    print(f"Deleted app {args.name}.")
    return 0


def cmd_app_data_delete(args) -> int:
    s = _storage()
    app = s.get_apps().get_by_name(args.name)
    if app is None:
        _die(f"App {args.name!r} does not exist.")
    channel_id = None
    if args.channel:
        chans = s.get_channels().get_by_app_id(app.id)
        ch = next((c for c in chans if c.name == args.channel), None)
        if ch is None:
            _die(f"Channel {args.channel!r} does not exist in app {args.name!r}.")
        channel_id = ch.id
    if not args.force:
        where = f"channel {args.channel!r} of " if args.channel else ""
        ans = input(f"Delete all event data of {where}app {args.name!r}? (YES to confirm): ")
        if ans.strip() != "YES":
            print("Aborted.")
            return 1
    ev = s.get_events()
    ev.remove(app.id, channel_id)
    ev.init(app.id, channel_id)
    print("Event data deleted.")
    return 0


def cmd_app_channel_new(args) -> int:
    from predictionio_tpu.data.storage import Channel

    s = _storage()
    app = s.get_apps().get_by_name(args.app)
    if app is None:
        _die(f"App {args.app!r} does not exist.")
    cid = s.get_channels().insert(Channel(id=None, name=args.channel, app_id=app.id))
    if cid is None:
        _die(f"Invalid or duplicate channel name {args.channel!r} "
             "(1-16 chars, [a-zA-Z0-9-]).")
    s.get_events().init(app.id, cid)
    print(f"Created channel {args.channel} (ID {cid}) in app {args.app}.")
    return 0


def cmd_app_channel_delete(args) -> int:
    s = _storage()
    app = s.get_apps().get_by_name(args.app)
    if app is None:
        _die(f"App {args.app!r} does not exist.")
    ch = next((c for c in s.get_channels().get_by_app_id(app.id)
               if c.name == args.channel), None)
    if ch is None:
        _die(f"Channel {args.channel!r} does not exist in app {args.app!r}.")
    s.get_events().remove(app.id, ch.id)
    s.get_channels().delete(ch.id)
    print(f"Deleted channel {args.channel} from app {args.app}.")
    return 0


# --------------------------------------------------------------------------
# pio accesskey ...
# --------------------------------------------------------------------------

def cmd_accesskey_new(args) -> int:
    from predictionio_tpu.data.storage import AccessKey

    s = _storage()
    app = s.get_apps().get_by_name(args.app)
    if app is None:
        _die(f"App {args.app!r} does not exist.")
    key = s.get_access_keys().insert(
        AccessKey(key="", app_id=app.id, events=tuple(args.events))
    )
    print(f"Created new access key: {key}")
    if args.events:
        print(f"  (restricted to events: {', '.join(args.events)})")
    return 0


def cmd_accesskey_list(args) -> int:
    s = _storage()
    keys = s.get_access_keys()
    if args.app:
        app = s.get_apps().get_by_name(args.app)
        if app is None:
            _die(f"App {args.app!r} does not exist.")
        rows = keys.get_by_app_id(app.id)
    else:
        rows = keys.get_all()
    for k in rows:
        ev = ",".join(k.events) if k.events else "(all)"
        print(f"{k.key}  app={k.app_id}  events={ev}")
    print(f"Finished listing {len(rows)} access key(s).")
    return 0


def cmd_accesskey_delete(args) -> int:
    if not _storage().get_access_keys().delete(args.key):
        _die(f"Access key {args.key!r} does not exist.")
    print("Deleted access key.")
    return 0


# --------------------------------------------------------------------------
# pio train / eval
# --------------------------------------------------------------------------

def _resolve_backend():
    """Start-up line of every verb that computes (train / eval / deploy /
    batchpredict): place the compile cache, log platform / device_kind /
    count, and stop if jax quietly fell back to the CPU on a TPU host."""
    from predictionio_tpu.backend import BackendError, resolve_backend

    try:
        return resolve_backend()
    except BackendError as e:
        _die(str(e))


def _log_compile_stats() -> None:
    from predictionio_tpu.backend import compile_stats

    st = compile_stats()
    logger.info("backend: compile_seconds=%.1f compiles=%d "
                "compile_cache_hits=%d", st["compileSeconds"],
                st["compiles"], st["compileCacheHits"])


def cmd_train(args) -> int:
    from predictionio_tpu.controller import EngineVariant, RuntimeContext, load_engine_factory
    from predictionio_tpu.parallel.distributed import initialize_distributed
    from predictionio_tpu.resilience.supervision import (
        PREEMPTED_EXIT_CODE,
        TrainPreempted,
        install_preemption_handler,
    )
    from predictionio_tpu.workflow import run_train

    initialize_distributed()
    _resolve_backend()
    # SIGTERM during training → final checkpoint + exit 143 (preemption
    # contract, README "Training supervision"): the supervisor's rerun
    # resumes via --checkpoint-dir.
    install_preemption_handler()
    if getattr(args, "checkpoint_dir", None):
        if args.checkpoint_every <= 0:
            _die("--checkpoint-dir requires --checkpoint-every N (the save "
                 "cadence); without it no checkpoints would be written and "
                 "a killed train could not resume.")
        os.environ["PIO_CHECKPOINT_DIR"] = args.checkpoint_dir
        os.environ["PIO_CHECKPOINT_EVERY"] = str(args.checkpoint_every)
    elif getattr(args, "checkpoint_every", 0) > 0:
        _die("--checkpoint-every requires --checkpoint-dir DIR (where to "
             "save); without it no checkpoints would be written.")
    if getattr(args, "prefetch_depth", 0) > 0:
        # Overlapped input pipeline (data/prefetch.py): the deep-model
        # train loops read this when constructing their DevicePrefetcher.
        os.environ["PIO_PREFETCH_DEPTH"] = str(args.prefetch_depth)
    if getattr(args, "fuse_steps", None):
        # K-step fused dispatch (data/fusion.py): an int pins the scan
        # depth, "auto" hands it to the HBM-guided autotuner.
        text = str(args.fuse_steps).strip().lower()
        if text != "auto":
            try:
                if int(text) < 1:
                    _die("--fuse-steps must be a positive integer or "
                         "'auto'.")
            except ValueError:
                _die(f"--fuse-steps {args.fuse_steps!r} is neither an "
                     "integer nor 'auto'.")
        os.environ["PIO_FUSE_STEPS"] = text
    if getattr(args, "batch_autoscale", False):
        # Opt-in: wider (concatenated) optimizer steps once fusion depth
        # caps out — a semantics change, so never on by default.
        os.environ["PIO_BATCH_AUTOSCALE"] = "on"
    if getattr(args, "pq", None):
        # Quantized-corpus build policy (retrieval/pq.py): templates
        # read PIO_PQ at train time when deciding whether to serialize
        # residual codes next to the IVF index.
        text = str(args.pq).strip().lower()
        if text not in ("auto", "on", "off"):
            _die(f"--pq {args.pq!r} must be auto|on|off.")
        os.environ["PIO_PQ"] = text
    if getattr(args, "pq_m", 0):
        if args.pq_m < 1:
            _die("--pq-m must be a positive integer (subspace count).")
        os.environ["PIO_PQ_M"] = str(args.pq_m)
    variant_path = Path(args.engine_json)
    if not variant_path.exists():
        _die(f"{variant_path} not found (expected an engine.json).")
    variant = EngineVariant.from_file(variant_path)
    engine = load_engine_factory(variant.engine_factory)()
    ctx = RuntimeContext.create(seed=args.seed, mesh_spec=args.mesh)
    if ctx.mesh is not None:
        print(f"Mesh: {dict(ctx.mesh.shape)} over {ctx.mesh.devices.size} device(s)")
    if getattr(args, "follow", False):
        return _train_follow(args, engine, variant, ctx)
    try:
        instance_id = run_train(engine, variant, ctx)
    except TrainPreempted as e:
        print(f"[preempted] {e}", file=sys.stderr)
        print("[preempted] rerun the same `pio train` command to resume.",
              file=sys.stderr)
        return PREEMPTED_EXIT_CODE
    _log_compile_stats()
    print(f"Training completed. Engine instance ID: {instance_id}")
    return 0


def _train_follow(args, engine, variant, ctx) -> int:
    """`pio train --follow` (ISSUE 10): the continuous-refresh daemon.

    Retrains on a cadence — delta warm-start when the last generation
    carries a watermark and continuable state, full retrain otherwise —
    and, with --promote-url / PIO_REFRESH_PROMOTE_URL, promotes each
    generation through the serving server's staged-reload canary gate
    (rolling back if the SLO burn trips inside the canary window).
    SIGTERM/SIGINT stop the loop; one mid-train exits with the
    preemption contract (checkpoint + exit 143) like any other train."""
    import signal

    from predictionio_tpu.refresh import RefreshConfig
    from predictionio_tpu.refresh.daemon import RefreshDaemon
    from predictionio_tpu.resilience.supervision import (
        PREEMPTED_EXIT_CODE,
        TrainPreempted,
        request_preemption,
    )

    cfg = RefreshConfig.from_env(
        interval_s=getattr(args, "refresh_interval", None),
        promote_url=getattr(args, "promote_url", None),
        canary_window_s=getattr(args, "canary_window", None),
        trigger_staleness_s=getattr(args, "trigger_staleness", None),
        trigger_delta_count=getattr(args, "trigger_delta_count", None),
    )
    daemon = RefreshDaemon(engine, variant, ctx, config=cfg)

    def _stop(signum, frame):
        print(f"[follow] signal {signum}: stopping after the current "
              "cycle (mid-train: checkpoint + resume semantics apply)",
              file=sys.stderr)
        request_preemption()   # an in-flight train checkpoints and exits
        daemon.stop()          # the between-cycles wait wakes immediately

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _stop)
        except (ValueError, OSError):
            continue
    where = f", promoting via {cfg.promote_url}" if cfg.promote_url else \
        " (no promote URL — serving servers reload on their own)"
    if cfg.trigger_staleness_s is not None \
            or cfg.trigger_delta_count is not None:
        trig = []
        if cfg.trigger_staleness_s is not None:
            trig.append(f"staleness≥{cfg.trigger_staleness_s:g}s")
        if cfg.trigger_delta_count is not None:
            trig.append(f"delta≥{cfg.trigger_delta_count} events")
        print(f"Refresh daemon: trigger mode ({' or '.join(trig)}, "
              f"backstop every {cfg.interval_s:g}s){where}. Ctrl-C to "
              "stop.")
    else:
        print(f"Refresh daemon: retraining every {cfg.interval_s:g}s"
              f"{where}. Ctrl-C to stop.")
    try:
        cycles = daemon.follow()
    except TrainPreempted as e:
        print(f"[preempted] {e}", file=sys.stderr)
        return PREEMPTED_EXIT_CODE
    print(f"Refresh daemon stopped after {cycles} cycle(s).")
    return 0


def cmd_eval(args) -> int:
    from predictionio_tpu.controller import load_engine_factory, RuntimeContext
    from predictionio_tpu.parallel.distributed import initialize_distributed
    from predictionio_tpu.resilience.supervision import (
        PREEMPTED_EXIT_CODE,
        TrainPreempted,
        install_preemption_handler,
    )
    from predictionio_tpu.workflow import run_evaluation

    initialize_distributed()
    _resolve_backend()
    # Same preemption contract as training (ISSUE 15 satellite): SIGTERM
    # checkpoints the sweep at the current (candidate, fold) boundary and
    # exits 143; rerunning the same command resumes.
    install_preemption_handler()
    evaluation = load_engine_factory(args.evaluation_class)()
    generator = load_engine_factory(args.params_generator_class)()
    ctx = RuntimeContext.create(seed=args.seed, mesh_spec=args.mesh)
    try:
        instance_id, result = run_evaluation(
            evaluation,
            generator,
            ctx,
            evaluation_class=args.evaluation_class,
            params_generator_class=args.params_generator_class,
            checkpoint_dir=getattr(args, "checkpoint_dir", None),
        )
    except TrainPreempted as e:
        print(f"[preempted] {e}", file=sys.stderr)
        print("[preempted] rerun the same `pio eval` command to resume "
              "from the checkpointed folds.", file=sys.stderr)
        return PREEMPTED_EXIT_CODE
    print(result.summary())
    print(f"Evaluation instance ID: {instance_id}")
    if args.output_json:
        inst = ctx.storage.get_evaluation_instances().get(instance_id)
        Path(args.output_json).write_text(inst.evaluator_results_json)
        print(f"Results written to {args.output_json}")
    return 0


def cmd_build(args) -> int:
    """Reference: `pio build` compiles the engine via sbt; with a Python
    engine there is nothing to compile, so this validates instead: the
    engine.json parses, the factory imports, params bind, and (with
    --compile-check) the flagship predict path traces under jit."""
    from predictionio_tpu.controller import EngineVariant, load_engine_factory

    variant_path = Path(args.engine_json)
    if not variant_path.exists():
        _die(f"{variant_path} not found (expected an engine.json).")
    variant = EngineVariant.from_file(variant_path)
    engine = load_engine_factory(variant.engine_factory)()
    params = engine.bind_engine_params(variant.raw)
    n_algos = len(params.algorithms_params)
    print(f"Engine factory {variant.engine_factory} OK "
          f"({n_algos} algorithm(s): "
          f"{', '.join(n for n, _ in params.algorithms_params)}).")
    print("Engine variant params bind cleanly. Build successful.")
    return 0


# --------------------------------------------------------------------------
# pio eventserver / deploy / dashboard
# --------------------------------------------------------------------------

def _install_drain_handlers(drain) -> None:
    """SIGTERM/SIGINT → graceful drain: stop accepting, finish in-flight
    requests, flush the spill journal — a k8s rolling restart must not
    lose events that were already 202-accepted."""
    import signal

    def _handler(signum, frame):
        logger.info("signal %d: draining", signum)
        try:
            drain()
        except Exception:
            # exit NON-zero with the traceback logged: a failed drain
            # (e.g. spill flush on a full disk) must not look clean to
            # the supervisor that sent the signal
            logger.exception("drain failed")
            raise SystemExit(1) from None
        raise SystemExit(0)

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _handler)
        except (ValueError, OSError):  # non-main thread / exotic platform
            continue


def cmd_eventserver(args) -> int:
    import time as _time

    from predictionio_tpu.server import EventServer

    srv = EventServer(storage=_storage(), host=args.ip, port=args.port)
    if getattr(args, "native", False):
        # C++ continuous-batching frontend: concurrent single-event POSTs
        # aggregate into ONE group-committed insert per callback.
        from predictionio_tpu.native.frontend import NativeFrontend

        fe = NativeFrontend(None, host=args.ip, port=args.port,
                            fallback_batch=srv.native_fallback_batch,
                            plugin_hook=(srv.plugins.header_block
                                         if srv.plugins else None))
        fe.start()

        def _drain_native():
            fe.stop()
            srv.drain()

        _install_drain_handlers(_drain_native)
        print(f"Event Server (native frontend) listening on "
              f"{args.ip}:{fe.port} (Ctrl-C to stop)")
        try:
            while True:
                _time.sleep(3600)
        except KeyboardInterrupt:
            _drain_native()
        return 0
    _install_drain_handlers(srv.drain)
    srv.start(block=False)
    print(f"Event Server listening on {args.ip}:{srv.port} "
          "(Ctrl-C to stop)")
    try:
        srv._thread.join()
    except KeyboardInterrupt:
        srv.drain()
    return 0


def cmd_deploy(args) -> int:
    from predictionio_tpu.controller import EngineVariant, load_engine_factory
    from predictionio_tpu.parallel.distributed import initialize_distributed
    from predictionio_tpu.server import EngineServer
    from predictionio_tpu.serving import SchedulerConfig

    initialize_distributed()
    _resolve_backend()
    variant_path = Path(args.engine_json)
    if not variant_path.exists():
        _die(f"{variant_path} not found (expected an engine.json).")
    variant = EngineVariant.from_file(variant_path)
    engine = load_engine_factory(variant.engine_factory)()
    # Serving-scheduler knobs: flags override the PIO_BATCH_*/PIO_QUEUE_*
    # env (SchedulerConfig.from_env ignores None overrides).
    sched_cfg = SchedulerConfig.from_env(
        enabled=False if args.no_batcher else None,
        window_ms=args.batch_window_ms,
        queue_depth=args.queue_depth,
        max_batch=args.max_batch,
        p99_target_ms=args.batch_p99_target_ms,
    )
    srv = EngineServer(
        engine, variant, _storage(), host=args.ip, port=args.port,
        instance_id=args.engine_instance_id, mesh_spec=args.mesh,
        scheduler_config=sched_cfg,
    )
    if args.native:
        from predictionio_tpu.native.frontend import NativeFrontend

        import threading as _threading

        stop_event = _threading.Event()

        def engine_fallback(method, path_with_qs, body):
            # Non-query routes (/reload and friends) keep working behind
            # the native frontend — the reference's deploy server
            # supports hot-reload after retrain (SURVEY §3.2).  GET /
            # and GET /metrics stay C++-answered in deploy mode
            # (frontend liveness + batching counters).  /stop
            # must stop the FRONTEND, and not from inside its own
            # callback thread (pio_frontend_stop joins the batchers):
            # answer first, signal the main loop to tear down.
            path = path_with_qs.split("?", 1)[0]
            if path == "/stop" and method == "POST":
                stop_event.set()
                return 200, {"status": "stopping"}
            return srv.handle(method, path, body)

        # Same batch ceiling as the scheduler config (flag beats
        # PIO_BATCH_MAX beats 64) — one knob, both batching stacks.
        fe = NativeFrontend(srv.query_batch, host=args.ip, port=args.port,
                            max_batch=sched_cfg.max_batch,
                            max_wait_us=args.max_wait_us,
                            fallback=engine_fallback,
                            plugin_hook=(srv.plugins.header_block
                                         if srv.plugins else None))
        def _drain_native_deploy():
            fe.stop()
            srv.plugins.stop()

        _install_drain_handlers(_drain_native_deploy)
        port = fe.start()
        print(f"Native engine frontend on {args.ip}:{port} "
              f"(instance {srv._instance.id}; continuous batching "
              f"≤{sched_cfg.max_batch}; Ctrl-C to stop)")
        try:
            stop_event.wait()
        except KeyboardInterrupt:
            pass
        fe.stop()
        srv.plugins.stop()
        return 0
    _install_drain_handlers(srv.stop)
    srv.start(block=False)
    print(f"Engine Server listening on {args.ip}:{srv.port} "
          f"(instance {srv._instance.id}; Ctrl-C to stop)")
    try:
        srv._thread.join()
    except KeyboardInterrupt:
        srv.stop()
    return 0


def cmd_batchpredict(args) -> int:
    """Reference: `pio batchpredict` (0.13+) — bulk queries from NDJSON.

    Uses the EngineServer's batched path so the whole file is answered in
    vectorized XLA chunks, not per-line predicts.
    """
    from predictionio_tpu.controller import EngineVariant, load_engine_factory
    from predictionio_tpu.parallel.distributed import initialize_distributed
    from predictionio_tpu.server import EngineServer

    initialize_distributed()
    _resolve_backend()
    variant_path = Path(args.engine_json)
    if not variant_path.exists():
        _die(f"{variant_path} not found (expected an engine.json).")
    variant = EngineVariant.from_file(variant_path)
    engine = load_engine_factory(variant.engine_factory)()
    srv = EngineServer(engine, variant, _storage(),
                       instance_id=args.engine_instance_id,
                       mesh_spec=args.mesh)
    queries = []
    with open(args.input) as f:
        for line_no, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                queries.append(json.loads(line))
            except json.JSONDecodeError as e:
                _die(f"{args.input}:{line_no}: {e}")
    n = 0
    with open(args.output, "w") as out:
        for start in range(0, len(queries), args.query_partitions):
            chunk = queries[start:start + args.query_partitions]
            for q, r in zip(chunk, srv.query_batch(chunk)):
                out.write(json.dumps({"query": q, "prediction": r}) + "\n")
                n += 1
    print(f"Wrote {n} predictions to {args.output}.")
    return 0


def cmd_adminserver(args) -> int:
    from predictionio_tpu.server.admin import AdminServer

    srv = AdminServer(storage=_storage(), host=args.ip, port=args.port)
    srv.start(block=False)
    print(f"Admin server listening on {args.ip}:{srv.port} (Ctrl-C to stop)")
    try:
        srv._thread.join()
    except KeyboardInterrupt:
        srv.stop()
    return 0


def cmd_template_get(args) -> int:
    """Reference: `pio template get <gallery-repo> <dir>` scaffolds a new
    engine from the template gallery.  The rebuild's gallery is the
    source checkout's examples/<name>; this copies the engine.json +
    quickstart into the target directory, ready for `pio build` /
    `pio train`."""
    import shutil

    gallery = Path(__file__).resolve().parents[2] / "examples"
    if not gallery.is_dir():
        # pip wheels ship only predictionio_tpu/*; the scaffold gallery
        # lives in the source checkout.
        _die("No template gallery in this installation (pip wheels ship "
             "only the package) — run from a source checkout, which has "
             "examples/<template>/engine.json scaffolds.")
    name = args.template.rstrip("/").split("/")[-1]  # accept repo-ish paths
    src = gallery / name
    if not src.is_dir():
        avail = sorted(d.name for d in gallery.iterdir()
                       if d.is_dir() and not d.name.startswith("_"))
        _die(f"Unknown template {name!r}. Available: {', '.join(avail)}")
    dst = Path(args.directory)
    if dst.exists() and (not dst.is_dir() or any(dst.iterdir())):
        _die(f"{dst} exists and is not empty.")
    shutil.copytree(src, dst, dirs_exist_ok=True)
    print(f"Template {name!r} copied to {dst}/")
    for f in sorted(p.name for p in dst.iterdir()):
        print(f"  {f}")
    print("Next: edit engine.json (appName), then `pio train` there.")
    return 0


def cmd_shell(args) -> int:
    """Reference: `pio-shell` (a spark-shell with the pio jars).  Here: a
    Python REPL with the storage, config, and template modules preloaded."""
    import code

    from predictionio_tpu import config as pio_config
    from predictionio_tpu.data.storage import get_storage

    storage = get_storage()
    banner = (
        f"predictionio_tpu shell\n"
        f"  storage  -> {type(storage).__name__} "
        f"({storage.config.repositories['METADATA'].source} metadata)\n"
        f"  apps     -> storage.get_apps().get_all()\n"
        f"  events   -> storage.get_events()\n"
        f"Modules: predictionio_tpu (pio), numpy (np), jax, jax.numpy (jnp)"
    )
    import jax
    import jax.numpy as jnp
    import numpy as np

    import predictionio_tpu as pio

    code.interact(banner=banner, local={
        "storage": storage, "pio": pio, "np": np, "jax": jax, "jnp": jnp,
        "config": pio_config,
    })
    return 0


def cmd_storageserver(args) -> int:
    """Host this process's configured storage over TCP (data/storage/remote.py)
    so OTHER processes can select it with type=pioserver — the reference's
    network-storage deployment shape (JDBC/HBase/ES) without their servers."""
    from predictionio_tpu.data.storage.remote import StorageServer

    secret = args.secret or os.environ.get("PIO_STORAGE_SERVER_SECRET")
    srv = StorageServer(_storage(), host=args.ip, port=args.port,
                        secret=secret)
    srv.start()
    print(f"Storage server listening on {args.ip}:{srv.port} (Ctrl-C to stop)")
    print("Clients: PIO_STORAGE_SOURCES_REMOTE_TYPE=pioserver "
          f"PIO_STORAGE_SOURCES_REMOTE_HOSTS={args.ip} "
          f"PIO_STORAGE_SOURCES_REMOTE_PORTS={srv.port} "
          "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE=REMOTE")
    try:
        srv._thread.join()
    except KeyboardInterrupt:
        srv.stop()
    return 0


def cmd_profile(args) -> int:
    """On-demand profiler capture (obs.profiler).

    With --url, arms the capture on a RUNNING admin server
    (POST /admin/profile) and returns immediately — the artifact lands on
    the server's disk.  Without it, captures THIS process for the window
    (mostly useful under `pio shell` or to smoke-test the platform)."""
    duration_ms = args.duration_ms
    if args.url:
        from urllib.error import HTTPError
        from urllib.request import Request, urlopen

        base = args.url.rstrip("/")
        url = base + f"/admin/profile?duration_ms={duration_ms:g}"
        try:
            with urlopen(Request(url, method="POST"), timeout=30) as resp:
                body = json.loads(resp.read() or b"{}")
        except HTTPError as e:
            payload = e.read()
            try:
                msg = json.loads(payload).get("message", "")
            except Exception:
                msg = payload.decode(errors="replace")[:200]
            _die(f"profile request failed: HTTP {e.code}: {msg}")
        except OSError as e:
            _die(f"cannot reach {args.url}: {e}")
        print(f"Profiling for {body.get('durationMs', duration_ms):g} ms; "
              f"artifacts: {body.get('path')}")
        if not args.out:
            print("(view in TensorBoard/XProf or chrome://tracing once "
                  "the window closes; --out FILE downloads the archive)")
            return 0
        # ISSUE 9 satellite: the capture path above is SERVER-local —
        # wait the window out, then pull the archive down over HTTP so
        # remote/fleet operation never needs box access.
        import time as _time

        _time.sleep(float(body.get("durationMs", duration_ms)) / 1e3)
        deadline = _time.monotonic() + 30
        while _time.monotonic() < deadline:
            try:
                with urlopen(base + "/admin/profile", timeout=10) as resp:
                    if not json.loads(resp.read() or b"{}").get("active"):
                        break
            except OSError:
                pass
            _time.sleep(0.25)
        try:
            with urlopen(base + "/admin/profile/artifact",
                         timeout=60) as resp:
                data = resp.read()
                disposition = resp.headers.get("Content-Disposition", "")
        except HTTPError as e:
            _die(f"artifact download failed: HTTP {e.code}")
        except OSError as e:
            _die(f"artifact download failed: {e}")
        out = Path(args.out)
        if out.is_dir():
            # The server names the archive after its capture dir
            # (Content-Disposition); fall back to a stable default.
            m = re.search(r'filename="([^"/\\]+)"', disposition)
            out = out / (m.group(1) if m else "pio_profile.tar.gz")
        out.write_bytes(data)
        print(f"Profile archive downloaded: {out} ({len(data):,} bytes)")
        return 0
    from predictionio_tpu.obs.profiler import ProfilerUnavailable, capture

    try:
        path = capture(duration_ms, args.out)
    except ValueError as e:  # bad --duration-ms: same clean error as --url
        _die(str(e))
    except ProfilerUnavailable as e:
        _die(f"this platform cannot capture a profile: {e}")
    print(f"Profile captured: {path}")
    return 0


def cmd_dashboard(args) -> int:
    from predictionio_tpu.server.dashboard import DashboardServer

    fleet = ([u.strip() for u in args.fleet.split(",") if u.strip()]
             if getattr(args, "fleet", None) else None)
    srv = DashboardServer(storage=_storage(), host=args.ip, port=args.port,
                          fleet=fleet)
    srv.start(block=False)
    print(f"Dashboard listening on {args.ip}:{srv.port} (Ctrl-C to stop)")
    try:
        srv._thread.join()
    except KeyboardInterrupt:
        srv.stop()
    return 0


# --------------------------------------------------------------------------
# pio rollout — coordinated wave promotion across a fleet (ISSUE 15)
# --------------------------------------------------------------------------

def cmd_rollout(args) -> int:
    from predictionio_tpu.fleet import RolloutConfig, RolloutController
    from predictionio_tpu.obs.fleet import fleet_instances_from_env

    urls = ([u.strip().rstrip("/") for u in args.instances.split(",")
             if u.strip()] if args.instances
            else fleet_instances_from_env())
    if not urls:
        _die("no instances (--instances URL,URL or PIO_FLEET_INSTANCES)")
    cfg = RolloutConfig.from_env(
        waves=args.waves, bake_s=args.bake_s, poll_s=args.poll_s,
        state_path=args.state)
    ctl = RolloutController(urls, cfg)
    if args.resume or args.unwind:
        try:
            state = ctl.resume(unwind=args.unwind)
        except RuntimeError as e:
            _die(str(e))
    else:
        prior = ctl.load_state()
        if prior and prior.get("status") in ("in_progress",
                                             "rolling_back"):
            _die(f"rollout {prior.get('rolloutId')} is journaled "
                 f"{prior.get('status')!r} at {ctl.state_path} — finish "
                 "it first (--resume to continue, --unwind to roll it "
                 "back)")
        state = ctl.run(args.engine_instance_id)
    print(f"rollout {state.get('rolloutId')}: {state['status']} "
          f"(target instance {state.get('target')})")
    print(f"  promoted: {len(state.get('promoted', []))}/"
          f"{len(state.get('instances', []))} instance(s)"
          + (f" through wave {state.get('wave')}"
             if state.get('wave') is not None else ""))
    for url, why in (state.get("skipped") or {}).items():
        print(f"  skipped {url}: {why}")
    if state.get("haltReason"):
        print(f"  halt: {state['haltReason']}")
    for url in state.get("rolledBack", []):
        print(f"  rolled back {url}")
    for url, why in (state.get("unwindFailures") or {}).items():
        print(f"  UNWIND FAILED {url}: {why} — roll this instance back "
              "by hand (POST /admin/rollback)")
    print(f"  state journal: {ctl.state_path}")
    # An explicitly requested unwind that rolled every instance back IS
    # the success case; a rollout (or resumed rollout) that got halted
    # and rolled back is not.
    ok = state["status"] == "promoted" or (
        args.unwind and state["status"] == "rolled_back"
        and not state.get("unwindFailures"))
    return 0 if ok else 1


# --------------------------------------------------------------------------
# pio spill — manual spill-journal operations (ISSUE 4 satellite: the
# stopgap for ROADMAP resilience follow-on (b) until shared-queue spill)
# --------------------------------------------------------------------------

def _spill_dir(args) -> "Path":
    from predictionio_tpu.config import load_config
    from predictionio_tpu.resilience.spill import resolve_spill_dir

    d = resolve_spill_dir(getattr(args, "dir", None), load_config().home)
    if d is None:
        _die("spilling is disabled (PIO_SPILL_DIR=off and no --dir given).")
    return d


def _spill_cli_backend(args) -> str:
    """local|shared for the spill verbs: --backend > PIO_SPILL_BACKEND >
    auto (shared only on a pioserver EVENTDATA source)."""
    from predictionio_tpu.resilience.shared_spill import (
        resolve_spill_backend,
    )

    try:
        ev_type = _storage().config.source_for("EVENTDATA").type
    except Exception:
        ev_type = None
    return resolve_spill_backend(getattr(args, "backend", None), ev_type)


def _shared_spill(args):
    from predictionio_tpu.data.storage import StorageError
    from predictionio_tpu.resilience.shared_spill import SharedSpillQueue

    try:
        storage = _storage()
        storage.get_spill_queues()  # probe support
    except StorageError as e:
        _die(f"shared spill queue unavailable on this storage: {e}")
    return SharedSpillQueue(storage)


def cmd_spill_inspect(args) -> int:
    from predictionio_tpu.resilience.spill import journal_summary

    if _spill_cli_backend(args) == "shared":
        q = _shared_spill(args)
        st = q.stats()
        print(f"shared spill queue [{q.queue}] "
              f"(storage-backed, fleet-wide):")
        print(f"  pending: {st.get('pending', 0)} record(s) / "
              f"{st.get('pendingEvents', 0)} event(s)")
        print(f"  leased: {st.get('leased', 0)} record(s) "
              f"({st.get('expired', 0)} with expired leases awaiting "
              "takeover)")
        print(f"  dead-lettered: {st.get('dead', 0)} record(s) / "
              f"{st.get('deadEvents', 0)} event(s)")
        if args.json:
            print(json.dumps(st))
        return 0
    s = journal_summary(_spill_dir(args))
    print(f"spill journal: {s['dir']}")
    print(f"  pending: {s['pendingRecords']} record(s) / "
          f"{s['pendingEvents']} event(s) "
          f"(offset {s['replayedOffset']}/{s['records']})")
    if s["pendingTokens"]:
        print(f"  next tokens: {', '.join(t or '-' for t in s['pendingTokens'])}")
    print(f"  dead-lettered: {s['deadRecords']} record(s) / "
          f"{s['deadEvents']} event(s)")
    for inst in s["privateInstanceDirs"]:
        print(f"  private instance dir (locked-journal divert): {inst}")
    if args.json:
        print(json.dumps(s))
    return 0


def _open_spill_exclusive(args):
    """The mutating verbs need THE journal, not a diverted private one."""
    from predictionio_tpu.resilience.spill import SpillJournal

    try:
        return SpillJournal(_spill_dir(args), divert_if_locked=False)
    except RuntimeError as e:
        _die(str(e))


def _spill_insert_fn(storage):
    """One journal/queue record → storage, token-pinned (shared by both
    drain backends)."""
    from predictionio_tpu.data.json_support import event_from_json
    from predictionio_tpu.resilience import idempotency_key

    def insert(record):
        evs = [event_from_json(e) for e in record["events"]]
        with idempotency_key(record["token"]):
            storage.get_events().insert_batch(evs, record["appId"],
                                              record.get("channelId"))
    return insert


def cmd_spill_drain(args) -> int:
    """Foreground replay into storage — the same record-at-a-time,
    token-pinned insert the event server's background worker does, for
    when that server is gone (crashed box, decommission) but its spill
    must not be.  Against the shared queue this is just another lease
    drainer (safe next to live instances — leases serialize the work);
    against the local journal it takes the exclusive flock."""
    from predictionio_tpu.resilience.spill import ReplayWorker

    storage = _storage()
    if _spill_cli_backend(args) == "shared":
        from predictionio_tpu.resilience.shared_spill import LeaseDrainer

        q = _shared_spill(args)
        # owner=None → LeaseDrainer mints a pid+uuid identity: two
        # operators draining concurrently must never share an owner, or
        # one's dead_letter could park a record the other just landed.
        drainer = LeaseDrainer(q, _spill_insert_fn(storage),
                               batch=args.batch)
        landed = drainer.drain_once()
        remaining = q.depth()
        print(f"Replayed {landed} event(s); {remaining} still pending "
              "in the shared queue"
              + (" (storage unavailable or leased elsewhere — re-run "
                 "after recovery)." if remaining else "."))
        return 0 if remaining == 0 else 1
    journal = _open_spill_exclusive(args)
    worker = ReplayWorker(journal, _spill_insert_fn(storage),
                          batch=args.batch)
    try:
        landed = worker.drain_once()
        remaining = journal.depth()
    finally:
        journal.close()
    print(f"Replayed {landed} event(s); {remaining} still pending"
          + (" (storage unavailable — re-run after recovery)."
             if remaining else "."))
    return 0 if remaining == 0 else 1


def cmd_spill_requeue_dead(args) -> int:
    if _spill_cli_backend(args) == "shared":
        n = _shared_spill(args).requeue_dead()
        if n == 0:
            print("No dead-lettered records in the shared queue.")
        else:
            print(f"Requeued {n} dead-lettered event(s) — any instance's "
                  "drainer (or `pio spill drain`) replays them.")
        return 0
    journal = _open_spill_exclusive(args)
    try:
        n = journal.requeue_dead()
    finally:
        journal.close()
    if n == 0:
        print("No dead-lettered records.")
    else:
        print(f"Requeued {n} dead-lettered event(s) for replay "
              "(drain with `pio spill drain` or restart the event server).")
    return 0


# --------------------------------------------------------------------------
# pio import / export
# --------------------------------------------------------------------------

# Streamed-import commit granularity (module-level so tests can shrink
# it to exercise the chunk-boundary resume path).
IMPORT_CHUNK = 50_000


def cmd_import(args) -> int:
    """Streamed import: parse + insert in bounded chunks so a 25M-event
    file never materializes as one Python list (reference: FileToEvents;
    VERDICT r4 item 1a).  Each chunk is one group-committed insert_batch.

    Chunks committed before a parse error STAY committed (event ids are
    store-assigned, so a naive full re-run would duplicate them); the
    error message reports the exact resume point and ``--from-line``
    skips the already-imported prefix on retry."""
    from predictionio_tpu.data.json_support import event_from_json

    s = _storage()
    channel_id = _resolve_channel(s, args.appid, args.channel)
    ev = s.get_events()
    ev.init(args.appid, channel_id)
    start_line = max(1, getattr(args, "from_line", 1) or 1)
    total = 0
    chunk = []
    last_committed_line = start_line - 1
    with open(args.input) as f:
        for line_no, line in enumerate(f, 1):
            if line_no < start_line:
                continue
            line = line.strip()
            if not line:
                continue
            try:
                chunk.append(event_from_json(json.loads(line)))
            except Exception as e:
                _die(
                    f"{args.input}:{line_no}: {e}\n"
                    f"{total} event(s) up to line {last_committed_line} "
                    f"were already imported and remain stored; fix the "
                    f"line and re-run with --from-line "
                    f"{last_committed_line + 1} to avoid duplicates.")
            if len(chunk) >= IMPORT_CHUNK:
                total += len(ev.insert_batch(chunk, args.appid, channel_id))
                chunk = []
                last_committed_line = line_no
    if chunk:
        total += len(ev.insert_batch(chunk, args.appid, channel_id))
    print(f"Imported {total} events to app {args.appid}.")
    return 0


def cmd_export(args) -> int:
    from predictionio_tpu.data.json_support import event_to_json

    s = _storage()
    channel_id = _resolve_channel(s, args.appid, args.channel)
    n = 0
    with open(args.output, "w") as f:
        for ev in s.get_events().find(args.appid, channel_id):
            f.write(json.dumps(event_to_json(ev)) + "\n")
            n += 1
    print(f"Exported {n} events from app {args.appid} to {args.output}.")
    return 0


def _resolve_channel(s, app_id: int, channel_name: Optional[str]) -> Optional[int]:
    if not channel_name:
        return None
    ch = next((c for c in s.get_channels().get_by_app_id(app_id)
               if c.name == channel_name), None)
    if ch is None:
        _die(f"Channel {channel_name!r} does not exist in app {app_id}.")
    return ch.id


# --------------------------------------------------------------------------
# parser
# --------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pio", description="predictionio_tpu console"
    )
    p.add_argument("--version", action="version", version=__version__)
    p.add_argument("-v", "--verbose", action="store_true")
    sub = p.add_subparsers(dest="verb", required=True)

    st = sub.add_parser("status", help="storage + device sanity check "
                                       "+ metrics snapshot")
    st.add_argument("--metrics-url", dest="metrics_url", default=None,
                    metavar="URL",
                    help="scrape a running server's /metrics into the "
                         "status report (e.g. http://127.0.0.1:7070)")
    st.add_argument("--fleet", dest="fleet", default=None,
                    metavar="URLS",
                    help="comma-separated instance base URLs (or unset: "
                         "PIO_FLEET_INSTANCES) — scrape and merge "
                         "/metrics + SLO state across the fleet instead "
                         "of one process")
    st.set_defaults(fn=cmd_status)

    app = sub.add_parser("app", help="app management").add_subparsers(
        dest="app_verb", required=True
    )
    a = app.add_parser("new")
    a.add_argument("name")
    a.add_argument("--description")
    a.add_argument("--access-key", dest="access_key")
    a.set_defaults(fn=cmd_app_new)
    app.add_parser("list").set_defaults(fn=cmd_app_list)
    a = app.add_parser("delete")
    a.add_argument("name")
    a.add_argument("-f", "--force", action="store_true")
    a.set_defaults(fn=cmd_app_delete)
    a = app.add_parser("data-delete")
    a.add_argument("name")
    a.add_argument("--channel")
    a.add_argument("-f", "--force", action="store_true")
    a.set_defaults(fn=cmd_app_data_delete)
    a = app.add_parser("channel-new")
    a.add_argument("app")
    a.add_argument("channel")
    a.set_defaults(fn=cmd_app_channel_new)
    a = app.add_parser("channel-delete")
    a.add_argument("app")
    a.add_argument("channel")
    a.set_defaults(fn=cmd_app_channel_delete)

    ak = sub.add_parser("accesskey", help="access key management").add_subparsers(
        dest="ak_verb", required=True
    )
    a = ak.add_parser("new")
    a.add_argument("app")
    a.add_argument("events", nargs="*")
    a.set_defaults(fn=cmd_accesskey_new)
    a = ak.add_parser("list")
    a.add_argument("app", nargs="?")
    a.set_defaults(fn=cmd_accesskey_list)
    a = ak.add_parser("delete")
    a.add_argument("key")
    a.set_defaults(fn=cmd_accesskey_delete)

    b = sub.add_parser("build", help="validate an engine variant")
    b.add_argument("--engine-json", default="engine.json")
    b.set_defaults(fn=cmd_build)

    t = sub.add_parser("train", help="train an engine variant")
    t.add_argument("--engine-json", default="engine.json")
    t.add_argument("--seed", type=int, default=0)
    t.add_argument("--checkpoint-dir", dest="checkpoint_dir", default=None,
                   help="orbax checkpoint root; with --checkpoint-every, a "
                        "killed train resumes from the last complete step")
    t.add_argument("--checkpoint-every", dest="checkpoint_every", type=int,
                   default=0, metavar="N",
                   help="save every N sweeps/steps (0 = off)")
    t.add_argument("--mesh", default=None, metavar="SPEC",
                   help="device mesh, e.g. 'data=8,model=2' or 'auto' "
                        "(default: env PIO_MESH, else single device)")
    t.add_argument("--prefetch-depth", dest="prefetch_depth", type=int,
                   default=0, metavar="N",
                   help="staged batches the input pipeline keeps ahead "
                        "of the device (default: env PIO_PREFETCH_DEPTH, "
                        "else 2; raise on fast-feeder/slow-step "
                        "workloads, lower if HBM headroom warns)")
    t.add_argument("--fuse-steps", dest="fuse_steps", default=None,
                   metavar="K|auto",
                   help="optimizer steps fused into one XLA dispatch "
                        "(lax.scan over a K-batch superbatch; "
                        "bitwise-equal to K=1).  'auto' grows depth "
                        "until the HBM headroom guardrail pushes back, "
                        "then backs off one notch and pins (default: "
                        "env PIO_FUSE_STEPS, else 1)")
    t.add_argument("--batch-autoscale", dest="batch_autoscale",
                   action="store_true",
                   help="let the fusion autotuner also widen the "
                        "effective batch (concatenate prepped batches) "
                        "once fusion depth caps out — fewer, wider "
                        "optimizer steps: a semantics change, opt-in "
                        "(env PIO_BATCH_AUTOSCALE=on)")
    t.add_argument("--pq", dest="pq", default=None, metavar="auto|on|off",
                   help="quantized-corpus build policy: serialize "
                        "residual PQ codes (1+M bytes/item) next to the "
                        "IVF index so serving LUT-scans the packed "
                        "codes and re-ranks a shortlist exactly "
                        "(default: env PIO_PQ, else auto — builds above "
                        "PIO_PQ_MIN_ITEMS)")
    t.add_argument("--pq-m", dest="pq_m", type=int, default=0,
                   metavar="M",
                   help="PQ subspace count (bytes/item = 1+M; default: "
                        "env PIO_PQ_M, else ~dim/4)")
    t.add_argument("--follow", action="store_true",
                   help="continuous refresh: retrain on a cadence "
                        "(delta warm-start when possible), promote "
                        "through the serving server's staged-reload "
                        "canary gate (--promote-url), roll back on SLO "
                        "burn; Ctrl-C/SIGTERM stops")
    t.add_argument("--refresh-interval", dest="refresh_interval",
                   type=float, default=None, metavar="S",
                   help="follow-mode cadence in seconds (default env "
                        "PIO_REFRESH_INTERVAL_S, else 300)")
    t.add_argument("--promote-url", dest="promote_url", default=None,
                   metavar="URL",
                   help="engine-server base URL each refreshed "
                        "generation is promoted through (POST /reload → "
                        "staged canary gate; default env "
                        "PIO_REFRESH_PROMOTE_URL; unset = train only)")
    t.add_argument("--canary-window", dest="canary_window", type=float,
                   default=None, metavar="S",
                   help="post-promotion SLO-burn watch window; a trip "
                        "rolls the promotion back (default env "
                        "PIO_REFRESH_CANARY_WINDOW_S, else 60; 0 = off)")
    t.add_argument("--trigger-staleness", dest="trigger_staleness",
                   type=float, default=None, metavar="S",
                   help="follow-mode trigger: fire a refresh cycle when "
                        "event→servable staleness crosses S seconds "
                        "(default env PIO_REFRESH_TRIGGER_STALENESS_S; "
                        "the --refresh-interval cadence becomes a "
                        "backstop ceiling)")
    t.add_argument("--trigger-delta-count", dest="trigger_delta_count",
                   type=int, default=None, metavar="N",
                   help="follow-mode trigger: fire a refresh cycle when "
                        "N events have landed past the served watermark "
                        "(default env PIO_REFRESH_TRIGGER_DELTA_COUNT)")
    t.set_defaults(fn=cmd_train)

    e = sub.add_parser("eval", help="evaluate engine-params candidates")
    e.add_argument("evaluation_class")
    e.add_argument("params_generator_class")
    e.add_argument("--seed", type=int, default=0)
    e.add_argument("--mesh", default=None, metavar="SPEC")
    e.add_argument("--output-json", dest="output_json")
    e.add_argument("--checkpoint-dir", dest="checkpoint_dir", default=None,
                   help="persist each completed (candidate, fold) unit "
                        "here so a SIGTERM'd sweep resumes instead of "
                        "restarting (default env PIO_EVAL_CHECKPOINT_DIR;"
                        " cleared when the sweep completes)")
    e.set_defaults(fn=cmd_eval)

    es = sub.add_parser("eventserver", help="start the event ingestion server")
    es.add_argument("--ip", default="0.0.0.0")
    es.add_argument("--port", type=int, default=7070)
    es.add_argument("--native", action="store_true",
                    help="serve through the C++ continuous-batching "
                         "frontend (group-committed ingest)")
    es.set_defaults(fn=cmd_eventserver)

    d = sub.add_parser("deploy", help="serve a trained engine over HTTP")
    d.add_argument("--engine-json", default="engine.json")
    d.add_argument("--ip", default="0.0.0.0")
    d.add_argument("--port", type=int, default=8000)
    d.add_argument("--engine-instance-id", dest="engine_instance_id")
    d.add_argument("--mesh", default=None, metavar="SPEC",
                   help="device mesh for model re-load/serve sharding")
    d.add_argument("--native", action="store_true",
                   help="serve via the C++ continuous-batching frontend")
    d.add_argument("--max-batch", type=int, default=None,
                   help="max queries per batched dispatch — applies to "
                        "the serving scheduler AND the native frontend "
                        "(default env PIO_BATCH_MAX, else 64)")
    d.add_argument("--max-wait-us", type=int, default=2000)
    d.add_argument("--no-batcher", action="store_true",
                   help="disable the serving micro-batcher (per-request "
                        "dispatch; admission control stays on)")
    d.add_argument("--batch-window-ms", dest="batch_window_ms", type=float,
                   default=None,
                   help="initial batch gather window (default env "
                        "PIO_BATCH_WINDOW_MS, else 2.0; autotuned live)")
    d.add_argument("--queue-depth", dest="queue_depth", type=int,
                   default=None,
                   help="admission queue depth; full queue answers 429 "
                        "(default env PIO_QUEUE_DEPTH, else 128)")
    d.add_argument("--batch-p99-target-ms", dest="batch_p99_target_ms",
                   type=float, default=None,
                   help="autotuner served-latency p99 target (default env "
                        "PIO_BATCH_P99_TARGET_MS, else 100)")
    d.set_defaults(fn=cmd_deploy)

    bp = sub.add_parser("batchpredict", help="bulk predict from NDJSON queries")
    bp.add_argument("--engine-json", default="engine.json")
    bp.add_argument("--input", required=True)
    bp.add_argument("--output", required=True)
    bp.add_argument("--engine-instance-id", dest="engine_instance_id")
    bp.add_argument("--mesh", default=None, metavar="SPEC")
    bp.add_argument("--query-partitions", type=int, default=256,
                    help="queries per vectorized predict chunk")
    bp.set_defaults(fn=cmd_batchpredict)

    adm = sub.add_parser("adminserver", help="app-management REST API server")
    adm.add_argument("--ip", default="127.0.0.1")
    adm.add_argument("--port", type=int, default=7071)
    adm.set_defaults(fn=cmd_adminserver)

    tpl = sub.add_parser("template", help="engine template gallery")
    tplsub = tpl.add_subparsers(dest="template_cmd", required=True)
    tg = tplsub.add_parser("get", help="scaffold an engine from a template")
    tg.add_argument("template", help="template name (e.g. recommendation)")
    tg.add_argument("directory", help="target directory")
    tg.set_defaults(fn=cmd_template_get)

    sh = sub.add_parser("shell", help="interactive shell with storage "
                                      "preloaded (reference: pio-shell)")
    sh.set_defaults(fn=cmd_shell)

    ss = sub.add_parser("storageserver",
                        help="serve this PIO_HOME's storage over TCP "
                             "(clients use type=pioserver)")
    ss.add_argument("--ip", default="127.0.0.1")
    ss.add_argument("--port", type=int, default=7077)
    ss.add_argument("--secret", default=None,
                    help="shared auth secret clients must present "
                         "(default: env PIO_STORAGE_SERVER_SECRET); "
                         "strongly recommended when binding non-loopback")
    ss.set_defaults(fn=cmd_storageserver)

    db = sub.add_parser("dashboard", help="engine/evaluation instance dashboard")
    db.add_argument("--ip", default="127.0.0.1")
    db.add_argument("--port", type=int, default=9000)
    db.add_argument("--fleet", default=None, metavar="URLS",
                    help="comma-separated instance base URLs to aggregate "
                         "at GET /fleet.json (default: "
                         "PIO_FLEET_INSTANCES)")
    db.set_defaults(fn=cmd_dashboard)

    pf = sub.add_parser("profile", help="on-demand JAX profiler capture "
                                        "(local, or a running admin "
                                        "server via --url)")
    pf.add_argument("--duration-ms", dest="duration_ms", type=float,
                    default=2000.0, help="capture window (default 2000)")
    pf.add_argument("--url", default=None,
                    help="admin server base URL (e.g. "
                         "http://127.0.0.1:7071) — capture happens there")
    pf.add_argument("--out", default=None,
                    help="local capture: artifact directory (default: "
                         "fresh temp dir; env PIO_PROFILE_OUT). With "
                         "--url: LOCAL file/dir the capture archive is "
                         "downloaded to after the window closes "
                         "(GET /admin/profile/artifact)")
    pf.set_defaults(fn=cmd_profile)

    ro = sub.add_parser("rollout",
                        help="promote a generation across a fleet in "
                             "gated waves, rolling the WHOLE fleet back "
                             "on degradation")
    ro.add_argument("--instances", default=None, metavar="URLS",
                    help="comma-separated engine-server base URLs "
                         "(default: PIO_FLEET_INSTANCES)")
    ro.add_argument("--engine-instance-id", dest="engine_instance_id",
                    default=None,
                    help="candidate engine instance id (default: the "
                         "first promoted server's latest COMPLETED, "
                         "then pinned fleet-wide)")
    ro.add_argument("--waves", default=None, metavar="SPEC",
                    help="wave tranches, counts or percentages "
                         "(default env PIO_ROLLOUT_WAVES, else "
                         "'1,25%%,100%%')")
    ro.add_argument("--bake-s", dest="bake_s", type=float, default=None,
                    help="per-wave observation window watching the "
                         "fleet-merged SLO burn + quality gate "
                         "(default env PIO_ROLLOUT_BAKE_S, else 10)")
    ro.add_argument("--poll-s", dest="poll_s", type=float, default=None,
                    help="gate poll cadence inside the bake (default "
                         "env PIO_ROLLOUT_POLL_S, else 1)")
    ro.add_argument("--state", default=None, metavar="FILE",
                    help="wave-state journal (default env "
                         "PIO_ROLLOUT_STATE, else "
                         "$PIO_HOME/rollout/state.json)")
    ro.add_argument("--resume", action="store_true",
                    help="continue a preempted rollout from its journal "
                         "(re-verifies what each instance serves first)")
    ro.add_argument("--unwind", action="store_true",
                    help="roll back everything the journaled rollout "
                         "already promoted, instead of continuing")
    ro.set_defaults(fn=cmd_rollout)

    sp = sub.add_parser("spill", help="inspect/drain the storage-outage "
                                      "spill journal")
    spsub = sp.add_subparsers(dest="spill_verb", required=True)
    si = spsub.add_parser("inspect", help="pending/dead-letter counts "
                                          "(read-only; safe while the "
                                          "event server runs)")
    _backend_help = ("spill home to operate on: 'shared' = the "
                     "storage-backed fleet queue, 'local' = this box's "
                     "JSONL journal (default: PIO_SPILL_BACKEND, else "
                     "auto — shared on a pioserver EVENTDATA source)")
    si.add_argument("--dir", default=None,
                    help="journal directory (default: PIO_SPILL_DIR, "
                         "else $PIO_HOME/spill)")
    si.add_argument("--backend", default=None,
                    choices=("auto", "local", "shared"),
                    help=_backend_help)
    si.add_argument("--json", action="store_true",
                    help="also print the summary as one JSON line")
    si.set_defaults(fn=cmd_spill_inspect)
    sd = spsub.add_parser("drain", help="foreground replay into storage "
                                        "(local: event server must be "
                                        "stopped; shared: safe anytime — "
                                        "leases serialize)")
    sd.add_argument("--dir", default=None)
    sd.add_argument("--backend", default=None,
                    choices=("auto", "local", "shared"),
                    help=_backend_help)
    sd.add_argument("--batch", type=int, default=100,
                    help="records per replay batch")
    sd.set_defaults(fn=cmd_spill_drain)
    sq = spsub.add_parser("requeue-dead",
                          help="move dead-lettered records back into the "
                               "queue/journal for replay")
    sq.add_argument("--dir", default=None)
    sq.add_argument("--backend", default=None,
                    choices=("auto", "local", "shared"),
                    help=_backend_help)
    sq.set_defaults(fn=cmd_spill_requeue_dead)

    imp = sub.add_parser("import", help="import NDJSON events")
    imp.add_argument("--appid", type=int, required=True)
    imp.add_argument("--channel")
    imp.add_argument("--input", required=True)
    imp.add_argument("--from-line", type=int, default=1, dest="from_line",
                     help="resume a partially-committed import at this "
                          "1-based line (printed by a failed run)")
    imp.set_defaults(fn=cmd_import)

    exp = sub.add_parser("export", help="export events as NDJSON")
    exp.add_argument("--appid", type=int, required=True)
    exp.add_argument("--channel")
    exp.add_argument("--output", required=True)
    exp.set_defaults(fn=cmd_export)

    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="[%(levelname)s] [%(name)s] %(message)s",
    )
    from predictionio_tpu.controller import ParamsBindingError
    from predictionio_tpu.data.storage import StorageError
    from predictionio_tpu.workflow import WorkflowError

    try:
        return args.fn(args)
    except SystemExit:
        raise
    except KeyboardInterrupt:
        return 130
    except (ParamsBindingError, StorageError, WorkflowError) as e:
        # User-input errors get a clean message; unexpected ones traceback.
        print(f"[error] {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
