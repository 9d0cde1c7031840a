"""The benchmark's one command.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

A new process: builds the cell's system from its configuration file and
the seed, warms the shapes its traffic uses (all of that is ``setup_s``),
measures for ``--seconds``, compares what the timed path produced with
the plain reference, and prints one JSON object as the last line of
standard output.  No accelerator, fewer chips than the cell asks for, or
a device kind that ``peaks.json`` does not know is an error and prints
no result; there is no CPU run.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, Optional

_IMPORTED_AT = time.time()
CHECKOUT = Path(__file__).resolve().parent.parent


def _process_start() -> float:
    """Wall-clock time this process started (``/proc``), so ``setup_s``
    holds the interpreter's own start-up too."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat", encoding="ascii") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration):
        return _IMPORTED_AT


def _environment(deployment_env: Optional[Dict[str, str]] = None) -> None:
    """``deployment_env``: the operator's settings the configuration file
    states (``"env"``), set before the program reads them.
    State the program keeps goes inside the checkout or nowhere:
    metadata and model stores in memory, ``PIO_HOME`` under the
    checkout.  The compile cache is placed by the program's own
    ``resolve_backend`` (``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``)."""
    os.environ["PIO_HOME"] = str(CHECKOUT / ".bench_state")
    os.environ["PIO_STORAGE_SOURCES_BENCH_TYPE"] = "memory"
    for repo in ("METADATA", "EVENTDATA", "MODELDATA"):
        os.environ[f"PIO_STORAGE_REPOSITORIES_{repo}_NAME"] = "bench"
        os.environ[f"PIO_STORAGE_REPOSITORIES_{repo}_SOURCE"] = "BENCH"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for key, value in (deployment_env or {}).items():
        os.environ[key] = str(value)


def _memory_now(devices) -> Dict[str, int]:
    """The fullest chip right now: arrays in use, and what the runtime
    holds back for the loaded programs' scratch (``bytes_reserved``; the
    TPU runtime does not count it as in use)."""
    best = {"in_use": 0, "reserved": 0}
    for d in devices:
        s = d.memory_stats() or {}
        now = {"in_use": int(s.get("bytes_in_use", 0)),
               "reserved": int(s.get("bytes_reserved", 0))}
        if sum(now.values()) > sum(best.values()):
            best = now
    return best


def _device_block(devices, trace: Optional[Dict[str, Any]],
                  held: Dict[str, int]) -> Dict[str, Any]:
    """``memory_peak_bytes``: the runtime's own ``peak_bytes_in_use``, or
    arrays in use + reserved program scratch as read with the window's
    programs loaded (``held``), whichever is larger.  A program cannot
    run unless its scratch can be reserved (``PERF.md`` section 4 has the
    chip experiment), so the chip holds both while the window runs.  The
    parts are reported beside it under keys the driver does not read."""
    in_use_peak = max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in devices)
    block = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices),
             "memory_peak_bytes": max(in_use_peak, sum(held.values())),
             "memory_parts": {"peak_bytes_in_use": in_use_peak,
                              "bytes_in_use": held["in_use"],
                              "bytes_reserved": held["reserved"]}}
    if trace is not None:
        block["busy_s"] = trace["busy_s"]
        block["window_s"] = trace["window_s"]
    return block


def _annotate_layers(stack: contextlib.ExitStack,
                     spans: Dict[str, str]) -> None:
    """Traced runs only: ``TraceAnnotation`` spans around the calls into
    each layer, written from here so they share the device trace's
    clock.  The idle-gap labels of ``breakdown`` come from these.
    ``spans`` is the configuration's ``"trace_spans"``: label ->
    ``"module:Owner.attribute"`` of the program."""
    import jax

    for label, target in spans.items():
        module, _, path = target.partition(":")
        owner = importlib.import_module(module)
        *parents, attr = path.split(".")
        for name in parents:
            owner = getattr(owner, name)
        inner = getattr(owner, attr)

        def spanned(*a, _inner=inner, _label=f"bench:{label}", **kw):
            with jax.profiler.TraceAnnotation(_label):
                return _inner(*a, **kw)

        setattr(owner, attr, spanned)
        stack.callback(setattr, owner, attr, inner)


def open_chip(deployment_env: Optional[Dict[str, str]], chips: int = 1,
              require_chip: bool = True):
    """Set the environment, take the accelerator and let the program
    place its compile cache; returns jax's devices.  No TPU, fewer chips
    than asked for, or a device kind without peaks on record ends the
    process with no result."""
    _environment(deployment_env)
    import jax

    from benchmark import rooflines

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise SystemExit(f"no accelerator: jax runs on "
                             f"{devices[0].platform!r}; the benchmark has "
                             "no CPU run")
        if len(devices) < chips:
            raise SystemExit(f"{chips} chip(s) needed; jax sees "
                             f"{len(devices)}")
        rooflines.peaks(devices[0].device_kind)
    from predictionio_tpu.backend import resolve_backend

    resolve_backend()
    return devices


def read_layer_metrics(cell, ctx: Dict[str, Any]) -> Dict[str, Any]:
    from benchmark import manifest

    out = {}
    for m in cell.per_layer:
        spec = manifest.layer_metric_spec(m["name"])
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        # A reader that found nothing to read returns nothing, and the
        # metric stays out of the line.
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, started_at: Optional[float] = None,
             dump_trace: Optional[str] = None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object.  ``require_chip``
    is False only in the tests, which drive everything but the look for
    a chip at tiny sizes on the CPU; ``dump_trace`` is a path the
    extracted trace is also written to."""
    started_at = started_at or time.time()
    devices = open_chip(cell.config.get("env"), cell.chips, require_chip)
    import jax

    from benchmark import builders, compare, drives, prom, trace_reduce
    from predictionio_tpu.backend import compile_stats

    t_init = time.time()
    split: Dict[str, float] = {"process_init_s": t_init - started_at}
    config, mix = cell.config, cell.traffic
    drive = drives.load(mix["drive"])
    layer_spans = contextlib.ExitStack()
    if trace:
        # Before the system is built: the scheduler binds its dispatch
        # method when the server is constructed.
        _annotate_layers(layer_spans, config.get("trace_spans", {}))
    system = builders.build(config, seed, split)
    t_built = time.time()
    drive.warm(system, mix)
    split["warm_s"] = time.time() - t_built
    compiled = compile_stats()
    split["compile_s"] = compiled["compileSeconds"]
    held = _memory_now(devices)
    setup_s = time.time() - started_at
    print("setup_s split: " + json.dumps(
        {k: round(v, 3) for k, v in split.items()}), flush=True)

    trace_dir = None
    with layer_spans as stack:
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            stack.callback(shutil.rmtree, trace_dir, True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            window_span = lambda: jax.profiler.TraceAnnotation(  # noqa: E731
                trace_reduce.WINDOW_SPAN)
        else:
            window_span = contextlib.nullcontext
        before = prom.snapshot()
        try:
            window = drive.run(system, mix, config, seed, seconds,
                               window_span)
        finally:
            if trace:
                jax.profiler.stop_trace()
        after = prom.snapshot()
        compiled_after = compile_stats()
        held = max(held, _memory_now(devices),
                   key=lambda m: sum(m.values()))
        reduced = None
        if trace:
            extracted = trace_reduce.extract(trace_dir)
            if dump_trace:
                # Tools and tests only: the extracted events, to look at
                # by hand or to cut a recorded trace from.
                with open(dump_trace, "w", encoding="utf-8") as f:
                    json.dump(extracted, f)
            reduced = trace_reduce.reduce(extracted)
    # The peak is read here; the reference runs only after it, with the
    # window closed and the program's state freed.
    device = _device_block(devices, reduced, held)
    system.free()
    del system
    gc.collect()
    t_check = time.time()
    numbers = dict(window.check())
    split["check_s"] = time.time() - t_check
    numbers["failed"] = float(window.failed)
    numbers["compiles_in_window"] = float(
        compiled_after["compiles"] - compiled["compiles"])
    rung = config.get("expect_rung")
    if rung:
        calls = prom.delta(before, after, "pio_retrieval_requests_total")
        ours = prom.delta(before, after, "pio_retrieval_requests_total",
                          {"rung": rung})
        numbers["other_rung_calls"] = calls - ours
        numbers["rung_idle"] = float(ours <= 0)
    correct, compared = compare.verdict(numbers, config["limits"])

    metrics = {"setup_s": setup_s, **window.metrics}
    if trace:
        ctx = {"cell": cell, "config": config, "mix": mix,
               "seconds": seconds, "window": window, "before": before,
               "after": after, "trace": reduced, "split": split,
               "device_kind": devices[0].device_kind,
               "require_chip": require_chip}
        out_metrics = read_layer_metrics(cell, ctx)
    else:
        out_metrics = {m["name"]: {"value": float(metrics[m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
    result = {"correct": bool(correct), "attempted": int(window.attempted),
              "failed": int(window.failed), "metrics": out_metrics,
              "device": device}
    if trace and reduced is not None:
        result["breakdown"] = trace_reduce.breakdown(reduced)
    result["setup_split_s"] = split
    if "call_ms" in window.extras:
        result["call_ms"] = window.extras["call_ms"]
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if str(CHECKOUT) not in sys.path:
        sys.path.insert(0, str(CHECKOUT))
    from benchmark import manifest

    cell = manifest.cell(manifest.load(), args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      started_at=_process_start())
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
