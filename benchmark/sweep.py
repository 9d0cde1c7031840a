"""Find the knee once: a ladder of offered rates against one serving
system (one process, one set-up, ``--seconds`` a rung).  The knee is the
highest rate whose requests still finish at the pace they arrive: p95
stays of the order of a dispatch and the last response lands with the
last arrival.  The cell's fixed rate (``traffic/serve-steady.json``) is
about four fifths of it.  Run on the chip; never by the benchmark.

    python3 -m benchmark.sweep --workload <serving cell> --rates 100,200
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from benchmark import builders, drives, manifest, run

    cell = manifest.cell(manifest.load(), args.workload)
    run.open_chip(cell.config.get("env"), cell.chips)
    system = builders.build(cell.config, args.seed, {})
    drive = drives.load(cell.traffic["drive"])
    drive.warm(system, cell.traffic)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, arrival={"process": "poisson",
                                          "rate_per_s": rate})
        try:
            w = drive.run(system, mix, cell.config, args.seed + i,
                          args.seconds, contextlib.nullcontext)
        except Exception as e:  # a rung that falls over ends the ladder
            print(json.dumps({"rate_per_s": rate,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
            break
        lat = w.extras["latency_ms"]
        first = float(np.median(lat[:len(lat) // 10 + 1]))
        last = float(np.median(lat[-len(lat) // 10 - 1:]))
        print(json.dumps({
            "rate_per_s": rate, "attempted": w.attempted,
            "failed": w.failed, "statuses": w.extras["statuses"],
            "p50_ms": w.metrics["query_p50_ms"],
            "p95_ms": w.metrics["query_p95_ms"],
            "max_ms": float(lat.max()),
            # a growing backlog: the last tenth waits longer than the
            # first tenth
            "p50_first_tenth_ms": first, "p50_last_tenth_ms": last,
            "completed_in_window_per_s": w.metrics["queries_per_s"],
            "late_p95_ms": float(np.percentile(w.extras["late_ms"], 95)),
        }), flush=True)
        if last > 3 * first or w.failed:
            # Past the knee.  The backlog and the batcher's tuned-down
            # knobs would colour every rung after it.
            break
    system.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
