"""Open-loop HTTP: requests sent when they are due, by a child process
that never imports jax (``benchmark/loadgen.py``), to the system's
``POST /queries.json``."""

from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np

from benchmark import compare, traffic
from benchmark.drives import FAILED_MS, Window, distinct_nums, sample

CHECKOUT = Path(__file__).resolve().parents[2]


def warm(system, mix) -> None:
    """Every batch shape the batcher can form (the retrieval facade pads
    a cohort to a power of two) at each ``num``; then one real request
    over HTTP."""
    top = int(os.environ.get("PIO_BATCH_MAX", "64") or 64)
    for num in distinct_nums(mix):
        b = 1
        while b <= top:
            system.query_batch([traffic.query_json(u, num)
                                for u in range(b)])
            b *= 2
    conn = http.client.HTTPConnection("127.0.0.1", system.port, timeout=60)
    try:
        conn.request("POST", "/queries.json",
                     body=json.dumps(traffic.query_json(0, 10)),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read()
        if resp.status != 200:
            raise RuntimeError(f"warm-up query answered {resp.status}")
    finally:
        conn.close()


def run(system, mix, config, seed: int, seconds: float,
        window_span) -> Window:
    due, users, nums = traffic.serving_requests(mix, seed, seconds,
                                                system.population)
    spec = {"port": system.port, "due_s": due.tolist(),
            "bodies": [json.dumps(traffic.query_json(u, k))
                       for u, k in zip(users, nums)],
            "connections": int(mix.get("connections", 128)),
            "timeout_s": float(mix.get("timeout_s", 30.0))}
    tmp = tempfile.mkdtemp(prefix="bench_loadgen_")
    spec_path, out_path = f"{tmp}/spec.json", f"{tmp}/out.json"
    with open(spec_path, "w", encoding="utf-8") as f:
        json.dump(spec, f)
    child = subprocess.Popen(
        [sys.executable, "-m", "benchmark.loadgen", spec_path, out_path],
        cwd=str(CHECKOUT), stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        text=True)
    try:
        if child.stdout.readline().strip() != "ready":
            raise RuntimeError("load generator did not start")
        with window_span():
            child.stdin.write("go\n")
            child.stdin.flush()
            child.wait(timeout=seconds + spec["timeout_s"] + 30)
        if child.returncode != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
        with open(out_path, encoding="utf-8") as f:
            res = json.load(f)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        for p in (spec_path, out_path):
            if os.path.exists(p):
                os.unlink(p)
        os.rmdir(tmp)
    status = np.array(res["status"])
    done, sent = np.array(res["done_s"]), np.array(res["sent_s"])
    ok = status == 200
    lat = np.where(ok, (done - due) * 1e3, FAILED_MS)
    w = Window()
    w.attempted, w.failed = len(due), int((~ok).sum())
    w.metrics = {
        "query_p50_ms": float(np.percentile(lat, 50)),
        "query_p95_ms": float(np.percentile(lat, 95)),
        "queries_per_s": float((ok & (done <= seconds)).sum() / seconds),
    }
    w.extras = {"late_ms": (sent - due) * 1e3, "latency_ms": lat,
                "offered_per_s": len(due) / seconds,
                "statuses": {int(s): int((status == s).sum())
                             for s in np.unique(status)}}
    answered = np.flatnonzero(ok)
    pick = answered[sample(seed, len(answered),
                           int(mix.get("check_answers", 256)))] \
        if len(answered) else answered
    samples = [(int(users[i]), int(nums[i]), res["answers"][i])
               for i in pick]
    w.check = lambda: compare.serving_numbers(config, seed, samples)
    return w
