#!/usr/bin/env python
"""chip_smoke.py — the system's main path on one TPU chip, end to end.

events in the store -> ``pio train`` -> ``pio deploy`` -> ``/queries.json``
at the repo's north-star width: explicit ALS-WR, rank 64, MovieLens-25M
shape (162,541 users x 59,047 items x 25M ratings from ``synth_ratings``:
uniform users, Zipf(1.25) items, half-star ratings, seeded), plus every
Pallas kernel of ``ops/pallas_kernels.py`` compiled by Mosaic and checked
against its XLA twin, and a 2.5M x 64 corpus (640 MB on the chip) served
through the retrieval facade.

One parent that never imports jax; children that each own the chip in
turn, every one started with ``JAX_PLATFORMS=tpu`` so a missing chip is
fatal inside jax:

1. ``python -m predictionio_tpu.cli train``   (device prep, Pallas gram +
   LU solver) — the parent then loads the stored factors with numpy and
   checks training RMSE on a seeded subsample against the global mean.
2. ``python -m predictionio_tpu.cli deploy`` twice — default routing (the
   host numpy rung at this corpus size: the plain reference), then
   ``PIO_RETRIEVAL_RUNG=device``; same HTTP queries, answers compared.
3. a kernel child (this file, ``--stage kernels``).

On a TPU the last stdout line is one JSON object with exactly the keys
``ok`` and ``device`` (``{"ok": true, "device": {"platform": "tpu", "kind":
..., "count": 1}}``); ``ok`` is true and the exit code 0 only when every
stage passed.  The line before it is the run's summary (``"claim": null``).
Without a TPU the script stops before doing any work, prints no result and
exits non-zero.  ``--cpu-dry-run`` drives the same
code at toy sizes on the CPU to debug the script itself: it prints no
result line and never exits 0.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import io
import json
import os
import pickle
import re
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

# MovieLens-25M shape (BASELINE.md); the dry run shrinks counts, never
# rank 64, the bucket bounds or the corpus width.
# lu_batch: the largest solve one ALS dispatch chunk hands the LU kernel
# at this shape (20,464 item rows); the XLA Cholesky twin needs ~90 KB of
# scratch per system, so 131k systems at once would not fit the chip.
FULL = dict(users=162_541, items=59_047, ratings=25_000_000,
            corpus=2_500_000, lu_batch=20_464, rmse_sample=1_000_000)
DRY = dict(users=1_500, items=900, ratings=60_000,
           corpus=6_000, lu_batch=300, rmse_sample=20_000)
RANK = 64
CORPUS_DIM = 64
# Stated tolerance of every exact-rung score against the reference: both
# are float32 dot products of 64 terms, summed in a different order
# (ops.topk.SCORE_PRECISION keeps the MXU from rounding to bfloat16).
SCORE_RTOL = 1e-5
DRY_RUN_EXIT = 3
# What `pio train` must resolve to, as its log states it: on one chip the
# compiled kernels and device prep; the dry run's CPU takes the XLA path.
TRAIN_ON_TPU = dict(use_pallas="True", kernels="compiled", solver="lu",
                    gram_dtype="bfloat16", device_prep="True",
                    pallas="compiled")
TRAIN_ON_CPU = dict(use_pallas="False", kernels="interpret",
                    solver="cholesky", gram_dtype="float32",
                    device_prep="False", pallas="interpret")


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------
# data (seeded)
# --------------------------------------------------------------------------

def synth_ratings(seed: int, n_users: int, n_items: int, n: int):
    rng = np.random.default_rng(seed)
    users = rng.integers(0, n_users, n)
    items = (rng.zipf(1.25, size=n) % n_items).astype(np.int64)
    ratings = (rng.integers(1, 11, n) * 0.5).astype(np.float32)
    return users, items, ratings


def storage_env(home: Path) -> dict:
    return {"PIO_HOME": str(home),
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PARQUET"}


def open_storage(home: Path):
    from predictionio_tpu.config import load_config
    from predictionio_tpu.data.storage import Storage

    return Storage(load_config(env=storage_env(home)))


def seed_store(home: Path, users, items, ratings, n_users, n_items) -> float:
    """Bulk-ingest the ratings as ``rate`` events through the columnar
    half of ``pio import`` (``Events.insert_columnar``)."""
    import pyarrow as pa

    from predictionio_tpu.data.storage.base import App

    t0 = time.perf_counter()
    storage = open_storage(home)
    app_id = storage.get_apps().insert(App(id=None, name="smoke"))
    events = storage.get_events()
    events.init(app_id)

    def dcol(idx, vals):
        return pa.DictionaryArray.from_arrays(
            pa.array(idx, type=pa.int32()), pa.array(vals))

    n = len(ratings)
    zeros = np.zeros(n, np.int32)
    table = pa.table({
        "event": dcol(zeros, ["rate"]),
        "entity_type": dcol(zeros, ["user"]),
        "entity_id": dcol(users.astype(np.int32),
                          [f"u{i}" for i in range(n_users)]),
        "target_entity_type": dcol(zeros, ["item"]),
        "target_entity_id": dcol(items.astype(np.int32),
                                 [f"i{i}" for i in range(n_items)]),
        "properties_json": dcol(
            (ratings * 2).astype(np.int32) - 1,
            ['{"rating": %.1f}' % (k * 0.5) for k in range(1, 11)]),
        "event_time_us": pa.array(
            np.arange(n, dtype=np.int64) + 1_750_000_000_000_000),
    })
    check(events.insert_columnar(table, app_id) == n,
          "insert_columnar stored fewer rows than it was given")
    return time.perf_counter() - t0


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------

def child_env(home: Path, platform: str, extra: dict = None) -> dict:
    """The environment of every stage child: the caller's, with the
    storage pointed at the throwaway home and ``JAX_PLATFORMS`` forced —
    never inherited (the sandbox exports ``JAX_PLATFORMS=cpu``)."""
    env = dict(os.environ)
    env.update(storage_env(home))
    env["JAX_PLATFORMS"] = platform
    env["PYTHONPATH"] = os.pathsep.join(
        [str(HERE)] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                       if p])
    env.update(extra or {})
    return env


def tail(path: Path, n: int = 40) -> str:
    try:
        return "\n".join(path.read_text(errors="replace").splitlines()[-n:])
    except OSError:
        return "(no log)"


def probe_platform() -> dict:
    """What jax finds in THIS environment, asked of a throwaway child so
    the parent never touches jax (a parent that has holds the chip)."""
    code = ("import json, jax; d = jax.devices(); print(json.dumps({"
            "'platform': d[0].platform, 'kind': d[0].device_kind, "
            "'count': len(d)}))")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    if r.returncode != 0:
        raise SmokeFailure("jax could not start a backend:\n"
                           + "\n".join(r.stderr.splitlines()[-6:]))
    return json.loads(r.stdout.strip().splitlines()[-1])


BACKEND_RE = re.compile(
    r"backend: platform=(\S+) device_kind=(.+?) devices=(\d+) pallas=(\S+)")
LOOP_RE = re.compile(
    r"ALS loop: rank=(\d+) iterations=(\d+) use_pallas=(\S+) "
    r"\((\d+)/(\d+) chunks, kernels (\S+)\) solver=(\S+) gram_dtype=(\S+) "
    r"device_prep=(\S+)")
COMPILE_RE = re.compile(
    r"backend: compile_seconds=([\d.]+) compiles=(\d+) "
    r"compile_cache_hits=(\d+)")
FACTORS_RE = re.compile(
    r"ALS factors: user_factors on (\d+) device\(s\), item_factors on "
    r"(\d+) device\(s\); bytes_in_use per device (\[.*?\])")
INSTANCE_RE = re.compile(r"Engine instance ID: (\S+)")


def device_of(m) -> dict:
    return {"platform": m.group(1), "kind": m.group(2),
            "count": int(m.group(3))}


def stage_train(home: Path, args, expect: dict) -> dict:
    log_path = home / "train.log"
    cmd = [sys.executable, "-m", "predictionio_tpu.cli", "train",
           "--engine-json", str(home / "engine.json"),
           "--seed", str(args.seed)]
    if args.mesh:
        cmd += ["--mesh", args.mesh]
    trace_path = home / "train_trace.jsonl"
    t0 = time.perf_counter()
    with open(log_path, "w") as lf:
        rc = subprocess.run(
            cmd, env=child_env(home, expect["platform"],
                               {"PIO_TRACE_FILE": str(trace_path)}),
            stdout=lf, stderr=subprocess.STDOUT, cwd=str(home),
            timeout=args.stage_timeout).returncode
    wall = time.perf_counter() - t0
    text = log_path.read_text(errors="replace")
    if rc != 0:
        raise SmokeFailure(f"pio train exited {rc}:\n{tail(log_path)}")
    b, loop = BACKEND_RE.search(text), LOOP_RE.search(text)
    comp, inst = COMPILE_RE.search(text), INSTANCE_RE.search(text)
    check(b is not None, "train log has no 'backend:' start-up line")
    check(loop is not None, "train log has no 'ALS loop:' line")
    check(comp is not None and inst is not None,
          "train log lacks the compile-seconds or instance-id line")
    dev = device_of(b)
    check(dev["platform"] == expect["platform"],
          f"pio train ran on platform={dev['platform']}")
    got = dict(use_pallas=loop.group(3), kernels=loop.group(6),
               solver=loop.group(7), gram_dtype=loop.group(8),
               device_prep=loop.group(9), pallas=b.group(4))
    want = dict(expect["train"])
    if args.mesh:
        # The mesh path buckets on the host, and GSPMD cannot partition
        # Mosaic kernels: a multi-device run takes the XLA twins.
        want.update(device_prep="False", use_pallas="False",
                    solver="cholesky")
    check(int(loop.group(1)) == RANK, f"trained rank {loop.group(1)}")
    check(int(loop.group(2)) >= 3, "fewer than 3 sweeps")
    check(got == want, f"train resolved {got}, expected {want}")
    # DASE phase seconds from the run's own trace (obs/trace.py).
    phases = {}
    for line in trace_path.read_text().splitlines():
        doc = json.loads(line)
        if doc["name"] == "workflow.train":
            phases = {sp["name"].split(".", 1)[1]:
                      round(sp["durationMs"] / 1e3, 1) for sp in doc["spans"]}
    out = dict(device=dev, wall_s=round(wall, 1), phases_s=phases,
               compile_s=float(comp.group(1)), compiles=int(comp.group(2)),
               cache_hits=int(comp.group(3)), instance=inst.group(1), **got)
    if args.mesh:
        f = FACTORS_RE.search(text)
        check(f is not None, "train log has no 'ALS factors:' line")
        n_mesh = int(args.mesh.split("=")[1])
        in_use = json.loads(f.group(3))
        check(int(f.group(1)) == n_mesh and int(f.group(2)) == n_mesh,
              f"factors on {f.group(1)}/{f.group(2)} devices, mesh {n_mesh}")
        # A fresh process starts every device at 0 bytes (the CPU
        # backend of the dry run reports no memory stats at all).
        check(args.cpu_dry_run or (len(in_use) >= n_mesh and all(
            v > 0 for v in in_use[:n_mesh])),
            f"a mesh device holds no bytes: {in_use}")
        out.update(factor_devices=n_mesh, bytes_in_use=in_use)
    return out


class _Stub:
    """Stand-in for any class whose module would import jax."""

    def __setstate__(self, state):
        if isinstance(state, tuple):      # (dict | None, slots dict)
            for part in state:
                if part:
                    self.__dict__.update(part)
        else:
            self.__dict__.update(state)


def _numpy_from_jax_pickle(fun, args, arr_state, aval_state):
    arr = fun(*args)
    arr.__setstate__(arr_state)
    return arr


class NoJaxUnpickler(pickle.Unpickler):
    """Reads a stored model with numpy only: jax arrays come back as the
    ndarrays they were pickled from, model classes as attribute bags."""

    _REAL = ("numpy", "builtins", "collections", "copyreg", "datetime")

    def find_class(self, module, name):
        if module == "jax._src.array" and name == "_reconstruct_array":
            return _numpy_from_jax_pickle
        if module.split(".")[0] in self._REAL \
                or module == "predictionio_tpu.data.event":
            return super().find_class(module, name)
        return type(name, (_Stub,), {})


def load_factors(home: Path, instance_id: str):
    storage = open_storage(home)
    blob = storage.get_models().get(instance_id)
    check(blob is not None, f"no stored model for instance {instance_id}")
    manifest = pickle.loads(blob.models)
    wrapper = NoJaxUnpickler(io.BytesIO(manifest["payloads"][0])).load()
    uf = np.asarray(wrapper.model.user_factors, np.float32)
    itf = np.asarray(wrapper.model.item_factors, np.float32)
    inst = storage.get_engine_instances().get(instance_id)
    return uf, itf, wrapper.user_index, wrapper.item_index, dict(inst.env)


def check_rmse(home: Path, train: dict, users, items, ratings, sizes,
               seed: int) -> dict:
    uf, itf, uidx, iidx, env = load_factors(home, train["instance"])
    check(uf.shape[1] == RANK and itf.shape[1] == RANK,
          f"stored factor widths {uf.shape} / {itf.shape}")
    check(np.isfinite(uf).all() and np.isfinite(itf).all(),
          "stored factors are not finite")
    check(env.get("platform") == train["device"]["platform"],
          f"engine instance env names platform={env.get('platform')}")
    rng = np.random.default_rng(seed + 1)
    pick = rng.choice(len(ratings), min(sizes["rmse_sample"], len(ratings)),
                      replace=False)
    ucode = np.full(sizes["users"], -1, np.int64)
    for key, code in uidx._fwd.items():
        ucode[int(key[1:])] = code
    icode = np.full(sizes["items"], -1, np.int64)
    for key, code in iidx._fwd.items():
        icode[int(key[1:])] = code
    u, i, r = ucode[users[pick]], icode[items[pick]], ratings[pick]
    check((u >= 0).all() and (i >= 0).all(),
          "a sampled rating's user or item is missing from the model")
    pred = np.einsum("nk,nk->n", uf[u], itf[i])
    rmse = float(np.sqrt(np.mean((pred - r) ** 2)))
    base = float(np.sqrt(np.mean((r - ratings.mean()) ** 2)))
    check(np.isfinite(rmse), "training RMSE is not finite")
    check(rmse < base, f"training RMSE {rmse:.4f} is not below the "
                       f"global-mean predictor's {base:.4f}")
    return dict(rmse=round(rmse, 4), global_mean_rmse=round(base, 4),
                rmse_sample=int(len(pick)), n_users=int(uf.shape[0]),
                n_items=int(itf.shape[0]))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body=None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method, headers={
        "Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
        ctype = resp.headers.get("Content-Type", "")
        return resp.status, (json.loads(raw) if "json" in ctype
                             else raw.decode())


def metric_total(text: str, name: str, **labels) -> float:
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name + "{") and line.split(" ")[0] != name:
            continue
        if all(f'{k}="{v}"' in line for k, v in labels.items()):
            total += float(line.rsplit(" ", 1)[1])
    return total


def stage_deploy(home: Path, args, expect: dict, queries, burst,
                 rung: str) -> dict:
    """One ``pio deploy`` child: wait until it answers, send the queries,
    read ``GET /`` and ``/metrics``, ``POST /stop``, require exit 0."""
    port = free_port()
    log_path = home / f"deploy_{rung}.log"
    extra = {} if rung == "auto" else {"PIO_RETRIEVAL_RUNG": rung}
    cmd = [sys.executable, "-m", "predictionio_tpu.cli", "deploy",
           "--engine-json", str(home / "engine.json"),
           "--ip", "127.0.0.1", "--port", str(port)]
    if args.mesh:
        cmd += ["--mesh", args.mesh]
    base = f"http://127.0.0.1:{port}"
    t0 = time.perf_counter()
    lf = open(log_path, "w")
    proc = subprocess.Popen(cmd, env=child_env(home, expect["platform"],
                                               extra),
                            stdout=lf, stderr=subprocess.STDOUT,
                            cwd=str(home))
    try:
        deadline = time.monotonic() + args.stage_timeout
        while True:
            if proc.poll() is not None:
                raise SmokeFailure(f"pio deploy ({rung}) exited "
                                   f"{proc.returncode} before serving:\n"
                                   f"{tail(log_path)}")
            try:
                status, root = http("GET", base + "/", timeout=5)
                if status == 200:
                    break
            except (urllib.error.URLError, OSError):
                pass
            check(time.monotonic() < deadline,
                  f"pio deploy ({rung}) never answered GET /")
            time.sleep(0.5)
        ready_s = time.perf_counter() - t0

        def ask(q):
            status, body = http("POST", base + "/queries.json", q)
            check(status == 200, f"query {q} answered {status}")
            return [(s["item"], float(s["score"]))
                    for s in body["itemScores"]]

        # One at a time (B=1 dispatches), then a concurrent burst the
        # scheduler batches into wide dispatches — a wide batch is what
        # puts the score matmul on the MXU.
        answers = [ask(q) for q in queries]
        with concurrent.futures.ThreadPoolExecutor(len(burst)) as pool:
            answers += list(pool.map(ask, burst))
        _, root = http("GET", base + "/")
        _, metrics = http("GET", base + "/metrics")
        status, _ = http("POST", base + "/stop", {})
        check(status == 200, f"POST /stop answered {status}")
        rc = proc.wait(timeout=120)
        check(rc == 0, f"pio deploy ({rung}) exited {rc} after /stop:\n"
                       f"{tail(log_path)}")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        lf.close()
    b = root["backend"]
    dev = dict(platform=b["platform"], kind=b["deviceKind"],
               count=b["deviceCount"])
    check(dev["platform"] == expect["platform"],
          f"pio deploy ({rung}) ran on platform={dev['platform']}")
    m = BACKEND_RE.search(log_path.read_text(errors="replace"))
    check(m is not None and device_of(m) == dev,
          f"deploy ({rung}) log and GET / disagree on the device")
    rungs = {r: metric_total(metrics, "pio_retrieval_requests_total", rung=r)
             for r in ("host", "device", "chunked")}
    return dict(device=dev, wall_s=round(time.perf_counter() - t0, 1),
                ready_s=round(ready_s, 1), compile_s=b["compileSeconds"],
                compiles=b["compiles"], cache_hits=b["compileCacheHits"],
                pallas=b["pallas"], rung_requests=rungs, answers=answers)


def compare_answers(ref, got) -> dict:
    """Device-rung answers against the host numpy rung's: the same id
    set per query, each score within ``SCORE_RTOL`` of the reference's
    largest score for that query."""
    worst = 0.0
    for q, (a, b) in enumerate(zip(ref, got)):
        check(len(a) == len(b) and len(a) > 0,
              f"query {q}: {len(a)} reference vs {len(b)} device results")
        sa, sb = dict(a), dict(b)
        check(set(sa) == set(sb),
              f"query {q}: id sets differ: only-host "
              f"{sorted(set(sa) - set(sb))}, only-device "
              f"{sorted(set(sb) - set(sa))}")
        scale = max(abs(v) for v in sa.values())
        err = max(abs(sa[i] - sb[i]) for i in sa) / scale
        worst = max(worst, err)
    check(worst <= SCORE_RTOL,
          f"device scores off by {worst:.2e} relative (> {SCORE_RTOL})")
    return dict(queries=len(ref), worst_score_rel_err=float(f"{worst:.3e}"))


def stage_kernels_child(home: Path, args, expect: dict) -> dict:
    out_path = home / "kernels.json"
    log_path = home / "kernels.log"
    cmd = [sys.executable, str(Path(__file__).resolve()), "--stage",
           "kernels", "--seed", str(args.seed), "--json-out", str(out_path)]
    if args.cpu_dry_run:
        cmd.append("--cpu-dry-run")
    extra = {}
    if args.cpu_dry_run:  # existing threshold, so a toy corpus is "large"
        extra["PIO_SERVE_CHUNK_ABOVE"] = "1000"
        extra["PIO_SERVE_HOST_MACS"] = "100000"
    t0 = time.perf_counter()
    with open(log_path, "w") as lf:
        rc = subprocess.run(cmd, env=child_env(home, expect["platform"],
                                               extra),
                            stdout=lf, stderr=subprocess.STDOUT,
                            timeout=args.stage_timeout).returncode
    text = log_path.read_text(errors="replace")
    for line in text.splitlines():
        if line.startswith("kernels:"):
            log("  " + line)
    if rc != 0:
        raise SmokeFailure(f"kernel child exited {rc}:\n{tail(log_path)}")
    res = json.loads(out_path.read_text())
    res["wall_s"] = round(time.perf_counter() - t0, 1)
    check(res["device"]["platform"] == expect["platform"],
          f"kernel child ran on platform={res['device']['platform']}")
    return res


# --------------------------------------------------------------------------
# stage 3, in the child: the only code here that imports jax
# --------------------------------------------------------------------------

def run_kernels(args) -> int:
    import jax
    import jax.numpy as jnp

    from predictionio_tpu import backend as backend_mod
    from predictionio_tpu.models.als import _ridge
    from predictionio_tpu.ops import pallas_kernels as pk
    from predictionio_tpu.ops.topk import chunked_top_k
    from predictionio_tpu.retrieval import K_MENU, Retriever

    import logging

    logging.basicConfig(level=logging.INFO,
                        format="[%(levelname)s] [%(name)s] %(message)s")
    be = backend_mod.resolve_backend()
    sizes = DRY if args.cpu_dry_run else FULL
    interpret = be.pallas != "compiled"
    check(args.cpu_dry_run or not interpret,
          f"kernels would run in interpret mode on platform={be.platform}")
    rng = np.random.default_rng(args.seed)
    results = {}

    def say(name, **kv):
        results[name] = kv
        log(f"kernels: {name}: " + " ".join(f"{k}={v}" for k, v in
                                            kv.items()))

    def rel_err(got, ref):
        got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
        return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)),
                                                     1e-30))

    def median_ms(fn, reps=5):
        jax.block_until_ready(fn())
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append((time.perf_counter() - t0) * 1e3)
        return round(float(np.median(ts)), 3)

    # The XLA twins run at "highest" matmul precision: they are the
    # float32 reference, and XLA:TPU's default rounds f32 matmul inputs
    # to bfloat16.
    def twin(fn):
        def run(*a, **k):
            with jax.default_matmul_precision("highest"):
                return fn(*a, **k)
        return run

    # -- ALS gram kernel at the shapes stage 1 hands it: bf16 gathered
    # factors, L a short bucket / a long L-chunked bucket / a ragged tail.
    gram_shapes = [(256, 40), (64, 4096), (64, 1160)] if not interpret \
        else [(16, 40), (8, 1160)]
    worst = 0.0
    for r, l in gram_shapes:
        f = jnp.asarray(rng.standard_normal((r, l, RANK)) / 8, jnp.bfloat16)
        w = jnp.asarray(rng.random((r, l)) < 0.9, jnp.float32)
        c = jnp.asarray(rng.integers(1, 11, (r, l)) * 0.5, jnp.float32) * w
        a, b = pk.fused_gram_vector_pallas(f, w, c, interpret=interpret)
        ra, rb = twin(pk.fused_gram_vector_xla)(f.astype(jnp.float32), w, c)
        check(bool(jnp.isfinite(a).all() and jnp.isfinite(b).all()),
              f"gram {r}x{l}: non-finite output")
        worst = max(worst, rel_err(a, ra), rel_err(b, rb))
        # the same rows as halves of 128-lane rows (the packed view's
        # form), the other half noise: the kernel keeps each slot's half
        # itself and builds the same sums in the same order
        part = jnp.asarray(rng.integers(0, 2, (r, l)), jnp.int32)
        noise = jnp.asarray(rng.standard_normal(f.shape) * 1e3, f.dtype)
        wide = jnp.where(part[..., None] == 1,
                         jnp.concatenate([noise, f], -1),
                         jnp.concatenate([f, noise], -1))
        pa, pb = pk.fused_gram_vector_pallas(wide, w, c, part, pack=2,
                                             interpret=interpret)
        check(interpret or bool((pa == a).all() and (pb == b).all()),
              f"gram {r}x{l}: packed rows differ from the same rows plain")
        worst = max(worst, rel_err(pa, ra), rel_err(pb, rb))
    check(worst <= 1e-4, f"gram kernel off by {worst:.2e} vs XLA twin")
    say("gram", shapes=gram_shapes, twin="fused_gram_vector_xla",
        worst_rel_err=f"{worst:.2e}", tol="1e-4")

    # -- the dense gram kernel: a block of ratings over a whole factor
    # table (NaN = no rating, a real 0.0 among the values), one and
    # several source tiles, rows that pad a row tile.  Implicit weights
    # round w*x to bfloat16 as the gathered kernel does, so they stand
    # further from the float32 twin.
    dense_shapes = [(40, 60_000), (20, 2_500)] if not interpret \
        else [(5, 300)]
    worst = {False: 0.0, True: 0.0}
    for j, n_src in dense_shapes:
        x = jnp.asarray(rng.standard_normal((n_src, RANK)) / 8, jnp.bfloat16)
        vals = rng.integers(0, 6, (j, n_src)).astype(np.float32)
        vals[rng.random((j, n_src)) > 0.05] = np.nan
        block = jnp.asarray(vals, pk.DENSE_BLOCK_DTYPE)
        for implicit in (False, True):
            a, b = pk.fused_gram_dense_pallas(
                block, x, 0.5, implicit=implicit, interpret=interpret)
            ra, rb = twin(pk.fused_gram_dense_xla)(
                block, x.astype(jnp.float32), 0.5, implicit=implicit)
            check(bool(jnp.isfinite(a).all() and jnp.isfinite(b).all()),
                  f"dense gram {j}x{n_src}: non-finite output")
            worst[implicit] = max(worst[implicit], rel_err(a, ra),
                                  rel_err(b, rb))
    check(worst[False] <= 1e-4 and worst[True] <= 5e-3,
          f"dense gram kernel off by {worst[False]:.2e} (explicit) / "
          f"{worst[True]:.2e} (implicit) vs XLA twin")
    say("gram_dense", shapes=dense_shapes, twin="fused_gram_dense_xla",
        worst_rel_err=f"{worst[False]:.2e}/{worst[True]:.2e}",
        tol="1e-4/5e-3")

    # -- the LU solver against the Cholesky branch of _ridge.
    nb = sizes["lu_batch"]
    y = jnp.asarray(rng.standard_normal((nb, 2 * RANK, RANK)) / 8,
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        amat = jnp.einsum("blk,blm->bkm", y, y)
    bvec = jnp.asarray(rng.standard_normal((nb, RANK)), jnp.float32)
    reg = jnp.asarray(0.01 * rng.integers(1, 200, nb), jnp.float32)
    cholesky = jax.jit(lambda a, b, r: twin(_ridge)(a, b, r, "cholesky"))
    ref = cholesky(amat, bvec, reg)
    x = pk.ridge_solve_lu_pallas(amat, bvec, reg, interpret=interpret)
    check(bool(jnp.isfinite(x).all()), "lu: non-finite output")
    err = rel_err(x, ref)
    check(err <= 1e-3, f"lu solver off by {err:.2e} vs Cholesky")
    say("lu", batch=nb, rank=RANK, twin="_ridge(cholesky)",
        worst_rel_err=f"{err:.2e}", tol="1e-3",
        ms=median_ms(lambda: pk.ridge_solve_lu_pallas(
            amat, bvec, reg, interpret=interpret)),
        twin_ms=median_ms(lambda: cholesky(amat, bvec, reg)))

    # -- the large corpus: 2.5M x 64 float32 resident on the chip.
    n = sizes["corpus"]
    corpus_h = (rng.standard_normal((n, CORPUS_DIM)) / 8).astype(np.float32)
    queries_h = (rng.standard_normal((64, CORPUS_DIM)) / 8).astype(
        np.float32)
    corpus_d = jnp.asarray(corpus_h)
    jax.block_until_ready(corpus_d)
    # The plain reference, computed once: every score in float64, and the
    # true k-th best score per query for each k on the menu.
    exact = queries_h.astype(np.float64) @ corpus_h.astype(np.float64).T
    scale = np.abs(exact).max()
    kth_best = {k: -np.partition(-exact, k - 1, axis=1)[:, k - 1]
                for k in K_MENU}

    def topk_agrees(name, s, i, q_h, k):
        """Returned ids are distinct and valid, their scores match the
        float64 reference, and none is worse than the true k-th best by
        more than the tolerance.  ``q_h`` is a prefix of ``queries_h``."""
        s, i = np.asarray(s), np.asarray(i)
        check(s.shape == (len(q_h), k) and i.shape == (len(q_h), k),
              f"{name}: shape {s.shape}/{i.shape}")
        check(np.isfinite(s).all(), f"{name}: non-finite scores")
        check(i.min() >= 0 and i.max() < n, f"{name}: id out of range")
        kth = kth_best[k]
        worst_s = worst_k = 0.0
        for row in range(len(q_h)):
            check(len(set(i[row].tolist())) == k, f"{name}: duplicate ids")
            true = exact[row, i[row]]
            worst_s = max(worst_s, np.abs(true - s[row]).max() / scale)
            worst_k = max(worst_k, (kth[row] - true.min()) / scale)
        check(worst_s <= SCORE_RTOL,
              f"{name}: scores off by {worst_s:.2e} relative")
        check(worst_k <= SCORE_RTOL,
              f"{name}: returned an item {worst_k:.2e} below the k-th best")
        return max(worst_s, worst_k)

    # fused_topk_pallas vs chunked_top_k, directly, B=1 and B=64.
    worst = 0.0
    timing = {}
    for b in (1, 64):
        q_d = jnp.asarray(queries_h[:b])
        for k in K_MENU:
            s, i, _ = pk.fused_topk_pallas(q_d, corpus_d, k, n_valid=n,
                                           interpret=interpret)
            worst = max(worst, topk_agrees(f"fused_topk b{b} k{k}", s, i,
                                           queries_h[:b], k))
            ts, ti = twin(chunked_top_k)(q_d, corpus_d, k, chunk=262_144)
            worst = max(worst, topk_agrees(f"chunked_top_k b{b} k{k}", ts,
                                           ti, queries_h[:b], k))
            if k == 10 and not interpret:
                timing[f"b{b}_k10_ms"] = median_ms(
                    lambda: pk.fused_topk_pallas(q_d, corpus_d, k,
                                                 n_valid=n))
                timing[f"b{b}_k10_twin_ms"] = median_ms(
                    lambda: chunked_top_k(q_d, corpus_d, k, chunk=262_144))
    say("fused_topk", corpus=f"{n}x{CORPUS_DIM}", batches=[1, 64],
        ks=list(K_MENU), twin="chunked_top_k",
        worst_rel_err=f"{worst:.2e}", tol=SCORE_RTOL, **timing)

    # The facade, unforced: plan() must route a batch whose work exceeds
    # PIO_SERVE_HOST_MACS to `chunked`, which runs fused_topk_pallas.
    # B=1 at 2.5M x 64 is 1.6e8 MACs — under the 2e8 host threshold, so
    # the facade answers it on the host by design; B=2 compiles the same
    # 8-row kernel block B=1 would.
    retr = Retriever(corpus_d, name="smoke")
    plans = {}
    worst = 0.0
    for b in (1, 2, 64):
        for k in K_MENU:
            plan = retr.plan(b, k)
            s, i, info = retr.topk(queries_h[:b], k)
            check(info["rung"] == plan.rung, "topk() ran another rung than "
                                             "plan() chose")
            plans[b] = plan.rung
            worst = max(worst, topk_agrees(
                f"Retriever.topk b{b} k{k} ({plan.rung})", s, i,
                queries_h[:b], k))
    if not args.cpu_dry_run:
        check(plans[1] == "host" and plans[2] == "chunked"
              and plans[64] == "chunked",
              f"Retriever.plan() chose {plans} at {n}x{CORPUS_DIM}")
    say("retriever", corpus=f"{n}x{CORPUS_DIM}", plans=plans,
        ks=list(K_MENU), reference="float64 numpy",
        worst_rel_err=f"{worst:.2e}", tol=SCORE_RTOL)

    # -- pq_scan over a packed code matrix of the same corpus length:
    # D=64 quantizes to M=16 residual subspaces + the coarse table = 17
    # uint8 code rows (42 MB resident).  The LUT matmul's default
    # precision is part of the kernel under test, so the twin is the
    # exact gather scan and the tolerance is the LUTs' bfloat16 rounding.
    s_tables = 17
    codes = jnp.asarray(rng.integers(0, 256, (s_tables, n)), jnp.uint8)
    worst = 0.0
    for b in (1, 64):
        luts = jnp.asarray(rng.standard_normal((b, s_tables, 256)) / 8,
                           jnp.float32)
        for k in ((40, 400) if not interpret else (4, 40)):
            ps, pi = pk.pq_scan_pallas(luts, codes, k, n_valid=n,
                                       interpret=interpret)
            xs, xi = pk.pq_scan_xla(luts, codes, k, n_valid=n)
            ps, pi, xs, xi = map(np.asarray, (ps, pi, xs, xi))
            check(np.isfinite(ps).all(), f"pq_scan b{b} k{k}: non-finite")
            scale = np.abs(xs).max()
            # Same candidates up to the rounding at the k-th boundary:
            # every Pallas score is within tolerance of the twin's score
            # at the same rank.
            err = float(np.abs(ps - xs).max() / scale)
            overlap = np.mean([len(set(pi[r]) & set(xi[r])) / k
                               for r in range(b)])
            check(err <= 1e-2, f"pq_scan b{b} k{k} off by {err:.2e}")
            check(overlap >= 0.9, f"pq_scan b{b} k{k}: only {overlap:.2f} "
                                  "of the twin's ids")
            worst = max(worst, err)
    say("pq_scan", codes=f"{s_tables}x{n} uint8", batches=[1, 64],
        twin="pq_scan_xla", worst_rel_err=f"{worst:.2e}", tol="1e-2")

    stats = backend_mod.compile_stats()
    out = dict(device=dict(platform=be.platform, kind=be.device_kind,
                           count=be.device_count),
               pallas=be.pallas, compile_s=stats["compileSeconds"],
               compiles=stats["compiles"],
               cache_hits=stats["compileCacheHits"], kernels=results)
    Path(args.json_out).write_text(json.dumps(out))
    return 0


# --------------------------------------------------------------------------
# parent
# --------------------------------------------------------------------------

def result_line(ok: bool, device: dict) -> str:
    """The last stdout line of a run that reached a TPU: exactly the keys
    ``ok`` and ``device`` (``platform``, ``kind``, ``count`` as jax reports
    them) — whoever reads it compares the key sets."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def fail(args, device: dict) -> int:
    """A stage failed on the chip: say so in the result line (the dry run
    prints none) and exit 1."""
    if not args.cpu_dry_run:
        log(result_line(False, device))
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ratings", type=int, default=None,
                    help="cut the rating count (printed); never the widths")
    ap.add_argument("--iterations", type=int, default=3)
    ap.add_argument("--mesh", default=None,
                    help="e.g. data=4: stages 1-2 over a device mesh with "
                         "factorSharding=sharded (a four-chip host)")
    ap.add_argument("--stage-timeout", type=float, default=900.0)
    ap.add_argument("--keep", action="store_true",
                    help="keep the throwaway PIO_HOME (logs) afterwards")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="debug this script at toy sizes on the CPU; "
                         "prints no result and exits non-zero")
    ap.add_argument("--stage", choices=["kernels"], help=argparse.SUPPRESS)
    ap.add_argument("--json-out", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.stage == "kernels":
        try:
            return run_kernels(args)
        except SmokeFailure as e:
            log(f"kernels: FAILED: {e}")
            return 1

    t_start = time.perf_counter()
    want_platform = "cpu" if args.cpu_dry_run else "tpu"
    try:
        import predictionio_tpu.data.storage  # noqa: F401 — fail at once
    except ImportError as e:
        print(f"chip_smoke: the predictionio_tpu package is not next to "
              f"this script ({e}); nothing run", file=sys.stderr)
        return 2
    try:
        found = probe_platform()
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    if found["platform"] != want_platform:
        print(f"chip_smoke: jax found platform={found['platform']} "
              f"(device_kind={found['kind']}, {found['count']} device(s)), "
              f"not {want_platform}; nothing run", file=sys.stderr)
        return 2
    log(f"chip_smoke: jax finds platform={found['platform']} "
        f"device_kind={found['kind']} devices={found['count']}")

    sizes = dict(DRY if args.cpu_dry_run else FULL)
    if args.ratings is not None and args.ratings != sizes["ratings"]:
        log(f"chip_smoke: CUT ratings {sizes['ratings']} -> {args.ratings} "
            "(users, items, rank and corpus widths unchanged)")
        sizes["ratings"] = args.ratings
    expect = dict(platform=want_platform,
                  train=TRAIN_ON_CPU if args.cpu_dry_run else TRAIN_ON_TPU)

    home = Path(tempfile.mkdtemp(prefix="pio_smoke_"))
    summary = {}
    ok = False
    try:
        users, items, ratings = synth_ratings(
            args.seed, sizes["users"], sizes["items"], sizes["ratings"])
        seed_s = seed_store(home, users, items, ratings, sizes["users"],
                            sizes["items"])
        algo = {"rank": RANK, "numIterations": args.iterations,
                "lambda_": 0.01, "seed": args.seed}
        if args.mesh:
            algo["factorSharding"] = "sharded"
        (home / "engine.json").write_text(json.dumps({
            "id": "default",
            "engineFactory":
                "predictionio_tpu.templates.recommendation:engine",
            "datasource": {"params": {"appName": "smoke"}},
            "algorithms": [{"name": "als", "params": algo}]}))
        log(f"chip_smoke: seeded {sizes['ratings']} rate events "
            f"({sizes['users']} users x {sizes['items']} items, seed "
            f"{args.seed}) in {seed_s:.1f}s")

        # stage 1
        train = stage_train(home, args, expect)
        train.update(check_rmse(home, train, users, items, ratings, sizes,
                                args.seed))
        summary["train"] = train
        d = train["device"]
        log(f"stage 1 train: platform={d['platform']} "
            f"device_kind={d['kind']} devices={d['count']} "
            f"wall_s={train['wall_s']} phases_s={train['phases_s']} "
            f"compile_s={train['compile_s']} "
            f"(cache hits {train['cache_hits']}/{train['compiles']}) "
            f"use_pallas={train['use_pallas']} solver={train['solver']} "
            f"gram_dtype={train['gram_dtype']} "
            f"device_prep={train['device_prep']} | train RMSE "
            f"{train['rmse']} on {train['rmse_sample']} sampled ratings "
            f"< global-mean {train['global_mean_rmse']}"
            + (f" | factors on {train['factor_devices']} devices, "
               f"bytes_in_use {train['bytes_in_use']}" if args.mesh else ""))

        # stage 2: distinct users (a repeat is answered by the result
        # cache and reaches no rung)
        qrng = np.random.default_rng(args.seed + 2)
        quser = qrng.choice(sizes["users"], 42, replace=False)
        queries = [{"user": f"u{u}", "num": 10} for u in quser[:8]] + \
                  [{"user": f"u{u}", "num": 100} for u in quser[8:10]]
        burst = [{"user": f"u{u}", "num": 10} for u in quser[10:]]
        host = stage_deploy(home, args, expect, queries, burst, "auto")
        check(host["rung_requests"]["host"] > 0
              and host["rung_requests"]["device"] == 0,
              f"default routing did not stay on the host rung: "
              f"{host['rung_requests']}")
        dev = stage_deploy(home, args, expect, queries, burst, "device")
        check(dev["rung_requests"]["device"] > 0,
              'pio_retrieval_requests_total{rung="device"} is 0')
        cmp_ = compare_answers(host.pop("answers"), dev.pop("answers"))
        summary["deploy_host"], summary["deploy_device"] = host, dev
        summary["deploy_compare"] = cmp_
        for name, st in (("host rung", host), ("device rung", dev)):
            d = st["device"]
            log(f"stage 2 deploy ({name}): platform={d['platform']} "
                f"device_kind={d['kind']} devices={d['count']} "
                f"wall_s={st['wall_s']} ready_s={st['ready_s']} "
                f"compile_s={st['compile_s']} (cache hits "
                f"{st['cache_hits']}/{st['compiles']}) "
                f"rung_requests={st['rung_requests']} exit=0")
        log(f"stage 2 compare: {cmp_['queries']} queries, all 200, device "
            f"id sets == host id sets, worst score error "
            f"{cmp_['worst_score_rel_err']} relative (tol {SCORE_RTOL})")

        # stage 3 (one chip's worth of kernels; skipped under --mesh,
        # which repeats stages 1-2 only)
        if not args.mesh:
            kern = stage_kernels_child(home, args, expect)
            summary["kernels"] = kern
            d = kern["device"]
            log(f"stage 3 kernels: platform={d['platform']} "
                f"device_kind={d['kind']} devices={d['count']} "
                f"wall_s={kern['wall_s']} compile_s={kern['compile_s']} "
                f"(cache hits {kern['cache_hits']}/{kern['compiles']}) "
                f"pallas={kern['pallas']}: gram, gram_dense, lu, "
                f"fused_topk, pq_scan each within tolerance of its XLA twin")
        ok = True
    except SmokeFailure as e:
        log(f"chip_smoke: FAILED: {e}")
    except subprocess.TimeoutExpired as e:
        log(f"chip_smoke: FAILED: stage timed out: {e}")
    finally:
        if args.keep or not ok:
            log(f"chip_smoke: logs kept in {home}")
        else:
            shutil.rmtree(home, ignore_errors=True)

    total = time.perf_counter() - t_start
    if not ok:
        return fail(args, found)
    stages = [summary["train"], summary["deploy_host"],
              summary["deploy_device"]] + (
        [summary["kernels"]] if "kernels" in summary else [])
    devices = [s["device"] for s in stages]
    if any(dv != devices[0] for dv in devices):
        log(f"chip_smoke: FAILED: stages disagree on the device: {devices}")
        return fail(args, found)
    log(f"chip_smoke: all stages passed in {total:.1f}s; compile seconds "
        f"by stage {[s['compile_s'] for s in stages]}")
    if args.cpu_dry_run:
        log("chip_smoke: DRY RUN on the CPU — not a result")
        return DRY_RUN_EXIT
    # The PR's summary (claims nothing), then the result line — last, and
    # holding nothing but "ok" and "device".
    log("chip_smoke: summary " + json.dumps({
        "total_s": round(total, 1),
        "compile_s": round(sum(s["compile_s"] for s in stages), 1),
        "claim": None}))
    log(result_line(True, devices[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
