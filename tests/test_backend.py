"""Backend resolution, the compile cache, and what keeps a chip run honest
(ISSUE 21): no quiet CPU fallback, kernels that still lower for Mosaic,
native libraries built from the tracked sources only."""

import importlib.util
import json
import os
import subprocess
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu import backend
from predictionio_tpu.models import als
from predictionio_tpu.ops import pallas_kernels as pk

REPO = Path(__file__).resolve().parents[1]


# -- chip_smoke.py without a chip -------------------------------------------

def test_chip_smoke_refuses_cpu_at_once():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform=cpu" in r.stderr and "nothing run" in r.stderr
    assert r.stdout.strip() == ""       # no result line, no work started


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "predictionio_tpu" in r.stderr


def test_chip_smoke_result_line_has_exactly_ok_and_device():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    for ok in (True, False):
        line = smoke.result_line(ok, {"platform": "tpu", "kind": "TPU v5 lite",
                                      "count": 1, "extra": "dropped"})
        doc = json.loads(line)
        assert "\n" not in line and doc["ok"] is ok
        assert set(doc) == {"ok", "device"}
        assert doc["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                                 "count": 1}
        assert type(doc["device"]["count"]) is int


# -- the compile cache --------------------------------------------------------

def test_compile_cache_leaves_env_setting_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def forbidden(*a, **k):
        raise AssertionError("the env var is set: nothing else may be")

    monkeypatch.setattr(jax.config, "update", forbidden)
    assert backend.configure_compile_cache() == str(tmp_path)


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("PIO_HOME", "/tmp/somewhere/else")
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    path = backend.configure_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert seen == {"jax_compilation_cache_dir": path}


# -- no fallback that hides the device ---------------------------------------

def test_backend_probes_raise_when_the_backend_cannot_start(monkeypatch):
    def down():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", down)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pk.pallas_supported()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        als._resolve_gram_dtype("auto")
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        als.prepare_als_inputs(np.zeros(4, np.int32), np.zeros(4, np.int32),
                               np.ones(4, np.float32), 2, 2,
                               als.ALSConfig(rank=4))


def test_resolve_backend_names_platform_and_interpret_mode(caplog):
    with caplog.at_level("INFO", logger="predictionio_tpu.backend"):
        b = backend.resolve_backend()
    assert b.platform == "cpu" and b.pallas == "interpret"
    assert b.device_count == len(jax.devices())
    assert b.as_env()["platform"] == "cpu"
    line = caplog.text
    assert "platform=cpu" in line and "device_kind=cpu" in line
    assert f"devices={b.device_count}" in line and "pallas=interpret" in line


def test_cpu_fallback_on_a_tpu_host_is_an_error(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(backend, "attached_tpu_chips", lambda: 1)
    monkeypatch.setattr(backend, "configure_compile_cache", lambda: "x")
    with pytest.raises(backend.BackendError, match="one process per chip"):
        backend.resolve_backend()
    # saying so makes it legitimate
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert backend.resolve_backend().platform == "cpu"


def test_a_chip_held_by_another_process_names_the_cause(monkeypatch):
    def held():
        raise RuntimeError(
            "Unable to initialize backend 'tpu': ABORTED: Internal error "
            "when accessing libtpu multi-process lockfile.")

    monkeypatch.setattr(backend, "describe_backend", held)
    monkeypatch.setattr(backend, "configure_compile_cache", lambda: "x")
    with pytest.raises(backend.BackendError, match="one process per chip"):
        backend.resolve_backend()


def test_compile_build_has_no_retry(monkeypatch):
    class Lowered:
        calls = 0

        def compile(self, compiler_options=None):
            Lowered.calls += 1
            raise RuntimeError("refused")

    with pytest.raises(RuntimeError, match="refused"):
        als._compile_build(Lowered())
    assert Lowered.calls == 1


def test_prewarm_compile_failure_is_a_warning(caplog):
    import concurrent.futures

    class Lowered:
        def compile(self):
            raise RuntimeError("vmem exhausted")

    fut = concurrent.futures.Future()
    with caplog.at_level("WARNING", logger="predictionio_tpu.models.als"):
        als._compile_train_loop({}, Lowered(), fut)
    assert fut.result() is None
    assert "vmem exhausted" in caplog.text


def test_mesh_runs_take_the_xla_twins_on_tpu(monkeypatch):
    """GSPMD cannot partition a Mosaic custom call: on a multi-device
    mesh the auto settings resolve to the XLA gram + Cholesky, and a
    forced kernel is an error that says why."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    monkeypatch.setattr(als, "pallas_supported", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    sds = jax.ShapeDtypeStruct((8, 16), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    buckets = [("plain", sds, sds, sds, sds)]
    st = als._resolve_loop_statics(als.ALSConfig(rank=64), buckets, buckets)
    assert st["solver"] == "cholesky"
    assert st["pallas_flags"] == ((False,), (False,))
    with pytest.raises(ValueError, match="cannot be partitioned"):
        als._resolve_loop_statics(als.ALSConfig(rank=64, solver="lu"),
                                  buckets, buckets)
    one = jax.ShapeDtypeStruct((8, 16), jnp.int32)
    st = als._resolve_loop_statics(als.ALSConfig(rank=64),
                                   [("plain", one, one, one, one)] * 2,
                                   [("plain", one, one, one, one)])
    assert st["solver"] == "lu" and st["pallas_flags"][0] == (True, True)


@pytest.mark.parametrize("rank,solver", [(64, "lu"), (128, "cholesky")])
def test_auto_solver_on_one_chip_follows_the_lanes_solve_vmem_limit(
        monkeypatch, rank, solver):
    """Past rank 70 the lanes solve's working set overflows VMEM and
    ``auto`` keeps XLA's Cholesky; the gram kernel is not bound by it."""
    monkeypatch.setattr(als, "pallas_supported", lambda: True)
    one = jax.ShapeDtypeStruct((8, 16), jnp.int32)
    buckets = [("plain", one, one, one, one)]
    st = als._resolve_loop_statics(als.ALSConfig(rank=rank), buckets, buckets)
    assert st["solver"] == solver
    assert st["pallas_flags"] == ((True,), (True,))


# -- every kernel still lowers for Mosaic -------------------------------------

def _lowers_for_tpu(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


F32, BF16, U8 = jnp.float32, jnp.bfloat16, jnp.uint8


@pytest.mark.parametrize("name,fn,shapes", [
    # training shapes: ALS buckets at rank 64 (a short bucket, a ragged
    # L-chunked one), the ML-25M solve batch
    ("gram", partial(pk.fused_gram_vector_pallas, interpret=False),
     [((256, 40, 64), BF16), ((256, 40), F32), ((256, 40), F32)]),
    ("gram-ragged", partial(pk.fused_gram_vector_pallas, interpret=False),
     [((64, 1160, 64), BF16), ((64, 1160), F32), ((64, 1160), F32)]),
    # the packed view's 128-lane rows and each slot's part (rank 64: two
    # halves; rank 32: four quarters, a ragged last chunk)
    ("gram-packed", lambda f, w, c, part: pk.fused_gram_vector_pallas(
        f, w, c, part, pack=2, interpret=False),
     [((2048, 512, 128), BF16), ((2048, 512), F32), ((2048, 512), F32),
      ((2048, 512), jnp.int32)]),
    ("gram-packed-r32", lambda f, w, c, part: pk.fused_gram_vector_pallas(
        f, w, c, part, pack=4, interpret=False),
     [((64, 2100, 128), BF16), ((64, 2100), F32), ((64, 2100), F32),
      ((64, 2100), jnp.int32)]),
    ("lu", partial(pk.ridge_solve_lu_pallas, interpret=False),
     [((6040, 64, 64), F32), ((6040, 64), F32), ((6040,), F32)]),
    # the templates' default rank (a last block of 2 rows and 2 columns),
    # and als-netflix-r64's largest user-side batch, its dense rows
    ("lu-k10", partial(pk.ridge_solve_lu_pallas, interpret=False),
     [((6040, 10, 10), F32), ((6040, 10), F32), ((6040,), F32)]),
    ("lu-netflix-users", partial(pk.ridge_solve_lu_pallas, interpret=False),
     [((23488, 64, 64), F32), ((23488, 64), F32), ((23488,), F32)]),
    # serving shapes: a 2.5M x 64 corpus, menu k, B=1 and B=64
    ("topk-b1", partial(pk.fused_topk_pallas, k=10, n_valid=2_499_990,
                        interpret=False),
     [((1, 64), F32), ((2_500_000, 64), F32)]),
    ("topk-b64", partial(pk.fused_topk_pallas, k=1000, interpret=False),
     [((64, 64), F32), ((2_500_000, 64), F32)]),
    ("pq-b1", partial(pk.pq_scan_pallas, k=40, interpret=False),
     [((1, 17, 256), F32), ((17, 2_500_000), U8)]),
    ("pq-b64", partial(pk.pq_scan_pallas, k=400, n_valid=999_000,
                       interpret=False),
     [((64, 9, 256), F32), ((9, 1_000_000), U8)]),
])
def test_pallas_kernels_lower_for_tpu(name, fn, shapes):
    _lowers_for_tpu(fn, *shapes)


def test_running_topk_kernels_store_on_lane_aligned_offsets():
    """What Mosaic (libtpu 0.0.34) refused was a store at a traced lane
    index; the repaired kernels write whole lane-aligned blocks.  Pin the
    shapes that guarantee it: k padded to a lane multiple everywhere."""
    for k in (1, 10, 100, 1000):
        assert pk._lane_pad(k) % 128 == 0 and pk._lane_pad(k) >= k
    q = jnp.asarray(np.random.default_rng(0).standard_normal((3, 16)),
                    jnp.float32)
    items = jnp.asarray(np.random.default_rng(1).standard_normal((300, 16)),
                        jnp.float32)
    s, i, _ = pk.fused_topk_pallas(q, items, 10, tile=128, interpret=True)
    assert s.shape == (3, 10) and i.shape == (3, 10)
    ref = np.argsort(-(np.asarray(q) @ np.asarray(items).T), axis=1)[:, :10]
    assert [set(r) for r in np.asarray(i).tolist()] == \
        [set(r) for r in ref.tolist()]


# -- native libraries ---------------------------------------------------------

def test_native_library_is_named_by_source_hash(tmp_path, monkeypatch):
    from predictionio_tpu.native import build

    src = tmp_path / "thing.cc"
    src.write_text('extern "C" int answer() { return 41; }\n')
    # a foreign binary under the old fixed name must never be opened
    (tmp_path / "libthing.so").write_bytes(b"not an ELF file")
    monkeypatch.setattr(build, "NATIVE_DIR", tmp_path)
    monkeypatch.setattr(build, "_cache", {})
    lib = build.load_library("thing")
    if lib is None:
        pytest.skip("no g++ here")
    assert lib.answer() == 41
    first = build._library_path(src)
    assert first.exists() and first.name != "libthing.so"
    # editing the source changes the name; the old build is cleaned up
    src.write_text('extern "C" int answer() { return 42; }\n')
    monkeypatch.setattr(build, "_cache", {})
    assert build.load_library("thing").answer() == 42
    second = build._library_path(src)
    assert second != first and second.exists() and not first.exists()
