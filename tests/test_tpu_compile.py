"""Mosaic's verdict on the ALS dense gram kernel at real widths, with no
chip: libtpu compiles for a described v5e in the sandbox (PERF.md, PR 21).
It proves compilation, not results.  The topology is described inside a
fixture, by the one worker that runs this file; keep every such test
here."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rank,rows,n_src,implicit", [
    (64, 96, 480_189, False),     # als-netflix-r64's item side
    (64, 4096, 17_770, True),     # its user side, implicit weights
    (128, 64, 480_189, False),    # the widest rank that plans dense rows
], ids=["r64-items", "r64-users-implicit", "r128"])
def test_dense_gram_kernel_compiles_for_v5e(one_chip, rank, rows, n_src,
                                            implicit):
    from predictionio_tpu.ops.pallas_kernels import (
        DENSE_BLOCK_DTYPE, dense_block_width, fused_gram_dense_pallas,
    )

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda block, x, alpha: fused_gram_dense_pallas(
            block, x, alpha, implicit=implicit)).lower(
        shape((rows, dense_block_width(n_src)), DENSE_BLOCK_DTYPE),
        shape((n_src, rank), jnp.bfloat16),
        shape((), jnp.float32)).compile()
    # the name the benchmark's als_gram_roofline and als_dense_gram_ms read
    assert "fused_gram_dense_pallas" in compiled.as_text()
