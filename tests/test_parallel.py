"""Mesh/sharding tests on the 8-device virtual CPU mesh (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from predictionio_tpu.parallel import (
    AXIS_DATA,
    AXIS_MODEL,
    batch_sharding,
    make_mesh,
    replicated,
    sharding,
)
from predictionio_tpu.parallel.collectives import collective_microbench


def test_virtual_device_count():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"


def test_make_mesh_default():
    m = make_mesh()
    assert m.axis_names == (AXIS_DATA,)
    assert m.shape[AXIS_DATA] == 8


def test_make_mesh_2d_and_wildcard():
    m = make_mesh({AXIS_DATA: 4, AXIS_MODEL: 2})
    assert m.shape == {AXIS_DATA: 4, AXIS_MODEL: 2}
    m2 = make_mesh({AXIS_DATA: -1, AXIS_MODEL: 2})
    assert m2.shape[AXIS_DATA] == 4


def test_make_mesh_errors():
    with pytest.raises(ValueError, match="need"):
        make_mesh({AXIS_DATA: 16})  # oversubscribed
    # Undersubscribed is fine: take a device prefix (`--mesh data=3`).
    assert dict(make_mesh({AXIS_DATA: 3}).shape) == {AXIS_DATA: 3}
    with pytest.raises(ValueError, match="divisible"):
        make_mesh({AXIS_DATA: -1, AXIS_MODEL: 3})
    with pytest.raises(ValueError, match="one mesh axis"):
        make_mesh({AXIS_DATA: -1, AXIS_MODEL: -1})


def test_sharded_matmul_matches_single_device():
    """pjit over the mesh computes the same result as one device."""
    m = make_mesh({AXIS_DATA: 4, AXIS_MODEL: 2})
    rng = np.random.default_rng(0)
    a = rng.normal(size=(32, 16)).astype(np.float32)
    b = rng.normal(size=(16, 8)).astype(np.float32)
    a_sh = jax.device_put(a, sharding(m, AXIS_DATA, None))
    b_sh = jax.device_put(b, sharding(m, None, AXIS_MODEL))

    @jax.jit
    def matmul(x, y):
        return x @ y

    out = matmul(a_sh, b_sh)
    # sharded reduction order differs from single-device accumulation
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4, atol=1e-5)
    assert not out.is_fully_replicated or out.sharding.is_fully_replicated


def test_batch_sharding_and_replicated():
    m = make_mesh()
    x = jnp.arange(16.0).reshape(16, 1)
    xs = jax.device_put(x, batch_sharding(m))
    assert xs.sharding.spec == PartitionSpec(AXIS_DATA)
    r = jax.device_put(x, replicated(m))
    assert r.sharding.is_fully_replicated


def test_psum_semantics_on_mesh():
    """shard_map + psum over the data axis == global sum (the treeAggregate
    analogue, SURVEY.md §2.4 'hierarchical reduction')."""
    from functools import partial

    m = make_mesh()
    x = jnp.ones((8, 4))
    xs = jax.device_put(x, batch_sharding(m))

    @partial(jax.shard_map, mesh=m, in_specs=PartitionSpec(AXIS_DATA),
             out_specs=PartitionSpec())
    def global_sum(v):
        return jax.lax.psum(v.sum(keepdims=True), AXIS_DATA)

    out = global_sum(xs)
    assert float(out.ravel()[0]) == 32.0


def test_collective_microbench_runs():
    m = make_mesh()
    res = collective_microbench(m, size_mb=0.25, iters=2)
    assert set(res) == {"all_reduce", "all_gather", "all_to_all"}
    for v in res.values():
        assert v["seconds"] > 0 and v["algo_bw_gbps"] > 0
