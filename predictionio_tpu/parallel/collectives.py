"""Collective micro-benchmark harness.

Reference analogue: none (Spark's shuffle metrics live in the external Spark
UI).  SURVEY.md §2.5 makes a collective micro-bench a first-class build
deliverable — it grounds the samples/sec/chip numbers in measured ICI
bandwidth and catches sharding regressions on real hardware.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


__all__ = ["collective_microbench"]


def _timed(fn, *args, iters: int = 5) -> float:
    fn(*args).block_until_ready()  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args).block_until_ready()
    return (time.perf_counter() - t0) / iters


def collective_microbench(
    mesh: Mesh, *, size_mb: float = 4.0, axis: str | None = None, iters: int = 5
) -> Dict[str, Dict[str, float]]:
    """Measure all_reduce / all_gather / all_to_all over one mesh axis.

    Returns {collective: {seconds, algo_bw_gbps}} where algo bandwidth is
    payload_bytes / time (the ring-efficiency factor is left to the reader —
    this is a regression harness, not a NIC spec sheet).
    """
    axis = axis or mesh.axis_names[0]
    n = mesh.shape[axis]
    per_device_rows = max(1, int(size_mb * 1024 * 1024 / 4) // 128)
    global_shape = (per_device_rows * n, 128)
    x = jnp.zeros(global_shape, jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, PartitionSpec(axis)))
    bytes_payload = x.size * x.dtype.itemsize

    in_spec = PartitionSpec(axis)
    results: Dict[str, Dict[str, float]] = {}

    @partial(
        jax.shard_map, mesh=mesh, in_specs=in_spec, out_specs=PartitionSpec()
    )
    def _psum(v):
        return jax.lax.psum(v, axis)

    @partial(
        jax.shard_map, mesh=mesh, in_specs=in_spec, out_specs=PartitionSpec(),
        check_vma=False,  # all_gather output replication isn't statically inferable
    )
    def _all_gather(v):
        return jax.lax.all_gather(v, axis, tiled=True)

    @partial(
        jax.shard_map, mesh=mesh, in_specs=in_spec, out_specs=in_spec
    )
    def _all_to_all(v):
        return jax.lax.all_to_all(
            v.reshape(n, v.shape[0] // n, v.shape[1]), axis, 0, 0, tiled=False
        ).reshape(v.shape)

    for name, fn in (("all_reduce", _psum), ("all_gather", _all_gather),
                     ("all_to_all", _all_to_all)):
        jitted = jax.jit(fn)
        secs = _timed(jitted, x, iters=iters)
        results[name] = {
            "seconds": secs,
            "algo_bw_gbps": bytes_payload / secs / 1e9,
            "payload_mb": bytes_payload / 1e6,
            "axis_size": float(n),
        }
    return results
