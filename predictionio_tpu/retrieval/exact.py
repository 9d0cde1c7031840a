"""Exact retrieval rungs — host, single-device, chunked, mesh-sharded.

Every rung returns the SAME answer (the true top-k id set, scores within
fp tolerance — test-pinned); they differ only in where the work runs and
what memory it touches:

- ``host``    — numpy over host-resident vectors; wins whenever one
  device dispatch round-trip costs more than the matmul (B=1 serving).
- ``device``  — one jitted ``top_k_scores`` dispatch; the [B, N] score
  block materializes, fine for small/medium corpora.
- ``chunked`` — ``chunked_top_k`` scan slabs (auto-padded tail); score
  memory bounded at [B, chunk] for corpora that outgrow HBM comfort.
  On TPU the facade swaps in the fused Pallas kernel
  (``ops.pallas_kernels.fused_topk``) which never materializes even the
  slab.
- ``sharded`` — corpus row-sharded over a mesh axis, per-shard local
  top-k + O(k·shards·B) all-gather merge (``ops.topk.sharded_top_k``).

The jitted callables are cached per (rung, B, k) in a caller-owned dict
so the serving hot path is ONE cached dispatch — a fresh closure per
request would re-trace and pay eager round-trips (the exact trap the ALS
template's ``_mips_jit`` cache used to guard; that cache now lives here,
shared by every engine).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

import numpy as np

from predictionio_tpu.obs import dispatch_stage, get_registry
from predictionio_tpu.ops.pallas_kernels import (
    fused_topk_pallas,
    fused_topk_tiles,
    pallas_supported,
)
from predictionio_tpu.ops.topk import (
    chunked_top_k,
    host_top_k,
    sharded_top_k,
    top_k_scores,
)

__all__ = ["exact_host", "exact_device", "exact_chunked", "exact_sharded",
           "SERVE_CACHE_LOCK"]

# Guards cold-path serving cache builds (jit compiles, device staging):
# a burst of concurrent first requests on the threaded server must not
# each trace its own program or stage its own corpus copy.  One process-
# wide lock — builds are rare and short relative to what they prevent.
SERVE_CACHE_LOCK = threading.Lock()


def _cached(jit_cache: Dict, key, build):
    fn = jit_cache.get(key)
    if fn is None:
        with SERVE_CACHE_LOCK:
            fn = jit_cache.get(key)
            if fn is None:
                fn = build()
                jit_cache[key] = fn
    return fn


def _staged_call(fn, stage_args) -> Tuple[np.ndarray, ...]:
    """THE device round trip of the exact device rungs, in the three
    host stages a dispatch spends around the kernel: ``h2d``
    (``stage_args()``: the per-call uploads), ``launch`` (the jitted call
    returning; dispatch is asynchronous) and ``wait`` (``device_get``
    returning: kernel + D2H).  No synchronisation beyond the one
    ``device_get`` the rungs always made; whatever ``fn`` returns beside
    scores and ids (the Pallas top-k's round count) rides the same one."""
    import jax

    with dispatch_stage("retrieval.h2d", "h2d"):
        args = stage_args()
    with dispatch_stage("retrieval.launch", "launch"):
        out = fn(*args)
    with dispatch_stage("retrieval.wait", "wait"):
        out = jax.device_get(out)
    return tuple(np.asarray(x) for x in out)


def exact_host(queries: np.ndarray, host_vecs: np.ndarray, k: int, *,
               exclude: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
    s, i = host_top_k(queries, host_vecs, k, exclude=exclude)
    return np.asarray(s), np.asarray(i)


def exact_device(queries: np.ndarray, items_dev, n_items: int, k: int, *,
                 jit_cache: Dict, exclude: Optional[np.ndarray] = None
                 ) -> Tuple["np.ndarray", "np.ndarray"]:
    """One top_k_scores dispatch; ONE host transfer for the results.

    The corpus-padding part of the mask (``n_items < n``) is request-
    invariant — staged on device ONCE as a [N] row and broadcast inside
    the program.  Only a per-request ``exclude`` uploads per call, at
    its own [B, ≤N] width (never a fresh host-built [B, N] block).
    """
    import jax
    import jax.numpy as jnp

    b = queries.shape[0]
    n = items_dev.shape[0]
    pad_row = None
    if n_items < n:
        pad_row = _cached(jit_cache, ("pad_row", n, n_items),
                          lambda: jnp.arange(n) >= n_items)
    has_pad = pad_row is not None
    if exclude is None:
        def build():
            def _fn(q, items, pr):
                e = jnp.broadcast_to(pr[None, :], (q.shape[0], n)) \
                    if has_pad else None
                return top_k_scores(q, items, k, exclude=e)
            return jax.jit(_fn)

        fn = _cached(jit_cache, ("device", b, k, False, has_pad), build)
        return _staged_call(fn, lambda: (
            jnp.asarray(queries, jnp.float32), items_dev, pad_row))
    else:
        ne = exclude.shape[1]

        def build():
            def _fn(q, items, e, pr):
                e = jnp.pad(e, ((0, 0), (0, n - ne)))
                if has_pad:
                    e = e | pr[None, :]
                return top_k_scores(q, items, k, exclude=e)
            return jax.jit(_fn)

        # exclude changes per request — it rides as a traced arg, so the
        # cache key only needs the static shapes.
        fn = _cached(jit_cache, ("device", b, k, True, has_pad, ne), build)
        return _staged_call(fn, lambda: (
            jnp.asarray(queries, jnp.float32), items_dev,
            jnp.asarray(exclude), pad_row))


def exact_chunked(queries: np.ndarray, items_dev, n_items: int, k: int, *,
                  jit_cache: Dict, chunk: int = 262_144,
                  exclude: Optional[np.ndarray] = None
                  ) -> Tuple["np.ndarray", "np.ndarray"]:
    """Bounded-score-memory scan; fused Pallas kernel where supported.

    ``exclude`` ([B, ≤N] bool) rides the scan chunk-by-chunk — the
    Pallas kernel takes no mask, so excluded requests use the XLA scan
    (score memory stays bounded at [B, chunk] either way).  A Pallas call
    observes ``pio_topk_fold_rounds_per_tile{rung}`` once: the selection
    rounds the kernel ran over the corpus tiles it scanned (k on a kernel
    that folds every tile in full; near k·ln(N/tile)/tiles per row when a
    tile is folded only as far as its scores beat the running k-th).
    """
    import jax
    import jax.numpy as jnp

    b = queries.shape[0]
    n = items_dev.shape[0]
    use_pallas = pallas_supported() and exclude is None
    # exclude uploads at its native [B, ≤N] width — the width-padding to
    # the staged corpus happens in-program, never as a fresh host-built
    # [B, N] block per request (same discipline as exact_device).
    ne = exclude.shape[1] if exclude is not None else None

    def build():
        if use_pallas:
            def _fn(q, items, e):
                return fused_topk_pallas(q, items, min(k, n),
                                         n_valid=n_items)
        else:
            def _fn(q, items, e):
                if e is not None and ne < n:
                    e = jnp.pad(e, ((0, 0), (0, n - ne)))
                return chunked_top_k(q, items, k,
                                     chunk=min(chunk, n),
                                     n_valid=n_items, exclude=e)
        return jax.jit(_fn)

    fn = _cached(jit_cache, ("chunked", b, k, use_pallas, ne), build)
    s, i, *rounds = _staged_call(fn, lambda: (
        jnp.asarray(queries, jnp.float32), items_dev,
        jnp.asarray(exclude) if exclude is not None else None))
    if rounds:
        get_registry().histogram(
            "pio_topk_fold_rounds_per_tile",
            "Selection rounds the fused top-k kernel ran per corpus tile "
            "it scanned, per call.", ("rung",),
            buckets=(0.01, 0.03, 0.1, 0.3, 1, 3, 10, 30, 100, 300, 1000),
        ).observe(int(rounds[0]) / fused_topk_tiles(n), rung="chunked")
    return s, i


def exact_sharded(queries: np.ndarray, items_sharded, n_items: int, k: int,
                  *, jit_cache: Dict
                  ) -> Tuple["np.ndarray", "np.ndarray"]:
    """Mesh-sharded exact: local score+top-k per shard, tiny cross-device
    merge.  ``items_sharded`` must be row-sharded with a NamedSharding
    whose dim-0 spec names a mesh axis (the facade stages it that way)."""
    import jax
    import jax.numpy as jnp

    sh = items_sharded.sharding
    mesh, axis = sh.mesh, sh.spec[0]
    b = queries.shape[0]

    def build():
        def _fn(q, items):
            return sharded_top_k(mesh, axis, q, items, k, n_valid=n_items)
        return jax.jit(_fn)

    fn = _cached(jit_cache, ("sharded", b, k), build)
    return _staged_call(fn, lambda: (
        jnp.asarray(queries, jnp.float32), items_sharded))
