"""Retrieval subsystem — THE way serving reaches an item corpus.

ISSUE 8: every template's predict path used to hand-roll its own
host-vs-device-vs-chunked-vs-sharded branching over ``ops.topk``; serve
latency and HBM grew linearly with catalog size on ONE device.  This
facade puts three rungs behind one call:

1. **Exact** (``retrieval/exact.py``) — host numpy for small work,
   single-dispatch device, bounded-memory chunked scan (fused Pallas
   score+top-K kernel on TPU), and mesh-sharded scoring with an
   O(k·shards·B) cross-device merge for corpora row-sharded at
   model-load time.
2. **IVF** (``retrieval/ivf.py``) — train-time k-means coarse index,
   sublinear candidate scan, versioned with the model generation via a
   corpus fingerprint (an index that does not match the vectors it is
   served next to is dropped loudly, never silently mis-served).
3. **PQ** (``retrieval/pq.py``, ISSUE 13) — train-time residual product
   quantization: the resident corpus shrinks to 1+M bytes/item, serving
   LUT-scores packed codes (``ivf_pq`` prunes by cell first; ``pq_flat``
   scans every code row) and re-ranks a ``PIO_PQ_RERANK`` shortlist
   against exact embeddings so recall never rides quantization error.
   Codebooks carry the same fingerprint tripwire as the IVF index.
4. **Fused kernels** (``ops/pallas_kernels.fused_topk`` /
   ``pq_scan``) — ride inside the chunked and PQ rungs where the
   backend supports them.

Templates hold ONE :class:`Retriever` per loaded model (via
:func:`cached_retriever` — weak-keyed, so it dies with the generation)
and call :meth:`Retriever.topk`.  ``tools/lint_retrieval.py`` pins the
invariant: no template or server handler may call ``ops.topk``
primitives directly.

Routing knobs (all read per request, so ops can retune a live server):

- ``PIO_RETRIEVAL_RUNG`` — auto|host|device|chunked|sharded|ivf|ivf_pq|
  pq_flat (force)
- ``PIO_SERVE_HOST_MACS`` — host fast path when B·N·D is at or below
  this (default 2e8): one device dispatch round-trip costs more than
  that many host MACs, which is exactly the lone-client B=1 case
- ``PIO_SERVE_CHUNK_ABOVE`` — chunked scan above this many items
- ``PIO_SERVE_SHARD_ABOVE`` — shard-at-load threshold (see
  :meth:`Retriever.maybe_shard`)
- ``PIO_IVF_NPROBE`` — IVF lists probed per query
- ``PIO_PQ_RERANK`` — exact-re-rank shortlist size (default 4·k)
- ``PIO_CORPUS_DTYPE`` — f32|bf16|int8 staged re-rank corpus

Observability: ``pio_retrieval_requests_total{rung}``,
``pio_retrieval_candidates_total{rung}`` (rows actually scored),
``pio_retrieval_ms{rung}``, and a ``retrieval`` span (rung, k, nprobe,
candidates, batch) in the live request's trace tree.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
import weakref
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.obs import get_registry, span
from predictionio_tpu.obs.waterfall import record_stage
from predictionio_tpu.retrieval import exact as _exact
from predictionio_tpu.retrieval.ivf import (
    IVFIndex,
    build_ivf,
    corpus_fingerprint,
    ivf_build_config,
    search_ivf_device,
    search_ivf_host,
)
from predictionio_tpu.retrieval.pq import (
    PQCodebook,
    build_pq,
    pq_build_config,
    quantize_int8,
    search_ivf_pq_device,
    search_ivf_pq_host,
    search_pq_device,
    search_pq_host,
)

logger = logging.getLogger(__name__)

__all__ = ["Retriever", "Plan", "cached_retriever", "arm_on_create",
           "iter_hits", "hit_columns",
           "build_train_index", "build_train_pq", "IVFIndex",
           "PQCodebook", "build_ivf", "build_pq",
           "corpus_fingerprint", "K_MENU"]

# Compiled-program menu (SURVEY §7): K pads up so the serving frontend's
# varying ``num`` values hit a handful of XLA programs, not one each.
K_MENU = (1, 10, 100, 1000)
_NEG_SENTINEL = -1e37  # scores at/below this are padding, never results

RUNGS = ("host", "device", "chunked", "sharded", "ivf", "ivf_pq",
         "pq_flat")
# Rungs that honor a per-request exclude mask (everything else pins the
# query to an exact rung — a blacklisted id must never be returned).
EXCLUDE_RUNGS = ("host", "device", "chunked")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)) or default)
    except ValueError:
        return default


def menu_k(num: int, n_items: int) -> int:
    return min(n_items, next((m for m in K_MENU if m >= num), num))


@dataclasses.dataclass
class Plan:
    """One routing decision — exposed for tests and the trace span."""

    rung: str
    k: int
    nprobe: int = 0
    rerank: int = 0  # PQ rungs: exact-re-scored shortlist size


class Retriever:
    """Facade over the retrieval rungs for ONE item corpus.

    ``item_vecs`` may be a host numpy array or a jax array (possibly
    row-sharded over a mesh; possibly carrying padding rows past
    ``n_items``).  The retriever lazily stages whatever copies its rungs
    need (host copy, device copy, sharded copy) — at most one of each,
    built under a process-wide lock.
    """

    def __init__(self, item_vecs, *, n_items: Optional[int] = None,
                 ivf: Optional[IVFIndex] = None,
                 pq: Optional[PQCodebook] = None, name: str = "default",
                 host_fn=None):
        self._vecs = item_vecs
        self.n_items = int(n_items if n_items is not None
                           else item_vecs.shape[0])
        self.dim = int(item_vecs.shape[1])
        self.name = name
        self._host_fn = host_fn
        self._host: Optional[np.ndarray] = None
        self._dev = None
        self._jit: Dict = {}
        # RLock: ivf_index()/pq_codebook() validate fingerprints under
        # the lock and that validation stages host_vecs(), which locks
        # again.
        self._lock = threading.RLock()
        self._ivf_raw = ivf
        self._ivf: Optional[IVFIndex] = None
        self._ivf_checked = False
        self._ivf_dev = None
        self._pq_raw = pq
        self._pq: Optional[PQCodebook] = None
        self._pq_checked = False
        self._pq_dev = None
        self._rerank_dev: Dict = {}
        self._fp: Optional[str] = None
        # Recall capture hook (ISSUE 16): armed per generation by
        # ``obs.recall.RecallMonitor`` — called after approximate-rung
        # answers with (retriever, plan, queries, ids, scanned) so
        # sampled requests can be exactly re-ranked off-thread.  None
        # (the default, and whenever PIO_RECALL=off) costs one attribute
        # read per topk.
        self.recall_hook = None
        reg = get_registry()
        self._m_requests = reg.counter(
            "pio_retrieval_requests_total",
            "Corpus retrievals by rung.", ("rung", "corpus"))
        self._m_candidates = reg.counter(
            "pio_retrieval_candidates_total",
            "Candidate item rows actually scored.", ("rung", "corpus"))
        self._m_latency = reg.histogram(
            "pio_retrieval_ms", "Retrieval latency per rung.", ("rung",))
        self._m_ivf_rejected = reg.counter(
            "pio_retrieval_ivf_rejected_total",
            "IVF indexes dropped for a fingerprint mismatch with the "
            "served corpus.", ("corpus",))
        self._m_pq_rejected = reg.counter(
            "pio_retrieval_pq_rejected_total",
            "PQ codebooks dropped for a fingerprint mismatch with the "
            "served corpus.", ("corpus",))

    # -- corpus staging -----------------------------------------------------

    @property
    def vecs(self):
        """The corpus array currently backing retrieval (numpy, device,
        or mesh-sharded — whatever :meth:`maybe_shard` last staged).
        Callers that keep their own reference (the model wrapper) sync
        from here after a re-shard so the pre-shard copy can be freed."""
        return self._vecs

    @property
    def sharded(self) -> bool:
        sh = getattr(self._vecs, "sharding", None)
        try:
            from jax.sharding import NamedSharding
        except Exception:  # pragma: no cover - jax always present in prod
            return False
        return (isinstance(sh, NamedSharding) and bool(sh.spec)
                and sh.spec[0] is not None
                and self._vecs.shape[0] % sh.mesh.shape[sh.spec[0]] == 0)

    def host_vecs(self) -> np.ndarray:
        """[n_items, D] numpy copy (trimmed of padding rows)."""
        if self._host is None:
            with self._lock:
                if self._host is None:
                    if self._host_fn is not None:
                        self._host = np.asarray(self._host_fn(),
                                                dtype=np.float32)
                    else:
                        import jax

                        self._host = np.asarray(
                            jax.device_get(self._vecs),
                            dtype=np.float32)[: self.n_items]
        return self._host

    def device_vecs(self):
        """Unsharded device copy — staged ONCE, reused across requests
        (the old per-request ``jnp.asarray(model.item_vecs)`` uploaded
        the whole corpus on every predict)."""
        if self.sharded:
            return self._vecs
        if self._dev is None:
            with _exact.SERVE_CACHE_LOCK:
                if self._dev is None:
                    import jax.numpy as jnp

                    self._dev = jnp.asarray(self._vecs, jnp.float32)
        return self._dev

    def maybe_shard(self, mesh, *, axis: Optional[str] = None) -> bool:
        """Row-shard the corpus over ``mesh`` at model-load time.

        The post_load hook's contract (SURVEY §3.2 re-parallelization):
        above ``PIO_SERVE_SHARD_ABOVE`` items the corpus is padded
        HOST-side (a device-side pad would stage the full corpus on one
        chip first — OOM at exactly the scale this targets) and
        device_put shard-by-shard; predict then routes through the
        sharded rung.  Returns True when the corpus was (re)sharded.
        """
        if mesh is None:
            return False
        from predictionio_tpu.parallel.mesh import AXIS_DATA, put_sharded

        axis = axis or AXIS_DATA
        if axis not in mesh.shape:
            return False
        if self.n_items <= _env_int("PIO_SERVE_SHARD_ABOVE", 1_000_000):
            return False
        from jax.sharding import NamedSharding, PartitionSpec as P

        host = self.host_vecs()
        d = mesh.shape[axis]
        pad = (-host.shape[0]) % d
        vecs = np.pad(host, ((0, pad), (0, 0))) if pad else host
        self._vecs = put_sharded(vecs, mesh, NamedSharding(mesh, P(axis)))
        self._dev = None
        self._jit = {}
        # The f32 re-rank staging may hold the pre-shard unsharded
        # device copy — drop it so the post-shard resolution (host-copy
        # based) applies and the old whole-corpus buffer can free.
        self._rerank_dev = {}
        return True

    # -- IVF / PQ lifecycle --------------------------------------------------

    def _corpus_fp(self) -> str:
        """SHA-1 of the served corpus — computed once, shared by the IVF
        and PQ tripwires (each validation used to re-hash the matrix)."""
        if self._fp is None:
            with self._lock:
                if self._fp is None:
                    self._fp = corpus_fingerprint(self.host_vecs())
        return self._fp

    def ivf_index(self) -> Optional[IVFIndex]:
        """The generation's IVF index, fingerprint-validated ONCE against
        the corpus actually being served.  A mismatch (index from another
        generation next to these vectors) drops the index and counts —
        exact serving continues, recall never silently collapses."""
        if self._ivf_checked:
            return self._ivf
        with self._lock:
            if self._ivf_checked:
                return self._ivf
            idx = self._ivf_raw
            if idx is not None:
                if (idx.n_items != self.n_items or idx.dim != self.dim
                        or idx.fingerprint != self._corpus_fp()):
                    logger.error(
                        "IVF index fingerprint mismatch for corpus %r "
                        "(index n=%d/d=%d vs corpus n=%d/d=%d) — dropping "
                        "the index; serving stays exact", self.name,
                        idx.n_items, idx.dim, self.n_items, self.dim)
                    self._m_ivf_rejected.inc(corpus=self.name)
                    idx = None
            self._ivf = idx
            self._ivf_checked = True
        return self._ivf

    def ivf_device_arrays(self):
        """Centroids ``[C, D]`` + padded lists ``[C, L]`` staged on
        device ONCE per generation — index constants; re-uploading them
        per request is the same trap the staged corpus copy closed."""
        if self._ivf_dev is None:
            with _exact.SERVE_CACHE_LOCK:
                if self._ivf_dev is None:
                    import jax.numpy as jnp

                    idx = self.ivf_index()
                    self._ivf_dev = (jnp.asarray(idx.centroids),
                                     jnp.asarray(idx.lists))
        return self._ivf_dev

    def pq_codebook(self) -> Optional[PQCodebook]:
        """The generation's PQ codebook, fingerprint-validated ONCE
        against the served corpus.  A mismatched codebook (codes from
        another generation next to these vectors) is dropped loudly —
        exact serving continues, results are never silently wrong."""
        if self._pq_checked:
            return self._pq
        with self._lock:
            if self._pq_checked:
                return self._pq
            pq = self._pq_raw
            if pq is not None:
                if (pq.n_items != self.n_items or pq.dim != self.dim
                        or pq.fingerprint != self._corpus_fp()):
                    logger.error(
                        "PQ codebook fingerprint mismatch for corpus %r "
                        "(codes n=%d/d=%d vs corpus n=%d/d=%d) — "
                        "dropping the codebook; serving stays exact",
                        self.name, pq.n_items, pq.dim, self.n_items,
                        self.dim)
                    self._m_pq_rejected.inc(corpus=self.name)
                    pq = None
            self._pq = pq
            self._pq_checked = True
        return self._pq

    def pq_device_arrays(self):
        """Coarse book [256, D] + codebooks [M, 256, D/M] + the packed
        code matrix TRANSPOSED to scan layout [1+M, N] uint8 — staged on
        device ONCE per generation (the code matrix IS the resident
        quantized corpus; re-uploading it per request would defeat the
        whole memory story)."""
        if self._pq_dev is None:
            with _exact.SERVE_CACHE_LOCK:
                if self._pq_dev is None:
                    import jax.numpy as jnp

                    pq = self.pq_codebook()
                    self._pq_dev = (
                        jnp.asarray(pq.coarse),
                        jnp.asarray(pq.codebooks),
                        jnp.asarray(np.ascontiguousarray(pq.codes.T)))
        return self._pq_dev

    def rerank_arrays(self):
        """The staged exact re-rank corpus under ``PIO_CORPUS_DTYPE``:
        ``(vectors, None)`` for f32/bf16 or ``(int8, row_scales)`` —
        per-dtype copies staged once so a live retune of the env never
        re-uploads on the hot path.  f32 reuses the exact rungs' staged
        device copy outright."""
        raw = os.environ.get("PIO_CORPUS_DTYPE", "f32").strip().lower() \
            or "f32"
        dtype = {"f32": "f32", "float32": "f32", "bf16": "bf16",
                 "bfloat16": "bf16", "int8": "int8"}.get(raw)
        if dtype is None:
            logger.warning("PIO_CORPUS_DTYPE=%r is not one of "
                           "f32|bf16|int8; staging f32", raw)
            dtype = "f32"
        staged = self._rerank_dev.get(dtype)
        if staged is not None:
            return staged
        if dtype == "f32" and self.n_items * self.dim * 4 > 1 << 28:
            # The default keeps the re-rank corpus exact, but above
            # ~256 MB that re-stages the very fp32 residency PQ exists
            # to remove — say so ONCE, with the fix, instead of letting
            # the first request OOM a chip that only fits the codes.
            logger.warning(
                "PQ re-rank corpus %r stages %.0f MB of fp32 on device "
                "(PIO_CORPUS_DTYPE=f32 default); set "
                "PIO_CORPUS_DTYPE=bf16 or int8 to shrink the resident "
                "re-rank copy 2-4x", self.name,
                self.n_items * self.dim * 4 / 2 ** 20)
        if dtype == "f32" and not self.sharded:
            # device_vecs() takes SERVE_CACHE_LOCK itself — stage it
            # BEFORE acquiring the lock here (non-reentrant).
            staged = (self.device_vecs(), None)
            self._rerank_dev[dtype] = staged
            return staged
        with _exact.SERVE_CACHE_LOCK:
            staged = self._rerank_dev.get(dtype)
            if staged is None:
                import jax.numpy as jnp

                if dtype == "f32":
                    # A mesh-sharded corpus can't feed the PQ gather
                    # directly; re-rank gets its own unsharded copy
                    # (pick bf16/int8 at this scale).
                    staged = (jnp.asarray(self.host_vecs()), None)
                elif dtype == "bf16":
                    staged = (jnp.asarray(self.host_vecs(),
                                          jnp.bfloat16), None)
                else:
                    q8, sc = quantize_int8(self.host_vecs())
                    staged = (jnp.asarray(q8), jnp.asarray(sc))
                self._rerank_dev[dtype] = staged
        return staged

    # -- routing ------------------------------------------------------------

    def plan(self, b: int, num: int, *, has_exclude: bool = False) -> Plan:
        k = menu_k(num, self.n_items)
        forced = os.environ.get("PIO_RETRIEVAL_RUNG", "auto").strip().lower()
        if forced not in RUNGS and forced not in ("", "auto"):
            # An unrecognized forcing must degrade as loudly as an
            # impossible one — a typo'd bench must not silently measure
            # auto routing.
            logger.warning("PIO_RETRIEVAL_RUNG=%r is not one of %s; "
                           "auto routing", forced, ("auto",) + RUNGS)
        if forced in RUNGS:
            if has_exclude and forced not in EXCLUDE_RUNGS:
                # The sharded/IVF executors take no per-request mask —
                # honoring the exclusion beats honoring the forcing (a
                # blacklisted item must never be returned).
                logger.warning(
                    "PIO_RETRIEVAL_RUNG=%s cannot honor a per-request "
                    "exclude mask for corpus %r; serving exact", forced,
                    self.name)
                forced = "auto"
            if forced == "sharded" and not self.sharded:
                logger.warning("PIO_RETRIEVAL_RUNG=sharded but corpus %r "
                               "is not mesh-sharded; serving exact-device",
                               self.name)
                forced = "device"
            if forced == "ivf" and self.ivf_index() is None:
                logger.warning("PIO_RETRIEVAL_RUNG=ivf but corpus %r has "
                               "no valid index; serving exact", self.name)
                forced = "auto"
            if forced in ("ivf_pq", "pq_flat") \
                    and self.pq_codebook() is None:
                logger.warning("PIO_RETRIEVAL_RUNG=%s but corpus %r has "
                               "no valid PQ codebook; serving exact",
                               forced, self.name)
                forced = "auto"
            if forced == "ivf_pq" and self.ivf_index() is None:
                logger.warning("PIO_RETRIEVAL_RUNG=ivf_pq but corpus %r "
                               "has no valid IVF index; serving pq_flat",
                               self.name)
                forced = "pq_flat"
            if forced in RUNGS:
                return self._finish_plan(forced, b, k)
        work = b * self.n_items * self.dim
        host_macs = _env_int("PIO_SERVE_HOST_MACS", 2 * 10 ** 8)
        if has_exclude:
            # Per-request [B, N] masks ride the exact rungs only (an
            # excluded id must never cost recall the way an unprobed
            # IVF cell or a quantized shortlist would); past the chunk
            # threshold the mask rides the scan so score memory stays
            # bounded at [B, chunk].
            if work <= host_macs:
                return self._finish_plan("host", b, k)
            if self.n_items > _env_int("PIO_SERVE_CHUNK_ABOVE", 2_000_000):
                return self._finish_plan("chunked", b, k)
            return self._finish_plan("device", b, k)
        if self.pq_codebook() is not None:
            # Quantized serving when the generation carries codes:
            # IVF-pruned when it also carries a valid index, full LUT
            # scan otherwise (the norm-variant / opted-out-of-IVF
            # shape) — the exact re-rank holds recall either way.
            if self.ivf_index() is not None:
                return self._finish_plan("ivf_pq", b, k)
            return self._finish_plan("pq_flat", b, k)
        if self.ivf_index() is not None:
            return self._finish_plan("ivf", b, k)
        if work <= host_macs:
            return self._finish_plan("host", b, k)
        if self.sharded:
            return self._finish_plan("sharded", b, k)
        if self.n_items > _env_int("PIO_SERVE_CHUNK_ABOVE", 2_000_000):
            return self._finish_plan("chunked", b, k)
        return self._finish_plan("device", b, k)

    def _rerank_count(self, k: int) -> int:
        """PQ shortlist size: ``PIO_PQ_RERANK`` (absolute), default 4·k —
        clamped to [k, n_items].  The top-k the caller sees is always
        computed from exact scores over this many candidates."""
        raw = os.environ.get("PIO_PQ_RERANK", "").strip()
        r = 0
        if raw:
            try:
                r = int(raw)
            except ValueError:
                logger.warning("PIO_PQ_RERANK=%r is not an integer; "
                               "using the 4·k default", raw)
        if r <= 0:
            r = 4 * k
        return min(self.n_items, max(r, k))

    def _finish_plan(self, rung: str, b: int, k: int) -> Plan:
        if rung == "pq_flat":
            return Plan(rung=rung, k=k, rerank=self._rerank_count(k))
        if rung not in ("ivf", "ivf_pq"):
            return Plan(rung=rung, k=k)
        idx = self.ivf_index()
        # Static-shape guard: the probed lists must reach k (or, with a
        # PQ shortlist, rerank) REAL candidates even for the query
        # landing on the shortest lists.
        reach = self._rerank_count(k) if rung == "ivf_pq" else k
        nprobe = min(idx.nlist,
                     max(idx.default_nprobe(), idx.min_nprobe_for(reach)))
        if rung == "ivf_pq":
            return Plan(rung=rung, k=k, nprobe=nprobe, rerank=reach)
        return Plan(rung="ivf", k=k, nprobe=nprobe)

    # -- the one entry point ------------------------------------------------

    def topk(self, queries: np.ndarray, num: int, *,
             exclude: Optional[np.ndarray] = None
             ) -> Tuple[np.ndarray, np.ndarray, Dict[str, Any]]:
        """Top-k over the corpus for query VECTORS ``[B, D]``.

        Returns ``([B, k] scores, [B, k] int32 ids, info)`` with
        ``k = menu_k(num) ≤ n_items`` — callers slice ``[:num]`` per row
        (:func:`iter_hits` skips padding sentinels).  ``exclude`` is an
        optional ``[B, n_items]`` bool mask (True = never return).
        """
        q = np.ascontiguousarray(queries, dtype=np.float32)
        b = q.shape[0]
        p = self.plan(b, num, has_exclude=exclude is not None)
        # annotate: pio:retrieval on a profiler capture's host timeline,
        # around the exact device rungs' pio:retrieval.h2d/.launch/.wait.
        with span("retrieval", annotate=True, corpus=self.name,
                  rung=p.rung, batch=b, k=p.k) as sp:
            scores, ids, scanned = self._execute(q, p, exclude)
            if p.nprobe:
                sp.set(nprobe=p.nprobe)
            if p.rerank:
                sp.set(rerank=p.rerank)
            sp.set(candidates=scanned)
        # The span's own reading, so pio_retrieval_ms minus its children
        # (pio_dispatch_stage_ms h2d/launch/wait) is the span's self time.
        ms = sp.duration_ms
        self._m_requests.inc(rung=p.rung, corpus=self.name)
        self._m_candidates.inc(scanned, rung=p.rung, corpus=self.name)
        self._m_latency.observe(ms, rung=p.rung)
        # Waterfall hand-off (ISSUE 9): the serving batcher routes this
        # into the per-dispatch sink and fans it out to every member of
        # the cohort as the rung-tagged "retrieval" stage (⊂ dispatch).
        record_stage("retrieval", ms, rung=p.rung,
                     retrievalCandidates=scanned)
        hook = self.recall_hook
        if hook is not None and p.rung in ("ivf", "ivf_pq", "pq_flat"):
            # Sampled recall capture (ISSUE 16) — the hook does its own
            # shared-draw sampling and bounded enqueue; it must never be
            # able to fail a serving answer.
            try:
                hook(self, p, q, ids, scanned)
            except Exception:
                logger.debug("recall capture failed", exc_info=True)
        info = {"rung": p.rung, "k": p.k, "nprobe": p.nprobe,
                "rerank": p.rerank, "candidates": scanned, "ms": ms}
        return scores, ids, info

    def _execute(self, q: np.ndarray, p: Plan,
                 exclude: Optional[np.ndarray]):
        b = q.shape[0]
        if p.rung == "host":
            s, i = _exact.exact_host(q, self.host_vecs(), p.k,
                                     exclude=exclude)
            return s, i, b * self.n_items
        if p.rung in ("pq_flat", "ivf_pq"):
            return self._execute_pq(q, p)
        if p.rung == "ivf":
            idx = self.ivf_index()
            # The sub-linear scan keeps the same host-vs-device economics
            # as the exact rungs, judged on the rows actually scored.
            est = b * p.nprobe * idx.pad_len * self.dim
            if est <= _env_int("PIO_SERVE_HOST_MACS", 2 * 10 ** 8):
                return search_ivf_host(idx, self.host_vecs(), q, p.k,
                                       p.nprobe)
            qp = _pow2_pad(q)
            s, i, scanned = search_ivf_device(
                idx, self.device_vecs(), qp, p.k, p.nprobe,
                jit_cache=self._jit, consts=self.ivf_device_arrays())
            # scanned counts the padded batch's probes; rescale to real.
            return s[:b], i[:b], int(scanned * b / max(len(qp), 1))
        qp = _pow2_pad(q)
        if exclude is not None and len(qp) > b:
            # The pow2 pad added all-zero query rows; give them
            # all-False mask rows so shapes stay aligned.
            exclude = np.concatenate(
                [exclude, np.zeros((len(qp) - b, exclude.shape[1]),
                                   dtype=bool)])
        if p.rung == "sharded":
            s, i = _exact.exact_sharded(qp, self._vecs, self.n_items, p.k,
                                        jit_cache=self._jit)
        elif p.rung == "chunked":
            s, i = _exact.exact_chunked(qp, self.device_vecs(),
                                        self.n_items, p.k,
                                        jit_cache=self._jit,
                                        exclude=exclude)
        else:
            s, i = _exact.exact_device(qp, self.device_vecs(),
                                       self.n_items, p.k,
                                       jit_cache=self._jit,
                                       exclude=exclude)
        return s[:b], i[:b], b * self.n_items

    def _execute_pq(self, q: np.ndarray, p: Plan):
        """Quantized rungs: LUT scan (IVF-pruned or full) → exact
        re-rank.  Same host-vs-device economics as the other rungs,
        judged on code rows touched (≈1 lookup ≈ 1 MAC) plus the
        re-rank matmul."""
        b = q.shape[0]
        pq = self.pq_codebook()
        host_macs = _env_int("PIO_SERVE_HOST_MACS", 2 * 10 ** 8)
        rerank_macs = b * p.rerank * self.dim
        if p.rung == "pq_flat":
            est = b * self.n_items * pq.n_tables + rerank_macs
            if est <= host_macs:
                return search_pq_host(pq, self.host_vecs(), q, p.k,
                                      p.rerank)
            qp = _pow2_pad(q)
            s, i, scanned = search_pq_device(
                pq, qp, p.k, p.rerank, jit_cache=self._jit,
                consts=self.pq_device_arrays(),
                rerank_consts=self.rerank_arrays())
            return s[:b], i[:b], int(scanned * b / max(len(qp), 1))
        idx = self.ivf_index()
        est = b * p.nprobe * idx.pad_len * pq.n_tables + rerank_macs
        if est <= host_macs:
            return search_ivf_pq_host(idx, pq, self.host_vecs(), q, p.k,
                                      p.nprobe, p.rerank)
        qp = _pow2_pad(q)
        s, i, scanned = search_ivf_pq_device(
            idx, pq, qp, p.k, p.nprobe, p.rerank, jit_cache=self._jit,
            ivf_consts=self.ivf_device_arrays(),
            pq_consts=self.pq_device_arrays(),
            rerank_consts=self.rerank_arrays())
        # scanned counts the padded batch's probes; rescale to real.
        return s[:b], i[:b], int(scanned * b / max(len(qp), 1))


def _pow2_pad(q: np.ndarray) -> np.ndarray:
    """Pad the batch to the next power of two (compiled-program menu)."""
    b = q.shape[0]
    pad = (1 << max(b - 1, 0).bit_length()) - b
    if pad:
        return np.concatenate([q, np.zeros((pad, q.shape[1]), q.dtype)])
    return q


def iter_hits(scores_row, ids_row, num: int) -> Iterator[Tuple[int, float]]:
    """(item_id, score) pairs of one result row, sentinel-padding
    skipped, at most ``num``.  The rule of what a row answers, for the
    single-row callers that filter as they go; a cohort's rows leave
    through :func:`hit_columns`, which is this rule a cohort at a
    time."""
    taken = 0
    for s, i in zip(scores_row, ids_row):
        if taken >= num:
            return
        if i < 0 or s <= _NEG_SENTINEL:
            continue
        yield int(i), float(s)
        taken += 1


def hit_columns(scores: np.ndarray, ids: np.ndarray, nums: Sequence[int]
                ) -> List[Tuple[List[int], List[float]]]:
    """Every row of a cohort's ``scores[B, K]``, ``ids[B, K]`` as
    ``(item ids, scores)`` Python lists: row ``r`` holds what
    ``iter_hits(scores[r], ids[r], nums[r])`` yields, as two columns.

    One ``tolist`` an array for the whole cohort (it widens a float32
    as ``float`` does) and one vector mask for the padding: a row with
    none in its first ``nums[r]`` columns is a slice, and only a row
    that has some is walked by :func:`iter_hits`."""
    if not len(nums):
        return []
    width = min(max(max(nums), 0), scores.shape[1])
    if not width:
        return [([], []) for _ in nums]
    s, i = scores[:, :width], ids[:, :width]
    padding = (i < 0) | (s <= _NEG_SENTINEL)
    out = [(ir, sr) if n >= width else (ir[:max(n, 0)], sr[:max(n, 0)])
           for ir, sr, n in zip(i.tolist(), s.tolist(), nums)]
    if padding.any():
        first = np.where(padding.any(axis=1), padding.argmax(axis=1), width)
        for r in np.flatnonzero(first < np.minimum(nums, width)):
            hits = list(iter_hits(scores[r], ids[r], nums[r]))
            out[r] = ([h[0] for h in hits], [h[1] for h in hits])
    return out


# -- per-model retriever cache ----------------------------------------------

_RETRIEVERS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_RETRIEVERS_LOCK = threading.Lock()
_PENDING_ARM: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def cached_retriever(owner, build) -> Retriever:
    """ONE retriever per loaded model object, built lazily, dying with
    the generation (weak-keyed — a swapped-out model wrapper releases
    its staged corpus copies with itself).  Keeping the cache OUT of the
    wrapper dataclasses means nothing jit- or device-shaped ever rides
    the model pickle."""
    r = _RETRIEVERS.get(owner)
    if r is None:
        pending = None
        with _RETRIEVERS_LOCK:
            r = _RETRIEVERS.get(owner)
            if r is None:
                r = build()
                _RETRIEVERS[owner] = r
                pending = _PENDING_ARM.pop(owner, None)
        if pending is not None:
            try:
                pending(r)
            except Exception:
                logger.debug("retriever arm callback failed",
                             exc_info=True)
    return r


def arm_on_create(owner, fn) -> None:
    """Run ``fn(retriever)`` for ``owner``'s retriever — immediately if
    one is already cached, else right after ``cached_retriever`` builds
    it.  Lets observers (obs/recall.py) attach per-generation hooks
    WITHOUT forcing retriever creation at model load: creation — and
    with it index/codebook fingerprint validation — stays lazy on the
    first query.  At most one pending callback per owner (latest wins);
    a callback for a swapped-out generation is expected to no-op when
    it fires."""
    with _RETRIEVERS_LOCK:
        r = _RETRIEVERS.get(owner)
        if r is None:
            _PENDING_ARM[owner] = fn
            return
    fn(r)


def build_train_index(item_vecs: np.ndarray, *, name: str,
                      seed: Optional[int] = None,
                      require_explicit: bool = False
                      ) -> Optional[IVFIndex]:
    """Train-time IVF build under the env policy (``PIO_IVF`` /
    ``PIO_IVF_NLIST`` / ``PIO_IVF_MIN_ITEMS``) — called by template
    ``train()`` so the index is serialized inside the SAME model
    artifact the generation swap moves.

    ``require_explicit`` is for norm-variant corpora (raw ALS factors,
    popularity-scaled norms): k-means cells partition by direction, so a
    high-norm item in an unprobed cell is an unrecoverable miss — the
    index builds only under an explicit ``PIO_IVF=on``, never ``auto``.
    """
    if require_explicit:
        mode = os.environ.get("PIO_IVF", "auto").strip().lower() or "auto"
        if mode not in ("on", "1", "true", "yes"):
            logger.debug("IVF build skipped for %r: norm-variant corpus "
                         "needs explicit PIO_IVF=on (got %r)", name, mode)
            return None
    build, nlist, min_items = ivf_build_config(len(item_vecs))
    if not build:
        logger.debug("IVF build skipped for %r (n=%d < min=%d or PIO_IVF "
                     "off)", name, len(item_vecs), min_items)
        return None
    t0 = time.perf_counter()
    # seed=None (templates with no configured seed) pins to 0 — two
    # trains over identical data must build identical indexes, or recall
    # characteristics and bench comparisons drift run-to-run.
    idx = build_ivf(np.asarray(item_vecs, dtype=np.float32), nlist=nlist,
                    seed=0 if seed is None else seed, force=True)
    logger.info("IVF index for %r built in %.1fs (nlist=%d)", name,
                time.perf_counter() - t0, idx.nlist if idx else -1)
    return idx


def build_train_pq(item_vecs: np.ndarray, *, name: str,
                   ivf: Optional[IVFIndex] = None,
                   seed: Optional[int] = None) -> Optional[PQCodebook]:
    """Train-time residual-PQ build under the env policy (``PIO_PQ`` /
    ``PIO_PQ_M`` / ``PIO_PQ_MIN_ITEMS``) — called by template
    ``train()`` AFTER the IVF build so the residual coarse book can ride
    the same cell structure, and serialized inside the SAME model
    artifact the generation swap moves.

    Unlike IVF, PQ needs no norm-variance opt-in: the exact re-rank
    re-scores every returned candidate against the true embeddings, so
    quantization error orders a shortlist but never the final top-k.
    """
    vecs = np.asarray(item_vecs, dtype=np.float32)
    build, m, min_items = pq_build_config(len(vecs), vecs.shape[1])
    if not build:
        logger.debug("PQ build skipped for %r (n=%d < min=%d or PIO_PQ "
                     "off)", name, len(vecs), min_items)
        return None
    t0 = time.perf_counter()
    # seed=None pins to 0 like build_train_index — identical data must
    # build identical codes or recall/bench comparisons drift.
    pq = build_pq(vecs, m=m, ivf=ivf, seed=0 if seed is None else seed)
    logger.info("PQ codebook for %r built in %.1fs (M=%d, %d B/item)",
                name, time.perf_counter() - t0, pq.m,
                pq.bytes_per_item())
    return pq
