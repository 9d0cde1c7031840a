"""Alternating least squares, TPU-shaped.

Reference behavior: Spark MLlib ``ALS.train`` / ``ALS.trainImplicit`` as
invoked by the recommendation template (SURVEY.md §2.2, §3.1 hot loop).
MLlib's implementation is shuffle-shaped: user×item factor blocks exchanged
between executors, per-block normal equations solved via JNI BLAS.

The TPU design replaces all of that with one batched XLA program per side
per iteration (SURVEY.md §7 step 5):

- ragged ratings → degree-bucketed padded blocks (host-side, once);
  rows that rated a large enough share of the other side go to a dense
  block instead, and their normal equations are a masked product over
  the whole factor table (``ops.pallas_kernels.fused_gram_dense``)
- per-entity normal equations built by batched einsum over gathered
  factors (MXU) — ``A_u = Σ_i w_ui · y_i y_iᵀ``
- batched Cholesky solves (``ops.linalg.batched_ridge_solve``)
- factor "exchange" = nothing within a chip, an all-gather across the mesh
  (factors replicated; solve rows sharded on the ``data`` axis)

Regularization follows MLlib's ALS-WR scaling: λ·n_u per user (n_u = that
user's rating count), λ·n_i per item.  Implicit feedback follows
Hu-Koren-Volinsky: confidence c = 1 + α·r, preference p = 1(r>0), with the
``YᵀY`` term shared across users.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import logging
import math
import threading
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.obs import get_registry, phase
from predictionio_tpu.ops.linalg import gram, masked_gram
from predictionio_tpu.ops.pallas_kernels import (
    DENSE_BLOCK_DTYPE,
    dense_block_width,
    fits_vmem,
    fused_gram_dense,
    fused_gram_vector_pallas,
    gather_table_pack,
    gram_takes_packed,
    lanes_solve_fits_vmem,
    pallas_supported,
    ridge_solve_lu_pallas,
)
from predictionio_tpu.ops.ragged import LEN_ALIGN, Padded, bucket_by_length
from predictionio_tpu.ops.topk import chunked_top_k, top_k_scores
from predictionio_tpu.parallel.mesh import AXIS_DATA, put_sharded

__all__ = ["ALSConfig", "ALSModel", "ALSInputs", "prepare_als_inputs",
           "train_als", "train_als_prepared", "recommend", "predict_scores",
           "fold_in"]

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class ALSConfig:
    rank: int = 32
    iterations: int = 10
    reg: float = 0.01          # MLlib regParam (λ), ALS-WR scaled by degree
    alpha: float = 1.0         # implicit confidence scale
    implicit: bool = False
    max_degree: Optional[int] = None   # truncate overlong entities (None = exact)
    # "auto" fits bounds to the degree histogram (ops.ragged.fit_bounds,
    # DP-minimal padded slots, sublane-aligned); a tuple pins them.
    bucket_bounds: Union[Sequence[int], str] = "auto"
    # Zipf-head entities longer than this are split into partial rows and
    # their normal-equation pieces segment-summed — exact, and it removes
    # the dominant padding waste (measured 3.7x padded slots on the ML-1M
    # item side without it).  None disables splitting.
    split_above: Optional[int] = 4096
    seed: int = 42
    dtype: str = "float32"     # factor storage dtype; solves always f32
    # Gather + matmul input precision for the gram/rhs builds (factor
    # MASTER copies and all accumulation stay f32; only the gathered
    # operands are cast).  XLA's gather on the v5e is bound by rows, not
    # bytes: in bf16, 0.41-0.46 G rows/s (2.2-2.5 ns a row) from a table
    # of up to 240,000 x 64 and 0.08-0.09 G rows/s (11.3-12.0 ns) from
    # one of 480,189 x 64, sorted indices or not (one v5e, PR 29:
    # als-netflix-r64's sweep and gathers alone; float32 gathers not
    # measured there).  The gathered rows are what a sweep spends its
    # time on wherever rows are too sparse for the dense block, so
    # "auto" = bfloat16 on TPU, float32 elsewhere (CPU tests keep
    # numpy-oracle exactness).
    gram_dtype: str = "auto"
    # Normal-equation solver: "auto" = the Pallas shrinking-elimination
    # kernel ("lu") on one TPU chip at a rank whose working set fits
    # VMEM — the XLA batched Cholesky was the single largest cost of an
    # iteration there — Cholesky elsewhere.  "cholesky"/"lu" force a path.
    solver: str = "auto"
    use_pallas: Optional[bool] = None  # None = auto (on for single-chip TPU)
    # HBM guard: cap the gathered [rows, L, K] block at this many floats;
    # jumbo buckets are solved in row chunks.  Round 4 doubled the default
    # (1<<26 → 1<<27): the Pallas gram path gathers in bf16 with NO
    # relayout copy alongside, so the same byte budget admits twice the
    # rows — and halving the chunk count cuts both the cold compile time
    # (program size ∝ chunk count) and per-chunk dispatch overhead.  1 GB
    # f32-equivalent blocks OOMed the 16 GB chip at ML-25M scale; 512
    # MB-equivalent (256 MB bf16 gathered) leaves headroom.
    max_block_floats: int = 1 << 27
    # "auto" = bucket on-device (ops/device_prep.py) when running on TPU
    # with no mesh and no max_degree truncation; True/False force.  The
    # host-numpy bucketing + padded-block upload was 84% of end-to-end
    # train wall time at ML-25M (round-2 verdict item 3); the device path
    # ships compact COO once and runs the layout transform as one XLA
    # program.
    device_prep: Union[bool, str] = "auto"
    # Factor placement on a mesh (SURVEY §2.4 row 2 — the blueprint's
    # blocked ALS).  "replicated" keeps both factor matrices whole on
    # every chip (cheapest at ML-25M rank 64: ~57 MB); "sharded"
    # row-shards the PERSISTENT factor state over the ``data`` axis so it
    # scales 1/n_chips — XLA inserts the per-sweep gathers (transient,
    # full-size) and re-shards the solve outputs, riding ICI; "auto"
    # shards once both matrices exceed ``factor_shard_threshold`` bytes.
    factor_sharding: str = "auto"
    factor_shard_threshold: int = 256 << 20
    # Windowed per-chunk gather for blocked mode (SURVEY §2.4 row 2 /
    # §7 "hard parts").  Sharding the PERSISTENT factors (above) still
    # left each sweep's TRANSIENT gather full-size: every chunk read the
    # whole other-side factor matrix (~51 GB at 100M users rank 128 —
    # past HBM).  Windowed mode gathers, per HBM chunk, ONLY the factor
    # rows that chunk's indices touch: prep computes the sorted unique
    # window + remaps the chunk indices to window-local, and the sweep
    # fetches the window from the sharded factors with a masked local
    # take + psum over the data axis (each row lives in exactly one
    # shard, so the sum is exact in f32) — transient ∝ chunk working
    # set (≤ max_block_floats/rank rows), not matrix size.  "auto" =
    # on whenever the factors are sharded; per-chunk it only engages
    # when the window is under half the matrix (else the plain gather
    # is smaller).  True/False force.
    gather_window: Union[bool, str] = "auto"


@dataclasses.dataclass
class ALSModel:
    """Trained factors. ``user_factors [U,K]``, ``item_factors [I,K]``."""

    user_factors: jax.Array
    item_factors: jax.Array
    rank: int
    implicit: bool

    def tree_flatten(self):  # manual pytree-ish helpers for checkpointing
        return {"user_factors": self.user_factors, "item_factors": self.item_factors}


def _init_factors(n_users: int, n_items: int, k: int, seed: int):
    """Deterministic scaled-normal factor init, identical on every backend.

    MLlib uses Xavier-ish normal / sqrt(k).  Both prep paths (host and
    device-side) MUST share this: jax.random's threefry bits are
    backend-deterministic, so the same ``ALSConfig.seed`` produces the same
    model whether prep ran on TPU, CPU, or a mesh (round-3 advisor finding:
    a numpy init here diverged from the device path's jax.random init,
    breaking mesh-vs-meshless equivalence on real TPU backends).
    """
    key = jax.random.PRNGKey(seed)
    ku, ki = jax.random.split(key)
    scale = np.sqrt(k).astype(np.float32)
    uf = jax.random.normal(ku, (n_users, k), jnp.float32) / scale
    itf = jax.random.normal(ki, (n_items, k), jnp.float32) / scale
    return uf, itf


def _shard_factors(config: ALSConfig, n_users: int, n_items: int) -> bool:
    """Whether a mesh run row-shards the persistent factor matrices."""
    if config.factor_sharding == "sharded":
        return True
    if config.factor_sharding == "replicated":
        return False
    if config.factor_sharding != "auto":
        raise ValueError(
            f"factor_sharding must be 'auto', 'replicated' or 'sharded' "
            f"(got {config.factor_sharding!r})")
    return (n_users + n_items) * config.rank * 4 > config.factor_shard_threshold


def _factor_constraint(arr: jax.Array) -> Optional[NamedSharding]:
    """The sharding to re-impose on factor state each sweep, if blocked."""
    sh = getattr(arr, "sharding", None)
    if isinstance(sh, NamedSharding) and sh.spec and sh.spec[0] == AXIS_DATA:
        return sh
    return None


def _resolve_gram_dtype(gram_dtype: str) -> str:
    """"auto" → bfloat16 on TPU (gather row-rate win), float32 elsewhere."""
    if gram_dtype == "auto":
        return "bfloat16" if pallas_supported() else "float32"
    return gram_dtype


def _gather_wide(table: jax.Array, indices: jax.Array,
                 pack: int) -> Tuple[jax.Array, jax.Array]:
    """The rows ``table[indices]`` lie in, read through the view that lays
    ``pack`` rows side by side in one 128-lane row: row ``index // pack``
    of the view ``[R, L, pack·K]``, and the part ``index % pack`` of it
    that is the row asked for ``[R, L]``.  The view is padded to whole
    rows; an index is wrapped and clamped first, as ``table[...]`` does
    it, so the pad is never the part that is named."""
    n, k = table.shape
    indices = jnp.clip(jnp.where(indices < 0, indices + n, indices), 0, n - 1)
    if n % pack:
        table = jnp.pad(table, ((0, -n % pack), (0, 0)))
    return table.reshape(-1, pack * k)[indices // pack], indices % pack


def _gather_packed(table: jax.Array, indices: jax.Array,
                   pack: int) -> jax.Array:
    """``table[indices]`` through the packed view (:func:`_gather_wide`),
    each slot's part kept HERE: XLA makes that a pass of its own over the
    gathered rows, so this is the form of the XLA twin and of every view
    the sparse gram kernel does not take (``gram_takes_packed``); the
    kernel path hands the wide rows over and the kernel keeps the part."""
    wide, part = _gather_wide(table, indices, pack)
    k = table.shape[1]
    part = part[..., None]
    rows = wide[..., :k]
    for j in range(1, pack):
        rows = jnp.where(part == j, wide[..., j * k:(j + 1) * k], rows)
    return rows


def _gather_rows(factors: jax.Array, indices: jax.Array,
                 gram_dtype) -> jax.Array:
    """``factors.astype(gram_dtype)[indices]``, bit for bit, in the form
    that keeps the table the gather reads in the chip's fast memory
    (``pallas_kernels.gather_table_pack``: XLA:TPU gathers from VMEM at a
    fifth of what a row costs from HBM).  A table that lies there as it
    is, or that no view brings there, is gathered as it is."""
    table = factors.astype(gram_dtype)
    pack = gather_table_pack(*table.shape, table.dtype.itemsize) or 1
    if pack == 1:
        return table[indices]
    return _gather_packed(table, indices, pack)


def _gram_pieces(
    indices: jax.Array,    # [R, L] int32 — other-side ids
    values: jax.Array,     # [R, L] f32
    mask: jax.Array,       # [R, L] bool
    factors: jax.Array,    # [N, K] other-side factors
    alpha: jax.Array,      # scalar α
    implicit: bool,
    use_pallas: bool,
    gram_dtype,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-row normal-equation pieces: A [R,K,K], b [R,K], degree [R]."""
    m = mask.astype(jnp.float32)
    if implicit:
        # Hu-Koren-Volinsky per MLlib: c = 1 + α·|r|, p = 1(r>0).
        # A = YᵀY + Σ (c-1)·y yᵀ,  b = Σ c·p·y — (c-1) ≥ 0 keeps A PSD.
        w = alpha * jnp.abs(values) * m       # c - 1
        cvec = (1.0 + w) * (values > 0).astype(jnp.float32) * m
    else:
        w = m
        cvec = values * m
    if use_pallas:
        # Gather in gram_dtype (bf16 on TPU: the v5e gather engine is
        # row-rate limited and bf16 halves the bytes) and feed the fused
        # kernel DIRECTLY — Pallas consumes the gather's natural K-minor
        # layout, so no relayout copy is emitted (the einsum path's dots
        # want L-minor and XLA copies the whole [R,L,K] block to get it:
        # 47.7 ms/iter at the ML-25M shape, round-3 phase profile).
        # A table read through the packed view goes to the kernel as the
        # view's 128-lane rows with each slot's part beside them: the
        # kernel's fetch of a row is 128 lanes wide either way, and the
        # pass XLA makes of keeping the part outside it is not made.
        pack = gather_table_pack(
            *factors.shape, jnp.dtype(gram_dtype).itemsize) or 1
        if gram_takes_packed(factors.shape[1], pack):
            f, part = _gather_wide(factors.astype(gram_dtype), indices,
                                   pack)              # [R, L, pack·K]
        else:
            f, part, pack = _gather_rows(factors, indices, gram_dtype), \
                None, 1                               # [R, L, K]
        a, b = fused_gram_vector_pallas(f, w, cvec, part, pack=pack,
                                        interpret=not pallas_supported())
    else:
        # Gather in gram_dtype: the factor cast is [N, K] (cheap, one pass)
        # and the row-rate-limited gather then moves half the bytes in
        # bf16.  Single-temp formulation: fold sqrt(w) into the gathered
        # factors so only ONE [R, L, K] intermediate exists (the naive f
        # and f*w pair doubled peak HBM and OOMed the ML-25M shape).
        # Entries with cvec != 0 but w == 0 (implicit feedback with
        # alpha == 0) get an epsilon fold weight so the rhs survives the
        # division exactly; the epsilon perturbs A by ~1e-12 per entry —
        # far below the ridge.
        f = _gather_rows(factors, indices, gram_dtype)   # [R, L, K]
        sw = jnp.sqrt(w + jnp.where(cvec != 0.0, 1e-12, 0.0))
        g = f * sw[..., None].astype(gram_dtype)
        a = jax.lax.dot_general(g, g, (((1,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = (cvec / jnp.maximum(sw, 1e-30)).astype(gram_dtype)
        b = jnp.einsum("blk,bl->bk", g, s,
                       preferred_element_type=jnp.float32)
    return a, b, m.sum(axis=1)


def _solve_bucket(
    indices, values, mask, factors, yty, reg, alpha,
    implicit: bool, use_pallas: bool, gram_dtype, solver: str,
) -> jax.Array:
    """One padded block of normal equations + batched solves → [R, K]."""
    a, b, degree = _gram_pieces(indices, values, mask, factors, alpha,
                                implicit, use_pallas, gram_dtype)
    if implicit:
        a = yty[None, :, :] + a
    return _ridge(a, b, reg * jnp.maximum(degree, 1.0), solver)  # ALS-WR: λ·n_u


def _ridge(a: jax.Array, b: jax.Array, reg_vec: jax.Array,
           solver: str = "cholesky") -> jax.Array:
    """Batched SPD solve ``(A + diag(reg)) x = b``.

    ``lu`` = the Pallas shrinking-elimination kernel, one system per
    vector lane; anything else (``_resolve_loop_statics`` admits only
    ``cholesky``) = XLA's batched Cholesky, whose K-step while-loop of
    small dynamic slices is the mesh path, the path above the kernel's
    VMEM limit and the reference.
    """
    if solver == "lu":
        return ridge_solve_lu_pallas(a, b, reg_vec,
                                     interpret=not pallas_supported())
    k = a.shape[-1]
    eye = jnp.eye(k, dtype=a.dtype)
    a_reg = a + reg_vec[:, None, None] * eye
    chol = jnp.linalg.cholesky(a_reg)
    y = jax.scipy.linalg.solve_triangular(chol, b[..., None], lower=True)
    x = jax.scipy.linalg.solve_triangular(chol, y, lower=True, trans="T")
    return x[..., 0]


def _scatter_rows(dst: jax.Array, row_ids: jax.Array, rows: jax.Array) -> jax.Array:
    """Write solved rows back; row_id == -1 rows (bucket padding) dropped.

    Invalid rows are routed out-of-bounds so ``mode="drop"`` discards them —
    never clamp them to a real index (a clamped duplicate write races the
    genuine row-0 update).
    """
    safe = jnp.where(row_ids >= 0, row_ids, dst.shape[0])
    return dst.at[safe].set(rows, mode="drop")


@functools.partial(jax.jit, static_argnames=(
    "implicit", "use_pallas", "gram_dtype", "solver"))
def _side_step(
    indices, values, mask, row_ids, dst_factors, src_factors, reg, alpha, *,
    implicit, use_pallas, gram_dtype="float32", solver="cholesky",
):
    yty = gram(src_factors) if implicit else jnp.zeros(
        (src_factors.shape[1], src_factors.shape[1]), jnp.float32)
    solved = _solve_bucket(indices, values, mask, src_factors, yty, reg, alpha,
                           implicit, use_pallas,
                           jnp.dtype(_resolve_gram_dtype(gram_dtype)), solver)
    return _scatter_rows(dst_factors, row_ids, solved)


def _merged_solve(
    indices, values, mask, seg_ids, ent_ids, dst_factors, src_factors, yty,
    reg, alpha, implicit, use_pallas, gram_dtype, solver,
):
    """Split-bucket step: partial rows → segment-summed normal equations.

    Over-long entities arrive as several partial rows (ops/ragged.py
    ``split_above``); their A/b/degree pieces are scatter-added per segment
    before the solve, so the result is bitwise the same math as an unsplit
    row without paying max-degree padding.  Shared by the fused training
    loop and the standalone jitted wrapper below.
    """
    a, b, deg = _gram_pieces(indices, values, mask, src_factors, alpha,
                             implicit, use_pallas, gram_dtype)
    n_seg = ent_ids.shape[0]
    k = src_factors.shape[1]
    A = jnp.zeros((n_seg, k, k), jnp.float32).at[seg_ids].add(a, mode="drop")
    B = jnp.zeros((n_seg, k), jnp.float32).at[seg_ids].add(b, mode="drop")
    degree = jnp.zeros((n_seg,), jnp.float32).at[seg_ids].add(deg, mode="drop")
    if implicit:
        A = yty[None, :, :] + A
    solved = _ridge(A, B, reg * jnp.maximum(degree, 1.0), solver)
    return _scatter_rows(dst_factors, ent_ids, solved)


@functools.partial(jax.jit, static_argnames=(
    "implicit", "use_pallas", "gram_dtype", "solver"))
def _merged_side_step(
    indices, values, mask, seg_ids, ent_ids, dst_factors, src_factors,
    reg, alpha, *, implicit, use_pallas, gram_dtype="float32",
    solver="cholesky",
):
    yty = gram(src_factors) if implicit else jnp.zeros(
        (src_factors.shape[1], src_factors.shape[1]), jnp.float32)
    return _merged_solve(indices, values, mask, seg_ids, ent_ids,
                         dst_factors, src_factors, yty, reg, alpha,
                         implicit, use_pallas,
                         jnp.dtype(_resolve_gram_dtype(gram_dtype)), solver)


def _window_gather(src: jax.Array, win: jax.Array,
                   sharding: Optional[NamedSharding]) -> jax.Array:
    """Fetch factor rows ``win`` from (possibly row-sharded) ``src``.

    Sharded case: masked local take + ``psum`` over the data axis via
    ``shard_map`` — each requested row lives in exactly ONE shard, so
    every other shard contributes exact zeros and the f32 sum is
    bitwise the row value.  The transient this materializes is
    ``[len(win), K]`` (the chunk's working set); relying on GSPMD's own
    gather lowering here is exactly what re-materialized the full
    matrix per sweep in round 4.
    """
    if sharding is None:
        return src[win]
    mesh = sharding.mesh
    d = mesh.shape[AXIS_DATA]
    shard_rows = src.shape[0] // d  # blocked mode pads rows to divide

    def local(src_local, win_rep):
        lo = jax.lax.axis_index(AXIS_DATA) * shard_rows
        loc = win_rep - lo
        ok = (loc >= 0) & (loc < shard_rows)
        rows = jnp.where(ok[:, None],
                         src_local[jnp.where(ok, loc, 0)], 0.0)
        return jax.lax.psum(rows, AXIS_DATA)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(AXIS_DATA, None), P()),
                         out_specs=P())(src, win)


def _chunk_window(idx: np.ndarray, msk: np.ndarray, n_src: int,
                  pad_to: int = 64) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Sorted unique src ids a chunk touches + the window-local remap.

    Returns ``None`` when windowing would not shrink the gather (window
    ≥ half the matrix) — the caller keeps the plain full-matrix path.
    Padding repeats the LAST (max) id so the array stays sorted for
    ``searchsorted``; duplicate fetches of one row are harmless.
    """
    win = np.unique(idx[msk])
    if win.size == 0:
        win = np.zeros(1, idx.dtype)
    padded = -(-win.size // pad_to) * pad_to
    if padded >= n_src // 2:
        return None
    win = np.pad(win, (0, padded - win.size), mode="edge")
    local = np.searchsorted(win, idx).astype(np.int32)
    local[~msk] = 0
    return win.astype(np.int32), local


def _chunk_split_bucket(
    p: Padded, rank: int, max_block_floats: int, pad_rows: int,
) -> List[Tuple]:
    """Cut a split bucket into HBM-bounded chunks at ENTITY boundaries.

    All partial rows of one entity must land in the same dispatch (their
    normal-equation pieces segment-sum before the solve), and ragged.py
    lays partial rows out grouped by entity, so cutting between entities
    is always legal.  Each chunk gets re-based seg_ids and its own ent_ids
    slice.
    """
    r, l = p.indices.shape
    rows_max = max(pad_rows, (max_block_floats // max(l * rank, 1))
                   // pad_rows * pad_rows)
    if r <= rows_max:
        return [(p.indices, p.values, p.mask, p.seg_ids, p.ent_ids)]
    n_seg = len(p.ent_ids)
    # First partial row of each segment (segments are contiguous row runs).
    seg_starts = np.searchsorted(p.seg_ids, np.arange(n_seg + 1), side="left")
    chunks = []
    e0 = 0
    while e0 < n_seg:
        e1 = e0 + 1
        while e1 < n_seg and seg_starts[e1 + 1] - seg_starts[e0] <= rows_max:
            e1 += 1
        r0, r1 = int(seg_starts[e0]), int(seg_starts[e1])
        if r1 == r0:  # trailing padding-only segments
            break
        rows = slice(r0, r1)
        seg = p.seg_ids[rows] - e0
        n_seg_chunk = e1 - e0
        # Row/segment padding to the mesh granule.
        row_pad = (-(r1 - r0)) % pad_rows
        seg_pad = (-n_seg_chunk) % pad_rows
        idx = np.pad(p.indices[rows], ((0, row_pad), (0, 0)))
        vals = np.pad(p.values[rows], ((0, row_pad), (0, 0)))
        msk = np.pad(p.mask[rows], ((0, row_pad), (0, 0)))
        seg = np.pad(seg, (0, row_pad),
                     constant_values=n_seg_chunk + seg_pad)  # OOB → dropped
        ent = np.pad(p.ent_ids[e0:e1], (0, seg_pad), constant_values=-1)
        chunks.append((idx, vals, msk, seg.astype(np.int32), ent))
        e0 = e1
    return chunks


def _device_buckets(
    buckets: List[Padded],
    mesh: Optional[Mesh],
    rank: int,
    max_block_floats: int,
    pad_rows: int,
    window_n_src: Optional[int] = None,
) -> List[Tuple]:
    """Transfer padded buckets, splitting any whose gathered [R, L, K]
    block would exceed the HBM budget into fixed-shape row chunks (last
    chunk row-padded with row_id = -1, which the scatter drops).

    Returns ``("plain", idx, vals, msk, row_ids)`` or
    ``("merged", idx, vals, msk, seg_ids, ent_ids)`` tuples.  With
    ``window_n_src`` (blocked factor-sharded mode), chunks whose src
    working set is under half the matrix become ``("plain_w", ...,
    win)`` / ``("merged_w", ..., win)``: indices are window-local and
    ``win`` (replicated) names the factor rows the sweep must fetch.

    ISSUE 13 satellite (carried since PR 5): the staging rides the
    SHARED input path — a :class:`~predictionio_tpu.data.prefetch.
    DevicePrefetcher` whose source generator does the host-side
    chunk/pad/window work and whose put function issues the transfers,
    so (a) the next chunk's numpy padding overlaps the previous chunk's
    asynchronously-draining H2D instead of serializing after it, and
    (b) ALS staging shows up in the same ``pio_prefetch_*`` metrics and
    train-loop lints that already cover the deep models, instead of its
    own private path.
    """

    def windowed(kind, idx, msk, rest):
        if window_n_src is None:
            return kind, (idx, *rest), None
        w = _chunk_window(idx, msk, window_n_src)
        if w is None:
            return kind, (idx, *rest), None
        win, local = w
        return kind, (local, *rest), win

    def entries():
        """(kind, host_arrs, win) stream — all chunk/pad/window numpy
        work happens HERE, i.e. on the prefetcher's prep thread."""
        for p in buckets:
            if p.split:
                for idx, vals, msk, seg, ent in _chunk_split_bucket(
                        p, rank, max_block_floats, pad_rows):
                    yield windowed("merged", idx, msk,
                                   (vals, msk, seg, ent))
                continue
            r, l = p.indices.shape
            rows_max = max(pad_rows,
                           (max_block_floats // max(l * rank, 1))
                           // pad_rows * pad_rows)
            chunks = [(p.indices, p.values, p.mask, p.row_ids)] \
                if r <= rows_max else []
            if r > rows_max:
                for start in range(0, r, rows_max):
                    sl = slice(start, start + rows_max)
                    idx, vals = p.indices[sl], p.values[sl]
                    msk, rid = p.mask[sl], p.row_ids[sl]
                    short = rows_max - idx.shape[0]
                    if short:
                        idx = np.pad(idx, ((0, short), (0, 0)))
                        vals = np.pad(vals, ((0, short), (0, 0)))
                        msk = np.pad(msk, ((0, short), (0, 0)))
                        rid = np.pad(rid, (0, short), constant_values=-1)
                    chunks.append((idx, vals, msk, rid))
            for idx, vals, msk, rid in chunks:
                yield windowed("plain", idx, msk, (vals, msk, rid))

    def put_entry(entry):
        kind, host_arrs, win = entry
        if mesh is not None:
            # put_sharded takes the HOST arrays directly — a jnp.asarray
            # first would waste a full default-device upload (+ download
            # in a multi-host gang).
            row = NamedSharding(mesh, P(AXIS_DATA))
            arrs = [put_sharded(a, mesh, row) for a in host_arrs]
            if win is not None:
                arrs.append(put_sharded(win, mesh,
                                        NamedSharding(mesh, P())))
        else:
            arrs = [jnp.asarray(a) for a in host_arrs]
            if win is not None:
                arrs.append(jnp.asarray(win))
        return (kind + "_w" if win is not None else kind, *arrs)

    from predictionio_tpu.data.prefetch import DevicePrefetcher

    out = []
    with DevicePrefetcher(entries(), prep_fn=lambda e: e,
                          put_fn=put_entry, count_fn=lambda e: 1,
                          model="als") as pf:
        for batch in pf:
            out.append(batch.args)
    return out


@dataclasses.dataclass
class ALSInputs:
    """Device-resident padded buckets + factor init (prep done once).

    Separating prep from the iteration loop lets callers (serving reloads,
    the benchmark's slope timing, incremental retrains) re-run the fused
    training program without re-bucketing or re-uploading.

    Two layouts: the host/mesh path stores PRE-CHUNKED tuples
    (``chunk_specs is None``); the device-prep path stores BUCKET-level
    arrays plus static ``chunk_specs`` and the training loop slices the
    HBM chunks in-graph — emitting per-chunk outputs from the build
    program cost ~1.1 s of compile per chunk, ~45 s of the round-3 cold
    start (measured before PR 1, on another installation).
    """

    uf0: jax.Array
    itf0: jax.Array
    user_buckets: List[Tuple]
    item_buckets: List[Tuple]
    n_users: int
    n_items: int
    # Per side: tuple over buckets of ("plain", ((cs, cn), ...)),
    # ("merged", pad_to, ((e0, e1, r0, r1), ...)) or ("dense", ());
    # None = pre-chunked.
    chunk_specs: Optional[Tuple[Tuple, Tuple]] = None
    # Future resolving to (statics, compiled loop executable) from the
    # plan-shape pre-warm, or None; loop_warm_statics mirrors the statics
    # the pre-warm lowered so a mismatched train can skip the wait.
    loop_warm: Optional[object] = None
    loop_warm_statics: Optional[dict] = None
    # Ratings a sweep builds normal equations from, per side (users,
    # items) as (dense, gathered): what pio_als_gram_ratings_total
    # advances by per sweep.
    gram_ratings: Tuple[Tuple[int, int], Tuple[int, int]] = ((0, 0), (0, 0))


def prepare_als_inputs(
    user_ids: np.ndarray,
    item_ids: np.ndarray,
    ratings: Optional[np.ndarray],
    n_users: int,
    n_items: int,
    config: ALSConfig,
    mesh: Optional[Mesh] = None,
    host_ids: Optional[Tuple[np.ndarray, np.ndarray]] = None,
) -> ALSInputs:
    """Bucketing + transfer for :func:`train_als_prepared`.

    Dispatches to the device-side layout transform
    (:mod:`predictionio_tpu.ops.device_prep`) on TPU — compact COO up,
    one XLA program builds the padded blocks in HBM — and to the
    host-numpy path elsewhere (CPU tests, meshes, max_degree truncation).
    ``host_ids``: optional numpy copies of (user_ids, item_ids) for
    callers that pass pre-uploaded device arrays — lets the bucket plan
    run on host (one bincount) instead of per-op device round-trips.
    """
    use_dev = config.device_prep
    if use_dev == "auto":
        use_dev = (pallas_supported() and mesh is None
                   and config.max_degree is None)
    logger.info("ALS prep: device_prep=%s (mesh=%s)", bool(use_dev),
                dict(mesh.shape) if mesh is not None else None)
    if use_dev:
        return _prepare_als_inputs_device(user_ids, item_ids, ratings,
                                          n_users, n_items, config,
                                          host_ids=host_ids)
    k = config.rank
    # Row counts pad to the lcm of the mesh axis (sharded dims must
    # divide) and the TPU sublane (LEN_ALIGN): unaligned bucket rows made
    # XLA pad/relayout every gathered [R, L, K] block in-graph, EVERY
    # iteration — measured 292 vs 177 ms/iter at the ML-25M shape, ~70 ms
    # of it pad/misc ops (the device-prep plan has always 8-aligned its
    # rows; this brings the host/mesh layout into lock-step).
    d = mesh.shape[AXIS_DATA] if mesh is not None else 1
    pad_rows = math.lcm(LEN_ALIGN, d)
    with phase("prep.init_factors"):
        uf, itf = _init_factors(n_users, n_items, k, config.seed)
    sharded = mesh is not None and _shard_factors(config, n_users, n_items)
    window = config.gather_window
    if window == "auto":
        # A 1-device "mesh" has no cross-shard transient to shrink — the
        # window only adds a second gather level (measured ~3% per-iter
        # on the real chip: 288 vs 280 ms).  Windows pay off from 2
        # shards up, where they bound the transient (BASELINE.md).
        window = sharded and d > 1
    elif not isinstance(window, bool):
        raise ValueError(f"gather_window must be 'auto', True or False "
                         f"(got {config.gather_window!r})")
    window = window and sharded  # windows only exist over sharded factors
    if mesh is not None:
        if sharded:
            # Row-shard the persistent state; rows pad to the axis size
            # (sharded dims must divide).  Padded rows are never gathered
            # (indices < n) nor scattered to (row_ids < n); the final
            # model slices them off (train_als_prepared).
            uf = jnp.pad(uf, ((0, (-n_users) % d), (0, 0)))
            itf = jnp.pad(itf, ((0, (-n_items) % d), (0, 0)))
            spec = P(AXIS_DATA, None)
        else:
            spec = P()
        uf = put_sharded(uf, mesh, NamedSharding(mesh, spec))
        itf = put_sharded(itf, mesh, NamedSharding(mesh, spec))

    def one_side(rows, cols, n_rows, n_src):
        # One side at a time, so only one side's host buckets are alive:
        # both phases are observed once per side here.
        with phase("prep.plan"):
            buckets = bucket_by_length(
                rows, cols, ratings, n_rows,
                bucket_bounds=config.bucket_bounds,
                max_len=config.max_degree, pad_rows_to=pad_rows,
                split_above=config.split_above)
        gathered = sum(int(p.mask.sum()) for p in buckets)
        with phase("prep.upload"):
            return (0, gathered), _device_buckets(
                buckets, mesh, k, config.max_block_floats, pad_rows,
                window_n_src=n_src if window else None)

    count_u, user_buckets = one_side(user_ids, item_ids, n_users, n_items)
    count_i, item_buckets = one_side(item_ids, user_ids, n_items, n_users)
    return ALSInputs(uf0=uf, itf0=itf, user_buckets=user_buckets,
                     item_buckets=item_buckets, n_users=n_users,
                     n_items=n_items, gram_ratings=(count_u, count_i))


# (BucketPlan, nnz) -> AOT-compiled build program.  LRU-bounded: a
# long-lived retrain loop sees a new nnz every cycle and must not leak one
# executable per retrain.
_BUILD_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_BUILD_CACHE_MAX = 6


def _build_cache_get(key):
    co = _BUILD_CACHE.get(key)
    if co is not None:
        _BUILD_CACHE.move_to_end(key)
    return co


def _build_cache_put(key, co):
    _BUILD_CACHE[key] = co
    while len(_BUILD_CACHE) > _BUILD_CACHE_MAX:
        _BUILD_CACHE.popitem(last=False)



# warm_key -> Future[(statics, loop executable) | None]; LRU-bounded like
# the build cache (retrain loops see a new plan every data refresh).
_WARM_CACHE: "collections.OrderedDict" = collections.OrderedDict()


def _warm_cache_get(key):
    fut = _WARM_CACHE.get(key)
    if fut is not None:
        _WARM_CACHE.move_to_end(key)
    return fut


def _warm_cache_put(key, fut):
    _WARM_CACHE[key] = fut
    while len(_WARM_CACHE) > _BUILD_CACHE_MAX:
        _WARM_CACHE.popitem(last=False)


def _compile_build(lowered):
    """Compile a prep build program at REDUCED optimization effort.

    The build runs once per dataset (~5 s exec) but its default-effort
    compile was the cold-start wall (33 + 48 s for the two sides at the
    ML-25M shape).  ``exec_time_optimization_effort=-1`` compiles the
    same program in ~21 + 31 s with no measurable exec regression (the
    program is scatter/gather-bound; there's nothing for the scheduler
    to win).  The hot training loop stays at DEFAULT effort — low effort
    there measured 533 vs 184 ms/iter.  Both options are accepted by
    the XLA:TPU compiler of libtpu 0.0.34 and by the CPU compiler of
    jaxlib 0.9.0 (forced ``device_prep=True`` in tests), so they are
    passed unconditionally: a refusal is a compile error, not a retry.
    """
    return lowered.compile(compiler_options={
        "exec_time_optimization_effort": -1.0,
        "memory_fitting_effort": -1.0,
    })


def _plan_side(rows: jax.Array, n_rows: int, config: ALSConfig,
               host_rows: Optional[np.ndarray] = None,
               n_src: Optional[int] = None,
               host_cols: Optional[np.ndarray] = None):
    """One side's :class:`~ops.device_prep.BucketPlan` from COO ids.

    With ``host_rows`` (the caller's numpy copy of the same ids) the
    degree statistics run as one ``np.bincount`` — ~0.3 s at 25M rows.
    The device fallback exists for device-only callers; each of its
    small jitted stats ops pays its own compile and dispatch.
    ``n_src`` (the other side's row count) lets dense-enough rows go to
    the dense block; without it the plan is the all-sparse one.  With
    ``host_cols`` too, a side whose densest row repeats a (row, col) pair
    (views, plays: the data a block cannot hold) is planned all-sparse
    here, for the cost of looking at one row, rather than found out by
    the build.
    """
    from predictionio_tpu.ops.device_prep import (
        degree_histogram, plan_buckets,
    )

    split_above = config.split_above or 1 << 20
    if host_rows is not None:
        # Exact replica of ops.device_prep.degree_histogram: counts over
        # ALL n_rows entities (zero-degree included), degrees clipped at
        # the cap into cap+1 bins, over-cap degrees in entity-id order.
        # Match the device scatter-add's index semantics exactly: JAX
        # ``.at[rows].add`` WRAPS negative ids numpy-style (id + n_rows
        # for -n_rows <= id < 0) and drops ids outside [-n_rows, n_rows).
        host_rows = np.asarray(host_rows)
        if host_rows.size and host_rows.min() < 0:
            host_rows = np.where(host_rows < 0, host_rows + n_rows,
                                 host_rows)
        in_range = (host_rows >= 0) & (host_rows < n_rows)
        if not in_range.all():
            host_rows = host_rows[in_range]
        counts = np.bincount(host_rows, minlength=n_rows)
        hist = np.bincount(np.minimum(counts, split_above),
                           minlength=split_above + 1)
        over = counts > split_above
        n_over = int(over.sum())
        n_part = int(((counts[over] + split_above - 1)
                      // split_above).sum())
        over_deg = counts[over].astype(np.int64) if n_over else None
    else:
        counts = jnp.zeros(n_rows, jnp.int32).at[rows].add(1)
        hist, n_over, n_part = degree_histogram(counts, split_above)
        over_deg = None
        if n_over:
            # Degrees of the over-cap entities in id order — the plan
            # needs them to place split-chunk boundaries (tiny D2H).
            ids = jnp.nonzero(counts > split_above, size=n_over)[0]
            over_deg = np.asarray(counts[ids])
    def plan(n_src):
        return plan_buckets(hist, n_over, n_part, n_rows,
                            split_above=split_above,
                            bucket_bounds=config.bucket_bounds,
                            max_block_floats=config.max_block_floats,
                            rank=config.rank, over_degrees=over_deg,
                            n_src=n_src)

    out = plan(n_src)
    if out.dense_rows and host_rows is not None and host_cols is not None \
            and len(host_cols) == len(host_rows):
        top = np.asarray(host_cols)[host_rows == counts.argmax()]
        if len(np.unique(top)) != len(top):
            return plan(None)
    return out


def _plan_bucket_shapes(plan):
    """ShapeDtypeStruct bucket tuples exactly as the prep path emits them.

    Mirrors ``_prepare_als_inputs_device.one_side``: plain buckets at
    BUCKET level (one entry per plan bucket, chunk slicing is in-graph),
    then the merged split bucket, then the dense block.  Keeping this
    in lock-step with ``ops.device_prep.build_buckets`` and
    ``build_dense_block`` is what lets the loop pre-warm lower an
    IDENTICAL program from shapes alone (test-asserted:
    tests/test_device_prep.py::TestPlanShapeLockstep, TestDensePrep).
    """
    S = jax.ShapeDtypeStruct
    f32, i32, b_ = jnp.float32, jnp.int32, jnp.bool_
    out = []
    for b, rp in zip(plan.bounds, plan.rows_padded):
        out.append(("plain", S((rp, b), i32), S((rp, b), f32),
                    S((rp, b), b_), S((rp,), i32)))
    specs = [("plain", ch) for ch in plan.plain_chunks]
    if plan.split_len is not None:
        pr, sl, ns = plan.split_rows, plan.split_len, plan.split_segs
        out.append(("merged", S((pr, sl), i32), S((pr, sl), f32),
                    S((pr, sl), b_), S((pr,), i32), S((ns,), i32)))
        specs.append(("merged", plan.pad_rows_to, plan.split_chunks))
    if plan.dense_rows:
        jp = plan.dense_rows
        out.append(("dense",
                    S((jp, dense_block_width(plan.dense_src)),
                      DENSE_BLOCK_DTYPE),
                    S((jp,), i32), S((jp,), f32)))
        specs.append(("dense", ()))
    return out, tuple(specs)


def _lower_train_loop_from_plans(config: ALSConfig, plan_u, plan_i,
                                 n_users: int, n_items: int):
    """Lower the fused loop from plan shapes only → (statics, Lowered).

    The loop program depends only on the bucket LAYOUT (plan + rank) —
    verified identical HLO to the live call's lowering, real-array
    layouts included — so it can be lowered before prep outputs exist.
    Lowering runs on the CALLING thread (it holds the GIL; doing it on
    the warm thread stretched a concurrent warm re-prep 5.9 → 18 s).
    """
    ub, spec_u = _plan_bucket_shapes(plan_u)
    ib, spec_i = _plan_bucket_shapes(plan_i)
    statics = _resolve_loop_statics(config, ub, ib, (spec_u, spec_i))
    S = jax.ShapeDtypeStruct
    k = config.rank
    lowered = _train_loop.lower(
        S((n_users, k), jnp.float32), S((n_items, k), jnp.float32),
        tuple(tuple(b[1:]) for b in ub),
        tuple(tuple(b[1:]) for b in ib),
        S((), jnp.float32), S((), jnp.float32), S((), jnp.int32),
        factor_shardings=(None, None), **statics)
    return statics, lowered


def _compile_train_loop(statics, lowered, fut) -> None:
    """Warm-thread tail: the compile itself, no GIL-heavy work.

    Delivers ``(statics, executable)`` (or ``None`` on failure) through
    ``fut``; :func:`train_als_prepared` CALLS the executable directly, so
    the overlap does not depend on in-flight dedupe inside the compiler.
    A failure here is not fatal — the train then compiles the same
    program itself and raises the same error in the open — but it is
    said at WARNING, with the error.
    """
    try:
        fut.set_result((statics, lowered.compile()))
    except Exception as e:  # pre-warm must never sink a train
        logger.warning("ALS loop pre-warm compile failed: %s: %s",
                       type(e).__name__, e)
        fut.set_result(None)


def _dense_block_holds(ratings) -> bool:
    """Whether a dense block can stand for these ratings: each is a value
    its dtype holds exactly (and none is NaN, the block's "no rating"),
    so the ``w`` and ``c`` the dense kernel derives are the gathered
    path's, bit for bit.  Stars, half-stars, counts and ones are."""
    if ratings is None:
        return True
    if isinstance(ratings, np.ndarray):
        r = np.asarray(ratings, np.float32)
        return np.array_equal(
            r.astype(DENSE_BLOCK_DTYPE).astype(np.float32), r)
    r = jnp.asarray(ratings, jnp.float32)
    return bool(jnp.all(
        r.astype(DENSE_BLOCK_DTYPE).astype(jnp.float32) == r))


def _prepare_als_inputs_device(
    user_ids, item_ids, ratings, n_users: int, n_items: int,
    config: ALSConfig, host_ids=None, dense: bool = True,
) -> ALSInputs:
    """Device-side prep: COO up once, layout transform on the chip.

    ``dense=False`` plans no dense rows: the second pass of a prep whose
    dense block could not hold its rows' ratings."""
    from predictionio_tpu.ops.device_prep import (
        build_buckets, build_dense_block,
    )

    k = config.rank
    # The DEVICE data always comes from user_ids/item_ids — host_ids is a
    # stats-only hint; feeding it to jnp.asarray would re-upload the COO
    # a second time when the caller already device_put it.  Numpy inputs
    # convert to int32 ONCE and serve both the upload and the host stats.
    def one_input(ids, hint):
        if hint is not None:
            return np.asarray(hint, dtype=np.int32), jnp.asarray(
                ids, dtype=jnp.int32)
        if isinstance(ids, np.ndarray):
            h = np.asarray(ids, dtype=np.int32)
            return h, jnp.asarray(h)
        return None, jnp.asarray(ids, dtype=jnp.int32)

    # The prep.* phases (pio_train_phase_ms) time calls, not the device:
    # uploads and dispatches are asynchronous.  All are the foreground
    # thread's but prep.build_run, the build program's dispatch, made by
    # the thread that compiled it while the loop is lowered here.
    with phase("prep.upload"):
        host_u, rows_u = one_input(user_ids,
                                   host_ids[0] if host_ids else None)
        host_i, rows_i = one_input(item_ids,
                                   host_ids[1] if host_ids else None)
        if ratings is None:
            vals = jnp.ones(rows_u.shape[0], jnp.float32)
        else:
            vals = jnp.asarray(ratings, dtype=jnp.float32)

    with phase("prep.plan"):
        dense = dense and _dense_block_holds(ratings)
        plan_u = _plan_side(rows_u, n_users, config, host_rows=host_u,
                            n_src=n_items if dense else None,
                            host_cols=host_i)
        plan_i = _plan_side(rows_i, n_items, config, host_rows=host_i,
                            n_src=n_users if dense else None,
                            host_cols=host_u)
    nnz = rows_u.shape[0]

    # The build program emits BUCKET-level arrays (chunk slicing happens
    # in-graph inside the training loop — see _expand_chunks); its compile
    # is the cold-start wall, so every op it doesn't contain is compile
    # time saved.  BOTH sides compile as ONE program.  AOT executables
    # bypass the in-memory jit cache, so memoize per (plans, nnz) — warm
    # re-preps (retrains, the bench's second pass) skip the compile; a
    # new process finds both programs in the persistent compile cache
    # (backend.configure_compile_cache).  The program is RUN from the
    # thread that compiled it, as soon as it exists, so the chip buckets
    # while this thread lowers the loop (a warm compile cache answers in
    # a second or two; the build run used to start only after the loop's
    # lowering, and all of it was the caller's wait).
    import concurrent.futures

    build_u = dataclasses.replace(plan_u, plain_chunks=(), split_chunks=())
    build_i = dataclasses.replace(plan_i, plain_chunks=(), split_chunks=())

    def dense_block(r, c, v, p):
        if not p.dense_rows:
            return None
        return build_dense_block.__wrapped__(
            r, c, v, n_rows=p.n_rows, n_src=p.dense_src,
            dense_min=p.dense_min, dense_rows=p.dense_rows)

    def build_both(ru, ri, v, *, pu, pi):
        return ((*build_buckets.__wrapped__(ru, ri, v, pu),
                 dense_block(ru, ri, v, pu)),
                (*build_buckets.__wrapped__(ri, ru, v, pi),
                 dense_block(ri, ru, v, pi)))

    co = _build_cache_get((build_u, build_i, nnz))
    pend = concurrent.futures.Future()
    if co is not None:
        with phase("prep.build_run"):
            pend.set_result((co, co(rows_u, rows_i, vals)))
    else:
        with phase("prep.lower_build"):
            lowered = jax.jit(
                build_both, static_argnames=("pu", "pi")).lower(
                    rows_u, rows_i, vals, pu=build_u, pi=build_i)
        # Daemon thread + Future (same pattern as _compile_train_loop): a
        # non-daemon executor worker would block interpreter exit if the
        # backend compile RPC ever hangs.

        def _compile_and_run_build(lowered=lowered, fut=pend):
            try:
                co = _compile_build(lowered)
                with phase("prep.build_run"):
                    out = co(rows_u, rows_i, vals)
                fut.set_result((co, out))
            except BaseException as e:  # delivered to the waiter
                fut.set_exception(e)

        threading.Thread(target=_compile_and_run_build, daemon=True).start()

    # Fire the fused-loop compile from plan-derived shapes — its cold
    # compile overlaps prep execution and whatever the caller does
    # before training, and the resulting EXECUTABLE is handed to
    # train_als_prepared through the future.  Submitted AFTER the build
    # compile, which prep waits on first.  LRU'd so warm re-preps
    # (retrains, the bench's second pass) reuse the executable instead
    # of re-lowering.
    # Key on exactly what the lowering consumes (plans + dims + the
    # statics-determining config fields): keying on the whole config made
    # a seed sweep recompile a byte-identical program per seed.
    warm_key = (plan_u, plan_i, n_users, n_items, config.rank,
                config.implicit, _resolve_gram_dtype(config.gram_dtype),
                config.solver, config.use_pallas)
    cached = _warm_cache_get(warm_key)
    if cached is not None and cached[0].done() \
            and cached[0].result() is None:
        cached = None  # failed pre-warm: retry rather than stay poisoned
    if cached is None:
        fut = concurrent.futures.Future()
        loop_statics = None
        try:
            with phase("prep.lower_loop"):
                loop_statics, loop_lowered = _lower_train_loop_from_plans(
                    config, plan_u, plan_i, n_users, n_items)
            threading.Thread(target=_compile_train_loop,
                             args=(loop_statics, loop_lowered, fut),
                             daemon=True).start()
        except Exception as e:
            logger.warning("ALS loop pre-warm lower failed: %s: %s",
                           type(e).__name__, e)
            fut.set_result(None)
        # Statics stored ALONGSIDE the future so a train with different
        # statics can skip the wait without blocking on a compile it
        # would discard.
        cached = (fut, loop_statics)
        _warm_cache_put(warm_key, cached)
    fut, warm_statics = cached

    with phase("prep.init_factors"):
        uf, itf = _init_factors(n_users, n_items, k, config.seed)

    with phase("prep.compile_wait"):
        co, (side_u, side_i) = pend.result()
    _build_cache_put((build_u, build_i, nnz), co)

    # A block holds one value a (row, source) pair: where a dense row's
    # ratings repeat a pair, fewer slots are filled than it has ratings,
    # and the whole prep is made again all-sparse.  That costs a second
    # build and lowering, so _plan_side keeps the common case out (a
    # densest row that repeats a pair plans no dense rows); the read of
    # a scalar a side waits for the build, only where a side has a block.
    short = {side: (int(built[2][3]), plan.dense_ratings)
             for side, built, plan in (("user", side_u, plan_u),
                                       ("item", side_i, plan_i))
             if built[2] is not None}
    if any(filled != want for filled, want in short.values()):
        logger.warning(
            "ALS prep: dense rows repeat a (user, item) pair (slots filled, "
            "ratings: %s); preparing again without dense rows", short)
        del side_u, side_i
        return _prepare_als_inputs_device(
            user_ids, item_ids, ratings, n_users, n_items, config,
            host_ids=host_ids, dense=False)

    def one_side(built, plan):
        plain, split, block = built
        out = [("plain", *b) for b in plain]
        specs = [("plain", ch) for ch in plan.plain_chunks]
        if split is not None:
            out.extend(("merged", *b) for b in split)
            specs.append(("merged", plan.pad_rows_to, plan.split_chunks))
        if block is not None:
            out.append(("dense", *block[:3]))
            specs.append(("dense", ()))
        return out, tuple(specs)

    user_buckets, spec_u = one_side(side_u, plan_u)
    item_buckets, spec_i = one_side(side_i, plan_i)
    return ALSInputs(uf0=uf, itf0=itf, user_buckets=user_buckets,
                     item_buckets=item_buckets, n_users=n_users,
                     n_items=n_items, chunk_specs=(spec_u, spec_i),
                     loop_warm=fut, loop_warm_statics=warm_statics,
                     gram_ratings=tuple(
                         (p.dense_ratings, nnz - p.dense_ratings)
                         for p in (plan_u, plan_i)))


def train_als(
    user_ids: np.ndarray,
    item_ids: np.ndarray,
    ratings: Optional[np.ndarray],
    n_users: int,
    n_items: int,
    config: ALSConfig,
    mesh: Optional[Mesh] = None,
    *,
    checkpoint_dir=None,
    save_every: int = 0,
) -> ALSModel:
    """Train from COO triplets.

    With a mesh, solve rows are sharded over the ``data`` axis and factors
    are replicated — the per-iteration factor exchange is the implicit
    all-gather XLA inserts, riding ICI (reference: Spark shuffle between
    in/out ALS blocks).
    """
    inputs = prepare_als_inputs(user_ids, item_ids, ratings, n_users,
                                n_items, config, mesh)
    return train_als_prepared(inputs, config, checkpoint_dir=checkpoint_dir,
                              save_every=save_every)


def train_als_prepared(inputs: ALSInputs, config: ALSConfig, *,
                       checkpoint_dir=None, save_every: int = 0) -> ALSModel:
    """The fused iteration loop over pre-built device buckets.

    With ``checkpoint_dir`` + ``save_every``, the fori_loop is chunked at
    sweep granularity and factor state orbax-saved every ``save_every``
    sweeps; a killed train resumes from the latest complete sweep and —
    because the loop bound is a traced scalar (one compiled program
    regardless of chunking) and sweep math is index-independent — the
    resumed result is bitwise equal to an uninterrupted run
    (SURVEY.md §5.4: resume is a capability the reference lacks;
    tests/test_checkpoint_resume.py pins the equality).

    Supervision (resilience/supervision.py): each sweep chunk's factors
    are finiteness-checked before they can be checkpointed — a
    non-finite chunk rolls back to the last-good sweep (bounded by
    ``PIO_DIVERGENCE_RETRIES``, then ``TrainDiverged``); SIGTERM
    preemption force-saves the current sweep and raises
    ``TrainPreempted``; ``PIO_STEP_TIMEOUT_S`` arms a watchdog around
    every device dispatch (one ``sweeps()`` call).
    """
    k = config.rank
    uf, itf = inputs.uf0, inputs.itf0
    user_buckets = inputs.user_buckets
    item_buckets = inputs.item_buckets
    reg = jnp.float32(config.reg)
    alpha = jnp.float32(config.alpha)
    statics = _resolve_loop_statics(config, user_buckets, item_buckets,
                                    inputs.chunk_specs)
    pallas_chunks = sum(map(sum, statics["pallas_flags"]))
    logger.info(
        "ALS loop: rank=%d iterations=%d use_pallas=%s (%d/%d chunks, "
        "kernels %s) solver=%s gram_dtype=%s device_prep=%s",
        k, config.iterations, pallas_chunks > 0, pallas_chunks,
        sum(map(len, statics["pallas_flags"])),
        "compiled" if pallas_supported() else "interpret",
        statics["solver"], statics["gram_dtype"],
        inputs.chunk_specs is not None)
    # The WHOLE alternation loop is one jitted program: a fori_loop over
    # iterations with every bucket step unrolled in the body.  One dispatch
    # per training run instead of O(iterations x buckets) — launch/host
    # round-trip latency, not FLOPs, dominated the per-step formulation
    # (measured: solver/precision/padding changes moved ML-1M train time
    # <10%; fusing the loop is what actually buys throughput).
    ubk = tuple(tuple(b[1:]) for b in user_buckets)
    ibk = tuple(tuple(b[1:]) for b in item_buckets)

    # Blocked (factor-sharded) mode: re-impose the row-sharding on the
    # carry each sweep so GSPMD keeps the persistent state sharded instead
    # of silently replicating it after the scatter.
    factor_shardings = (_factor_constraint(uf), _factor_constraint(itf))

    # Use the pre-warm's executable when it compiled EXACTLY this program
    # (same statics, meshless): the train then waits on the overlapped
    # compile instead of issuing its own.
    warm_exe = None
    if (inputs.loop_warm is not None and factor_shardings == (None, None)
            and inputs.loop_warm_statics == statics):
        with phase("train.loop_wait"):
            # blocks only while the pre-warm is still compiling
            warm = inputs.loop_warm.result()
        if warm is not None and warm[0] == statics:
            warm_exe = warm[1]

    gram_ratings = get_registry().counter(
        "pio_als_gram_ratings_total",
        "Ratings whose normal equations an ALS dispatch built (the plan's "
        "counts x sweeps), by side and by path: dense (a masked product "
        "over the whole factor table) or gathered (factor rows by index).",
        ("side", "path"))

    gather_ratings = get_registry().counter(
        "pio_als_gather_ratings_total",
        "Gathered ratings (pio_als_gram_ratings_total's path=gathered) by "
        "side and by the form of the fetch, which follows the other side's "
        "factor table: packed (a view that lays several rows in one "
        "128-lane row brings the table into the chip's fast memory) or "
        "plain (the table as it is).",
        ("side", "form"))
    select_ratings = get_registry().counter(
        "pio_als_gather_select_ratings_total",
        "Gathered ratings (pio_als_gather_ratings_total) by side and by "
        "where each packed row's part is kept: kernel (inside the sparse "
        "gram kernel, on the rows it fetches anyway), xla (a pass of its "
        "own over the gathered rows, between the gather and the gram) or "
        "none (the table is gathered as it is: nothing to keep).",
        ("side", "where"))
    gdt = jnp.dtype(statics["gram_dtype"])
    packs = tuple(gather_table_pack(n_src, k, gdt.itemsize) or 1
                  for n_src in (itf.shape[0], uf.shape[0]))
    forms = tuple(
        ("plain", "none") if pack == 1 else
        ("packed", "kernel" if any(flags) and gram_takes_packed(k, pack)
         else "xla")
        for pack, flags in zip(packs, statics["pallas_flags"]))

    def sweeps(uf, itf, n):
        for side, (form, where), (dense, gathered) in zip(
                ("user", "item"), forms, inputs.gram_ratings):
            gram_ratings.inc(float(dense) * n, side=side, path="dense")
            gram_ratings.inc(float(gathered) * n, side=side,
                             path="gathered")
            gather_ratings.inc(float(gathered) * n, side=side, form=form)
            select_ratings.inc(float(gathered) * n, side=side, where=where)
        if warm_exe is not None:
            return warm_exe(uf, itf, ubk, ibk, reg, alpha, jnp.int32(n))
        return _train_loop(
            uf, itf, ubk, ibk, reg, alpha, jnp.int32(n),
            factor_shardings=factor_shardings, **statics)

    from predictionio_tpu.resilience.supervision import (
        DivergenceGuard,
        RollbackRequested,
        StepWatchdog,
        TrainDiverged,
        TrainPreempted,
        all_finite,
        preemption_requested,
    )

    guard = DivergenceGuard("als")
    if checkpoint_dir and save_every > 0:
        from predictionio_tpu.workflow.checkpoint import TrainCheckpointer

        # Fingerprint = config + data dims: checkpoints from a different
        # config or a grown dataset are discarded, not resumed into.
        fp = f"als|{config}|{inputs.n_users}x{inputs.n_items}"
        ckpt = TrainCheckpointer(checkpoint_dir, save_every=save_every,
                                 fingerprint=fp)
        watchdog = StepWatchdog("als", checkpoint_fn=ckpt.flush)
        try:
            done = ckpt.restore_step((uf, itf), total_steps=config.iterations)
            if ckpt.restored_state is not None:
                uf, itf = ckpt.restored_state
            while done < config.iterations:
                n = min(save_every, config.iterations - done)
                watchdog.arm(done + n)
                with phase("train.dispatch"):
                    uf2, itf2 = sweeps(uf, itf, n)
                    finite = all_finite((uf2, itf2))  # forces the dispatch
                watchdog.disarm()
                if not finite:
                    # Rollback IN PLACE: re-restore the latest durable
                    # sweep (or the factor init when none exists) and
                    # replay.  The sweep math is index-independent, so a
                    # replayed chunk is the same program.  diverged()
                    # raises TrainDiverged once the retries are spent.
                    try:
                        guard.diverged(done + n, "non-finite factors")
                    except RollbackRequested:
                        pass
                    ckpt.restored_state = None
                    done = ckpt.restore_step((uf, itf),
                                             total_steps=config.iterations)
                    if ckpt.restored_state is not None:
                        uf, itf = ckpt.restored_state
                    else:
                        uf, itf = inputs.uf0, inputs.itf0
                        done = 0
                    continue
                uf, itf = uf2, itf2
                done += n
                saved = ckpt.maybe_save(done, (uf, itf))
                if preemption_requested():
                    if not saved:
                        ckpt.save(done, (uf, itf))
                    ckpt.flush()
                    raise TrainPreempted("als", done, True)
            ckpt.complete()
        finally:
            watchdog.stop()
            ckpt.close()
    else:
        watchdog = StepWatchdog("als")
        watchdog.arm(int(config.iterations))
        try:
            with phase("train.dispatch"):
                uf, itf = sweeps(uf, itf, config.iterations)
                # forces the dispatch
                finite = all_finite((uf, itf))
            # No checkpoint to roll back to: a non-finite result is a
            # terminal divergence (never silently returned/persisted).
            if not finite:
                raise TrainDiverged("als", int(config.iterations),
                                    "non-finite factors", 0)
        finally:
            watchdog.stop()
    # Blocked mode pads factor rows to the mesh axis size; the model keeps
    # the true extents.
    if uf.shape[0] != inputs.n_users:
        uf = uf[:inputs.n_users]
    if itf.shape[0] != inputs.n_items:
        itf = itf[:inputs.n_items]
    # Where the result lives, as the devices report it: a mesh run that
    # left three of four chips empty shows here, not in a timing.
    logger.info(
        "ALS factors: user_factors on %d device(s), item_factors on %d "
        "device(s); bytes_in_use per device %s",
        len(uf.sharding.device_set), len(itf.sharding.device_set),
        [(d.memory_stats() or {}).get("bytes_in_use", 0)
         for d in jax.local_devices()])
    return ALSModel(user_factors=uf, item_factors=itf, rank=k,
                    implicit=config.implicit)


def _expand_chunks(buckets, specs):
    """Static in-graph slicing of bucket-level arrays into HBM chunks.

    Runs inside :func:`_train_loop` (slices/pads of device arrays are
    free-ish graph ops); mirrors exactly the chunk layout the build
    program used to emit per-chunk (ops/device_prep.py build_buckets'
    chunk tail) before round 4 moved it here to shrink the prep
    compile.
    """
    if specs is None:
        return buckets  # pre-chunked (host/mesh path)
    out = []
    for arrs, spec in zip(buckets, specs):
        if spec[0] == "dense":
            out.append(arrs)
        elif spec[0] == "plain":
            idx, vals, msk, rid = arrs
            chunks = spec[1]
            if len(chunks) <= 1:
                out.append(arrs)
                continue
            for cs, cn in chunks:
                out.append((idx[cs:cs + cn], vals[cs:cs + cn],
                            msk[cs:cs + cn], rid[cs:cs + cn]))
        else:
            _, pad_to, chunks = spec
            if not chunks:
                out.append(arrs)
                continue
            idx, vals, msk, seg, ent = arrs
            for e0, e1, r0, r1 in chunks:
                n_chunk = e1 - e0
                seg_pad = (-n_chunk) % pad_to
                row_pad = (-(r1 - r0)) % pad_to
                oob = n_chunk + seg_pad  # padding rows → dropped slot
                seg_c = seg[r0:r1]
                seg_c = jnp.where((seg_c >= e0) & (seg_c < e1),
                                  seg_c - e0, oob)

                def padrows(a):
                    return jnp.pad(a, ((0, row_pad),) + ((0, 0),)
                                   * (a.ndim - 1))

                out.append((padrows(idx[r0:r1]), padrows(vals[r0:r1]),
                            padrows(msk[r0:r1]),
                            jnp.pad(seg_c, (0, row_pad), constant_values=oob),
                            jnp.pad(ent[e0:e1], (0, seg_pad),
                                    constant_values=-1)))
    return tuple(out)


def _resolve_loop_statics(config: ALSConfig, user_buckets, item_buckets,
                          chunk_specs=None):
    """The static arguments of :func:`_train_loop` for this config/layout.

    Shared by the training entry point and the compile pre-warm so both
    hit the same jit-cache entry.  With ``chunk_specs``, kinds/flags are
    emitted per EXPANDED chunk in :func:`_expand_chunks` order.
    """
    k = config.rank
    if config.solver not in ("auto", "cholesky", "lu"):
        raise ValueError(
            f"solver={config.solver!r}: expected 'auto', 'cholesky' or "
            "'lu'")
    # Mosaic kernels are opaque custom calls: GSPMD cannot partition them
    # ("Mosaic kernels cannot be automatically partitioned", raised while
    # lowering for a 4-chip v5e mesh), so the compiled kernels are the
    # one-chip path and a multi-device mesh takes the XLA gram + Cholesky
    # twins.  Wrapping the kernels in shard_map over the data axis is the
    # way to lift this.
    sh = getattr(user_buckets[0][1], "sharding", None) if user_buckets \
        else None
    on_tpu = pallas_supported()
    one_chip = on_tpu and (sh is None or len(sh.device_set) == 1)
    if on_tpu and not one_chip and (
            config.use_pallas or config.solver == "lu"):
        raise ValueError(
            f"use_pallas={config.use_pallas!r} / solver={config.solver!r} "
            f"on a {len(sh.device_set)}-device mesh: the compiled Pallas "
            "kernels cannot be partitioned by GSPMD; leave both on auto "
            "(XLA gram + Cholesky) for mesh runs")
    use_pallas = config.use_pallas
    if use_pallas is None:
        # Default ON for one-chip TPU (round 4).  Round-3 measured the
        # einsum path at 250 ms/iter (ML-25M shape): gather+gram 138,
        # solve 32.5, layout copies 47.7, scatter/misc 33.  The copies
        # were XLA relayouting every gathered [R,L,K] block from the
        # gather's K-minor layout to the L-minor layout the gram dots
        # want, and A relayouts feeding the lanes-solve.  The round-4
        # kernels consume/emit natural layouts end to end (gather → fused
        # gram → in-kernel-transposing solve → scatter), which removes
        # those copies (measured 250.4 → 187.8 ms/iter, copy phase
        # 47.7 → 0.5; before PR 1, on another installation).
        # (A scalar-loop gather inside the kernel measured 0.30 G rows/s
        # before PR 1, on another installation; XLA's own reads 0.41-0.46
        # from a small table and 0.08-0.09 from als-netflix-r64's user
        # table, one v5e, PR 29.  Neither is the lever: a row that rated
        # enough of the other side skips the fetch, see the ``dense``
        # kind in _train_loop.)
        use_pallas = one_chip

    def _bucket_pallas(idx) -> bool:
        return use_pallas and fits_vmem(idx.shape[1], k)

    solver = config.solver
    if solver == "auto":
        # The elimination kernel targets the VPU; on CPU meshes the XLA
        # Cholesky is fine and interpret-mode Pallas would be slow.
        # High ranks overflow the kernel's VMEM working set — Cholesky.
        solver = "lu" if one_chip and lanes_solve_fits_vmem(k) \
            else "cholesky"

    def side_meta(buckets, specs):
        kinds, flags = [], []
        for i, b in enumerate(buckets):
            n = 1
            if specs is not None:
                chunks = specs[i][-1]
                n = max(len(chunks), 1)
            kinds.extend([b[0]] * n)
            flags.extend([_bucket_pallas(b[1])] * n)
        return tuple(kinds), tuple(flags)

    uspec = chunk_specs[0] if chunk_specs else None
    ispec = chunk_specs[1] if chunk_specs else None
    uk, upf = side_meta(user_buckets, uspec)
    ik, ipf = side_meta(item_buckets, ispec)
    return dict(
        kinds=(uk, ik),
        pallas_flags=(upf, ipf),
        implicit=config.implicit,
        gram_dtype=_resolve_gram_dtype(config.gram_dtype),
        solver=solver,
        chunk_specs=chunk_specs,
    )


@functools.partial(jax.jit, static_argnames=(
    "kinds", "pallas_flags", "implicit", "gram_dtype", "solver",
    "factor_shardings", "chunk_specs"))
def _train_loop(uf0, itf0, user_buckets, item_buckets, reg, alpha, iterations,
                *, kinds, pallas_flags, implicit, gram_dtype, solver,
                factor_shardings=(None, None), chunk_specs=None):
    # ``iterations`` is a traced scalar on purpose: the fori_loop bound being
    # dynamic means warmup (1 iter) and the real run (N iters) share one
    # compiled program.
    gdt = jnp.dtype(gram_dtype)
    user_buckets = _expand_chunks(
        user_buckets, chunk_specs[0] if chunk_specs else None)
    item_buckets = _expand_chunks(
        item_buckets, chunk_specs[1] if chunk_specs else None)

    def side(buckets, side_kinds, side_pallas, dst, src, src_sharding):
        # yty hoisted: identical for every bucket of the side (full-matrix
        # gram even in windowed mode — GSPMD reduces the sharded rows to
        # one [K,K], which is the cheap direction).
        yty = gram(src) if implicit else jnp.zeros(
            (src.shape[1], src.shape[1]), jnp.float32)
        for kind, use_pallas, arrs in zip(side_kinds, side_pallas, buckets):
            if kind == "dense":
                # Rows dense enough over ``src``: a masked product over
                # the whole table stands in for the gather.
                block, ent, deg = arrs
                a, b = fused_gram_dense(block, src.astype(gdt), alpha,
                                        implicit=implicit,
                                        use_pallas=use_pallas)
                if implicit:
                    a = yty[None, :, :] + a
                dst = _scatter_rows(dst, ent, _ridge(
                    a, b, reg * jnp.maximum(deg, 1.0), solver))
                continue
            if kind.endswith("_w"):
                # windowed chunk: fetch only the factor rows it touches
                *arrs, win = arrs
                bsrc = _window_gather(src, win, src_sharding)
            else:
                bsrc = src
            if kind.startswith("merged"):
                idx, vals, msk, seg, ent = arrs
                dst = _merged_solve(idx, vals, msk, seg, ent, dst, bsrc, yty,
                                    reg, alpha, implicit, use_pallas, gdt,
                                    solver)
            else:
                idx, vals, msk, rid = arrs
                solved = _solve_bucket(idx, vals, msk, bsrc, yty, reg, alpha,
                                       implicit, use_pallas, gdt, solver)
                dst = _scatter_rows(dst, rid, solved)
        return dst

    def constrain(x, s):
        return jax.lax.with_sharding_constraint(x, s) if s is not None else x

    def body(_, carry):
        uf, itf = carry
        uf = constrain(side(user_buckets, kinds[0], pallas_flags[0], uf, itf,
                            factor_shardings[1]),
                       factor_shardings[0])
        itf = constrain(side(item_buckets, kinds[1], pallas_flags[1], itf, uf,
                             factor_shardings[0]),
                        factor_shardings[1])
        return (uf, itf)

    return jax.lax.fori_loop(0, iterations, body, (uf0, itf0))


@jax.jit
def predict_scores(user_factors: jax.Array, item_factors: jax.Array,
                   users: jax.Array, items: jax.Array) -> jax.Array:
    """Pointwise r̂_ui for parallel (user, item) id vectors."""
    return jnp.einsum("bk,bk->b", user_factors[users], item_factors[items],
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("k",))
def _recommend_impl(user_factors, item_factors, user_indices, seen, *, k):
    q = user_factors[user_indices]
    return top_k_scores(q, item_factors, k, exclude=seen)


def recommend(
    model: ALSModel,
    user_indices: jax.Array,          # [B] int
    k: int,
    *,
    seen: Optional[jax.Array] = None,  # [B, n_items] bool — exclude
    chunk: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k items per user (reference: MLlib recommendProducts).

    Gather + score + top-k is ONE jitted dispatch — the serving path's
    latency budget is dominated by per-call round-trips, not FLOPs.
    """
    if chunk:
        q = model.user_factors[user_indices]
        return chunked_top_k(q, model.item_factors, k, chunk=chunk)
    return _recommend_impl(model.user_factors, model.item_factors,
                           user_indices, seen, k=k)


def fold_in(
    item_factors: np.ndarray,     # [I, K] host item factors (frozen)
    item_ids: np.ndarray,         # [d] int — the user's observed items
    ratings: np.ndarray,          # [d] float — ratings / implicit strengths
    *,
    reg: float,
    alpha: float = 1.0,
    implicit: bool = False,
    yty: Optional[np.ndarray] = None,   # [K, K] — required when implicit
) -> np.ndarray:
    """Serve-time ALS fold-in: one ridge solve for an UNSEEN user against
    the frozen item factors (ISSUE 10).

    This is exactly the user-side normal equation the training sweep
    solves (:func:`_gram_pieces` semantics, ALS-WR ``λ·n_u`` ridge;
    implicit = Hu-Koren-Volinsky with the shared ``YᵀY`` term passed in
    by the caller, cached per generation), in host numpy — rank is tens
    and degree is a visitor's recent-event count, so one K×K solve is
    microseconds and the serving path never pays a device dispatch.
    The folded factor is per-process and ephemeral by design: the next
    refresh trains the user in and makes it durable.
    """
    item_ids = np.asarray(item_ids, np.int64)
    r = np.asarray(ratings, np.float64)
    y = np.asarray(item_factors, np.float64)[item_ids]      # [d, K]
    k = y.shape[1]
    if implicit:
        if yty is None:
            raise ValueError("implicit fold_in needs the cached YᵀY")
        w = alpha * np.abs(r)                               # c - 1
        c = (1.0 + w) * (r > 0)
        a = np.asarray(yty, np.float64) + (y * w[:, None]).T @ y
        b = y.T @ c
    else:
        a = y.T @ y
        b = y.T @ r
    n = max(len(item_ids), 1)
    a = a + reg * n * np.eye(k)
    try:
        u = np.linalg.solve(a, b)
    except np.linalg.LinAlgError:                       # singular corner:
        u = np.linalg.lstsq(a, b, rcond=None)[0]        # degenerate events
    return u.astype(np.float32)


def rmse(model: ALSModel, user_ids, item_ids, ratings) -> float:
    """Explicit-feedback fit metric (host-side convenience)."""
    pred = predict_scores(model.user_factors, model.item_factors,
                          jnp.asarray(user_ids), jnp.asarray(item_ids))
    return float(jnp.sqrt(jnp.mean((pred - jnp.asarray(ratings)) ** 2)))
