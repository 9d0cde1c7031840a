"""Pallas TPU kernels for the ALS hot loop.

SURVEY.md §7 flags the ragged→dense gather/gram layout as "likely the one
place a Pallas kernel pays off".  The XLA formulation of the per-entity
normal equations reads the gathered factor block ``F [R, L, K]`` from HBM
twice (once for ``A = Fᵀ·diag(w)·F``, once for ``b = Fᵀ·c``).  The fused
kernel below tiles rows into VMEM once and emits both outputs per pass —
halving HBM traffic on the training hot loop.

Grid: one program per solve row; per-program working set is
``L·K + K² + K`` floats (≤ ~0.6 MB at L=1024, K=128 — well inside VMEM).
Matmuls sit on the MXU via ``dot_general`` with f32 accumulation.

On CPU (tests) the kernels run in interpret mode; the ``fused_*`` /
``pq_scan`` dispatchers take the XLA twin unless Pallas is requested or
the backend is a TPU.  Every kernel here has been compiled by Mosaic
(libtpu 0.0.34, v5e) and checked against its XLA twin by
``chip_smoke.py``; ``tests/test_pallas_kernels.py`` keeps the
jaxpr→Mosaic lowering green on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_gram_vector", "fused_gram_vector_pallas",
           "fused_gram_vector_xla", "pallas_supported",
           "fused_gram_dense", "fused_gram_dense_pallas",
           "fused_gram_dense_xla", "dense_weights", "dense_block_width",
           "dense_row_density", "gather_table_pack", "gram_takes_packed",
           "DENSE_TILE_R",
           "ridge_solve_lu_pallas", "lanes_solve_fits_vmem",
           "fused_topk", "fused_topk_pallas", "fused_topk_tiles",
           "pq_scan", "pq_scan_pallas", "pq_scan_xla"]


def pallas_supported() -> bool:
    """True when the default backend runs the Mosaic-compiled kernels
    (TPU); anywhere else a requested kernel runs in the Pallas
    interpreter (the CPU test path).  A backend that cannot start raises
    here — there is no quiet CPU answer (``backend.resolve_backend``
    logs which of the two modes a process got)."""
    return jax.default_backend() == "tpu"


# Per-program VMEM budget: the double-buffered [TILE_R, L, K] f32 input
# tile plus w/c blocks, both outputs, and Mosaic's stack share ~16 MB —
# fits_vmem budgets the tile at ≤ 2 MB (L·K ≤ 65536 at TILE_R=8).
_VMEM_BUDGET_FLOATS = 1 << 20  # halved again inside fits_vmem


def fits_vmem(l: int, k: int) -> bool:
    """Whether the fused gram kernel can handle a [*, l, k] bucket.

    Since the L-chunked grid (round 4), any bucket length fits — the
    staged tile is at most [TILE_R, _L_CHUNK·64/k, k].  Only the rank
    bounds the working set (the [TILE_R, k, k] f32 accumulator)."""
    del l
    return k <= 256


# Where the dense product beats the gather: the rates, measured on one
# TPU v5e at K = 64 (a gathered row: my chip runs, PR 36, PERF.md §6; a
# source row of the dense kernel: my chip runs, PR 29, PERF.md §5).
# - XLA:TPU keeps a gather's operand in VMEM (memory space 1 of the
#   compiled text) while its PHYSICAL bytes fit what the chip's 128 MiB
#   leave beside 16 MiB of scoped memory: 112 MiB.  A row is laid out in
#   whole 128-lane tiles, so a bf16 row of rank 32, 64 or 128 takes 256 B
#   alike and the step lies at 458,752 rows for all three (229,376 in
#   float32).  8,192 x 512 random rows alone: 2.32-2.63 ns a row from
#   tables of 17,770 to 458,000 rows at K = 64, 11.30-11.50 from 460,000
#   to 1,000,000; 11.18 at K = 32 and 10.75 at K = 128 from 480,189 rows
#   (1.92 from 240,000); in float32 3.20 from 220,000 and 11.12 from
#   240,000.  Compiled for a described v5e (no chip) the operand loses
#   its place between 458,720 and 458,759 rows
#   (tests/test_tpu_compile.py holds the mark to that).
# - ``gather_table_pack``: a table of rank <= 64 wastes half its lanes
#   or more, so the same rows laid ``128 // rank`` to a 128-lane row
#   (``models/als.py::_gather_packed``) lie under the step up to 917,504
#   rows at rank 64: alone 3.80 ns a row at 480,189 rows and 3.91 at
#   900,000 (of which the pass that keeps each row's half is 1.9), 4.36
#   at rank 32, 5.26-5.59 in float32; 12.79 at 1,000,000 rows, past the
#   view's reach, where the table is gathered as it is.  In
#   als-netflix-r64's loop a 128-lane row from VMEM cost 1.85 ns a slot
#   and the pass 0.93; with the merged rows' padding and the sparse gram
#   kernel's 0.6 ns, which a dense row does not pay, a rating at the
#   margin cost 3.5.  Since PR 42 the pass is not made at ranks 64 and
#   32 (``gram_takes_packed``: the sparse gram kernel takes the view's
#   rows and keeps each slot's part in VMEM; every other view and the
#   XLA twin keep the pass): the item side's 31.07M slots a sweep cost
#   1.76 ns in the gathers, all 21 of which the compiler now serves from
#   VMEM, and 0.37 in the kernel (chip runs, PRs 41 and 42), 2.4 a rating at
#   the margin.  The rule keeps 3.5: planned at 2.4 the sweep read 456.9
#   ms against 458.6 (the same runs) for more chunks to lower.
# - From HBM (no view under the step: Amazon 2014's 21M users; rank
#   128) a row cost the loop 11.97 ns (the parent's trace, PR 29).  A
#   table under the step as it is (als-netflix-r64's 17,770 items, every
#   table of ML-25M) 2.17.
# - A source row of the dense kernel costs 0.094 ns in the loop at a row
#   tile of 16 (2,192 rows over 480,189 in 98.2 ms, 57,232 over 17,770 in
#   99.0 ms) and 0.089-0.093 at the row tile of 32 it has now (3,488 rows
#   in 149.3 ms, 23,488 in 38.7 ms: 92 TFLOP/s of the 98.5 an output 64
#   wide leaves of the MXU); alone 0.098-0.104.  The rule keeps 0.094.
_GATHER_FAST_TABLE_BYTES = 112 << 20
_LANES = 128
# ns a gathered row: the table in VMEM as it is, through the packed
# view, in HBM
_GATHER_NS_PER_ROW = (2.2, 3.5, 12.0)
_DENSE_NS_PER_SRC_ROW_K64 = 0.094


def gather_table_pack(n_rows: int, rank: int, itemsize: int) -> Optional[int]:
    """How many factor rows share one row of the table a gather should
    read so that the table lies in the chip's fast memory: 1 = the table
    as it is, ``128 // rank`` = the packed view, None = neither fits (the
    gather then reads the table as it is, at the slow rate)."""
    for pack in (1, _LANES // rank):
        lanes = -(-rank * pack // _LANES) * _LANES
        if pack and -(-n_rows // pack) * lanes * itemsize \
                <= _GATHER_FAST_TABLE_BYTES:
            return pack
    return None


def gram_takes_packed(rank: int, pack: int) -> bool:
    """Whether the sparse gram kernel keeps each slot's part of a packed
    row itself (``fused_gram_vector_pallas(..., part, pack=pack)``): where
    the parts fill the 128 lanes and, transposed, each is whole packed
    bf16 sublane tiles, at the ranks it was compiled and timed for: 64
    and 32.  Every other view (rank 10's twelve parts of 10 lanes) keeps
    the pass XLA makes of it."""
    return pack * rank == _LANES and rank in (32, 64)


def dense_row_density(rank: int, n_src: int) -> float:
    """ρ*: the share of the ``n_src`` source rows a row must have rated
    for the masked product over the whole table to cost less than
    gathering its rows.  The product's cost a source row grows with K²;
    the gather's cost a row follows where the table (in the gram dtype,
    bf16) lies, not the rank.  Past rank 128 the dense kernel's float32
    accumulators do not fit VMEM and no row is dense enough."""
    if rank > 128:
        return float("inf")
    pack = gather_table_pack(n_src, rank, 2)
    as_it_is, packed, from_hbm = _GATHER_NS_PER_ROW
    rate = from_hbm if pack is None else as_it_is if pack == 1 else packed
    return _DENSE_NS_PER_SRC_ROW_K64 * (rank / 64.0) ** 2 / rate


def fused_gram_vector_xla(f: jax.Array, w: jax.Array, c: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
    """Reference path: ``A[r] = Σ_l w[r,l]·f[r,l]⊗f[r,l]``, ``b[r] = Σ_l
    c[r,l]·f[r,l]`` via two einsums (XLA fuses what it can)."""
    a = jnp.einsum("blk,bl,blm->bkm", f, w, f,
                   preferred_element_type=jnp.float32)
    b = jnp.einsum("blk,bl->bk", f, c, preferred_element_type=jnp.float32)
    return a, b


TILE_R = 8     # rows per program — TPU sublane granularity for f32
_L_CHUNK = 1024  # max slots staged per grid step (VMEM tile bound)


def _gram_kernel(f_ref, w_ref, c_ref, *rest, l_real: int, l_chunk: int,
                 pack: int = 1):
    """One (row-tile, L-chunk) grid step of the fused (A, b) build.

    ``f`` arrives in the gather's NATURAL layout and dtype — bf16,
    K-minor — so XLA inserts NO relayout copy between the gather and this
    kernel (round-3's 47 ms/iter copy phase was exactly that relayout).
    The kernel accumulates both outputs in f32 across L-chunks; the final
    chunk of a non-multiple L masks the over-read tail (Pallas pads OOB
    block loads with unspecified values — a NaN there would poison the
    accumulation through 0·NaN).

    ``pack > 1``: ``f`` holds the packed view's rows, ``pack`` factor rows
    side by side in 128 lanes, and ``part_ref`` names the one each slot
    asked for.  The part is kept in VMEM, on the rows the kernel fetches
    anyway, and in TRANSPOSED space: after one ``[LC, 128] → [128, LC]``
    transpose (the MXU's contraction wants one operand transposed
    either way) the parts are blocks of K sublanes and ``part``, ``w``
    and ``c`` are lane vectors, so keeping a part is a select between
    sublane blocks under a mask that broadcasts along sublanes, and no
    per-slot value has to be spread from lanes to sublanes.  A part not
    asked for reaches no arithmetic (it may hold anything, NaN too).
    The products and the order of the sum are the plain body's.
    """
    if pack > 1:
        part_ref, a_ref, b_ref = rest
    else:
        a_ref, b_ref = rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        a_ref[:] = jnp.zeros_like(a_ref)
        b_ref[:] = jnp.zeros_like(b_ref)

    n_chunks = pl.num_programs(1)
    partial_tail = l_real % l_chunk != 0
    k = a_ref.shape[-1]

    def accumulate(masked: bool):
        for r in range(TILE_R):
            f = f_ref[r]                              # [LC, pack·K] bf16
            w = w_ref[r]                              # [LC] f32
            c = c_ref[r]
            if masked:
                # Masks built at their target ranks: Mosaic cannot insert
                # a minor dim on an i1 vector.
                off = j * l_chunk
                valid1 = (jax.lax.broadcasted_iota(
                    jnp.int32, (l_chunk,), 0) + off) < l_real
                valid2 = (jax.lax.broadcasted_iota(
                    jnp.int32, (l_chunk, 1), 0) + off) < l_real
                w = jnp.where(valid1, w, 0.0)
                c = jnp.where(valid1, c, 0.0)
                f = jnp.where(valid2, f, jnp.zeros((), f.dtype))
            if pack > 1:
                ft = f.T                              # [pack·K, LC]
                part = part_ref[r][None, :]           # [1, LC] int32
                fs = ft[:k]                           # [K, LC]
                for h in range(1, pack):
                    fs = jnp.where(part == h, ft[h * k:(h + 1) * k], fs)
                fw = fs * w[None, :].astype(f.dtype)  # VPU
                on = (1,)        # the slots' axis; MXU: [K,L]·[K,L]ᵀ
            else:
                # Reshape to 2-D in f32 BEFORE the dtype cast: Mosaic only
                # supports minor-dim insertion on 32-bit vectors.
                fs = f
                fw = f * w[:, None].astype(f.dtype)   # VPU
                on = (0,)                             # MXU: [K,L]·[L,K]
            a_ref[r] += jax.lax.dot_general(
                fw, fs, dimension_numbers=((on, on), ((), ())),
                preferred_element_type=jnp.float32)
            b_ref[r] += jax.lax.dot_general(          # MXU: [1,L]·(the same)
                c[None, :].astype(f.dtype), fs,
                dimension_numbers=(((1,), on), ((), ())),
                preferred_element_type=jnp.float32)[0]

    if partial_tail:
        @pl.when(j == n_chunks - 1)
        def _tail():
            accumulate(masked=True)

        @pl.when(j < n_chunks - 1)
        def _body():
            accumulate(masked=False)
    else:
        accumulate(masked=False)


@functools.partial(jax.jit, static_argnames=("pack", "interpret"))
def fused_gram_vector_pallas(f: jax.Array, w: jax.Array, c: jax.Array,
                             part: Optional[jax.Array] = None,
                             *, pack: int = 1, interpret: bool = False
                             ) -> Tuple[jax.Array, jax.Array]:
    """Fused (A, b) build — one VMEM pass over the gathered factors.

    Accepts ``f`` in any float dtype (bf16 keeps the gather at its
    measured row-rate AND avoids a materialized f32 convert); rows are
    padded up to the TILE_R sublane granule (padding rows compute garbage
    that is sliced off), L is chunked so any bucket length fits VMEM.

    With ``pack > 1`` (:func:`gram_takes_packed`), ``f [R, L, pack·K]``
    is the packed view's rows as the gather returns them and ``part [R,
    L]`` the part of each that is the row asked for (``index % pack``):
    the kernel keeps it, so no pass over the gathered rows stands
    between the gather and this call.
    """
    r, l, lanes = f.shape
    k = lanes // pack
    operands = [f, w.astype(jnp.float32), c.astype(jnp.float32)]
    if pack > 1:
        operands.append(part.astype(jnp.int32))
    r_pad = (-r) % TILE_R
    if r_pad:
        operands = [jnp.pad(x, ((0, r_pad),) + ((0, 0),) * (x.ndim - 1))
                    for x in operands]
    rp = r + r_pad
    # Chunk length scales inversely with rank to hold the staged tile at
    # ~[TILE_R, 1024, 64]-equivalent bytes; a packed row is 128 real
    # lanes at any rank, the bytes a rank-64 row takes padded.
    lc = min(l, max(128, _L_CHUNK * 64 // max(k, 1)) if pack == 1
             else _L_CHUNK)
    n_chunks = -(-l // lc)
    kernel = functools.partial(_gram_kernel, l_real=l, l_chunk=lc, pack=pack)
    per_slot = pl.BlockSpec((TILE_R, lc), lambda i, j: (i, j))
    a, b = pl.pallas_call(
        kernel,
        grid=(rp // TILE_R, n_chunks),
        in_specs=[pl.BlockSpec((TILE_R, lc, lanes), lambda i, j: (i, j, 0))]
        + [per_slot] * (len(operands) - 1),
        out_specs=[
            pl.BlockSpec((TILE_R, k, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((TILE_R, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, k, k), jnp.float32),
            jax.ShapeDtypeStruct((rp, k), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return a[:r], b[:r]


# ---------------------------------------------------------------------------
# Dense normal equations: a row that rated a large enough share of the
# other side builds ``A = (Xᵀ ∘ w) X``, ``b = c X`` as a masked product
# over the WHOLE factor table ``X [N, K]`` instead of from gathered rows.
# The v5e fetches rows by index at a fixed cost a row whatever does it;
# the MXU streams the table at a fixed cost a SOURCE row whatever the
# row's degree, so above a density ``degree / N`` the product is the
# cheaper fetch (:func:`dense_row_density`).  Same operands as the
# gathered path — table in ``gram_dtype``, ``w·x`` rounded to it, float32
# accumulation — so nothing is approximated; only the order of the sum
# differs.
#
# The row's ratings arrive as one row of a ``[J, N]`` block of
# ``DENSE_BLOCK_DTYPE`` values, NaN where the row has no rating (a real
# rating of 0.0 is a zero, not an absence).  ops/device_prep.py builds the
# block once and only from ratings that are exact in that dtype, so the
# ``w`` and ``c`` derived here are the gathered path's, bit for bit.
# ---------------------------------------------------------------------------

DENSE_BLOCK_DTYPE = jnp.bfloat16
DENSE_TILE_R = 32        # block rows per program: M = 32·K streamed LHS rows
_DENSE_TILE_SRC = 2048   # source rows per grid step (contraction depth)


def dense_src_tile(n_src: int, rank: int = 64) -> int:
    """Source rows one grid step of the dense kernel contracts over: the
    stacked ``[TILE_R·K, TU]`` operand is held at 8 MB of bf16."""
    return min(_DENSE_TILE_SRC * 64 // max(rank, 64), _lane_pad(n_src))


def dense_block_width(n_src: int) -> int:
    """Columns of a dense block over ``n_src`` source rows: padded to the
    kernel's largest source tile (every rank's tile divides it once the
    side is longer than one), so the loop never pads the block itself."""
    tu = dense_src_tile(n_src)
    return -(-n_src // tu) * tu


def dense_weights(vals: jax.Array, alpha, implicit: bool
                  ) -> Tuple[jax.Array, jax.Array]:
    """``(w, c)`` in float32 from block values (NaN = no rating): what
    ``models.als._gram_pieces`` derives from ``values`` and ``mask``."""
    vals = vals.astype(jnp.float32)
    present = vals == vals
    v = jnp.where(present, vals, 0.0)
    if implicit:
        w = alpha * jnp.abs(v)
        return w, (1.0 + w) * (v > 0).astype(jnp.float32)
    return present.astype(jnp.float32), v


def fused_gram_dense_xla(block: jax.Array, x: jax.Array, alpha, *,
                         implicit: bool) -> Tuple[jax.Array, jax.Array]:
    """XLA twin of :func:`fused_gram_dense_pallas` (CPU, tests):
    ``block [J, ≥N]``, ``x [N, K]`` already in the gram dtype.  A row
    tile at a time, so the weighted table it forms is ``[TILE_R, K, N]``
    whatever ``J`` is."""
    n, _ = x.shape
    xt = x.T

    def rows(vals):                                           # [N]
        w, c = dense_weights(vals, alpha, implicit)
        lhs = xt * w[None, :].astype(x.dtype)                 # [K, N]
        return (jnp.dot(lhs, x, preferred_element_type=jnp.float32),
                jnp.dot(c.astype(x.dtype), x,
                        preferred_element_type=jnp.float32))

    return jax.lax.map(rows, block[:, :n], batch_size=DENSE_TILE_R)


def _gram_dense_kernel(alpha_ref, blk_ref, xt_ref, x_ref, a_ref, b_ref,
                       lhs_ref, *, implicit: bool):
    """One (row tile, source tile) step: the tile's ``K`` transposed
    table rows, weighted per block row on the VPU into one stacked
    ``[TILE_R·K, TU]`` operand, then ONE product against the table tile
    — the tile is the MXU's stationary operand for every row of the row
    tile — accumulated in float32 in the output blocks, which stay in
    VMEM across the source axis."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        a_ref[:] = jnp.zeros_like(a_ref)
        b_ref[:] = jnp.zeros_like(b_ref)

    k = xt_ref.shape[0]
    gdt = x_ref.dtype
    w, c = dense_weights(blk_ref[:], alpha_ref[0], implicit)
    xt = xt_ref[:]                                    # [K, TU]
    for r in range(DENSE_TILE_R):
        # Sublane broadcast of the row's weights over the K table rows,
        # rounded to the gram dtype as the gathered kernel rounds ``fw``.
        lhs_ref[r * k:(r + 1) * k, :] = xt * w[r:r + 1, :].astype(gdt)
    x = x_ref[:]                                      # [TU, K]
    a_ref[:] += jnp.dot(lhs_ref[:], x, preferred_element_type=jnp.float32)
    b_ref[:] += jnp.dot(c.astype(gdt), x,
                        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("implicit", "interpret"))
def fused_gram_dense_pallas(block: jax.Array, x: jax.Array, alpha, *,
                            implicit: bool, interpret: bool = False
                            ) -> Tuple[jax.Array, jax.Array]:
    """``A [J,K,K]``, ``b [J,K]`` of ``J`` dense rows over the whole
    table ``x [N, K]`` (already in the gram dtype).

    ``block [J, W]`` with ``W ≥ N``; columns from ``N`` on must be NaN.
    A block that prep padded (``dense_block_width`` columns, a multiple
    of ``DENSE_TILE_R`` rows) is taken as it is; any other is padded
    here with absent slots.  The table is zero-padded to the block's
    width so an absent slot meets a zero, never an over-read."""
    j, width = block.shape
    n, k = x.shape
    tu = dense_src_tile(width, k)
    wp = -(-width // tu) * tu
    jp = -(-j // DENSE_TILE_R) * DENSE_TILE_R
    if (jp, wp) != (j, width):
        block = jnp.pad(block, ((0, jp - j), (0, wp - width)),
                        constant_values=jnp.nan)
    xp = jnp.pad(x, ((0, wp - n), (0, 0)))
    kernel = functools.partial(_gram_dense_kernel, implicit=implicit)
    a, b = pl.pallas_call(
        kernel,
        grid=(jp // DENSE_TILE_R, wp // tu),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((DENSE_TILE_R, tu), lambda i, t: (i, t)),
            pl.BlockSpec((k, tu), lambda i, t: (0, t)),
            pl.BlockSpec((tu, k), lambda i, t: (t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((DENSE_TILE_R * k, k), lambda i, t: (i, 0)),
            pl.BlockSpec((DENSE_TILE_R, k), lambda i, t: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((jp * k, k), jnp.float32),
            jax.ShapeDtypeStruct((jp, k), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((DENSE_TILE_R * k, tu), x.dtype)],
        interpret=interpret,
    )(jnp.asarray(alpha, jnp.float32).reshape(1), block, xp.T, xp)
    return a.reshape(jp, k, k)[:j], b[:j]


def fused_gram_dense(block: jax.Array, x: jax.Array, alpha, *,
                     implicit: bool, use_pallas: bool
                     ) -> Tuple[jax.Array, jax.Array]:
    """Dispatch as the loop decides it: the kernel (compiled on a TPU,
    interpreted elsewhere) or the XLA twin."""
    if use_pallas:
        return fused_gram_dense_pallas(block, x, alpha, implicit=implicit,
                                       interpret=not pallas_supported())
    return fused_gram_dense_xla(block, x, alpha, implicit=implicit)


# ---------------------------------------------------------------------------
# Batched ridge solve by elimination, one system per vector lane.
#
# XLA's batched Cholesky lowers to a K-step while-loop of small dynamic
# slices.  Elimination without pivoting is dense [K, K, 128] VPU work
# with no data-dependent control flow, which is the shape the hardware
# likes.  No pivoting: A + lambda*diag is SPD with lambda > 0 (ALS-WR
# always scales reg by degree >= 1).
# ---------------------------------------------------------------------------

SOLVE_LANES = 128  # systems per program — one per vector lane


def lanes_solve_fits_vmem(k: int) -> bool:
    """Whether the lanes solve's per-program working set fits VMEM.

    The kernel holds the natural [128, k, k] input block (double-buffered)
    plus the lane-major [k, k, 128] scratch, all f32.  Budget ~12 MB of
    the ~16 MB/core keeps headroom; above it (k ≳ 72) callers must take
    the Cholesky path — the kernel would fail to compile where XLA's
    solver still works (round-2 advisor finding).
    """
    return 5 * k * k * SOLVE_LANES * 4 <= 12 * 1024 * 1024


def _load_lane_major(a_ref, b_ref, reg_ref, m_ref, v_ref):
    """In-kernel batch→lane staging: natural [T,K,K]/[T,K] blocks →
    lane-major ``m [K,K,T]`` / ``v [K,1,T]`` VMEM scratch, ridge added.

    Doing the transpose HERE (a 2-D [T, K·K] ↔ [K·K, T] VMEM shuffle)
    instead of host-side removes the [B,K,K] relayout copy + transpose XLA
    emitted between the gram dots and the solve — measured ~20 ms of the
    round-3 iteration at the ML-25M shape.
    """
    t, k, _ = a_ref.shape
    regv = reg_ref[:].reshape(1, 1, t)
    ci = jax.lax.broadcasted_iota(jnp.int32, (1, k, 1), 1)
    # K two-dimensional [T,K]→[K,T] transposes: Mosaic has no 3-D
    # minor-collapsing reshape, but 2-D f32 transposes lower cleanly.
    for r in range(k):
        sl = a_ref[:, pl.ds(r, 1), :].reshape(t, k)
        tr = jnp.transpose(sl, (1, 0)).reshape(1, k, t)
        m_ref[pl.ds(r, 1)] = tr + (ci == r).astype(jnp.float32) * regv
    v_ref[:] = jnp.transpose(b_ref[:], (1, 0)).reshape(k, 1, t)


def _store_lane_major(x_ref, v_ref):
    t, k = x_ref.shape
    x_ref[:] = jnp.transpose(v_ref[:].reshape(k, t), (1, 0))


def _lu_kernel(a_ref, b_ref, reg_ref, x_ref, m_ref, v_ref):
    """Cholesky-free LDU solve for SOLVE_LANES SPD systems per program.

    Layout is the whole trick: systems live on the LANE dimension —
    ``m [K, K, 128]`` holds matrix element (r, c) of system t at
    ``m[r, c, t]``.  Row/column j of all 128 systems are then contiguous
    dynamic sublane slices (``m[pl.ds(j,1)]``, ``m[:, pl.ds(j,1)]``), the
    pivot is a plain [1,1,128] lane vector, and the elimination update is
    a lane-parallel FMA with no one-hot masks materialized.  Because
    every system is confined to its own lane, a boundary block whose
    tail lanes are Pallas OOB padding solves garbage there without
    touching real lanes — the padded x rows are simply never written
    back.

    The elimination SHRINKS in rows and columns together: under the
    pivots of block ``jb`` only the trailing sub-matrix ``m[jb:, jb:]`` is
    rewritten, in 8-row and 8-column (sublane-granule) quanta so every
    slice stays aligned.  Nothing left of column ``jb`` in a trailing row
    is read again by anything (later pivots read columns >= their own
    block, back-substitution reads ``m[0:j, j]``), so at K = 64 a program
    rewrites 13,056 register tiles where rows at their full width took
    18,432: ~K³/3 multiply-subtracts (the full-width rows were ~K³/2),
    twice what a factorisation that also used A's symmetry would do.
    This one does not: the multiplier of row r
    under pivot j is read from COLUMN j (``m[r, j]``), so BOTH triangles
    of A are read and must hold the symmetric matrix, as the gram kernels
    hand it over.  (A form that takes the multiplier from the pivot row,
    rewrites only tiles at or right of a row's diagonal tile, 7,680 a
    program, and reads the upper triangle alone halves the program's time
    again but its loop lowered 11-17 s slower inside the benchmark's
    process in three chip calls of four: PERF.md §6 PR 39.)  Back-substitution runs K cheap
    [1, ·, T] steps on the upper-triangular remainder.  No pivoting:
    A + diag(reg) is SPD (ALS-WR reg ≥ λ).
    """
    k = a_ref.shape[1]
    _load_lane_major(a_ref, b_ref, reg_ref, m_ref, v_ref)
    blk = 8  # sublane granule — update starts stay aligned

    # Forward elimination, block-quantized shrinkage.  Unrolled at BLOCK
    # granularity with a fori_loop over the 8 pivots inside: each pivot's
    # update spans the aligned sub-matrix from its own block down and
    # rightwards (rows above the pivot inside the block are masked out of
    # the multiplier).
    # The fully-unrolled form emitted ~6 Mosaic ops per pivot and cost
    # 0.83 s of kernel lowering PER DISTINCT BATCH SIZE — with ~34 chunk
    # batch sizes in the fused ALS loop that was most of its 37 s
    # lowering wall; this form lowers ~2x faster with execution equal
    # within measurement noise (21-34 ms at 131k systems either way).
    for jb in range(0, k, blk):
        rows = k - jb

        def fwd(j, _):
            inv = 1.0 / m_ref[pl.ds(j, 1), pl.ds(j, 1), :]    # [1,1,T]
            row_n = m_ref[pl.ds(j, 1), pl.ds(jb, rows), :] * inv  # [1,rows,T]
            bj = v_ref[pl.ds(j, 1), :, :] * inv               # [1,1,T]
            col = m_ref[pl.ds(jb, rows), pl.ds(j, 1), :]      # [rows,1,T]
            # Rows <= j inside the block must not change: zero their
            # multiplier (cheap [rows,1,1] iota mask, not a [K,K] mask).
            sub_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, 1, 1), 0)
            col = jnp.where(sub_iota + jb > j, col, 0.0)
            m_ref[pl.ds(jb, rows), pl.ds(jb, rows)] = (
                m_ref[pl.ds(jb, rows), pl.ds(jb, rows)] - col * row_n)
            v_ref[pl.ds(jb, rows)] = v_ref[pl.ds(jb, rows)] - col * bj
            return 0

        jax.lax.fori_loop(jb, min(jb + blk, k), fwd, 0)

    # Back-substitution on the upper triangle (v_ref holds modified b).
    for j in range(k - 1, -1, -1):
        inv = 1.0 / m_ref[pl.ds(j, 1), pl.ds(j, 1), :]
        xj = v_ref[pl.ds(j, 1), :, :] * inv               # [1,1,T]
        v_ref[pl.ds(j, 1)] = xj
        if j:
            col = m_ref[pl.ds(0, j), pl.ds(j, 1), :]      # [j,1,T]
            v_ref[pl.ds(0, j)] = v_ref[pl.ds(0, j)] - col * xj
    _store_lane_major(x_ref, v_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ridge_solve_lu_pallas(a: jax.Array, b: jax.Array, reg: jax.Array,
                          *, interpret: bool = False) -> jax.Array:
    """Batched SPD solve ``(A + diag(reg)) x = b`` via shrinking
    elimination — [B,K,K],[B,K],[B]→[B,K].  ``A`` is read whole: both
    triangles must hold the symmetric matrix.

    Inputs stay in their NATURAL layouts — the lane-major staging happens
    inside the kernel, so no relayout copies are emitted between the gram
    build, this solve, and the factor scatter.  A non-multiple-of-128
    batch rides Pallas's auto-padded boundary block (lane-isolated
    systems make the padding harmless).
    """
    bt, k = b.shape
    return pl.pallas_call(
        _lu_kernel,
        grid=(-(-bt // SOLVE_LANES),),
        in_specs=[
            pl.BlockSpec((SOLVE_LANES, k, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((SOLVE_LANES, k), lambda i: (i, 0)),
            pl.BlockSpec((1, SOLVE_LANES), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((SOLVE_LANES, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bt, k), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, k, SOLVE_LANES), jnp.float32),
                        pltpu.VMEM((k, 1, SOLVE_LANES), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32),
      reg.astype(jnp.float32).reshape(1, bt))


def fused_gram_vector(f: jax.Array, w: jax.Array, c: jax.Array,
                      *, use_pallas: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Dispatch: Pallas on TPU, einsum elsewhere (or force via flag)."""
    if use_pallas is None:
        use_pallas = pallas_supported()
    if use_pallas:
        return fused_gram_vector_pallas(f, w, c,
                                        interpret=not pallas_supported())
    return fused_gram_vector_xla(f, w, c)


# ---------------------------------------------------------------------------
# Fused corpus-score + running top-K (ISSUE 8: million-item retrieval).
#
# The XLA retrieval path (ops.topk) either materializes the full [B, N]
# score block (top_k_scores) or scans [B, chunk] slabs through HBM
# (chunked_top_k).  This kernel streams corpus tiles into VMEM, scores a
# tile on the MXU, and folds it into a running top-K held in VMEM — the
# [B, N] scores never exist anywhere, and HBM traffic is one read of the
# corpus plus O(B·k) output.  The fold is built ONLY from Mosaic-supported
# primitives (axis reductions, where, broadcasted_iota, a lane roll,
# whole-block stores) — no in-kernel sort/top_k dependence — and costs
# what the tile's scores can change: every tile pays one compare of its
# [B, T] scores against each row's running k-th best and a count of the
# winners; only a tile that holds one pays selection rounds, one per
# candidate of its worst row (≤ k), each a few passes over [B, T] and
# [B, kp].  Over a corpus in no score order a row's top-k changes in about
# k·ln(N/T) tiles, so the rounds summed over a scan are a small fraction
# of the tiles (pio_topk_fold_rounds_per_tile) and the kernel's time is
# the tile's DMA (B ≤ 64 or so) or its HIGHEST matmul (B = 256); a corpus
# stored in ascending score order is the worst input, k rounds a tile.
# ---------------------------------------------------------------------------

_TOPK_TILE = 1024        # corpus rows per grid step (lane-aligned)
_TOPK_NEG_INF = -3.4e38  # matches ops.topk.NEG_INF
_LANES = 128


def _lane_pad(k: int) -> int:
    return -(-k // _LANES) * _LANES


def fused_topk_tiles(n: int, tile: int = _TOPK_TILE) -> int:
    """Corpus tiles one ``fused_topk_pallas`` call scans over ``n`` rows:
    its grid, and what its round count is read against."""
    return -(-n // tile)


def _fold_tile_topk(s, j, out_s_ref, out_i_ref, rounds_ref, m_ref, *,
                    tile: int, k: int, n_real: int):
    """Fold one tile's scores ``s [B, T]`` into the running top-k, as far
    as the scores can change it.

    ``out_*_ref`` are [B, kp] blocks (kp = k rounded up to a lane
    multiple) that persist across the sequential TPU grid: lanes < k hold
    the running best SORTED descending (equal scores in ascending id),
    lanes ≥ k stay NEG_INF forever.  A row's threshold is therefore lane
    k-1: NEG_INF until the row has seen k real items, its k-th best
    after.  Only a real score (global id < ``n_real``; tail tiles read an
    OOB-padded block whose garbage columns fail that test) STRICTLY over
    the threshold is a candidate: a tile with none ends at the test, with
    no store and no round.  Otherwise the candidates alone are staged in
    ``m_ref [B, T]`` and the tile runs as many insertion rounds as its
    worst row has candidates, at most k.  A round takes each row's best
    remaining candidate (lowest column among equals) and, where it still
    beats the row's threshold, inserts it behind every running score ≥
    it: the lanes behind shift by one (``pltpu.roll``) and the old k-th
    falls off.  A row with no candidate left is rewritten as it was.
    Strictly-over is what keeps an all-equal row (the zero pad rows of a
    cohort) from entering every tile, and what keeps the earlier id when
    scores tie.  Every store is a whole lane-aligned block picked by an
    iota-select (Mosaic refuses an unaligned or traced lane index on a
    store).  ``rounds_ref`` (SMEM [1, 1]) counts the rounds run.
    """
    b = s.shape[0]
    kp = out_s_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        out_s_ref[:] = jnp.full_like(out_s_ref, _TOPK_NEG_INF)
        out_i_ref[:] = jnp.zeros_like(out_i_ref)
        rounds_ref[0, 0] = 0

    cols = jax.lax.broadcasted_iota(jnp.int32, (b, tile), 1)
    cand = (s > out_s_ref[:, k - 1:k]) & (cols < n_real - j * tile)
    worst = jnp.max(jnp.sum(cand.astype(jnp.int32), axis=1, keepdims=True))
    trip = jnp.minimum(worst, k)
    rounds_ref[0, 0] += trip

    @pl.when(trip > 0)
    def _enter():
        m_ref[:] = jnp.where(cand, s, _TOPK_NEG_INF)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (b, kp), 1)

        def insert(_, carry):
            m = m_ref[:]
            v = jnp.max(m, axis=1, keepdims=True)            # [B, 1]
            col = jnp.min(jnp.where(m == v, cols, tile),
                          axis=1, keepdims=True)
            m_ref[:] = jnp.where(cols == col, _TOPK_NEG_INF, m)
            run_s, run_i = out_s_ref[:], out_i_ref[:]
            # Slot = how many running scores stay ahead; kp (past every
            # lane: nothing moves) for a row whose best no longer enters.
            slot = jnp.where(
                v > out_s_ref[:, k - 1:k],
                jnp.sum((run_s >= v).astype(jnp.int32), axis=1,
                        keepdims=True), kp)
            keep = lanes < slot
            if k < kp:
                keep |= lanes >= k
            here = lanes == slot
            out_s_ref[:] = jnp.where(keep, run_s, jnp.where(
                here, v, pltpu.roll(run_s, 1, 1)))
            out_i_ref[:] = jnp.where(keep, run_i, jnp.where(
                here, j * tile + col, pltpu.roll(run_i, 1, 1)))
            return carry

        jax.lax.fori_loop(0, trip, insert, 0)


def _running_topk_call(kernel, grid: int, in_specs, bp: int, k: int,
                       tile: int, interpret: bool):
    """The shared pallas_call scaffolding of the two running-top-k
    kernels: [bp, kp] f32/int32 outputs revisited by every grid step, the
    [1, 1] round counter in SMEM, [bp, tile] candidate scratch."""
    kp = _lane_pad(k)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bp, kp), lambda j: (0, 0)),
            pl.BlockSpec((bp, kp), lambda j: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, kp), jnp.float32),
            jax.ShapeDtypeStruct((bp, kp), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bp, tile), jnp.float32)],
        interpret=interpret,
    )


def _topk_kernel(q_ref, items_ref, out_s_ref, out_i_ref, rounds_ref, m_ref,
                 *, tile: int, k: int, n_real: int):
    """One corpus tile scored on the MXU and folded into the running
    top-k (:func:`_fold_tile_topk`)."""
    # HIGHEST: Mosaic's default, like XLA:TPU's, rounds f32 operands to
    # bfloat16 (1.6e-3 relative on a v5e) and the exact rungs promise
    # float32 scores (ops.topk.SCORE_PRECISION).
    s = jax.lax.dot_general(                     # MXU: [B,D]·[T,D]ᵀ
        q_ref[:], items_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    _fold_tile_topk(s, pl.program_id(0), out_s_ref, out_i_ref, rounds_ref,
                    m_ref, tile=tile, k=k, n_real=n_real)


@functools.partial(jax.jit,
                   static_argnames=("k", "tile", "n_valid", "interpret"))
def fused_topk_pallas(queries: jax.Array, items: jax.Array, k: int, *,
                      tile: int = _TOPK_TILE,
                      n_valid: Optional[int] = None,
                      interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scores+ids of the top-k items per query — [B,D]·[N,D]ᵀ without
    ever materializing the [B, N] score block.

    Returns ([B, k] f32, [B, k] int32, int32 scalar): scores sorted
    descending, their ids, and the selection rounds the scan ran (see
    :func:`_fold_tile_topk`; at most k per tile).  ``n_valid`` masks
    trailing corpus-padding rows.  An equal score that arrives later
    never displaces an earlier one, so exactly-equal scores come out in
    ascending id, ``lax.top_k``'s order — on equal SCORES; callers still
    compare id SETS, not sequences, across rungs, whose matmuls round
    differently.
    """
    b, d = queries.shape
    n = items.shape[0]
    assert 1 <= k <= n, f"k={k} outside [1, {n}]"
    n_real = n if n_valid is None else min(n_valid, n)
    b_pad = (-b) % TILE_R
    if b_pad:
        queries = jnp.pad(queries, ((0, b_pad), (0, 0)))
    bp = b + b_pad
    kernel = functools.partial(_topk_kernel, tile=tile, k=k, n_real=n_real)
    out_s, out_i, rounds = _running_topk_call(
        kernel, fused_topk_tiles(n, tile),
        [pl.BlockSpec((bp, d), lambda j: (0, 0)),
         pl.BlockSpec((tile, d), lambda j: (j, 0))],
        bp, k, tile, interpret,
    )(queries.astype(jnp.float32), items.astype(jnp.float32))
    return out_s[:b, :k], out_i[:b, :k], rounds[0, 0]


def fused_topk(queries: jax.Array, items: jax.Array, k: int, *,
               n_valid: Optional[int] = None,
               use_pallas: Optional[bool] = None,
               chunk: Optional[int] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Dispatch: fused Pallas kernel on TPU, XLA top-k elsewhere.

    The XLA fallback rides :func:`ops.topk.chunked_top_k` (which folds
    small corpora into one ``top_k_scores`` dispatch), so callers get
    bounded score-block memory either way.  ``chunk`` sizes the
    fallback's scan slab only — the Pallas kernel's VMEM tile is fixed.
    """
    from predictionio_tpu.ops.topk import chunked_top_k

    b = queries.shape[0]
    if k <= 0:
        return (jnp.zeros((b, 0), jnp.float32),
                jnp.zeros((b, 0), jnp.int32))
    k = min(k, items.shape[0])
    if use_pallas is None:
        use_pallas = pallas_supported()
    if use_pallas:
        return fused_topk_pallas(queries, items, k, n_valid=n_valid,
                                 interpret=not pallas_supported())[:2]
    if chunk:
        return chunked_top_k(queries, items, k, chunk=chunk,
                             n_valid=n_valid)
    return chunked_top_k(queries, items, k, n_valid=n_valid)


# ---------------------------------------------------------------------------
# Asymmetric PQ LUT scan + running top-K (ISSUE 13: quantized corpora).
#
# The quantized corpus is a packed [S, N] uint8 code matrix (S = coarse
# table + M residual subspaces); a query's per-table distance LUTs
# ([B, S, 256] f32) are computed ONCE per dispatch and held whole in
# VMEM.  Each grid step stages one code tile, expands table t's codes to
# a one-hot [256, T] block and accumulates lut_t · one_hot on the MXU —
# a [B, 256]×[256, T] matmul per table, which is exactly the gather
# "lut[t, code]" expressed as the small-integer arithmetic the MXU eats
# (Mosaic has no vector gather; the one-hot contraction is the
# supported spelling).  Tile scores fold into the running top-K through
# fused_topk's own fold, gated on the running k-th best in the same way
# (_fold_tile_topk) — the [B, N] score block never
# materializes, and HBM traffic is ONE read of the (1+M)-byte-per-item
# codes instead of 4·D bytes of fp32 corpus.
# ---------------------------------------------------------------------------

_PQ_TILE = 512  # code rows per grid step (lane-aligned)


def _pq_scan_kernel(luts_ref, codes_ref, out_s_ref, out_i_ref, rounds_ref,
                    m_ref, *, tile: int, k: int, n_real: int,
                    n_tables: int):
    """One code tile LUT-scored and folded into the running top-k.

    ``luts_ref`` is the flattened [B, S·256] table stack (lane slices
    ``pl.ds(t·256, 256)`` address table t); ``codes_ref`` the [S, T]
    uint8 tile.  Tail tiles read OOB-padded garbage codes — their
    columns are masked in :func:`_fold_tile_topk`.
    """
    b = luts_ref.shape[0]
    codes = codes_ref[:].astype(jnp.int32)               # [S, T]
    cc = jax.lax.broadcasted_iota(jnp.int32, (256, tile), 0)
    s = jnp.zeros((b, tile), jnp.float32)
    for t in range(n_tables):
        # One-hot of table t's codes: [256, T] with a single 1 per lane.
        oh = (codes[t:t + 1, :] == cc).astype(jnp.float32)
        s = s + jax.lax.dot_general(                     # MXU
            luts_ref[:, pl.ds(t * 256, 256)], oh,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    _fold_tile_topk(s, pl.program_id(0), out_s_ref, out_i_ref, rounds_ref,
                    m_ref, tile=tile, k=k, n_real=n_real)


@functools.partial(jax.jit,
                   static_argnames=("k", "tile", "n_valid", "interpret"))
def pq_scan_pallas(luts: jax.Array, codes: jax.Array, k: int, *,
                   tile: int = _PQ_TILE, n_valid: Optional[int] = None,
                   interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """Top-k LUT scores over a packed code matrix — [B, S, 256] tables ×
    [S, N] uint8 codes without ever materializing the [B, N] block.

    Returns ([B, k] f32, [B, k] int32) sorted descending; ``n_valid``
    masks trailing padding columns.  Same fold as ``fused_topk_pallas``
    (:func:`_fold_tile_topk`), so the same cost and tie order: equal
    scores in ascending id.
    """
    b, s, width = luts.shape
    assert width == 256, f"LUT width {width} != 256"
    assert codes.shape[0] == s, (codes.shape, s)
    n = codes.shape[1]
    assert 1 <= k <= n, f"k={k} outside [1, {n}]"
    n_real = n if n_valid is None else min(n_valid, n)
    b_pad = (-b) % TILE_R
    if b_pad:
        luts = jnp.pad(luts, ((0, b_pad), (0, 0), (0, 0)))
    bp = b + b_pad
    kernel = functools.partial(_pq_scan_kernel, tile=tile, k=k,
                               n_real=n_real, n_tables=s)
    out_s, out_i, _ = _running_topk_call(
        kernel, -(-n // tile),
        [pl.BlockSpec((bp, s * 256), lambda j: (0, 0)),
         pl.BlockSpec((s, tile), lambda j: (0, j))],
        bp, k, tile, interpret,
    )(luts.astype(jnp.float32).reshape(bp, s * 256), codes)
    return out_s[:b, :k], out_i[:b, :k]


def pq_scan_xla(luts: jax.Array, codes: jax.Array, k: int, *,
                chunk: int = 262_144, n_valid: Optional[int] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """XLA gather fallback: `lax.scan` over code chunks, per-table
    ``jnp.take`` into the LUTs, running top-k merge — bounded [B, chunk]
    score memory, any N (clamped overlapping tail window, masked
    re-reads, same trick as ``ops.topk.chunked_top_k``)."""
    s, n = codes.shape
    b = luts.shape[0]
    limit = n if n_valid is None else min(n_valid, n)

    def score(cslab):                                    # [S, C] uint8
        ci = cslab.astype(jnp.int32)
        acc = jnp.take(luts[:, 0, :], ci[0], axis=1)
        for t in range(1, s):
            acc = acc + jnp.take(luts[:, t, :], ci[t], axis=1)
        return acc                                       # [B, C]

    if n <= chunk:
        sc = score(codes)
        if limit < n:
            pad = (jnp.arange(n, dtype=jnp.int32) >= limit)[None, :]
            sc = jnp.where(pad, _TOPK_NEG_INF, sc)
        return jax.lax.top_k(sc, k)
    steps = -(-n // chunk)
    init = (jnp.full((b, k), _TOPK_NEG_INF, dtype=jnp.float32),
            jnp.zeros((b, k), dtype=jnp.int32))

    def step(carry, nominal):
        best_s, best_i = carry
        start = jnp.minimum(nominal, n - chunk)
        cslab = jax.lax.dynamic_slice(codes, (0, start), (s, chunk))
        sc = score(cslab)
        ids = start + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        invalid = (ids < nominal) | (ids >= limit)
        sc = jnp.where(invalid, _TOPK_NEG_INF, sc)
        merged_s = jnp.concatenate([best_s, sc], axis=1)
        merged_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, sc.shape)], axis=1)
        top_s, pos = jax.lax.top_k(merged_s, k)
        return (top_s, jnp.take_along_axis(merged_i, pos, axis=1)), None

    starts = jnp.arange(steps, dtype=jnp.int32) * chunk
    (best_s, best_i), _ = jax.lax.scan(step, init, starts)
    return best_s, best_i


def pq_scan(luts: jax.Array, codes: jax.Array, k: int, *,
            n_valid: Optional[int] = None,
            use_pallas: Optional[bool] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Dispatch: fused Pallas LUT kernel on TPU, chunked XLA gather scan
    elsewhere — bounded score memory either way."""
    b = luts.shape[0]
    n = codes.shape[1]
    if k <= 0:
        return (jnp.zeros((b, 0), jnp.float32),
                jnp.zeros((b, 0), jnp.int32))
    k = min(k, n)
    if use_pallas is None:
        use_pallas = pallas_supported()
    if use_pallas:
        return pq_scan_pallas(luts, codes, k, n_valid=n_valid,
                              interpret=not pallas_supported())
    return pq_scan_xla(luts, codes, k, n_valid=n_valid)
