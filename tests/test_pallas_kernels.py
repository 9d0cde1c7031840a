"""Pallas fused gram kernel == einsum oracle (interpret mode on CPU)."""

import numpy as np
import pytest
import jax.numpy as jnp

from predictionio_tpu.ops.pallas_kernels import (
    fused_gram_vector,
    fused_gram_vector_pallas,
    fused_gram_vector_xla,
)


def _inputs(seed=0, r=6, l=16, k=8):
    rng = np.random.default_rng(seed)
    f = rng.standard_normal((r, l, k)).astype(np.float32)
    w = np.abs(rng.standard_normal((r, l))).astype(np.float32)
    c = rng.standard_normal((r, l)).astype(np.float32)
    return jnp.asarray(f), jnp.asarray(w), jnp.asarray(c)


def test_pallas_matches_einsum():
    f, w, c = _inputs()
    a1, b1 = fused_gram_vector_xla(f, w, c)
    a2, b2 = fused_gram_vector_pallas(f, w, c, interpret=True)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b2),
                               rtol=1e-5, atol=1e-5)


def test_matches_numpy_oracle():
    f, w, c = _inputs(seed=1, r=3, l=5, k=4)
    a, b = fused_gram_vector_pallas(f, w, c, interpret=True)
    fn, wn, cn = map(np.asarray, (f, w, c))
    for r in range(3):
        expect_a = (fn[r] * wn[r][:, None]).T @ fn[r]
        expect_b = fn[r].T @ cn[r]
        np.testing.assert_allclose(np.asarray(a[r]), expect_a, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(b[r]), expect_b, rtol=1e-5)


def test_dispatcher_cpu_path():
    f, w, c = _inputs(seed=2)
    a, b = fused_gram_vector(f, w, c)  # auto: einsum on CPU
    a2, b2 = fused_gram_vector_xla(f, w, c)
    np.testing.assert_allclose(np.asarray(a), np.asarray(a2), rtol=1e-6)


def test_lu_solver_in_train_als():
    """solver="lu" end-to-end (interpret) == cholesky path."""
    from predictionio_tpu.models.als import ALSConfig, train_als

    rng = np.random.default_rng(5)
    users = rng.integers(0, 12, 60)
    items = rng.integers(0, 9, 60)
    ratings = rng.integers(1, 6, 60).astype(np.float32)
    base = dict(rank=4, iterations=2, reg=0.1, seed=2, gram_dtype="float32")
    m_ch = train_als(users, items, ratings, 12, 9,
                     ALSConfig(**base, solver="cholesky"))
    m_lu = train_als(users, items, ratings, 12, 9,
                     ALSConfig(**base, solver="lu"))
    np.testing.assert_allclose(np.asarray(m_ch.user_factors),
                               np.asarray(m_lu.user_factors),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("solver", ["gj", "LU", "lu ", ""])
def test_unknown_solver_is_refused(solver):
    """Anything but auto / cholesky / lu used to train with Cholesky
    without a word."""
    from predictionio_tpu.models.als import ALSConfig, train_als

    with pytest.raises(ValueError, match="'auto', 'cholesky' or 'lu'"):
        train_als(np.array([0, 1]), np.array([0, 1]),
                  np.array([1.0, 2.0], np.float32), 2, 2,
                  ALSConfig(rank=2, iterations=1, solver=solver))


# (B, K, rows of the factor whose Gram matrix is A, added to A's diagonal,
#  least ridge[, width of the ridge's range]): A = y^T y + diag * I, reg
#  uniform in [least, least + width), width 1 unless given.
_LU_CASES = {
    "b67_k32": (67, 32, 32, 2.0, 0.1),
    # the retired Gauss-Jordan kernel's case: a Gram matrix of K + 3 rows
    "b5_k8_gram": (5, 8, 11, 0.0, 0.5),
    "k1": (3, 1, 4, 0.0, 0.1),
    # one system past a full block of 128 lanes, at the chip's rank
    "b129_k64": (129, 64, 64, 2.0, 0.1),
    # no ridge at all: the elimination alone on a well-conditioned system
    "reg0": (9, 16, 64, 1.0, 0.0),
    # the templates' default rank: one whole granule of 8 and a last block
    # of 2 rows and 2 columns
    "k10": (33, 10, 12, 0.0, 0.1),
    "k20": (17, 20, 25, 0.0, 0.1),
    # the last rank lanes_solve_fits_vmem admits
    "k70": (5, 70, 80, 0.0, 0.1),
    # two full blocks of 128 lanes and one system
    "b257_k64": (257, 64, 64, 2.0, 0.1),
    # the retrain cell's lightest users under ALS-WR: ONE rating, so A is
    # one row's outer product plus 0.065 on the diagonal, the
    # worst-conditioned system the sweep solves
    "b16_k64_deg1": (16, 64, 1, 0.0, 0.065, 0.0),
}


def _lu_case(case):
    B, K, rows, diag, least, width = (*_LU_CASES[case], 1.0)[:6]
    rng = np.random.default_rng(3)
    y = rng.standard_normal((B, rows, K)).astype(np.float32)
    A = np.einsum("blk,blm->bkm", y, y) + diag * np.eye(K, dtype=np.float32)
    b = rng.standard_normal((B, K)).astype(np.float32)
    reg = ((least + width * rng.random(B)).astype(np.float32) if least
           else np.zeros(B, np.float32))
    ref = np.stack([np.linalg.solve(A[i].astype(np.float64)
                                    + np.float64(reg[i]) * np.eye(K), b[i])
                    for i in range(B)])
    return A, b, reg, ref


@pytest.mark.parametrize("case", sorted(_LU_CASES))
def test_ridge_solve_lu_matches_oracle(case):
    """Shrinking-elimination solver (the TPU auto path) vs numpy."""
    from predictionio_tpu.ops.pallas_kernels import ridge_solve_lu_pallas

    A, b, reg, ref = _lu_case(case)
    x = np.asarray(ridge_solve_lu_pallas(
        jnp.asarray(A), jnp.asarray(b), jnp.asarray(reg), interpret=True))
    np.testing.assert_allclose(x, ref, rtol=2e-4, atol=2e-4)


# -- fused corpus-score + running top-K (ISSUE 8) ----------------------------


def _topk_inputs(b=3, n=700, d=16, seed=4):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, d)).astype(np.float32)
    items = rng.standard_normal((n, d)).astype(np.float32)
    return q, items


def _oracle_ids(q, items, k):
    return np.argsort(-(q @ items.T), axis=1, kind="stable")[:, :k]


def test_fused_topk_kernel_matches_oracle():
    """Interpret-mode kernel vs numpy: same id SET and sorted scores
    (tie order may differ from lax.top_k — documented contract)."""
    from predictionio_tpu.ops.pallas_kernels import fused_topk_pallas

    q, items = _topk_inputs()
    s, i, _ = fused_topk_pallas(jnp.asarray(q), jnp.asarray(items), 10,
                                tile=256, interpret=True)
    s, i = np.asarray(s), np.asarray(i)
    want = _oracle_ids(q, items, 10)
    np.testing.assert_array_equal(np.sort(i, axis=1),
                                  np.sort(want, axis=1))
    np.testing.assert_allclose(
        s, np.take_along_axis(q @ items.T, want, axis=1), rtol=1e-5)
    assert (np.diff(s, axis=1) <= 1e-6).all()  # sorted descending


def test_fused_topk_kernel_tail_tile_and_n_valid():
    """A corpus that does not divide the tile reads an OOB-padded tail
    block; n_valid additionally masks trailing padding rows — neither
    may ever win a slot."""
    from predictionio_tpu.ops.pallas_kernels import fused_topk_pallas

    q, items = _topk_inputs(n=600)
    items[500:] = 50.0  # poison rows past n_valid
    s, i, _ = fused_topk_pallas(jnp.asarray(q), jnp.asarray(items), 8,
                                tile=256, n_valid=500, interpret=True)
    i = np.asarray(i)
    assert int(i.max()) < 500
    want = _oracle_ids(q, items[:500], 8)
    np.testing.assert_array_equal(np.sort(i, axis=1),
                                  np.sort(want, axis=1))


# The fold is gated on each row's running k-th best (ISSUE 26): a tile
# costs selection rounds only as far as its scores can enter.  The third
# return is the rounds the scan ran.

_GATE_TILE = 256
_GATE_N = 5 * _GATE_TILE - 37          # five tiles, the last one ragged


def _gate_corpus(order, b, seed=11):
    q, items = _topk_inputs(b=b, n=_GATE_N, seed=seed)
    first = items @ q[0]
    if order == "ascending":            # every tile beats row 0's k-th
        items = items[np.argsort(first, kind="stable")]
    elif order == "descending":         # only the first tile does
        items = items[np.argsort(-first, kind="stable")]
    return q, np.ascontiguousarray(items)


def _gated(q, items, k, **kw):
    from predictionio_tpu.ops.pallas_kernels import fused_topk_pallas

    s, i, rounds = fused_topk_pallas(jnp.asarray(q), jnp.asarray(items), k,
                                     tile=_GATE_TILE, interpret=True, **kw)
    return np.asarray(s), np.asarray(i), int(rounds)


@pytest.mark.parametrize("b", [1, 8, 24])
@pytest.mark.parametrize("k", [1, 10, 128, 200])
@pytest.mark.parametrize("order", ["random", "ascending", "descending"])
def test_gated_fold_matches_oracle(order, k, b):
    q, items = _gate_corpus(order, b)
    s, i, rounds = _gated(q, items, k)
    scores = q @ items.T
    want = _oracle_ids(q, items, k)
    np.testing.assert_array_equal(np.sort(i, axis=1), np.sort(want, axis=1))
    np.testing.assert_allclose(
        s, np.take_along_axis(scores, want, axis=1), rtol=1e-5, atol=1e-6)
    assert (np.diff(s, axis=1) <= 0).all()          # sorted descending
    tiles = -(-_GATE_N // _GATE_TILE)
    # The first tile fills k slots; no tile ever runs more than k rounds.
    assert k <= rounds <= k * tiles
    if b == 1 and order == "descending":
        assert rounds == k                          # k / tiles a tile
    if b == 1 and order == "ascending":
        assert rounds == k * tiles                  # the worst input


@pytest.mark.parametrize("n_valid", [_GATE_N - 1, 3 * _GATE_TILE + 5,
                                     2 * _GATE_TILE, 40])
def test_gated_fold_masks_what_lies_past_n_valid(n_valid):
    """Rows past n_valid (a ragged tail, a cut inside a tile, whole
    tiles) are never candidates: they win no slot and add no round."""
    q, items = _gate_corpus("random", 4)
    items[n_valid:] = 50.0                          # poison
    s, i, rounds = _gated(q, items, 8, n_valid=n_valid)
    assert int(i.max()) < n_valid
    np.testing.assert_array_equal(
        np.sort(i, axis=1), np.sort(_oracle_ids(q, items[:n_valid], 8), 1))
    _, _, clean = _gated(q, np.ascontiguousarray(items[:n_valid]), 8)
    assert rounds == clean


@pytest.mark.parametrize("k", [1, 10, 128])
def test_gated_fold_keeps_the_earlier_id_among_equal_scores(k):
    """Duplicated rows whose equal scores straddle tiles: the score
    multiset is the oracle's, every id is a real holder of its score,
    none repeats, and a tie is settled for the lower id (lax.top_k's
    order, which a stable argsort gives the oracle)."""
    q, items = _gate_corpus("random", 3, seed=5)
    items[_GATE_TILE:2 * _GATE_TILE] = items[:_GATE_TILE]
    items[3 * _GATE_TILE + 7:3 * _GATE_TILE + 107] = items[50:150]
    s, i, _ = _gated(q, items, k)
    scores = q @ items.T
    want = _oracle_ids(q, items, k)
    np.testing.assert_allclose(
        s, np.take_along_axis(scores, want, axis=1), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.take_along_axis(scores, i, axis=1), s, rtol=1e-5, atol=1e-6)
    assert all(len(set(r)) == k for r in i.tolist())
    np.testing.assert_array_equal(i, want)


@pytest.mark.parametrize("real, pad", [(1, 7), (5, 3), (9, 7)])
def test_gated_fold_zero_pad_rows_add_no_round(real, pad):
    """The all-zero rows a cohort is padded with score 0.0 everywhere:
    under a strict compare they enter in the first tile only, return k
    zeros, and the scan runs the rounds of the real rows alone."""
    q, items = _gate_corpus("random", real, seed=7)
    padded = np.concatenate([q, np.zeros((pad, q.shape[1]), np.float32)])
    s, i, rounds = _gated(padded, items, 10)
    s_real, i_real, rounds_real = _gated(q, items, 10)
    np.testing.assert_array_equal(s[:real], s_real)
    np.testing.assert_array_equal(i[:real], i_real)
    assert (s[real:] == 0.0).all()
    np.testing.assert_array_equal(i[real:], np.tile(np.arange(10), (pad, 1)))
    assert rounds == rounds_real


def test_fused_topk_dispatcher_cpu_falls_back_to_chunked():
    from predictionio_tpu.ops.pallas_kernels import fused_topk

    q, items = _topk_inputs(n=300)
    s, i = fused_topk(jnp.asarray(q), jnp.asarray(items), 7)
    want = _oracle_ids(q, items, 7)
    np.testing.assert_array_equal(np.sort(np.asarray(i), axis=1),
                                  np.sort(want, axis=1))
    # k=0 / k>n edge behavior mirrors the facade contract
    s0, i0 = fused_topk(jnp.asarray(q), jnp.asarray(items), 0)
    assert s0.shape == (3, 0) and i0.shape == (3, 0)


# ---------------------------------------------------------------------------
# The packed view's rows straight into the sparse gram kernel: 128-lane
# rows and each slot's part, the part kept inside the kernel, against the
# same rows with the part kept by XLA before it (``_gather_rows``).
# ---------------------------------------------------------------------------

_EDGE = "edge"      # a slot list that visits the wrap, the clamp, the pad


@pytest.mark.parametrize("rank,n_rows,r,l,neighbour", [
    (64, 600, 8, 2048, None),      # two whole L-chunks of 1,024
    (64, 600, 8, 1100, None),      # a ragged last chunk (masked tail)
    (64, 601, 5, 24, None),        # R no multiple of TILE_R, an odd table
    (64, 601, 3, 16, _EDGE),       # indices 0, n-1, negative, past the end
    (64, 601, 8, 1100, 1e30),      # the other half huge: it reaches no sum
    (64, 601, 4, 40, float("nan")),    # ... nor does a NaN there
    (32, 603, 5, 1100, _EDGE),     # four parts a row, three pad rows
    (32, 603, 8, 136, float("nan")),
], ids=["whole-chunks", "ragged-chunk", "ragged-rows-odd-table",
        "wrap-clamp-pad", "neighbour-1e30", "neighbour-nan",
        "rank32-wrap-clamp-pad", "rank32-neighbour-nan"])
def test_gram_kernel_keeps_each_packed_rows_part(rank, n_rows, r, l,
                                                 neighbour):
    from predictionio_tpu.models import als
    from predictionio_tpu.ops.pallas_kernels import gram_takes_packed

    pack, dtype = 128 // rank, jnp.dtype(jnp.bfloat16)
    assert gram_takes_packed(rank, pack)
    rng = np.random.default_rng(n_rows + l)
    table = rng.standard_normal((n_rows, rank)).astype(np.float32)
    idx = rng.integers(0, n_rows, (r, l)).astype(np.int32)
    if neighbour is _EDGE:
        idx[0, :8] = [0, n_rows - 1, -1, -n_rows, n_rows, n_rows + 7,
                      -n_rows - 5, 1]
    elif neighbour is not None:
        # every slot names the first row of a 128-lane row (the table's
        # last whole one among them); every other row, the other parts
        # of each fetched row, is poisoned
        idx = idx // pack * pack
        idx[0, 0] = (n_rows - 1) // pack * pack
        poisoned = np.arange(n_rows) % pack != 0
        table[poisoned] = neighbour
    table, idx = jnp.asarray(table), jnp.asarray(idx)
    # weights whose products with a bf16 value are exact in bf16, so that
    # a path in which the CPU's XLA skips a rounding (it may, where it
    # fuses a convert away) computes the same numbers
    w = jnp.asarray(rng.integers(0, 3, (r, l)), jnp.float32)
    c = jnp.asarray(rng.integers(-4, 5, (r, l)) / 2, jnp.float32)

    rows = als._gather_rows(table, idx, dtype)
    assert np.array_equal(np.asarray(rows, np.float32),
                          np.asarray(table.astype(dtype)[idx], np.float32))
    wide, part = als._gather_wide(table.astype(dtype), idx, pack)
    assert wide.shape == (r, l, 128) and part.shape == (r, l)
    a, b = fused_gram_vector_pallas(wide, w, c, part, pack=pack,
                                    interpret=True)
    assert a.shape == (r, rank, rank) and b.shape == (r, rank)
    # The same products in the plain kernel's order, the part kept before
    # any arithmetic: equal to float32 rounding here, bit for bit on the
    # chip at rank 64 (chip_smoke.py checks that).
    a0, b0 = fused_gram_vector_pallas(rows, w, c, interpret=True)
    for got, want in ((a, a0), (b, b0)) + tuple(
            zip((a, b), fused_gram_vector_xla(rows, w, c))):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# Dense normal equations: the masked product over the whole factor table
# against its XLA twin and against the gathered path on the same ratings.
# ---------------------------------------------------------------------------

def _dense_case(n_src, rows, seed=0, k=8, with_zero=True):
    """The same ratings twice: as padded gathered rows (``_gram_pieces``'
    input) and as a dense block, NaN where a row has no rating."""
    from predictionio_tpu.ops.pallas_kernels import DENSE_BLOCK_DTYPE

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n_src, k)).astype(np.float32)
    length = max(2, n_src // 3)
    idx = np.stack([rng.choice(n_src, length, replace=False)
                    for _ in range(rows)]).astype(np.int32)
    vals = rng.integers(0 if with_zero else 1, 6,
                        (rows, length)).astype(np.float32)
    if with_zero:
        vals[:, 0] = 0.0           # a real rating of 0.0 in every row
    mask = rng.random((rows, length)) < 0.8
    mask[:, 0] = True
    block = np.full((rows, n_src), np.nan, np.float32)
    for r in range(rows):
        block[r, idx[r][mask[r]]] = vals[r][mask[r]]
    return (jnp.asarray(x), jnp.asarray(idx), jnp.asarray(vals),
            jnp.asarray(mask), jnp.asarray(block).astype(DENSE_BLOCK_DTYPE))


@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
@pytest.mark.parametrize("n_src,rows", [
    (256, 16),     # whole tiles
    (300, 5),      # source length no tile multiple, row tile with padding
    (2500, 19),    # two source tiles, two row tiles, both padded
], ids=["whole-tiles", "ragged-one-tile", "ragged-two-tiles"])
def test_dense_gram_matches_twin_and_gathered_path(n_src, rows, implicit):
    from predictionio_tpu.models.als import _gram_pieces
    from predictionio_tpu.ops.pallas_kernels import (
        fused_gram_dense_pallas, fused_gram_dense_xla,
    )

    x, idx, vals, mask, block = _dense_case(n_src, rows, seed=n_src)
    alpha = jnp.float32(0.7)
    a_k, b_k = fused_gram_dense_pallas(block, x, alpha, implicit=implicit,
                                       interpret=True)
    a_x, b_x = fused_gram_dense_xla(block, x, alpha, implicit=implicit)
    assert a_k.shape == (rows, x.shape[1], x.shape[1])
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_x),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(b_k), np.asarray(b_x),
                               rtol=1e-4, atol=1e-4)
    a_g, b_g, deg = _gram_pieces(idx, vals, mask, x, alpha, implicit, True,
                                 jnp.float32)
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_g),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(b_k), np.asarray(b_g),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(deg), np.asarray(mask).sum(1))


@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_dense_weights_are_the_gathered_paths_bit_for_bit(implicit):
    """``w`` and ``c`` from a block value equal what ``_gram_pieces``
    derives from the rating itself, after the kernels' rounding to the
    gram dtype — a rating of 0.0 included, an absent slot zero in both."""
    from predictionio_tpu.ops.pallas_kernels import (
        DENSE_BLOCK_DTYPE, dense_weights,
    )

    vals = jnp.asarray([0.0, 0.5, 1.0, 3.0, 4.5, 5.0, -2.0, 96.0],
                       jnp.float32)
    alpha = jnp.float32(0.3)
    if implicit:
        w = alpha * jnp.abs(vals)
        c = (1.0 + w) * (vals > 0).astype(jnp.float32)
    else:
        w, c = jnp.ones_like(vals), vals
    block = jnp.concatenate([vals, jnp.asarray([jnp.nan])]
                            ).astype(DENSE_BLOCK_DTYPE)
    w_d, c_d = dense_weights(block, alpha, implicit)
    for got, want in ((w_d, w), (c_d, c)):
        got, want = (np.asarray(v.astype(jnp.bfloat16).astype(jnp.float32))
                     for v in (got, want))
        assert np.array_equal(got[:-1], want)
        assert got[-1] == 0.0


def test_dense_gram_rounds_the_weighted_table_to_the_gram_dtype():
    """bf16 operands: ``w·x`` is rounded to bf16 before the product, as
    the gathered kernel rounds ``fw``; a numpy model of that rounding is
    the oracle (XLA on the CPU may keep excess precision elsewhere)."""
    from predictionio_tpu.ops.pallas_kernels import fused_gram_dense_pallas

    x, idx, vals, mask, block = _dense_case(384, 4, seed=9)
    xb = x.astype(jnp.bfloat16)
    alpha = jnp.float32(0.7)
    a_k, _ = fused_gram_dense_pallas(block, xb, alpha, implicit=True,
                                     interpret=True)
    xf = np.asarray(xb.astype(jnp.float32))
    want = np.zeros((4, 8, 8))
    for r in range(4):
        w = np.float32(0.7) * np.abs(np.asarray(vals[r])) * np.asarray(mask[r])
        wb = np.asarray(jnp.asarray(w).astype(jnp.bfloat16)
                        .astype(jnp.float32))
        f = xf[np.asarray(idx[r])]
        fw = np.asarray(jnp.asarray(f * wb[:, None]).astype(jnp.bfloat16)
                        .astype(jnp.float32))
        want[r] = fw.T.astype(np.float64) @ f
    np.testing.assert_allclose(np.asarray(a_k), want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("rank,n_src,width,tile", [
    (64, 17_770, 18_432, 2048), (64, 480_189, 481_280, 2048),
    (128, 480_189, 481_280, 1024), (8, 15, 128, 128),
], ids=["netflix-items", "netflix-users", "rank128", "toy"])
def test_dense_tiles_and_block_width(rank, n_src, width, tile):
    from predictionio_tpu.ops.pallas_kernels import (
        dense_block_width, dense_src_tile,
    )

    assert dense_block_width(n_src) == width
    assert dense_src_tile(width, rank) == tile
    assert width % tile == 0


def test_dense_row_density_follows_the_rank_and_the_table():
    from predictionio_tpu.ops.pallas_kernels import (
        dense_row_density, gather_table_pack,
    )

    n = 17_770
    assert 0.0 < dense_row_density(64, n) < 0.1
    assert dense_row_density(128, n) == pytest.approx(
        4 * dense_row_density(64, n))
    assert dense_row_density(32, n) == pytest.approx(
        dense_row_density(64, n) / 4)
    assert dense_row_density(256, n) == float("inf")
    # Which table gets which rate.  The step is the table's physical
    # bytes, 128 lanes a row whatever the rank: 458,752 bf16 rows.
    assert [gather_table_pack(rows, 64, 2) for rows in
            (n, 440_000, 458_752, 458_753, 480_189, 917_504, 917_505,
             21_000_000)] == [1, 1, 1, 2, 2, 2, None, None]
    assert [gather_table_pack(458_753, rank, 2) for rank in
            (16, 32, 50, 64, 65, 100, 128)] == [8, 4, 2, 2, None, None, None]
    assert gather_table_pack(229_377, 64, 4) == 2       # float32 rows
    assert gather_table_pack(1_600_000, 32, 2) == 4
    # als-netflix-r64's users, 9% past the step: gathered through the
    # packed view at under twice the rate of a table under it, so a row
    # goes dense at over half that table's density, not at a fifth of it
    small, packed = dense_row_density(64, n), dense_row_density(64, 480_189)
    assert small / 2 < packed < small
    assert dense_row_density(64, 458_752) == small
    # past every view's reach (Amazon 2014's 21M users; at rank 128 no
    # lane is left to share): the slow gather, rows go dense far earlier
    slow = dense_row_density(64, 21_000_000)
    assert slow < small / 4 and slow < packed / 3
    assert dense_row_density(64, 917_505) == slow
    assert dense_row_density(128, 480_189) == pytest.approx(4 * slow)
    assert dense_row_density(128, 458_752) == pytest.approx(4 * small)
