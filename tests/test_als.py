"""ALS model: convergence, exactness vs a numpy oracle, mesh equivalence.

The oracle re-implements the per-entity normal equations directly from the
Hu-Koren-Volinsky / ALS-WR math the reference's MLlib ALS computes
(SURVEY.md §2.2) — if the padded/bucketed XLA path diverges from the naive
loop, these fail.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from predictionio_tpu.models.als import (
    ALSConfig,
    ALSModel,
    predict_scores,
    recommend,
    rmse,
    train_als,
)
from predictionio_tpu.parallel.mesh import make_mesh


def _toy(seed=0, n_users=30, n_items=20, rank_true=3, density=0.5):
    """Low-rank synthetic ratings."""
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((n_users, rank_true))
    v = rng.standard_normal((n_items, rank_true))
    full = u @ v.T
    mask = rng.random((n_users, n_items)) < density
    users, items = np.nonzero(mask)
    return users, items, full[users, items].astype(np.float32)


def _numpy_als_side(indices_per_row, vals_per_row, y, reg, implicit, alpha):
    """Naive per-row normal equations (the oracle)."""
    k = y.shape[1]
    yty = y.T @ y
    out = np.zeros((len(indices_per_row), k), dtype=np.float64)
    for r, (idx, vals) in enumerate(zip(indices_per_row, vals_per_row)):
        n = max(len(idx), 1)
        if implicit:
            w = alpha * np.abs(np.asarray(vals))
            p = (np.asarray(vals) > 0).astype(np.float64)
            f = y[idx]
            a = yty + (f * w[:, None]).T @ f + reg * n * np.eye(k)
            b = f.T @ ((1.0 + w) * p)
        else:
            f = y[idx]
            a = f.T @ f + reg * n * np.eye(k)
            b = f.T @ np.asarray(vals)
        if len(idx) == 0:
            a = reg * n * np.eye(k) + (yty if implicit else 0)
            b = np.zeros(k)
        out[r] = np.linalg.solve(a, b)
    return out


@pytest.mark.parametrize("implicit", [False, True])
def test_single_step_matches_oracle(implicit):
    users, items, ratings = _toy()
    n_users, n_items = 30, 20
    # gram_dtype f32: this test checks the math against a float64 oracle
    # at tight tolerance; the bf16 speed default is covered by the
    # convergence tests below.
    cfg = ALSConfig(rank=4, iterations=1, reg=0.1, alpha=2.0,
                    implicit=implicit, seed=7, bucket_bounds=(4, 8),
                    gram_dtype="float32")
    model = train_als(users, items, ratings, n_users, n_items, cfg)

    # Expected first-iteration factors from the shared deterministic init
    # (the oracle below re-derives the normal-equation math in numpy).
    from predictionio_tpu.models.als import _init_factors
    uf0, if0 = (np.asarray(a) for a in _init_factors(n_users, n_items, 4, 7))
    by_user = [(items[users == u], ratings[users == u]) for u in range(n_users)]
    uf1 = _numpy_als_side([i for i, _ in by_user], [v for _, v in by_user],
                          if0.astype(np.float64), 0.1, implicit, 2.0)
    by_item = [(users[items == i], ratings[items == i]) for i in range(n_items)]
    if1 = _numpy_als_side([u for u, _ in by_item], [v for _, v in by_item],
                          uf1, 0.1, implicit, 2.0)
    np.testing.assert_allclose(np.asarray(model.user_factors), uf1,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(model.item_factors), if1,
                               rtol=2e-3, atol=2e-3)


def test_explicit_converges():
    users, items, ratings = _toy(density=0.7)
    cfg = ALSConfig(rank=6, iterations=12, reg=0.01, seed=1)
    model = train_als(users, items, ratings, 30, 20, cfg)
    assert rmse(model, users, items, ratings) < 0.15


def test_implicit_ranks_observed_higher():
    rng = np.random.default_rng(3)
    # Two user cliques each consuming a disjoint item half.
    users, items = [], []
    for u in range(20):
        half = u % 2
        for i in rng.choice(10, size=6, replace=False):
            users.append(u)
            items.append(half * 10 + i)
    users, items = np.array(users), np.array(items)
    cfg = ALSConfig(rank=8, iterations=10, implicit=True, alpha=40.0, reg=0.01)
    model = train_als(users, items, None, 20, 20, cfg)
    s = np.asarray(model.user_factors @ model.item_factors.T)
    own = s[0, :10].mean()
    other = s[0, 10:].mean()
    assert own > other + 0.1


def test_mesh_equivalence():
    """Sharded run == single-device run (the local[n] analogue, SURVEY §4)."""
    users, items, ratings = _toy(seed=5)
    cfg = ALSConfig(rank=4, iterations=3, reg=0.05, seed=9, bucket_bounds=(8,))
    m1 = train_als(users, items, ratings, 30, 20, cfg)
    mesh = make_mesh({"data": 8})
    m2 = train_als(users, items, ratings, 30, 20, cfg, mesh=mesh)
    np.testing.assert_allclose(np.asarray(m1.user_factors),
                               np.asarray(m2.user_factors), rtol=1e-3, atol=1e-3)


def test_blocked_factor_sharded_equivalence():
    """Blueprint blocked ALS (SURVEY §2.4 row 2): row-sharding the
    PERSISTENT factor matrices over the data axis changes placement, not
    math — and the state really stays sharded across sweeps."""
    from jax.sharding import NamedSharding

    users, items, ratings = _toy(seed=5)
    base = dict(rank=4, iterations=3, reg=0.05, seed=9, bucket_bounds=(8,))
    mesh = make_mesh({"data": 8})
    # Mesh-divisible extents: the returned factors keep their sharding.
    m1 = train_als(users, items, ratings, 32, 24, ALSConfig(**base))
    m2 = train_als(users, items, ratings, 32, 24,
                   ALSConfig(**base, factor_sharding="sharded"), mesh=mesh)
    sh = m2.user_factors.sharding
    assert isinstance(sh, NamedSharding) and sh.spec[0] == "data", \
        "blocked mode must keep the factor state row-sharded"
    np.testing.assert_allclose(np.asarray(m1.user_factors),
                               np.asarray(m2.user_factors),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(m1.item_factors),
                               np.asarray(m2.item_factors),
                               rtol=1e-3, atol=1e-3)
    # Non-divisible extents ride the padding path; same math.
    m3 = train_als(users, items, ratings, 30, 20, ALSConfig(**base))
    m4 = train_als(users, items, ratings, 30, 20,
                   ALSConfig(**base, factor_sharding="sharded"), mesh=mesh)
    assert m4.user_factors.shape == (30, 4)
    np.testing.assert_allclose(np.asarray(m3.user_factors),
                               np.asarray(m4.user_factors),
                               rtol=1e-3, atol=1e-3)


def test_blocked_windowed_gather_equivalence():
    """Windowed blocked mode (VERDICT r4 item 2): per-chunk gathers fetch
    only the factor rows the chunk touches, via masked local take + psum
    over the data axis — placement changes, math does not.  The data is
    built so user-side chunks touch <half the item matrix (windows
    engage, asserted) while the item side exceeds the threshold and
    stays on the plain path — both paths in one compiled loop."""
    from predictionio_tpu.models.als import (
        prepare_als_inputs, train_als_prepared,
    )

    rng = np.random.default_rng(11)
    n_u, n_i, nnz = 96, 400, 1500
    users = rng.integers(0, n_u, nnz)
    items = rng.integers(0, 100, nnz)  # only the first 100 of 400 items
    ratings = rng.uniform(1, 5, nnz).astype(np.float32)
    mesh = make_mesh({"data": 8})
    for extra in (dict(), dict(implicit=True, alpha=40.0)):
        base = dict(rank=4, iterations=3, reg=0.05, seed=9,
                    bucket_bounds=(16,), **extra)
        m1 = train_als(users, items, ratings, n_u, n_i, ALSConfig(**base))
        cfg = ALSConfig(**base, factor_sharding="sharded",
                        gather_window=True)
        inputs = prepare_als_inputs(users, items, ratings, n_u, n_i, cfg,
                                    mesh=mesh)
        ukinds = [b[0] for b in inputs.user_buckets]
        ikinds = [b[0] for b in inputs.item_buckets]
        assert any(k.endswith("_w") for k in ukinds), ukinds
        assert not any(k.endswith("_w") for k in ikinds), ikinds
        m2 = train_als_prepared(inputs, cfg)
        np.testing.assert_allclose(np.asarray(m1.user_factors),
                                   np.asarray(m2.user_factors),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(m1.item_factors),
                                   np.asarray(m2.item_factors),
                                   rtol=1e-3, atol=1e-3)


def test_factor_sharding_auto_threshold():
    from predictionio_tpu.models.als import _shard_factors

    small = ALSConfig(rank=4)
    assert not _shard_factors(small, 30, 20)
    big = ALSConfig(rank=128, factor_shard_threshold=1 << 20)
    assert _shard_factors(big, 100_000, 50_000)


def test_recommend_excludes_seen():
    users, items, ratings = _toy(density=0.4)
    cfg = ALSConfig(rank=4, iterations=5)
    model = train_als(users, items, ratings, 30, 20, cfg)
    seen = np.zeros((1, 20), dtype=bool)
    seen[0, items[users == 0]] = True
    _, ids = recommend(model, jnp.asarray([0]), 5, seen=jnp.asarray(seen))
    assert not (set(np.asarray(ids)[0].tolist()) & set(items[users == 0].tolist()))


def test_predict_scores_shape():
    users, items, ratings = _toy()
    cfg = ALSConfig(rank=4, iterations=2)
    model = train_als(users, items, ratings, 30, 20, cfg)
    s = predict_scores(model.user_factors, model.item_factors,
                       jnp.asarray([0, 1]), jnp.asarray([3, 4]))
    assert s.shape == (2,)


def test_split_above_matches_unsplit():
    """Segment-summed split path == plain path (exact, not approximate)."""
    users, items, ratings = _toy(density=0.8)
    base = dict(rank=4, iterations=3, reg=0.05, seed=11, gram_dtype="float32",
                bucket_bounds=(4,))
    m_plain = train_als(users, items, ratings, 30, 20,
                        ALSConfig(**base, split_above=None))
    m_split = train_als(users, items, ratings, 30, 20,
                        ALSConfig(**base, split_above=8))
    np.testing.assert_allclose(np.asarray(m_plain.user_factors),
                               np.asarray(m_split.user_factors),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(m_plain.item_factors),
                               np.asarray(m_split.item_factors),
                               rtol=1e-4, atol=1e-4)


def test_split_above_matches_unsplit_on_mesh():
    users, items, ratings = _toy(density=0.8)
    base = dict(rank=4, iterations=2, reg=0.05, seed=11, gram_dtype="float32",
                bucket_bounds=(4,))
    mesh = make_mesh({"data": 8})
    m_plain = train_als(users, items, ratings, 30, 20,
                        ALSConfig(**base, split_above=None))
    m_split = train_als(users, items, ratings, 30, 20,
                        ALSConfig(**base, split_above=8), mesh=mesh)
    np.testing.assert_allclose(np.asarray(m_plain.user_factors),
                               np.asarray(m_split.user_factors),
                               rtol=1e-3, atol=1e-3)


def test_degree_zero_entities_get_near_zero_factors():
    """Pinned semantics (VERDICT.md weak-5): unrated entities solve to the
    ridge solution of an empty system — (lambda I) x = 0 -> x = 0 — so they
    never outrank real recommendations (MLlib simply omits them; scoring
    behavior matches: 0-dot = 0)."""
    users = np.array([0, 0, 1, 1, 2])
    items = np.array([0, 1, 0, 2, 1])
    ratings = np.ones(5, dtype=np.float32)
    # users 3, 4 and item 3 have no ratings at all
    model = train_als(users, items, ratings, 5, 4,
                      ALSConfig(rank=4, iterations=2, reg=0.1, seed=0))
    uf = np.asarray(model.user_factors)
    assert np.abs(uf[3:]).max() < 1e-5
    assert np.abs(np.asarray(model.item_factors)[3]).max() < 1e-5
    # rated rows are non-trivial
    assert np.abs(uf[:3]).max() > 1e-2


def test_split_chunking_matches_unsplit():
    """HBM chunking of split buckets (entity-boundary cuts) stays exact."""
    users, items, ratings = _toy(density=0.9)
    base = dict(rank=4, iterations=3, reg=0.05, seed=13, gram_dtype="float32",
                bucket_bounds=(4,))
    m_plain = train_als(users, items, ratings, 30, 20,
                        ALSConfig(**base, split_above=None))
    # max_block_floats tiny -> every split bucket is forced into chunks.
    m_chunk = train_als(users, items, ratings, 30, 20,
                        ALSConfig(**base, split_above=4,
                                  max_block_floats=4 * 4 * 8))
    np.testing.assert_allclose(np.asarray(m_plain.user_factors),
                               np.asarray(m_chunk.user_factors),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(m_plain.item_factors),
                               np.asarray(m_chunk.item_factors),
                               rtol=1e-4, atol=1e-4)


def test_bf16_gram_quality():
    """bf16 gathered operands (the TPU default) must not hurt fit quality.

    PARITY.md pins this: master factors and accumulation stay f32; only
    the gathered gram/rhs operands are bf16.  RMSE after full training on
    a recoverable low-rank problem must match the f32 path closely.
    """
    rng = np.random.default_rng(7)
    n_u, n_i, n = 80, 60, 3000
    tu = rng.standard_normal((n_u, 4))
    ti = rng.standard_normal((n_i, 4))
    users = rng.integers(0, n_u, n)
    items = rng.integers(0, n_i, n)
    ratings = np.sum(tu[users] * ti[items], axis=1).astype(np.float32)
    f32 = ALSConfig(rank=8, iterations=8, reg=0.05, seed=1,
                    gram_dtype="float32")
    bf16 = ALSConfig(rank=8, iterations=8, reg=0.05, seed=1,
                     gram_dtype="bfloat16")
    m32 = train_als(users, items, ratings, n_u, n_i, f32)
    m16 = train_als(users, items, ratings, n_u, n_i, bf16)
    r32 = rmse(m32, users, items, ratings)
    r16 = rmse(m16, users, items, ratings)
    scale = float(np.sqrt(np.mean(ratings ** 2)))
    assert abs(r16 - r32) < 0.02 * scale, (r32, r16)


def test_fit_bounds_reduces_padding():
    """DP-fitted bounds must never pad more than the fixed defaults and
    must stay sublane-aligned."""
    from predictionio_tpu.ops.ragged import fit_bounds

    rng = np.random.default_rng(0)
    counts = np.concatenate([
        rng.integers(100, 220, 5000),       # user-like bulk
        (rng.zipf(1.3, 500) % 4000) + 1,    # zipf tail
    ])
    bounds = fit_bounds(counts, cap=4096)
    assert all(b % 8 == 0 for b in bounds)
    assert bounds == sorted(set(bounds))

    def padded(bs):
        c = np.minimum(counts, 4096)
        tot, prev = 0, 0
        for b in sorted(bs):
            sel = (c > prev) & (c <= b)
            tot += sel.sum() * b
            prev = b
        assert prev >= c.max()
        return tot

    fixed = [16, 64, 256, 1024, 4096]
    assert padded(bounds) <= padded(fixed)
    assert padded(bounds) <= 1.15 * counts.clip(max=4096).sum()


def test_gather_window_auto_skips_single_device_axis():
    """A 1-device data axis has no cross-shard transient — auto windowing
    must skip (it would only add a second gather level); an explicit
    gather_window=True still forces it (how tests exercise the path)."""
    from predictionio_tpu.models.als import prepare_als_inputs

    rng = np.random.default_rng(3)
    users = rng.integers(0, 64, 800)
    items = rng.integers(0, 20, 800)  # 20 of 400 items → windows viable
    ratings = rng.uniform(1, 5, 800).astype(np.float32)
    mesh1 = make_mesh({"data": 1})
    base = dict(rank=4, iterations=1, seed=0, bucket_bounds=(16,),
                factor_sharding="sharded")
    inp_auto = prepare_als_inputs(users, items, ratings, 64, 400,
                                  ALSConfig(**base), mesh=mesh1)
    assert not any(b[0].endswith("_w") for b in inp_auto.user_buckets)
    inp_forced = prepare_als_inputs(users, items, ratings, 64, 400,
                                    ALSConfig(**base, gather_window=True),
                                    mesh=mesh1)
    assert any(b[0].endswith("_w") for b in inp_forced.user_buckets)


def test_host_layout_rows_sublane_aligned():
    """The host/mesh prep path must keep bucket ROW counts 8-aligned
    (and mesh-divisible): unaligned rows made XLA pad/relayout every
    gathered block in-graph — ~70 ms/iter at the ML-25M shape (round 5).
    Guard the layout invariant, not the timing."""
    from predictionio_tpu.models.als import prepare_als_inputs

    users, items, ratings = _toy(seed=2, n_users=50, n_items=40,
                                 density=0.6)
    cfg = ALSConfig(rank=4, iterations=1, seed=0, device_prep=False)
    inp = prepare_als_inputs(users, items, ratings, 50, 40, cfg, mesh=None)
    for b in (*inp.user_buckets, *inp.item_buckets):
        assert b[1].shape[0] % 8 == 0, (b[0], b[1].shape)
    # a NON-divisor axis (3 of the 8 CPU devices): rows must pad to
    # lcm(sublane, 3) = 24, which only holds if the lcm term survives
    import math

    from predictionio_tpu.ops.ragged import LEN_ALIGN

    mesh = make_mesh({"data": 3})
    inp2 = prepare_als_inputs(users, items, ratings, 50, 40, cfg, mesh=mesh)
    granule = math.lcm(LEN_ALIGN, 3)
    for b in (*inp2.user_buckets, *inp2.item_buckets):
        assert b[1].shape[0] % granule == 0, (b[0], b[1].shape)


# ---------------------------------------------------------------------------
# Dense rows: a whole train with them against the same train planned
# all-sparse, and the counter that says how often the dense path engages.
# ---------------------------------------------------------------------------

def _dense_toy(seed=0, n_users=60, n_items=40):
    """Whole-number ratings, no repeated pair, a dense head on both
    sides over a sparse rest (``_toy``'s ratings are not values a dense
    block holds exactly, so they plan all-sparse)."""
    rng = np.random.default_rng(seed)
    mask = rng.random((n_users, n_items)) < 0.1
    mask[:6] |= rng.random((6, n_items)) < 0.8
    mask[:, :5] |= rng.random((n_users, 5)) < 0.8
    users, items = np.nonzero(mask)
    return (users.astype(np.int32), items.astype(np.int32),
            rng.integers(1, 6, len(users)).astype(np.float32))


@pytest.mark.parametrize("use_pallas", [None, True],
                         ids=["xla-twin", "interpreter"])
@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_train_with_dense_rows_matches_all_sparse_plan(implicit, use_pallas):
    from predictionio_tpu.models.als import (
        _prepare_als_inputs_device, train_als_prepared,
    )

    users, items, ratings = _dense_toy()
    cfg = ALSConfig(rank=4, iterations=3, reg=0.05, seed=11,
                    gram_dtype="float32", implicit=implicit, alpha=0.5,
                    split_above=16, use_pallas=use_pallas)
    dense = _prepare_als_inputs_device(users, items, ratings, 60, 40, cfg)
    sparse = _prepare_als_inputs_device(users, items, ratings, 60, 40, cfg,
                                        dense=False)
    assert "dense" in {b[0] for b in dense.user_buckets}
    assert "dense" in {b[0] for b in dense.item_buckets}
    assert "dense" not in {b[0] for b in
                           sparse.user_buckets + sparse.item_buckets}
    m_dense = train_als_prepared(dense, cfg)
    m_sparse = train_als_prepared(sparse, cfg)
    np.testing.assert_allclose(np.asarray(m_dense.user_factors),
                               np.asarray(m_sparse.user_factors),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(m_dense.item_factors),
                               np.asarray(m_sparse.item_factors),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dense,kinds", [
    (True, {"plain", "dense"}), (False, {"plain", "merged"})],
    ids=["dense-rows", "merged-rows"])
@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_lanes_solve_trains_as_cholesky_at_the_templates_rank(
        implicit, dense, kinds):
    """Rank 10 (the templates' default, no multiple of the solve's granule
    of 8) through the three call sites of ``_ridge``: plain buckets, dense
    rows and merged partial rows."""
    from predictionio_tpu.models.als import (
        _prepare_als_inputs_device, train_als_prepared,
    )

    users, items, ratings = _dense_toy()
    models = {}
    for solver in ("cholesky", "lu"):
        cfg = ALSConfig(rank=10, iterations=3, reg=0.065, seed=11,
                        gram_dtype="float32", implicit=implicit, alpha=0.5,
                        split_above=16, solver=solver)
        inputs = _prepare_als_inputs_device(users, items, ratings, 60, 40,
                                            cfg, dense=dense)
        assert {b[0] for b in inputs.user_buckets} == kinds
        models[solver] = train_als_prepared(inputs, cfg)
    for side in ("user_factors", "item_factors"):
        np.testing.assert_allclose(
            np.asarray(getattr(models["lu"], side)),
            np.asarray(getattr(models["cholesky"], side)),
            rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("device_prep", [True, False],
                         ids=["device-prep", "host-prep"])
def test_gram_ratings_counter_adds_up_to_ratings_times_sweeps(device_prep):
    from predictionio_tpu.models.als import (
        prepare_als_inputs, train_als_prepared,
    )
    from predictionio_tpu.obs import get_registry

    users, items, ratings = _dense_toy(seed=3)
    cfg = ALSConfig(rank=4, iterations=3, reg=0.05, seed=11,
                    device_prep=device_prep, split_above=16)
    inputs = prepare_als_inputs(users, items, ratings, 60, 40, cfg)
    counter = get_registry().counter(
        "pio_als_gram_ratings_total", "", ("side", "path"))

    def read():
        return {(s, p): counter.value(side=s, path=p)
                for s in ("user", "item") for p in ("dense", "gathered")}

    before = read()
    train_als_prepared(inputs, cfg)
    grown = {k: v - before[k] for k, v in read().items()}
    for side in ("user", "item"):
        assert grown[side, "dense"] + grown[side, "gathered"] \
            == len(users) * cfg.iterations
        # device prep on this matrix plans dense rows; host prep none
        assert (grown[side, "dense"] > 0) == device_prep


def test_benchmark_reads_the_dense_share_and_the_dense_kernel():
    """The two per-layer metrics this kind brought, through the readers
    the benchmark already had: the counter's dense share, and the dense
    kernel's device time under a name ``als_gram_roofline``'s pattern
    also matches; nothing on a program that has neither."""
    import types

    from benchmark import manifest, prom, trace_reduce
    from benchmark.readers import op_ms_per_unit, prom_ratio
    from predictionio_tpu.models.als import (
        prepare_als_inputs, train_als_prepared,
    )

    users, items, ratings = _dense_toy(seed=5)
    cfg = ALSConfig(rank=4, iterations=2, reg=0.05, seed=11,
                    device_prep=True, split_above=16)
    inputs = prepare_als_inputs(users, items, ratings, 60, 40, cfg)
    before = prom.snapshot()
    train_als_prepared(inputs, cfg)
    ctx = {"before": before, "after": prom.snapshot()}
    share = prom_ratio.read(ctx, **manifest.layer_metric_spec(
        "als_dense_rating_pct")["args"])
    (du, gu), (di, gi) = inputs.gram_ratings
    assert du > 0 and di > 0
    assert share == pytest.approx(100.0 * (du + di) / (du + gu + di + gi))
    assert prom_ratio.read({"before": {}, "after": {}},
                           **manifest.layer_metric_spec(
                               "als_dense_rating_pct")["args"]) is None

    spec = manifest.layer_metric_spec("als_dense_gram_ms")
    window = types.SimpleNamespace(extras={"sweeps": 5})
    reduced = {"op_s": {"fused_gram_dense_pallas": 0.5,
                        "fused_gram_vector_pallas": 0.1, "fusion": 2.0}}
    assert op_ms_per_unit.read({"trace": reduced, "window": window},
                               **spec["args"]) == pytest.approx(100.0)
    assert op_ms_per_unit.read(
        {"trace": {"op_s": {"fused_gram_vector_pallas": 0.1}},
         "window": window}, **spec["args"]) is None
    gram = manifest.layer_metric_spec("als_gram_roofline")["args"]["pattern"]
    assert trace_reduce.kernel_seconds(reduced, gram) == pytest.approx(0.6)
    for name in ("als_dense_rating_pct", "als_dense_gram_ms"):
        (entry,) = [m for m in manifest.load()["per_layer"]
                    if m["name"] == name]
        assert entry["workloads"] == ["als-netflix-r64.retrain"]
        assert (entry["layer"], entry["moves"]) == ("ALS kernels",
                                                    "rating_iters_per_s")


def _bits(x):
    return np.asarray(x).view(np.uint16 if x.dtype.itemsize == 2
                              else np.uint32)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("rank", [32, 64, 128])
@pytest.mark.parametrize("n_rows", [480, 481], ids=["even", "odd"])
def test_gather_rows_is_the_plain_gather_bit_for_bit(n_rows, rank, dtype,
                                                     monkeypatch):
    """Every form of the fetch returns ``table.astype(dtype)[idx]``: the
    table as it is, and the packed view (rows 0 and the last, repeats,
    and an odd row count, whose pad row must never reach a result)."""
    import jax

    from predictionio_tpu.models import als
    from predictionio_tpu.ops import pallas_kernels

    table = jax.random.normal(jax.random.PRNGKey(rank + n_rows),
                              (n_rows, rank), jnp.float32)
    rng = np.random.default_rng(n_rows)
    idx = rng.integers(0, n_rows, (8, 24)).astype(np.int32)
    idx[0, :6] = [0, n_rows - 1, n_rows - 1, 0, n_rows - 2, 1]
    idx = jnp.asarray(idx)
    want = _bits(table.astype(dtype)[idx])
    assert np.array_equal(
        _bits(als._gather_rows(table, idx, jnp.dtype(dtype))), want)
    # a fast memory of 100 KB: this table is past the step as it is, and
    # under it through the view wherever the rank leaves lanes to share
    monkeypatch.setattr(pallas_kernels, "_GATHER_FAST_TABLE_BYTES", 100_000)
    pack = pallas_kernels.gather_table_pack(
        n_rows, rank, jnp.dtype(dtype).itemsize)
    assert pack == {32: 4, 64: 2 if dtype == "bfloat16" else None,
                    128: None}[rank]
    assert np.array_equal(
        _bits(als._gather_rows(table, idx, jnp.dtype(dtype))), want)
    for pack in (2, 4):
        if rank * pack <= 128:
            got = als._gather_packed(table.astype(dtype), idx, pack)
            assert got.shape == (8, 24, rank)
            assert np.array_equal(_bits(got), want)


def test_a_table_under_the_step_lowers_to_the_plain_gather(monkeypatch):
    """A side step over a table that lies in the fast memory as it is
    (als-netflix-r64's 17,770 items; every table of ML-25M) is the same
    program text as with ``factors.astype(dtype)[indices]`` in the
    function's place; past the step the text changes."""
    import jax

    from predictionio_tpu.models import als
    from predictionio_tpu.ops import pallas_kernels

    def lowered(n_src):
        S = jax.ShapeDtypeStruct
        r, l, k = 16, 8, 64
        return als._side_step.lower(
            S((r, l), jnp.int32), S((r, l), jnp.float32),
            S((r, l), jnp.bool_), S((r,), jnp.int32),
            S((40, k), jnp.float32), S((n_src, k), jnp.float32),
            S((), jnp.float32), S((), jnp.float32), implicit=False,
            use_pallas=False, gram_dtype="bfloat16").as_text()

    new = {n: lowered(n) for n in (17_770, 480_189)}
    assert pallas_kernels.gather_table_pack(17_770, 64, 2) == 1
    assert pallas_kernels.gather_table_pack(480_189, 64, 2) == 2
    monkeypatch.setattr(
        als, "_gather_rows",
        lambda factors, indices, dtype: factors.astype(dtype)[indices])
    als._side_step.clear_cache()
    try:
        assert lowered(17_770) == new[17_770]
        assert lowered(480_189) != new[480_189]
    finally:
        als._side_step.clear_cache()


def test_gather_form_counter_follows_the_source_table(monkeypatch):
    """``pio_als_gather_ratings_total`` splits the gathered ratings by the
    form the other side's table takes, and the benchmark's
    ``als_gather_under_step_pct`` reads the item side's share."""
    from benchmark import manifest, prom
    from benchmark.readers import prom_ratio
    from predictionio_tpu.models import als
    from predictionio_tpu.models.als import (
        prepare_als_inputs, train_als_prepared,
    )
    from predictionio_tpu.ops import pallas_kernels

    users, items, ratings = _dense_toy(seed=7)
    cfg = ALSConfig(rank=4, iterations=2, reg=0.05, seed=11,
                    device_prep=False, split_above=16)
    inputs = prepare_als_inputs(users, items, ratings, 60, 40, cfg)
    plain = train_als_prepared(inputs, cfg)
    # a fast memory that holds the 40 items' table (rank 4 in float32:
    # 128 lanes x 4 B a row) and not the 60 users' as it is
    monkeypatch.setattr(pallas_kernels, "_GATHER_FAST_TABLE_BYTES",
                        50 * 128 * 4)
    packs = []
    packed = als._gather_packed
    monkeypatch.setattr(
        als, "_gather_packed",
        lambda table, idx, pack: packs.append((table.shape[0], pack))
        or packed(table, idx, pack))
    als._train_loop.clear_cache()       # traced anew under the small memory
    spec = manifest.layer_metric_spec("als_gather_under_step_pct")
    before = prom.snapshot()
    try:
        model = train_als_prepared(inputs, cfg)
    finally:
        als._train_loop.clear_cache()
    assert packs and set(packs) == {(60, 32)}
    # the same rows reach the same kernels: the same factors, bit for bit
    assert np.array_equal(_bits(model.user_factors),
                          _bits(plain.user_factors))
    assert np.array_equal(_bits(model.item_factors),
                          _bits(plain.item_factors))
    ctx = {"before": before, "after": prom.snapshot()}
    (_, gu), (_, gi) = inputs.gram_ratings      # host prep: all gathered
    assert gu == gi == len(users)

    def grown(side, form):
        return prom.delta(ctx["before"], ctx["after"],
                          "pio_als_gather_ratings_total",
                          {"side": side, "form": form})

    assert grown("user", "plain") == gu * cfg.iterations
    assert grown("item", "packed") == gi * cfg.iterations
    assert grown("user", "packed") == grown("item", "plain") == 0
    assert prom_ratio.read(ctx, **spec["args"]) == pytest.approx(100.0)
    assert prom_ratio.read({"before": {}, "after": {}},
                           **spec["args"]) is None
    (entry,) = [m for m in manifest.load()["per_layer"]
                if m["name"] == "als_gather_under_step_pct"]
    assert entry["workloads"] == ["als-netflix-r64.retrain"]
    assert (entry["layer"], entry["moves"]) == ("fused ALS loop",
                                                "rating_iters_per_s")


def test_train_through_the_kernel_that_keeps_the_part_equals_the_xla_pass(
        monkeypatch):
    """The packed view forced on a small table at rank 64: the sparse gram
    kernel (interpreter) takes the 128-lane rows and keeps each slot's
    half itself; the same train with the half kept by XLA before the
    kernel (``_gather_rows``) gives the same factors, and
    ``pio_als_gather_select_ratings_total`` says which of the two ran
    (``als_select_in_kernel_pct`` reads the item side's share)."""
    from benchmark import manifest, prom
    from benchmark.readers import prom_ratio
    from predictionio_tpu.models import als
    from predictionio_tpu.models.als import (
        prepare_als_inputs, train_als_prepared,
    )
    from predictionio_tpu.ops import pallas_kernels

    users, items, ratings = _dense_toy(seed=9)
    cfg = ALSConfig(rank=64, iterations=3, reg=1.0, seed=11,
                    device_prep=False, split_above=16, use_pallas=True,
                    gram_dtype="bfloat16", solver="cholesky")
    inputs = prepare_als_inputs(users, items, ratings, 60, 40, cfg)
    # a fast memory that holds the 40 items' bf16 table (128 lanes x 2 B
    # a row) and the 60 users' only two rows to a 128-lane row
    monkeypatch.setattr(pallas_kernels, "_GATHER_FAST_TABLE_BYTES",
                        50 * 128 * 2)
    assert pallas_kernels.gather_table_pack(60, 64, 2) == 2
    assert pallas_kernels.gather_table_pack(40, 64, 2) == 1
    spec = manifest.layer_metric_spec("als_select_in_kernel_pct")
    wide_calls, models, shares, grown = [], {}, {}, {}
    gather_wide = als._gather_wide
    monkeypatch.setattr(
        als, "_gather_wide",
        lambda table, idx, pack: wide_calls.append(table.shape)
        or gather_wide(table, idx, pack))
    for where in ("kernel", "xla"):
        if where == "xla":
            monkeypatch.setattr(als, "gram_takes_packed",
                                lambda rank, pack: False)
        als._train_loop.clear_cache()
        del wide_calls[:]
        before = prom.snapshot()
        try:
            models[where] = train_als_prepared(inputs, cfg)
        finally:
            als._train_loop.clear_cache()
        ctx = {"before": before, "after": prom.snapshot()}
        assert wide_calls and set(wide_calls) == {(60, 64)}
        shares[where] = prom_ratio.read(ctx, **spec["args"])
        grown[where] = {
            (side, w): prom.delta(ctx["before"], ctx["after"],
                                  "pio_als_gather_select_ratings_total",
                                  {"side": side, "where": w})
            for side in ("user", "item") for w in ("kernel", "xla", "none")}
    (_, gu), (_, gi) = inputs.gram_ratings
    for where, other in (("kernel", "xla"), ("xla", "kernel")):
        assert grown[where]["item", where] == gi * cfg.iterations
        assert grown[where]["user", "none"] == gu * cfg.iterations
        assert grown[where]["item", other] == grown[where]["item", "none"] \
            == grown[where]["user", "kernel"] == grown[where]["user", "xla"] \
            == 0
    assert shares == {"kernel": pytest.approx(100.0), "xla": 0.0}
    assert prom_ratio.read({"before": {}, "after": {}},
                           **spec["args"]) is None
    # the same rows reach the same products (whole-number ratings: exact
    # in bf16); the two bodies' float32 sums may differ in their last
    # bits here, which a ridge this strong does not amplify
    for name in ("user_factors", "item_factors"):
        np.testing.assert_allclose(
            np.asarray(getattr(models["kernel"], name)),
            np.asarray(getattr(models["xla"], name)), rtol=1e-4, atol=1e-5)
    (entry,) = [m for m in manifest.load()["per_layer"]
                if m["name"] == "als_select_in_kernel_pct"]
    assert entry["workloads"] == ["als-netflix-r64.retrain"]
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "ALS kernels", "rating_iters_per_s", "program_counter")
