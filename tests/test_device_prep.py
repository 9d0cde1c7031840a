"""Device-side bucketing (ops/device_prep.py) vs the host-numpy oracle.

The device path must produce byte-identical bucket CONTENTS (same entries
per entity, same within-row event order, same split-segment layout) as
``bucket_by_length``; only row/slot ordering metadata may differ, and the
ALS consumer is invariant to that by construction (row_ids route scatter).
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from predictionio_tpu.models.als import ALSConfig, train_als, rmse
from predictionio_tpu.ops.device_prep import (
    build_buckets, build_dense_block, degree_histogram, plan_buckets,
)
from predictionio_tpu.ops.ragged import bucket_by_length


def _coo(seed=3, n_rows=400, n_cols=300, n=20_000, zipf=1.3):
    rng = np.random.default_rng(seed)
    rows = (rng.zipf(zipf, n) % n_rows).astype(np.int32)
    cols = rng.integers(0, n_cols, n).astype(np.int32)
    vals = rng.random(n).astype(np.float32)
    return rows, cols, vals


def _device_side(rows, cols, vals, n_rows, split_above):
    counts = jnp.zeros(n_rows, jnp.int32).at[jnp.asarray(rows)].add(1)
    hist, n_over, n_part = degree_histogram(counts, split_above)
    plan = plan_buckets(hist, n_over, n_part, n_rows,
                        split_above=split_above, pad_rows_to=8)
    return build_buckets(jnp.asarray(rows), jnp.asarray(cols),
                         jnp.asarray(vals), plan)


class TestDeviceBucketEquivalence:
    @pytest.mark.parametrize("split_above", [64, 8192])
    def test_matches_host_oracle(self, split_above):
        rows, cols, vals = _coo()
        n_rows = 400
        host = bucket_by_length(rows.astype(np.int64), cols.astype(np.int64),
                                vals, n_rows, split_above=split_above,
                                pad_rows_to=8)
        plain, split = _device_side(rows, cols, vals, n_rows, split_above)
        host_plain = [p for p in host if not p.split]
        assert len(host_plain) == len(plain)
        for hp, dp in zip(host_plain, plain):
            idx, val, msk, rid = [np.asarray(x) for x in dp]
            hmap = {int(r): i for i, r in enumerate(hp.row_ids) if r >= 0}
            dmap = {int(r): i for i, r in enumerate(rid) if r >= 0}
            assert set(hmap) == set(dmap)
            for r in hmap:
                hi, di = hmap[r], dmap[r]
                assert np.array_equal(hp.indices[hi][hp.mask[hi]],
                                      idx[di][msk[di]])
                assert np.array_equal(hp.values[hi][hp.mask[hi]],
                                      val[di][msk[di]])
        host_split = [p for p in host if p.split]
        if not host_split:
            assert split is None
            return
        hs = host_split[0]
        assert len(split) == 1  # unchunked plan: one merged block
        didx, dval, dmsk, dseg, dent = [np.asarray(x) for x in split[0]]
        for e_h, ent_id in enumerate(hs.ent_ids):
            if ent_id < 0:
                continue
            h_rows = np.where(hs.seg_ids == e_h)[0]
            h_seq = np.concatenate(
                [hs.indices[r][hs.mask[r]] for r in h_rows])
            (e_d,) = np.where(dent == ent_id)
            d_rows = np.where(dseg == e_d[0])[0]
            d_seq = np.concatenate([didx[r][dmsk[r]] for r in d_rows])
            assert np.array_equal(h_seq, d_seq)

    def test_nnz_conserved(self):
        rows, cols, vals = _coo(seed=7)
        plain, split = _device_side(rows, cols, vals, 400, 64)
        tot = sum(int(np.asarray(p[2]).sum()) for p in plain)
        if split is not None:
            tot += sum(int(np.asarray(c[2]).sum()) for c in split)
        assert tot == len(rows)

    def test_no_split_when_all_short(self):
        rows = np.arange(100, dtype=np.int32)
        cols = np.arange(100, dtype=np.int32)
        vals = np.ones(100, np.float32)
        plain, split = _device_side(rows, cols, vals, 100, 4096)
        assert split is None
        assert sum(int(np.asarray(p[2]).sum()) for p in plain) == 100


class TestTrainWithDevicePrep:
    def test_train_converges_like_host_path(self):
        """Same data through both prep paths → same fit quality.

        Inits differ (host numpy rng vs device PRNG) so factors are not
        bitwise comparable; RMSE after a few sweeps must match closely.
        """
        rng = np.random.default_rng(0)
        n_u, n_i, n = 120, 80, 4000
        true_u = rng.standard_normal((n_u, 4))
        true_i = rng.standard_normal((n_i, 4))
        users = rng.integers(0, n_u, n)
        items = (rng.zipf(1.4, n) % n_i).astype(np.int64)
        ratings = np.sum(true_u[users] * true_i[items], axis=1).astype(
            np.float32)
        cfg_host = ALSConfig(rank=8, iterations=6, reg=0.05, seed=1,
                             device_prep=False, split_above=64)
        cfg_dev = ALSConfig(rank=8, iterations=6, reg=0.05, seed=1,
                            device_prep=True, split_above=64)
        m_host = train_als(users, items, ratings, n_u, n_i, cfg_host)
        m_dev = train_als(users, items, ratings, n_u, n_i, cfg_dev)
        r_host = rmse(m_host, users, items, ratings)
        r_dev = rmse(m_dev, users, items, ratings)
        assert abs(r_host - r_dev) < 0.05 * max(r_host, 0.1)

    def test_chunking_path(self):
        """A tiny max_block_floats forces bucket chunking on device."""
        rows, cols, vals = _coo(seed=5, n_rows=64, n_cols=64, n=6000,
                                zipf=1.2)
        cfg = ALSConfig(rank=8, iterations=2, reg=0.05, seed=1,
                        device_prep=True, split_above=32,
                        max_block_floats=1 << 14)
        m = train_als(rows, cols, vals, 64, 64, cfg)
        assert np.isfinite(np.asarray(m.user_factors)).all()
        assert np.isfinite(np.asarray(m.item_factors)).all()


class TestPlanShapeLockstep:
    def test_plan_bucket_shapes_match_build(self):
        """_plan_bucket_shapes (the loop pre-warm's shape oracle) must stay
        in lock-step with what the prep path actually emits — the pre-warm
        compiles the training loop from these shapes BEFORE prep runs, and
        a drift would silently turn the overlapped compile into a wasted
        one plus a second, serial compile."""
        from predictionio_tpu.models.als import (
            _plan_bucket_shapes, _plan_side, prepare_als_inputs,
        )

        rows, cols, vals = _coo(seed=7, n_rows=96, n_cols=64, n=9000,
                                zipf=1.2)
        cfg = ALSConfig(rank=8, iterations=1, seed=1, device_prep=True,
                        split_above=32, max_block_floats=1 << 14)
        inputs = prepare_als_inputs(rows, cols, vals, 96, 64, cfg)
        plan_u = _plan_side(jnp.asarray(rows, jnp.int32), 96, cfg)
        plan_i = _plan_side(jnp.asarray(cols, jnp.int32), 64, cfg)
        for plan, buckets, specs in (
                (plan_u, inputs.user_buckets, inputs.chunk_specs[0]),
                (plan_i, inputs.item_buckets, inputs.chunk_specs[1])):
            shapes, spec_pred = _plan_bucket_shapes(plan)
            assert spec_pred == specs
            assert len(shapes) == len(buckets)
            for pred, real in zip(shapes, buckets):
                assert pred[0] == real[0]  # kind
                assert len(pred) == len(real)
                for s, a in zip(pred[1:], real[1:]):
                    assert s.shape == a.shape, (s.shape, a.shape)
                    assert s.dtype == a.dtype, (s.dtype, a.dtype)
        # At least one merged bucket must have been exercised.
        assert any(b[0] == "merged" for b in inputs.user_buckets)

    def test_host_stats_match_device_stats(self):
        """_plan_side(host_rows=...) must yield the IDENTICAL BucketPlan
        to the device stats path — the plan keys the build/warm caches and
        any drift would silently compile two programs per dataset."""
        from predictionio_tpu.models.als import _plan_side

        rows, _, _ = _coo(seed=11, n_rows=200, n_cols=50, n=30_000, zipf=1.2)
        cfg = ALSConfig(rank=8, split_above=64, max_block_floats=1 << 14)
        dev_plan = _plan_side(jnp.asarray(rows, jnp.int32), 200, cfg)
        host_plan = _plan_side(jnp.asarray(rows, jnp.int32), 200, cfg,
                               host_rows=rows)
        assert dev_plan == host_plan

    def test_loop_warm_executable_delivered_and_used(self):
        """The plan-shape pre-warm must deliver a usable executable whose
        statics match what train_als_prepared resolves — otherwise the
        cold-start overlap silently degrades to a second compile."""
        from predictionio_tpu.models.als import (
            _resolve_loop_statics, prepare_als_inputs, train_als_prepared,
        )

        rows, cols, vals = _coo(seed=9, n_rows=64, n_cols=48, n=5000,
                                zipf=1.2)
        cfg = ALSConfig(rank=8, iterations=2, seed=1, device_prep=True,
                        split_above=32, max_block_floats=1 << 14)
        inputs = prepare_als_inputs(rows, cols, vals, 64, 48, cfg)
        assert inputs.loop_warm is not None
        warm = inputs.loop_warm.result(timeout=120)
        assert warm is not None, "pre-warm compile failed"
        statics, exe = warm
        live = _resolve_loop_statics(cfg, inputs.user_buckets,
                                     inputs.item_buckets, inputs.chunk_specs)
        assert statics == live == inputs.loop_warm_statics
        # and the train path accepts these inputs end-to-end
        m = train_als_prepared(inputs, cfg)
        assert np.isfinite(np.asarray(m.user_factors)).all()


# ---------------------------------------------------------------------------
# The dense block: which rows go to it, what it holds, and that everything
# else plans and builds as it did.
# ---------------------------------------------------------------------------

def _plan_from_degrees(deg, split_above=4096, **kw):
    deg = np.asarray(deg, np.int64)
    hist = np.bincount(np.minimum(deg, split_above),
                       minlength=split_above + 1)
    over = deg[deg > split_above]
    n_part = int(((over + split_above - 1) // split_above).sum())
    return plan_buckets(hist, len(over), n_part, len(deg),
                        split_above=split_above,
                        max_block_floats=1 << 20, rank=64,
                        over_degrees=over, **kw)


def _dense_block(rows, cols, vals, plan):
    return build_dense_block(rows, cols, vals, n_rows=plan.n_rows,
                             n_src=plan.dense_src, dense_min=plan.dense_min,
                             dense_rows=plan.dense_rows)


def _dense_exact_coo(seed=0, n_u=60, n_i=40):
    """No repeated pair, whole-number ratings (a 0.0 among them), a few
    heavy users and items over a sparse rest."""
    rng = np.random.default_rng(seed)
    pairs = [(u, i) for u in range(n_u) for i in range(n_i)
             if rng.random() < (0.8 if i < 5 or u < 6 else 0.1)]
    users = np.array([p[0] for p in pairs], np.int32)
    items = np.array([p[1] for p in pairs], np.int32)
    return users, items, rng.integers(0, 6, len(pairs)).astype(np.float32)


class TestDenseRule:
    # The Netflix cell's two sides scaled by 100 with their densities
    # kept: a head that rated half of the other side, a tail under 1%.
    N_SRC = 4800
    DEGREES = np.array([2329, 2100, 1800, 1500, 1200, 950, 700, 500, 400,
                        330, 280, 240, 200, 170, 150, 130, 110, 100, 90, 85,
                        80, 70, 60, 50, 40, 30, 25, 20, 15, 10, 8, 5, 3] * 2)

    def _row_bytes(self):
        from predictionio_tpu.ops.pallas_kernels import (
            DENSE_BLOCK_DTYPE, dense_block_width,
        )
        return dense_block_width(self.N_SRC) * np.dtype(
            DENSE_BLOCK_DTYPE).itemsize

    def _over_rho(self):
        from predictionio_tpu.ops.pallas_kernels import dense_row_density
        return int(np.ceil(dense_row_density(64, self.N_SRC) * self.N_SRC))

    @pytest.mark.parametrize("split_above", [4096, 256],
                             ids=["inside-histogram", "among-split-rows"])
    def test_every_row_over_the_density_goes_dense(self, split_above):
        from predictionio_tpu.ops.pallas_kernels import DENSE_TILE_R

        d_rho = self._over_rho()
        want = self.DEGREES[self.DEGREES >= d_rho]
        plan = _plan_from_degrees(self.DEGREES, split_above,
                                  n_src=self.N_SRC, dense_budget=1 << 30)
        assert plan.dense_min == d_rho
        assert plan.dense_rows == -(-len(want) // DENSE_TILE_R) * DENSE_TILE_R
        assert plan.dense_ratings == int(want.sum())
        assert plan.dense_src == self.N_SRC
        # what is left is planned as a side made of the other rows alone
        rest = _plan_from_degrees(self.DEGREES[self.DEGREES < d_rho],
                                  split_above)
        assert dataclasses.replace(
            plan, dense_min=0, dense_rows=0, dense_src=0, dense_ratings=0,
            n_rows=rest.n_rows) == rest

    @pytest.mark.parametrize("split_above", [4096, 256],
                             ids=["inside-histogram", "among-split-rows"])
    @pytest.mark.parametrize("drop", [0, 1], ids=["clean-cut", "tied-cut"])
    def test_densest_first_within_the_byte_budget(self, split_above, drop):
        """Room for one row tile where four tiles' worth pass the density:
        the densest rows go, the block stays inside the budget, and a
        class of equal degrees that straddles it stays out whole."""
        from predictionio_tpu.ops.pallas_kernels import DENSE_TILE_R

        # every degree DENSE_TILE_R / 2 times, so a tile ends between two
        # classes; with one row dropped it ends inside a class
        degrees = np.repeat(self.DEGREES[::2], DENSE_TILE_R // 2)[drop:]
        over = np.sort(degrees[degrees >= self._over_rho()])[::-1]
        assert len(over) > 4 * DENSE_TILE_R
        budget = DENSE_TILE_R * self._row_bytes() + 7
        plan = _plan_from_degrees(degrees, split_above, n_src=self.N_SRC,
                                  dense_budget=budget)
        chosen = over[over >= plan.dense_min]
        assert plan.dense_rows == DENSE_TILE_R
        assert plan.dense_rows * self._row_bytes() <= budget
        assert plan.dense_ratings == int(chosen.sum())
        assert np.array_equal(chosen, over[:len(chosen)])     # densest first
        if drop:
            # the tile would end inside the class of 1,800: it stays out
            assert plan.dense_min == 1801
            assert len(chosen) == DENSE_TILE_R - 1
        else:
            assert plan.dense_min == 2100 and len(chosen) == DENSE_TILE_R
        none = _plan_from_degrees(degrees, split_above, n_src=self.N_SRC,
                                  dense_budget=self._row_bytes())
        assert none.dense_rows == 0 and none.dense_min == 0

    @pytest.mark.parametrize("degrees,n_src", [
        # Amazon 2014: the most-rated product far under 1% of 21M users
        (np.r_[np.full(3, 25_000), np.full(40, 6_000),
               np.full(3000, 40), np.full(9000, 3)], 21_000_000),
        # every row the same, under the density
        (np.full(5000, 12), 4800),
    ], ids=["amazon-like", "uniform"])
    def test_no_row_over_the_density_plans_as_before(self, degrees, n_src):
        with_src = _plan_from_degrees(degrees, n_src=n_src)
        without = _plan_from_degrees(degrees)
        assert dataclasses.asdict(with_src) == dataclasses.asdict(without)
        assert with_src.dense_rows == 0

    def test_netflix_item_side_plans_fewer_dense_rows_than_the_budget_allows(
            self):
        """als-netflix-r64's items over its 480,189 users, on a chip of
        16 GB: since the user table is gathered through the packed view at
        nearly the fast rate, the density cuts the block, not the budget
        (which allows 3,488 rows and, at the slow gather's 0.78%, got
        them all); the users' side plans as it did."""
        import json
        import os

        from benchmark import ratings
        from predictionio_tpu.ops.pallas_kernels import (
            DENSE_BLOCK_DTYPE, DENSE_TILE_R, dense_block_width,
            dense_row_density,
        )

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(
                root, "benchmark", "configs", "als-netflix-r64.json")) as f:
            user_deg, item_deg = ratings.degree_sequences(json.load(f))
        n_users, n_items = len(user_deg), len(item_deg)
        budget = int(0.2 * 15.75 * 2 ** 30)
        row_bytes = dense_block_width(n_users) * np.dtype(
            DENSE_BLOCK_DTYPE).itemsize
        allowed = budget // row_bytes // DENSE_TILE_R * DENSE_TILE_R
        assert allowed == 3488
        items = _plan_from_degrees(item_deg, n_src=n_users,
                                   dense_budget=budget)
        d_rho = int(np.ceil(dense_row_density(64, n_users) * n_users))
        assert items.dense_min == d_rho
        assert int((item_deg >= d_rho).sum()) <= items.dense_rows \
            < allowed // 2
        assert items.dense_ratings == int(item_deg[item_deg >= d_rho].sum())
        # most of the side's ratings are still worth a product
        assert 0.5 < items.dense_ratings / item_deg.sum() < 0.8
        users = _plan_from_degrees(user_deg, n_src=n_items,
                                   dense_budget=budget)
        assert (users.dense_min, users.dense_rows) == (760, 23488)

    def test_rank_moves_the_threshold(self):
        p64 = _plan_from_degrees(self.DEGREES, n_src=self.N_SRC,
                                 dense_budget=1 << 30)
        p128 = plan_buckets(
            np.bincount(self.DEGREES, minlength=4097), 0, 0,
            len(self.DEGREES), split_above=4096, rank=128,
            n_src=self.N_SRC, dense_budget=1 << 30)
        assert p128.dense_min == pytest.approx(4 * p64.dense_min, abs=4)
        assert plan_buckets(
            np.bincount(self.DEGREES, minlength=4097), 0, 0,
            len(self.DEGREES), split_above=4096, rank=256,
            n_src=self.N_SRC, dense_budget=1 << 30).dense_rows == 0


class TestDenseBlockBuild:
    def _built(self, split_above=16):
        from predictionio_tpu.models.als import _plan_side

        users, items, vals = _dense_exact_coo()
        cfg = ALSConfig(rank=8, split_above=split_above,
                        max_block_floats=1 << 14)
        plan = _plan_side(jnp.asarray(users), 60, cfg, host_rows=users,
                          n_src=40)
        coo = (jnp.asarray(users), jnp.asarray(items), jnp.asarray(vals))
        return (users, items, vals, plan,
                (*build_buckets(*coo, plan), _dense_block(*coo, plan)))

    def test_block_holds_each_dense_rows_ratings_by_source_id(self):
        users, items, vals, plan, (plain, split, dense) = self._built()
        assert plan.dense_rows and dense is not None
        block, ent, deg, filled = [np.asarray(x) for x in dense]
        block = block.astype(np.float32)
        counts = np.bincount(users, minlength=60)
        real = ent >= 0
        assert set(ent[real]) == set(np.where(counts >= plan.dense_min)[0])
        assert int(filled) == plan.dense_ratings
        assert int(filled) == int(counts[ent[real]].sum())
        assert np.array_equal(deg[real], counts[ent[real]])
        assert np.isnan(block[~real]).all() and (deg[~real] == 0).all()
        for slot in np.where(real)[0]:
            mine = users == ent[slot]
            row = np.full(block.shape[1], np.nan, np.float32)
            row[items[mine]] = vals[mine]
            assert np.array_equal(block[slot], row, equal_nan=True)
        # a real rating of 0.0 is a zero in the block, not an absence
        assert (block == 0.0).sum() == (vals[np.isin(users, ent[real])]
                                        == 0.0).sum() > 0

    def test_every_rating_lands_once(self):
        users, _, _, plan, (plain, split, dense) = self._built()
        tot = sum(int(np.asarray(p[2]).sum()) for p in plain)
        if split is not None:
            tot += sum(int(np.asarray(c[2]).sum()) for c in split)
        assert tot + int(dense[3]) == len(users)
        assert tot == len(users) - plan.dense_ratings
        # no dense row is left in a sparse bucket
        dense_ids = set(np.asarray(dense[1])) - {-1}
        for p in plain:
            assert not dense_ids & set(np.asarray(p[3]))

    def test_repeated_pair_shows_in_the_filled_count(self):
        from predictionio_tpu.models.als import _plan_side

        users, items, vals = _dense_exact_coo()
        users, items, vals = (np.concatenate([a, a[:7]])
                              for a in (users, items, vals))
        cfg = ALSConfig(rank=8, split_above=16)
        plan = _plan_side(jnp.asarray(users), 60, cfg, host_rows=users,
                          n_src=40)
        dense = _dense_block(jnp.asarray(users), jnp.asarray(items),
                             jnp.asarray(vals), plan)
        assert int(dense[3]) == plan.dense_ratings - 7


class TestDensePrep:
    def _cfg(self, **kw):
        return ALSConfig(rank=8, iterations=3, reg=0.05, seed=1,
                         split_above=16, max_block_floats=1 << 14, **kw)

    def test_plan_bucket_shapes_match_build_with_dense_rows(self):
        from predictionio_tpu.models.als import (
            _plan_bucket_shapes, _plan_side, prepare_als_inputs,
        )

        users, items, vals = _dense_exact_coo()
        cfg = self._cfg(device_prep=True)
        inputs = prepare_als_inputs(users, items, vals, 60, 40, cfg)
        plan_u = _plan_side(jnp.asarray(users), 60, cfg, n_src=40)
        plan_i = _plan_side(jnp.asarray(items), 40, cfg, n_src=60)
        for plan, buckets, specs in (
                (plan_u, inputs.user_buckets, inputs.chunk_specs[0]),
                (plan_i, inputs.item_buckets, inputs.chunk_specs[1])):
            shapes, spec_pred = _plan_bucket_shapes(plan)
            assert spec_pred == specs
            assert [s[0] for s in shapes] == [b[0] for b in buckets]
            assert shapes[-1][0] == "dense"
            for pred, real in zip(shapes, buckets):
                assert len(pred) == len(real)
                for s, a in zip(pred[1:], real[1:]):
                    assert (s.shape, s.dtype) == (a.shape, a.dtype)
        assert inputs.gram_ratings == tuple(
            (p.dense_ratings, len(users) - p.dense_ratings)
            for p in (plan_u, plan_i))

    @pytest.mark.parametrize("implicit", [False, True],
                             ids=["explicit", "implicit"])
    def test_device_prep_with_dense_rows_trains_like_host_prep(self,
                                                               implicit):
        from predictionio_tpu.models.als import prepare_als_inputs

        users, items, vals = _dense_exact_coo(seed=4)
        dev = prepare_als_inputs(users, items, vals, 60, 40,
                                 self._cfg(device_prep=True,
                                           implicit=implicit))
        assert [b[0] for b in dev.user_buckets][-1] == "dense"
        assert [b[0] for b in dev.item_buckets][-1] == "dense"
        m_dev = train_als(users, items, vals, 60, 40,
                          self._cfg(device_prep=True, implicit=implicit))
        m_host = train_als(users, items, vals, 60, 40,
                           self._cfg(device_prep=False, implicit=implicit))
        np.testing.assert_allclose(np.asarray(m_dev.user_factors),
                                   np.asarray(m_host.user_factors),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(m_dev.item_factors),
                                   np.asarray(m_host.item_factors),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("spoil", ["repeated-pair", "inexact-rating",
                                       "nan-rating"])
    def test_ratings_a_block_cannot_hold_plan_all_sparse(self, spoil):
        """A repeated (user, item) pair, a rating the block's dtype would
        round, a NaN: the side is planned without dense rows, as before
        this kind existed, and every rating still counts."""
        from predictionio_tpu.models.als import prepare_als_inputs

        users, items, vals = _dense_exact_coo()
        if spoil == "repeated-pair":
            # in a dense row that is not its side's densest, so that the
            # plan's look at one row passes and the build finds it
            by_u, by_i = np.bincount(users), np.bincount(items)
            again = np.where((users != by_u.argmax()) & (by_u[users] > 20)
                             & (items != by_i.argmax()))[0][:5]
            users, items, vals = (np.concatenate([a, a[again]])
                                  for a in (users, items, vals))
        elif spoil == "inexact-rating":
            vals = vals + np.float32(1 / 3)
        else:
            vals = vals.copy()
            vals[3] = np.nan
        inputs = prepare_als_inputs(users, items, vals, 60, 40,
                                    self._cfg(device_prep=True))
        kinds = {b[0] for b in inputs.user_buckets + inputs.item_buckets}
        assert "dense" not in kinds
        assert inputs.gram_ratings == ((0, len(users)), (0, len(users)))

    def test_a_densest_row_that_repeats_a_pair_plans_all_sparse(self):
        """Views and plays repeat pairs, and there the most-seen item
        does: one look at the densest row keeps such data off the path
        that builds a block only to find it cannot hold the ratings."""
        from predictionio_tpu.models.als import _plan_side

        users, items, vals = _dense_exact_coo()
        cfg = self._cfg()
        clean = _plan_side(jnp.asarray(users), 60, cfg, host_rows=users,
                           n_src=40, host_cols=items)
        assert clean.dense_rows
        top = np.bincount(users).argmax()
        again = np.where(users == top)[0][:1]
        users2, items2 = (np.concatenate([a, a[again]])
                          for a in (users, items))
        spoiled = _plan_side(jnp.asarray(users2), 60, cfg, host_rows=users2,
                             n_src=40, host_cols=items2)
        assert spoiled.dense_rows == 0
        assert spoiled == _plan_side(jnp.asarray(users2), 60, cfg,
                                     host_rows=users2)
