"""Online learning (ISSUE 10): event-delta warm-start refresh, serve-time
ALS fold-in, and canaried continuous promotion.

Acceptance spine: ingest events → follow-mode refresh → the warm-started
generation serves measurably different (fresher) results than the prior
generation, promotion rides the staged-reload canary gate, an injected
divergent refresh is rejected/rolled back with the old generation still
serving, warm-start from the serialized carry is bitwise-equal to
continued training on CPU, and an ALS fold-in user receives
non-cold-start recommendations without a retrain.
"""

import datetime as dt
import json
import threading
import time
from urllib.request import Request, urlopen

import numpy as np
import pytest

from predictionio_tpu.controller import (
    EngineVariant,
    RuntimeContext,
    WarmStartFallback,
)
from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage import App, get_storage
from predictionio_tpu.refresh import (
    RefreshConfig,
    WarmStartContext,
    data_watermark,
    staleness_s,
)
from predictionio_tpu.refresh.daemon import (
    HttpPromoter,
    PromotionRejected,
    RefreshDaemon,
)
from predictionio_tpu.workflow.core_workflow import load_models, run_train

UTC = dt.timezone.utc


# -- engines ---------------------------------------------------------------

TT_VARIANT = {
    "id": "default",
    "engineFactory": "predictionio_tpu.templates.twotower:engine",
    "datasource": {"params": {"appName": "app"}},
    "algorithms": [{"name": "twotower",
                    "params": {"embedDim": 8, "hiddenDims": [16],
                               "outDim": 8, "epochs": 2, "batchSize": 32,
                               "seed": 1}}],
}

ALS_VARIANT = {
    "id": "default",
    "engineFactory": "predictionio_tpu.templates.recommendation:engine",
    "datasource": {"params": {"appName": "app"}},
    "algorithms": [{"name": "als",
                    "params": {"rank": 8, "numIterations": 6,
                               "lambda_": 0.01, "seed": 3}}],
}


@pytest.fixture()
def ctx(pio_home):
    return RuntimeContext.create(storage=get_storage())


def _mk_app(ctx, name="app"):
    storage = ctx.storage
    app_id = storage.get_apps().insert(App(id=None, name=name))
    storage.get_events().init(app_id)
    return app_id


def _view(u, i, when=None):
    kw = {"event_time": when} if when is not None else {}
    return Event(event="view", entity_type="user", entity_id=f"u{u}",
                 target_entity_type="item", target_entity_id=f"i{i}", **kw)


def _rate(u, i, rating, when=None):
    kw = {"event_time": when} if when is not None else {}
    return Event(event="rate", entity_type="user", entity_id=f"u{u}",
                 target_entity_type="item", target_entity_id=f"i{i}",
                 properties=DataMap({"rating": float(rating)}), **kw)


def _seed_clique_views(ctx, app_id, n_users=10, n_items=6):
    evs = [_view(u, i) for u in range(n_users) for i in range(n_items)
           if i % 2 == u % 2]
    ctx.storage.get_events().insert_batch(evs, app_id)
    return len(evs)


def _seed_clique_rates(ctx, app_id, n_users=12, n_items=8, seed=0):
    rng = np.random.default_rng(seed)
    evs = [_rate(u, i, 3 + 2 * rng.random())
           for u in range(n_users) for i in range(n_items)
           if i % 2 == u % 2]
    ctx.storage.get_events().insert_batch(evs, app_id)
    return len(evs)


def _tt():
    from predictionio_tpu.templates.twotower import engine

    return engine(), EngineVariant.from_dict(TT_VARIANT)


def _als():
    from predictionio_tpu.templates.recommendation import engine

    return engine(), EngineVariant.from_dict(ALS_VARIANT)


def _warm_ctx(ctx, eng, variant, instance, **kw):
    return WarmStartContext(
        instance=instance,
        models=load_models(eng, instance, ctx),
        start_time=data_watermark(instance),
        **kw)


# ==========================================================================
# Watermarks + windowed reads
# ==========================================================================

class TestWatermarkWindows:
    def test_full_train_records_watermark(self, ctx):
        app_id = _mk_app(ctx)
        _seed_clique_rates(ctx, app_id)
        eng, variant = _als()
        before = dt.datetime.now(UTC)
        iid = run_train(eng, variant, ctx)
        inst = ctx.storage.get_engine_instances().get(iid)
        assert inst.env["refreshMode"] == "full"
        wm = data_watermark(inst)
        assert wm is not None
        assert before <= wm <= dt.datetime.now(UTC)

    def test_until_bound_excludes_future_events(self, ctx):
        """An event stamped past the watermark belongs to the NEXT
        generation — the full read is until-bounded too."""
        app_id = _mk_app(ctx)
        _seed_clique_views(ctx, app_id)
        ctx.storage.get_events().insert(
            _view(0, 99, when=dt.datetime.now(UTC) + dt.timedelta(hours=1)),
            app_id)
        eng, variant = _tt()
        iid = run_train(eng, variant, ctx)
        w = load_models(eng, ctx.storage.get_engine_instances().get(iid),
                        ctx)[0]
        assert "i99" not in w.item_index

    def test_windows_chain_without_gap_or_overlap(self, ctx):
        """gen1 full + gen2 warm cover every event exactly once: the
        warm generation's example count equals the TOTAL corpus."""
        app_id = _mk_app(ctx)
        n1 = _seed_clique_views(ctx, app_id)
        eng, variant = _tt()
        iid1 = run_train(eng, variant, ctx)
        inst1 = ctx.storage.get_engine_instances().get(iid1)
        # delta: stamped between the two watermarks (ingest wall clock)
        delta = [_view(0, 9), _view(2, 9), _view(99, 9), _view(99, 0)]
        ctx.storage.get_events().insert_batch(delta, app_id)
        warm = _warm_ctx(ctx, eng, variant, inst1, eval_tolerance=10.0)
        iid2 = run_train(eng, variant, ctx, warm_from=warm)
        inst2 = ctx.storage.get_engine_instances().get(iid2)
        assert inst2.env["refreshMode"] == "warm"
        assert inst2.env["warmStartFrom"] == iid1
        w2 = load_models(eng, inst2, ctx)[0]
        assert w2.n_examples == n1 + len(delta)
        # fresher: entities first seen in the delta are servable now
        assert "u99" in w2.user_index and "i9" in w2.item_index

    def test_windowed_event_store_clamps_explicit_bounds(self, ctx):
        from predictionio_tpu.data.store import WindowedEventStore

        app_id = _mk_app(ctx)
        t0 = dt.datetime(2026, 1, 1, tzinfo=UTC)
        ctx.storage.get_events().insert_batch(
            [_view(1, 1, when=t0),
             _view(1, 2, when=t0 + dt.timedelta(days=1)),
             _view(1, 3, when=t0 + dt.timedelta(days=2))], app_id)
        win = WindowedEventStore(ctx.storage,
                                 t0 + dt.timedelta(hours=12),
                                 t0 + dt.timedelta(days=1, hours=12))
        # window applies when the caller passes no bounds
        assert [e.target_entity_id for e in win.find("app")] == ["i2"]
        # a caller bound OUTSIDE the window is clamped to it
        got = list(win.find("app", start_time=t0 - dt.timedelta(days=9),
                            until_time=t0 + dt.timedelta(days=9)))
        assert [e.target_entity_id for e in got] == ["i2"]
        # a NARROWER caller bound inside the window is kept
        got = list(win.find("app",
                            until_time=t0 + dt.timedelta(hours=13)))
        assert got == []
        assert win.find_columnar("app").num_rows == 1

    def test_windowed_aggregate_properties_is_cumulative(self, ctx):
        """$set/$unset state accumulates from t=0: a delta-scoped read
        must still see properties written BEFORE the window (only the
        until bound applies) — otherwise a warm run's datasource sees
        phantom-empty entities."""
        from predictionio_tpu.data.store import WindowedEventStore

        app_id = _mk_app(ctx)
        t0 = dt.datetime(2026, 1, 1, tzinfo=UTC)
        ctx.storage.get_events().insert_batch([
            Event(event="$set", entity_type="item", entity_id="i1",
                  properties=DataMap({"color": "red"}), event_time=t0),
            Event(event="$set", entity_type="item", entity_id="i2",
                  properties=DataMap({"color": "blue"}),
                  event_time=t0 + dt.timedelta(days=2)),
        ], app_id)
        win = WindowedEventStore(ctx.storage,
                                 t0 + dt.timedelta(days=1),
                                 t0 + dt.timedelta(days=3))
        props = win.aggregate_properties("app", "item")
        assert set(props) == {"i1", "i2"}, \
            "pre-window $set state must stay visible"
        # the until bound still applies
        early = WindowedEventStore(ctx.storage, t0 + dt.timedelta(days=1),
                                   t0 + dt.timedelta(days=1, hours=1))
        assert set(early.aggregate_properties("app", "item")) == {"i1"}


# ==========================================================================
# Warm-start bitwise + state growth
# ==========================================================================

class TestWarmStartState:
    def _data(self, rng, n, n_users=20, n_items=12):
        return (rng.integers(0, n_users, n).astype(np.int64),
                rng.integers(0, n_items, n).astype(np.int64))

    def test_host_roundtrip_continuation_is_bitwise(self, pio_home):
        """Acceptance pin: continuing training from the SERIALIZED carry
        (host-numpy snapshot, what the wrapper pickles) is bitwise what
        continuing in-process would produce — the checkpoint loses
        nothing."""
        from predictionio_tpu.models import two_tower as tt

        cfg = tt.TwoTowerConfig(n_users=20, n_items=12, embed_dim=8,
                                hidden_dims=(16,), out_dim=8,
                                batch_size=16, epochs=1, seed=7)
        rng = np.random.default_rng(0)
        u1, i1 = self._data(rng, 96)
        u2, i2 = self._data(rng, 48)
        base = tt.train(u1, i1, cfg)
        snap = tt.state_to_host(base)
        # in-process continuation
        a = tt.train(u2, i2, cfg, warm_state=tt.state_from_host(
            tt.state_to_host(base)))
        # continuation from the serialized snapshot (fresh buffers)
        b = tt.train(u2, i2, cfg, warm_state=tt.state_from_host(snap))
        import jax

        for la, lb in zip(jax.tree.leaves(a.params),
                          jax.tree.leaves(b.params)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        # 96/16 = 6 base steps + 48/16 = 3 continuation steps
        assert int(a.step) == int(b.step) == int(base.step) + 3 == 9

    def test_grow_state_preserves_rows_and_moments(self, pio_home):
        import dataclasses as dc

        import jax

        from predictionio_tpu.models import two_tower as tt

        cfg = tt.TwoTowerConfig(n_users=10, n_items=6, embed_dim=8,
                                hidden_dims=(16,), out_dim=8,
                                batch_size=16, epochs=1, seed=7)
        rng = np.random.default_rng(1)
        u, i = self._data(rng, 64, 10, 6)
        st = tt.train(u, i, cfg)
        grown_cfg = dc.replace(cfg, n_users=13, n_items=7)
        g = tt.grow_state(tt.state_from_host(tt.state_to_host(st)),
                          grown_cfg)
        assert g.params["user_embed"].shape == (13, 8)
        assert g.params["item_embed"].shape == (7, 8)
        np.testing.assert_array_equal(
            np.asarray(g.params["user_embed"][:10]),
            np.asarray(st.params["user_embed"]))
        # optimizer moments: old rows carried, new rows zero, step kept
        mus_old = [x for x in jax.tree.leaves(st.opt_state)
                   if getattr(x, "shape", ()) == (10, 8)]
        mus_new = [x for x in jax.tree.leaves(g.opt_state)
                   if getattr(x, "shape", ()) == (13, 8)]
        assert mus_old and len(mus_old) == len(mus_new)
        for old, new in zip(mus_old, mus_new):
            np.testing.assert_array_equal(np.asarray(new[:10]),
                                          np.asarray(old))
            assert not np.asarray(new[10:]).any()
        assert int(g.step) == int(st.step)
        # growing to the SAME sizes is the identity
        same = tt.grow_state(tt.state_from_host(tt.state_to_host(st)), cfg)
        np.testing.assert_array_equal(np.asarray(same.params["user_embed"]),
                                      np.asarray(st.params["user_embed"]))


# ==========================================================================
# Fallback gates
# ==========================================================================

class TestWarmFallbacks:
    def _gen1(self, ctx):
        app_id = _mk_app(ctx)
        _seed_clique_views(ctx, app_id)
        eng, variant = _tt()
        iid = run_train(eng, variant, ctx)
        inst = ctx.storage.get_engine_instances().get(iid)
        ctx.storage.get_events().insert_batch(
            [_view(0, 9), _view(99, 9)], app_id)
        return app_id, eng, variant, inst

    @staticmethod
    def _walk_spans(doc):
        stack = [doc]
        while stack:
            d = stack.pop()
            yield d
            stack.extend(d.get("spans", []))

    def _assert_fallback(self, ctx, eng, variant, warm, reason_fragment):
        iid = run_train(eng, variant, ctx, warm_from=warm)
        inst = ctx.storage.get_engine_instances().get(iid)
        assert inst.status == "COMPLETED"
        assert inst.env["refreshMode"] == "full_fallback"
        from predictionio_tpu.obs import get_recorder

        # the fallback annotation attaches inside the workflow.train
        # trace tree (publish_event child-span semantics)
        events = [s for doc in get_recorder().recent(50)
                  for s in self._walk_spans(doc)
                  if s["name"] == "refresh.warm_fallback"]
        assert events, "fallback must land a trace event"
        assert reason_fragment in events[-1]["attrs"]["reason"]
        return inst

    def _als_gen1(self, ctx):
        app_id = _mk_app(ctx)
        _seed_clique_rates(ctx, app_id)
        eng, variant = _als()
        iid1 = run_train(eng, variant, ctx)
        inst1 = ctx.storage.get_engine_instances().get(iid1)
        ctx.storage.get_events().insert(_rate(0, 1, 5.0), app_id)
        return app_id, eng, variant, inst1

    def test_als_rank_change_falls_back(self, ctx):
        app_id, eng, variant, inst1 = self._als_gen1(ctx)
        warm = _warm_ctx(ctx, eng, variant, inst1)
        v2 = json.loads(json.dumps(ALS_VARIANT))
        v2["algorithms"][0]["params"]["rank"] = 16
        inst2 = self._assert_fallback(ctx, eng, EngineVariant.from_dict(v2),
                                      warm, "config changed")
        # the fallback still covers the delta: it IS a fresh full corpus
        assert data_watermark(inst2) > data_watermark(inst1)

    def test_als_eval_regression_falls_back(self, ctx):
        # tolerance -1 → allowed regression threshold 0: the sweep's
        # residual on the delta sample reads as a regression — pins the
        # ALS eval gate path itself
        app_id, eng, variant, inst1 = self._als_gen1(ctx)
        warm = _warm_ctx(ctx, eng, variant, inst1, eval_tolerance=-1.0)
        self._assert_fallback(ctx, eng, variant, warm, "regressed")

    def test_als_unsized_carry_falls_back(self, ctx):
        """A pre-ISSUE-17 pickle has no n_examples — the fraction gate
        cannot be computed, so the carry declines instead of guessing."""
        app_id, eng, variant, inst1 = self._als_gen1(ctx)
        warm = _warm_ctx(ctx, eng, variant, inst1)
        warm.models[0].n_examples = 0
        self._assert_fallback(ctx, eng, variant, warm, "vs 0 trained")

    def test_oversized_delta_falls_back(self, ctx):
        app_id, eng, variant, inst = self._gen1(ctx)
        warm = _warm_ctx(ctx, eng, variant, inst, max_delta_fraction=0.0)
        self._assert_fallback(ctx, eng, variant, warm, "too large")

    def test_eval_regression_falls_back(self, ctx):
        app_id, eng, variant, inst = self._gen1(ctx)
        # a diverse delta (distinct items → nonzero in-batch loss), and
        # tolerance -1 → allowed regression threshold is 0: any positive
        # post-continuation loss reads as a regression — the gate path
        # itself is what this pins
        ctx.storage.get_events().insert_batch(
            [_view(u, i) for u, i in ((1, 0), (3, 2), (5, 4), (7, 1))],
            app_id)
        warm = _warm_ctx(ctx, eng, variant, inst, eval_tolerance=-1.0)
        self._assert_fallback(ctx, eng, variant, warm, "regressed")

    def test_config_change_falls_back(self, ctx):
        app_id, eng, variant, inst = self._gen1(ctx)
        warm = _warm_ctx(ctx, eng, variant, inst)
        v2 = json.loads(json.dumps(TT_VARIANT))
        v2["algorithms"][0]["params"]["embedDim"] = 16
        self._assert_fallback(ctx, eng, EngineVariant.from_dict(v2), warm,
                              "config changed")

    def test_missing_carry_falls_back(self, ctx):
        app_id, eng, variant, inst = self._gen1(ctx)
        warm = _warm_ctx(ctx, eng, variant, inst)
        warm.models[0].train_state = None
        self._assert_fallback(ctx, eng, variant, warm, "no train state")

    def test_mixed_engine_is_all_or_nothing(self, ctx):
        """One algorithm declining aborts the WHOLE warm attempt — a
        generation is one consistent data window."""
        eng, variant = _tt()
        app_id = _mk_app(ctx)
        _seed_clique_views(ctx, app_id)
        iid = run_train(eng, variant, ctx)
        inst = ctx.storage.get_engine_instances().get(iid)
        ctx.storage.get_events().insert(_view(0, 1), app_id)
        warm = _warm_ctx(ctx, eng, variant, inst, eval_tolerance=10.0)

        class Declines:
            def warm_start(self, *a, **k):
                raise WarmStartFallback("nope")

        # engine.train with warm must propagate the fallback, not return
        # a half-warm model list
        params = eng.bind_engine_params(variant.raw)
        warm.models = [warm.models[0]]
        real = eng.make_algorithms

        def fake_algos(ep):
            return [Declines()]

        eng.make_algorithms = fake_algos
        try:
            with pytest.raises(WarmStartFallback):
                eng.train(RuntimeContext.create(storage=ctx.storage),
                          params, warm=warm)
        finally:
            eng.make_algorithms = real


# ==========================================================================
# ALS delta warm-start (ISSUE 17)
# ==========================================================================

class TestALSWarmStart:
    def _gen1(self, ctx):
        app_id = _mk_app(ctx)
        _seed_clique_rates(ctx, app_id)
        eng, variant = _als()
        iid = run_train(eng, variant, ctx)
        inst = ctx.storage.get_engine_instances().get(iid)
        return app_id, eng, variant, inst

    def test_warm_refresh_moves_only_delta_touched_rows(self, ctx):
        """Factor-init + reduced-sweep retrain end-to-end: the warm
        generation completes as ``warm``, the delta-touched user's factor
        row moves, every untouched row carries over bit-for-bit, and the
        new taste is immediately servable."""
        app_id, eng, variant, inst1 = self._gen1(ctx)
        models1 = load_models(eng, inst1, ctx)
        algo = eng.make_algorithms(eng.bind_engine_params(variant.raw))[0]
        # u0 (even clique) suddenly loves ODD items, hard
        ctx.storage.get_events().insert_batch(
            [_rate(0, 1, 5.0), _rate(0, 3, 5.0), _rate(0, 5, 5.0)], app_id)
        warm = _warm_ctx(ctx, eng, variant, inst1)
        iid2 = run_train(eng, variant, ctx, warm_from=warm)
        inst2 = ctx.storage.get_engine_instances().get(iid2)
        assert inst2.status == "COMPLETED"
        assert inst2.env["refreshMode"] == "warm"
        assert data_watermark(inst2) > data_watermark(inst1)
        w1, w2 = models1[0], load_models(eng, inst2, ctx)[0]
        uf1, if1 = w1.host_factors()
        uf2, if2 = w2.host_factors()
        u_rows = dict(w1.user_index.items())
        i_rows = dict(w1.item_index.items())
        moved_u = {u_rows["u0"]}
        moved_i = {i_rows[f"i{j}"] for j in (1, 3, 5)}
        for r in range(uf1.shape[0]):
            if r in moved_u:
                assert not np.array_equal(uf2[r], uf1[r])
            else:
                np.testing.assert_array_equal(uf2[r], uf1[r])
        for r in range(if1.shape[0]):
            if r not in moved_i:
                np.testing.assert_array_equal(if2[r], if1[r])
        assert w2.n_examples == w1.n_examples + 3
        # the new taste serves: an odd item reaches u0's top-3
        from predictionio_tpu.templates.recommendation import Query

        top = algo.predict(w2, Query(user="u0", num=3)).itemScores
        assert any(int(s.item[1:]) % 2 == 1 for s in top)

    def test_warm_refresh_grows_union_index_for_new_entities(self, ctx):
        """Delta-new user AND item get fresh appended rows; the new user
        is non-cold immediately after the warm refresh."""
        app_id, eng, variant, inst1 = self._gen1(ctx)
        ctx.storage.get_events().insert_batch(
            [_rate(99, 0, 5.0), _rate(99, 2, 5.0),
             _rate(99, 99, 4.0)], app_id)  # u99 and i99 are brand new
        warm = _warm_ctx(ctx, eng, variant, inst1)
        iid2 = run_train(eng, variant, ctx, warm_from=warm)
        inst2 = ctx.storage.get_engine_instances().get(iid2)
        assert inst2.env["refreshMode"] == "warm"
        w1 = load_models(eng, inst1, ctx)[0]
        w2 = load_models(eng, inst2, ctx)[0]
        assert "u99" in dict(w2.user_index.items())
        assert "i99" in dict(w2.item_index.items())
        # union-extend: previous ids keep their exact rows
        assert dict(w2.user_index.items())["u99"] == len(w1.user_index)
        for key, row in w1.item_index.items():
            assert dict(w2.item_index.items())[key] == row
        algo = eng.make_algorithms(eng.bind_engine_params(variant.raw))[0]
        from predictionio_tpu.templates.recommendation import Query

        res = algo.predict(w2, Query(user="u99", num=4))
        assert len(res.itemScores) == 4  # non-cold without a full retrain


# ==========================================================================
# ALS serve-time fold-in
# ==========================================================================

class TestFoldIn:
    def _trained(self, ctx):
        app_id = _mk_app(ctx)
        _seed_clique_rates(ctx, app_id)
        eng, variant = _als()
        iid = run_train(eng, variant, ctx)
        inst = ctx.storage.get_engine_instances().get(iid)
        models = load_models(eng, inst, ctx)  # post_load attaches events
        algo = eng.make_algorithms(eng.bind_engine_params(ALS_VARIANT))[0]
        return app_id, eng, variant, models[0], algo

    def test_fold_in_matches_training_solve(self, pio_home):
        """fold_in of a training user's OWN events against the final item
        factors lands close to that user's trained factor (the same
        normal equation the last user sweep solved)."""
        from predictionio_tpu.models import als as als_lib

        rng = np.random.default_rng(0)
        n_u, n_i, d = 30, 20, 400
        us = rng.integers(0, n_u, d)
        its = rng.integers(0, n_i, d)
        rs = rng.integers(1, 6, d).astype(np.float32)
        cfg = als_lib.ALSConfig(rank=8, iterations=12, reg=0.05, seed=1)
        model = als_lib.train_als(us, its, rs, n_u, n_i, cfg)
        itf = np.asarray(model.item_factors)
        uf = np.asarray(model.user_factors)
        sel = us == 3
        vec = als_lib.fold_in(itf, its[sel], rs[sel], reg=cfg.reg)
        cos = float(vec @ uf[3] /
                    (np.linalg.norm(vec) * np.linalg.norm(uf[3]) + 1e-12))
        assert cos > 0.98, cos

    def test_unseen_user_gets_non_cold_start_recs(self, ctx):
        from predictionio_tpu.obs import get_registry
        from predictionio_tpu.templates.recommendation import Query

        app_id, eng, variant, w, algo = self._trained(ctx)
        for i in (0, 2, 4):
            ctx.storage.get_events().insert(_rate("new", i, 5.0), app_id)
        # fold-in user replaces their cold-start empty answer
        res = algo.batch_predict(w, [(0, Query(user="unew", num=4))])
        scores = res[0][1].itemScores
        assert scores, "fold-in user must receive recommendations"
        even = sum(1 for s in scores if int(s.item[1:]) % 2 == 0)
        assert even >= 3, scores
        # repeat visitor rides the cache — no second solve
        algo.batch_predict(w, [(0, Query(user="unew", num=4))])
        c = get_registry().get("pio_fold_in_total")
        assert c.value(result="solved") == 1
        assert c.value(result="cached") >= 1

    def test_user_with_no_events_stays_cold(self, ctx):
        from predictionio_tpu.obs import get_registry
        from predictionio_tpu.templates.recommendation import Query

        app_id, eng, variant, w, algo = self._trained(ctx)
        res = algo.batch_predict(w, [(0, Query(user="ughost", num=4))])
        assert res[0][1].itemScores == []
        c = get_registry().get("pio_fold_in_total")
        assert c.value(result="no_events") == 1
        # the negative outcome is cached too: a repeat unknown-user query
        # must not pay a second event-store read on the serving path
        res = algo.batch_predict(w, [(0, Query(user="ughost", num=4))])
        assert res[0][1].itemScores == []
        assert c.value(result="no_events") == 1
        assert c.value(result="cached") >= 1

    def test_fold_in_off_switch(self, ctx, monkeypatch):
        from predictionio_tpu.templates.recommendation import Query

        app_id, eng, variant, w, algo = self._trained(ctx)
        ctx.storage.get_events().insert(_rate("new", 0, 5.0), app_id)
        monkeypatch.setenv("PIO_FOLD_IN", "off")
        res = algo.batch_predict(w, [(0, Query(user="unew", num=4))])
        assert res[0][1].itemScores == []

    def test_cache_is_bounded(self, ctx, monkeypatch):
        from predictionio_tpu.templates.recommendation import Query

        app_id, eng, variant, w, algo = self._trained(ctx)
        for uname in ("a", "b", "c"):
            ctx.storage.get_events().insert(_rate(uname, 0, 4.0), app_id)
        monkeypatch.setenv("PIO_FOLD_IN_CACHE", "2")
        for uname in ("a", "b", "c"):
            algo.batch_predict(w, [(0, Query(user=f"u{uname}", num=2))])
        assert len(w._fold_cache) == 2

    def test_fold_cache_does_not_survive_pickle(self, ctx):
        import pickle

        app_id, eng, variant, w, algo = self._trained(ctx)
        ctx.storage.get_events().insert(_rate("new", 0, 4.0), app_id)
        assert w.fold_in_user("unew") is not None
        clone = pickle.loads(pickle.dumps(w))
        assert len(clone._fold_cache) == 0
        assert getattr(clone, "_event_store", None) is None


# ==========================================================================
# Daemon + canaried promotion
# ==========================================================================

class _FakePromoter:
    canary_window_s = 1.0

    def __init__(self, verdict="promoted", ctx=None):
        self.promoted = []
        self.watched = 0
        self.verdict = verdict
        self.ctx = ctx

    def promote(self, instance_id):
        self.promoted.append(instance_id)
        return {"engineInstanceId": instance_id}

    def canary_watch(self):
        self.watched += 1
        return self.verdict

    def served_watermark(self):
        # mirrors a live server that loaded what promote() was given
        if self.ctx is None or not self.promoted:
            return None
        inst = self.ctx.storage.get_engine_instances().get(
            self.promoted[-1])
        return data_watermark(inst) if inst else None


class TestDaemon:
    def _daemon(self, ctx, eng, variant, **kw):
        return RefreshDaemon(eng, variant, ctx,
                             config=RefreshConfig(interval_s=0.01), **kw)

    def test_cycle_trains_promotes_and_publishes(self, ctx):
        from predictionio_tpu.obs import get_registry

        app_id = _mk_app(ctx)
        _seed_clique_rates(ctx, app_id)
        eng, variant = _als()
        promoter = _FakePromoter(ctx=ctx)
        d = self._daemon(ctx, eng, variant, promoter=promoter)
        out1 = d.run_once()
        assert out1["result"] == "full"          # no previous generation
        assert promoter.promoted == [out1["instance"]]
        ctx.storage.get_events().insert(_rate(0, 1, 5.0), app_id)
        out2 = d.run_once()
        assert out2["result"] == "warm"  # ALS continues the generation
        assert promoter.promoted[-1] == out2["instance"]
        assert promoter.watched == 2
        reg = get_registry()
        runs = reg.get("pio_refresh_runs_total")
        assert runs.value(result="full") == 1
        assert runs.value(result="warm") == 1
        promos = reg.get("pio_refresh_promotions_total")
        assert promos.value(result="promoted") == 2
        # staleness gauge: everything ingested before the watermark is
        # servable → 0
        assert reg.get("pio_refresh_staleness_s").value() == 0.0

    def test_failed_cycle_records_and_continues(self, ctx, monkeypatch):
        from predictionio_tpu.obs import get_registry

        app_id = _mk_app(ctx)
        eng, variant = _als()   # no events → the datasource raises
        promoter = _FakePromoter()
        d = self._daemon(ctx, eng, variant, promoter=promoter)
        out = d.run_once()
        assert out["result"] == "failed"
        assert promoter.promoted == []
        assert get_registry().get("pio_refresh_runs_total") \
            .value(result="failed") == 1

    def test_follow_paces_and_stops(self, ctx):
        app_id = _mk_app(ctx)
        _seed_clique_rates(ctx, app_id)
        eng, variant = _als()
        d = self._daemon(ctx, eng, variant)
        waits = []

        def fake_sleep(s):
            waits.append(s)
            if len(waits) >= 2:
                d.stop()

        cycles = d.follow(sleep=fake_sleep)
        # cycle, sleep, cycle, sleep(sets stop) → loop exits at the check
        assert cycles == 2 and len(waits) == 2
        assert all(w >= 0 for w in waits)

    def test_staleness_reports_served_not_trained_on_rollback(self, ctx):
        """A rejected/rolled-back promotion leaves the OLD watermark
        serving — the staleness gauge must report that gap, not the
        freshness of the instance nobody serves."""
        from predictionio_tpu.obs import get_registry

        app_id = _mk_app(ctx)
        _seed_clique_rates(ctx, app_id)
        eng, variant = _als()
        promoter = _FakePromoter(verdict="rolled_back", ctx=ctx)
        d = self._daemon(ctx, eng, variant, promoter=promoter)
        out1 = d.run_once()
        old_wm = data_watermark(
            ctx.storage.get_engine_instances().get(out1["instance"]))
        # pin the "server" to generation 1 regardless of later promotes
        promoter.served_watermark = lambda: old_wm
        ctx.storage.get_events().insert(_rate(0, 1, 5.0), app_id)
        out2 = d.run_once()
        assert out2["promotion"] == "rolled_back"
        s = get_registry().get("pio_refresh_staleness_s").value()
        assert s > 0.0, "gauge must show the served (old) generation's gap"

    def test_staleness_measures_unservable_ingest(self, ctx):
        app_id = _mk_app(ctx)
        _seed_clique_rates(ctx, app_id)
        eng, variant = _als()
        d = self._daemon(ctx, eng, variant)
        out = d.run_once()
        inst = ctx.storage.get_engine_instances().get(out["instance"])
        # events landing AFTER the promoted watermark are not servable
        late = dt.datetime.now(UTC) + dt.timedelta(seconds=0)
        ctx.storage.get_events().insert(_rate(0, 1, 5.0, when=late), app_id)
        d._publish_staleness(inst)
        from predictionio_tpu.obs import get_registry

        s = get_registry().get("pio_refresh_staleness_s").value()
        assert s > 0.0
        # unit helper semantics
        assert staleness_s(None, dt.datetime.now(UTC)) is None
        t = dt.datetime.now(UTC)
        assert staleness_s(t, t + dt.timedelta(seconds=5)) == 0.0
        assert staleness_s(t + dt.timedelta(seconds=5), t) == 5.0


def _http(base, method, path):
    req = Request(base + path, method=method)
    with urlopen(req, timeout=10) as resp:
        return resp.status, json.loads(resp.read() or b"{}")


class TestServerPromotionE2E:
    """The acceptance spine against a LIVE engine server over HTTP."""

    def _server(self, ctx, eng, variant):
        from predictionio_tpu.server import EngineServer

        srv = EngineServer(eng, variant, ctx.storage, host="127.0.0.1",
                           port=0)
        srv.start(block=False)
        return srv, f"http://127.0.0.1:{srv.port}"

    def test_warm_refresh_promotes_and_serves_fresher_results(self, ctx):
        """ingest → refresh → the warm generation, promoted through the
        canary gate, serves entities the old generation could not."""
        app_id = _mk_app(ctx)
        _seed_clique_views(ctx, app_id)
        eng, variant = _tt()
        run_train(eng, variant, ctx)
        srv, base = self._server(ctx, eng, variant)
        try:
            st, body = _http(base, "GET", "/")
            gen1 = body["modelGeneration"]
            wm1 = body["dataWatermark"]
            assert wm1 is not None
            # the not-yet-refreshed server cold-starts the new user
            st, body = _http_query(base, {"user": "u99", "num": 3})
            assert st == 200 and body["itemScores"] == []
            # ingest the delta: new user u99 + new item i9
            ctx.storage.get_events().insert_batch(
                [_view(0, 9), _view(2, 9), _view(99, 9), _view(99, 0)],
                app_id)
            cfg = RefreshConfig(interval_s=0.01, eval_tolerance=10.0,
                                canary_window_s=0.0)
            promoter = HttpPromoter(base, canary_window_s=0.0)
            d = RefreshDaemon(eng, variant, ctx, config=cfg,
                              promoter=promoter)
            out = d.run_once()
            assert out["result"] == "warm"
            assert out["promotion"] == "promoted"
            st, body = _http(base, "GET", "/")
            assert body["modelGeneration"] == gen1 + 1
            assert body["engineInstanceId"] == out["instance"]
            assert body["dataWatermark"] > wm1
            assert body["refreshMode"] == "warm"
            # fresher answers: the delta user now gets their delta item
            st, body = _http_query(base, {"user": "u99", "num": 3})
            assert st == 200
            items = [s["item"] for s in body["itemScores"]]
            assert "i9" in items
        finally:
            srv.stop()

    def test_promotion_under_a_live_drive_answers_every_query_in_time(
            self, ctx):
        """The loop with both servers live: a delta POSTed to the event
        server, one refresh cycle promoting through the canary window
        while clients keep querying with a deadline.  Every query across
        train, swap and canary is a 200, none of them one the server
        itself attests as late, and what is served afterwards is as
        fresh as the delta."""
        from urllib.error import HTTPError

        from predictionio_tpu.data.storage import AccessKey
        from predictionio_tpu.server import EventServer

        app_id = _mk_app(ctx)
        _seed_clique_views(ctx, app_id)
        eng, variant = _tt()
        run_train(eng, variant, ctx)
        srv, base = self._server(ctx, eng, variant)
        key = ctx.storage.get_access_keys().insert(
            AccessKey(key="", app_id=app_id))
        evsrv = EventServer(storage=ctx.storage, host="127.0.0.1", port=0)
        evsrv.start()
        stop = threading.Event()
        outcomes = []  # (status, the server's own remaining budget)

        def drive(i):
            k = i
            while not stop.is_set():
                req = Request(
                    base + "/queries.json", method="POST",
                    data=json.dumps({"user": f"u{k % 10}",
                                     "num": 3}).encode(),
                    headers={"Content-Type": "application/json",
                             "X-PIO-Deadline-Ms": "20000"})
                try:
                    with urlopen(req, timeout=30) as resp:
                        outcomes.append((resp.status, float(
                            resp.headers["X-PIO-Deadline-Remaining-Ms"])))
                except HTTPError as e:
                    outcomes.append((e.code, None))
                k += 1

        drivers = [threading.Thread(target=drive, args=(i,), daemon=True)
                   for i in range(3)]
        try:
            _, body = _http(base, "GET", "/")
            gen1, wm1 = body["modelGeneration"], body["dataWatermark"]
            delta = [{"event": "view", "entityType": "user",
                      "entityId": f"u{u}", "targetEntityType": "item",
                      "targetEntityId": "i9"} for u in (0, 2, 99)]
            req = Request(
                f"http://127.0.0.1:{evsrv.port}/batch/events.json"
                f"?accessKey={key}", data=json.dumps(delta).encode(),
                method="POST", headers={"Content-Type": "application/json"})
            with urlopen(req, timeout=10) as resp:
                assert [r["status"] for r in json.loads(resp.read())] \
                    == [201] * 3
            latest = ctx.storage.get_events().latest_event_time(app_id)
            for t in drivers:
                t.start()
            d = RefreshDaemon(
                eng, variant, ctx,
                config=RefreshConfig(interval_s=0.01, eval_tolerance=10.0),
                promoter=HttpPromoter(base, canary_window_s=0.5,
                                      canary_poll_s=0.05))
            out = d.run_once()
            assert out["promotion"] == "promoted"
            before = len(outcomes)
            deadline = time.monotonic() + 10.0
            while len(outcomes) < before + 6 and time.monotonic() < deadline:
                time.sleep(0.01)  # a few answers from the new generation
            _, body = _http(base, "GET", "/")
            assert body["modelGeneration"] == gen1 + 1
            assert body["dataWatermark"] > wm1
            assert staleness_s(latest, dt.datetime.fromisoformat(
                body["dataWatermark"])) == 0.0
        finally:
            stop.set()
            for t in drivers:
                t.join(timeout=30)
            evsrv.stop()
            srv.stop()
        assert len(outcomes) >= 12
        assert {s for s, _ in outcomes} == {200}, outcomes
        assert min(rem for _, rem in outcomes) >= 0.0

    def test_divergent_refresh_is_rejected_old_generation_serves(
            self, ctx, monkeypatch):
        """Injected divergent refresh: the staged-reload gate rejects the
        NaN candidate (409) and the old generation keeps answering."""
        app_id = _mk_app(ctx)
        _seed_clique_rates(ctx, app_id)
        eng, variant = _als()
        run_train(eng, variant, ctx)
        srv, base = self._server(ctx, eng, variant)
        try:
            serving_before = srv._instance.id
            ctx.storage.get_events().insert(_rate(0, 1, 5.0), app_id)
            # poison the SERVER's candidate load: whatever the refresh
            # trained comes up non-finite — the validation stage must
            # catch it at the gate
            from predictionio_tpu.server import engine_server as es_mod

            real_load = es_mod.load_models

            def poisoned(engine, instance, c=None):
                models = real_load(engine, instance, c)
                uf = np.asarray(models[0].model.user_factors).copy()
                uf[0, 0] = np.nan
                models[0].model.user_factors = uf
                return models

            monkeypatch.setattr(es_mod, "load_models", poisoned)
            promoter = HttpPromoter(base, canary_window_s=0.0)
            d = RefreshDaemon(eng, variant, ctx,
                              config=RefreshConfig(interval_s=0.01),
                              promoter=promoter)
            out = d.run_once()
            assert out["promotion"] == "rejected"
            assert srv._instance.id == serving_before
            st, body = _http_query(base, {"user": "u1", "num": 2})
            assert st == 200 and body["itemScores"]
            from predictionio_tpu.obs import get_registry

            assert get_registry().get("pio_refresh_promotions_total") \
                .value(result="rejected") == 1
        finally:
            srv.stop()

    def test_slo_burn_in_canary_window_rolls_back(self, ctx, monkeypatch):
        """A promotion whose canary window sees the SLO burning is rolled
        back over the same gate — the previous generation serves again."""
        app_id = _mk_app(ctx)
        _seed_clique_rates(ctx, app_id)
        eng, variant = _als()
        run_train(eng, variant, ctx)
        srv, base = self._server(ctx, eng, variant)
        try:
            gen1_instance = srv._instance.id
            ctx.storage.get_events().insert(_rate(0, 1, 5.0), app_id)
            promoter = HttpPromoter(base, canary_window_s=5.0,
                                    canary_poll_s=0.01)
            monkeypatch.setattr(
                promoter, "slo_state",
                lambda: {"degraded": True, "burn": {}, "threshold": 14.4})
            d = RefreshDaemon(eng, variant, ctx,
                              config=RefreshConfig(interval_s=0.01),
                              promoter=promoter)
            out = d.run_once()
            assert out["promotion"] == "rolled_back"
            # the rollback restored the pre-promotion generation
            assert srv._instance.id == gen1_instance
            st, body = _http_query(base, {"user": "u1", "num": 2})
            assert st == 200 and body["itemScores"]
        finally:
            srv.stop()


def _http_query(base, q):
    req = Request(base + "/queries.json", data=json.dumps(q).encode(),
                  method="POST",
                  headers={"Content-Type": "application/json"})
    with urlopen(req, timeout=10) as resp:
        return resp.status, json.loads(resp.read() or b"{}")


# ==========================================================================
# Event-server ingest watermark gauge
# ==========================================================================

class TestIngestWatermarkGauge:
    def _server(self, pio_home):
        from predictionio_tpu.data.storage import AccessKey
        from predictionio_tpu.server import EventServer

        storage = get_storage()
        app_id = storage.get_apps().insert(App(id=None, name="gapp"))
        storage.get_events().init(app_id)
        key = storage.get_access_keys().insert(
            AccessKey(key="", app_id=app_id))
        srv = EventServer(storage=storage)
        return srv, storage, app_id, key

    def test_gauge_tracks_stored_event_time(self, pio_home):
        from predictionio_tpu.obs import get_registry

        srv, storage, app_id, key = self._server(pio_home)
        t = "2026-03-01T12:00:00Z"
        st, body = srv.handle(
            "POST", "/events.json", {"accessKey": [key]},
            json.dumps({"event": "view", "entityType": "user",
                        "entityId": "u1", "targetEntityType": "item",
                        "targetEntityId": "i1", "eventTime": t}).encode())
        assert st == 201
        g = get_registry().get("pio_events_latest_ts")
        want = dt.datetime(2026, 3, 1, 12, tzinfo=UTC).timestamp()
        assert g.value(app=str(app_id)) == pytest.approx(want)
        # an OLDER event must not move the watermark backwards
        st, _ = srv.handle(
            "POST", "/events.json", {"accessKey": [key]},
            json.dumps({"event": "view", "entityType": "user",
                        "entityId": "u1",
                        "eventTime": "2020-01-01T00:00:00Z"}).encode())
        assert st == 201
        assert g.value(app=str(app_id)) == pytest.approx(want)

    def test_gauge_seeds_from_store_on_restart(self, pio_home):
        """A fresh server process reports the STORE-wide watermark, not
        just its own ingest, as soon as an app is touched."""
        from predictionio_tpu.obs import get_registry
        from predictionio_tpu.server import EventServer

        srv, storage, app_id, key = self._server(pio_home)
        future = dt.datetime(2029, 6, 1, tzinfo=UTC)
        storage.get_events().insert(_view(1, 1, when=future), app_id)
        srv2 = EventServer(storage=storage)
        st, _ = srv2.handle(
            "POST", "/events.json", {"accessKey": [key]},
            json.dumps({"event": "view", "entityType": "user",
                        "entityId": "u1"}).encode())
        assert st == 201
        g = get_registry().get("pio_events_latest_ts")
        assert g.value(app=str(app_id)) == pytest.approx(future.timestamp())

    def test_restart_seed_covers_named_channels(self, pio_home):
        """The app-level gauge must not regress after a restart just
        because the newest event lives in a NAMED channel."""
        from predictionio_tpu.data.storage import Channel
        from predictionio_tpu.obs import get_registry
        from predictionio_tpu.server import EventServer

        srv, storage, app_id, key = self._server(pio_home)
        ch_id = storage.get_channels().insert(
            Channel(id=None, name="live", app_id=app_id))
        storage.get_events().init(app_id, ch_id)
        newest = dt.datetime(2029, 9, 1, tzinfo=UTC)
        storage.get_events().insert(_view(1, 1, when=newest), app_id,
                                    channel_id=ch_id)
        srv2 = EventServer(storage=storage)
        st, _ = srv2.handle(
            "POST", "/events.json", {"accessKey": [key]},
            json.dumps({"event": "view", "entityType": "user",
                        "entityId": "u1"}).encode())
        assert st == 201
        g = get_registry().get("pio_events_latest_ts")
        assert g.value(app=str(app_id)) == pytest.approx(newest.timestamp())

    def test_batch_ingest_advances_gauge(self, pio_home):
        from predictionio_tpu.obs import get_registry

        srv, storage, app_id, key = self._server(pio_home)
        batch = [{"event": "view", "entityType": "user", "entityId": "u1",
                  "eventTime": f"2026-04-0{d}T00:00:00Z"} for d in (1, 3, 2)]
        st, body = srv.handle("POST", "/batch/events.json",
                              {"accessKey": [key]},
                              json.dumps(batch).encode())
        assert st == 200 and all(r["status"] == 201 for r in body)
        g = get_registry().get("pio_events_latest_ts")
        want = dt.datetime(2026, 4, 3, tzinfo=UTC).timestamp()
        assert g.value(app=str(app_id)) == pytest.approx(want)

    def test_pio_status_prints_watermark(self, capsys):
        from predictionio_tpu.cli.main import _print_serving_snapshot

        lines = [
            'pio_events_latest_ts{app="7"} 1.7720640e+09',
            "pio_refresh_staleness_s 12.5",
            'pio_refresh_runs_total{result="warm"} 3',
        ]
        _print_serving_snapshot(lines)
        out = capsys.readouterr().out
        assert "events latest [app 7]" in out
        assert "refresh staleness: 12.5s" in out
        assert "warm=3" in out
