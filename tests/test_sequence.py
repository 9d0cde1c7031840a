"""The sequence engine: the backbone's operators and router against the
plain reference (``models/lfm2_reference.py``), the served path through
both kinds of state, the state cache, the packing of turns, and the
template through train -> deploy -> ``/queries.json``.  CPU, tiny
widths, seeded weights."""

import gc
import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.controller import EngineVariant, RuntimeContext
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import App, get_storage
from predictionio_tpu.models import lfm2, seq_runtime
from predictionio_tpu.models import lfm2_reference as ref
from predictionio_tpu.ops.ragged import pack_turns
from predictionio_tpu.serving.result_cache import canonical_query
from predictionio_tpu.serving.state_cache import StateCache, StateCacheFull

CFG = lfm2.LFM2Config(
    vocab_size=97, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
    num_attention_heads=4, num_key_value_heads=2,
    layer_types=("conv", "full_attention", "conv", "conv",
                 "full_attention"),
    dense_ff=(True, False, False, False, False))
PAGE = 8
# bfloat16 weights, state and matmul inputs against a float32 reference:
# logits of size ~2 agree to a few hundredths.
TOL = 0.08


@pytest.fixture(scope="module")
def params():
    return lfm2.init_params(CFG, jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def history():
    """A history on which the bfloat16 path and the float32 reference
    pick the same experts at every event.  At these widths a near-tie in
    a router turns a pick and moves a logit by half a unit (35 of the
    seeds 0-39 hold such an event), which says nothing about the state;
    on this one every logit agrees to 0.035."""
    return np.random.default_rng(31).integers(
        0, CFG.vocab_size, 21).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, history):
    """The reference's logits after each event of the whole history."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(params, CFG, jnp.asarray(history)))


def _cache(max_users=6, budget=1 << 19, write_slots=4):
    return StateCache(lfm2.state_layout(CFG, PAGE), budget_bytes=budget,
                      max_users=max_users, write_slots=write_slots,
                      page_size=PAGE)


def _runtime(params, cache):
    rt = lfm2.SequenceRuntime(CFG, lfm2.cast_for_serving(params), cache)
    rt.token_buckets, rt.read_buckets = (8, 16), (4,)
    return rt


@pytest.fixture(scope="module")
def runtime(params):
    return _runtime(params, _cache())


@pytest.fixture()
def fresh(runtime):
    runtime.cache.reset()
    return runtime


def _ask(rt, *turns):
    with rt.cache.transaction():
        return rt.extend([lfm2.Turn(u, np.asarray(items, np.int32),
                                    CFG.vocab_size) for u, items in turns])


def _dense(answer):
    """(scores, ids) of the whole vocabulary -> logits by item id."""
    scores, ids = answer
    out = np.empty(CFG.vocab_size, np.float32)
    out[ids] = scores
    return out


# -- each operator and the router, alone ------------------------------------

def _layer(params, kind, dense=False):
    for l, p in enumerate(params["layers"]):
        if CFG.layer_types[l] == kind and CFG.dense_ff[l] == dense:
            return lfm2.cast_for_serving({"embed": params["embed"],
                                          "final_norm": params["final_norm"],
                                          "layers": [p]})["layers"][0]
    raise AssertionError(kind)


def _tokens(n, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (n, CFG.hidden_size), jnp.float32)


def test_conv_operator_continues_from_its_two_rows_of_state(params):
    p = _layer(params, "conv", dense=True)
    u = _tokens(9)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.conv_mixer(
            CFG, jax.tree_util.tree_map(
                lambda a: jnp.asarray(a, jnp.float32), p), u))
    zeros = jnp.zeros((9, 2, CFG.hidden_size), jnp.bfloat16)
    # Two users side by side: events 0-4 of one, 0-3 of another copy.
    idx = jnp.asarray([0, 1, 2, 3, 4, 0, 1, 2, 3], jnp.int32)
    both = jnp.concatenate([u[:5], u[:4]])
    out, rows = lfm2.conv_op(CFG, p, both, idx, zeros)
    np.testing.assert_allclose(out[:5], whole[:5], atol=TOL)
    np.testing.assert_allclose(out[5:], whole[:4], atol=TOL)
    # ...then events 5-8 from the state the first left, one at a time
    # and all at once.
    state = rows[4]
    for t in range(5, 9):
        one, new = lfm2.conv_op(CFG, p, u[t:t + 1],
                                jnp.zeros(1, jnp.int32), state[None])
        np.testing.assert_allclose(one[0], whole[t], atol=TOL)
        state = new[0]
    rest, _ = lfm2.conv_op(CFG, p, u[5:], jnp.arange(4, dtype=jnp.int32),
                           jnp.broadcast_to(rows[4], (4, 2, CFG.hidden_size)))
    np.testing.assert_allclose(rest, whole[5:], atol=TOL)


def test_attention_operator_over_pages(params):
    p = _layer(params, "full_attention")
    n = 19                                   # two pages and a part
    u = _tokens(n, 1)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.attention_mixer(
            CFG, jax.tree_util.tree_map(
                lambda a: jnp.asarray(a, jnp.float32), p), u))
    pool = jnp.zeros((8, PAGE, CFG.kv_width), jnp.bfloat16)
    pages = [5, 2, 7]                         # out of order on purpose

    def batch(lo, hi):
        pos = np.arange(lo, hi)
        held = pages[:-(-hi // PAGE)]
        pad = lfm2.PAGES_PER_BLOCK - len(held)
        return {
            "tok_pos": jnp.asarray(pos, jnp.int32),
            "tok_seg": jnp.zeros(hi - lo, jnp.int32),
            "tok_row": jnp.asarray(
                [pages[q // PAGE] * PAGE + q % PAGE for q in pos],
                jnp.int32),
            "pages": {"ids": jnp.asarray(held + [0] * pad, jnp.int32),
                      "seg": jnp.asarray([0] * len(held) + [-2] * pad,
                                         jnp.int32),
                      "base": jnp.asarray(
                          [i * PAGE for i in range(len(held))] + [0] * pad,
                          jnp.int32),
                      "blocks": jnp.int32(1)}}

    kp = vp = pool
    got = []
    for lo, hi in ((0, 11), (11, 12), (12, 19)):
        out, kp, vp = lfm2.attention_op(CFG, p, u[lo:hi], batch(lo, hi),
                                        kp, vp, PAGE)
        got.append(np.asarray(out))
    np.testing.assert_allclose(np.concatenate(got), whole, atol=TOL)


def test_dense_and_expert_layers(params):
    u = _tokens(12, 2)
    f32 = lambda p: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), p)
    pd = _layer(params, "conv", dense=True)
    pe = _layer(params, "conv", dense=False)
    with jax.default_matmul_precision("highest"):
        want_d = np.asarray(ref.dense_mlp(f32(pd), u))
        want_e = np.asarray(ref.expert_mlp(CFG, f32(pe), u))
    np.testing.assert_allclose(lfm2.dense_ff(CFG, pd, u), want_d, atol=TOL)
    valid = jnp.arange(12) < 10              # two padded tokens
    got, sizes = lfm2.moe_ff(CFG, pe, u, valid)
    np.testing.assert_allclose(got[:10], want_e[:10], atol=TOL)
    np.testing.assert_array_equal(got[10:], 0.0)
    assert int(sizes.sum()) == 10 * CFG.num_experts_per_tok


def test_the_bias_picks_and_does_not_weigh(params):
    p = dict(_layer(params, "conv", dense=False))
    u = _tokens(16, 4)
    s = np.asarray(jax.nn.sigmoid(u @ p["w_g"]))
    # A bias that lifts the expert each token likes LEAST into its picks.
    worst = s.argmin(-1)
    p["b"] = jnp.zeros(CFG.num_experts).at[np.bincount(worst).argmax()].set(
        5.0)
    lifted = int(np.bincount(worst).argmax())
    ids, w = lfm2.route(CFG, u, p["w_g"], p["b"])
    ids, w = np.asarray(ids), np.asarray(w)
    by_s = np.argsort(-s, -1)[:, :CFG.num_experts_per_tok]
    assert (ids == lifted).any(-1).all()          # picked by s + b ...
    assert not (by_s == lifted).any(-1).all()     # ... not by s alone
    picked = np.take_along_axis(s, ids, 1)
    np.testing.assert_allclose(w, picked / (picked.sum(-1, keepdims=True)
                                            + 1e-6), rtol=1e-5)
    with jax.default_matmul_precision("highest"):
        dense = np.asarray(ref.router(CFG, p, u))
    np.testing.assert_allclose(np.take_along_axis(dense, ids, 1), w,
                               rtol=1e-5)
    assert (np.count_nonzero(dense, axis=1)
            == CFG.num_experts_per_tok).all()
    # Leaving the bias out changes the experts, so the answers.
    cfg_off = lfm2.dataclasses.replace(CFG, use_expert_bias=False)
    ids_off, _ = lfm2.route(cfg_off, u, p["w_g"], p["b"])
    assert not np.array_equal(np.sort(ids, -1), np.sort(ids_off, -1))


# -- prefill + turns through both kinds of state = one forward pass ---------

@pytest.mark.parametrize("split", range(1, 21))
def test_history_split_at_every_point(fresh, history, want, split):
    first = _ask(fresh, ("u", history[:split]))[0]
    np.testing.assert_allclose(_dense(first), want[split - 1], atol=TOL)
    second = _ask(fresh, ("u", history[split:]))[0]
    np.testing.assert_allclose(_dense(second), want[-1], atol=TOL)
    assert fresh.cache.length("u") == len(history)


def test_one_event_at_a_time(fresh, history, want):
    for t, item in enumerate(history):
        got = _ask(fresh, ("u", [item]))[0]
        np.testing.assert_allclose(_dense(got), want[t], atol=TOL)


def test_two_turns_of_one_user_in_one_cohort_and_in_two(fresh, history,
                                                        want):
    a, b, c = history[:9], history[9:14], history[14:]
    # One cohort: both turns of "u" around another user's turn.
    _ask(fresh, ("u", a))
    one = _ask(fresh, ("u", b), ("other", history[:4]), ("u", c), ("u", []))
    # Two cohorts.
    fresh.cache.reset()
    _ask(fresh, ("u", a))
    two = _ask(fresh, ("u", b)) + _ask(fresh, ("other", history[:4])) \
        + _ask(fresh, ("u", c)) + _ask(fresh, ("u", []))
    for got in (one, two):
        np.testing.assert_allclose(_dense(got[0]), want[13], atol=TOL)
        np.testing.assert_allclose(_dense(got[1]), want[3], atol=TOL)
        np.testing.assert_allclose(_dense(got[2]), want[-1], atol=TOL)
        np.testing.assert_allclose(_dense(got[3]), want[-1], atol=TOL)
    for x, y in zip(one, two):
        np.testing.assert_allclose(_dense(x), _dense(y), atol=TOL)


def test_a_programs_first_run_settles_the_heap_once(params, history,
                                                   monkeypatch):
    """What tracing and compiling leave behind goes out of the cycle
    collector's full passes after the program's first run, and a run of
    a program that exists settles nothing."""
    rt = _runtime(params, _cache())
    gc.unfreeze()
    _ask(rt, ("a", history[:3]))
    assert gc.get_freeze_count() > 0
    settled = []
    monkeypatch.setattr(seq_runtime, "_settle_heap", lambda: settled.append(1))
    _ask(rt, ("a", history[3:5]))       # the same program
    assert settled == []
    _ask(rt, ("b", history[:12]))       # the larger bucket
    assert settled == [1]
    gc.unfreeze()


def test_a_user_with_nothing_gets_nothing(fresh, history, want):
    scores, ids = _ask(fresh, ("nobody", []))[0]
    assert len(scores) == 0 and len(ids) == 0
    assert not fresh.cache.has("nobody")
    # ... until a turn of the same cohort has brought its first events.
    got = _ask(fresh, ("late", []), ("late", history[:5]), ("late", []))
    assert len(got[0][0]) == 0
    np.testing.assert_allclose(_dense(got[1]), want[4], atol=TOL)
    np.testing.assert_allclose(_dense(got[2]), want[4], atol=TOL)


def test_a_failed_dispatch_advances_nothing(fresh, history, want):
    _ask(fresh, ("u", history[:10]))
    before = fresh.cache.snapshot()
    with pytest.raises(RuntimeError, match="after the program"):
        with fresh.cache.transaction():
            fresh.extend([lfm2.Turn("u", history[10:15], 5),
                          lfm2.Turn("new", history[:3], 5)])
            raise RuntimeError("after the program, before the answers")
    assert fresh.cache.snapshot() == before
    assert fresh.cache.length("u") == 10
    assert not fresh.cache.has("new")
    # The retry applies the events once: the answer of one clean pass.
    got = _ask(fresh, ("u", history[10:]))[0]
    np.testing.assert_allclose(_dense(got), want[-1], atol=TOL)


def test_eviction_then_the_same_answer_from_a_refill(params, history, want):
    """Three users' histories do not fit the pages: the least recently
    used goes, and re-reading its history answers as its state did."""
    cache = _cache(max_users=3, write_slots=3, budget=(
        8 * ((2 * CFG.n_conv + 1) * CFG.hidden_size * 2)
        + 8 * (2 * CFG.n_attn * PAGE * CFG.kv_width * 2)))
    assert cache.n_pages == 7
    rt = _runtime(params, cache)
    hit = _ask(rt, ("a", history))[0]             # 3 pages
    _ask(rt, ("b", history))                      # 3 pages
    assert cache.has("a") and cache.has("b")
    _ask(rt, ("c", history[:12]))                 # needs 2: evicts "a"
    assert not cache.has("a") and cache.has("b")
    miss = _ask(rt, ("a", history))[0]            # evicts "b"
    assert not cache.has("b")
    np.testing.assert_allclose(_dense(miss), _dense(hit), atol=1e-5)
    np.testing.assert_allclose(_dense(miss), want[-1], atol=TOL)
    # A dispatch whose own users cannot fit is refused and leaves none of
    # them behind (what it evicted on the way stays evicted: a later
    # miss, never a wrong answer).
    with pytest.raises(StateCacheFull):
        _ask(rt, ("x", history), ("y", history), ("z", history))
    assert not any(cache.has(u) for u in "xyz")
    assert cache.snapshot()["pagesUsed"] == sum(
        -(-cache.length(u) // PAGE) for u in "abc")
    again = _ask(rt, ("a", history))[0]
    np.testing.assert_allclose(_dense(again), _dense(hit), atol=1e-5)


# -- the state cache and the packing, by themselves -------------------------

def test_state_cache_budget_write_pool_and_free():
    cache = _cache(max_users=4, budget=1 << 18)
    held = cache.bytes_in_use()
    assert held <= 1 << 18
    assert held > (1 << 18) - cache.page_bytes - cache.slot_bytes
    with cache.transaction():
        plan = cache.plan(["u", "v"], [PAGE + 1, 3])
        assert plan.read_slot == [cache.ZERO_SLOT] * 2
        assert [len(p) for p in plan.seg_pages] == [2, 1]
        assert len({*plan.write_slot, cache.ZERO_SLOT,
                    cache.SCRAP_SLOT}) == 4
        cache.stage(plan)
        # Inside the transaction the staged state is what is read.
        assert cache.read_slot("u") == plan.write_slot[0]
        assert cache.length("u") == PAGE + 1
    first = plan.write_slot[0]
    with cache.transaction():
        plan = cache.plan(["u"], [2])
        assert plan.read_slot == [first] and plan.seg_start == [PAGE + 1]
        second = plan.write_slot[0]               # one of the pool
        assert second not in (first, cache.read_slot("v"))
        assert len(plan.seg_pages[0]) == 2        # room left in page two
        cache.stage(plan)
    assert cache.read_slot("u") == second
    assert first in cache._free_slots             # the old one came back
    assert cache.snapshot()["pagesUsed"] == 3
    with pytest.raises(RuntimeError):
        cache.plan(["v"], [1])                    # outside a transaction
    # Freed (a reload): no user, no device memory; the next transaction
    # finds the pools again, empty.
    cache.free()
    assert cache.bytes_in_use() == 0 and not cache.has("u")
    assert cache.snapshot()["pagesUsed"] == 0
    with cache.transaction():
        assert cache.bytes_in_use() == held
        assert cache.plan(["u"], [1]).seg_start == [0]


def test_pack_turns_merges_splits_and_orders():
    turns = [(0, "a", np.arange(5)), (1, "b", np.arange(3)),
             (2, "a", np.arange(5, 7)), (3, "c", np.zeros(0, np.int32)),
             (4, "d", np.arange(20))]
    packs = list(pack_turns(turns, max_tokens=16, max_reads=8))
    first = packs[0]
    assert first.seg_key == ["a", "b", "d"]
    np.testing.assert_array_equal(first.seg_len, [7, 3, 6])
    np.testing.assert_array_equal(first.tokens[:7], np.arange(7))
    np.testing.assert_array_equal(first.tok_idx[7:10], [0, 1, 2])
    assert first.read_turn == [0, 1, 2, 3]
    np.testing.assert_array_equal(first.read_tok, [4, 9, 6, -1])
    assert first.read_key[3] == "c"
    # "d" continues in the next pack and is answered at its last token.
    assert packs[1].seg_key == ["d"] and packs[1].read_turn == [4]
    np.testing.assert_array_equal(packs[1].tokens, np.arange(6, 20))
    np.testing.assert_array_equal(packs[1].read_tok, [13])
    assert sum(p.n_tokens for p in packs) == 30
    # The answers' limit closes a pack too.
    many = [(i, f"u{i}", np.arange(1)) for i in range(5)]
    assert [len(p.read_turn) for p in
            pack_turns(many, max_tokens=16, max_reads=2)] == [2, 2, 1]
    # A page limit keeps a pack's users within the attention's list.
    held = {"a": 30, "b": 30}
    packs = list(pack_turns(
        [(0, "a", np.arange(4)), (1, "b", np.arange(4))], max_tokens=16,
        max_reads=8, max_pages=5,
        pages_of=lambda k, n: -(-(held[k] + n) // 8)))
    assert [p.seg_key for p in packs] == [["a"], ["b"]]


def test_a_stateful_query_is_never_a_cache_key():
    from predictionio_tpu.templates.recommendation import Query as Plain
    from predictionio_tpu.templates.sequence import Query

    assert canonical_query(Plain(user="u1"))
    with pytest.raises(TypeError, match="stateful"):
        canonical_query(Query(user="u1", events=["i1"]))


# -- the template: train -> deploy -> /queries.json with events -------------

VARIANT = {
    "engineFactory": "predictionio_tpu.templates.sequence:engine",
    "datasource": {"params": {"appName": "seqapp"}},
    "preparator": {"params": {"vocabSize": 64}},
    "algorithms": [{"name": "sequence", "params": {
        "hiddenSize": 32, "intermediateSize": 48, "moeIntermediateSize": 16,
        "numExperts": 4, "numExpertsPerTok": 2, "numAttentionHeads": 4,
        "numKeyValueHeads": 2, "layerTypes": ["conv", "full_attention",
                                              "conv"],
        "numDenseLayers": 1, "steps": 150, "batchSize": 16, "window": 12,
        "learningRate": 0.01, "seed": 5, "stateBudgetMB": 1.0,
        "maxUsers": 16}}],
}
N_ITEMS = 12


def _seed_cycles(ctx, n_users=12, length=14):
    """Every user walks the items in a cycle (i -> i + 1), from a start
    of their own: the next item is a function of the last."""
    storage = ctx.storage
    app_id = storage.get_apps().insert(App(id=None, name="seqapp"))
    storage.get_events().init(app_id)
    import datetime as dt

    t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    for u in range(n_users):
        for step in range(length):
            storage.get_events().insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{(u + step) % N_ITEMS}",
                event_time=t0 + dt.timedelta(seconds=60 * step + u)),
                app_id)
    return app_id


@pytest.fixture()
def ctx(pio_home):
    return RuntimeContext.create(storage=get_storage())


@pytest.fixture()
def deployed(ctx):
    from predictionio_tpu.server import EngineServer
    from predictionio_tpu.templates.sequence import engine
    from predictionio_tpu.workflow.core_workflow import run_train

    _seed_cycles(ctx)
    eng = engine()
    variant = EngineVariant.from_dict(VARIANT)
    run_train(eng, variant, ctx)
    srv = EngineServer(eng, variant, ctx.storage, host="127.0.0.1", port=0)
    srv.start()
    yield srv
    srv.stop()


def _post(srv, path, doc=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}",
        data=json.dumps(doc).encode() if doc is not None else b"",
        method="POST", headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def _items(answer):
    return [s["item"] for s in answer["itemScores"]]


def test_train_deploy_query_with_events(deployed):
    srv = deployed
    model = srv._models[0]
    # A new user's first turn: the walk continues where the events end.
    first = _post(srv, "/queries.json",
                  {"user": "visitor", "num": 3, "events": ["i2", "i3", "i4"]})
    assert _items(first)[0] == "i5" and len(first["itemScores"]) == 3
    scores = [s["score"] for s in first["itemScores"]]
    assert scores == sorted(scores, reverse=True)
    key = "visitor"
    assert model.state_cache.length(key) == 3
    # The next turn extends the state; no events answers from it as it is.
    second = _post(srv, "/queries.json",
                   {"user": "visitor", "num": 3, "events": ["i5", "i6"]})
    assert _items(second)[0] == "i7"
    assert model.state_cache.length(key) == 5
    again = _post(srv, "/queries.json", {"user": "visitor", "num": 3})
    assert _items(again) == _items(second)
    assert model.state_cache.length(key) == 5
    # An item the model has no id for is skipped, not an error.
    _post(srv, "/queries.json", {"user": "visitor", "events": ["nope"]})
    assert model.state_cache.length(key) == 5
    # The same turn twice is two turns, never a cached answer.
    assert srv.result_cache.snapshot()["entries"] == 0


def test_a_miss_reads_the_history_back_and_answers_as_a_hit(deployed):
    srv = deployed
    model = srv._models[0]
    # "u3" is in the store (items 3..16 mod 12, the last is i4) and not in
    # the cache: its first query is a miss that reads the store.
    turn = {"user": "u3", "num": 4, "events": ["i5", "i6"]}
    from_store = _post(srv, "/queries.json", turn)
    assert _items(from_store)[0] == "i7"
    key = "u3"
    assert model.state_cache.length(key) == 14 + 2
    # The same user fed event by event into a fresh state, then evicted
    # and read back: three roads, one answer.
    walk = [f"i{(3 + s) % N_ITEMS}" for s in range(14)]
    by_turns = _post(srv, "/queries.json",
                     {"user": "walker", "num": 4, "events": walk})
    by_turns = _post(srv, "/queries.json", {**turn, "user": "walker"})
    assert _items(by_turns) == _items(from_store)
    np.testing.assert_allclose(
        [s["score"] for s in by_turns["itemScores"]],
        [s["score"] for s in from_store["itemScores"]], atol=1e-4)
    assert model.state_cache.evict(key)
    text = srv.stats.registry.render()
    assert 'pio_seq_state_total{result="evicted"} 1' in text
    assert 'pio_seq_tokens_total{kind="prefill"} 14' in text
    after = _post(srv, "/queries.json", {"user": "u3", "num": 4})
    assert _items(after)[0] == "i5"               # the store's 14 events
    assert model.state_cache.length(key) == 14


def test_a_store_that_cannot_answer_fails_the_query_and_moves_nothing(
        deployed):
    from predictionio_tpu.data.storage.base import StorageUnavailable

    srv = deployed
    model = srv._models[0]
    store = model._ctx.event_store
    find = store.find_by_entity

    def down(*a, **kw):
        raise StorageUnavailable("event store timed out")

    turn = {"user": "u3", "num": 4, "events": ["i5", "i6"]}
    store.find_by_entity = down
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(srv, "/queries.json", turn)
        assert err.value.code >= 500
        # Not answered from the turn's two events alone, and nothing
        # committed that later turns would hit.
        assert not model.state_cache.has("u3")
        assert model.state_cache.snapshot()["users"] == 0
    finally:
        store.find_by_entity = find
    answer = _post(srv, "/queries.json", turn)
    assert _items(answer)[0] == "i7"
    assert model.state_cache.length("u3") == 14 + 2
    # A user the cache holds is served while the store is down.
    store.find_by_entity = down
    try:
        hit = _post(srv, "/queries.json", {"user": "u3", "events": ["i7"]})
    finally:
        store.find_by_entity = find
    assert _items(hit)[0] == "i8" and model.state_cache.length("u3") == 17


def test_reload_drops_the_generations_state(deployed):
    srv = deployed
    old = srv._models[0]
    _post(srv, "/queries.json", {"user": "v", "events": ["i1", "i2"]})
    assert old.state_cache.length("v") == 2
    assert _post(srv, "/reload")["status"] == "reloaded"
    new = srv._models[0]
    assert new is not old
    assert old.state_cache.snapshot()["users"] == 0
    assert old.state_cache.bytes_in_use() == 0    # the pools went too
    assert not new.state_cache.has("v")
    # The next turn starts from what it brings (the store has no "v").
    answer = _post(srv, "/queries.json", {"user": "v", "events": ["i3"]})
    assert _items(answer)[0] == "i4"
    assert new.state_cache.length("v") == 1
    # ... and a rollback finds the old generation's state gone too.
    assert _post(srv, "/admin/rollback")["status"] == "rolled_back"
    assert srv._models[0] is old
    assert new.state_cache.snapshot()["users"] == 0


def test_a_dispatch_that_fails_after_predict_leaves_state_alone(deployed):
    srv = deployed
    model = srv._models[0]
    bind = srv._bind_query
    srv.query_batch([{"user": "v", "events": ["i1", "i2"]}])
    key = "v"
    calls = []
    serve = srv._serving.serve

    def broken(q, predictions):
        calls.append(q.user)
        if len(calls) == 2:
            raise RuntimeError("serve fell over")
        return serve(q, predictions)

    srv._serving.serve = broken
    try:
        with pytest.raises(RuntimeError, match="fell over"):
            srv._dispatch_batch([
                bind({"user": "v", "events": ["i3"]}),
                bind({"user": "w", "events": ["i7", "i8"]})])
    finally:
        srv._serving.serve = serve
    assert model.state_cache.length(key) == 2
    assert not model.state_cache.has("w")
    # The batcher's retry, member by member, applies each event once.
    out = [srv._dispatch_batch([bind(q)])[0][0] for q in (
        {"user": "v", "events": ["i3"]}, {"user": "w", "events": ["i7",
                                                                  "i8"]})]
    assert model.state_cache.length(key) == 3
    assert _items(out[0])[0] == "i4" and _items(out[1])[0] == "i9"
