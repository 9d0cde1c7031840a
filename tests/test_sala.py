"""The block-selected / lightning backbone of the sequence engine: the
ragged step against the plain reference's whole forward pass
(``models/sala_reference.py``) through all three kinds of state, the
selection alone, the kernels against their XLA twins, the state cache's
pooled write side and page tables, the host-side counters, and the
template through train -> deploy -> ``/queries.json``.  CPU, tiny widths,
seeded weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.controller import EngineVariant
from predictionio_tpu.models import sala, seq_runtime
from predictionio_tpu.models import sala_reference as ref
from predictionio_tpu.obs import get_registry
from predictionio_tpu.ops import sala_kernels
from predictionio_tpu.serving.state_cache import StateCache, StateCacheFull
from tests.test_sequence import (  # noqa: F401 - fixtures, used by name
    _items, _post, _seed_cycles, ctx)

# Pages of 16 events = 2 blocks of 8 = 8 strides of 2; a query past 24
# events selects 4 blocks: the first, the 2 that end with its own, and
# one of its choosing.
CFG = sala.SALAConfig(
    vocab_size=97, hidden_size=32, intermediate_size=48,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    lightning_nh=4, lightning_head_dim=8,
    mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                 "minicpm4"),
    layer_index=(9, 10, 11, 16), published_layers=32, kernel_size=4,
    kernel_stride=2, block_size=8, topk=4, init_blocks=1, window_size=16,
    dense_len=24)
PAGE, TABLE = 16, 8
N = 75
# bfloat16 weights, keys, values and matmul inputs against a float32
# reference: logits of size ~12 agree to a few hundredths.
TOL = 0.08


@pytest.fixture(scope="module")
def params():
    return sala.init_params(CFG, jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def history():
    return np.random.default_rng(5).integers(
        0, CFG.vocab_size, N).astype(np.int32)


def _forward(params, tokens, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(params, CFG, jnp.asarray(tokens),
                                      **kw))


@pytest.fixture(scope="module")
def want(params, history):
    return _forward(params, history)


def _runtime(params, max_users=6, write_slots=4, budget=1 << 21):
    rt = sala.make_runtime(CFG, params, budget_bytes=budget,
                           max_users=max_users, write_slots=write_slots,
                           page_size=PAGE, table_len=TABLE)
    rt.token_buckets, rt.read_buckets = (16, 32), (4,)
    return rt


@pytest.fixture(scope="module")
def runtime(params):
    return _runtime(params)


@pytest.fixture()
def fresh(runtime):
    runtime.cache.reset()
    return runtime


def _ask(rt, *turns):
    with rt.cache.transaction():
        return rt.extend([seq_runtime.Turn(u, np.asarray(items, np.int32),
                                           CFG.vocab_size)
                          for u, items in turns])


def _dense(answer):
    scores, ids = answer
    out = np.empty(CFG.vocab_size, np.float32)
    out[ids] = scores
    return out


# -- the served path against the whole forward pass -------------------------

@pytest.mark.parametrize("cuts", [
    (N,),                        # all at once: chunks of 32 by the runtime
    (23, 24, 25, N),             # across dense_len one event at a time
    (15, 16, 17, 31, 33, N),     # across page borders and pooled windows
    (40, 41, 43, 46, 50, 51, N),  # short turns on the selected path
    (1, 2, 3, 4, 5, N),
], ids=["at-once", "dense-len", "page-borders", "turns", "from-nothing"])
def test_prefill_then_turns_is_one_forward_pass(fresh, history, want, cuts):
    at = 0
    for upto in cuts:
        answer = _ask(fresh, ("a", history[at:upto]))[0]
        at = upto
        np.testing.assert_allclose(_dense(answer), want[at - 1], atol=TOL)
    assert fresh.cache.length("a") == N


def test_two_turns_of_a_user_and_another_user_in_one_call(fresh, history,
                                                          want, params):
    other = history[::-1].copy()
    want_b = _forward(params, other[:30])
    _ask(fresh, ("a", history[:40]), ("b", other[:20]))
    a1, b, a2, a3 = _ask(fresh, ("a", history[40:43]), ("b", other[20:30]),
                         ("a", history[43:50]), ("a", []))
    np.testing.assert_allclose(_dense(a1), want[42], atol=TOL)
    np.testing.assert_allclose(_dense(a2), want[49], atol=TOL)
    np.testing.assert_allclose(_dense(a3), want[49], atol=TOL)
    np.testing.assert_allclose(_dense(b), want_b[29], atol=TOL)


def test_a_failed_dispatch_restores_all_three_kinds(fresh, history, want):
    _ask(fresh, ("a", history[:45]))
    cache = fresh.cache
    slot = cache.read_slot("a")
    before = {k: np.asarray(v) for k, v in cache.arrays.items()}
    pages = list(cache._entries["a"].pages)
    free = (sorted(cache._free_pages), sorted(cache._free_slots))
    with pytest.raises(RuntimeError, match="serve failed"):
        with cache.transaction():
            fresh.extend([seq_runtime.Turn("a", history[45:60], 5),
                          seq_runtime.Turn("b", history[:20], 5)])
            assert cache.length("a") == 60 and cache.read_slot("a") != slot
            raise RuntimeError("serve failed")
    assert cache.length("a") == 45 and cache.read_slot("a") == slot
    assert not cache.has("b")
    assert (sorted(cache._free_pages), sorted(cache._free_slots)) == free
    after = {k: np.asarray(v) for k, v in cache.arrays.items()}
    per = PAGE // CFG.kernel_stride
    for name in before:
        if name.startswith("s") or name == "h_last":      # fixed
            np.testing.assert_array_equal(after[name][slot],
                                          before[name][slot])
        elif name.startswith("kv"):                       # paged
            rows = np.concatenate([np.arange(p * PAGE, (p + 1) * PAGE)
                                   for p in pages])[:45]
            np.testing.assert_array_equal(after[name][rows],
                                          before[name][rows])
        elif name.startswith("idx"):                      # index
            rows = np.concatenate([np.arange(p * per, (p + 1) * per)
                                   for p in pages])[:(45 - 4) // 2 + 1]
            np.testing.assert_array_equal(after[name][rows],
                                          before[name][rows])
    # ... and the same turn again answers as if nothing had happened.
    answer = _ask(fresh, ("a", history[45:60]))[0]
    np.testing.assert_allclose(_dense(answer), want[59], atol=TOL)


def _evicted():
    for line in get_registry().render().splitlines():
        if line.startswith('pio_seq_state_total{result="evicted"}'):
            return float(line.rpartition(" ")[2])
    return 0.0


def test_a_call_of_more_users_than_a_program_touches_evicts_nobody(
        params, history, want):
    """Six residents, a pool of four write slots (the read bucket): one
    call with a turn of each runs as two programs inside the pool, the
    first committed when the second is planned."""
    rt = _runtime(params)
    users = "abcdef"
    for u in users:
        _ask(rt, (u, history[:40]))
    cache = rt.cache
    assert len(cache._free_slots) == cache.write_slots == 4
    evicted, dispatches = _evicted(), []
    run = rt._run
    rt._run = lambda pack, k: dispatches.append(len(pack.seg_key)) \
        or run(pack, k)
    with cache.transaction():
        answers = rt.extend([
            seq_runtime.Turn(u, history[40:43 + i], CFG.vocab_size)
            for i, u in enumerate(users)])
        # Inside the call: the first program's users are committed (the
        # pool was dry), the second's are staged.
        assert dispatches == [4, 2] and sorted(cache._staged) == ["e", "f"]
    for i, answer in enumerate(answers):
        np.testing.assert_allclose(_dense(answer), want[42 + i], atol=TOL)
    assert _evicted() == evicted and all(cache.has(u) for u in users)
    assert [cache.length(u) for u in users] == [43 + i for i in range(6)]
    assert len(cache._free_slots) == 4


def test_eviction_then_a_refill_gives_the_same_answer(params, history, want):
    rt = _runtime(params, max_users=2, write_slots=2)
    _ask(rt, ("a", history[:50]))
    _ask(rt, ("b", history[:10]))
    _ask(rt, ("c", history[:10]))                  # evicts a
    assert not rt.cache.has("a")
    answer = _ask(rt, ("a", history[:60]))[0]
    np.testing.assert_allclose(_dense(answer), want[59], atol=TOL)


@pytest.mark.parametrize("kw,moved", [
    ({"forced_only": True}, "selection left out"),
    ({"no_decay": True}, "decay left out"),
], ids=["forced-blocks-only", "no-decay"])
def test_leaving_a_mechanism_out_moves_the_answers(params, history, want,
                                                   kw, moved):
    """The negative controls' arithmetic at this size: every answer past
    ``dense_len`` moves by several times what the served path may be off
    by (one block of four is left out here; 31 of 64 at full size)."""
    off = np.abs(_forward(params, history, **kw) - want).max(axis=1)
    assert np.median(off[CFG.dense_len + 8:]) > 2 * TOL, moved
    if "forced_only" in kw:      # the dense path has no selection
        assert off[:CFG.dense_len].max() < 1e-4


def test_float8_weights_move_the_answers(params, history, want):
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        if a.dtype == jnp.bfloat16 else a, params)
    off = np.abs(_forward(low, history) - want).max(axis=1)
    assert np.median(off) > 4 * TOL


# -- the selection alone -----------------------------------------------------

def _selection_case(case):
    rng = np.random.default_rng(11)
    n, h, kv, hd = 70, CFG.num_attention_heads, 2, CFG.head_dim
    q = rng.normal(size=(n, h, hd)).astype(np.float32)
    k = rng.normal(size=(n, kv, hd)).astype(np.float32)
    if case == "ties":
        k[:] = k[0]                  # every pooled key alike: all scores tie
    return q, k


@pytest.mark.parametrize("case,positions", [
    ("forced", [24, 31, 32, 69]),        # block starts and ends
    ("middle", [27, 45, 60]),            # a query in its block's middle
    ("ties", [40, 55, 69]),
], ids=["forced-blocks", "mid-block", "ties"])
def test_selection_picks_the_references_blocks(case, positions):
    q, k = _selection_case(case)
    n = len(q)
    with jax.default_matmul_precision("highest"):
        mask = np.asarray(ref.selection_mask(CFG, jnp.asarray(q),
                                             jnp.asarray(k)))
    # The program's side: the pooled keys as rows of one user's pages
    # (pages 1 ... of the pool, in order), the queries a tile each.
    per, kvw = PAGE // CFG.kernel_stride, 2 * CFG.head_dim
    nj = (n - CFG.kernel_size) // CFG.kernel_stride + 1
    idx = np.zeros(((1 + TABLE) * per, kvw), np.float32)
    win = (np.arange(nj) * CFG.kernel_stride)[:, None] \
        + np.arange(CFG.kernel_size)
    idx[per:per + nj] = k[win].mean(axis=1).reshape(nj, kvw)
    tiles = len(positions)
    tile_pos = np.full((tiles, 8), -1, np.int32)
    tile_pos[:, 0] = positions
    qt = np.zeros((tiles, 2, 2, 8, CFG.head_dim), np.float32)
    qt[:, :, :, 0] = q[positions].reshape(tiles, 2, 2, -1) \
        / np.sqrt(CFG.head_dim)
    batch = {"tile_table": jnp.asarray(np.tile(np.arange(1, TABLE + 1),
                                               (tiles, 1)), jnp.int32),
             "tile_pos": jnp.asarray(tile_pos)}
    with jax.default_matmul_precision("highest"):
        sel = np.asarray(sala.select_blocks(
            CFG, jnp.asarray(qt), jnp.asarray(idx), batch, PAGE))
    for t, p in enumerate(positions):
        bq = p // CFG.block_size
        for g in range(2):
            mine = set(int(b) for b in sel[t, g, 0] if b <= bq)
            theirs = {b for b in range(bq + 1)
                      if mask[g, p, b * CFG.block_size]}
            assert mine == theirs, (case, p, g)
            assert {0, bq, bq - 1} <= mine          # the forced blocks
            assert len(mine) == min(CFG.topk, bq + 1)
    if case == "ties":                 # lax.top_k: the lowest ids win
        assert set(sel[-1, 0, 0]) == {0, 1, 7, 8}


# -- the kernels against their XLA twins ------------------------------------

def test_lightning_kernel_matches_its_twin():
    rng = np.random.default_rng(0)
    nt, heads, tq, hd = 5, 8, 8, 128
    q, k, v = (jnp.asarray(rng.normal(size=(nt, heads, tq, hd)),
                           jnp.bfloat16) for _ in range(3))
    state = jnp.asarray(rng.normal(size=(6, heads, hd, hd)),
                        jnp.float32).at[0].set(0.0)
    rate = jnp.asarray(2.0 ** (-8 * (np.arange(heads) + 1) / heads),
                       jnp.float32)
    tiles = [jnp.asarray(a, jnp.int32) for a in (
        [1, 0, 1, 1, 1], [8, 3, 5, 8, 0], [2, 2, 0, 3, 0], [4, 4, 5, 3, 1])]
    o_x, s_x = sala_kernels.lightning(q, k, v, state, rate, *tiles, hb=4,
                                      use_pallas=False)
    o_p, s_p = sala_kernels.lightning(q, k, v, state, rate, *tiles, hb=4,
                                      use_pallas=True)
    np.testing.assert_allclose(o_p[:4], o_x[:4], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_p[2:], s_x[2:], rtol=1e-5, atol=1e-5)
    # A user's second tile continued from the first; untouched slots stay.
    np.testing.assert_array_equal(s_p[2], state[2])
    assert float(jnp.abs(s_p[4] - state[2]).max()) > 1.0


def test_sparse_attention_kernel_matches_its_twin():
    rng = np.random.default_rng(0)
    nt, groups, heads, tq, hd, page, block, topk = 3, 2, 4, 8, 128, 128, 64, 6
    pool = jnp.asarray(rng.normal(size=(12 * page, 2 * groups * hd)),
                       jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(nt, groups, heads * tq, hd)) * 0.1,
                    jnp.bfloat16)
    pos = np.array([[700 + i for i in range(8)],
                    [40 + i if i < 5 else -1 for i in range(8)], [-1] * 8])
    meta = np.full((nt, groups, tq, 128), -1, np.int32)
    meta[..., :topk] = rng.integers(0, 11, (nt, groups, tq, topk))
    meta[..., sala_kernels.POS_LANE] = pos[:, None, :]
    meta[..., sala_kernels.DENSE_LANE] = (pos < 100)[:, None, :]
    pages = np.full((nt, groups, 16), 1023, np.int32)
    cnt = np.zeros((nt, groups), np.int32)
    for g in range(groups):
        cnt[0, g], cnt[1, g] = 6, 1
        pages[0, g, :6] = [(3 + j + g) << 10 | j for j in range(6)]
        pages[1, g, 0] = (9 + g) << 10
    args = (q, jnp.asarray(meta), jnp.asarray(cnt), jnp.asarray(pages), pool)
    kw = dict(page=page, block=block, topk=topk, pb=8)
    o_x = sala_kernels.sparse_attention(*args, **kw, use_pallas=False)
    o_p = sala_kernels.sparse_attention(*args, **kw, use_pallas=True)
    np.testing.assert_allclose(o_p, o_x, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(o_p[0]).max()) > 0.1 and not o_p[2].any()
    # Padding rows (position -1) attend to nothing.
    assert not np.asarray(o_p[1]).reshape(groups, heads, tq, hd)[:, :, 5:].any()


# -- the state cache's pooled write side and page tables ---------------------

def _cache(**kw):
    return StateCache(sala.state_layout(CFG, PAGE, TABLE),
                      budget_bytes=1 << 20, max_users=3, write_slots=2,
                      page_size=PAGE, **kw)


def test_pooled_write_side_commits_swaps_and_rolls_back():
    cache = _cache()
    assert cache.n_slots == 2 + 3 + 2
    with cache.transaction():
        plan = cache.plan(["u", "v"], [PAGE + 1, 3])
        assert plan.read_slot == [cache.ZERO_SLOT] * 2
        assert len(set(plan.table_row)) == 2 and 0 not in plan.table_row
        assert [(r, i) for r, i, _ in plan.new_pages] == [
            (plan.table_row[0], 0), (plan.table_row[0], 1),
            (plan.table_row[1], 0)]
        cache.stage(plan)
        assert cache.read_slot("u") == plan.write_slot[0]
    first = plan.write_slot[0]
    with cache.transaction():
        cache.stage(cache.plan(["w"], [1]))
    free = len(cache._free_slots)
    assert free == 2                  # three residents: the write side
    with cache.transaction():
        cache.plan(["v", "w"], [1, 1])
        with pytest.raises(StateCacheFull):     # a third user: no slot left
            cache.plan(["u"], [1])
    with cache.transaction():
        plan = cache.plan(["u"], [2])
        assert plan.read_slot == [first] and plan.write_slot[0] != first
        assert plan.new_pages == []            # room left in page two
        again = cache.plan(["u"], [1])         # the same slot, in place
        assert again.write_slot == plan.write_slot
        cache.stage(plan)
    assert cache.read_slot("u") == plan.write_slot[0]
    assert len(cache._free_slots) == free       # the old slot came back
    with pytest.raises(RuntimeError):
        with cache.transaction():
            cache.stage(cache.plan(["u", "w"], [40, 1]))
            raise RuntimeError
    assert cache.read_slot("u") == plan.write_slot[0]
    assert len(cache._free_slots) == free and cache.length("u") == PAGE + 3
    assert cache.snapshot()["pagesUsed"] == 4


def test_a_transaction_over_the_pool_commits_in_parts():
    """Three residents, two write slots.  The third user of one
    transaction finds the pool dry: the two whose program has run are
    committed then, and a failure after that rolls back the third alone,
    every state whole."""
    cache = _cache()
    with cache.transaction():
        cache.stage(cache.plan(["u", "v"], [PAGE, 3]))
    with cache.transaction():
        cache.stage(cache.plan(["w"], [5]))
    slots = {k: cache.read_slot(k) for k in "uvw"}
    pages = {k: list(cache._entries[k].pages) for k in "uvw"}
    with pytest.raises(RuntimeError, match="third program"):
        with cache.transaction():
            first = cache.plan(["u", "v"], [2, PAGE])
            cache.stage(first)
            assert not cache._free_slots
            second = cache.plan(["w"], [PAGE])
            # u and v are theirs for good; w writes where u's state was
            # or v's, which nobody reads any more.
            assert sorted(cache._staged) == ["w"]
            assert second.write_slot[0] in (slots["u"], slots["v"])
            assert second.read_slot == [slots["w"]]
            cache.stage(second)
            raise RuntimeError("third program")
    assert [cache.length(k) for k in "uvw"] == [PAGE + 2, 3 + PAGE, 5]
    assert cache.read_slot("u") == first.write_slot[0]
    assert cache.read_slot("v") == first.write_slot[1]
    assert cache.read_slot("w") == slots["w"]
    assert cache._entries["w"].pages == pages["w"]
    assert cache._entries["u"].pages[:1] == pages["u"]
    assert len(cache._free_slots) == 2
    held = sum(len(e.pages) for e in cache._entries.values())
    assert held == 2 + 2 + 1 == cache.snapshot()["pagesUsed"]
    # A split turn is in both programs' plans, so it is never committed
    # in part: with "u" in the second plan too, only "v" goes early.
    with cache.transaction():
        cache.stage(cache.plan(["u", "v"], [1, 1]))
        cache.plan(["u", "w"], [1, 1])
        assert sorted(cache._staged) == ["u", "w"]


def test_state_bytes_by_kind_and_a_history_longer_than_the_table():
    cache = _cache(registry=get_registry())
    text = get_registry().render()
    slot = 2 * 4 * 8 * 8 * 4 + 32 * 4
    assert f'pio_seq_state_bytes{{kind="fixed"}} {7 * slot}' in text
    with cache.transaction():
        cache.stage(cache.plan(["u"], [PAGE + 1]))
    text = get_registry().render()
    assert f'pio_seq_state_bytes{{kind="paged"}} {2 * 2 * PAGE * 32 * 2}' \
        in text
    assert f'pio_seq_state_bytes{{kind="index"}} {2 * 2 * 8 * 16 * 2}' \
        in text
    assert cache.max_events == TABLE * PAGE and cache.page_list_len is None
    with pytest.raises(StateCacheFull), cache.transaction():
        cache.plan(["v"], [TABLE * PAGE + 1])


# -- the host-side counters against a brute count ----------------------------

@pytest.mark.parametrize("start,n", [(0, 20), (20, 10), (30, 45), (74, 1)])
def test_counters_from_positions_match_a_brute_count(start, n):
    got = sala.selection_counts(CFG, start, n)
    q = np.zeros((start + n, 4, 8), np.float32)
    k = np.random.default_rng(1).normal(size=(start + n, 2, 8))
    mask = np.asarray(ref.selection_mask(CFG, jnp.asarray(q),
                                         jnp.asarray(k, jnp.float32)))
    pos = np.arange(start, start + n)
    sel = pos[pos + 1 > CFG.dense_len]
    assert got["dense"] == n - len(sel) and got["selected"] == len(sel)
    assert got["keys"] == int(mask[0, sel].sum())
    windows = sum(sum(1 for j in range(start + n)
                      if j * CFG.kernel_stride + CFG.kernel_size - 1 <= p)
                  for p in sel)
    assert got["pairs"] == windows


def test_a_dispatch_moves_the_counters(fresh, history):
    reg = get_registry()

    def read():
        out = {}
        for line in reg.render().splitlines():
            if line.startswith("pio_seq_") and not line.startswith("#"):
                key, _, value = line.rpartition(" ")
                out[key] = float(value)
        return out

    before = read()
    _ask(fresh, ("a", history[:28]), ("b", history[:4]))
    after = read()

    def grew(key):
        return after.get(key, 0.0) - before.get(key, 0.0)

    want = sala.selection_counts(CFG, 0, 28)
    assert grew('pio_seq_sparse_queries_total{path="dense"}') \
        == (want["dense"] + 4) * 2
    assert grew('pio_seq_sparse_queries_total{path="selected"}') \
        == want["selected"] * 2
    assert grew("pio_seq_sparse_keys_total") == want["keys"] * 2 * 2
    assert grew("pio_seq_index_pairs_total") == want["pairs"] * 2 * 2
    # 2 users x 2 lightning layers, in one dispatch of 32 events.
    assert grew("pio_seq_recurrent_updates_total") == 4
    assert after['pio_seq_state_bytes{kind="index"}'] > 0


# -- the template: train -> deploy -> /queries.json --------------------------

SALA_VARIANT = {
    "engineFactory": "predictionio_tpu.templates.sequence:engine",
    "datasource": {"params": {"appName": "seqapp"}},
    "preparator": {"params": {"vocabSize": 64}},
    "algorithms": [{"name": "sequence", "params": {
        "backbone": "sala", "hiddenSize": 32, "intermediateSize": 48,
        "numAttentionHeads": 4, "numKeyValueHeads": 2, "headDim": 8,
        "mixerTypes": ["minicpm4", "lightning-attn", "lightning-attn"],
        "sparseConfig": {"kernel_size": 4, "kernel_stride": 2,
                         "block_size": 8, "topk": 4, "window_size": 16,
                         "dense_len": 24},
        "steps": 150, "batchSize": 16, "window": 12, "learningRate": 0.01,
        "seed": 5, "stateBudgetMB": 8.0, "maxUsers": 80}}],
}


def test_train_deploy_query_on_the_sala_backbone(ctx):  # noqa: F811
    from predictionio_tpu.server import EngineServer
    from predictionio_tpu.templates.sequence import engine
    from predictionio_tpu.workflow.core_workflow import run_train

    _seed_cycles(ctx)
    eng = engine()
    variant = EngineVariant.from_dict(SALA_VARIANT)
    run_train(eng, variant, ctx)
    srv = EngineServer(eng, variant, ctx.storage, host="127.0.0.1", port=0)
    srv.start()
    try:
        model = srv._models[0]
        assert isinstance(model.config, sala.SALAConfig)
        first = _post(srv, "/queries.json", {
            "user": "visitor", "num": 3, "events": ["i2", "i3", "i4"]})
        assert _items(first)[0] == "i5" and len(first["itemScores"]) == 3
        second = _post(srv, "/queries.json", {
            "user": "visitor", "num": 3, "events": ["i5", "i6"]})
        assert _items(second)[0] == "i7"
        assert model.state_cache.length("visitor") == 5
        again = _post(srv, "/queries.json", {"user": "visitor", "num": 3})
        assert _items(again) == _items(second)
        # query_batch is the same path; a stored user is read back.
        out = srv.query_batch([{"user": "u3", "num": 2,
                                "events": ["i5", "i6"]}])
        assert [s["item"] for s in out[0]["itemScores"]][0] == "i7"
        # A call of more users than one program touches (64): two
        # programs within the write pool, nobody evicted.
        cache, evicted = model.state_cache, _evicted()
        out = srv.query_batch([{"user": f"b{i}", "num": 1,
                                "events": ["i2", "i3"]} for i in range(70)])
        assert {s["itemScores"][0]["item"] for s in out} == {"i4"}
        assert _evicted() == evicted and cache.has("visitor")
        assert cache.write_slots == 64 and len(cache._free_slots) == \
            cache.max_users + 64 - cache.snapshot()["users"]
        out = srv.query_batch([{"user": f"b{i}", "num": 1,
                                "events": ["i4"]} for i in range(70)])
        assert {s["itemScores"][0]["item"] for s in out} == {"i5"}
        assert _evicted() == evicted and cache.length("b69") == 3
    finally:
        srv.stop()


def test_an_unknown_backbone_is_an_error(ctx):  # noqa: F811
    from predictionio_tpu.templates.sequence import engine
    from predictionio_tpu.workflow.core_workflow import run_train

    _seed_cycles(ctx)
    bad = {**SALA_VARIANT, "algorithms": [{"name": "sequence", "params": {
        **SALA_VARIANT["algorithms"][0]["params"], "backbone": "nope"}}]}
    with pytest.raises(ValueError, match="unknown backbone"):
        run_train(engine(), EngineVariant.from_dict(bad), ctx)
