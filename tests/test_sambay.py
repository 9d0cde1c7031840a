"""The Mamba / sliding-window / shared-cache backbone of the sequence
engine: the ragged step against the plain reference's whole forward pass
(``models/sambay_reference.py``) through all three kinds of state, the
kernels against their XLA twins, the state cache's window pool beside the
pool that grows, the decoder split by its counters, and the template
through train -> deploy -> ``query_batch``.  CPU, tiny widths, seeded
weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.controller import EngineVariant
from predictionio_tpu.models import lfm2, sala, sambay, seq_runtime
from predictionio_tpu.models import sambay_reference as ref
from predictionio_tpu.obs import get_registry
from predictionio_tpu.ops import sambay_kernels
from predictionio_tpu.serving.state_cache import StateCache
from tests.test_sequence import (  # noqa: F401 - fixtures, used by name
    _items, _post, _seed_cycles, ctx)

# Eight layers: Mamba, window, Mamba, window, Mamba (the memory), the full
# layer, a GMU, a cross layer.  Pages of 8 events; a window of 12 reaches
# back over at most 3 of them.
CFG = sambay.SambaYConfig(
    vocab_size=97, hidden_size=32, intermediate_size=48,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=8,
    sliding_window=12, d_state=4)
PAGE, TABLE = 8, 12
N = 75
# bfloat16 weights, keys, values and matmul inputs against a float32
# reference: logits of spread 1 agree to under a tenth (the widest of 40
# answers read 0.082; with float32 weights and bfloat16 keys alone
# 0.021), where a control moves them by 0.3 and more.
TOL = 0.12


@pytest.fixture(scope="module")
def params():
    return sambay.init_params(CFG, jax.random.PRNGKey(3))


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
    rt = sambay.make_runtime(CFG, params, budget_bytes=budget,
                             max_users=max_users, write_slots=write_slots,
                             page_size=PAGE, table_len=TABLE)
    rt.token_buckets, rt.read_buckets = (16, 32), (0, 4)
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


def _counters():
    out = {}
    for line in get_registry().render().splitlines():
        if line.startswith("pio_seq_") and not line.startswith("#"):
            key, _, value = line.rpartition(" ")
            out[key] = float(value)
    return out


def test_the_layer_pattern_follows_the_depth():
    assert CFG.kinds == ("mamba", "window", "mamba", "window", "mamba",
                         "full", "gmu", "cross")
    big = sambay.SambaYConfig.from_published({
        "vocab_size": 200064, "hidden_size": 2560,
        "intermediate_size": 10240, "num_attention_heads": 40,
        "num_key_value_heads": 20, "num_hidden_layers": 32,
        "sliding_window": 512, "mb_per_layer": 2, "layer_norm_eps": 1e-5})
    assert [big.count(k) for k in ("mamba", "window", "full", "cross",
                                   "gmu")] == [9, 8, 1, 7, 7]
    assert (big.memory_layer, big.full_layer, big.dt_rank, big.d_inner,
            big.head_dim, big.kv_width) == (16, 17, 160, 5120, 64, 2560)
    n = sum(int(np.prod(s)) for layer in range(32)
            for s in sambay.layer_shapes(big, layer).values()) \
        + 200064 * 2560 + 2 * 2560
    assert round(n / 1e6, 1) == 3852.6
    layout = sambay.state_layout(big, 128)
    assert layout["fixed_bytes"] == 9 * (16 + 3) * 5120 * 4 \
        + (2560 + 5120) * 4
    assert (layout["window_bytes"], layout["paged_bytes"]) \
        == (5_242_880, 655_360)
    with pytest.raises(ValueError, match="multiple of 4"):
        sambay.SambaYConfig(97, 32, 48, 4, 2, 6)


# -- the served path against the whole forward pass -------------------------

@pytest.mark.parametrize("cuts", [
    (N,),                          # all at once: chunks of 32 by the runtime
    (11, 12, 13, N),               # across the window's edge, one at a time
    (7, 8, 9, 15, 17, N),          # across page borders
    (20, 21, 24, 25, 33, 40, N),   # turns that release a window page
    (1, 2, 3, 4, 5, N),
    (5, 9),                        # a user shorter than the window
], ids=["at-once", "window-edge", "page-borders", "releases",
        "from-nothing", "short-user"])
def test_prefill_then_turns_is_one_forward_pass(fresh, history, want, cuts):
    at = 0
    for upto in cuts:
        answer = _ask(fresh, ("a", history[at:upto]))[0]
        at = upto
        np.testing.assert_allclose(_dense(answer), want[at - 1], atol=TOL)
        held = fresh.cache._entries["a"]
        # What the window of the NEXT event reaches, and no page more.
        first = max(at - (CFG.sliding_window - 1), 0) // PAGE
        assert (held.wbase, len(held.wpages)) \
            == (first, (at - 1) // PAGE + 1 - first)
        assert len(held.wpages) <= fresh.cache.window_pages_per_user == 3
    assert fresh.cache.length("a") == cuts[-1]


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
    # ... and a later call with no event answers from the stored rows.
    again = _ask(fresh, ("a", []))[0]
    np.testing.assert_allclose(_dense(again), _dense(a3), atol=1e-6)


def test_a_failed_dispatch_restores_all_three_kinds(fresh, history, want):
    _ask(fresh, ("a", history[:45]))
    cache = fresh.cache
    slot = cache.read_slot("a")
    before = {k: np.asarray(v) for k, v in cache.arrays.items()}
    entry = cache._entries["a"]
    pages, wpages, wbase = list(entry.pages), list(entry.wpages), entry.wbase
    free = (sorted(cache._free_pages), sorted(cache._free_slots),
            sorted(cache._free_wpages))
    released = _counters().get(
        'pio_seq_window_pages_total{event="released"}', 0.0)
    with pytest.raises(RuntimeError, match="serve failed"):
        with cache.transaction():
            fresh.extend([seq_runtime.Turn("a", history[45:70], 5),
                          seq_runtime.Turn("b", history[:20], 5)])
            assert cache.length("a") == 70 and cache.read_slot("a") != slot
            # The turn passed the window's old pages; they are still a's.
            assert cache._staged["a"].wbase > wbase
            assert cache._entries["a"].wpages == wpages
            raise RuntimeError("serve failed")
    assert cache.length("a") == 45 and cache.read_slot("a") == slot
    assert not cache.has("b")
    assert (cache._entries["a"].wpages, cache._entries["a"].wbase) \
        == (wpages, wbase)
    assert (sorted(cache._free_pages), sorted(cache._free_slots),
            sorted(cache._free_wpages)) == free
    after = {k: np.asarray(v) for k, v in cache.arrays.items()}
    for name in before:
        if name in ("table",):
            continue
        if name == "kv":                                   # full
            rows = np.concatenate([np.arange(p * PAGE, (p + 1) * PAGE)
                                   for p in pages])[:45]
        elif name.startswith("wkv"):                       # window
            rows = np.concatenate([np.arange(p * PAGE, (p + 1) * PAGE)
                                   for p in wpages])[:45 - wbase * PAGE]
        else:                                              # fixed
            rows = [slot]
        np.testing.assert_array_equal(after[name][rows], before[name][rows])
    # ... and the same turn again answers as if nothing had happened, its
    # window found where it was; only now do the passed pages go back.
    answer = _ask(fresh, ("a", history[45:70]))[0]
    np.testing.assert_allclose(_dense(answer), want[69], atol=TOL)
    assert _counters()['pio_seq_window_pages_total{event="released"}'] \
        > released


def _evicted():
    return _counters().get('pio_seq_state_total{result="evicted"}', 0.0)


def test_a_call_of_more_users_than_a_program_touches_evicts_nobody(
        params, history, want):
    """Six residents, a pool of four write slots: one call with a turn of
    each runs as two programs inside the pool, the first committed (its
    window pages released) when the second is planned."""
    rt = _runtime(params)
    users = "abcdef"
    for u in users:
        _ask(rt, (u, history[:40]))
    cache = rt.cache
    evicted = _evicted()
    with cache.transaction():
        answers = rt.extend([
            seq_runtime.Turn(u, history[40:43 + i], CFG.vocab_size)
            for i, u in enumerate(users)])
        assert sorted(cache._staged) == ["e", "f"]
    for i, answer in enumerate(answers):
        np.testing.assert_allclose(_dense(answer), want[42 + i], atol=TOL)
    assert _evicted() == evicted and all(cache.has(u) for u in users)
    assert len(cache._free_slots) == 4
    held = sum(len(e.wpages) for e in cache._entries.values())
    assert held == cache.snapshot()["windowPagesUsed"] <= 6 * 3


def test_eviction_then_a_refill_gives_the_same_answer(params, history, want):
    rt = _runtime(params, max_users=2, write_slots=2)
    cache = rt.cache
    _ask(rt, ("a", history[:50]))
    _ask(rt, ("b", history[:10]))
    _ask(rt, ("c", history[:10]))                  # evicts a
    assert not cache.has("a")
    assert cache.snapshot()["windowPagesUsed"] == 2 * 2
    assert cache.snapshot()["pagesUsed"] == 2 * 2
    answer = _ask(rt, ("a", history[:60]))[0]
    np.testing.assert_allclose(_dense(answer), want[59], atol=TOL)
    # A reload frees all three kinds.
    cache.free()
    snap = _counters()
    assert [snap[f'pio_seq_state_bytes{{kind="{k}"}}']
            for k in ("fixed", "window", "full")] == [0, 0, 0]
    assert len(cache._free_wpages) == cache.n_wpages \
        and len(cache._free_pages) == cache.n_pages
    answer = _ask(rt, ("a", history[:60]))[0]      # allocates anew
    np.testing.assert_allclose(_dense(answer), want[59], atol=TOL)


# -- the negative controls' arithmetic at this size --------------------------

def _turn_starts(n, every=5):
    return np.arange(n) % every == 0


@pytest.mark.parametrize("kw,moved", [
    ({"zero_lambda": True}, "the second softmax left out"),
    ({"window": 9}, "a window page released one too early"),
    ({"state_resets": _turn_starts(N)}, "the scan state lost at every turn"),
], ids=["zero-lambda", "short-window", "state-resets"])
def test_leaving_a_mechanism_out_moves_the_answers(params, history, want,
                                                   kw, moved):
    off = np.abs(_forward(params, history, **kw) - want).max(axis=1)
    assert np.median(off[16:]) > 2 * TOL, moved
    if "window" in kw:             # a history inside the window reads it all
        assert off[:9].max() < 1e-4


def test_float8_weights_move_the_answers(params, history, want):
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        if a.dtype == jnp.bfloat16 else a, params)
    off = np.abs(_forward(low, history) - want).max(axis=1)
    assert np.median(off) > 2 * TOL


# -- the kernels against their XLA twins ------------------------------------

def test_selective_scan_kernel_matches_its_twin():
    rng = np.random.default_rng(0)
    nt, tq, e, n = 5, 16, 1024, 16
    x = jnp.asarray(rng.normal(size=(nt, tq, e)), jnp.float32)
    cnt = np.array([16, 3, 5, 16, 0])
    real = np.arange(tq)[None, :] < cnt[:, None]
    delta = jnp.asarray(np.where(real[..., None], rng.uniform(
        1e-3, 0.3, (nt, tq, e)), 0.0), jnp.float32)
    bt, ct = (jnp.asarray(rng.normal(size=(nt, n, tq)), jnp.float32)
              for _ in range(2))
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1)[:, None], (n, e))
    d = jnp.ones((1, e), jnp.float32)
    state = jnp.asarray(rng.normal(size=(6, n, e)),
                        jnp.float32).at[0].set(0.0)
    tiles = [jnp.asarray(v, jnp.int32) for v in (
        [1, 0, 1, 1, 1], cnt, [2, 2, 0, 3, 0], [4, 4, 5, 3, 1])]
    y_x, s_x = sambay_kernels.selective_scan(x, delta, bt, ct, a, d, state,
                                             *tiles, use_pallas=False)
    y_p, s_p = sambay_kernels.selective_scan(x, delta, bt, ct, a, d, state,
                                             *tiles, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_p)[real[:4].nonzero()],
                               np.asarray(y_x)[real[:4].nonzero()],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_p[2:], s_x[2:], rtol=1e-5, atol=1e-5)
    # A user's second tile continued from the first; untouched slots stay.
    np.testing.assert_array_equal(s_p[2], state[2])
    assert float(jnp.abs(s_p[4] - state[2]).max()) > 0.1
    # The recurrence itself, an event at a time, for the first tile.
    h = np.asarray(state[2])
    for t in range(tq):
        dt = np.asarray(delta[0, t])
        h = np.exp(dt[None] * np.asarray(a)) * h + (
            dt * np.asarray(x[0, t]))[None] * np.asarray(bt[0, :, t])[:, None]
        np.testing.assert_allclose(
            y_x[0, t], (h * np.asarray(ct[0, :, t])[:, None]).sum(0)
            + np.asarray(x[0, t]), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window,name", [
    (0, "sambay_shared_attention"), (300, "sambay_window_attention")])
def test_paged_attention_kernel_matches_its_twin(window, name):
    rng = np.random.default_rng(0)
    nt, pairs, rows, pw, page = 3, 2, 16, 128, 128
    pool = jnp.asarray(rng.normal(size=(12 * page, 2 * pairs * pw)),
                       jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(nt, pairs, rows, pw)) * 0.1,
                    jnp.bfloat16)
    pos = np.array([[700 + i % 8 for i in range(rows)],
                    [40 + i % 8 if i % 8 < 5 else -1 for i in range(rows)],
                    [-1] * rows])
    pages = np.full((nt, 8), 1023, np.int32)
    pages[0, :6] = [(3 + j) << 10 | j for j in range(6)]
    pages[1, 0] = 9 << 10
    args = (q, jnp.asarray(pos, jnp.int32),
            jnp.asarray([6, 1, 0], jnp.int32), jnp.asarray(pages), pool)
    kw = dict(page=page, window=window, pb=4, name=name)
    o_x = sambay_kernels.paged_attention(*args, **kw, use_pallas=False)
    o_p = sambay_kernels.paged_attention(*args, **kw, use_pallas=True)
    # The kernel rounds its weights to bfloat16 against a running maximum
    # (two steps of 4 pages here), the twin against the row's own: values
    # of size 0.1 agree to bfloat16's last bits.
    np.testing.assert_allclose(o_p, o_x, rtol=1e-2, atol=1e-3)
    assert float(jnp.abs(o_p[0]).max()) > 0.05 and not o_p[2].any()
    # Padding rows (position -1) attend to nothing.
    assert not np.asarray(o_p[1])[:, pos[1] < 0].any()
    # The plain softmax over what a row sees, for one row.
    keys = np.asarray(pool, np.float32).reshape(12, page, 2, pairs, pw)
    hist = np.concatenate([keys[3 + j] for j in range(6)])   # [768, 2, ...]
    p0 = pos[0, 3]
    seen = np.arange(768) <= p0
    if window:
        seen &= p0 - np.arange(768) < window
    s = hist[:, 0, 1] @ np.asarray(q[0, 1, 3], np.float32)
    w = np.where(seen, np.exp(s - s[seen].max()), 0.0)
    np.testing.assert_allclose(o_x[0, 1, 3], (w / w.sum()) @ hist[:, 1, 1],
                               rtol=2e-2, atol=2e-3)


# -- the state cache's window pool -------------------------------------------

def _cache(**kw):
    return StateCache(sambay.state_layout(CFG, PAGE, TABLE),
                      budget_bytes=1 << 20, max_users=3, write_slots=2,
                      page_size=PAGE, **kw)


def test_window_pages_go_back_at_commit_and_not_before():
    cache = _cache()
    assert cache.window_pages_per_user == 3
    assert cache.n_wpages == 3 * 3 + 2 + 1024 // PAGE + 1
    with cache.transaction():
        cache.stage(cache.plan(["u"], [18]))     # pages 0-2, all in reach
    first = list(cache._entries["u"].wpages)
    assert len(first) == 3 and cache._entries["u"].wbase == 0
    free = len(cache._free_wpages)
    with pytest.raises(RuntimeError):
        with cache.transaction():
            plan = cache.plan(["u"], [8])        # 18..25: page 3 is new
            assert plan.seg_wpages[0][:3] == first and plan.seg_wbase == [0]
            cache.stage(plan)
            # Page 0 (events 0-7) lies behind event 26's window (15..26)
            # and is still u's: the turn can roll back.
            assert cache._staged["u"].wbase == 1
            assert cache._entries["u"].wpages == first
            assert len(cache._free_wpages) == free - 1
            raise RuntimeError
    assert cache._entries["u"].wpages == first
    assert len(cache._free_wpages) == free
    with cache.transaction():
        cache.stage(cache.plan(["u"], [8]))
    held = cache._entries["u"]
    assert (held.wbase, len(held.wpages)) == (1, 3)
    assert held.wpages[:2] == first[1:]
    assert len(cache._free_wpages) == free       # one back, one taken
    assert first[0] in cache._free_wpages
    assert len(held.pages) == 4                  # the full pool keeps all


def test_a_long_history_in_one_transaction_recycles_its_own_pages():
    """Pages the transaction handed out itself go back as soon as a later
    program of it has passed them: a history of any length is read within
    the pool."""
    cache = _cache()
    free = len(cache._free_wpages)
    with cache.transaction():
        for _ in range(3):                       # 3 programs of 32 events
            cache.stage(cache.plan(["u"], [32]))
            assert free - len(cache._free_wpages) <= 3
    assert cache.length("u") == 96
    held = cache._entries["u"]
    assert (held.wbase, len(held.wpages)) == ((96 - 11) // PAGE, 2)
    assert len(held.pages) == 12 == TABLE


def test_commit_early_with_two_page_kinds():
    """Three residents, two write slots: the third user of a transaction
    finds the slot pool dry, the first two are committed there and then,
    and what fell behind their windows goes back with that commit."""
    cache = _cache()
    with cache.transaction():
        cache.stage(cache.plan(["u", "v"], [20, 20]))
    with cache.transaction():
        cache.stage(cache.plan(["w"], [5]))
    free = len(cache._free_wpages)
    with pytest.raises(RuntimeError, match="third"):
        with cache.transaction():
            cache.stage(cache.plan(["u", "v"], [10, 10]))
            assert len(cache._free_wpages) == free - 2
            cache.stage(cache.plan(["w"], [1]))
            assert sorted(cache._staged) == ["w"]
            # u and v (pages 1, 2 each): page 3 taken, page 1 back.
            assert len(cache._free_wpages) == free
            raise RuntimeError("third")
    assert [cache.length(k) for k in "uvw"] == [30, 30, 5]
    assert len(cache._free_wpages) == free
    assert [len(cache._entries[k].wpages) for k in "uvw"] == [2, 2, 1]
    held = sum(len(e.wpages) for e in cache._entries.values())
    assert held == cache.snapshot()["windowPagesUsed"]


def test_state_bytes_name_the_kinds_that_exist():
    cache = _cache(registry=get_registry())
    with cache.transaction():
        cache.stage(cache.plan(["u"], [PAGE + 1]))
    text = _counters()
    row = CFG.kv_width * 2
    assert text['pio_seq_state_bytes{kind="full"}'] == 2 * PAGE * row
    assert text['pio_seq_state_bytes{kind="window"}'] == 2 * 2 * PAGE * row
    slot = 3 * (4 + 3) * 64 * 4 + (32 + 64) * 4
    assert text['pio_seq_state_bytes{kind="fixed"}'] == 7 * slot
    assert cache.evict("u")
    text = _counters()
    assert text['pio_seq_state_bytes{kind="full"}'] == 0
    assert text['pio_seq_state_bytes{kind="window"}'] == 0
    assert len(cache._free_slots) == 3 + 2


@pytest.mark.parametrize("layout", ["lfm2", "sala"])
def test_a_one_kind_layout_never_touches_the_window_path(layout):
    """LFM2's and SALA's layouts declare one page kind: the arrays they
    allocate are the ones they did, their plans carry no window list, the
    window pool and its counter stay where they were."""
    from tests.test_sala import CFG as SALA_CFG

    if layout == "sala":
        made = sala.state_layout(SALA_CFG, 16, 8)
        names = {"s0", "s1", "h_last", "kv0", "kv1", "idx0", "idx1",
                 "table"}
    else:
        from tests.test_sequence import CFG as LFM2_CFG

        made = lfm2.state_layout(LFM2_CFG, 16)
        names = None
    taken = _counters().get('pio_seq_window_pages_total{event="taken"}', 0.0)
    cache = StateCache(made, budget_bytes=1 << 20, max_users=3,
                       write_slots=2, page_size=16)
    assert cache.n_wpages == 0 and cache._free_wpages == []
    if names:
        assert set(cache.arrays) == names
    assert not any(k.startswith("wkv") for k in cache.arrays)
    with cache.transaction():
        plan = cache.plan(["u", "v"], [40, 3])
        assert plan.seg_wpages == [] and plan.seg_wbase == []
        cache.stage(plan)
        assert cache._staged["u"].wpages == [] and not cache._txn_wpages
    assert cache._entries["u"].wpages == [] and "windowPages" \
        not in cache.snapshot()
    assert _counters().get('pio_seq_window_pages_total{event="taken"}',
                           0.0) == taken


# -- the decoder split, by its counters --------------------------------------

@pytest.mark.parametrize("start,n,reads", [
    (0, 20, [19]), (20, 10, [24, 29]), (30, 45, [74]), (74, 1, [74])])
def test_counters_from_positions_match_a_brute_count(start, n, reads):
    got = sambay.attention_counts(CFG, start, n, reads)
    pos = np.arange(start, start + n)
    every = np.arange(start + n)
    near = (every[None, :] <= pos[:, None]) \
        & (pos[:, None] - every[None, :] < CFG.sliding_window)
    assert got["window_keys"] == int(near.sum())
    assert got["window_rows"] == int(near.any(axis=0).sum())
    assert got["shared_keys"] == sum(p + 1 for p in reads)


def test_only_read_rows_pass_the_full_layer(fresh, history):
    before = _counters()
    programs = []
    program = fresh.program
    fresh.program = lambda t, r, k: programs.append((t, r)) \
        or program(t, r, k)
    try:
        # 70 events of one user = chunks of 32, 32 and 6: the first two
        # end no turn and run no cross-decoder; b's two turns read twice.
        _ask(fresh, ("a", history[:70]), ("b", history[:4]),
             ("b", history[4:6]))
    finally:
        fresh.program = program
    after = _counters()

    def grew(key):
        return after.get(key, 0.0) - before.get(key, 0.0)

    assert programs == [(32, 0), (32, 0), (16, 4)]
    assert grew("pio_seq_cross_rows_total") == 3
    assert grew('pio_seq_tokens_total{kind="new"}') == 76
    assert grew("pio_seq_dispatches_total") == 3
    # (user, Mamba layer) pairs: a in three programs, b in one.
    assert grew("pio_seq_recurrent_updates_total") == 4 * 3
    want = sambay.attention_counts(CFG, [0, 0], [70, 6], [69, 3, 5])
    assert grew("pio_seq_window_keys_total") == want["window_keys"] * 2
    assert grew("pio_seq_shared_keys_total") == want["shared_keys"] * 2
    assert grew('pio_seq_window_pages_total{event="taken"}') == 9 + 1
    assert grew('pio_seq_window_pages_total{event="released"}') == 7
    assert fresh.cache.snapshot()["windowPagesUsed"] == 3


def test_a_pack_may_hold_one_more_segment_than_reads(fresh, history, want):
    """Four one-event turns fill the read bucket and a long turn behind
    them is split: its first chunk is a fifth segment with no read."""
    out = _ask(fresh, *[(f"u{i}", history[:1]) for i in range(4)],
               ("long", history[:60]))
    np.testing.assert_allclose(_dense(out[4]), want[59], atol=TOL)
    np.testing.assert_allclose(_dense(out[0]), want[0], atol=TOL)


def test_a_chunk_that_ends_no_turn_lowers_no_cross_decoder(runtime):
    """The (t, 0) program's text holds the self-decoder's scan and window
    attention and none of the shared cache's readers, nor the head."""
    cache = runtime.cache
    if not cache.arrays:
        cache.reset()
    step = runtime.step
    vec = jax.ShapeDtypeStruct((sum(sambay.vector_sizes(
        32, 0, step.shapes(32, 0, cache))),), jnp.int32)
    text = step.program(cache, 32, 0, 16).lower(
        runtime.params, cache.arrays, vec).as_text(debug_info=True)
    assert "ssm_scan" in text and "window_attention" in text
    assert "shared_attention" not in text and "seq_head" not in text
    vec = jax.ShapeDtypeStruct((sum(sambay.vector_sizes(
        32, 4, step.shapes(32, 4, cache))),), jnp.int32)
    text = step.program(cache, 32, 4, 16).lower(
        runtime.params, cache.arrays, vec).as_text(debug_info=True)
    assert "shared_attention" in text and "seq_head" in text \
        and "gmu" in text


# -- the template: train -> deploy -> query_batch ----------------------------

SAMBAY_VARIANT = {
    "engineFactory": "predictionio_tpu.templates.sequence:engine",
    "datasource": {"params": {"appName": "seqapp"}},
    "preparator": {"params": {"vocabSize": 64}},
    "algorithms": [{"name": "sequence", "params": {
        "backbone": "sambay", "hiddenSize": 32, "intermediateSize": 48,
        "numAttentionHeads": 4, "numKeyValueHeads": 2,
        "numHiddenLayers": 4, "slidingWindow": 6,
        "ssmConfig": {"d_state": 4},
        "steps": 150, "batchSize": 16, "window": 12, "learningRate": 0.01,
        "seed": 5, "stateBudgetMB": 8.0, "maxUsers": 80}}],
}


def test_train_deploy_query_on_the_sambay_backbone(ctx):  # noqa: F811
    from predictionio_tpu.server import EngineServer
    from predictionio_tpu.templates.sequence import engine
    from predictionio_tpu.workflow.core_workflow import run_train

    _seed_cycles(ctx)
    eng = engine()
    variant = EngineVariant.from_dict(SAMBAY_VARIANT)
    run_train(eng, variant, ctx)
    srv = EngineServer(eng, variant, ctx.storage, host="127.0.0.1", port=0)
    srv.start()
    try:
        model = srv._models[0]
        assert isinstance(model.config, sambay.SambaYConfig)
        assert model.config.kinds == ("mamba", "window", "mamba", "full")
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
        cache = model.state_cache
        out = srv.query_batch([{"user": f"b{i}", "num": 1,
                                "events": ["i2", "i3"]} for i in range(70)])
        assert {s["itemScores"][0]["item"] for s in out} == {"i4"}
        assert cache.has("visitor") and cache.write_slots == 64
        out = srv.query_batch([{"user": f"b{i}", "num": 1,
                                "events": ["i4"]} for i in range(70)])
        assert {s["itemScores"][0]["item"] for s in out} == {"i5"}
        assert cache.length("b69") == 3
        assert "windowPagesUsed" in cache.snapshot()
    finally:
        srv.stop()
