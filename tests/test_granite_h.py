"""The Mamba-2 / no-position attention backbone of the sequence engine:
the ragged step against the plain reference's whole forward pass
(``models/granite_h_reference.py``) through both kinds of state, the
kernel against its XLA twin and against the recurrence itself, the four
multipliers and the missing rotary, the write pool of a backbone whose
slot is large, and the template through train -> deploy ->
``query_batch``.  CPU, tiny widths with every published RATIO kept (one
attention layer among Mamba-2 ones, one group, heads x P = 2 d, N = 2 P,
the MLP 4 d wide), seeded weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.controller import EngineVariant
from predictionio_tpu.models import granite_h, seq_runtime
from predictionio_tpu.models import granite_h_reference as ref
from predictionio_tpu.obs import get_registry
from predictionio_tpu.ops import granite_h_kernels
from predictionio_tpu.serving.state_cache import StateCache
from tests.test_sequence import (  # noqa: F401 - fixtures, used by name
    _items, _post, _seed_cycles, ctx)

CFG = granite_h.GraniteHConfig(
    vocab_size=97, hidden_size=32, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    layer_types=("mamba", "mamba", "attention", "mamba", "mamba"),
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16,
    attention_multiplier=1.0 / 8)
PAGE, TABLE = 8, 12
N = 75
# bfloat16 weights, keys, values and matmul inputs against a float32
# reference: logits of spread 0.11 agree to 0.001 (the widest of ~150
# answers read 0.0012; the floor is the head's bfloat16 rounding of the
# last hidden row), where a dropped multiplier moves them by 0.02 and
# more and float8 weights by 0.015.
TOL = 0.003


@pytest.fixture(scope="module")
def params():
    """``init_params``, with the embedding a quarter as large (so that at
    d = 32 the 12-fold input does not drown the layers' 0.22-fold
    branches, as at d = 2,048 it does not) and the query and key columns
    four times (a peaked softmax, as a trained model's)."""
    p = granite_h.init_params(CFG, jax.random.PRNGKey(3))
    p["embed"] = (p["embed"].astype(jnp.float32) / 4).astype(jnp.bfloat16)
    qk = (CFG.num_attention_heads + CFG.num_key_value_heads) * CFG.head_dim
    for layer in p["layers"]:
        if "w_qkv" in layer:
            w = layer["w_qkv"].astype(jnp.float32)
            layer["w_qkv"] = w.at[:, :qk].multiply(4.0).astype(jnp.bfloat16)
    return p


@pytest.fixture(scope="module")
def history():
    return np.random.default_rng(5).integers(
        0, CFG.vocab_size, N).astype(np.int32)


def _forward(params, tokens, cfg=CFG, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(params, cfg, jnp.asarray(tokens),
                                      **kw))


@pytest.fixture(scope="module")
def want(params, history):
    return _forward(params, history)


def _runtime(params, max_users=6, write_slots=4, budget=1 << 22):
    rt = granite_h.make_runtime(CFG, params, budget_bytes=budget,
                                max_users=max_users,
                                write_slots=write_slots, page_size=PAGE,
                                table_len=TABLE)
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


def test_the_published_shape():
    types = ["mamba"] * 40
    for i in (5, 15, 25, 35):
        types[i] = "attention"
    doc = {"vocab_size": 100352, "hidden_size": 2048,
           "shared_intermediate_size": 8192, "intermediate_size": 8192,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "layer_types": types, "mamba_n_heads": 64, "mamba_d_head": 64,
           "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_expand": 2,
           "mamba_n_groups": 1, "embedding_multiplier": 12,
           "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
           "logits_scaling": 8, "rms_norm_eps": 1e-5,
           "num_local_experts": 0, "position_embedding_type": "nope"}
    big = granite_h.GraniteHConfig.from_published(doc)
    assert (big.count("mamba"), big.count("attention")) == (36, 4)
    assert (big.head_dim, big.d_inner, big.conv_width, big.kv_width) \
        == (64, 4096, 4352, 1024)
    n = sum(int(np.prod(s)) for layer in range(40)
            for s in granite_h.layer_shapes(big, layer).values()) \
        + 100352 * 2048 + 2048
    assert round(n / 1e6, 1) == 3191.4
    layout = granite_h.state_layout(big, 128)
    # 36 states of 2 MiB and the convolution's rows: 77.4 MB a user, so a
    # program touches 32 users and the write pool holds 32 slots.
    assert layout["fixed_bytes"] == 36 * (64 * 64 * 128 + 3 * 4352) * 4 \
        + 2048 * 4 == 77_385_728
    assert layout["paged_bytes"] == 4 * 128 * 1024 * 2 == 1 << 20
    # ... the convolution's three rows as ONE row a slot, 102 x 128 lanes.
    arrays = jax.eval_shape(lambda: layout["allocate"](66, 4))
    assert arrays["c35"].shape == (66, 3 * 4352) == (66, 102 * 128)
    assert arrays["s35"].shape == (66, 64, 64, 128)
    assert granite_h.READ_BUCKETS == (0, 8, 32)
    with pytest.raises(ValueError, match="routed experts"):
        granite_h.GraniteHConfig.from_published({**doc,
                                                 "num_local_experts": 8})
    with pytest.raises(ValueError, match="nope"):
        granite_h.GraniteHConfig.from_published(
            {**doc, "position_embedding_type": "rope"})
    with pytest.raises(ValueError, match="unknown layer type"):
        dataclasses.replace(CFG, layer_types=("mamba", "conv"))
    with pytest.raises(ValueError, match="mamba_n_groups"):
        dataclasses.replace(CFG, mamba_n_groups=2)


# -- the served path against the whole forward pass -------------------------

@pytest.mark.parametrize("cuts", [
    (N,),                          # all at once: chunks of 32 by the runtime
    (50, 51, 54, 61, N),           # a prefill, then turns
    (7, 8, 9, 15, 17, 40, N),      # across page borders
    (20, 43, N),                   # a turn over two tiles (16 events each)
    (1, 2, 3, 4, 5, N),
], ids=["at-once", "prefill-then-turns", "page-borders", "two-tiles",
        "from-nothing"])
def test_prefill_then_turns_is_one_forward_pass(fresh, history, want, cuts):
    at = 0
    for upto in cuts:
        answer = _ask(fresh, ("a", history[at:upto]))[0]
        at = upto
        np.testing.assert_allclose(_dense(answer), want[at - 1], atol=TOL)
    assert fresh.cache.length("a") == cuts[-1]
    assert len(fresh.cache._entries["a"].pages) == -(-cuts[-1] // PAGE)


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
    # ... and a later call with no event answers from the stored row.
    again = _ask(fresh, ("a", []))[0]
    np.testing.assert_allclose(_dense(again), _dense(a3), atol=1e-6)


def test_a_turn_split_over_two_programs(fresh, history, want):
    """Four one-event turns fill the read bucket and a long turn behind
    them is split: its first chunk is a fifth segment with no read, the
    rest runs in the next programs."""
    out = _ask(fresh, *[(f"u{i}", history[:1]) for i in range(4)],
               ("long", history[:60]))
    np.testing.assert_allclose(_dense(out[4]), want[59], atol=TOL)
    np.testing.assert_allclose(_dense(out[0]), want[0], atol=TOL)


def test_a_failed_program_rolls_every_slot_and_page_back(fresh, history,
                                                         want):
    _ask(fresh, ("a", history[:45]))
    cache = fresh.cache
    slot = cache.read_slot("a")
    before = {k: np.asarray(v) for k, v in cache.arrays.items()}
    pages = list(cache._entries["a"].pages)
    free = (sorted(cache._free_pages), sorted(cache._free_slots))
    with pytest.raises(RuntimeError, match="serve failed"):
        with cache.transaction():
            fresh.extend([seq_runtime.Turn("a", history[45:70], 5),
                          seq_runtime.Turn("b", history[:20], 5)])
            assert cache.length("a") == 70 and cache.read_slot("a") != slot
            raise RuntimeError("serve failed")
    assert cache.length("a") == 45 and cache.read_slot("a") == slot
    assert not cache.has("b")
    assert (sorted(cache._free_pages), sorted(cache._free_slots)) == free
    after = {k: np.asarray(v) for k, v in cache.arrays.items()}
    rows = np.concatenate([np.arange(p * PAGE, (p + 1) * PAGE)
                           for p in pages])[:45]
    for name in before:
        if name == "table":
            continue
        keep = rows if name.startswith("kv") else [slot]
        np.testing.assert_array_equal(after[name][keep], before[name][keep])
    # ... and the same turn again answers as if nothing had happened.
    answer = _ask(fresh, ("a", history[45:70]))[0]
    np.testing.assert_allclose(_dense(answer), want[69], atol=TOL)


def test_a_call_of_more_users_than_the_write_pool_commits_in_parts(
        params, history, want):
    """Six residents, a pool of four write slots (the published model's is
    32 of 77 MB): one call with a turn of each runs as two programs inside
    the pool, the first committed when the second is planned; nobody is
    evicted."""
    rt = _runtime(params)
    users = "abcdef"
    for u in users:
        _ask(rt, (u, history[:40]))
    cache = rt.cache
    evicted = _counters().get('pio_seq_state_total{result="evicted"}', 0.0)
    with cache.transaction():
        answers = rt.extend([
            seq_runtime.Turn(u, history[40:43 + i], CFG.vocab_size)
            for i, u in enumerate(users)])
        assert sorted(cache._staged) == ["e", "f"]
    for i, answer in enumerate(answers):
        np.testing.assert_allclose(_dense(answer), want[42 + i], atol=TOL)
    assert _counters().get('pio_seq_state_total{result="evicted"}', 0.0) \
        == evicted and all(cache.has(u) for u in users)
    assert len(cache._free_slots) == 4
    snap = _counters()
    assert snap['pio_seq_state_bytes{kind="fixed"}'] \
        == cache.n_slots * cache.slot_bytes
    assert snap['pio_seq_state_bytes{kind="paged"}'] \
        == cache.snapshot()["pagesUsed"] * cache.page_bytes


def test_eviction_then_a_refill_gives_the_same_answer(params, history, want):
    rt = _runtime(params, max_users=2, write_slots=2)
    _ask(rt, ("a", history[:50]))
    _ask(rt, ("b", history[:10]))
    _ask(rt, ("c", history[:10]))                  # evicts a
    assert not rt.cache.has("a")
    answer = _ask(rt, ("a", history[:60]))[0]
    np.testing.assert_allclose(_dense(answer), want[59], atol=TOL)


def test_a_state_stored_in_bfloat16_would_not_pass():
    """75 one-event dispatches of a user whose heads remember 10-500
    events, against the recurrence in float64: with the state STORED
    float32 the error is the tiles' bfloat16 inputs' (2^-9 of each
    increment, which does not grow with the events remembered); rounding
    the stored state to bfloat16 after every dispatch adds 2^-9 of the
    WHOLE state each time.  The bound lies between the two, and the cache
    holds the state in float32."""
    rng = np.random.default_rng(2)
    heads, hp, n, tq = 8, 8, 16, 8
    a = -jnp.asarray(np.geomspace(0.02, 1.0, heads), jnp.float32)
    x = rng.normal(size=(N, heads * hp)).astype(np.float32)
    dt = rng.uniform(0.05, 0.15, (N, heads)).astype(np.float32)
    b, c = (rng.normal(size=(N, n)).astype(np.float32) for _ in range(2))
    truth = np.zeros((heads, hp, n))
    for t in range(N):
        truth = np.exp(np.float64(dt[t]) * np.asarray(a, np.float64)
                       )[:, None, None] * truth \
            + (np.float64(dt[t])[:, None]
               * x[t].reshape(heads, hp))[:, :, None] * b[t]

    def chain(stored):
        state = jnp.zeros((4, heads, hp, n), jnp.float32)
        one = lambda v: jnp.asarray(v, jnp.int32)[None]  # noqa: E731
        for t in range(N):
            pad = lambda v: jnp.zeros((1, tq) + v.shape[1:],  # noqa: E731
                                      jnp.float32).at[0, 0].set(v[t])
            _, state = granite_h_kernels.ssd_update(
                pad(x), pad(dt), pad(b), pad(c), a, state, one(1),
                one(1), one(2 + t % 2), one(3 - t % 2), use_pallas=False)
            state = state.astype(stored).astype(jnp.float32)
        return np.asarray(state[3 - (N - 1) % 2], np.float64)

    def off(s):
        return np.sqrt(((s - truth) ** 2).mean() / (truth ** 2).mean())

    kept, rounded = off(chain(jnp.float32)), off(chain(jnp.bfloat16))
    assert kept < 2.5e-3 < 0.5 * rounded, (kept, rounded)
    layout = granite_h.state_layout(CFG, PAGE)
    assert layout["allocate"](3, 2)["s0"].dtype == jnp.float32


# -- what each published number does to the answers --------------------------

@pytest.mark.parametrize("name,value", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 8 ** -0.5), ("logits_scaling", 1.0)])
def test_each_multiplier_moves_the_answers(params, history, want, name,
                                           value):
    """The served path holds the reference to TOL; the reference with one
    multiplier at its usual default (1, or 1 / sqrt(head size)) is several
    TOL away at most positions, so a program that dropped it fails."""
    other = dataclasses.replace(CFG, **{name: value})
    off = np.abs(_forward(params, history, other) - want).max(axis=1)
    assert np.median(off[16:]) > 2 * TOL, (name, np.median(off[16:]))


@pytest.mark.parametrize("kw,moved", [
    ({"rotary": True}, "a rotary embedding the model does not have"),
    ({"state_resets": np.arange(N) % 5 == 0},
     "the Mamba-2 state lost at every turn"),
], ids=["rotary", "state-resets"])
def test_a_changed_mechanism_moves_the_answers(params, history, want, kw,
                                               moved):
    off = np.abs(_forward(params, history, **kw) - want).max(axis=1)
    assert np.median(off[16:]) > 2 * TOL, (moved, np.median(off[16:]))


def test_float8_weights_move_the_answers(params, history, want):
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        if a.dtype == jnp.bfloat16 else a, params)
    off = np.abs(_forward(low, history) - want).max(axis=1)
    assert np.median(off) > 2 * TOL


# -- the kernel against its XLA twin and the recurrence ----------------------

def _tiles(rng, hp, nt=6, tq=16, heads=8, n=16, slots=8):
    cnt = np.array([16, 3, 5, 16, 0, 0])
    real = np.arange(tq)[None, :] < cnt[:, None]
    x = jnp.asarray(rng.normal(size=(nt, tq, heads * hp)), jnp.float32)
    dt = jnp.asarray(np.where(real[..., None], rng.uniform(
        1e-3, 0.5, (nt, tq, heads)), 0.0), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(nt, tq, n)), jnp.float32)
            for _ in range(2))
    a = -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)
    state = jnp.asarray(rng.normal(size=(slots, heads, hp, n)),
                        jnp.float32).at[0].set(0.0)
    # Tiles 0-1: one user over two tiles (slot 2 -> 4); tile 2: a new user
    # (the zero slot -> 5); tile 3: slot 3 -> 6; tiles 4-5: padding, named
    # as the runtime names it (the last real tile's slots, no first).
    per_tile = [jnp.asarray(v, jnp.int32) for v in (
        [1, 0, 1, 1, 0, 0], cnt, [2, 2, 0, 3, 3, 3], [4, 4, 5, 6, 6, 6])]
    return (x, dt, b, c, a, state, *per_tile), real


@pytest.mark.parametrize("hp", [8, 32], ids=["64-lanes", "256-lanes"])
def test_ssd_kernel_matches_its_twin_and_the_recurrence(hp):
    """The rows flat, ``heads * P`` channels along the lanes (a head
    block's 4 x P of them no multiple of 128, and one), ``dt`` a number a
    head: the kernel (interpreted) against its twin, and both against
    the recurrence an event at a time."""
    args, real = _tiles(np.random.default_rng(0), hp)
    x, dt, b, c, a, state = (np.asarray(v, np.float64)
                             for v in args[:6])
    heads = dt.shape[-1]
    assert args[0].shape == dt.shape[:2] + (heads * hp,)
    x = x.reshape(x.shape[:2] + (heads, hp))
    y_x, s_x = granite_h_kernels.ssd_update(*args, use_pallas=False)
    y_p, s_p = granite_h_kernels.ssd_update(*args, use_pallas=True, hb=4)
    assert y_x.shape == y_p.shape == args[0].shape
    at = real.nonzero()
    np.testing.assert_allclose(np.asarray(y_p)[at], np.asarray(y_x)[at],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_p, s_x, rtol=1e-5, atol=1e-5)
    # Padding rows and padding tiles leave the state as it was: slots
    # nobody wrote are untouched, and the padding tiles' slot holds what
    # the last real tile left.
    np.testing.assert_array_equal(np.asarray(s_p)[[0, 1, 2, 3, 7]],
                                  np.asarray(args[5])[[0, 1, 2, 3, 7]])
    # The recurrence itself, an event at a time, in float64.
    first, cnt, rd, wr = (np.asarray(v) for v in args[6:])
    want_state = state.copy()
    s = None
    for i in range(len(cnt)):
        if first[i]:
            s = state[rd[i]].copy()
        for t in range(cnt[i]):
            s = np.exp(dt[i, t] * a)[:, None, None] * s \
                + (dt[i, t][:, None] * x[i, t])[:, :, None] * b[i, t]
            want_y = (s @ c[i, t]).reshape(-1)
            # bfloat16 inputs of the tile's products: 2^-8 of |y| ~ 30
            np.testing.assert_allclose(y_x[i, t], want_y, atol=0.25,
                                       rtol=0.02)
        want_state[wr[i]] = s
    np.testing.assert_allclose(s_x, want_state, atol=0.03, rtol=0.01)


def test_a_heads_number_is_spread_over_its_lanes_exactly():
    """``_spread``: a float32 number a (row, head), of any size, comes
    out on each of the head's lanes bit for bit (the 0/1 product in full
    float32 adds one term and zeros)."""
    rng = np.random.default_rng(4)
    v = jnp.asarray(rng.normal(size=(48, 8))
                    * np.exp(8 * rng.normal(size=(48, 8))), jnp.float32)
    for hp in (8, 64):
        np.testing.assert_array_equal(
            jax.jit(granite_h_kernels._spread, static_argnums=1)(v, hp),
            np.repeat(np.asarray(v), hp, axis=1))


# -- the convolution against the plain recurrence ----------------------------

def _plain_conv(w, rows):
    """``sum_j w[j] * rows[t - 3 + j]`` over a user's whole line of input
    rows from nothing, float32, the newest tap first (the program's order
    of the sum) -> [len(rows), C]."""
    k = len(w) - 1
    line = np.concatenate([np.zeros((k, rows.shape[1]), np.float32), rows])
    out = w[k] * line[k:]
    for j in range(1, k + 1):
        out = out + w[k - j] * line[k - j:len(line) - j]
    return out


@pytest.mark.parametrize("held", [0, 1, 2, 3])
@pytest.mark.parametrize("events", [1, 2, 3, 4, 16, 17])
def test_the_convolution_is_the_plain_one_bit_for_bit(events, held):
    """A segment of ``events`` events of a user whose stored rows hold
    ``held`` real events (the rest zeros: the user's line began there),
    then a second user's segment (another length, another past) and
    padding tokens, in one batch: every new row of the convolution and
    every new stored row are the plain sum's over the user's whole line,
    bit for bit in float32; the padding segment's slots keep the zero
    slot's rows and no other slot is touched."""
    rng = np.random.default_rng(100 * events + held)
    cw, k, t = CFG.conv_width, CFG.mamba_d_conv - 1, 32
    p = {"conv_w": jnp.asarray(rng.normal(size=(k + 1, cw)), jnp.float32),
         "conv_b": jnp.asarray(rng.normal(size=cw), jnp.float32)}
    w = np.asarray(p["conv_w"])
    lens, past = [events, 1 + (events + held) % 5], [held, (held + 2) % 4]
    lines = [rng.normal(size=(h + n, cw)).astype(np.float32)
             for h, n in zip(past, lens)]
    tails = rng.normal(size=(6, k * cw)).astype(np.float32)
    tails[0] = 0.0                                  # the zero slot
    for slot, line, h in zip((2, 3), lines, past):
        tails[slot] = np.concatenate(
            [np.zeros((k, cw), np.float32), line[:h]])[-k:].reshape(-1)
    x = rng.normal(size=(t, cw)).astype(np.float32)     # padding: anything
    x[:lens[0]], x[lens[0]:sum(lens)] = lines[0][held:], lines[1][past[1]:]
    pad = t - sum(lens)
    batch = {
        "tok_seg": np.concatenate([np.repeat([0, 1], lens), -np.ones(pad)]),
        "tok_idx": np.concatenate([np.arange(lens[0]), np.arange(lens[1]),
                                   np.zeros(pad)]),
        # two segments and a padding one: the zero slot -> the scrap slot
        "seg_read": [2, 3, 0], "seg_write": [4, 5, 1],
        "seg_last": [lens[0] - 1, sum(lens) - 1, 0],
        "seg_len": lens + [0]}
    batch = {name: jnp.asarray(v, jnp.int32) for name, v in batch.items()}
    # Op by op: inside one program XLA's CPU backend contracts a product
    # and a sum into one rounding, which the plain sum does not.
    got, new = (np.asarray(v) for v in granite_h._conv(
        CFG, p, jnp.asarray(x), batch, jnp.asarray(tails)))
    at = 0
    for slot, line, h, n in zip((4, 5), lines, past, lens):
        want = jax.nn.silu(jnp.asarray(_plain_conv(w, line)[h:])
                           + p["conv_b"])
        np.testing.assert_array_equal(got[at:at + n], np.asarray(want))
        np.testing.assert_array_equal(
            new[slot], np.concatenate([np.zeros((k, cw), np.float32),
                                       line])[-k:].reshape(-1))
        at += n
    np.testing.assert_array_equal(new[1], tails[0])
    np.testing.assert_array_equal(new[[0, 2, 3]], tails[[0, 2, 3]])
    assert np.isfinite(got).all()


def test_the_reference_in_blocks_is_the_recurrence():
    """``benchmark/reference_granite_h.ssd``: the masked product inside a
    block and the state carried between blocks against an event-by-event
    scan, with the state zeroed where a turn starts."""
    from benchmark import reference_granite_h

    rng = np.random.default_rng(1)
    s, heads, hp, n = 48, 4, 8, 16
    x = jnp.asarray(rng.normal(size=(s, heads, hp)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.5, (s, heads)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(s, n)), jnp.float32)
            for _ in range(2))
    a = -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)
    for starts in ([], [5, 16, 17, 40]):
        turn = np.zeros(s, np.int32)
        turn[starts] = 1
        turn = np.cumsum(turn).astype(np.int32)
        with jax.default_matmul_precision("highest"):
            got = reference_granite_h.ssd(x, dt, b, c, a,
                                          jnp.asarray(turn), block=8)
        state = np.zeros((heads, hp, n))
        for t in range(s):
            if t in starts:
                state[:] = 0
            state = np.exp(np.asarray(dt[t] * a, np.float64)
                           )[:, None, None] * state \
                + np.asarray(dt[t][:, None] * x[t], np.float64
                             )[:, :, None] * np.asarray(b[t], np.float64)
            np.testing.assert_allclose(got[t], state @ np.asarray(c[t]),
                                       atol=2e-4, rtol=2e-4)


def test_a_chunk_that_ends_no_turn_lowers_no_head(runtime):
    """The (t, 0) program's text holds the recurrence and the attention
    and no head; the (t, 4) program's holds all three scopes."""
    cache = runtime.cache
    if not cache.arrays:
        cache.reset()
    step = runtime.step
    texts = {}
    for r in (0, 4):
        vec = jax.ShapeDtypeStruct((sum(granite_h.vector_sizes(
            32, r, step.shapes(32, r, cache))),), jnp.int32)
        texts[r] = step.program(cache, 32, r, 16).lower(
            runtime.params, cache.arrays, vec).as_text(debug_info=True)
    assert "ssd_update" in texts[0] and "gqa_attention" in texts[0]
    assert "seq_head" not in texts[0] and "seq_head" in texts[4]


def test_counters_move_by_what_the_positions_say(fresh, history):
    before = _counters()
    _ask(fresh, ("a", history[:10]), ("b", history[:3]))
    _ask(fresh, ("a", history[10:12]), ("b", []))
    after = _counters()

    def grew(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    # Four Mamba-2 layers: (a, b) then a alone; one attention layer: each
    # new event attends its history up to itself, and a user's rows are
    # read once a dispatch.
    assert grew("pio_seq_recurrent_updates_total") == 4 * 3
    assert grew("pio_seq_attended_keys_total") \
        == sum(range(1, 11)) + sum(range(1, 4)) + 11 + 12
    assert grew("pio_seq_attention_rows_total") == 10 + 3 + 12
    assert grew("pio_seq_dispatches_total") == 2
    assert grew('pio_seq_tokens_total{kind="new"}') == 15


# -- the template: train -> deploy -> query_batch ----------------------------

GRANITE_H_VARIANT = {
    "engineFactory": "predictionio_tpu.templates.sequence:engine",
    "datasource": {"params": {"appName": "seqapp"}},
    "preparator": {"params": {"vocabSize": 64}},
    "algorithms": [{"name": "sequence", "params": {
        "backbone": "granite_h", "hiddenSize": 32, "intermediateSize": 64,
        "numAttentionHeads": 4, "numKeyValueHeads": 2, "headDim": 8,
        "layerTypes": ["mamba", "attention", "mamba"],
        "ssmConfig": {"mamba_d_state": 8},
        "embeddingMultiplier": 4.0, "attentionMultiplier": 0.125,
        "steps": 150, "batchSize": 16, "window": 12, "learningRate": 0.01,
        "seed": 5, "stateBudgetMB": 8.0, "maxUsers": 80}}],
}


def test_train_deploy_query_on_the_granite_h_backbone(ctx):  # noqa: F811
    from predictionio_tpu.server import EngineServer
    from predictionio_tpu.templates.sequence import engine
    from predictionio_tpu.workflow.core_workflow import run_train

    _seed_cycles(ctx)
    eng = engine()
    variant = EngineVariant.from_dict(GRANITE_H_VARIANT)
    run_train(eng, variant, ctx)
    srv = EngineServer(eng, variant, ctx.storage, host="127.0.0.1", port=0)
    srv.start()
    try:
        model = srv._models[0]
        assert isinstance(model.config, granite_h.GraniteHConfig)
        assert model.config.layer_types == ("mamba", "attention", "mamba")
        assert (model.config.mamba_n_heads, model.config.mamba_d_head,
                model.config.mamba_d_state) == (8, 8, 8)
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
        # A call of more users than one program touches (32): three
        # programs within the write pool of 32, nobody evicted.
        cache = model.state_cache
        out = srv.query_batch([{"user": f"b{i}", "num": 1,
                                "events": ["i2", "i3"]} for i in range(70)])
        assert {s["itemScores"][0]["item"] for s in out} == {"i4"}
        assert cache.has("visitor") and cache.write_slots == 32
        out = srv.query_batch([{"user": f"b{i}", "num": 1,
                                "events": ["i4"]} for i in range(70)])
        assert {s["itemScores"][0]["item"] for s in out} == {"i5"}
        assert cache.length("b69") == 3
    finally:
        srv.stop()
