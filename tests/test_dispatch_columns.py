"""A cohort's answers leave as columns (ISSUE 32): between the retrieval
call's two arrays and the JSON nothing is built per item.

Parity: the JSON of ``EngineServer.query_batch`` is, value for value and
byte for byte, what the object path gave (kept here as the reference:
``iter_hits`` -> ``ItemScore`` -> ``dataclasses.asdict``), for the three
templates that answer with item lists.  Counts: no ``ItemScore`` is
built on the way unless somebody reads one, and the server says which
happened.  CPU, tiny seeded models, no training.
"""

import dataclasses
import datetime as dt
import importlib
import json
import pickle

import numpy as np
import pytest

from predictionio_tpu import retrieval
from predictionio_tpu.controller import (
    Engine,
    EngineVariant,
    FirstServing,
    ItemScoreColumns,
    Serving,
)
from predictionio_tpu.controller.columns import dispatch_tally
from predictionio_tpu.data.event import BiMap
from predictionio_tpu.data.storage import EngineInstance, Model, get_storage
from predictionio_tpu.obs import get_registry, reset_observability
from predictionio_tpu.retrieval import hit_columns, iter_hits
from predictionio_tpu.server import EngineServer
from predictionio_tpu.server.engine_server import _by_query

N_ITEMS = 12          # under K_MENU's 100: a num over 10 pads its rows
ITEMS = BiMap({f"i{j}": j for j in range(N_ITEMS)})


@pytest.fixture(autouse=True)
def fresh_registry():
    reset_observability()
    yield
    reset_observability()


# -- tiny seeded models, one a template --------------------------------------

def _users(n):
    return BiMap({f"u{j}": j for j in range(n)})


def _als_model(n_users=6, n_items=N_ITEMS):
    import jax.numpy as jnp

    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.templates.recommendation.engine import (
        ALSModelWrapper,
    )

    rng = np.random.default_rng(32)
    return ALSModelWrapper(
        model=ALSModel(
            user_factors=jnp.asarray(rng.normal(size=(n_users, 4)),
                                     jnp.float32),
            item_factors=jnp.asarray(rng.normal(size=(n_items, 4)),
                                     jnp.float32),
            rank=4, implicit=False),
        user_index=_users(n_users),
        item_index=BiMap({f"i{j}": j for j in range(n_items)}))


def _twotower_model():
    from predictionio_tpu.templates.twotower.engine import (
        TwoTowerModelWrapper,
    )

    rng = np.random.default_rng(33)
    return TwoTowerModelWrapper(
        user_vecs=rng.normal(size=(6, 8)).astype(np.float32),
        item_vecs=rng.normal(size=(N_ITEMS, 8)).astype(np.float32),
        user_index=_users(6), item_index=ITEMS)


SEQ_PARAMS = {
    "hiddenSize": 32, "intermediateSize": 48, "moeIntermediateSize": 16,
    "numExperts": 4, "numExpertsPerTok": 2, "numAttentionHeads": 4,
    "numKeyValueHeads": 2,
    "layerTypes": ["conv", "full_attention", "conv"], "numDenseLayers": 1}


def _sequence_model():
    import jax

    from predictionio_tpu.models import lfm2
    from predictionio_tpu.templates.sequence.engine import SequenceModel

    cfg = lfm2.LFM2Config(
        vocab_size=N_ITEMS, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        num_attention_heads=4, num_key_value_heads=2,
        layer_types=("conv", "full_attention", "conv"),
        dense_ff=(True, False, False))
    return SequenceModel(
        config=cfg, params=lfm2.init_params(cfg, jax.random.PRNGKey(34)),
        item_index=ITEMS, state_budget_bytes=1 << 20, max_users=16)


# Per template: the algorithm's name and params, the model, the query a
# cohort member sends, what kind of member a user is there, and which
# rows of the retrieval block the answered members own.
def _als_rows(kinds):
    """The template scores trained users first, then folded-in ones."""
    return [i for i, k in enumerate(kinds) if k == "known"] \
        + [i for i, k in enumerate(kinds) if k == "folded"]


def _in_order(kinds):
    return [i for i, k in enumerate(kinds) if k != "cold"]


def _everyone(kinds):
    """The sequence engine gives a user with no event an empty row."""
    return list(range(len(kinds)))


def _plain_query(user, num, n):
    return {"user": user, "num": num}


def _turn_query(user, num, n):
    """A known user brings events; a cold one has none, ever."""
    events = None if user == "nobody" else \
        [f"i{(n + j) % N_ITEMS}" for j in range(3 + n)]
    return {"user": user, "num": num, "events": events}


TEMPLATES = {
    "recommendation": dict(
        algo="als", params={"rank": 4}, model=_als_model, rows=_als_rows,
        query=_plain_query,
        kind={"nobody": "cold", "visitor": "folded"}),
    "twotower": dict(
        algo="twotower", params={}, model=_twotower_model, rows=_in_order,
        query=_plain_query, kind={"nobody": "cold", "visitor": "cold"}),
    "sequence": dict(
        algo="sequence", params=SEQ_PARAMS, model=_sequence_model,
        rows=_everyone, query=_turn_query, kind={"nobody": "cold"}),
}

# (user, num): "nobody" is a cold start, "visitor" is folded in where
# the template folds (cold elsewhere).  With 12 items a num of 11 is
# answered from a block of 100 columns padded past the 12th (a padded
# row that is still a slice), a num of 20 gets the 12 there are (fewer
# hits than num: the walk).
COHORTS = {
    "mixed": [("u0", 3), ("nobody", 10), ("u1", 11), ("visitor", 4),
              ("u2", 20), ("u3", 10), ("u1", 1)],
    "in_order": [("u0", 10), ("u1", 10), ("u2", 10), ("u3", 10)],
    "short_rows": [("u4", 20), ("u5", 100)],
    "one": [("u2", 5)],
    "one_cold": [("nobody", 5)],
    "none_wanted": [("u0", 0), ("u1", 3)],
}


def _deploy(template, model, serving_class=FirstServing):
    mod = importlib.import_module(
        f"predictionio_tpu.templates.{template}.engine")
    spec = TEMPLATES[template]
    base = mod.engine()
    eng = Engine(datasource_class=base.datasource_class,
                 preparator_class=base.preparator_class,
                 algorithm_classes=base.algorithm_classes,
                 serving_class=serving_class, query_class=base.query_class)
    variant = EngineVariant.from_dict({
        "engineFactory": f"predictionio_tpu.templates.{template}:engine",
        "datasource": {"params": {"appName": "columns"}},
        "algorithms": [{"name": spec["algo"], "params": spec["params"]}]})
    storage = get_storage()
    now = dt.datetime.now(dt.timezone.utc)
    iid = storage.get_engine_instances().insert(EngineInstance(
        id=None, status="COMPLETED", start_time=now, end_time=now,
        engine_id=variant.engine_factory, engine_version="test",
        engine_variant=variant.variant_id,
        engine_factory=variant.engine_factory,
        datasource_params=json.dumps({"appName": "columns"}),
        algorithms_params=json.dumps(variant.raw["algorithms"])))
    storage.get_models().insert(Model(id=iid, models=pickle.dumps({
        "entries": [{"kind": "pickle", "class": type(model).__name__}],
        "payloads": [pickle.dumps(model)]})))
    srv = EngineServer(eng, variant, storage, host="127.0.0.1", port=0,
                       engine_version="test", instance_id=iid)
    return srv, mod


@pytest.fixture()
def deploy(pio_home):
    servers = []

    def make(template, model=None, serving_class=FirstServing):
        model = model or TEMPLATES[template]["model"]()
        srv, mod = _deploy(template, model, serving_class)
        servers.append(srv)
        return srv, mod

    yield make
    for srv in servers:
        srv.stop()


class _CountingArray(np.ndarray):
    """Counts ``tolist`` calls on the arrays ``hit_columns`` is given
    (views and slices of one keep the class)."""

    calls = 0

    def tolist(self):
        type(self).calls += 1
        return super().tolist()


@pytest.fixture()
def blocks(monkeypatch):
    """Every ``hit_columns`` call's ``(scores, ids, nums)``, with the
    arrays handed on as tolist-counting views."""
    seen = []
    real = retrieval.hit_columns
    _CountingArray.calls = 0

    def spy(scores, ids, nums):
        seen.append((np.array(scores), np.array(ids), list(nums)))
        return real(np.asarray(scores).view(_CountingArray),
                    np.asarray(ids).view(_CountingArray), nums)

    monkeypatch.setattr(retrieval, "hit_columns", spy)
    for name in ("recommendation", "twotower"):
        monkeypatch.setattr(importlib.import_module(
            f"predictionio_tpu.templates.{name}.engine"), "hit_columns", spy)
    return seen


def _object_path(mod, item_index, scores_row, ids_row, num):
    """The reference: what the parent built for one answered row."""
    inverse = item_index.inverse
    return dataclasses.asdict(mod.PredictedResult(itemScores=[
        mod.ItemScore(item=inverse[i], score=s)
        for i, s in iter_hits(scores_row, ids_row, num)]))


def _fold_in_visitor(srv, monkeypatch):
    vec = np.random.default_rng(35).normal(size=4).astype(np.float32)
    monkeypatch.setattr(
        srv._models[0], "fold_in_user",
        lambda user: vec if user == "visitor" else None)


# -- parity ------------------------------------------------------------------

@pytest.mark.parametrize("cohort", sorted(COHORTS))
@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_query_batch_json_is_the_object_paths(deploy, blocks, monkeypatch,
                                              template, cohort):
    srv, mod = deploy(template)
    spec = TEMPLATES[template]
    if template == "recommendation":
        _fold_in_visitor(srv, monkeypatch)
    members = COHORTS[cohort]
    kinds = [spec["kind"].get(user, "known") for user, _ in members]
    queries = [spec["query"](user, num, n)
               for n, (user, num) in enumerate(members)]
    got = srv.query_batch(queries)

    want = [{"itemScores": []} for _ in members]
    owners = spec["rows"](kinds)
    if owners:
        (scores, ids, nums), = blocks
        assert nums == [members[i][1] for i in owners]
        item_index = srv._models[0].item_index
        for row, i in enumerate(owners):
            want[i] = _object_path(mod, item_index, scores[row], ids[row],
                                   members[i][1])
    else:
        assert blocks == []
    assert got == want
    assert json.dumps(got, sort_keys=False) == \
        json.dumps(want, sort_keys=False)
    # The cohorts hold what they are meant to hold.
    for (user, num), kind, answer in zip(members, kinds, got):
        hits = answer["itemScores"]
        assert len(hits) == (0 if kind == "cold"
                             else max(min(num, N_ITEMS), 0))
        assert all(type(h["score"]) is float and type(h["item"]) is str
                   for h in hits)
        assert [h["score"] for h in hits] == sorted(
            (h["score"] for h in hits), reverse=True)


@pytest.mark.parametrize("seed", range(6))
def test_hit_columns_is_iter_hits_row_by_row(seed):
    rng = np.random.default_rng(seed)
    b, k = int(rng.integers(1, 9)), int(rng.choice([1, 10, 100]))
    scores = rng.normal(size=(b, k)).astype(np.float32)
    ids = rng.integers(0, 1000, size=(b, k)).astype(np.int32)
    # Padding as the rungs write it (a tail of -1 ids at -inf or the
    # float32 floor) and where no rung puts it (anywhere).
    for r in range(b):
        style = rng.integers(0, 4)
        if style == 1:
            cut = int(rng.integers(0, k + 1))
            ids[r, cut:], scores[r, cut:] = -1, -np.inf
        elif style == 2:
            hole = rng.random(k) < 0.3
            ids[r, hole] = -1
            scores[r, rng.random(k) < 0.2] = np.finfo(np.float32).min
        elif style == 3:
            scores[r, int(rng.integers(0, k))] = np.nan
    nums = [int(n) for n in rng.choice([0, 1, 3, 10, 11, 100, 250], size=b)]
    got = hit_columns(scores, ids, nums)
    assert len(got) == b
    for r in range(b):
        pairs = list(iter_hits(scores[r], ids[r], nums[r]))
        want_ids, want_scores = [p[0] for p in pairs], [p[1] for p in pairs]
        got_ids, got_scores = got[r]
        assert got_ids == want_ids
        assert all(type(i) is int for i in got_ids)
        assert all(type(s) is float for s in got_scores)
        # NaN is a score like any other to both (it is not padding).
        assert [repr(s) for s in got_scores] == \
            [repr(s) for s in want_scores]
    assert hit_columns(scores[:0], ids[:0], []) == []


def test_bimap_keys_of_is_the_inverse_in_one_pass():
    m = BiMap.string_int(["a", "b", "c"])
    assert m.keys_of([2, 0, 0]) == ["c", "a", "a"] == \
        [m.inverse[v] for v in (2, 0, 0)]
    assert m.keys_of([]) == []
    with pytest.raises(KeyError):
        m.keys_of([3])


# -- counts ------------------------------------------------------------------

def _items_counter():
    c = get_registry().get("pio_dispatch_items_total")
    return {form: c.value(form=form) for form in ("columns", "objects")}


@pytest.fixture()
def built(monkeypatch):
    """How many ``ItemScore`` of the recommendation template were
    constructed."""
    mod = importlib.import_module(
        "predictionio_tpu.templates.recommendation.engine")

    made = []
    init = mod.ItemScore.__init__

    def counting(self, *a, **kw):
        made.append(1)
        init(self, *a, **kw)

    monkeypatch.setattr(mod.ItemScore, "__init__", counting)
    return made


def test_a_cohort_of_256_builds_no_object_and_says_so(deploy, blocks, built):
    srv, _ = deploy("recommendation", _als_model(n_users=256, n_items=50))
    queries = [{"user": f"u{j}", "num": 10} for j in range(256)]
    before = _items_counter()
    out = srv.query_batch(queries)
    assert [len(r["itemScores"]) for r in out] == [10] * 256
    assert built == []
    assert len(blocks) == 1 and _CountingArray.calls <= 2
    after = _items_counter()
    assert after["columns"] - before["columns"] == 2560
    assert after["objects"] - before["objects"] == 0
    # Once a dispatch, not once an item or a query.
    srv.query_batch(queries[:3])
    assert _items_counter() == {"columns": after["columns"] + 30,
                                "objects": after["objects"]}


def test_a_serving_that_reads_objects_gets_them_and_is_counted(
        deploy, blocks, built):
    mod = importlib.import_module(
        "predictionio_tpu.templates.recommendation.engine")

    seen = []

    class ReadingServing(Serving):
        def serve(self, query, predictions):
            scored = predictions[0].itemScores
            seen.append({
                "len": len(scored), "first": scored[0].item,
                "head": scored[:2], "all": list(scored),
                "equal": scored == list(scored),
                "unequal": scored == list(scored)[1:],
                "reflected": list(scored) == scored,
                "joined": scored + [mod.ItemScore("x", 0.0)],
                "same": predictions[0] == mod.PredictedResult(
                    itemScores=list(scored)),
            })
            best = max(scored, key=lambda s: s.score)
            return mod.PredictedResult(itemScores=[
                mod.ItemScore(item=best.item, score=best.score)])

    srv, _ = deploy("recommendation", serving_class=ReadingServing)
    queries = [{"user": "u0", "num": 4}, {"user": "u1", "num": 3}]
    out = srv.query_batch(queries)
    # Seven objects the two rows' lists were built from, once; the two
    # "x" and the two hand-built answers are the serving's own.
    assert len(built) == 7 + 2 + 2
    assert _items_counter() == {"columns": 0, "objects": 7}
    (scores, ids, nums), = blocks
    item_index = srv._models[0].item_index
    for row, (got, answer) in enumerate(zip(seen, out)):
        want = [mod.ItemScore(**h) for h in _object_path(
            mod, item_index, scores[row], ids[row], nums[row])["itemScores"]]
        assert got["all"] == want and got["len"] == len(want)
        assert got["first"] == want[0].item
        assert got["head"] == want[:2] and type(got["head"]) is list
        assert got["equal"] is True and got["unequal"] is False
        assert got["reflected"] is True and got["same"] is True
        assert got["joined"] == want + [mod.ItemScore("x", 0.0)]
        assert answer == {"itemScores": [dataclasses.asdict(want[0])]}


def test_an_object_a_serving_changed_is_served_changed(deploy):
    class Flooring(FirstServing):
        def serve(self, query, predictions):
            predictions[0].itemScores[0].score = 0.5
            return predictions[0]

    srv, _ = deploy("twotower", serving_class=Flooring)
    (answer,) = srv.query_batch([{"user": "u0", "num": 3}])
    assert answer["itemScores"][0]["score"] == 0.5
    assert len(answer["itemScores"]) == 3
    assert _items_counter() == {"columns": 0, "objects": 3}


def test_item_score_columns_reads_as_the_list_it_stands_for():
    @dataclasses.dataclass
    class Hit:
        item: str
        score: float

    tally = dispatch_tally()
    tally.columns = tally.objects = 0
    cols = ItemScoreColumns(["a", "b", "c"], [3.0, 2.0, 1.0], Hit)
    assert len(cols) == 3 and bool(cols)
    assert not ItemScoreColumns([], [], Hit)
    rendered = cols.pio_json()
    assert rendered == [{"item": "a", "score": 3.0},
                        {"item": "b", "score": 2.0},
                        {"item": "c", "score": 1.0}]
    assert list(rendered[0]) == ["item", "score"]
    assert (tally.columns, tally.objects) == (3, 0)
    plain = [Hit("a", 3.0), Hit("b", 2.0), Hit("c", 1.0)]
    assert cols[1] == plain[1] and cols[-1] == plain[-1]
    assert (tally.columns, tally.objects) == (3, 3)
    assert cols[1] is cols[1]                    # built once, kept
    assert cols[1:] == plain[1:] and list(reversed(cols)) == plain[::-1]
    assert cols == plain and plain == cols and cols != plain[:2]
    assert cols == ItemScoreColumns(["a", "b", "c"], [3.0, 2.0, 1.0], Hit)
    assert Hit("b", 2.0) in cols and cols.index(Hit("c", 1.0)) == 2
    assert sorted(cols, key=lambda h: h.score)[0] == plain[2]
    assert (cols == "abc") is False
    with pytest.raises(TypeError):
        hash(cols)
    with pytest.raises(IndexError):
        cols[3]
    assert "a" in repr(cols)
    # Rendering after a read gives what the objects now say, and counts
    # nothing twice.
    cols[0].score = 9.0
    assert cols.pio_json()[0] == {"item": "a", "score": 9.0}
    assert (tally.columns, tally.objects) == (3, 6)   # the equal twin's 3


@pytest.mark.parametrize("answers, want", [
    # one algorithm, every index in order
    ([[(0, "a0"), (1, "a1"), (2, "a2")]], [["a0"], ["a1"], ["a2"]]),
    # a cold member moved to the front
    ([[(1, "a1"), (0, "a0"), (2, "a2")]], [["a0"], ["a1"], ["a2"]]),
    # two algorithms, one of each kind
    ([[(0, "a0"), (1, "a1")], [(1, "b1"), (0, "b0")]],
     [["a0", "b0"], ["a1", "b1"]]),
    # an iterator of pairs, as a generator-built batch_predict may give
    ([iter([(0, "a0"), (1, "a1")])], [["a0"], ["a1"]]),
    # no algorithm at all
    ([], [[], []]),
])
def test_by_query_regroups_any_batch_predict(answers, want):
    n = len(want)
    got = _by_query(answers, n)
    assert got == want and all(type(ps) is list for ps in got)


def test_by_query_names_the_index_nobody_answered():
    with pytest.raises(KeyError):
        _by_query([[(0, "a0"), (2, "a2")]], 3)
