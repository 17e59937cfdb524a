"""The similar-product template's rules before the top-k: served batches
against the benchmark's plain float64 reference (steps 1-5) item for item,
the batch against the batch of ones, baskets of every length, unknown ids
and zero rows, the Pallas side in the interpreter against the XLA side,
the combine's cases, and the parent's algorithm (kept here as a helper)
answering short where the template now answers in full."""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models.similarproduct import (
    BASKET_SLOTS,
    SimilarALSAlgorithm,
    SimilarALSParams,
    SimilarModel,
    SimilarProductServing,
)
from predictionio_tpu.models import staged_rules
from predictionio_tpu.obs import tracing
from predictionio_tpu.obs.registry import MetricRegistry
from predictionio_tpu.ops import similarity as S
from predictionio_tpu.utils.bimap import BiMap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks", "chip")]

import reference_simprod  # noqa: E402

N_ITEMS, N_CATEGORIES, RANK = 3000, 30, 8
#: float32 products against the reference's float64: a list's score may
#: differ by this share of the list's best score
SCORE_TOLERANCE = 2e-5
#: category 29 holds one item in 1,000 of the catalogue
RARE = 29


@pytest.fixture(scope="module")
def ctx():
    from predictionio_tpu.parallel.mesh import ComputeContext

    return ComputeContext.create(batch="simprod-rules-test")


@pytest.fixture(scope="module")
def shop(ctx):
    """A seeded tenant staged as `deploy` stages it (two algorithms over
    one vocabulary, 70% of the ``like`` rows zero), and the same arrays as
    the reference's `Shop`."""
    rng = np.random.default_rng(7)
    view = rng.normal(size=(N_ITEMS, RANK)).astype(np.float32)
    like = rng.normal(size=(N_ITEMS, RANK)).astype(np.float32)
    like[rng.random(N_ITEMS) >= 0.3] = 0.0
    like[5] = 0.0  # item 5 has no like vector; 6 and 7 have
    like[6:8] = rng.normal(size=(2, RANK))
    category = rng.integers(0, RARE, N_ITEMS).astype(np.int32)
    category[[100, 1100, 2100]] = RARE
    item_map = BiMap([f"i{i}" for i in range(N_ITEMS)])
    categories = {f"i{i}": [f"c{c}"] for i, c in enumerate(category)}
    algos, models = [], []
    for name, table in (("view", view), ("like", like)):
        algo = SimilarALSAlgorithm(SimilarALSParams(event_name=name, rank=RANK))
        algos.append(algo)
        models.append(algo.stage_model(ctx, SimilarModel(
            item_factors=table, item_map=item_map, item_categories=categories,
        )))
    return algos, models, reference_simprod.Shop(
        tables=[view, like], category=category
    )


def _basket(rng, n):
    return [f"i{int(r)}" for r in rng.choice(N_ITEMS, n, replace=False)]


def _mixed_queries():
    rng = np.random.default_rng(11)
    black = [f"i{i}" for i in range(0, 400, 7)] + ["no-such-item"]
    white = [f"i{i}" for i in range(100, 260)]
    return [
        {"items": ["i0"], "num": 10},
        {"items": _basket(rng, 16), "num": 10},
        {"items": _basket(rng, 17), "num": 10},                 # past RECENT_SLOTS
        {"items": _basket(rng, 50), "num": 10},
        {"items": ["i3", "i3", "i4"], "num": 10},               # each once
        {"items": ["i1", "zz", "i9999999"], "num": 10},          # unknown ids dropped
        {"items": ["zz", "i9999999"], "num": 10},                # none known: empty
        {"items": ["i5"], "num": 10},                            # zero like row
        {"items": ["i5", "i6"], "num": 10},                      # one zero, one not
        {"items": _basket(rng, 3), "num": 10, "categories": [f"c{RARE}"]},
        {"items": _basket(rng, 2), "num": 10, "categories": ["c1", "c3"]},
        {"items": _basket(rng, 2), "num": 10, "categories": ["c99"]},   # no such
        {"items": _basket(rng, 4), "num": 10, "blackList": black},
        {"items": white[:3], "num": 10, "whiteList": white},
        {"items": _basket(rng, 2), "num": 10, "whiteList": white,
         "blackList": white[:60], "categories": ["c2", "c4", "c5"]},
        {"items": _basket(rng, 5), "num": 3},
        {"items": _basket(rng, 5), "num": 1},
    ]


def _bound(registry, fn, *args):
    tracing.StageSink(registry).bind()
    try:
        return fn(*args)
    finally:
        tracing._bound_stages.set(None)


def _assert_lists_equal_reference(shop_ref, queries, served, a):
    """Algorithm ``a``'s served lists against the reference's L_a."""
    found = reference_simprod.reference_lists(shop_ref, queries, 10)
    for query, answer, (num, _ok, lists, _s) in zip(queries, served, found):
        rows, scores = lists[a]
        got = reference_simprod.parse_answer(answer, num, N_ITEMS)
        assert got is not None, (query, answer)
        assert got[0].tolist() == rows.tolist(), (a, query)
        scale = abs(scores[0]) if len(scores) else 1.0
        assert np.all(np.abs(got[1] - scores) <= SCORE_TOLERANCE * scale), query


def test_mixed_batch_equals_the_reference_item_for_item(shop):
    algos, models, shop_ref = shop
    queries = _mixed_queries()
    registry = MetricRegistry()
    served = [
        _bound(registry, algo.batch_predict, model, queries)
        for algo, model in zip(algos, models)
    ]
    for a in range(2):
        _assert_lists_equal_reference(shop_ref, queries, served[a], a)
    # steps 1-5: the combine of the two served lists is the reference's answer
    serving = SimilarProductServing()
    want = reference_simprod.reference_answers(shop_ref, queries, 10)
    for q, (query, (rows, scores)) in enumerate(zip(queries, want)):
        answer = _bound(registry, serving.serve, query, [served[0][q], served[1][q]])
        got = reference_simprod.parse_answer(answer, query["num"], N_ITEMS)
        assert got[0].tolist() == rows.tolist(), query
        np.testing.assert_allclose(got[1], scores, rtol=0, atol=2e-3)
    view, like = served
    assert [len(a["itemScores"]) for a in view[:4]] == [10] * 4
    assert view[6] == like[6] == {"itemScores": []}              # none known
    assert len(view[7]["itemScores"]) == 10 and like[7] == {"itemScores": []}
    assert len(like[8]["itemScores"]) == 10                      # i6 carries it
    # the rare category answers in full: its three items less any named
    assert {s["item"] for s in view[9]["itemScores"]} <= {"i100", "i1100", "i2100"}
    assert 1 <= len(view[9]["itemScores"]) <= len(want[9][0]) <= 3
    assert view[11] == {"itemScores": []}
    assert len(view[-2]["itemScores"]) == 3 and len(view[-1]["itemScores"]) == 1
    # whiteList, blackList and the query's own items are never served
    for answers in served:
        for query, answer in zip(queries, answers):
            got = {s["item"] for s in answer["itemScores"]}
            assert not got & set(query["items"]), query
            assert not got & set(query.get("blackList") or ()), query
            if query.get("whiteList"):
                assert got <= set(query["whiteList"]), query
    # the batch of one is the same step
    for query, answer in zip(queries, view):
        assert algos[0].predict(models[0], query) == answer
    # the counters, in the registry of the server whose thread ran the batch
    got = registry.to_dict()

    def value(family, **labels):
        return sum(
            s["value"] for s in got[family]["samples"]
            if all(s["labels"].get(k) == v for k, v in labels.items())
        )

    n = len(queries)
    assert value("pio_similar_queries_total", algorithm="view") == n
    assert value("pio_similar_queries_total", algorithm="like", result="empty") >= 2
    assert value("pio_similar_queries_total", algorithm="view", result="empty") == 2
    assert value("pio_similar_query_items_total", algorithm="view", known="no") == 4
    assert value("pio_similar_query_items_total", algorithm="view", known="zero_row") == 0
    assert value("pio_similar_query_items_total", algorithm="like", known="zero_row") >= 2
    known = sum(len(set(q["items"]) - {"zz", "i9999999"}) for q in queries)
    assert value("pio_similar_query_items_total", algorithm="view", known="yes") == known
    assert value("pio_similar_filtered_queries_total", rule="category") == 2 * 4
    assert value("pio_similar_filtered_queries_total", rule="whiteList") == 2 * 2
    assert value("pio_similar_filtered_queries_total", rule="blackList") == 2 * 2
    assert value("pio_similar_excluded_items_total", algorithm="view") > known
    assert value("pio_serving_combined_items_total") == sum(len(r) for r, _s in want)
    stages = {s["labels"]["stage"]: s["count"] for s in got["pio_stage_seconds"]["samples"]}
    assert stages["predict.prep"] == stages["predict.enqueue"] == 2
    assert stages["predict.device_get"] == stages["predict.materialize"] == 2


@pytest.mark.parametrize("length", [1, 16, 17, 50, BASKET_SLOTS + 1])
def test_a_basket_of_any_length_sums_over_every_item(shop, length):
    """No truncation at `RECENT_SLOTS` or at `BASKET_SLOTS`: the list is
    the reference's, which sums the cosine to each item of the basket."""
    algos, models, shop_ref = shop
    rng = np.random.default_rng(length)
    queries = [{"items": _basket(rng, length), "num": 10}]
    for a in range(2):
        served = algos[a].batch_predict(models[a], queries)
        _assert_lists_equal_reference(shop_ref, queries, served, a)


def _parent_predict(table, item_map, categories, query):
    """The template's algorithm before this change, as a helper: cosine to
    the MEAN of the query items' raw vectors, the best ``num + len(items)``
    rounded up to a power of two of all items, the filters on those."""
    num = int(query.get("num", 10))
    idx = [i for i in (item_map.get(it, -1) for it in query["items"]) if i >= 0]
    if not idx:
        return {"itemScores": []}
    k = min(1 << max(0, num + len(idx) - 1).bit_length(), len(item_map))
    unit = table / np.linalg.norm(table, axis=1, keepdims=True)
    mean = table[idx].mean(0)
    scores = unit @ (mean / np.linalg.norm(mean))
    wanted = set(query.get("categories") or [])
    out = []
    for row in np.argsort(-scores)[:k]:
        item = item_map.inverse(int(row))
        if item in query["items"] or (wanted and not wanted & set(categories[item])):
            continue
        out.append({"item": item, "score": float(scores[row])})
    return {"itemScores": out[:num]}


def test_a_rare_category_answers_in_full_where_the_parent_answered_short(shop):
    algos, models, shop_ref = shop
    query = {"items": ["i0", "i1"], "num": 3, "categories": [f"c{RARE}"]}
    served = algos[0].predict(models[0], query)
    assert [s["item"] for s in served["itemScores"]] == [
        f"i{r}" for r in reference_simprod.reference_lists(shop_ref, [query], 3)[0][2][0][0]
    ]
    positive = int((
        reference_simprod.all_scores(shop_ref, [query])[0, 0, [100, 1100, 2100]] > 0
    ).sum())
    assert len(served["itemScores"]) == positive >= 1
    categories = {f"i{i}": [f"c{c}"] for i, c in enumerate(shop_ref.category)}
    parent = _parent_predict(
        shop_ref.tables[0], models[0].item_map, categories, query
    )
    assert len(parent["itemScores"]) < positive  # 3 items of 3,000 in the best 8


def _step(shop, fused, item_slots=BASKET_SLOTS):
    """The rules step over one batch of the view model, on one side."""
    _algos, models, _ref = shop
    model, rng = models[0], np.random.default_rng(3)
    rules = S.QueryRules.blank(8, 1, item_slots=item_slots, mode=S.SIMILAR)
    slots, q_cats = rules.recent, rules.categories
    lists = []
    for b in range(6):  # rows 6 and 7 stay blank
        n = int(rng.integers(1, 40))
        own = rng.choice(N_ITEMS, n, replace=False).astype(np.int32)
        slots[b, :n] = own
        lists.append(own)
    # the program numbers categories as it meets them
    ids = model.rules.category_ids
    q_cats[1, 0], q_cats[2, 0] = ids["c3"], ids[f"c{RARE}"]
    lists += [staged_rules.NO_ROWS, staged_rules.NO_ROWS]
    rules = dataclasses.replace(rules, lists=S.pack_lists(lists))
    return jax.device_get(S._rules_top_k(
        None, model.item_factors, model.rules.catalog, rules,
        num=16, fused=fused, interpret=True,
    ))


def test_the_pallas_side_in_the_interpreter_equals_the_xla_side(shop):
    xla_s, xla_i = _step(shop, fused=False)
    pal_s, pal_i = _step(shop, fused=True)
    filled = np.isfinite(xla_s)
    assert np.array_equal(np.isfinite(pal_s), filled)
    assert filled[:2].all() and not filled[6:].any() and filled[2].sum() <= 3
    np.testing.assert_array_equal(pal_i[filled], xla_i[filled])
    np.testing.assert_allclose(pal_s[filled], xla_s[filled], rtol=1e-5, atol=1e-6)
    # the staged table is whole blocks, its phantom rows never served
    rows = shop[1][0].item_factors.shape[0]
    assert rows % S.CATALOG_ROW_MULTIPLE == 0 and rows > N_ITEMS
    assert xla_i[filled].max() < N_ITEMS


def _scores(*pairs):
    return {"itemScores": [{"item": i, "score": s} for i, s in pairs]}


@pytest.mark.parametrize("num,lists,want", [
    # two full lists, no item shared: each list's z, the larger first
    (3, [[("a", 3.0), ("b", 2.0), ("c", 1.0)], [("d", 30.0), ("e", 20.0), ("f", 10.0)]],
     [("a", 1.0), ("d", 1.0), ("b", 0.0)]),
    # one list empty: it adds nothing
    (2, [[("a", 3.0), ("b", 1.0)], []], [("a", 2 ** -0.5), ("b", -(2 ** -0.5))]),
    # a list of one item: z = 0
    (2, [[("a", 3.0), ("b", 1.0)], [("c", 9.0)]],
     [("a", 2 ** -0.5), ("c", 0.0)]),
    # deviation 0: every z of that list is 0
    (3, [[("a", 2.0), ("b", 2.0)], [("c", 5.0), ("d", 1.0)]],
     [("c", 2 ** -0.5), ("a", 0.0), ("b", 0.0)]),
    # num 1: the lists as they are, raw scores
    (1, [[("a", 0.4)], [("b", 0.7)]], [("b", 0.7)]),
    # an item in both lists: its z summed
    (2, [[("a", 3.0), ("b", 1.0)], [("b", 8.0), ("c", 2.0)]],
     [("a", 2 ** -0.5), ("b", 0.0)]),
    # ties: in the order the lists gave
    (4, [[("a", 1.0), ("b", 1.0)], [("c", 4.0), ("d", 4.0)]],
     [("a", 0.0), ("b", 0.0), ("c", 0.0), ("d", 0.0)]),
], ids=["two_full", "one_empty", "one_item", "deviation_0", "num_1", "in_both", "ties"])
def test_the_combine(num, lists, want):
    serving = SimilarProductServing()
    registry = MetricRegistry()
    predictions = [_scores(*pairs) for pairs in lists]
    answer = _bound(registry, serving.serve, {"items": ["q"], "num": num}, predictions)
    got = [(s["item"], s["score"]) for s in answer["itemScores"]]
    assert [i for i, _z in got] == [i for i, _z in want]
    np.testing.assert_allclose([z for _i, z in got], [z for _i, z in want], atol=1e-12)
    # the reference's statement of step 5 says the same
    rows = {name: k for k, name in enumerate("abcdef")}
    ref_rows, ref_z = reference_simprod.combine(
        [
            (np.array([rows[i] for i, _s in pairs], np.int64),
             np.array([s for _i, s in pairs], np.float64))
            for pairs in lists
        ],
        num,
    )
    assert [rows[i] for i, _z in got] == ref_rows.tolist()
    np.testing.assert_allclose([z for _i, z in got], ref_z, atol=1e-12)
    held = registry.to_dict()["pio_serving_combined_items_total"]["samples"]
    assert sum(s["value"] for s in held) == len(got)
    both = sum(s["value"] for s in held if s["labels"]["lists"] == "2")
    assert both == sum(all(i in dict(pairs) for pairs in lists) for i, _z in got)


def test_an_unstaged_model_serves_the_same_lists(shop):
    """Evaluation calls `batch_predict` on the model as trained: the rules
    are staged on first use, on the unpadded table."""
    algos, models, shop_ref = shop
    model = SimilarModel(
        item_factors=shop_ref.tables[0], item_map=models[0].item_map,
        item_categories=models[0].item_categories,
    )
    queries = _mixed_queries()[:6]
    assert algos[0].batch_predict(model, queries) == algos[0].batch_predict(
        models[0], queries
    )
    assert model.rules is not None
    assert isinstance(model.rules.inv_norm, jax.Array)
    assert jnp.asarray(model.rules.unavailable).sum() == 0
