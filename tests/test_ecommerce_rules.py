"""The e-commerce template's rules before the top-k: the masked top-k
against a dense numpy mask (XLA side and Pallas side in the interpreter),
the served answers of mixed batches against the benchmark's plain
reference item for item, the launch's two host operands against the
straightforward query-by-query construction byte for byte, and a write
seen by the next query through `EngineServer`."""

from __future__ import annotations

import collections
import dataclasses
import datetime as _dt
import json
import os
import sys
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.core import EngineParams
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.models.ecommerce import (
    ECommAlgorithm,
    ECommAlgorithmParams,
    ECommDataSourceParams,
    ECommModel,
    ecommerce_engine,
)
from predictionio_tpu.obs import tracing
from predictionio_tpu.obs.registry import MetricRegistry
from predictionio_tpu.ops import similarity as S
from predictionio_tpu.utils.bimap import BiMap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks", "chip")]

import reference_ecomm  # noqa: E402

#: float32 products against the reference's float64: a served score may
#: differ by this share of the query's best score
SCORE_TOLERANCE = 2e-5


# -- the masked top-k against a dense mask ------------------------------------


def _dense_case(seed, n_users=50, n_items=3072, rank=16, batch=8, cats_per_item=1,
                slots=1, n_categories=7, long_list=900):
    rng = np.random.default_rng(seed)
    users = rng.normal(size=(n_users, rank)).astype(np.float32)
    items = rng.normal(size=(n_items, rank)).astype(np.float32)
    cats = rng.integers(-1, n_categories, size=(cats_per_item, n_items)).astype(np.int32)
    unavailable = rng.random(n_items) < 0.05
    norm = np.linalg.norm(items, axis=1)
    inv = np.where(norm > 0, 1 / norm, 0).astype(np.float32)
    popularity = rng.permutation(n_items).astype(np.float32)
    catalog = S.CatalogRules(
        jnp.asarray(cats), jnp.asarray(unavailable), jnp.asarray(inv),
        jnp.asarray(popularity),
    )
    idx = rng.integers(0, n_users, batch).astype(np.int32)
    mode = rng.integers(0, 3, batch).astype(np.int32)
    recent = np.full((batch, S.RECENT_SLOTS), -1, np.int32)
    q_cats = np.full((batch, slots), S.NO_CATEGORY, np.int32)
    lists, allow = [], np.zeros(batch, bool)
    for b in range(batch):
        n = rng.integers(1, 11)
        recent[b, :n] = rng.choice(n_items, n, replace=False)
        if rng.random() < 0.5:
            n = rng.integers(1, slots + 1)
            q_cats[b, :n] = rng.integers(0, n_categories, n)
        length = int(rng.choice([0, 5, 100, long_list]))
        lists.append(rng.choice(n_items, length, replace=False).astype(np.int32))
        allow[b] = length > 0 and rng.random() < 0.3
    rules = S.QueryRules.blank(batch, slots)
    rules.idx[:], rules.mode[:], rules.allow[:] = idx, mode, allow
    rules.recent[:], rules.categories[:] = recent, q_cats
    rules = dataclasses.replace(rules, lists=S.pack_lists(lists))
    # the same, densely
    q = users[idx].astype(np.float64)
    for b in range(batch):
        if mode[b] == S.SIMILAR:
            rows = recent[b][recent[b] >= 0]
            q[b] = (items[rows].astype(np.float64) * inv[rows][:, None]).sum(0)
    scores = q @ items.T.astype(np.float64)
    scores = np.where(mode[:, None] == S.SIMILAR, scores * inv[None, :], scores)
    scores = np.where(mode[:, None] == S.POPULAR, popularity[None, :], scores)
    hit = np.zeros((batch, n_items), bool)
    for b in range(batch):
        hit[b, lists[b]] = True
    excluded = hit != allow[:, None]
    in_category = np.zeros((batch, n_items), bool)
    for c in range(cats_per_item):
        for k in range(slots):
            in_category |= cats[c][None, :] == q_cats[:, k][:, None]
    excluded |= unavailable[None, :]
    excluded |= (q_cats[:, :1] != S.NO_CATEGORY) & ~in_category
    excluded |= (mode[:, None] != S.POPULAR) & ~(scores > 0)
    return users, items, catalog, rules, np.where(excluded, -np.inf, scores)


@pytest.mark.parametrize("fused", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("shape", [
    {}, {"cats_per_item": 2, "slots": 2}, {"batch": 1},
    {"n_items": 20480, "batch": 4, "long_list": 18000},
], ids=["plain", "two_categories", "one_row", "past_one_capacity"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_masked_top_k_equals_a_dense_numpy_mask(seed, shape, fused):
    users, items, catalog, rules, dense = _dense_case(seed, **shape)
    num = 16
    got_s, got_i = S._rules_top_k(
        jnp.asarray(users), jnp.asarray(items), catalog, rules,
        num=num, fused=fused, interpret=True,
    )
    got_s, got_i = np.asarray(got_s), np.asarray(got_i)
    order = np.argsort(-dense, axis=1, kind="stable")[:, :num]
    want = np.take_along_axis(dense, order, axis=1)
    filled = np.isfinite(want)
    assert np.array_equal(np.isfinite(got_s), filled)
    # the same items up to float32 ties: each served item's dense score is
    # the wanted score of its slot
    served = np.take_along_axis(dense, got_i, axis=1)
    np.testing.assert_allclose(served[filled], want[filled], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_s[filled], want[filled], rtol=2e-4, atol=2e-4)
    if shape.get("long_list", 0) > S.LIST_CAPACITY:
        assert len(rules.list_cols) == S.LIST_CAPACITY * S.LIST_CAPACITY_STEP


def test_list_capacity_and_the_rule_that_picks_the_side(monkeypatch):
    assert S.list_capacity(0) == S.list_capacity(16384) == 16384
    assert S.list_capacity(16385) == 65536 == S.FUSED_LIST_CAPACITY
    rows, cols = S.pack_lists([np.array([7, 3], np.int32), np.array([5], np.int32)])
    assert cols[:4].tolist() == [3, 5, 7, S.NO_ITEM] and rows[:3].tolist() == [0, 1, 0]
    # off the TPU never the kernel; on it, every step that masks lists
    assert not S._use_pallas(64, 4162560, listed=True)
    monkeypatch.setattr(S.jax, "default_backend", lambda: "tpu")
    assert S._use_pallas(1, 1024, listed=True)
    assert not S._use_pallas(1, 1024)
    assert S._use_pallas(64, 4162560)


# -- the served answers against the plain reference ----------------------------


N_USERS, N_ITEMS, N_CATEGORIES, RANK = 40, 21000, 40, 8


@pytest.fixture(scope="module")
def ctx():
    from predictionio_tpu.parallel.mesh import ComputeContext

    return ComputeContext.create(batch="ecomm-rules-test")


@pytest.fixture()
def shop(ctx, memory_storage):
    """A seeded tenant staged as `deploy` stages it, its events in the
    store, and the same arrays as the reference's `Shop`."""
    rng = np.random.default_rng(7)
    users = (0.5 * rng.normal(size=(N_USERS, RANK))).astype(np.float32)
    items = rng.normal(size=(N_ITEMS, RANK)).astype(np.float32)
    items[11] = 0.0  # a zero row has no direction
    category = rng.integers(0, N_CATEGORIES, N_ITEMS).astype(np.int32)
    popularity = rng.permutation(N_ITEMS).astype(np.float32)
    app_id = memory_storage.get_meta_data_apps().insert(App(id=0, name="shop"))
    events = memory_storage.get_events()
    events.init(app_id)
    t0 = _dt.datetime(2024, 1, 1, tzinfo=_dt.timezone.utc)

    def seen_event(user, item, name="view", at=0):
        return Event(
            event=name, entity_type="user", entity_id=user,
            target_entity_type="item", target_entity_id=f"i{item}",
            event_time=t0 + _dt.timedelta(seconds=at),
        )

    seen = {}
    for u in range(N_USERS):
        # u3 has seen more than one capacity of the packed lists holds
        n = 17000 if u == 3 else int(rng.integers(1, 40))
        seen[f"u{u}"] = np.sort(rng.choice(N_ITEMS, n, replace=False))
        events.insert_batch(
            [seen_event(f"u{u}", i, "buy" if k % 9 == 0 else "view")
             for k, i in enumerate(seen[f"u{u}"])], app_id,
        )
    views = {}
    for j in range(4):  # unknown users with 1, 4, 10 and 13 views
        rows = rng.choice(N_ITEMS, (1, 4, 10, 13)[j], replace=False)
        events.insert_batch(
            [seen_event(f"x{j}", i, at=k) for k, i in enumerate(rows)], app_id
        )
        views[f"x{j}"] = rows[::-1]  # newest first
        seen[f"x{j}"] = rows
    # a cart event is no seen event; an older $set is not the latest
    events.insert(seen_event("u0", 5, "cart"), app_id)
    unavailable = np.sort(rng.choice(N_ITEMS, 300, replace=False))
    for at, rows in ((0, unavailable[:10]), (1, unavailable)):
        events.insert(Event(
            event="$set", entity_type="constraint", entity_id="unavailableItems",
            properties=DataMap({"items": [f"i{i}" for i in rows]}),
            event_time=t0 + _dt.timedelta(seconds=at),
        ), app_id)
    algo = ECommAlgorithm(ECommAlgorithmParams(app_name="shop", rank=RANK))
    model = algo.stage_model(ctx, ECommModel(
        user_factors=users, item_factors=items,
        user_map=BiMap([f"u{i}" for i in range(N_USERS)]),
        item_map=BiMap([f"i{i}" for i in range(N_ITEMS)]),
        item_categories={f"i{i}": [f"c{c}"] for i, c in enumerate(category)},
        popularity=popularity,
    ))
    flags = np.zeros(N_ITEMS, bool)
    flags[unavailable] = True
    # the program numbers categories as it meets them; the reference by name
    return algo, model, reference_ecomm.Shop(
        users=users, items=items, category=category,
        popularity=popularity.astype(np.float64), unavailable=flags,
        seen=seen, views=views,
    ), (memory_storage, app_id)


def _assert_equals_reference(shop_ref, queries, answers, num=10):
    want = reference_ecomm.reference_answers(shop_ref, queries, num)
    for query, answer, (rows, scores) in zip(queries, answers, want):
        got = reference_ecomm.parse_answer(answer, num, N_ITEMS)
        assert got is not None, (query, answer)
        assert got[0].tolist() == rows.tolist(), query
        scale = abs(scores[0]) if len(scores) else 1.0
        assert np.all(np.abs(got[1] - scores) <= SCORE_TOLERANCE * scale), query


def _mixed_queries():
    black = [f"i{i}" for i in range(0, 400, 7)] + ["no-such-item"]
    white = [f"i{i}" for i in range(100, 160)]
    return [
        {"user": "u0", "num": 10},
        {"user": "u1", "num": 10, "categories": ["c2"]},
        {"user": "u2", "num": 10, "blackList": black},
        {"user": "u3", "num": 10},                          # 17,000 seen items
        {"user": "u4", "num": 10, "whiteList": white},
        {"user": "u5", "num": 10, "whiteList": white, "blackList": white[:30],
         "categories": ["c1", "c3"]},
        {"user": "u6", "num": 10, "categories": ["c99"]},   # no such category
        {"user": "u7", "num": 10, "whiteList": ["i11"]},    # scores 0: not > 0
        {"user": "x0", "num": 10},                          # similar, 1 view
        {"user": "x2", "num": 10, "categories": ["c0"]},    # similar, 10 views
        {"user": "x3", "num": 10},                          # 13 views: latest 10
        {"user": "nobody", "num": 10},                      # popular
        {"user": "nobody", "num": 10, "categories": ["c4"], "blackList": black},
        {"user": "u8", "num": 3},
    ]


def test_mixed_batch_equals_the_reference_item_for_item(shop):
    algo, model, shop_ref, _ = shop
    queries = _mixed_queries()
    registry = MetricRegistry()
    tracing.StageSink(registry).bind()
    try:
        answers = algo.batch_predict(model, queries)
    finally:
        tracing._bound_stages.set(None)
    _assert_equals_reference(shop_ref, queries, answers)
    assert [len(a["itemScores"]) for a in answers[6:8]] == [0, 0]
    assert len(answers[-1]["itemScores"]) == 3
    # the batch of one is the same step
    for query, answer in zip(queries, answers):
        assert algo.predict(model, query) == answer
    # the counters, in the registry of the server whose thread ran the batch
    got = registry.to_dict()
    by = lambda family, label: {  # noqa: E731
        s["labels"][label]: s["value"] for s in got[family]["samples"]
    }
    assert by("pio_ecomm_queries_total", "branch") == {"known": 9, "similar": 3, "popular": 2}
    assert by("pio_ecomm_filtered_queries_total", "rule") == {
        "categories": 5, "whiteList": 3, "blackList": 3,
    }
    short = sum(len(a["itemScores"]) < q["num"] for q, a in zip(queries, answers))
    assert got["pio_ecomm_short_answers_total"]["samples"][0]["value"] == short >= 2
    assert got["pio_ecomm_excluded_items_total"]["samples"][0]["value"] > 17000
    stages = {s["labels"]["stage"]: s["count"] for s in got["pio_stage_seconds"]["samples"]}
    assert stages["predict.rules"] == stages["predict.prep"] == 1


# -- the launch's operands against the query-by-query construction -------------


def _per_query_operands(algo, model, queries):
    """``(per_query, lists, counts)`` built the straightforward way, one
    query at a time over the store's events (the form the template's prep
    had before it went by arrays), the packed lists by a plain sort of
    (item row, query row) pairs."""
    reader = algo._reader(model)
    item_row, counts = model.item_map.get, collections.Counter()
    wanted = [q.get("categories") or () for q in queries]
    batch = 1 << max(0, len(queries) - 1).bit_length()
    slots = 1 << max(0, max(1, max(map(len, wanted))) - 1).bit_length()
    recent_at, cats_at = 3, 3 + S.RECENT_SLOTS
    per_query = np.zeros((batch, cats_at + slots), np.int32)
    per_query[:, 1] = S.POPULAR
    per_query[:, recent_at:cats_at] = -1
    per_query[:, cats_at:] = S.NO_CATEGORY
    lists = []
    for i, q in enumerate(queries):
        user = str(q.get("user", ""))
        row = model.user_map.get(user, -1)
        if row >= 0:
            per_query[i, 0], per_query[i, 1] = row, S.KNOWN
            counts["known"] += 1
        else:
            views = [
                item_row(e.target_entity_id, -1)
                for e in reader.find("user", user, event_names=("view",), limit=10)
            ]
            views = [r for r in views if r >= 0]
            if views:
                per_query[i, 1] = S.SIMILAR
                per_query[i, recent_at:recent_at + len(views)] = views
            counts["similar" if views else "popular"] += 1
        seen = [
            item_row(e.target_entity_id, -1)
            for e in reader.find("user", user, event_names=("view", "buy"))
        ]
        seen = [r for r in seen if r >= 0]
        black = [item_row(str(x), -1) for x in q.get("blackList") or ()]
        white = q.get("whiteList") or ()
        counts["blackList"] += bool(black)
        counts["whiteList"] += bool(white)
        if white:
            per_query[i, 2] = 1
            rows = [
                r for r in {item_row(str(x), -1) for x in white}
                if r >= 0 and r not in black and r not in seen
            ]
        else:
            rows = seen + [r for r in black if r >= 0]
        lists.append(rows)
        if wanted[i]:
            counts["categories"] += 1
            per_query[i, cats_at:cats_at + len(wanted[i])] = [
                model.rules.category_ids.get(str(c), S.NO_CATEGORY - 1)
                for c in wanted[i]
            ]
    pairs = sorted((col, row) for row, x in enumerate(lists) for col in x)
    packed = np.zeros((2, S.list_capacity(len(pairs))), np.int32)
    packed[1] = S.NO_ITEM
    if pairs:
        packed[1, :len(pairs)], packed[0, :len(pairs)] = zip(*pairs)
    counts["excluded"] = len(pairs)
    counts["lookups"] = 1 + len(queries)
    return per_query, packed, counts


_BLACK = [f"i{i}" for i in range(0, 400, 7)]
_WHITE = [f"i{i}" for i in range(100, 160)]
_OPERAND_CASES = {
    "known_users": [{"user": "u0", "num": 10}, {"user": "u8", "num": 3}],
    "unknown_users_with_views": [{"user": "x0"}, {"user": "x2", "num": 4}, {"user": "x3"}],
    "unknown_user_without_views": [{"user": "nobody", "num": 10}],
    "categories_known_and_unknown": [
        {"user": "u1", "categories": ["c2"]}, {"user": "u6", "categories": ["c99"]},
        {"user": "x2", "categories": ["c1", "c99", "c3"]},
    ],
    "blacklist_with_unknown_ids": [
        {"user": "u2", "blackList": _BLACK + ["no-such-item"]},
        {"user": "nobody", "blackList": ["no-such-item", "neither"]},
    ],
    "whitelist_overlapping_the_seen_and_the_blacklist": [
        {"user": "u4", "whiteList": _WHITE},
        {"user": "u5", "whiteList": _WHITE + ["no-such-item"], "blackList": _WHITE[:30]},
        {"user": "u3", "whiteList": [f"i{i}" for i in range(0, 21000, 13)]},
    ],
    "a_repeated_item": [
        {"user": "u7", "blackList": ["i5", "i5", "i6"]},
        {"user": "u7", "whiteList": ["i7", "i7"]},
        {"user": "x1", "blackList": ["i5"]},
    ],
    "an_empty_post_slot": [{}, {"user": "u0"}, {}],
    "one_user_past_list_capacity": [{"user": "u3", "num": 10}, {"user": "u0"}],
}


@pytest.mark.parametrize("case", sorted(_OPERAND_CASES) + ["mixed_batch"])
def test_launch_operands_equal_the_per_query_construction(shop, monkeypatch, case):
    """What reaches the device is byte for byte what the query-by-query
    construction gives, and the template's counters move by the
    per-query counts, once a batch."""
    algo, model, shop_ref, _ = shop
    queries = _OPERAND_CASES.get(case) or _mixed_queries() + sum(_OPERAND_CASES.values(), [])
    if case == "a_repeated_item":
        # an item the user has seen, on the blackList too, and twice
        seen = [f"i{i}" for i in shop_ref.seen["u7"][:2]]
        queries = queries + [{"user": "u7", "blackList": seen + seen}]
    sent = []
    step = S.rules_top_k
    monkeypatch.setattr(
        S, "rules_top_k", lambda *a: sent.append(a[4]) or step(*a)
    )
    registry = MetricRegistry()
    tracing.StageSink(registry).bind()
    try:
        algo.batch_predict_collect(
            model, algo.batch_predict_launch(model, queries), queries
        )
        want_per_query, want_lists, want = _per_query_operands(algo, model, queries)
    finally:
        tracing._bound_stages.set(None)
    (operands,) = sent
    for got, expected in ((operands.per_query, want_per_query), (operands.lists, want_lists)):
        assert isinstance(got, np.ndarray) and got.dtype == np.int32
        assert got.shape == expected.shape
        assert got.tobytes() == expected.tobytes()
    if case in ("one_user_past_list_capacity", "mixed_batch"):
        assert operands.lists.shape[1] > S.LIST_CAPACITY
    got = registry.to_dict()
    value = lambda family, **labels: sum(  # noqa: E731
        s["value"] for s in got[family]["samples"] if s["labels"] == labels
    )
    for branch in ("known", "similar", "popular"):
        assert value("pio_ecomm_queries_total", branch=branch) == want[branch], branch
    for rule in ("categories", "whiteList", "blackList"):
        assert value("pio_ecomm_filtered_queries_total", rule=rule) == want[rule], rule
    assert value("pio_ecomm_excluded_items_total") == want["excluded"]
    assert value("pio_ecomm_rule_lookups_total") == want["lookups"]
    seen_lookups = {
        r: value("pio_ecomm_seen_lookups_total", result=r) for r in ("hit", "miss")
    }
    assert sum(seen_lookups.values()) == len(queries)
    assert seen_lookups["miss"] == len({str(q.get("user", "")) for q in queries})


def test_one_category_query_is_answered_in_full(shop):
    """The parent filtered the global top 64 on the host: a one-category
    query found a few of its items there, or none."""
    algo, model, shop_ref, _ = shop
    queries = [{"user": f"u{u}", "num": 10, "categories": [f"c{u % N_CATEGORIES}"]}
               for u in range(10, 30)]
    answers = algo.batch_predict(model, queries)
    assert all(len(a["itemScores"]) == 10 for a in answers)
    _assert_equals_reference(shop_ref, queries, answers)
    short = reference_ecomm.filter_after_top64(shop_ref, queries, 10)
    assert sum(len(rows) < 10 for rows, _ in short) > 10


def test_a_pool_s_int8_tables_serve_the_same_rules(shop):
    from predictionio_tpu.ops import quantize

    algo, model, shop_ref, _ = shop
    model = quantize.quantize_model_factors(model, "int8")
    assert isinstance(model.item_factors, quantize.QuantizedFactors)
    queries = _mixed_queries()
    answers = algo.batch_predict(model, queries)
    comparison = reference_ecomm.Comparison(10)
    comparison.add(shop_ref, queries, [
        reference_ecomm.parse_answer(a, 10, N_ITEMS) for a in answers
    ])
    numbers = comparison.numbers()
    assert numbers["rule_violations"] == numbers["bad_answers"] == 0
    assert numbers["short_answers"] == 0 and numbers["score_rms"] < 0.02


def _index_builds(registry, origin):
    found = registry.to_dict().get("pio_idmap_index_builds_total")
    return sum(
        s["value"] for s in (found or {"samples": []})["samples"]
        if s["labels"]["origin"] == origin
    )


def test_a_restored_model_has_its_dictionaries_before_the_first_query(ctx, shop):
    """A model out of its blob builds no id-map dictionary at restore;
    `stage_model` builds both, where a server stages the tenant, so no
    query builds one and the answers are the fresh model's."""
    import pickle

    from predictionio_tpu.obs.registry import get_registry

    algo, model, shop_ref, _ = shop
    unstaged = ECommModel(
        user_factors=shop_ref.users, item_factors=shop_ref.items,
        user_map=BiMap([f"u{i}" for i in range(N_USERS)]),
        item_map=BiMap([f"i{i}" for i in range(N_ITEMS)]),
        item_categories={
            f"i{i}": [f"c{c}"] for i, c in enumerate(shop_ref.category)
        },
        popularity=shop_ref.popularity.astype(np.float32),
    )
    restored = pickle.loads(pickle.dumps(unstaged, 5))
    assert restored.user_map._index is None and restored.item_map._index is None
    staged = algo.stage_model(ctx, restored)
    assert staged.user_map._index is not None
    assert staged.item_map._index is not None
    registry = MetricRegistry()
    process0 = _index_builds(get_registry(), "restored")
    tracing.StageSink(registry).bind()
    try:
        queries = _mixed_queries()
        answers = algo.batch_predict(staged, queries)
    finally:
        tracing._bound_stages.set(None)
    assert answers == algo.batch_predict(model, queries)
    assert _index_builds(registry, "restored") == 0
    assert _index_builds(get_registry(), "restored") == process0


def test_a_write_is_in_the_next_answer(shop):
    algo, model, shop_ref, (storage, app_id) = shop
    query = {"user": "u9", "num": 5}
    first = [s["item"] for s in algo.predict(model, query)["itemScores"]]
    assert algo.predict(model, query)["itemScores"][0]["item"] == first[0]  # kept
    storage.get_events().insert(Event(
        event="view", entity_type="user", entity_id="u9",
        target_entity_type="item", target_entity_id=first[0],
    ), app_id)
    second = [s["item"] for s in algo.predict(model, query)["itemScores"]]
    assert first[0] not in second and second[:4] == first[1:]
    event_id = storage.get_events().insert(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties=DataMap({"items": [second[0]]}),
    ), app_id)
    third = [s["item"] for s in algo.predict(model, query)["itemScores"]]
    assert second[0] not in third
    # the latest $set alone counts: the 300 of before are available again,
    # and a deleted $set gives way to the one before it
    assert storage.get_events().delete(event_id, app_id)
    assert [s["item"] for s in algo.predict(model, query)["itemScores"]] == second


def test_seen_rows_are_read_once_a_version_of_the_user(shop):
    """`pio_ecomm_seen_lookups_total`: a repeated user is one miss, then
    hits; a write to that user is a miss again, and the written item
    leaves the answer."""
    algo, model, _, (storage, app_id) = shop
    registry = MetricRegistry()
    tracing.StageSink(registry).bind()

    def lookups():
        samples = registry.to_dict()["pio_ecomm_seen_lookups_total"]["samples"]
        return {s["labels"]["result"]: s["value"] for s in samples}

    try:
        query = {"user": "u9", "num": 5}
        first = [s["item"] for s in algo.predict(model, query)["itemScores"]]
        assert lookups() == {"hit": 0, "miss": 1}
        for _ in range(2):
            assert [s["item"] for s in algo.predict(model, query)["itemScores"]] == first
        assert lookups() == {"hit": 2, "miss": 1}
        # twice in one batch, beside a user never asked about
        algo.batch_predict(model, [query, {"user": "u10"}, query])
        assert lookups() == {"hit": 4, "miss": 2}
        storage.get_events().insert(Event(
            event="buy", entity_type="user", entity_id="u9",
            target_entity_type="item", target_entity_id=first[0],
        ), app_id)
        second = [s["item"] for s in algo.predict(model, query)["itemScores"]]
        assert first[0] not in second and second[:4] == first[1:]
        assert lookups() == {"hit": 4, "miss": 3}
        assert [s["item"] for s in algo.predict(model, query)["itemScores"]] == second
        assert lookups() == {"hit": 5, "miss": 3}
    finally:
        tracing._bound_stages.set(None)


def test_a_write_is_seen_by_the_next_query_through_engine_server(ctx, memory_storage):
    from test_templates import _ALS_SMALL, _seed

    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.serving.engine_server import EngineServer

    app_id = _seed(memory_storage, "ecomapp")
    params = EngineParams(
        data_source=("", ECommDataSourceParams(app_name="ecomapp")),
        algorithms=[("ecomm", ECommAlgorithmParams(app_name="ecomapp", **_ALS_SMALL))],
    )
    run_train(ecommerce_engine(), params, engine_id="ecom", ctx=ctx, storage=memory_storage)
    registry = MetricRegistry()
    server = EngineServer(
        ecommerce_engine(), params, engine_id="ecom", storage=memory_storage,
        ctx=ctx, registry=registry,
    )
    http = server.serve(host="127.0.0.1", port=0)
    http.start()

    def ask(query):
        request = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/queries.json",
            data=json.dumps(query).encode(), method="POST",
        )
        with urllib.request.urlopen(request, timeout=30) as reply:
            return [s["item"] for s in json.loads(reply.read())["itemScores"]]

    try:
        first = ask({"user": "u0", "num": 4})
        assert first
        memory_storage.get_events().insert(Event(
            event="buy", entity_type="user", entity_id="u0",
            target_entity_type="item", target_entity_id=first[0],
        ), app_id)
        assert first[0] not in ask({"user": "u0", "num": 4})
        odd = ask({"user": "u1", "num": 4, "categories": ["odd"], "blackList": ["i1"]})
        assert odd and all(int(i[1:]) % 2 == 1 and i != "i1" for i in odd)
        stages = {
            s["labels"]["stage"]: s["count"]
            for s in registry.to_dict()["pio_stage_seconds"]["samples"]
        }
        # two-phase: every predict.* stage observed, the lookups inside prep
        for stage in ("predict.prep", "predict.rules", "predict.enqueue",
                      "predict.device_get", "predict.materialize"):
            assert stages[stage] >= 3, stage
    finally:
        http.shutdown()
        server.close()
