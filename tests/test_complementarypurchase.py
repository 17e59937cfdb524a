"""Complementary-purchase template (gallery parity: basket analysis
over buy events; TPU path: the pair-count table accumulated on the device
from chunked multi-hot BᵀB, carts scored exactly on the device by lift or
confidence with the support floor)."""

from __future__ import annotations

import datetime as dt
import os
import pickle
import sys

import jax
import numpy as np
import pytest

from predictionio_tpu.data import Event
from predictionio_tpu.data.storage import App
from predictionio_tpu.models.complementarypurchase import (
    ITEMS_PER_QUERY,
    MIN_TOP_K,
    CPAlgoParams,
    CPAlgorithm,
    CPDataSource,
    CPDataSourceParams,
    CPModel,
    CPTrainingData,
    baskets_of,
    complementarypurchase_engine,
)
from predictionio_tpu.ops import cooccurrence as co
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.utils.bimap import BiMap

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks", "chip"
))
import reference_cpurchase as ref  # noqa: E402


@pytest.fixture(scope="module")
def ctx():
    return ComputeContext.create(batch="cp-test")


def _buy(user, item, minute):
    return Event(
        event="buy",
        entity_type="user",
        entity_id=user,
        target_entity_type="item",
        target_entity_id=item,
        event_time=dt.datetime(2026, 1, 1, 12, minute,
                               tzinfo=dt.timezone.utc),
    )


def _seed(storage, app_name="CPApp"):
    """20 users buy bread+butter together; 10 buy beer alone; one user
    buys milk twice in sessions far apart (window split)."""
    app_id = storage.get_meta_data_apps().insert(App(id=0, name=app_name))
    events = storage.get_events()
    events.init(app_id)
    batch = []
    for u in range(20):
        batch.append(_buy(f"u{u}", "bread", 0))
        batch.append(_buy(f"u{u}", "butter", 1))
    for u in range(20, 30):
        batch.append(_buy(f"u{u}", "beer", 0))
    # one-off noise pair below min_support
    batch.append(_buy("u40", "bread", 2))
    batch.append(_buy("u40", "caviar", 3))
    events.insert_batch(batch, app_id)
    return app_id


def _train(ctx, storage, algo_params=CPAlgoParams(), ds_params=None):
    ds = CPDataSource(
        ds_params or CPDataSourceParams(app_name="CPApp")
    )
    data = ds.read_training(ctx)
    data.sanity_check()
    return CPAlgorithm(algo_params).train(ctx, data)


def _complements(model, items, num=5, params=CPAlgoParams()):
    """``[(item, score)]`` a cart of ``items`` is answered."""
    answer = CPAlgorithm(params).predict(model, {"items": items, "num": num})
    return [(s["item"], s["score"]) for s in answer["itemScores"]]


def _dense(table) -> np.ndarray:
    """The [n, n] pair counts of a table in the panel layout."""
    table = np.asarray(table)
    n_panels, n, width = table.shape
    return table.transpose(1, 0, 2).reshape(n, n_panels * width)[:, :n]


def _panels(counts: np.ndarray) -> np.ndarray:
    """A dense [n, n] count matrix in the panel layout, f32."""
    n = len(counts)
    n_panels, width = co.layout(n)
    wide = np.zeros((n, n_panels * width), np.float32)
    wide[:, :n] = counts
    return np.ascontiguousarray(wide.reshape(n, n_panels, width).transpose(1, 0, 2))


def _random_baskets(seed: int, n_items: int, n_baskets: int):
    """Baskets of 1-16 distinct items, drawn Zipf over the items, so that
    popular pairs pass the support floor and rare ones do not."""
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, n_items + 1)
    w /= w.sum()
    return [
        np.unique(rng.choice(n_items, rng.integers(1, 17), p=w))
        for _ in range(n_baskets)
    ]


def _training_data(baskets, n_items: int) -> CPTrainingData:
    indptr = np.zeros(len(baskets) + 1, np.int64)
    np.cumsum([len(b) for b in baskets], out=indptr[1:])
    return CPTrainingData(
        item_map=BiMap([f"p{i:05d}" for i in range(n_items)]),
        indptr=indptr,
        items=np.concatenate(baskets).astype(np.int32),
    )


class TestBasketing:
    def test_window_splits_baskets(self, ctx, memory_storage):
        _seed(memory_storage)
        # same user, purchases 2 hours apart: two baskets
        events = memory_storage.get_events()
        app_id = memory_storage.get_meta_data_apps().get_by_name(
            "CPApp"
        ).id
        events.insert(
            Event(
                event="buy", entity_type="user", entity_id="u99",
                target_entity_type="item", target_entity_id="milk",
                event_time=dt.datetime(2026, 1, 2, 9, 0,
                                       tzinfo=dt.timezone.utc),
            ),
            app_id,
        )
        events.insert(
            Event(
                event="buy", entity_type="user", entity_id="u99",
                target_entity_type="item", target_entity_id="eggs",
                event_time=dt.datetime(2026, 1, 2, 12, 0,
                                       tzinfo=dt.timezone.utc),
            ),
            app_id,
        )
        ds = CPDataSource(CPDataSourceParams(app_name="CPApp"))
        data = ds.read_training(ctx)
        milk = data.item_map.get("milk")
        eggs = data.item_map.get("eggs")
        baskets = np.split(data.items, data.indptr[1:-1])
        together = [
            b for b in baskets if milk in b and eggs in b
        ]
        assert together == []  # 3h gap > 1h window → separate baskets
        assert sum(milk in b for b in baskets) == 1
        assert sum(eggs in b for b in baskets) == 1

    def test_sanity_check_rejects_empty(self):
        with pytest.raises(ValueError, match="no buy events"):
            CPTrainingData(
                item_map=BiMap([]), indptr=np.zeros(1, np.int64),
                items=np.zeros(0, np.int32),
            ).sanity_check()
        one_item = _training_data([np.array([0]), np.array([1])], 2)
        with pytest.raises(ValueError, match="two items"):
            one_item.sanity_check()

    def test_vectorised_baskets_equal_the_per_basket_loop(self):
        """The one-pass grouping equals the earlier loop: each user's
        events in time order, split at window gaps, each basket's items
        unique and sorted."""
        rng = np.random.default_rng(5)
        n = 3000
        users = rng.integers(0, 40, n)
        items = rng.integers(0, 25, n)
        times = rng.integers(0, 50_000, n).astype(np.float64)
        indptr, flat = baskets_of(users, items, times, 3600.0, 25)
        order = np.lexsort((times, users))
        u, i, t = users[order], items[order], times[order]
        starts = np.flatnonzero(
            np.r_[True, (u[1:] != u[:-1]) | (np.diff(t) > 3600.0)]
        )
        bounds = np.append(starts, n)
        loop = [np.unique(i[lo:hi]) for lo, hi in zip(bounds[:-1], bounds[1:])]
        got = np.split(flat, indptr[1:-1])
        assert len(got) == len(loop)
        for a, b in zip(got, loop):
            np.testing.assert_array_equal(a, b)
        empty = baskets_of(users[:0], items[:0], times[:0], 3600.0, 25)
        assert empty[0].tolist() == [0] and len(empty[1]) == 0


class TestCooccurrence:
    def test_lift_finds_the_planted_pair(self, ctx, memory_storage):
        _seed(memory_storage)
        model = _train(ctx, memory_storage)
        comps = _complements(model, ["bread"])
        assert comps, "bread must have complements"
        assert comps[0][0] == "butter"
        # butter ↔ bread is symmetric
        assert _complements(model, ["butter"])[0][0] == "bread"
        # beer was always bought alone
        assert _complements(model, ["beer"]) == []

    def test_min_support_filters_noise(self, ctx, memory_storage):
        _seed(memory_storage)
        model = _train(ctx, memory_storage)
        # caviar co-occurred with bread exactly once < min_support 2
        others = [i for i, _ in _complements(model, ["bread"], 20)]
        assert "caviar" not in others
        # the floor applies at serve time: the same model, a lower floor
        permissive = CPAlgoParams(min_support=1)
        others = [
            i for i, _ in _complements(model, ["bread"], 20, permissive)
        ]
        assert "caviar" in others

    def test_confidence_metric(self, ctx, memory_storage):
        _seed(memory_storage)
        params = CPAlgoParams(metric="confidence")
        model = _train(ctx, memory_storage, params)
        comps = dict(_complements(model, ["bread"], 5, params))
        # 20 of 21 bread baskets contain butter
        assert comps["butter"] == pytest.approx(20 / 21, rel=1e-6)

    def test_bad_metric_rejected(self, ctx, memory_storage):
        _seed(memory_storage)
        with pytest.raises(ValueError, match="metric"):
            _train(ctx, memory_storage, CPAlgoParams(metric="magic"))

    def test_chunked_accumulation_matches_single_chunk(
        self, ctx, memory_storage
    ):
        _seed(memory_storage)
        one = _train(ctx, memory_storage, CPAlgoParams(chunk=4096))
        many = _train(ctx, memory_storage, CPAlgoParams(chunk=3))
        np.testing.assert_array_equal(
            np.asarray(one.table), np.asarray(many.table)
        )
        assert one.n_baskets == many.n_baskets == 31


class TestTrainCounts:
    @pytest.mark.parametrize("n_items,n_baskets,chunk", [
        (64, 700, 128), (4096, 1500, 1024),
    ])
    def test_counts_equal_the_reference(self, ctx, n_items, n_baskets, chunk):
        """The donated on-device accumulation counts every pair as the
        float64 reference counts them from the baskets, one at a time,
        and one chunk counts what many do."""
        baskets = _random_baskets(n_items, n_items, n_baskets)
        data = _training_data(baskets, n_items)
        model = CPAlgorithm(CPAlgoParams(chunk=chunk)).train(ctx, data)
        expected = ref.pair_counts(baskets, n_items)
        got = _dense(model.table)
        np.testing.assert_array_equal(got, expected)
        assert model.n_baskets == n_baskets
        whole = CPAlgorithm(CPAlgoParams(chunk=4096)).train(ctx, data)
        np.testing.assert_array_equal(np.asarray(whole.table), np.asarray(model.table))

    def test_panels_hold_the_counts_of_a_wide_catalog(self, ctx, monkeypatch):
        """Past a panel's width the table splits into panels of one
        width; the counts and the answers are the same."""
        monkeypatch.setattr(co, "PANEL_LANES", 256)
        n_items = 600
        assert co.layout(n_items) == (3, 256)
        baskets = _random_baskets(11, n_items, 900)
        model = CPAlgorithm(CPAlgoParams(chunk=256)).train(
            ctx, _training_data(baskets, n_items)
        )
        counts = ref.pair_counts(baskets, n_items)
        assert np.asarray(model.table).shape == (3, n_items, 256)
        np.testing.assert_array_equal(_dense(model.table), counts)
        np.testing.assert_array_equal(np.asarray(model.table)[2, :, 600 - 512:], 0)
        _assert_answers_equal_the_reference(model, counts, "lift", 2, seed=12)


def _carts(seed: int, n_items: int, n: int):
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, n_items + 1)
    w /= w.sum()
    return [
        rng.choice(n_items, rng.integers(1, 12), replace=False, p=w).tolist()
        for _ in range(n)
    ]


def _assert_answers_equal_the_reference(model, counts, metric, min_support,
                                        seed, num=10):
    n_items = len(counts)
    carts = _carts(seed, n_items, 40)
    diag = np.maximum(np.diag(counts), 1.0)
    algo = CPAlgorithm(CPAlgoParams(metric=metric, min_support=min_support))
    answers = algo.batch_predict(model, [
        {"items": [f"p{i:05d}" for i in cart], "num": num} for cart in carts
    ])
    assert answers == [algo.predict(model, {
        "items": [f"p{i:05d}" for i in cart], "num": num
    }) for cart in carts[:3]] + answers[3:]
    checked = 0
    for cart, answer in zip(carts, answers):
        items = sorted(set(cart))
        rules = dict(zip(items, ref.rule_rows(
            counts[items], items, diag, model.n_baskets, metric, min_support
        )))
        scores = ref.cart_scores(rules, cart, n_items)
        want_items, want = ref.answer_of(scores, num)
        got_items = [int(s["item"][1:]) for s in answer["itemScores"]]
        got = np.array([s["score"] for s in answer["itemScores"]])
        assert len(got_items) == len(want_items)
        assert not set(got_items) & set(cart)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        np.testing.assert_allclose(scores[got_items], got, rtol=1e-5)
        checked += len(got)
    assert checked > 100


class TestServedAnswers:
    @pytest.mark.parametrize("metric", co.METRICS)
    @pytest.mark.parametrize("n_items", [64, 4096])
    def test_answers_equal_the_float64_reference(self, ctx, n_items, metric):
        baskets = _random_baskets(n_items + 1, n_items, 3000)
        with jax.default_matmul_precision("highest"):
            model = CPAlgorithm(CPAlgoParams(metric=metric)).train(
                ctx, _training_data(baskets, n_items)
            )
            counts = ref.pair_counts(baskets, n_items)
            _assert_answers_equal_the_reference(
                model, counts, metric, 2, seed=n_items
            )

    def test_the_support_floor_and_the_cart_are_excluded(self, ctx):
        """Item a is bought with b in three baskets and with c in one:
        at floor 2 only b answers, at floor 1 both; a cart of a and b
        answers c at floor 1, never its own items."""
        counts = np.array([
            [5, 3, 1, 0], [3, 4, 1, 0], [1, 1, 2, 0], [0, 0, 0, 1],
        ], np.float64)
        model = CPAlgorithm().stage_model(ctx, CPModel(
            table=_panels(counts), n_baskets=6, item_map=BiMap(["a", "b", "c", "d"])
        ))
        assert _complements(model, ["a"]) == [("b", pytest.approx(3 * 6 / 20))]
        loose = CPAlgoParams(min_support=1)
        assert [i for i, _ in _complements(model, ["a"], 5, loose)] == ["b", "c"]
        assert [i for i, _ in _complements(model, ["a", "b"], 5, loose)] == ["c"]
        conf = CPAlgoParams(metric="confidence", min_support=1)
        assert _complements(model, ["a", "b"], 5, conf) == [
            ("c", pytest.approx(1 / 5 + 1 / 4))
        ]

    def test_duplicate_unknown_and_empty_carts(self, ctx):
        baskets = _random_baskets(3, 64, 700)
        model = CPAlgorithm().train(ctx, _training_data(baskets, 64))
        algo = CPAlgorithm()
        once = {"items": ["p00001", "p00000"], "num": 8}
        twice = {"items": ["p00000", "p00001", "p00000", "nope", "p00001"], "num": 8}
        got = algo.batch_predict(model, [once, twice, {"items": [], "num": 3},
                                         {"items": ["nope"]}, {"num": 2}])
        assert got[0] == got[1] and len(got[0]["itemScores"]) == 8
        assert got[2:] == [{"itemScores": []}] * 3

    def test_num_above_the_positive_count_answers_the_positives(self, ctx):
        baskets = [np.array([0, 1]), np.array([0, 1]), np.array([0, 2]),
                   np.array([0, 2]), np.array([3, 4])] * 2
        model = CPAlgorithm().train(ctx, _training_data(baskets, 40))
        got = CPAlgorithm().predict(model, {"items": ["p00000"], "num": 30})
        assert [s["item"] for s in got["itemScores"]] == ["p00001", "p00002"]
        assert all(s["score"] > 0 for s in got["itemScores"])
        assert CPAlgorithm().predict(model, {"items": ["p00000"], "num": 0}) == {
            "itemScores": []
        }

    def test_a_model_pickles_as_its_counts_and_answers_the_same(self, ctx):
        baskets = _random_baskets(4, 64, 700)
        model = CPAlgorithm().train(ctx, _training_data(baskets, 64))
        back = pickle.loads(pickle.dumps(model))
        assert isinstance(back.table, np.ndarray) and back.stats is None
        staged = CPAlgorithm().stage_model(ctx, back)
        assert isinstance(staged.table, jax.Array)
        query = {"items": ["p00002", "p00005"], "num": 10}
        assert CPAlgorithm().predict(staged, query) == CPAlgorithm().predict(model, query)

    def test_a_pickle_of_the_top_k_form_asks_for_a_retrain(self):
        """A model pickled before the table (each item's top complements
        as host arrays) cannot be turned into counts: loading it says so."""
        state = {
            "item_map": BiMap(["a", "b"]),
            "topk_items": np.array([[1], [0]], np.int32),
            "topk_scores": np.array([[2.0], [2.0]], np.float32),
        }
        class OldForm:  # pickles as the old dataclass did
            def __reduce__(self):
                return (object.__new__, (CPModel,), state)

        with pytest.raises(ValueError, match="retrain"):
            pickle.loads(pickle.dumps(OldForm()))

    def test_a_batch_bucket_compiles_its_pair_buckets_once(self, ctx, monkeypatch):
        """The first launch of a batch bucket runs the step at each of its
        pair buckets for carts with num up to MIN_TOP_K; later launches
        run it once."""
        baskets = _random_baskets(6, 64, 700)
        model = CPAlgorithm().train(ctx, _training_data(baskets, 64))
        algo = CPAlgorithm()
        calls = []
        step = co.basket_rule_scores

        def counting(table, stats, pairs, n_rows, k, **kw):
            calls.append((pairs.shape[1], n_rows, k))
            return step(table, stats, pairs, n_rows, k, **kw)

        monkeypatch.setattr(co, "basket_rule_scores", counting)
        algo.batch_predict(model, [{"items": ["p00001"], "num": 3}] * 3)
        assert calls == [(p * 4, 4, MIN_TOP_K) for p in ITEMS_PER_QUERY] + [
            (ITEMS_PER_QUERY[0] * 4, 4, MIN_TOP_K)
        ]
        calls.clear()
        cart = [f"p{i:05d}" for i in range(40)]
        algo.batch_predict(model, [{"items": cart, "num": 10}] * 4)
        assert calls == [(256, 4, MIN_TOP_K)]
        calls.clear()
        algo.batch_predict(model, [{"items": cart, "num": 17}] * 4)
        assert calls == [(256, 4, 32)]


class TestServing:
    def test_query_shape_and_exclusion(self, ctx, memory_storage):
        _seed(memory_storage)
        model = _train(ctx, memory_storage)
        algo = CPAlgorithm(CPAlgoParams())
        result = algo.predict(
            model, {"items": ["bread", "butter"], "num": 3}
        )
        items = [s["item"] for s in result["itemScores"]]
        # queried items never come back as their own complements
        assert "bread" not in items and "butter" not in items
        assert set(result) == {"itemScores"}

    def test_duplicate_query_items_not_double_counted(
        self, ctx, memory_storage
    ):
        _seed(memory_storage)
        model = _train(ctx, memory_storage)
        algo = CPAlgorithm(CPAlgoParams())
        once = algo.predict(model, {"items": ["bread"], "num": 3})
        twice = algo.predict(
            model, {"items": ["bread", "bread"], "num": 3}
        )
        assert once == twice

    def test_unknown_item_is_empty(self, ctx, memory_storage):
        _seed(memory_storage)
        model = _train(ctx, memory_storage)
        algo = CPAlgorithm(CPAlgoParams())
        assert algo.predict(
            model, {"items": ["nope"], "num": 3}
        ) == {"itemScores": []}

    def test_engine_end_to_end(self, ctx, memory_storage):
        """Full DASE assembly through Engine.train + predict."""
        from predictionio_tpu.core import EngineParams
        from predictionio_tpu.core.workflow import run_train, load_deployment

        _seed(memory_storage)
        engine = complementarypurchase_engine()
        params = EngineParams(
            data_source=("", CPDataSourceParams(app_name="CPApp")),
            preparator=("", None),
            algorithms=[("cooccurrence", CPAlgoParams())],
        )
        run_train(
            engine, params, engine_id="cp", ctx=ctx,
            storage=memory_storage,
        )
        _inst, algorithms, models, serving = load_deployment(
            engine, params, engine_id="cp", ctx=ctx,
            storage=memory_storage,
        )
        preds = algorithms[0].batch_predict(
            models[0], [{"items": ["bread"], "num": 2}]
        )
        out = serving.serve({"items": ["bread"], "num": 2}, [preds[0]])
        assert out["itemScores"][0]["item"] == "butter"


def test_stages_and_counters_on_the_engine_server(ctx, memory_storage):
    """Served through a single-tenant EngineServer's batcher: the launch
    and collect run the four predict stages once, and the three counters
    hold the batch's distinct known items, padded slots and unknown
    items."""
    from predictionio_tpu.core import EngineParams
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.obs import MetricRegistry
    from predictionio_tpu.serving.engine_server import EngineServer

    _seed(memory_storage)
    engine = complementarypurchase_engine()
    params = EngineParams(
        data_source=("", CPDataSourceParams(app_name="CPApp")),
        preparator=("", None),
        algorithms=[("cooccurrence", CPAlgoParams())],
    )
    run_train(engine, params, engine_id="cp-stages", ctx=ctx, storage=memory_storage)
    registry = MetricRegistry()
    es = EngineServer(
        engine, params, engine_id="cp-stages", storage=memory_storage,
        ctx=ctx, warmup=False, registry=registry, max_wait_ms=200.0,
        adaptive_wait=False,
    )
    queries = [
        {"items": ["bread", "nope"], "num": 1},
        {"items": ["butter", "bread", "butter"]},
    ]
    try:
        futures = [es._batchers[0].submit(q) for q in queries]
        answers = [f.result(30) for f in futures]
    finally:
        es.close()
    assert [s["item"] for s in answers[0]["itemScores"]] == ["butter"]
    assert answers[1] == {"itemScores": []}
    data = registry.to_dict()
    stages = {
        s["labels"]["stage"]: s["count"] for s in data["pio_stage_seconds"]["samples"]
    }
    for stage in ("predict.prep", "predict.enqueue", "predict.device_get",
                  "predict.materialize"):
        assert stages.get(stage) == 1, stage

    def value(family):
        [sample] = data[family]["samples"]
        return sample["value"]

    assert value("pio_cpurchase_items_total") == 3  # "butter" twice is one
    assert value("pio_cpurchase_item_slots_total") == ITEMS_PER_QUERY[0] * 2
    assert value("pio_cpurchase_unknown_items_total") == 1
