"""A multi-tenant engine server whose tenants are more than its model pool
holds: the preload fills the pool and stops, the pool evicts and reloads
from the model store's bytes under requests, every answer is the plain
reference's before and after a reload, and the pool's stages
(`tracing.POOL_STAGES`) say where a cold load's time went."""

import json
import threading
import time
import urllib.request
import weakref

import numpy as np
import pytest

from predictionio_tpu.core.engine import EngineParams
from predictionio_tpu.core.persistence import (
    publish_generation,
    serialize_models,
)
from predictionio_tpu.data.storage.base import EngineInstance
from predictionio_tpu.models.recommendation import (
    ALSAlgorithm,
    ALSParams,
    ALSRecModel,
    RecDataSourceParams,
    RecPreparatorParams,
    recommendation_engine,
)
from predictionio_tpu.obs import tracing
from predictionio_tpu.obs.registry import MetricRegistry
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.serving.engine_server import EngineServer
from predictionio_tpu.serving.modelpool import ModelPool
from predictionio_tpu.utils.bimap import BiMap

import datetime as _dt

ENGINE_ID = "srv-evict"
N_USERS, N_ITEMS, RANK, NUM = 300, 200, 8, 5
TENANT_BYTES = (N_USERS + N_ITEMS) * RANK * 4
#: the stages between the two instants `pio_http_request_seconds` spans
REQUEST_STAGES = (
    tracing.HTTP_ADMIT, tracing.ENGINE_DECODE, tracing.POOL_WAIT,
    tracing.ENGINE_SUBMIT, tracing.ENGINE_AWAIT, tracing.ENGINE_SERVE,
)


@pytest.fixture(scope="module")
def ctx():
    return ComputeContext.create(batch="srv-evict-test")


def _name(t: int) -> str:
    return f"t{t}"


def _params():
    return EngineParams(
        data_source=("", RecDataSourceParams(app_name=ENGINE_ID)),
        preparator=("", RecPreparatorParams()),
        algorithms=[("als", ALSParams(rank=RANK))],
    )


def _publish(storage, n_tenants: int, n_items_of=lambda t: N_ITEMS) -> dict:
    """Every tenant's model into the store as a train would publish it;
    ``{tenant index: (user table, item table)}`` for the reference."""
    instances = storage.get_meta_data_engine_instances()
    models = storage.get_model_data_models()
    now = _dt.datetime.now(_dt.timezone.utc)
    algorithms = [ALSAlgorithm(ALSParams(rank=RANK))]
    tables = {}
    for t in range(n_tenants):
        rng = np.random.default_rng([7, t])
        users = (0.25 * rng.standard_normal((N_USERS, RANK))).astype(np.float32)
        items = rng.standard_normal((n_items_of(t), RANK)).astype(np.float32)
        model = ALSRecModel(
            user_factors=users, item_factors=items,
            user_map=BiMap([f"u{i}" for i in range(N_USERS)]),
            item_map=BiMap([f"{_name(t)}.i{j}" for j in range(len(items))]),
        )
        iid = instances.insert(EngineInstance(
            id="", status="COMPLETED", start_time=now, end_time=now,
            engine_id=ENGINE_ID, engine_version="1", engine_variant=_name(t),
            engine_factory="recommendation",
        ))
        publish_generation(models, iid, serialize_models(iid, algorithms, [model]))
        tables[t] = (users, items)
    return tables


def _server(ctx, storage, n_tenants, room, registry=None, **kwargs):
    registry = registry or MetricRegistry()
    pool = ModelPool(
        budget_bytes=int((room + 0.5) * TENANT_BYTES), registry=registry
    )
    server = EngineServer(
        recommendation_engine(), _params(), engine_id=ENGINE_ID,
        storage=storage, ctx=ctx, registry=registry, pool=pool, max_batch=8,
        tenants={_name(t): _name(t) for t in range(n_tenants)}, **kwargs,
    )
    return server, pool, registry


def _post(base, tenant, users):
    body = json.dumps([{"user": f"u{u}", "num": NUM} for u in users]).encode()
    req = urllib.request.Request(
        f"{base}/batch/queries.json?accessKey={_name(tenant)}", data=body,
        method="POST", headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=20) as resp:
        return resp.status, json.loads(resp.read())


def _reference(tables, tenant, user):
    users, items = tables[tenant]
    scores = users[user].astype(np.float64) @ items.astype(np.float64).T
    best = np.argsort(-scores, kind="stable")[:NUM]
    return [f"{_name(tenant)}.i{j}" for j in best], scores[best]


def _stage(registry, name, field="count"):
    family = registry.to_dict().get("pio_stage_seconds", {"samples": []})
    return sum(
        s[field] for s in family["samples"] if s["labels"]["stage"] == name
    )


def _total(registry, family, field="value"):
    found = registry.to_dict().get(family, {"samples": []})["samples"]
    return sum(float(s.get(field) or 0.0) for s in found)


def _lru_misses(sequence, preloaded, room):
    """The posts of ``sequence`` that find their tenant not resident, by
    plain LRU over ``room`` tenants."""
    order = list(preloaded)
    misses = 0
    for t in sequence:
        if t in order:
            order.remove(t)
        else:
            misses += 1
            if len(order) >= room:
                order.pop(0)
        order.append(t)
    return misses


@pytest.mark.parametrize("n_tenants,room,staged", [(6, 4, 4), (4, 6, 4), (1, 1, 1)])
def test_preload_fills_the_pool_in_order_and_stops(
    ctx, memory_storage, monkeypatch, caplog, n_tenants, room, staged
):
    """Six tenants over room for four: four loads in the order given, no
    eviction, two left cold; a pool that fits loads all, in order."""
    _publish(memory_storage, n_tenants)
    loaded = []
    stage = EngineServer._stage

    def recording(self, *args, tenant=None, **kwargs):
        loaded.append(tenant)
        return stage(self, *args, tenant=tenant, **kwargs)

    monkeypatch.setattr(EngineServer, "_stage", recording)
    with caplog.at_level("INFO", logger="predictionio_tpu.serving.engine_server"):
        server, pool, registry = _server(ctx, memory_storage, n_tenants, room)
    try:
        assert loaded == [_name(t) for t in range(staged)]
        assert pool.resident() == sorted(loaded)
        assert pool.stats()["evictions"] == 0
        assert _total(registry, "pio_pool_evictions_total") == 0
        assert _total(registry, "pio_pool_misses_total") == staged
        assert _stage(registry, tracing.POOL_LOAD) == staged
        assert _total(registry, "pio_warmup_complete") == 1
        assert _total(registry, "pio_pool_loaded_bytes_total") == staged * TENANT_BYTES
        assert (
            f"preloaded {staged} of {n_tenants} tenant(s), "
            f"{n_tenants - staged} left cold"
        ) in caplog.text
    finally:
        server.close()
        pool.close()


def test_an_evicting_server_answers_as_the_reference_before_and_after_reloads(
    ctx, memory_storage, monkeypatch
):
    tables = _publish(memory_storage, 6)
    threads = []  # of every generation this server stages
    stage = EngineServer._stage

    def recording(self, *args, **kwargs):
        staged = stage(self, *args, **kwargs)
        for b in staged.batchers:
            threads.extend([b._thread, b._completer])
        return staged

    monkeypatch.setattr(EngineServer, "_stage", recording)
    server, pool, registry = _server(ctx, memory_storage, 6, 4)
    http = server.serve(host="127.0.0.1", port=0)
    http.start()
    base = f"http://127.0.0.1:{http.port}"
    budget = pool.budget_bytes
    rng = np.random.default_rng(36)
    sequence = [4, 4, 5, 0, 0, 1, 5, 2, 3, 4, 3, 5, 0, 1, 1, 2]
    expected_misses = _lru_misses(sequence, [0, 1, 2, 3], 4)
    assert expected_misses >= 6
    try:
        waits0 = _stage(registry, tracing.POOL_WAIT)
        loads0 = _stage(registry, tracing.POOL_LOAD)
        for tenant in sequence:
            users = [int(u) for u in rng.integers(0, N_USERS, 8)]
            status, slots = _post(base, tenant, users)
            assert status == 200 and len(slots) == 8
            for user, slot in zip(users, slots):
                assert slot["status"] == 200, slot
                rows = slot["prediction"]["itemScores"]
                ids, scores = _reference(tables, tenant, user)
                assert [r["item"] for r in rows] == ids
                np.testing.assert_allclose(
                    [r["score"] for r in rows], scores, rtol=1e-4, atol=1e-5
                )
            resident = _total(registry, "pio_pool_resident_bytes")
            assert resident <= budget + TENANT_BYTES
        misses = _total(registry, "pio_pool_misses_total") - 4
        assert misses == expected_misses
        assert _total(registry, "pio_pool_hits_total") == len(sequence) - misses
        # one load and one wait a miss, none on a hit
        assert _stage(registry, tracing.POOL_LOAD) - loads0 == misses
        assert _stage(registry, tracing.POOL_WAIT) - waits0 == misses
        assert _total(registry, "pio_pool_evictions_total") == misses
        assert _total(registry, "pio_pool_loaded_bytes_total") == (4 + misses) * TENANT_BYTES
        for nested in (
            tracing.POOL_READ, tracing.POOL_DESERIALIZE, tracing.POOL_PROMOTE,
            tracing.POOL_WARMUP, tracing.POOL_BATCHERS,
        ):
            assert _stage(registry, nested) == 4 + misses, nested
        nested_s = sum(
            _stage(registry, n, "sum") for n in tracing.POOL_STAGES[2:7]
        )
        assert 0.9 < nested_s / _stage(registry, tracing.POOL_LOAD, "sum") <= 1.0
        # the evicted generations' threads end: two a resident tenant stay
        deadline = time.monotonic() + 5.0
        alive = lambda: sum(t.is_alive() for t in threads)  # noqa: E731
        while time.monotonic() < deadline and (
            _stage(registry, tracing.POOL_CLOSE) < misses or alive() != 8
        ):
            time.sleep(0.01)
        assert _stage(registry, tracing.POOL_CLOSE) == misses
        assert len(threads) == 2 * (4 + misses) and alive() == 8
        assert _total(registry, "pio_batcher_leaked_threads_total") == 0
        assert _total(registry, "pio_pool_load_queue") == 0
        assert _total(registry, "pio_pool_tenants_resident") == 4
    finally:
        http.shutdown()
        server.close()
        pool.close()


def test_the_handlers_stages_cover_the_request_with_the_wait_among_them(
    ctx, memory_storage
):
    """Posts that each wait for a slow cold load: the stages of a request
    add up to `pio_http_request_seconds` with `pool.wait` among them."""
    _publish(memory_storage, 3)
    models = memory_storage.get_model_data_models()
    get = models.get

    def slow_get(model_id):
        time.sleep(0.02)
        return get(model_id)

    models.get = slow_get
    server, pool, registry = _server(ctx, memory_storage, 3, 1)
    http = server.serve(host="127.0.0.1", port=0)
    http.start()
    base = f"http://127.0.0.1:{http.port}"
    try:
        for tenant in (1, 2, 0, 1, 2, 0):
            assert _post(base, tenant, [1, 2, 3])[0] == 200
        requests = _total(registry, "pio_http_request_seconds", "sum")
        stages = {n: _stage(registry, n, "sum") for n in REQUEST_STAGES}
        # (a busy machine puts time between two stages, never into two)
        assert 0.9 < sum(stages.values()) / requests <= 1.0
        # every post waited for its load, and a load holds the slow read
        assert _stage(registry, tracing.POOL_WAIT) == 6
        assert stages[tracing.POOL_WAIT] >= 6 * 0.02
        # the wait is no part of engine.submit any more
        assert stages[tracing.ENGINE_SUBMIT] < 0.1 * stages[tracing.POOL_WAIT]
    finally:
        http.shutdown()
        server.close()
        pool.close()


def test_a_post_in_flight_on_the_tenant_an_eviction_would_take_completes(
    ctx, memory_storage, monkeypatch
):
    """Room for one: a post of tenant 0 is held inside its predict while a
    post of tenant 1 loads. Tenant 0 is pinned, so it is no victim; both
    posts are answered by their own tenant's tables, and the pool is back
    inside its budget as soon as a pin has drained."""
    tables = _publish(memory_storage, 2)
    armed, held, release = (threading.Event() for _ in range(3))
    collect = ALSAlgorithm.batch_predict_collect

    def holding(self, model, handle, queries):
        if armed.is_set() and model.item_map.inverse(0).startswith("t0."):
            armed.clear()
            held.set()
            release.wait(10.0)
        return collect(self, model, handle, queries)

    # before the server stages anything: a batcher keeps the bound hook
    monkeypatch.setattr(ALSAlgorithm, "batch_predict_collect", holding)
    server, pool, registry = _server(ctx, memory_storage, 2, 1)
    http = server.serve(host="127.0.0.1", port=0)
    http.start()
    base = f"http://127.0.0.1:{http.port}"
    armed.set()
    answers = {}

    def post(tenant):
        answers[tenant] = _post(base, tenant, [5, 6])

    try:
        first = threading.Thread(target=post, args=(0,))
        first.start()
        assert held.wait(10.0)
        # loads beside the pinned tenant, over the budget: there is nothing
        # to evict. The overcommit ends with the first pin that drains (this
        # post's own), not with the next load
        post(1)
        assert pool.resident() == ["t0"]
        assert _total(registry, "pio_pool_evictions_total") == 1
        assert not release.is_set()
        release.set()
        first.join(10.0)
        assert pool.resident() == ["t0"]
        for tenant in (0, 1):
            status, slots = answers[tenant]
            assert status == 200
            for user, slot in zip((5, 6), slots):
                assert slot["status"] == 200
                assert [r["item"] for r in slot["prediction"]["itemScores"]] == (
                    _reference(tables, tenant, user)[0]
                )
    finally:
        release.set()
        http.shutdown()
        server.close()
        pool.close()


def test_an_evicted_generation_goes_with_its_close_not_with_a_collection(
    ctx, memory_storage
):
    """The ledger is the device's truth: once an evicted generation's
    close has run, nothing keeps its tables (no reference cycle that only
    the collector would break)."""
    import gc

    _publish(memory_storage, 2)
    server, pool, registry = _server(ctx, memory_storage, 2, 1)
    gc.collect()
    gc.disable()
    try:
        with pool.pin("t0", server._tenant_loader("t0")) as staged:
            ref = weakref.ref(staged)
            tables = weakref.ref(staged.batchers[0])
        del staged
        with pool.pin("t1", server._tenant_loader("t1")):
            pass
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and _stage(registry, tracing.POOL_CLOSE) < 1:
            time.sleep(0.01)
        time.sleep(0.05)
        assert ref() is None and tables() is None
    finally:
        gc.enable()
        server.close()
        pool.close()


def test_a_warm_up_runs_only_the_buckets_this_process_has_not_warmed(
    ctx, memory_storage
):
    """The first tenant of a shape warms every bucket; the next tenants
    of that shape warm none and still count as warmed; a tenant of
    another shape, or an algorithm of other params, warms its own. The
    loader's warm-ups leave the server's `predict.*` series alone."""
    from predictionio_tpu.serving.engine_server import _model_signature

    tables = _publish(memory_storage, 4, lambda t: 150 if t == 2 else N_ITEMS)
    server, pool, registry = _server(ctx, memory_storage, 4, 6)
    try:
        data = registry.to_dict()
        warmed = {}
        for s in data["pio_warmup_seconds"]["samples"]:
            warmed.setdefault(s["labels"]["batcher"], set()).add(s["labels"]["bucket"])
        assert warmed == {
            f"{ENGINE_ID}/t0/algo0": {"1", "2", "4", "8"},
            f"{ENGINE_ID}/t2/algo0": {"1", "2", "4", "8"},
        }
        # the compile sites are the batchers' alone, each bucket once
        assert {
            s["labels"]["site"]: s["value"]
            for s in data["pio_jit_compiles_total"]["samples"]
        } == {f"{ENGINE_ID}/t0/algo0": 4, f"{ENGINE_ID}/t2/algo0": 4}
        assert len(server._warmed) == 8  # four buckets, two shapes
        assert _total(registry, "pio_warmup_complete") == 1
        # equal shapes under other params are other programs: all warmed
        model = ALSRecModel(
            *tables[0], BiMap([f"u{i}" for i in range(N_USERS)]),
            BiMap([f"i{j}" for j in range(N_ITEMS)]),
        )
        same = ALSAlgorithm(ALSParams(rank=RANK))
        assert server._precompile([same], [model], "same/")
        assert len(server._warmed) == 8
        other = ALSAlgorithm(ALSParams(rank=RANK, seed=99))
        assert server._precompile([other], [model], "again/")
        assert len(server._warmed) == 12
        assert {
            s["labels"]["bucket"]
            for s in registry.to_dict()["pio_warmup_seconds"]["samples"]
            if s["labels"]["batcher"] == "again/algo0"
        } == {"1", "2", "4", "8"}
        for t in range(4):
            with pool.pin(_name(t), server._tenant_loader(_name(t))) as staged:
                assert staged.warmed
        assert _stage(registry, tracing.POOL_WARMUP) == 4
        assert _stage(registry, tracing.PREDICT_ENQUEUE) == 0
        assert _model_signature(
            ALSRecModel(*tables[1], BiMap(["u0"]), BiMap(["i0", "i1"]))
        ) == (
            "ALSRecModel",
            ("user_factors", ((N_USERS, RANK), "float32")),
            ("item_factors", ((N_ITEMS, RANK), "float32")),
            ("user_map", ("BiMap", 1)), ("item_map", ("BiMap", 2)),
            ("item_phantom_mask", None),
        )
        assert _model_signature(
            ALSRecModel(*tables[2], BiMap(["u0"]), BiMap(["i0", "i1"]))
        ) != _model_signature(
            ALSRecModel(*tables[1], BiMap(["u0"]), BiMap(["i0", "i1"]))
        )
    finally:
        server.close()
        pool.close()
