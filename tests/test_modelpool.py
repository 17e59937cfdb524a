"""ModelPool: LRU eviction under a byte budget, active-request
pinning (the eviction-vs-in-flight-query race), single-flight cold
loads off the hot path, replace-on-reload retirement, and the
per-tenant metric surface."""

import threading
import time

import pytest

from predictionio_tpu.obs.registry import MetricRegistry
from predictionio_tpu.serving.modelpool import (
    ModelPool,
    PoolLoadError,
    PoolLoadTimeout,
    default_budget_bytes,
)


def _loader(tenant, nbytes=100, closed=None, calls=None, delay=0.0):
    def load():
        if calls is not None:
            calls.append(tenant)
        if delay:
            time.sleep(delay)
        close = None
        if closed is not None:
            close = lambda: closed.append(tenant)
        return f"model-{tenant}", nbytes, close

    return load


class TestPoolBasics:
    def test_hit_after_cold_load(self):
        pool = ModelPool(1000)
        try:
            calls = []
            with pool.pin("a", _loader("a", calls=calls)) as value:
                assert value == "model-a"
            with pool.pin("a", _loader("a", calls=calls)) as value:
                assert value == "model-a"
            assert calls == ["a"]  # second pin was a hit
        finally:
            pool.close()

    def test_loader_error_propagates_and_retries(self):
        pool = ModelPool(1000)
        try:
            def boom():
                raise RuntimeError("corrupt model")

            with pytest.raises(PoolLoadError):
                with pool.pin("a", boom):
                    pass
            # the failed load must not wedge the tenant
            with pool.pin("a", _loader("a")) as value:
                assert value == "model-a"
        finally:
            pool.close()

    def test_load_timeout(self):
        pool = ModelPool(1000)
        try:
            with pytest.raises(PoolLoadTimeout):
                with pool.pin(
                    "slow", _loader("slow", delay=5.0), timeout=0.05
                ):
                    pass
        finally:
            pool.close()

    def test_single_flight_concurrent_misses(self):
        pool = ModelPool(1000)
        try:
            calls = []
            results = []

            def worker():
                with pool.pin(
                    "a", _loader("a", calls=calls, delay=0.05)
                ) as v:
                    results.append(v)

            threads = [
                threading.Thread(target=worker) for _ in range(5)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert calls == ["a"]  # one load served all five
            assert results == ["model-a"] * 5
        finally:
            pool.close()

    def test_budget_env_override(self, monkeypatch):
        monkeypatch.setenv("PIO_POOL_BUDGET_BYTES", "12345")
        assert default_budget_bytes() == 12345
        monkeypatch.setenv("PIO_POOL_BUDGET_BYTES", "bogus")
        assert default_budget_bytes() > 0


class TestEviction:
    def test_lru_eviction_under_budget(self):
        closed = []
        pool = ModelPool(250)
        try:
            with pool.pin("a", _loader("a", 100, closed)):
                pass
            with pool.pin("b", _loader("b", 100, closed)):
                pass
            # refresh "a" so "b" is the LRU victim
            with pool.pin("a", _loader("a", 100, closed)):
                pass
            with pool.pin("c", _loader("c", 100, closed)):
                pass
            deadline = time.monotonic() + 2.0
            while "b" not in closed and time.monotonic() < deadline:
                time.sleep(0.01)
            assert closed == ["b"]
            assert pool.resident() == ["a", "c"]
        finally:
            pool.close()

    def test_pinned_entry_survives_eviction_pressure(self):
        # THE acceptance race: an eviction pass running while a query
        # holds a pin must not close the pinned model
        closed = []
        pool = ModelPool(150)
        try:
            with pool.pin("hot", _loader("hot", 100, closed)):
                # overflow the budget while "hot" is pinned
                with pool.pin("cold", _loader("cold", 100, closed)):
                    pass
                assert "hot" not in closed
                assert "hot" in pool.resident()
            # after the pin drains, "hot" becomes evictable again
            with pool.pin("third", _loader("third", 100, closed)):
                pass
            deadline = time.monotonic() + 2.0
            while len(closed) < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert "hot" not in pool.resident() or len(closed) >= 1
        finally:
            pool.close()

    def test_explicit_evict_refuses_pinned(self):
        pool = ModelPool(1000)
        try:
            with pool.pin("a", _loader("a")):
                assert pool.evict("a") is False
            assert pool.evict("a") is True
            assert pool.evict("missing") is False
        finally:
            pool.close()

    def test_replace_defers_close_until_unpinned(self):
        closed = []
        pool = ModelPool(1000)
        try:
            entered = threading.Event()
            release = threading.Event()

            def hold():
                with pool.pin("a", _loader("a", 100, closed)) as v:
                    entered.set()
                    release.wait(5.0)
                    # the OLD value must still be intact mid-reload
                    assert v == "model-a"

            t = threading.Thread(target=hold)
            t.start()
            entered.wait(5.0)
            pool.replace("a", lambda: ("model-a-v2", 100, None))
            time.sleep(0.05)
            assert closed == []  # old gen pinned → not closed yet
            release.set()
            t.join()
            deadline = time.monotonic() + 2.0
            while not closed and time.monotonic() < deadline:
                time.sleep(0.01)
            assert closed == ["a"]
            with pool.pin("a", _loader("a")) as v:
                assert v == "model-a-v2"
        finally:
            pool.close()


class TestMetricsAndStats:
    def test_metric_surface(self):
        registry = MetricRegistry()
        pool = ModelPool(250, registry=registry)
        try:
            with pool.pin("a", _loader("a", 100)):
                pass
            with pool.pin("a", _loader("a", 100)):
                pass
            with pool.pin("b", _loader("b", 200)):
                pass
            text = registry.render_prometheus()
            assert 'pio_pool_hits_total{tenant="a"} 1' in text
            assert 'pio_pool_misses_total{tenant="a"} 1' in text
            assert 'pio_pool_misses_total{tenant="b"} 1' in text
            assert 'pio_pool_evictions_total{tenant="a"} 1' in text
            assert 'pio_pool_resident_bytes{tenant="a"} 0' in text
            assert 'pio_pool_resident_bytes{tenant="b"} 200' in text
            assert "pio_pool_budget_bytes 250" in text
            assert "pio_pool_tenants_resident 1" in text
        finally:
            pool.close()

    def test_stats_snapshot(self):
        pool = ModelPool(500)
        try:
            with pool.pin("a", _loader("a", 100)):
                stats = pool.stats()
                assert stats["tenants"]["a"]["pins"] == 1
            with pool.pin("a", _loader("a", 100)):
                pass  # a hit, so the snapshot below shows hits == 1
            stats = pool.stats()
            assert stats["budgetBytes"] == 500
            assert stats["residentBytes"] == 100
            assert stats["tenantsResident"] == 1
            assert stats["tenants"]["a"]["hits"] == 1
        finally:
            pool.close()

    def test_close_idempotent_and_closes_entries(self):
        closed = []
        pool = ModelPool(1000)
        with pool.pin("a", _loader("a", 100, closed)):
            pass
        pool.close()
        pool.close()
        assert closed == ["a"]
        with pytest.raises(RuntimeError):
            with pool.pin("b", _loader("b")):
                pass


def _stage_count(registry, name, field="count"):
    family = registry.to_dict().get("pio_stage_seconds", {"samples": []})
    return sum(
        s[field] for s in family["samples"] if s["labels"]["stage"] == name
    )


class TestStagesAndTheLoadQueue:
    """The pool's stages (`tracing.POOL_STAGES`) and the two series that
    came with them: one `pool.load` a cold load and one `pool.wait` a
    lookup that missed, on the pool's registry whatever thread waits;
    nothing a hit; `pool.close` a closed victim."""

    def test_a_load_and_a_wait_a_miss_and_nothing_a_hit(self):
        from predictionio_tpu.obs import tracing

        registry = MetricRegistry()
        # the waiting thread is bound to the pool's registry, as a
        # server's handler is
        token = tracing._bound_stages.set(None)
        tracing.StageSink(registry).bind()
        pool = ModelPool(250, registry=registry)
        closed = []
        try:
            for tenant, nbytes in (("a", 100), ("a", 100), ("b", 200), ("b", 200)):
                with pool.pin(tenant, _loader(tenant, nbytes, closed, delay=0.02)):
                    pass
            assert _stage_count(registry, tracing.POOL_LOAD) == 2
            assert _stage_count(registry, tracing.POOL_WAIT) == 2
            assert _stage_count(registry, tracing.POOL_WAIT, "sum") >= 0.04
            assert _stage_count(registry, tracing.POOL_LOAD, "sum") >= 0.04
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline and not closed:
                time.sleep(0.005)
            assert closed == ["a"]
            assert _stage_count(registry, tracing.POOL_CLOSE) == 1
            text = registry.render_prometheus()
            assert "pio_pool_loaded_bytes_total 300" in text
            assert "pio_pool_load_queue 0" in text
        finally:
            tracing._bound_stages.reset(token)
            pool.close()

    def test_fits_says_whether_a_victim_would_be_needed(self):
        pool = ModelPool(250)
        try:
            assert pool.fits(250) and not pool.fits(251)
            with pool.pin("a", _loader("a", 100)):
                pass
            assert pool.fits(150) and not pool.fits(151)
            assert pool.fits(0)
        finally:
            pool.close()

    def test_the_queue_counts_loads_the_loader_has_not_begun(self):
        registry = MetricRegistry()
        pool = ModelPool(1000, registry=registry)
        begun, go = threading.Event(), threading.Event()

        def slow():
            begun.set()
            go.wait(5.0)
            return "model-a", 10, None

        def queue_depth():
            family = registry.to_dict()["pio_pool_load_queue"]
            return family["samples"][0]["value"]

        threads = [
            threading.Thread(
                target=lambda t=t, f=f: pool.pin(t, f).__enter__()
            )
            for t, f in (("a", slow), ("b", _loader("b")), ("c", _loader("c")))
        ]
        try:
            threads[0].start()
            assert begun.wait(5.0)
            assert queue_depth() == 0  # the running load is not queued
            for t in threads[1:]:
                t.start()
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline and queue_depth() != 2:
                time.sleep(0.005)
            assert queue_depth() == 2
            go.set()
            for t in threads:
                t.join(5.0)
            assert queue_depth() == 0
        finally:
            go.set()
            pool.close()

    def test_a_waiter_goes_on_before_the_victims_close(self):
        """The entry is in and the waiters are let go before the loader
        closes what it evicted: a slow close delays the next load, not
        the request that caused it."""
        closing, go = threading.Event(), threading.Event()

        def victim():
            def close():
                closing.set()
                go.wait(5.0)

            return "model-a", 200, close

        pool = ModelPool(250)
        try:
            with pool.pin("a", victim):
                pass
            with pool.pin("b", _loader("b", 200)) as value:
                # pinned while the victim's close is still running
                assert value == "model-b"
                assert closing.wait(5.0) and not go.is_set()
            go.set()
        finally:
            go.set()
            pool.close()


def test_an_overcommit_ends_with_the_first_pin_that_drains():
    """A load that finds every other tenant pinned goes in over the
    budget; the pool is back inside it when a pin drains, with no
    further load, and what waits for its close is no overcommit."""
    closed = []
    pool = ModelPool(250)
    try:
        with pool.pin("a", _loader("a", 200, closed)):
            with pool.pin("b", _loader("b", 200, closed)):
                assert pool.resident() == ["a", "b"]  # both pinned
                assert pool.stats()["residentBytes"] == 400
            # b's pin drained: b is the one unpinned entry, and goes
            assert pool.resident() == ["a"]
        assert pool.resident() == ["a"]
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and closed != ["b"]:
            time.sleep(0.005)
        assert closed == ["b"]
        assert pool.stats()["evictions"] == 1
        assert pool.stats()["residentBytes"] == 200
    finally:
        pool.close()
