"""Unified telemetry tests: metric registry, /metrics endpoints,
request-ID propagation through the serving stack, batcher telemetry,
and the lastServingSec / shed-cancellation fixes (ISSUE 1)."""

import json
import logging
import math
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import Future

import pytest

from fake_engine import (
    FakeAlgorithm,
    FakeDataSource,
    FakeParams,
    FakePreparator,
    FakeServing,
)
from predictionio_tpu.core import Engine, EngineParams
from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.obs import (
    MetricRegistry,
    get_registry,
    get_request_id,
    set_request_id,
)
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.serving.batching import BatcherOverloaded, MicroBatcher
from predictionio_tpu.serving.engine_server import EngineServer
from predictionio_tpu.utils.profiling import StepTimer


@pytest.fixture(scope="module")
def ctx():
    return ComputeContext.create(batch="obs-test")


def _call(url, method="GET", body=None, headers=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method, headers=headers or {}
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


# -- registry primitives ---------------------------------------------------


class TestRegistry:
    def test_counter_inc_and_labels(self):
        reg = MetricRegistry()
        c = reg.counter("t_total", "help", ("route",))
        c.labels("a").inc()
        c.labels("a").inc(2)
        c.labels("b").inc()
        assert c.labels("a").value == 3
        assert c.labels("b").value == 1

    def test_counter_rejects_negative(self):
        reg = MetricRegistry()
        with pytest.raises(ValueError):
            reg.counter("t_total").inc(-1)

    def test_gauge_set_and_callback(self):
        reg = MetricRegistry()
        g = reg.gauge("t_depth")
        g.set(7)
        assert g.value == 7
        g2 = reg.gauge("t_live")
        g2.set_function(lambda: 42)
        assert g2.value == 42

    def test_gauge_callback_failure_is_nan_not_500(self):
        reg = MetricRegistry()
        g = reg.gauge("t_bad")
        g.set_function(lambda: 1 / 0)
        assert math.isnan(g.value)
        # the scrape still renders
        assert "t_bad" in reg.render_prometheus()

    def test_histogram_counts_and_percentiles(self):
        reg = MetricRegistry()
        h = reg.histogram("t_lat", buckets=(0.1, 0.2, 0.4, 0.8))
        for _ in range(98):
            h.observe(0.05)
        h.observe(0.3)
        h.observe(0.7)
        child = h.labels()
        assert child.count == 100
        assert h.percentile(0.5) <= 0.1
        assert 0.2 < h.percentile(0.99) <= 0.8

    def test_histogram_empty_percentile_is_nan(self):
        reg = MetricRegistry()
        assert math.isnan(reg.histogram("t_e").percentile(0.5))

    def test_prometheus_text_format(self):
        reg = MetricRegistry()
        reg.counter("t_req", "requests", ("m",)).labels("GET").inc(5)
        reg.histogram("t_lat", "latency", buckets=(0.5, 1.0)).observe(0.7)
        text = reg.render_prometheus()
        assert "# TYPE t_req counter" in text
        assert 't_req{m="GET"} 5' in text
        assert "# TYPE t_lat histogram" in text
        assert 't_lat_bucket{le="0.5"} 0' in text
        assert 't_lat_bucket{le="1"} 1' in text
        assert 't_lat_bucket{le="+Inf"} 1' in text
        assert "t_lat_count 1" in text
        assert "t_lat_sum 0.7" in text

    def test_json_export_has_derived_percentiles(self):
        reg = MetricRegistry()
        h = reg.histogram("t_lat", buckets=(0.1, 1.0))
        h.observe(0.05)
        sample = reg.to_dict()["t_lat"]["samples"][0]
        assert sample["count"] == 1
        assert sample["p50"] is not None
        assert sample["p99"] <= 0.1

    def test_histogram_snapshot_carries_per_bucket_counts(self):
        """ISSUE 16: JSON snapshots expose raw bucket counts with an
        explicit +Inf key so federation can merge bucket-wise (and a
        merged histogram's percentiles match the union exactly)."""
        from predictionio_tpu.obs.federation import (
            merge_histogram_samples,
        )

        def snap(values):
            reg = MetricRegistry()
            h = reg.histogram("t_m", buckets=(0.1, 0.5, 1.0))
            for v in values:
                h.observe(v)
            return reg.to_dict()["t_m"]["samples"][0]

        a = snap([0.05, 0.3, 2.0])
        assert a["buckets"] == {"0.1": 1, "0.5": 1, "1": 0, "+Inf": 1}
        # existing keys stay intact (backward compatibility)
        assert a["count"] == 3 and "p95" in a and "sum" in a
        b = snap([0.05] * 10 + [0.7] * 3)
        merged = merge_histogram_samples([a, b])
        union = snap([0.05, 0.3, 2.0] + [0.05] * 10 + [0.7] * 3)
        assert merged["buckets"] == union["buckets"]
        assert (merged["p50"], merged["p95"], merged["p99"]) == (
            union["p50"],
            union["p95"],
            union["p99"],
        )

    def test_process_gauges_exported(self):
        """pio_process_resident_bytes / pio_process_open_fds read
        /proc at scrape time on every registry."""
        import os

        if not os.path.isdir("/proc/self"):
            pytest.skip("no procfs")
        from predictionio_tpu.obs.registry import (
            _install_process_metrics,
        )

        reg = MetricRegistry()
        _install_process_metrics(reg)  # default registry gets this
        data = reg.to_dict()
        rss = data["pio_process_resident_bytes"]["samples"][0]["value"]
        fds = data["pio_process_open_fds"]["samples"][0]["value"]
        assert rss > 1024 * 1024  # a python process holds > 1 MiB
        assert fds >= 3  # stdio at minimum
        # scrape-time evaluation: opening a file moves the fd gauge
        with open("/proc/self/status"):
            fds2 = reg.to_dict()["pio_process_open_fds"]["samples"][
                0
            ]["value"]
        assert fds2 >= fds + 1
        text = reg.render_prometheus()
        assert "pio_process_resident_bytes" in text
        assert "pio_process_open_fds" in text

    def test_get_or_create_is_idempotent_and_type_safe(self):
        reg = MetricRegistry()
        a = reg.counter("t_x", "h")
        assert reg.counter("t_x") is a
        with pytest.raises(ValueError):
            reg.gauge("t_x")
        with pytest.raises(ValueError):
            reg.counter("t_x", label_names=("other",))

    def test_concurrent_observe_loses_nothing(self):
        reg = MetricRegistry()
        h = reg.histogram("t_conc", buckets=(1.0,))
        c = reg.counter("t_conc_total")

        def work():
            for _ in range(500):
                h.observe(0.5)
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(8)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert h.labels().count == 4000
        assert c.value == 4000


class TestRequestIdContext:
    def test_forwarded_id_kept(self):
        assert set_request_id("abc-123") == "abc-123"
        assert get_request_id() == "abc-123"

    def test_malformed_id_replaced(self):
        rid = set_request_id('evil"\nid with spaces')
        assert rid != 'evil"\nid with spaces'
        assert len(rid) == 16  # minted token_hex(8)

    def test_oversized_id_replaced(self):
        assert len(set_request_id("x" * 500)) == 16


# -- engine-server integration --------------------------------------------


class DictQueryAlgorithm(FakeAlgorithm):
    def predict(self, model, query):
        return {"result": model.algo_id * 10 + int(query.get("x", 0))}

    def batch_predict(self, model, queries):
        return [self.predict(model, q) for q in queries]


class DictServing(FakeServing):
    def serve(self, query, predictions):
        return predictions[0]


def _engine():
    return Engine(
        FakeDataSource, FakePreparator, DictQueryAlgorithm, DictServing
    )


def _params():
    return EngineParams(
        data_source=("", FakeParams(id=1)),
        preparator=("", FakeParams(id=2)),
        algorithms=[("", FakeParams(id=3))],
        serving=("", FakeParams()),
    )


@pytest.fixture()
def obs_server(ctx, memory_storage):
    registry = MetricRegistry()
    run_train(
        _engine(), _params(), engine_id="obs", ctx=ctx,
        storage=memory_storage,
    )
    es = EngineServer(
        _engine(),
        _params(),
        engine_id="obs",
        storage=memory_storage,
        ctx=ctx,
        warmup=False,
        registry=registry,
    )
    http = es.serve(host="127.0.0.1", port=0)
    http.start()
    yield f"http://127.0.0.1:{http.port}", es, registry
    http.shutdown()
    es.close()


class TestEngineServerMetrics:
    def test_prometheus_scrape_has_request_and_batch_metrics(
        self, obs_server
    ):
        base, _, _ = obs_server
        status, body, _ = _call(
            f"{base}/queries.json", "POST", {"x": 7}
        )
        assert status == 200
        status, text, headers = _call(f"{base}/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        text = text.decode()
        # acceptance: request latency buckets + batch occupancy
        assert "pio_http_request_seconds_bucket" in text
        assert 'route="/queries.json"' in text
        assert "pio_batch_occupancy_bucket" in text
        assert "pio_batch_queue_depth" in text
        assert "pio_device_dispatch_seconds_bucket" in text
        assert "pio_http_requests_total" in text
        assert 'status="200"' in text

    def test_metrics_json_mirror(self, obs_server):
        base, _, _ = obs_server
        _call(f"{base}/queries.json", "POST", {"x": 1})
        status, body, _ = _call(f"{base}/metrics.json")
        assert status == 200
        data = json.loads(body)
        lat = data["pio_http_request_seconds"]
        assert lat["type"] == "histogram"
        sample = next(
            s for s in lat["samples"]
            if s["labels"]["route"] == "/queries.json"
        )
        assert sample["count"] >= 1
        assert sample["p50"] is not None
        occ = data["pio_batch_occupancy"]["samples"][0]
        assert occ["count"] >= 1

    def test_request_id_echoed_and_logged(self, obs_server, caplog):
        base, _, _ = obs_server
        with caplog.at_level(
            logging.DEBUG, logger="predictionio_tpu.access"
        ):
            status, _, headers = _call(
                f"{base}/queries.json", "POST", {"x": 1},
                headers={"X-Request-ID": "abc"},
            )
        assert status == 200
        assert headers["X-Request-ID"] == "abc"
        lines = [
            json.loads(r.message)
            for r in caplog.records
            if r.name == "predictionio_tpu.access"
        ]
        match = [l for l in lines if l.get("requestId") == "abc"]
        assert match, lines
        assert match[0]["route"] == "/queries.json"
        assert match[0]["status"] == 200
        assert match[0]["ms"] >= 0

    def test_request_id_minted_when_absent(self, obs_server):
        base, _, _ = obs_server
        _, _, headers = _call(f"{base}/")
        rid = headers["X-Request-ID"]
        assert len(rid) == 16
        int(rid, 16)  # hex

    def test_error_response_carries_request_id(self, obs_server):
        base, _, _ = obs_server
        status, body, headers = _call(
            f"{base}/queries.json", "POST", [1, 2],
            headers={"X-Request-ID": "err-42"},
        )
        assert status == 400
        assert json.loads(body)["requestId"] == "err-42"
        assert headers["X-Request-ID"] == "err-42"

    def test_request_id_traverses_batcher_log(self, obs_server, caplog):
        """The slow-query trace: the dispatch log line names the
        request IDs that rode in the device batch."""
        base, _, _ = obs_server
        with caplog.at_level(
            logging.DEBUG, logger="predictionio_tpu.serving.batching"
        ):
            _call(
                f"{base}/queries.json", "POST", {"x": 3},
                headers={"X-Request-ID": "trace-me"},
            )
        dispatches = [
            json.loads(r.message)
            for r in caplog.records
            if r.name == "predictionio_tpu.serving.batching"
        ]
        assert any(
            "trace-me" in d.get("requestIds", []) for d in dispatches
        ), dispatches

    def test_last_serving_sec_semantics_split(self, obs_server):
        """ADVICE r5: batch route used to store elapsed/n into
        lastServingSec while the single route stored wall clock."""
        base, _, _ = obs_server
        status, body, _ = _call(
            f"{base}/batch/queries.json", "POST",
            [{"x": i} for i in range(5)],
        )
        assert status == 200
        _, body, _ = _call(f"{base}/")
        info = json.loads(body)
        assert info["lastServingSec"] > 0
        assert info["lastBatchPerQuerySec"] > 0
        # wall clock of the whole batch >= 5x the per-query mean
        assert info["lastServingSec"] >= info["lastBatchPerQuerySec"] * 4.9

    def test_status_html_shows_both_latency_fields(self, obs_server):
        base, _, _ = obs_server
        req = urllib.request.Request(
            f"{base}/", headers={"Accept": "text/html"}
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            page = resp.read().decode()
        assert "Last Serving Time" in page
        assert "Last Batch Per-Query Time" in page


class TestShedCancellation:
    def test_partial_shed_cancels_accepted_futures(self, ctx):
        """Satellite: a multi-algorithm batch slot that sheds after
        some submits were accepted must cancel those futures (dropping
        them before device dispatch) instead of abandoning the work."""
        registry = MetricRegistry()
        release = threading.Event()
        # long fill window: the cancel races nothing
        ok = MicroBatcher(
            lambda items: items, max_wait_ms=500,
            registry=registry, name="ok",
        )

        class AlwaysOverloaded:
            """A batcher at its bound: the group it returns admitted
            no slot."""

            class Shed:
                admitted = 0

                def cancel(self, indices=None):
                    return 0

            def submit_group(self, items):
                return self.Shed()

        class FakeES:
            _shed_wasted = registry.counter(
                "pio_shed_wasted_dispatch_total", "h"
            )
            _abandon_slots = EngineServer._abandon_slots
            _submit_batch = EngineServer._submit_batch

        class PassthroughServing:
            def supplement(self, q):
                return q

        es = FakeES()
        try:
            entries, _groups, any_submitted = es._submit_batch(
                PassthroughServing(), [ok, AlwaysOverloaded()],
                [{"x": 1}],
            )
            assert entries[0][0] == "shed"
            assert any_submitted
            # the accepted slot was cancelled before dispatch: the
            # batcher counts it dropped, no device batch ever runs
            deadline = time.time() + 5
            cancelled = registry.counter(
                "pio_batch_cancelled_total", "", ("batcher",)
            ).labels("ok")
            while time.time() < deadline and cancelled.value < 1:
                time.sleep(0.02)
            assert cancelled.value == 1
            assert registry.counter(
                "pio_batches_total", "", ("batcher",)
            ).labels("ok").value == 0
        finally:
            release.set()
            ok.close()

    def test_uncancellable_future_counts_as_wasted(self):
        registry = MetricRegistry()

        class FakeES:
            _shed_wasted = registry.counter(
                "pio_shed_wasted_dispatch_total", "h"
            )
            _abandon = EngineServer._abandon

        f = Future()
        f.set_running_or_notify_cancel()  # dispatch already started
        FakeES()._abandon([f])
        assert registry.counter(
            "pio_shed_wasted_dispatch_total"
        ).value == 1


class TestMicroBatcherTelemetry:
    def test_dispatch_metrics_recorded(self):
        registry = MetricRegistry()
        b = MicroBatcher(
            lambda items: [i * 2 for i in items],
            max_batch=8, max_wait_ms=10, registry=registry, name="m",
        )
        futures = [b.submit(i) for i in range(20)]
        assert [f.result(5) for f in futures] == [
            i * 2 for i in range(20)
        ]
        b.close()
        data = registry.to_dict()
        occ = data["pio_batch_occupancy"]["samples"][0]
        assert occ["count"] >= 1
        assert occ["sum"] == 20  # occupancy sums to the item count
        assert data["pio_batches_total"]["samples"][0]["value"] >= 1
        assert (
            data["pio_device_dispatch_seconds"]["samples"][0]["count"]
            >= 1
        )

    def test_shed_counter(self):
        registry = MetricRegistry()
        release = threading.Event()
        b = MicroBatcher(
            lambda items: (release.wait(10), items)[1],
            max_batch=1, max_wait_ms=0.1, max_queue=1,
            registry=registry, name="shed",
        )
        try:
            b.submit(1)
            time.sleep(0.1)
            with pytest.raises(BatcherOverloaded):
                for _ in range(10):
                    b.submit(2)
            shed = registry.counter(
                "pio_batch_shed_total", "", ("batcher",)
            ).labels("shed")
            assert shed.value >= 1
        finally:
            release.set()
            b.close()

    def test_cancelled_slot_never_dispatches(self):
        registry = MetricRegistry()
        seen = []
        b = MicroBatcher(
            lambda items: (seen.extend(items), items)[1],
            max_batch=4, max_wait_ms=300, registry=registry, name="c",
        )
        try:
            keep = b.submit("keep")
            drop = b.submit("drop")
            assert drop.cancel()
            assert keep.result(5) == "keep"
            assert seen == ["keep"]
            assert registry.counter(
                "pio_batch_cancelled_total", "", ("batcher",)
            ).labels("c").value == 1
        finally:
            b.close()


class TestStepTimerPublish:
    def test_publish_folds_records_into_registry(self):
        registry = MetricRegistry()
        timer = StepTimer()
        timer.record("als/solve", 0.2)
        timer.record("als/solve", 0.4)
        timer.record("train/total", 1.0)
        timer.publish(registry)
        data = registry.to_dict()["pio_train_step_seconds"]
        solve = next(
            s for s in data["samples"]
            if s["labels"]["step"] == "als/solve"
        )
        assert solve["count"] == 2
        assert abs(solve["sum"] - 0.6) < 1e-6

    def test_run_train_publishes_to_global_registry(
        self, ctx, memory_storage
    ):
        run_train(
            _engine(), _params(), engine_id="obs-train", ctx=ctx,
            storage=memory_storage,
        )
        data = get_registry().to_dict()
        steps = data["pio_train_step_seconds"]["samples"]
        assert any(
            s["labels"]["step"] == "train/total" and s["count"] >= 1
            for s in steps
        )


class TestEventServerMetrics:
    def test_ingest_counters_and_scrape(self, memory_storage):
        from predictionio_tpu.data.storage import AccessKey, App
        from predictionio_tpu.serving.event_server import (
            create_event_server,
        )

        registry = MetricRegistry()
        apps = memory_storage.get_meta_data_apps()
        app_id = apps.insert(App(id=0, name="obsapp"))
        memory_storage.get_events().init(app_id)
        key = memory_storage.get_meta_data_access_keys().insert(
            AccessKey(key="obskey", appid=app_id)
        )
        http = create_event_server(
            host="127.0.0.1", port=0, storage=memory_storage,
            stats=True, registry=registry,
        )
        http.start()
        try:
            base = f"http://127.0.0.1:{http.port}"
            status, _, _ = _call(
                f"{base}/events.json?accessKey={key}", "POST",
                {"event": "view", "entityType": "user", "entityId": "u1"},
            )
            assert status == 201
            status, text, _ = _call(f"{base}/metrics")
            assert status == 200
            text = text.decode()
            # exactly 1: EventServer._count is the SINGLE mirroring
            # site — a second one (e.g. inside Stats) would read 2
            assert (
                "pio_events_ingested_total"
                f'{{app_id="{app_id}",status="201"}} 1' in text
            )
            # the legacy hourly view is preserved alongside
            status, body, _ = _call(
                f"{base}/stats.json?accessKey={key}"
            )
            assert status == 200
            assert json.loads(body)["statusCount"] == {"201": 1}
        finally:
            http.shutdown()


class TestOtherServerScrapes:
    def test_store_server_and_dashboard_expose_metrics(
        self, memory_storage
    ):
        from predictionio_tpu.serving.dashboard import create_dashboard
        from predictionio_tpu.serving.store_server import (
            create_store_server,
        )

        for factory in (create_store_server, create_dashboard):
            http = factory(
                host="127.0.0.1", port=0, storage=memory_storage,
                registry=MetricRegistry(),
            )
            http.start()
            try:
                base = f"http://127.0.0.1:{http.port}"
                status, _, _ = _call(f"{base}/")
                status, text, _ = _call(f"{base}/metrics")
                assert status == 200
                assert b"pio_http_requests_total" in text
                status, body, _ = _call(f"{base}/metrics.json")
                assert status == 200
                assert "pio_http_request_seconds" in json.loads(body)
            finally:
                http.shutdown()

    def test_dashboard_key_gates_debug_traces(self, memory_storage):
        """ISSUE 16 satellite: the dashboard mounts the shared
        telemetry surface — /metrics stays open (aggregates only) but
        /debug/traces carries per-request data and honors the server
        key like every other server."""
        from predictionio_tpu.serving.config import ServerConfig
        from predictionio_tpu.serving.dashboard import create_dashboard

        http = create_dashboard(
            host="127.0.0.1",
            port=0,
            storage=memory_storage,
            registry=MetricRegistry(),
            server_config=ServerConfig(
                key_auth_enforced=True, access_key="dash-key"
            ),
        )
        http.start()
        try:
            base = f"http://127.0.0.1:{http.port}"
            # the dashboard enforces its key server-wide (reference
            # KeyAuthentication mixes into the whole route tree)
            for path in ("/metrics", "/metrics.json", "/debug/traces"):
                status, _, _ = _call(f"{base}{path}")
                assert status == 401, path
            key = {"X-PIO-Server-Key": "dash-key"}
            status, text, _ = _call(f"{base}/metrics", headers=key)
            assert status == 200
            assert b"pio_http_requests_total" in text
            status, body, _ = _call(
                f"{base}/metrics.json", headers=key
            )
            assert status == 200
            assert "pio_http_request_seconds" in json.loads(body)
            status, body, _ = _call(
                f"{base}/debug/traces", headers=key
            )
            assert status == 200
            assert b"traceEvents" in body
        finally:
            http.shutdown()
