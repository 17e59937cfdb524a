"""Engine Server tests over a real socket: queries.json hot path,
micro-batching, reload, feedback loop (reference ServerActor behavior)."""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
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
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.serving.batching import MicroBatcher
from predictionio_tpu.serving.engine_server import EngineServer


@pytest.fixture(scope="module")
def ctx():
    return ComputeContext.create(batch="srv-test")


def _call(url, method="GET", body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


class DictQueryAlgorithm(FakeAlgorithm):
    """Fake algorithm answering dict queries (the server speaks JSON)."""

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
def server(ctx, memory_storage):
    run_train(
        _engine(), _params(), engine_id="srv", ctx=ctx,
        storage=memory_storage,
    )
    es = EngineServer(
        _engine(),
        _params(),
        engine_id="srv",
        storage=memory_storage,
        ctx=ctx,
        feedback=True,
        feedback_app_id=1,
    )
    memory_storage.get_events().init(1)
    http = es.serve(host="127.0.0.1", port=0)
    http.start()
    yield f"http://127.0.0.1:{http.port}", es, memory_storage
    http.shutdown()
    es.close()


class TestEngineServer:
    def test_status_page(self, server):
        base, _, _ = server
        status, body = _call(f"{base}/")
        assert status == 200
        assert body["engineId"] == "srv"
        assert body["requestCount"] == 0

    def test_query_hot_path(self, server):
        base, _, _ = server
        status, body = _call(
            f"{base}/queries.json", "POST", {"x": 7}
        )
        assert status == 200
        assert body["result"] == 37  # algo_id 3 → 30 + x
        _, info = _call(f"{base}/")
        assert info["requestCount"] == 1
        assert info["lastServingSec"] > 0

    def test_feedback_event_recorded_and_prid_injected(self, server):
        base, _, storage = server
        _, body = _call(f"{base}/queries.json", "POST", {"x": 1})
        assert "prId" in body
        events = list(
            storage.get_events().find(1, entity_type="pio_pr")
        )
        assert len(events) == 1
        assert events[0].event == "predict"
        assert events[0].properties["query"] == {"x": 1}

    def test_concurrent_queries_batched(self, server):
        base, es, _ = server
        results = [None] * 32

        def call(i):
            _, body = _call(f"{base}/queries.json", "POST", {"x": i})
            results[i] = body["result"]

        threads = [
            threading.Thread(target=call, args=(i,)) for i in range(32)
        ]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert results == [30 + i for i in range(32)]

    def test_reload_picks_latest(self, server, ctx, memory_storage):
        base, es, _ = server
        old_instance = es._instance.id
        run_train(
            _engine(), _params(), engine_id="srv", ctx=ctx,
            storage=memory_storage,
        )
        status, body = _call(f"{base}/reload", "POST")
        assert status == 200
        assert body["engineInstanceId"] != old_instance
        status, body = _call(f"{base}/queries.json", "POST", {"x": 2})
        assert body["result"] == 32

    def test_malformed_query(self, server):
        base, _, _ = server
        status, _ = _call(f"{base}/queries.json", "POST", [1, 2, 3])
        assert status == 400

    def test_html_status_page_content_negotiated(self, server):
        """GET / with Accept: text/html renders the status page
        (reference twirl index.scala.html); JSON stays the default."""
        base, _, _ = server
        req = urllib.request.Request(
            f"{base}/", headers={"Accept": "text/html"}
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.headers["Content-Type"] == "text/html"
            page = resp.read().decode()
        assert "<h1>Engine Server</h1>" in page
        assert "srv" in page
        assert "Engine Information" in page
        assert "Request Count" in page
        # default (no Accept preference) remains JSON
        status, body = _call(f"{base}/")
        assert status == 200 and body["status"] == "alive"


class TestBatchQueries:
    def test_batch_roundtrip_per_query_results(self, server):
        base, _, _ = server
        status, body = _call(
            f"{base}/batch/queries.json", "POST",
            [{"x": 1}, {"x": 2}, {"x": 3}],
        )
        assert status == 200
        assert [r["status"] for r in body] == [200, 200, 200]
        assert [r["prediction"]["result"] for r in body] == [31, 32, 33]

    def test_batch_matches_single_query_path(self, server):
        base, _, _ = server
        _, single = _call(f"{base}/queries.json", "POST", {"x": 9})
        _, batch = _call(
            f"{base}/batch/queries.json", "POST", [{"x": 9}]
        )
        # feedback injects a fresh prId per call; everything else equal
        single.pop("prId", None)
        got = batch[0]["prediction"]
        got.pop("prId", None)
        assert got == single

    def test_bad_slot_keeps_per_query_status(self, server):
        base, _, _ = server
        status, body = _call(
            f"{base}/batch/queries.json", "POST",
            [{"x": 1}, "not-a-query", {"x": 2}],
        )
        assert status == 200
        assert [r["status"] for r in body] == [200, 400, 200]
        assert "JSON object" in body[1]["message"]

    def test_non_array_rejected(self, server):
        base, _, _ = server
        status, body = _call(
            f"{base}/batch/queries.json", "POST", {"x": 1}
        )
        assert status == 400
        assert "array" in body["message"]

    def test_batch_limit(self, server):
        base, _, _ = server
        status, body = _call(
            f"{base}/batch/queries.json", "POST",
            [{"x": i} for i in range(101)],
        )
        assert status == 400
        assert "100" in body["message"]

    def test_batch_counts_toward_stats(self, server):
        base, _, _ = server
        _, before = _call(f"{base}/")
        _call(
            f"{base}/batch/queries.json", "POST",
            [{"x": i} for i in range(5)],
        )
        _, after = _call(f"{base}/")
        assert after["requestCount"] == before["requestCount"] + 5

    def test_empty_batch_is_empty_list(self, server):
        """[] on a fresh server must not divide by a zero request
        count in the stats update."""
        base, _, _ = server
        status, body = _call(f"{base}/batch/queries.json", "POST", [])
        assert status == 200 and body == []

    def test_supplement_error_stays_per_slot(self, server, monkeypatch):
        """A serving.supplement that rejects one query must produce a
        500 in THAT slot only — not reclassify the batch as a reload or
        abandon the other slots."""
        base, es, _ = server
        original = es._serving.supplement

        def picky(query):
            if query.get("x") == 13:
                raise ValueError("unlucky query")
            return original(query)

        monkeypatch.setattr(es._serving, "supplement", picky)
        status, body = _call(
            f"{base}/batch/queries.json", "POST",
            [{"x": 1}, {"x": 13}, {"x": 2}],
        )
        assert status == 200
        assert [r["status"] for r in body] == [200, 500, 200]
        assert "unlucky" in body[1]["message"]

    def test_batch_feedback_events_recorded(self, server):
        base, _, storage = server
        before = len(list(
            storage.get_events().find(1, entity_type="pio_pr")
        ))
        _, body = _call(
            f"{base}/batch/queries.json", "POST", [{"x": 1}, {"x": 2}]
        )
        assert all("prId" in r["prediction"] for r in body)
        after = len(list(
            storage.get_events().find(1, entity_type="pio_pr")
        ))
        assert after == before + 2


class _ParkedBatchFn:
    """A batcher's ``batch_fn`` for the group tests: the batch that
    starts with ``"plug"`` parks the collector until released, so what
    a post submits meanwhile stays queued; a batch holding ``x ==
    "boom"`` fails; answers are the fake engine's."""

    def __init__(self):
        self.parked = threading.Event()
        self.release = threading.Event()
        self.batches = []

    def __call__(self, items):
        if items[0] == "plug":
            self.parked.set()
            assert self.release.wait(10)
            return items
        self.batches.append([q["x"] for q in items])
        if any(q["x"] == "boom" for q in items):
            raise ValueError("injected batch failure")
        return [{"result": 30 + q["x"]} for q in items]

    def park(self, batcher):
        batcher.submit("plug")
        assert self.parked.wait(10)


class TestBatchPostAsGroups:
    """A post is one group a batcher (docs/serving.md "A post is one
    group"): the statuses stay per query."""

    def test_bad_entry_shed_tail_and_failed_batch_keep_their_slots(
        self, server
    ):
        base, es, _ = server
        fn = _ParkedBatchFn()
        batcher = MicroBatcher(fn, max_batch=2, max_wait_ms=1, max_queue=4)
        old, es._batchers = es._batchers, [batcher]
        submitted = threading.Event()
        admit = batcher.submit_group

        def spy(items):
            group = admit(items)
            submitted.set()
            return group

        batcher.submit_group = spy
        try:
            fn.park(batcher)
            answer = []
            post = threading.Thread(
                target=lambda: answer.append(_call(
                    f"{base}/batch/queries.json", "POST",
                    [{"x": 1}, "not-a-query", {"x": 2}, {"x": "boom"},
                     {"x": 4}, {"x": 5}, {"x": 6}],
                ))
            )
            post.start()
            assert submitted.wait(10)
            fn.release.set()
            post.join(15)
            status, body = answer[0]
        finally:
            fn.release.set()
            es._batchers = old
            batcher.close()
        assert status == 200
        # four fit the queue, the tail is shed; the second batch failed
        assert [r["status"] for r in body] == [
            200, 400, 200, 500, 500, 503, 503
        ]
        assert [body[i]["prediction"]["result"] for i in (0, 2)] == [31, 32]
        assert "injected" in body[3]["message"]
        assert "overloaded" in body[5]["message"]
        assert fn.batches == [[1, 2], ["boom", 4]]

    def test_second_batcher_shedding_abandons_the_firsts_matching_slots(
        self,
    ):
        """Two algorithms: a query is served only where both batchers
        admitted it, and what the first took of the rest is cancelled
        before its device sees it."""
        from predictionio_tpu.obs import MetricRegistry

        registry = MetricRegistry()
        first_fn, second_fn = _ParkedBatchFn(), _ParkedBatchFn()
        first = MicroBatcher(
            first_fn, max_batch=8, max_wait_ms=1,
            registry=registry, name="first",
        )
        second = MicroBatcher(
            second_fn, max_batch=8, max_wait_ms=1, max_queue=3,
            registry=registry, name="second",
        )

        class Seam:
            _shed_wasted = registry.counter(
                "pio_shed_wasted_dispatch_total", "h"
            )
            _abandon_slots = EngineServer._abandon_slots
            _submit_batch = EngineServer._submit_batch

        class Serving:
            def supplement(self, q):
                return q

        try:
            first_fn.park(first)
            second_fn.park(second)
            entries, groups, any_submitted = Seam()._submit_batch(
                Serving(), [first, second], [{"x": i} for i in range(5)]
            )
            assert [e[0] for e in entries] == ["ok"] * 3 + ["shed"] * 2
            assert [e[2] for e in entries[:3]] == [0, 1, 2]
            assert any_submitted
            assert [g.admitted for g in groups] == [5, 3]
            first_fn.release.set()
            second_fn.release.set()
            assert all(g.wait(10) for g in groups)
            assert [g.result(2) for g in groups] == [{"result": 32}] * 2
        finally:
            first_fn.release.set()
            second_fn.release.set()
            first.close()
            second.close()
        # exactly the two the second batcher shed never ran in the first
        assert first_fn.batches == [[0, 1, 2]]
        assert second_fn.batches == [[0, 1, 2]]
        cancelled = registry.counter(
            "pio_batch_cancelled_total", "", ("batcher",)
        )
        assert cancelled.labels("first").value == 2
        assert cancelled.labels("second").value == 0
        assert Seam._shed_wasted.value == 0


class TestBindAndUndeploy:
    def test_undeploy_before_deploy_stops_old_server(
        self, ctx, memory_storage
    ):
        """Second deploy on the same port posts /stop to the first and
        takes the port over (reference MasterActor StartServer →
        undeploy, CreateServer.scala:280-378)."""
        import time as _time

        run_train(
            _engine(), _params(), engine_id="srv", ctx=ctx,
            storage=memory_storage,
        )
        first = EngineServer(
            _engine(), _params(), engine_id="srv",
            storage=memory_storage, ctx=ctx, warmup=False,
        )
        http1 = first.serve(host="127.0.0.1", port=0)
        http1.start()
        port = http1.port
        second = EngineServer(
            _engine(), _params(), engine_id="srv",
            storage=memory_storage, ctx=ctx, warmup=False,
        )
        # bind_retries gives the old server time to release the socket
        http2 = second.serve(host="127.0.0.1", port=port)
        http2.start()
        try:
            status, body = _call(f"http://127.0.0.1:{port}/")
            assert status == 200 and body["status"] == "alive"
        finally:
            http2.shutdown()
            second.close()
            first.close()
        _time.sleep(0.1)

    def test_bind_retry_then_give_up(self, ctx, memory_storage, monkeypatch):
        """A port held by a non-engine process: undeploy fails, bind
        retries x3, then the original error surfaces."""
        import socket as _socket

        run_train(
            _engine(), _params(), engine_id="srv", ctx=ctx,
            storage=memory_storage,
        )
        blocker = _socket.socket()
        blocker.bind(("127.0.0.1", 0))
        blocker.listen(1)
        port = blocker.getsockname()[1]
        sleeps = []
        monkeypatch.setattr(
            "predictionio_tpu.serving.engine_server.time.sleep",
            sleeps.append,
        )
        es = EngineServer(
            _engine(), _params(), engine_id="srv",
            storage=memory_storage, ctx=ctx, warmup=False,
        )
        try:
            with pytest.raises(OSError):
                es.serve(
                    host="127.0.0.1", port=port, bind_retries=3,
                    undeploy_first=False,
                )
            assert len(sleeps) == 2  # 3 attempts → 2 backoffs
        finally:
            es.close()
            blocker.close()


class TestKeyAuthedAdminRoutes:
    """Key auth guards /stop and /reload but never /queries.json
    (reference: ServerActor mixes KeyAuthentication into the admin
    routes only)."""

    @pytest.fixture()
    def authed_server(self, ctx, memory_storage):
        from predictionio_tpu.serving.config import ServerConfig

        run_train(
            _engine(), _params(), engine_id="srv-auth", ctx=ctx,
            storage=memory_storage,
        )
        es = EngineServer(
            _engine(),
            _params(),
            engine_id="srv-auth",
            storage=memory_storage,
            ctx=ctx,
            server_config=ServerConfig(
                key_auth_enforced=True, access_key="topsecret"
            ),
        )
        http = es.serve(host="127.0.0.1", port=0)
        http.start()
        yield f"http://127.0.0.1:{http.port}"
        http.shutdown()
        es.close()

    def test_queries_stay_open(self, authed_server):
        status, body = _call(
            f"{authed_server}/queries.json", "POST", {"x": 5}
        )
        assert status == 200 and body["result"] == 35

    def test_reload_requires_key(self, authed_server):
        status, _ = _call(f"{authed_server}/reload", "POST")
        assert status == 401
        status, _ = _call(
            f"{authed_server}/reload?accessKey=topsecret", "POST"
        )
        assert status == 200

    def test_stop_requires_key(self, authed_server):
        status, _ = _call(f"{authed_server}/stop", "POST")
        assert status == 401


class TestMicroBatcher:
    def test_batches_and_results_in_order(self):
        seen_batches = []

        def batch_fn(items):
            seen_batches.append(len(items))
            return [i * 2 for i in items]

        b = MicroBatcher(batch_fn, max_batch=16, max_wait_ms=20)
        futures = [b.submit(i) for i in range(40)]
        assert [f.result(5) for f in futures] == [i * 2 for i in range(40)]
        assert sum(seen_batches) == 40
        assert max(seen_batches) > 1  # some coalescing happened
        b.close()

    def test_error_propagates_to_all(self):
        def bad(items):
            raise RuntimeError("boom")

        b = MicroBatcher(bad, max_batch=4, max_wait_ms=1)
        futures = [b.submit(i) for i in range(3)]
        for f in futures:
            with pytest.raises(RuntimeError, match="boom"):
                f.result(5)
        b.close()

    def test_wrong_result_count(self):
        b = MicroBatcher(lambda items: [1], max_batch=4, max_wait_ms=1)
        f1, f2 = b.submit("a"), b.submit("b")
        with pytest.raises(RuntimeError, match="results"):
            f1.result(5)
        b.close()

    def test_submit_after_close(self):
        b = MicroBatcher(lambda items: items)
        b.close()
        with pytest.raises(RuntimeError):
            b.submit(1)


class TestReviewRegressions:
    def test_graceful_close_serves_queued_items(self):
        import time

        def slow(items):
            time.sleep(0.05)
            return [i * 2 for i in items]

        b = MicroBatcher(slow, max_batch=2, max_wait_ms=1)
        futures = [b.submit(i) for i in range(10)]
        b.close()  # must drain, not abandon
        assert [f.result(5) for f in futures] == [i * 2 for i in range(10)]

    def test_query_during_reload_survives(self, server, ctx, memory_storage):
        base, es, _ = server
        run_train(
            _engine(), _params(), engine_id="srv", ctx=ctx,
            storage=memory_storage,
        )
        errors = []
        done = threading.Event()

        def hammer():
            while not done.is_set():
                status, body = _call(
                    f"{base}/queries.json", "POST", {"x": 1}
                )
                if status != 200:
                    errors.append((status, body))

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        [t.start() for t in threads]
        for _ in range(3):
            _call(f"{base}/reload", "POST")
        done.set()
        [t.join() for t in threads]
        assert errors == []


class TestLoadShedding:
    """Overload sheds at the queue-depth bound with a fast 503 instead
    of queueing into a predict-timeout hang (VERDICT r1 weak #7)."""

    def test_batcher_overload_raises(self):
        import threading

        from predictionio_tpu.serving.batching import (
            BatcherOverloaded,
            MicroBatcher,
        )

        release = threading.Event()

        def slow_fn(items):
            release.wait(timeout=10)
            return items

        b = MicroBatcher(
            slow_fn, max_batch=1, max_wait_ms=0.1, max_queue=3
        )
        try:
            futures = [b.submit(i) for i in range(3)]
            # worker holds one batch; queue fills to the bound
            import time

            time.sleep(0.1)
            b.submit(99)  # qsize dropped by the in-flight item
            with pytest.raises(BatcherOverloaded):
                for _ in range(10):
                    b.submit(100)
            release.set()
            for f in futures:
                f.result(timeout=10)
        finally:
            release.set()
            b.close()

    def test_overload_maps_to_503(self, ctx, memory_storage):
        import threading

        run_train(
            _engine(), _params(), engine_id="srv-shed", ctx=ctx,
            storage=memory_storage,
        )
        es = EngineServer(
            _engine(),
            _params(),
            engine_id="srv-shed",
            storage=memory_storage,
            ctx=ctx,
            max_batch=1,
            max_queue=1,
            warmup=False,
        )
        # swap in a batcher whose work blocks, then overfill it
        release = threading.Event()
        from predictionio_tpu.serving.batching import MicroBatcher

        slow = MicroBatcher(
            lambda items: (release.wait(10), items)[1],
            max_batch=1, max_wait_ms=0.1, max_queue=1,
        )
        es._batchers = [slow]
        http = es.serve(host="127.0.0.1", port=0)
        http.start()
        try:
            base = f"http://127.0.0.1:{http.port}"
            results = []

            def fire():
                results.append(
                    _call(f"{base}/queries.json", "POST", {"x": 1})[0]
                )

            threads = [
                threading.Thread(target=fire) for _ in range(6)
            ]
            for t in threads:
                t.start()
            import time

            time.sleep(0.3)
            release.set()
            for t in threads:
                t.join(timeout=15)
            assert 503 in results, results
        finally:
            release.set()
            http.shutdown()
            es.close()


class TestRemoteErrorLog:
    """--log-url (reference CreateServer.scala:446-457): serving
    failures POST a structured report to a remote collector."""

    def test_error_posts_to_log_url(self, ctx, memory_storage):
        import http.server
        import time

        received = []
        done = threading.Event()

        class Sink(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                length = int(self.headers.get("Content-Length") or 0)
                received.append(
                    (self.path, self.rfile.read(length))
                )
                self.send_response(200)
                self.end_headers()
                done.set()

            def log_message(self, *a):
                pass

        sink = http.server.HTTPServer(("127.0.0.1", 0), Sink)
        threading.Thread(target=sink.serve_forever, daemon=True).start()
        run_train(
            _engine(), _params(), engine_id="logsrv", ctx=ctx,
            storage=memory_storage,
        )
        es = EngineServer(
            _engine(),
            _params(),
            engine_id="logsrv",
            storage=memory_storage,
            ctx=ctx,
            log_url=f"http://127.0.0.1:{sink.server_port}/collect",
            log_prefix="pio-",
        )
        http_srv = es.serve(host="127.0.0.1", port=0)
        http_srv.start()
        try:
            base = f"http://127.0.0.1:{http_srv.port}"
            # a non-object query fails validation inside the handler
            status, _ = _call(f"{base}/queries.json", "POST", [1, 2])
            assert status == 400
            assert done.wait(5), "no report reached the collector"
            path, payload = received[0]
            assert path == "/collect"
            report = json.loads(payload)
            assert report["message"].startswith("pio-")
            assert report["engineInstance"]["engineId"] == "logsrv"
            assert json.loads(report["query"]) == [1, 2]
            # a good query must NOT log
            done.clear()
            status, _ = _call(f"{base}/queries.json", "POST", {"x": 1})
            assert status == 200
            time.sleep(0.3)
            assert len(received) == 1
        finally:
            http_srv.shutdown()
            es.close()
            sink.shutdown()

    def test_bad_log_url_fails_at_deploy(self, ctx, memory_storage):
        run_train(
            _engine(), _params(), engine_id="badlog", ctx=ctx,
            storage=memory_storage,
        )
        with pytest.raises(ValueError, match="log-url"):
            EngineServer(
                _engine(), _params(), engine_id="badlog",
                storage=memory_storage, ctx=ctx,
                log_url="collector.internal/log",  # missing scheme
            )

    def test_close_stops_sender_and_truncates_large_queries(
        self, ctx, memory_storage
    ):
        import time

        run_train(
            _engine(), _params(), engine_id="trunc", ctx=ctx,
            storage=memory_storage,
        )
        es = EngineServer(
            _engine(), _params(), engine_id="trunc",
            storage=memory_storage, ctx=ctx,
            log_url="http://127.0.0.1:9/collect",  # unreachable
        )
        sender = [
            t for t in threading.enumerate()
            if t.name == "remote-error-log"
        ]
        assert len(sender) == 1  # started once, at init
        es.close()
        deadline = time.time() + 5
        while time.time() < deadline and sender[0].is_alive():
            time.sleep(0.05)
        assert not sender[0].is_alive(), "sender did not stop on close"
        # oversized failing query: the queued report is bounded (the
        # sender is stopped, so the payload stays observable)
        class FakeReq:
            body = b"[" + b"1," * 100_000 + b"1]"
        es._post_remote_log(ValueError("boom"), FakeReq())
        payload = es._log_queue.get_nowait()
        assert len(payload) < 8192
        assert b'"queryTruncated": true' in payload


class TestWarmupTelemetry:
    """Warmup visibility (docs/observability.md): per-bucket compile
    wall time + a cold/warm gauge a scrape can read."""

    def test_warmup_records_bucket_times_and_complete_gauge(
        self, ctx, memory_storage
    ):
        from predictionio_tpu.obs import MetricRegistry

        run_train(
            _engine(), _params(), engine_id="srv-warm", ctx=ctx,
            storage=memory_storage,
        )
        registry = MetricRegistry()
        es = EngineServer(
            _engine(), _params(), engine_id="srv-warm",
            storage=memory_storage, ctx=ctx, warmup=True,
            max_batch=8, registry=registry,
        )
        try:
            data = registry.to_dict()
            assert (
                data["pio_warmup_complete"]["samples"][0]["value"] == 1
            )
            samples = [
                s for s in data["pio_warmup_seconds"]["samples"]
                if s["labels"]["batcher"] == "srv-warm/algo0"
            ]
            assert {s["labels"]["bucket"] for s in samples} == {
                "1", "2", "4", "8"
            }
            for s in samples:
                assert s["value"] >= 0
        finally:
            es.close()

    def test_warmup_disabled_reports_cold(self, ctx, memory_storage):
        from predictionio_tpu.obs import MetricRegistry

        run_train(
            _engine(), _params(), engine_id="srv-cold", ctx=ctx,
            storage=memory_storage,
        )
        registry = MetricRegistry()
        es = EngineServer(
            _engine(), _params(), engine_id="srv-cold",
            storage=memory_storage, ctx=ctx, warmup=False,
            registry=registry,
        )
        try:
            data = registry.to_dict()
            assert (
                data["pio_warmup_complete"]["samples"][0]["value"] == 0
            )
        finally:
            es.close()


class TwoPhaseDictAlgorithm(DictQueryAlgorithm):
    """Dict-query algorithm speaking the two-phase serving protocol."""

    launches = 0
    collects = 0

    def batch_predict_launch(self, model, queries):
        type(self).launches += 1
        return [self.predict(model, q) for q in queries]

    def batch_predict_collect(self, model, handle, queries):
        type(self).collects += 1
        assert len(handle) == len(queries)
        return handle


class TestTwoPhaseServing:
    def test_two_phase_algorithm_rides_the_pipeline(
        self, ctx, memory_storage
    ):
        """An algorithm overriding batch_predict_launch must be served
        through dispatch/collect, not the single-phase fallback."""
        engine = Engine(
            FakeDataSource, FakePreparator, TwoPhaseDictAlgorithm,
            DictServing,
        )
        run_train(
            engine, _params(), engine_id="srv-2p", ctx=ctx,
            storage=memory_storage,
        )
        es = EngineServer(
            engine, _params(), engine_id="srv-2p",
            storage=memory_storage, ctx=ctx, warmup=False,
        )
        TwoPhaseDictAlgorithm.launches = 0
        TwoPhaseDictAlgorithm.collects = 0
        try:
            out = es._batchers[0].submit({"x": 4}).result(5)
            assert out == {"result": 34}
            assert TwoPhaseDictAlgorithm.launches >= 1
            assert TwoPhaseDictAlgorithm.collects >= 1
        finally:
            es.close()

    def test_half_override_falls_back_to_single_phase(
        self, ctx, memory_storage, caplog
    ):
        """Overriding only batch_predict_launch must not wire a broken
        half-protocol into the pipeline — single-phase fallback with a
        load-time warning instead of per-request NotImplementedError."""

        class HalfAlgorithm(DictQueryAlgorithm):
            def batch_predict_launch(self, model, queries):
                return queries

        engine = Engine(
            FakeDataSource, FakePreparator, HalfAlgorithm, DictServing
        )
        run_train(
            engine, _params(), engine_id="srv-half", ctx=ctx,
            storage=memory_storage,
        )
        import logging

        with caplog.at_level(
            logging.WARNING, "predictionio_tpu.serving.engine_server"
        ):
            es = EngineServer(
                engine, _params(), engine_id="srv-half",
                storage=memory_storage, ctx=ctx, warmup=False,
            )
        try:
            assert any(
                "single-phase" in r.message for r in caplog.records
            )
            out = es._batchers[0].submit({"x": 2}).result(5)
            assert out == {"result": 32}
        finally:
            es.close()


def test_engine_server_refuses_pipeline_depth_zero(ctx, memory_storage):
    """At construction, not at a pool's first tenant load."""
    with pytest.raises(ValueError, match="serial batcher .* is gone"):
        EngineServer(
            _engine(), _params(), engine_id="srv-d0",
            storage=memory_storage, ctx=ctx, pipeline_depth=0,
        )


class TestWarmupFailureGauge:
    def test_all_failed_warmup_reports_cold(self, ctx, memory_storage):
        """pio_warmup_complete must stay 0 when every bucket compile
        failed — a traffic gate reading 1 would route load to a fully
        cold server."""
        from predictionio_tpu.obs import MetricRegistry

        class BrokenWarmup(DictQueryAlgorithm):
            def batch_predict(self, model, queries):
                raise RuntimeError("no shape compiles")

        engine = Engine(
            FakeDataSource, FakePreparator, BrokenWarmup, DictServing
        )
        run_train(
            engine, _params(), engine_id="srv-broken", ctx=ctx,
            storage=memory_storage,
        )
        registry = MetricRegistry()
        es = EngineServer(
            engine, _params(), engine_id="srv-broken",
            storage=memory_storage, ctx=ctx, warmup=True, max_batch=4,
            registry=registry,
        )
        try:
            data = registry.to_dict()
            assert (
                data["pio_warmup_complete"]["samples"][0]["value"] == 0
            )
        finally:
            es.close()


class TestCanaryCasRegressions:
    """PR 12 regression: canary-slot installs and clears happen under
    ``EngineServer._lock`` as a compare-and-set — a verdict applier
    finishing late must never clobber a newer canary, and close() must
    snapshot the canary + serving batchers in one locked step."""

    class _StubCanary:
        def __init__(self):
            self.closed = False
            self.staged = None
            self.retained = None

        def to_dict(self):
            return {"stub": True}

        def close(self):
            self.closed = True

    def test_late_verdict_never_clobbers_newer_canary(
        self, server, ctx, memory_storage
    ):
        _, es, _ = server
        newer, older = self._StubCanary(), self._StubCanary()
        es._canary = newer
        es._finish_canary(older)  # late applier from a prior reload
        assert es._canary is newer
        es._finish_canary(newer)  # the CURRENT canary clears normally
        assert es._canary is None

    def test_close_takes_and_clears_the_canary_snapshot(
        self, ctx, memory_storage
    ):
        run_train(
            _engine(), _params(), engine_id="srv-cas", ctx=ctx,
            storage=memory_storage,
        )
        es = EngineServer(
            _engine(), _params(), engine_id="srv-cas",
            storage=memory_storage, ctx=ctx,
        )
        canary = self._StubCanary()
        es._canary = canary
        es.close()
        assert canary.closed
        assert es._canary is None
