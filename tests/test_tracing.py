"""Span tracing tests (ISSUE 3): tracer primitives and bounds, the
disabled-tracer overhead contract, end-to-end traces through the engine
server (root HTTP span + linked batch-dispatch span, correct nesting),
the distributed event-server → store-server hop, the `pio-tpu trace`
CLI verb, the training timeline on disk, and the satellite fixes
(log_json reserved keys, build-info gauges, utils/profiling.trace)."""

import contextlib
import json
import logging
import time
import urllib.error
import urllib.request

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
from predictionio_tpu.cli.main import main as cli_main
from predictionio_tpu.obs import MetricRegistry, get_registry, set_request_id
from predictionio_tpu.obs import tracing
from predictionio_tpu.obs.context import log_json
from predictionio_tpu.obs.tracing import Tracer
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.serving.batching import MicroBatcher
from predictionio_tpu.serving.engine_server import EngineServer
from predictionio_tpu.utils import profiling
from predictionio_tpu.version import __version__


@pytest.fixture(scope="module")
def ctx():
    return ComputeContext.create(batch="tracing-test")


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


def _finished_trace(tracer, trace_id, duration, name="root"):
    """A finalized single-span trace with a controlled duration."""
    span = tracer.trace(name, trace_id=trace_id).__enter__()
    span.start = tracing.now() - duration
    span.__exit__(None, None, None)
    return span


def _assert_nested(trace, eps=5e-6):
    """Every child span lies within its parent's interval."""
    by_id = {s["spanId"]: s for s in trace["spans"]}
    checked = 0
    for s in trace["spans"]:
        parent = by_id.get(s["parentId"])
        if parent is None:
            continue
        assert s["start"] >= parent["start"] - eps, (s, parent)
        assert (
            s["start"] + s["durationMs"] / 1000
            <= parent["start"] + parent["durationMs"] / 1000 + eps
        ), (s, parent)
        checked += 1
    return checked


# -- tracer primitives -----------------------------------------------------


class TestTracer:
    def test_parenting_and_record(self):
        t = Tracer()
        with t.trace("root", trace_id="t1") as root:
            assert tracing.current_span() is root
            with tracing.span("child", foo="bar") as child:
                assert child.parent_id == root.span_id
                assert child.trace_id == "t1"
                with tracing.span("grandchild") as g:
                    assert g.parent_id == child.span_id
        assert tracing.current_span() is None
        data = t.to_dict()
        assert len(data["traces"]) == 1
        trace = data["traces"][0]
        assert trace["traceId"] == "t1"
        assert trace["root"] == "root"
        names = [s["name"] for s in trace["spans"]]
        # completion order; root last
        assert names == ["grandchild", "child", "root"]
        child = next(s for s in trace["spans"] if s["name"] == "child")
        assert child["attributes"]["foo"] == "bar"
        assert _assert_nested(trace) == 2

    def test_exception_sets_error_attribute(self):
        t = Tracer()
        with pytest.raises(ValueError):
            with t.trace("root", trace_id="terr"):
                raise ValueError("boom")
        trace = t.to_dict()["traces"][0]
        root = trace["spans"][-1]
        assert "ValueError: boom" in root["attributes"]["error"]

    def test_span_off_trace_is_shared_noop(self):
        assert tracing.current_span() is None
        assert tracing.span("orphan") is tracing.NOOP

    def test_disabled_tracer_is_shared_noop(self):
        t = Tracer(enabled=False)
        assert t.trace("x") is tracing.NOOP
        with t.trace("x") as sp:
            assert sp is None
        assert t.to_dict() == {
            "traces": [], "flight": [], "abandonedOpenTraces": 0,
        }

    def test_ring_buffer_bounded_and_flight_keeps_slowest(self):
        t = Tracer(max_traces=2, flight_slots=2)
        _finished_trace(t, "slow1", 0.5)
        _finished_trace(t, "fast1", 0.001)
        _finished_trace(t, "slow2", 0.6)
        _finished_trace(t, "fast2", 0.002)
        _finished_trace(t, "fast3", 0.003)
        data = t.to_dict()
        assert [x["traceId"] for x in data["traces"]] == ["fast2", "fast3"]
        # flight recorder retained the two slowest, slowest first,
        # even though the ring long evicted them
        assert [x["traceId"] for x in data["flight"]] == ["slow2", "slow1"]
        # the merged view serves both
        merged = {x["traceId"] for x in t.traces()}
        assert merged == {"fast2", "fast3", "slow2", "slow1"}

    def test_span_cap_drops_children_never_root(self):
        t = Tracer(max_spans_per_trace=3)
        with t.trace("root", trace_id="cap"):
            for i in range(5):
                with tracing.span(f"c{i}"):
                    pass
        trace = t.to_dict()["traces"][0]
        assert trace["droppedSpans"] == 2
        names = [s["name"] for s in trace["spans"]]
        assert names == ["c0", "c1", "c2", "root"]

    def test_orphan_record_is_dropped(self):
        t = Tracer()
        s = tracing.Span(t, "ghost", "never-opened")
        t.record(s)
        assert t.to_dict() == {
            "traces": [], "flight": [], "abandonedOpenTraces": 0,
        }

    def test_open_trace_cap_abandons_oldest_and_counts(self):
        t = Tracer(max_open_traces=2)
        a = t.trace("a", trace_id="a").__enter__()
        b = t.trace("b", trace_id="b").__enter__()
        c = t.trace("c", trace_id="c").__enter__()  # evicts a
        c.__exit__(None, None, None)
        b.__exit__(None, None, None)
        a.__exit__(None, None, None)  # its buf is gone — no trace
        data = t.to_dict()
        assert {x["traceId"] for x in data["traces"]} == {"b", "c"}
        assert data["abandonedOpenTraces"] == 1

    def test_same_trace_id_trees_do_not_collide(self):
        """Two local trees of one distributed trace (e.g. event server
        and store server sharing a process tracer) finalize separately."""
        t = Tracer()
        a = t.trace("eventserver POST", trace_id="shared").__enter__()
        # second root with the SAME trace id opens while the first is
        # still in flight
        b = t.trace("storeserver GET", trace_id="shared").__enter__()
        b.__exit__(None, None, None)
        a.__exit__(None, None, None)
        traces = t.to_dict()["traces"]
        assert len(traces) == 2
        assert {x["traceId"] for x in traces} == {"shared"}
        assert {x["root"] for x in traces} == {
            "eventserver POST", "storeserver GET"
        }

    def test_chrome_trace_shape_and_filter(self):
        t = Tracer()
        with t.trace("root", trace_id="ct1"):
            with tracing.span("child"):
                pass
        _finished_trace(t, "ct2", 0.01)
        full = t.chrome_trace()
        assert full["displayTimeUnit"] == "ms"
        events = full["traceEvents"]
        metas = [e for e in events if e["ph"] == "M"]
        spans = [e for e in events if e["ph"] == "X"]
        assert len(metas) == 2  # one process per trace
        assert len(spans) == 3
        for e in spans:
            assert isinstance(e["ts"], float)
            assert isinstance(e["dur"], float)
            assert e["args"]["traceId"] in ("ct1", "ct2")
        only = t.chrome_trace(trace_id="ct2")
        assert all(
            e["args"]["traceId"] == "ct2"
            for e in only["traceEvents"]
            if e["ph"] == "X"
        )

    def test_overlapping_siblings_get_distinct_tracks(self):
        """Perfetto's slice stack requires strict nesting per track —
        concurrent per-algorithm dispatch spans that partially overlap
        must land on separate tids; nested spans share one."""

        def span(name, start, dur_ms):
            return {"name": name, "start": start, "durationMs": dur_ms}

        lanes = {
            s["name"]: tid
            for s, tid in tracing._assign_lanes(
                [
                    span("root", 0.0, 100.0),
                    span("a", 0.010, 30.0),       # nests in root
                    span("b", 0.025, 40.0),       # overlaps a partially
                    span("inner", 0.012, 5.0),    # nests in a
                    span("later", 0.070, 10.0),   # after a and b ended
                ]
            )
        }
        assert lanes["root"] == lanes["a"] == lanes["inner"] == 1
        assert lanes["b"] == 2
        assert lanes["later"] == 1

    def test_sanitize_id(self):
        assert tracing.sanitize_id("abc-123.X:ok") == "abc-123.X:ok"
        assert tracing.sanitize_id(None) is None
        assert tracing.sanitize_id("") is None
        assert tracing.sanitize_id("bad id\n") is None
        assert tracing.sanitize_id("x" * 200) is None


class TestDisabledOverhead:
    def test_batcher_hot_path_pays_one_contextvar_read(self, monkeypatch):
        """Acceptance: with no open trace, submit() costs exactly one
        contextvar read (current_span) — no Span objects, no clock
        anchor, no recorder traffic."""
        calls = {"current_span": 0, "span_init": 0, "now": 0}
        real_current = tracing.current_span

        def counting_current():
            calls["current_span"] += 1
            return real_current()

        real_init = tracing.Span.__init__

        def counting_init(self, *a, **kw):
            calls["span_init"] += 1
            return real_init(self, *a, **kw)

        real_now = tracing.now

        def counting_now():
            calls["now"] += 1
            return real_now()

        monkeypatch.setattr(tracing, "current_span", counting_current)
        monkeypatch.setattr(tracing.Span, "__init__", counting_init)
        monkeypatch.setattr(tracing, "now", counting_now)
        assert tracing.current_span() is None  # no open trace here
        calls["current_span"] = 0
        b = MicroBatcher(lambda items: items, max_batch=4, max_wait_ms=5)
        try:
            futures = [b.submit(i) for i in range(8)]
            assert [f.result(5) for f in futures] == list(range(8))
        finally:
            b.close()
        assert calls["current_span"] == 8
        assert calls["span_init"] == 0
        assert calls["now"] == 0

    def test_debug_routes_key_authed_on_open_server(
        self, memory_storage
    ):
        """Traces carry per-request data: once an operator configures a
        server key, the /debug routes on an otherwise-open event server
        must require it (the event API keeps its per-app keys)."""
        import dataclasses

        from predictionio_tpu.serving.config import ServerConfig
        from predictionio_tpu.serving.event_server import (
            create_event_server,
        )

        config = dataclasses.replace(
            ServerConfig.from_env(),
            key_auth_enforced=True,
            access_key="opskey",
        )
        http = create_event_server(
            host="127.0.0.1", port=0, storage=memory_storage,
            registry=MetricRegistry(), tracer=Tracer(),
            server_config=config,
        )
        http.start()
        try:
            base = f"http://127.0.0.1:{http.port}"
            for route in ("/debug/traces", "/debug/traces.json"):
                status, _, _ = _call(f"{base}{route}")
                assert status == 401
                status, _, _ = _call(
                    f"{base}{route}",
                    headers={"X-PIO-Server-Key": "opskey"},
                )
                assert status == 200
            # the event API and aggregate metrics stay reachable
            status, _, _ = _call(f"{base}/")
            assert status == 200
            status, _, _ = _call(f"{base}/metrics")
            assert status == 200
        finally:
            http.shutdown()

    def test_disabled_http_server_serves_untraced(self, memory_storage):
        from predictionio_tpu.serving.store_server import (
            create_store_server,
        )

        tracer = Tracer(enabled=False)
        http = create_store_server(
            host="127.0.0.1", port=0, storage=memory_storage,
            registry=MetricRegistry(), tracer=tracer,
        )
        http.start()
        try:
            base = f"http://127.0.0.1:{http.port}"
            status, _, _ = _call(f"{base}/meta/apps")
            assert status == 200
            status, body, _ = _call(f"{base}/debug/traces.json")
            assert status == 200
            assert json.loads(body) == {
                "traces": [], "flight": [], "abandonedOpenTraces": 0,
            }
        finally:
            http.shutdown()


# -- satellites ------------------------------------------------------------


class TestLogJsonReservedKeys:
    def test_caller_fields_cannot_shadow(self, caplog):
        logger = logging.getLogger("test.reserved")
        set_request_id("rid-keep")
        with caplog.at_level(logging.INFO, logger="test.reserved"):
            log_json(
                logger, logging.INFO, "real_event",
                event="spoof", ts=-1, requestId="spoof", other=7,
            )
        rec = json.loads(caplog.records[-1].message)
        assert rec["event"] == "real_event"
        assert rec["requestId"] == "rid-keep"
        assert rec["ts"] > 0
        # colliding fields survive, re-keyed
        assert rec["event_"] == "spoof"
        assert rec["ts_"] == -1
        assert rec["requestId_"] == "spoof"
        assert rec["other"] == 7


class TestProcessMetrics:
    def test_build_info_and_start_time_on_default_registry(self):
        data = get_registry().to_dict()
        info = data["pio_build_info"]["samples"][0]
        assert info["labels"]["version"] == __version__
        assert info["value"] == 1
        start = data["pio_process_start_time_seconds"]["samples"][0]
        assert 0 < start["value"] <= time.time()

    def test_rendered_in_prometheus_text(self):
        text = get_registry().render_prometheus()
        assert f'pio_build_info{{version="{__version__}"}} 1' in text
        assert "pio_process_start_time_seconds" in text


class TestProfilingTrace:
    """utils/profiling.trace coverage (previously untested): the
    PIO_TRACE_DIR env path, the no-op path, directory creation."""

    @pytest.fixture()
    def profiler_calls(self, monkeypatch):
        calls = []

        def fake_trace(trace_dir, profiler_options=None):
            # Python's own tracer is off in every trace the package takes
            assert profiler_options.python_tracer_level == 0
            calls.append(trace_dir)
            return contextlib.nullcontext()

        monkeypatch.setattr(
            profiling.jax.profiler, "trace", fake_trace
        )
        return calls

    def test_noop_without_dir_or_env(self, monkeypatch, profiler_calls):
        monkeypatch.delenv("PIO_TRACE_DIR", raising=False)
        with profiling.trace():
            pass
        assert profiler_calls == []

    def test_env_dir_used_and_created(
        self, monkeypatch, tmp_path, profiler_calls
    ):
        target = tmp_path / "traces" / "nested"
        monkeypatch.setenv("PIO_TRACE_DIR", str(target))
        with profiling.trace():
            pass
        assert profiler_calls == [str(target)]
        assert target.is_dir()

    def test_explicit_dir_wins_over_env(
        self, monkeypatch, tmp_path, profiler_calls
    ):
        monkeypatch.setenv("PIO_TRACE_DIR", str(tmp_path / "env"))
        explicit = tmp_path / "explicit"
        with profiling.trace(str(explicit)):
            pass
        assert profiler_calls == [str(explicit)]
        assert explicit.is_dir()
        assert not (tmp_path / "env").exists()


# -- engine server end to end ----------------------------------------------


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
def traced_server(ctx, memory_storage):
    tracer = Tracer()
    run_train(
        _engine(), _params(), engine_id="tr", ctx=ctx,
        storage=memory_storage,
    )
    es = EngineServer(
        _engine(),
        _params(),
        engine_id="tr",
        storage=memory_storage,
        ctx=ctx,
        warmup=False,
        registry=MetricRegistry(),
        tracer=tracer,
    )
    http = es.serve(host="127.0.0.1", port=0)
    http.start()
    yield f"http://127.0.0.1:{http.port}", es, tracer
    http.shutdown()
    es.close()


class TestEngineServerTrace:
    def test_e2e_query_trace_with_linked_dispatch_span(
        self, traced_server
    ):
        """Acceptance: a query with an inbound X-Request-ID yields one
        trace holding the root HTTP span, a batch_dispatch span linked
        to the query span it coalesced, and strict parent/child timing
        nesting."""
        base, _es, _tracer = traced_server
        status, _, headers = _call(
            f"{base}/queries.json", "POST", {"x": 7},
            headers={"X-Request-ID": "e2e-trace-1"},
        )
        assert status == 200
        assert headers["X-Request-ID"] == "e2e-trace-1"

        status, body, _ = _call(f"{base}/debug/traces.json")
        assert status == 200
        traces = [
            t for t in json.loads(body)["traces"]
            if t["traceId"] == "e2e-trace-1"
        ]
        assert len(traces) == 1
        trace = traces[0]
        root = next(s for s in trace["spans"] if s["parentId"] is None)
        assert root["name"] == "engine POST"
        assert root["attributes"]["route"] == "/queries.json"
        assert root["attributes"]["status"] == 200
        dispatch = next(
            s for s in trace["spans"] if s["name"] == "batch_dispatch"
        )
        # linked to the coalesced query span (= the root it rode under)
        assert dispatch["parentId"] == root["spanId"]
        assert (
            f"e2e-trace-1:{root['spanId']}"
            in dispatch["attributes"]["links"]
        )
        assert dispatch["attributes"]["occupancy"] >= 1
        assert dispatch["attributes"]["queueWaitMs"] >= 0
        assert dispatch["attributes"]["batcher"] == "tr/algo0"
        # every child fits inside its parent's interval
        assert _assert_nested(trace) >= 1

    def test_batch_queries_dedupe_dispatch_spans(self, traced_server):
        """A /batch/queries.json request submits many slots under ONE
        span — the dispatch must record one copy per distinct parent
        (with deduped links), not one per slot."""
        base, _es, _tracer = traced_server
        status, _, _ = _call(
            f"{base}/batch/queries.json", "POST",
            [{"x": i} for i in range(10)],
            headers={"X-Request-ID": "batch-trace-1"},
        )
        assert status == 200
        status, body, _ = _call(f"{base}/debug/traces.json")
        trace = next(
            t for t in json.loads(body)["traces"]
            if t["traceId"] == "batch-trace-1"
        )
        root = next(s for s in trace["spans"] if s["parentId"] is None)
        dispatches = [
            s for s in trace["spans"] if s["name"] == "batch_dispatch"
        ]
        assert dispatches
        link = f"batch-trace-1:{root['spanId']}"
        for d in dispatches:
            assert d["parentId"] == root["spanId"]
            assert d["attributes"]["links"] == [link]
        # every slot rode in exactly one dispatch
        assert sum(
            d["attributes"]["occupancy"] for d in dispatches
        ) == 10
        _assert_nested(trace)

    def test_debug_traces_is_perfetto_valid(self, traced_server):
        base, _es, _tracer = traced_server
        _call(f"{base}/queries.json", "POST", {"x": 1})
        status, body, _ = _call(f"{base}/debug/traces")
        assert status == 200
        data = json.loads(body)
        events = data["traceEvents"]
        assert events
        spans = [e for e in events if e["ph"] == "X"]
        assert spans
        for e in spans:
            assert isinstance(e["name"], str)
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float))
            assert isinstance(e["pid"], int)
            assert isinstance(e["tid"], int)

    def test_scrape_survives_non_serializable_attribute(
        self, traced_server
    ):
        """Span attributes are caller-supplied; one numpy scalar or
        object must not make the recorder unscrapeable (the payload
        write happens outside the handler error boundary)."""
        base, _es, tracer = traced_server
        circular: list = []
        circular.append(circular)
        with tracer.trace("weird", trace_id="weird-1") as sp:
            sp.set("payload", object())
            sp.set("shards", {(0, 1): "tuple-keyed"})
            sp.set("loop", circular)
        for route in ("/debug/traces", "/debug/traces.json"):
            status, body, _ = _call(f"{base}{route}")
            assert status == 200
            assert "weird-1" in body.decode()
            json.loads(body)  # still valid JSON

    def test_scrape_routes_are_not_traced(self, traced_server):
        base, _es, tracer = traced_server
        for _ in range(3):
            _call(f"{base}/metrics")
            _call(f"{base}/debug/traces")
            _call(f"{base}/debug/traces.json")
        routes = {
            s["attributes"].get("route")
            for t in tracer.to_dict()["traces"]
            for s in t["spans"]
        }
        assert not any(
            r and (r.startswith("/metrics") or r.startswith("/debug/"))
            for r in routes
        )

    def test_flight_recorder_survives_ring_eviction(
        self, ctx, memory_storage
    ):
        """The slowest request outlives max_traces' worth of fast
        ones — that is the flight recorder's whole job."""
        tracer = Tracer(max_traces=4, flight_slots=2)
        run_train(
            _engine(), _params(), engine_id="fl", ctx=ctx,
            storage=memory_storage,
        )

        class SlowOnce(DictQueryAlgorithm):
            def batch_predict(self, model, queries):
                if any(q.get("slow") for q in queries):
                    time.sleep(0.2)
                return [self.predict(model, q) for q in queries]

        es = EngineServer(
            Engine(
                FakeDataSource, FakePreparator, SlowOnce, DictServing
            ),
            _params(),
            engine_id="fl",
            storage=memory_storage,
            ctx=ctx,
            warmup=False,
            registry=MetricRegistry(),
            tracer=tracer,
        )
        http = es.serve(host="127.0.0.1", port=0)
        http.start()
        try:
            base = f"http://127.0.0.1:{http.port}"
            _call(
                f"{base}/queries.json", "POST", {"x": 1, "slow": 1},
                headers={"X-Request-ID": "the-straggler"},
            )
            for i in range(8):
                _call(f"{base}/queries.json", "POST", {"x": i})
            data = json.loads(
                _call(f"{base}/debug/traces.json")[1]
            )
            assert all(
                t["traceId"] != "the-straggler" for t in data["traces"]
            ), "straggler should have been evicted from the ring"
            assert any(
                t["traceId"] == "the-straggler" for t in data["flight"]
            )
        finally:
            http.shutdown()
            es.close()


class TestDistributedTrace:
    def test_event_to_store_hop_shares_one_trace_id(self, tmp_path):
        """Acceptance: an event-server request whose storage lives
        behind the store server produces spans in BOTH servers under
        the inbound X-Request-ID, with the store-server root parented
        to the event server's httpstore client span."""
        from predictionio_tpu.data.storage import (
            AccessKey, App, Storage,
        )
        from predictionio_tpu.serving.event_server import (
            create_event_server,
        )
        from predictionio_tpu.serving.store_server import (
            create_store_server,
        )

        backing = Storage(
            env={
                "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
            }
        )
        store_tracer = Tracer()
        store_http = create_store_server(
            host="127.0.0.1", port=0, storage=backing,
            registry=MetricRegistry(), tracer=store_tracer,
        )
        store_http.start()
        event_tracer = Tracer()
        try:
            app_id = backing.get_meta_data_apps().insert(
                App(id=0, name="hopapp")
            )
            backing.get_meta_data_access_keys().insert(
                AccessKey(key="hopkey", appid=app_id)
            )
            es_storage = Storage(
                env={
                    "PIO_STORAGE_SOURCES_STORE_TYPE": "httpstore",
                    "PIO_STORAGE_SOURCES_STORE_URL":
                        f"http://127.0.0.1:{store_http.port}",
                    "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
                    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "STORE",
                    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
                    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
                }
            )
            es_storage.get_events().init(app_id)
            event_http = create_event_server(
                host="127.0.0.1", port=0, storage=es_storage,
                registry=MetricRegistry(), tracer=event_tracer,
            )
            event_http.start()
            try:
                base = f"http://127.0.0.1:{event_http.port}"
                status, _, headers = _call(
                    f"{base}/events.json?accessKey=hopkey", "POST",
                    {
                        "event": "view",
                        "entityType": "user",
                        "entityId": "u1",
                    },
                    headers={"X-Request-ID": "hop-1"},
                )
                assert status == 201
                assert headers["X-Request-ID"] == "hop-1"
            finally:
                event_http.shutdown()

            ev_traces = [
                t for t in event_tracer.to_dict()["traces"]
                if t["traceId"] == "hop-1"
            ]
            assert len(ev_traces) == 1
            ev_spans = ev_traces[0]["spans"]
            names = [s["name"] for s in ev_spans]
            assert "eventserver POST" in names
            assert "store/get_access_key" in names
            assert "store/insert_event" in names
            client_spans = [
                s for s in ev_spans if s["name"].startswith("httpstore ")
            ]
            assert client_spans, names

            # the store server recorded the SAME trace id end-to-end,
            # rooted under the event server's outbound client span
            st_traces = [
                t for t in store_tracer.to_dict()["traces"]
                if t["traceId"] == "hop-1"
            ]
            assert st_traces, store_tracer.to_dict()["traces"]
            ev_span_ids = {s["spanId"] for s in ev_spans}
            for t in st_traces:
                root = next(
                    s for s in t["spans"] if s["name"] == "storeserver GET"
                )
                assert root["parentId"] in ev_span_ids
            dao_names = {
                s["name"] for t in st_traces for s in t["spans"]
            }
            assert "dao/access_keys.get" in dao_names
        finally:
            store_http.shutdown()


class TestCLITrace:
    def test_trace_verb_writes_perfetto_file(
        self, traced_server, tmp_path, capsys
    ):
        base, _es, _tracer = traced_server
        _call(f"{base}/queries.json", "POST", {"x": 2})
        out = tmp_path / "trace.json"
        rc = cli_main(["trace", "--url", base, "--out", str(out)])
        assert rc == 0
        assert "perfetto" in capsys.readouterr().out.lower()
        data = json.loads(out.read_text())
        assert data["traceEvents"]

    def test_trace_verb_raw(self, traced_server, tmp_path):
        base, _es, _tracer = traced_server
        _call(f"{base}/queries.json", "POST", {"x": 2})
        out = tmp_path / "raw.json"
        rc = cli_main(
            ["trace", "--url", base, "--out", str(out), "--raw"]
        )
        assert rc == 0
        assert json.loads(out.read_text())["traces"]

    def test_trace_verb_key_authed_server(
        self, memory_storage, tmp_path, capsys
    ):
        """--access-key travels as the X-PIO-Server-Key header (query
        strings leak into proxy/access logs)."""
        import dataclasses

        from predictionio_tpu.serving.config import ServerConfig
        from predictionio_tpu.serving.store_server import (
            create_store_server,
        )

        config = dataclasses.replace(
            ServerConfig.from_env(),
            key_auth_enforced=True,
            access_key="sekret",
        )
        http = create_store_server(
            host="127.0.0.1", port=0, storage=memory_storage,
            server_config=config, registry=MetricRegistry(),
            tracer=Tracer(),
        )
        http.start()
        try:
            base = f"http://127.0.0.1:{http.port}"
            out = tmp_path / "authed.json"
            rc = cli_main(
                [
                    "trace", "--url", base, "--out", str(out),
                    "--access-key", "sekret",
                ]
            )
            assert rc == 0
            assert "traceEvents" in json.loads(out.read_text())
            # without the key: clean [ERROR], no traceback, no leak
            rc = cli_main(
                ["trace", "--url", base, "--out", str(out)]
            )
            assert rc == 1
            err = capsys.readouterr().err
            assert "[ERROR]" in err
            assert "sekret" not in err
        finally:
            http.shutdown()

    def test_trace_verb_unreachable_url(self, tmp_path, capsys):
        rc = cli_main(
            [
                "trace", "--url", "http://127.0.0.1:9",
                "--out", str(tmp_path / "x.json"),
            ]
        )
        assert rc == 1
        assert "[ERROR]" in capsys.readouterr().err


class TestTrainTimeline:
    def test_run_train_writes_trace_file(
        self, ctx, memory_storage, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("PIO_TRACE_DIR", str(tmp_path))
        # keep the jax device profiler out of it — this test is about
        # the span timeline
        monkeypatch.setattr(
            profiling.jax.profiler,
            "trace",
            lambda d, **options: contextlib.nullcontext(),
        )
        instance_id = run_train(
            _engine(), _params(), engine_id="tl", ctx=ctx,
            storage=memory_storage,
        )
        path = tmp_path / f"pio_train_{instance_id}.trace.json"
        assert path.exists()
        data = json.loads(path.read_text())
        names = {
            e["name"] for e in data["traceEvents"] if e["ph"] == "X"
        }
        assert "pio_train" in names
        assert "train/total" in names
        assert "train/persist_model" in names
        # all events belong to this run's trace
        assert all(
            e["args"]["traceId"] == instance_id
            for e in data["traceEvents"]
            if e["ph"] == "X"
        )

    def test_failed_train_still_writes_trace(
        self, ctx, memory_storage, tmp_path, monkeypatch
    ):
        """The timeline of a FAILED run is the one most worth keeping —
        the write must happen on the failure path too."""
        monkeypatch.setenv("PIO_TRACE_DIR", str(tmp_path))
        monkeypatch.setattr(
            profiling.jax.profiler,
            "trace",
            lambda d, **options: contextlib.nullcontext(),
        )
        params = EngineParams(
            data_source=("", FakeParams(id=1, error=True)),
            preparator=("", FakeParams(id=2)),
            algorithms=[("", FakeParams(id=3))],
            serving=("", FakeParams()),
        )
        with pytest.raises(ValueError):
            run_train(
                _engine(), params, engine_id="tlfail", ctx=ctx,
                storage=memory_storage,
            )
        files = list(tmp_path.glob("pio_train_*.trace.json"))
        assert len(files) == 1
        data = json.loads(files[0].read_text())
        root = next(
            e for e in data["traceEvents"]
            if e["ph"] == "X" and e["name"] == "pio_train"
        )
        assert "ValueError" in root["args"]["error"]
