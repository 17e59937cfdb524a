"""Fleet observability plane tests (ISSUE 16): metrics federation
merge/render, the SLO burn-rate monitor, device telemetry, and the
on-demand profile capture endpoint."""

import base64
import io
import json
import tarfile
import threading
import time
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.obs import MetricRegistry
from predictionio_tpu.obs import federation as fed
from predictionio_tpu.obs import timeline as timeline_mod
from predictionio_tpu.obs.device import CompileTracker, DeviceSampler
from predictionio_tpu.obs.slo import (
    CRITICAL,
    DEFAULT,
    SHEDDABLE,
    Objective,
    SLOMonitor,
    objectives_from_env,
)
from predictionio_tpu.serving.http import HTTPServer, Response, Router
from predictionio_tpu.serving.router import ServingRouter


def _call(url, method="GET", body=None, headers=None, timeout=10):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method, headers=headers or {}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# -- merge functions -------------------------------------------------------


def _registry_payload(observations, counter_incs=0):
    """A real registry's /metrics.json dict with one histogram and one
    counter — merges are tested against genuine snapshots, not
    hand-built dicts."""
    reg = MetricRegistry()
    hist = reg.histogram("t_seconds", buckets=(0.1, 0.5, 1.0, 5.0))
    for value in observations:
        hist.observe(value)
    c = reg.counter("t_total")
    if counter_incs:
        c.inc(counter_incs)
    return reg.to_dict()


class TestMergeFunctions:
    def test_histogram_merge_equals_union(self):
        """The merged histogram is indistinguishable from observing
        the union of samples into one registry: same count, same
        buckets, same derived percentiles (exactness — never averaged
        percentiles)."""
        xs = [0.05] * 40 + [0.3] * 30 + [0.7] * 5
        ys = [0.05] * 10 + [0.9] * 10 + [4.0] * 4 + [9.0]
        a = _registry_payload(xs)["t_seconds"]["samples"][0]
        b = _registry_payload(ys)["t_seconds"]["samples"][0]
        union = _registry_payload(xs + ys)["t_seconds"]["samples"][0]
        merged = fed.merge_histogram_samples([a, b])
        assert merged["count"] == union["count"] == len(xs) + len(ys)
        assert merged["buckets"] == union["buckets"]
        for q in ("p50", "p95", "p99"):
            assert merged[q] == union[q]
        assert merged["sum"] == pytest.approx(sum(xs) + sum(ys))

    def test_histogram_merge_reconstructs_missing_inf_bucket(self):
        # a pre-+Inf snapshot (old replica): overflow comes back as
        # count - sum(finite)
        a = _registry_payload([0.05, 9.0, 9.0])["t_seconds"]["samples"][0]
        legacy = dict(a)
        legacy["buckets"] = {
            k: v for k, v in a["buckets"].items() if k != "+Inf"
        }
        merged = fed.merge_histogram_samples([legacy])
        assert merged["buckets"]["+Inf"] == 2
        assert merged["count"] == 3

    def test_counter_merge_sums_and_gauges_drop(self):
        payloads = {
            "r0": _registry_payload([0.1], counter_incs=3),
            "r1": _registry_payload([0.2], counter_incs=4),
        }
        for p in payloads.values():
            p["t_gauge"] = {
                "type": "gauge",
                "samples": [{"labels": {}, "value": 7.0}],
            }
        merged = fed.merge_payloads(payloads)
        assert merged["t_total"]["samples"][0]["value"] == 7.0
        assert "t_gauge" not in merged  # summed gauges mean nothing
        assert merged["t_seconds"]["samples"][0]["count"] == 2

    def test_counter_merge_respects_label_sets(self):
        def payload(route_counts):
            reg = MetricRegistry()
            c = reg.counter("t_total", "h", ("route",))
            for route, n in route_counts.items():
                c.labels(route).inc(n)
            return reg.to_dict()

        merged = fed.merge_payloads(
            {
                "r0": payload({"a": 1, "b": 10}),
                "r1": payload({"a": 2}),
            }
        )
        by_route = {
            s["labels"]["route"]: s["value"]
            for s in merged["t_total"]["samples"]
        }
        assert by_route == {"a": 3.0, "b": 10.0}

    def test_combine_families_injects_replica_label(self):
        local = MetricRegistry()
        local.counter("r_total").inc(5)
        combined = fed.combine_families(
            local.to_dict(),
            {"r0": _registry_payload([], counter_incs=2)},
        )
        assert "r_total" in combined and "t_total" in combined
        sample = combined["t_total"]["samples"][0]
        assert sample["labels"][fed.REPLICA_LABEL] == "r0"
        # the router's own series carries no replica label
        assert (
            fed.REPLICA_LABEL
            not in combined["r_total"]["samples"][0]["labels"]
        )

    def test_render_prometheus_families(self):
        combined = fed.combine_families(
            {},
            {
                "r0": _registry_payload([0.05, 0.3], counter_incs=1),
                "r1": _registry_payload([0.7], counter_incs=2),
            },
        )
        text = fed.render_prometheus_families(combined)
        assert text.count("# TYPE t_total counter") == 1
        assert text.count("# TYPE t_seconds histogram") == 1
        assert 't_total{replica="r0"} 1' in text
        assert 't_total{replica="r1"} 2' in text
        # cumulative buckets rebuilt per-sample, +Inf == count
        assert 't_seconds_bucket{le="+Inf",replica="r0"} 2' in text
        assert 't_seconds_count{replica="r1"} 1' in text

    def test_counter_total_filters_labels(self):
        reg = MetricRegistry()
        c = reg.counter("t_total", "h", ("outcome",))
        c.labels("good").inc(6)
        c.labels("bad").inc(2)
        fams = reg.to_dict()
        assert fed.counter_total(fams, "t_total") == 8.0
        assert fed.counter_total(fams, "t_total", outcome="good") == 6.0
        assert fed.counter_total(fams, "missing_total") == 0.0


# -- SLO monitor -----------------------------------------------------------


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


class TestSLOMonitor:
    def _monitor(self, registry=None, **kw):
        kw.setdefault("short_window_s", 60.0)
        kw.setdefault("long_window_s", 600.0)
        clock = kw.pop("clock", None) or FakeClock()
        return (
            SLOMonitor(registry, clock=clock, **kw),
            clock,
        )

    def test_observe_scoring(self):
        mon, _ = self._monitor()
        obj = mon.objective(DEFAULT)
        mon.observe(DEFAULT, 200, 0.01)  # good
        mon.observe(DEFAULT, 500, 0.01)  # 5xx -> bad
        mon.observe(DEFAULT, 429, 0.01)  # shed -> bad
        mon.observe(DEFAULT, 200, obj.latency_s * 2)  # slow -> bad
        good, bad = mon._window_counts(DEFAULT, 60.0)
        assert (good, bad) == (1.0, 3.0)

    def test_burn_rate_math(self):
        # 10% bad against a 95% availability target burns at 2x budget
        mon, _ = self._monitor(
            objectives={SHEDDABLE: Objective(0.95, 2.0)}
        )
        mon.ingest(SHEDDABLE, good=90.0, bad=10.0)
        assert mon.burn_rate(SHEDDABLE) == pytest.approx(2.0)
        assert mon.budget_remaining(SHEDDABLE) == 0.0
        assert mon.max_burn_rate() == pytest.approx(2.0)

    def test_empty_window_burns_nothing(self):
        mon, _ = self._monitor()
        assert mon.burn_rate(CRITICAL) == 0.0
        assert mon.budget_remaining(CRITICAL) == 1.0
        assert mon.max_burn_rate() == 0.0

    def test_short_window_recovers_before_long(self):
        mon, clock = self._monitor()
        mon.ingest(DEFAULT, good=0.0, bad=50.0)
        assert mon.burn_rate(DEFAULT, "short") > 0
        clock.advance(120.0)  # past short (60s), inside long (600s)
        mon.ingest(DEFAULT, good=100.0, bad=0.0)
        assert mon.burn_rate(DEFAULT, "short") == 0.0
        assert mon.burn_rate(DEFAULT, "long") > 0.0

    def test_buckets_prune_past_long_horizon(self):
        mon, clock = self._monitor()
        mon.ingest(DEFAULT, good=1.0, bad=1.0)
        clock.advance(3600.0)
        mon.ingest(DEFAULT, good=1.0, bad=0.0)
        assert len(mon._buckets[DEFAULT]) == 1
        assert mon.burn_rate(DEFAULT, "long") == 0.0

    def test_unknown_class_folds_into_default(self):
        mon, _ = self._monitor()
        mon.observe("mystery", 500, 0.01)
        assert mon.burn_rate(DEFAULT, "short") > 0

    def test_registry_export(self):
        reg = MetricRegistry()
        mon, _ = self._monitor(registry=reg)
        mon.ingest(SHEDDABLE, good=9.0, bad=1.0)
        data = reg.to_dict()
        good = fed.counter_total(
            data, "pio_slo_requests_total", outcome="good"
        )
        assert good == 9.0
        burn = {
            (s["labels"]["class"], s["labels"]["window"]): s["value"]
            for s in data["pio_slo_burn_rate"]["samples"]
        }
        assert burn[(SHEDDABLE, "short")] == pytest.approx(2.0)
        assert burn[(CRITICAL, "short")] == 0.0
        remaining = {
            s["labels"]["class"]: s["value"]
            for s in data["pio_slo_budget_remaining"]["samples"]
        }
        assert remaining[SHEDDABLE] == 0.0
        assert remaining[CRITICAL] == 1.0

    def test_export_counter_false_registers_no_counter(self):
        # the router's fleet monitor must not re-emit request
        # counters beside the federated per-replica ones
        reg = MetricRegistry()
        mon, _ = self._monitor(registry=reg, export_counter=False)
        mon.ingest(DEFAULT, good=1.0, bad=0.0)
        assert "pio_slo_requests_total" not in reg.to_dict()
        assert "pio_slo_burn_rate" in reg.to_dict()

    def test_objectives_from_env(self, monkeypatch):
        monkeypatch.setenv("PIO_SLO_CRITICAL_AVAILABILITY", "0.9999")
        monkeypatch.setenv("PIO_SLO_SHEDDABLE_LATENCY_MS", "250")
        objs = objectives_from_env()
        assert objs[CRITICAL].availability == 0.9999
        assert objs[SHEDDABLE].latency_s == 0.25
        assert objs[DEFAULT].availability == 0.99

    def test_snapshot_shape(self):
        mon, _ = self._monitor()
        snap = mon.snapshot()
        assert set(snap) == set((CRITICAL, DEFAULT, SHEDDABLE))
        assert set(snap[DEFAULT]) == {
            "burnShort",
            "burnLong",
            "budgetRemaining",
            "availability",
            "latencyMs",
        }


# -- device telemetry ------------------------------------------------------


class TestDeviceTelemetry:
    def test_sampler_publishes_gauges(self):
        reg = MetricRegistry()
        sample = {
            "devices": {
                "tpu:0": {"used": 100.0, "limit": 1000.0},
                "tpu:1": {"used": 50.0, "limit": None},
            },
            "liveArrayBytes": 77.0,
        }
        sampler = DeviceSampler(
            reg, interval_s=60.0, sample_fn=lambda: sample
        )
        assert sampler.sample_once() == sample
        data = reg.to_dict()
        used = {
            s["labels"]["device"]: s["value"]
            for s in data["pio_device_hbm_used_bytes"]["samples"]
        }
        assert used == {"tpu:0": 100.0, "tpu:1": 50.0}
        limits = {
            s["labels"]["device"]: s["value"]
            for s in data["pio_device_hbm_limit_bytes"]["samples"]
        }
        assert limits == {"tpu:0": 1000.0}  # None limit: no series
        assert (
            data["pio_device_live_array_bytes"]["samples"][0]["value"]
            == 77.0
        )
        assert sampler.last_sample() == sample

    def test_sampler_thread_lifecycle(self):
        reg = MetricRegistry()
        calls = []
        sampler = DeviceSampler(
            reg,
            interval_s=0.05,
            sample_fn=lambda: calls.append(1) or {},
        )
        sampler.start()
        assert sampler.start() is sampler  # idempotent
        deadline = time.monotonic() + 5.0
        while len(calls) < 3 and time.monotonic() < deadline:
            time.sleep(0.02)
        sampler.stop()
        assert len(calls) >= 3  # eager first sample + cadence ticks
        settled = len(calls)
        time.sleep(0.15)
        assert len(calls) == settled  # thread actually stopped

    def test_sampler_survives_flaky_backend(self):
        reg = MetricRegistry()
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) % 2:
                raise RuntimeError("backend read failed")
            return {}

        sampler = DeviceSampler(reg, interval_s=0.03, sample_fn=flaky)
        # the eager first sample raises; start() must still launch the
        # cadence thread (and stop() must not join an unstarted thread)
        sampler.start()
        deadline = time.monotonic() + 5.0
        while len(calls) < 4 and time.monotonic() < deadline:
            time.sleep(0.02)
        sampler.stop()
        assert len(calls) >= 4

    def test_sample_devices_shape_on_cpu(self):
        from predictionio_tpu.obs.device import sample_devices

        sample = sample_devices()
        # jax is importable in CI: devices dict may be empty (CPU has
        # no memory_stats) but the shape holds
        assert set(sample) <= {"devices", "liveArrayBytes"}
        if sample:
            assert isinstance(sample["devices"], dict)

    def test_compile_tracker(self):
        reg = MetricRegistry()
        tracker = CompileTracker(reg)
        assert tracker.record("default", (8, 16)) is True
        assert tracker.record("default", (8, 16)) is False  # cache hit
        assert tracker.record("default", (16, 16)) is True  # retrace
        assert tracker.record("other", (8, 16)) is True  # new site
        data = reg.to_dict()
        compiles = {
            s["labels"]["site"]: s["value"]
            for s in data["pio_jit_compiles_total"]["samples"]
        }
        assert compiles == {"default": 2.0, "other": 1.0}
        retraces = {
            s["labels"]["site"]: s["value"]
            for s in data["pio_jit_retraces_total"]["samples"]
        }
        assert retraces == {"default": 1.0}


# -- router federation -----------------------------------------------------


class MetricReplica:
    """A replica-shaped server backed by a REAL metric registry, so the
    router federates genuine snapshots."""

    def __init__(self, name):
        self.name = name
        self.registry = MetricRegistry()
        self.requests = self.registry.counter(
            "r_requests_total", "h", ("route",)
        )
        self.latency = self.registry.histogram(
            "r_seconds", buckets=(0.1, 0.5, 1.0)
        )
        self.slo = SLOMonitor(
            self.registry, short_window_s=60.0, long_window_s=600.0
        )
        self.registry.gauge("pio_device_hbm_used_bytes", "h", ("device",))
        self.timeline = timeline_mod.Timeline(capacity=64)
        router = Router()
        router.route("GET", "/metrics.json", self._metrics)
        router.route("GET", "/debug/timeline.json", self._timeline)
        self.http = HTTPServer(
            router, host="127.0.0.1", port=0, service=f"rep-{name}"
        )
        self.http.start()
        self.url = f"http://127.0.0.1:{self.http.port}"

    def set_hbm(self, device, used, limit):
        self.registry.gauge(
            "pio_device_hbm_used_bytes", "h", ("device",)
        ).labels(device).set(used)
        self.registry.gauge(
            "pio_device_hbm_limit_bytes", "h", ("device",)
        ).labels(device).set(limit)

    def _metrics(self, request):
        return Response(200, self.registry.to_dict())

    def _timeline(self, request):
        return Response(200, self.timeline.to_dict())

    def close(self):
        self.http.shutdown()


def _probe(router):
    for replica in list(router._replicas.values()):
        router._probe_one(replica)


def _make_router(*replicas, **kwargs):
    kwargs.setdefault("probe_interval_s", 999.0)  # probes by hand
    kwargs.setdefault("registry", MetricRegistry())
    router = ServingRouter(**kwargs)
    for rep in replicas:
        router.add_replica(rep.url, replica_id=rep.name)
    return router


class TestRouterFederation:
    def test_federated_dict_merges_exactly(self):
        a, b = MetricReplica("a"), MetricReplica("b")
        a.requests.labels("q").inc(3)
        b.requests.labels("q").inc(4)
        a.latency.observe(0.05)
        b.latency.observe(0.3)
        b.latency.observe(0.7)
        router = _make_router(a, b)
        try:
            data = router.federated_dict()
            assert sorted(data["federation"]["replicas"]) == ["a", "b"]
            assert data["federation"]["stale"] == []
            fleet = data["fleet"]
            assert (
                fed.counter_total(fleet, "r_requests_total", route="q")
                == 7.0
            )
            hist = fleet["r_seconds"]["samples"][0]
            assert hist["count"] == 3
            assert hist["buckets"]["0.1"] == 1
            assert hist["buckets"]["0.5"] == 1
            assert hist["buckets"]["1"] == 1
            # per-replica payloads ride along unmerged
            assert (
                fed.counter_total(
                    data["perReplica"]["a"],
                    "r_requests_total",
                    route="q",
                )
                == 3.0
            )
            # the router's own registry is the local view
            assert "pio_router_replica_healthy" in data["local"]
        finally:
            router.close()
            a.close()
            b.close()

    def test_federated_text_labels_and_single_type_line(self):
        a, b = MetricReplica("a"), MetricReplica("b")
        a.requests.labels("q").inc(1)
        b.requests.labels("q").inc(2)
        router = _make_router(a, b)
        try:
            text = router.federated_text()
            assert 'r_requests_total{replica="a",route="q"} 1' in text
            assert 'r_requests_total{replica="b",route="q"} 2' in text
            assert text.count("# TYPE r_requests_total counter") == 1
            assert "pio_fleet_goodput_qps" in text
            assert "pio_fleet_replicas" in text
        finally:
            router.close()
            a.close()
            b.close()

    def test_dead_replica_marked_stale_with_last_snapshot(self):
        a, b = MetricReplica("a"), MetricReplica("b")
        a.requests.labels("q").inc(5)
        b.requests.labels("q").inc(2)
        router = _make_router(a, b)
        try:
            first = router.federated_dict()
            assert first["federation"]["stale"] == []
            b.close()  # hard kill: connection refused on next scrape
            second = router.federated_dict()
            assert "b" in second["federation"]["replicas"]
            assert second["federation"]["stale"] == ["b"]
            # the dead replica contributes its LAST snapshot
            assert (
                fed.counter_total(
                    second["fleet"], "r_requests_total", route="q"
                )
                == 7.0
            )
            stale = {
                s["labels"]["replica"]: s["value"]
                for s in second["local"]["pio_federation_stale"][
                    "samples"
                ]
            }
            assert stale == {"a": 0.0, "b": 1.0}
        finally:
            router.close()
            a.close()

    def test_fleet_slo_ingests_deltas_once(self):
        a = MetricReplica("a")
        for _ in range(9):
            a.slo.observe("default", 200, 0.01)
        a.slo.observe("default", 500, 0.01)
        router = _make_router(a)
        try:
            router.federated_dict()
            burn1 = router._fleet_slo.burn_rate("default")
            assert burn1 > 0
            # re-scraping without new traffic must not double-ingest
            router.federated_dict()
            good, bad = router._fleet_slo._window_counts(
                "default", 60.0
            )
            assert (good, bad) == (9.0, 1.0)
            # counter reset (replica restart) re-baselines, not
            # negative deltas
            router._slo_seen["a"][("default", "good")] = 100.0
            a.slo.observe("default", 200, 0.01)
            router.federated_dict()
            good2, _ = router._fleet_slo._window_counts(
                "default", 60.0
            )
            assert good2 == 9.0 + 10.0  # full post-reset value added
        finally:
            router.close()
            a.close()

    def test_autoscaler_signals_carry_burn_rate(self):
        a = MetricReplica("a")
        a.slo.ingest("sheddable", good=0.0, bad=50.0)
        router = _make_router(a)
        try:
            router.federated_dict()
            signals = router.autoscaler_signals()
            assert signals["burnRate"] > 1.0
        finally:
            router.close()
            a.close()

    def test_fleet_health_reports_hbm_headroom(self):
        a = MetricReplica("a")
        a.set_hbm("tpu:0", used=600.0, limit=1000.0)
        router = _make_router(a)
        try:
            # probe-path storage feeds fleet_health (no scrape fan-out)
            _probe(router)
            health = router.fleet_health()
            rep = health["replicas"]["a"]
            assert rep["hbmUsedBytes"] == 600.0
            assert rep["hbmLimitBytes"] == 1000.0
            assert rep["hbmHeadroomBytes"] == 400.0
            assert rep["stale"] is False
            assert "slo" in health and "goodputQps" in health
        finally:
            router.close()
            a.close()

    def test_status_endpoint_includes_fleet_health(self):
        a = MetricReplica("a")
        router = _make_router(a)
        http = router.serve(host="127.0.0.1", port=0)
        http.start()
        try:
            status, body = _call(f"http://127.0.0.1:{http.port}/")
            assert status == 200
            payload = json.loads(body)
            assert "fleetHealth" in payload
            assert "burnRate" in payload["fleetHealth"]
        finally:
            http.shutdown()
            router.close()
            a.close()

    def test_router_metrics_endpoints_serve_federated_view(self):
        a = MetricReplica("a")
        a.requests.labels("q").inc(2)
        router = _make_router(a)
        http = router.serve(host="127.0.0.1", port=0)
        http.start()
        try:
            base = f"http://127.0.0.1:{http.port}"
            status, body = _call(f"{base}/metrics.json")
            assert status == 200
            data = json.loads(body)
            assert data["federation"]["replicas"] == ["a"]
            assert (
                fed.counter_total(
                    data["fleet"], "r_requests_total", route="q"
                )
                == 2.0
            )
            status, text = _call(f"{base}/metrics")
            assert status == 200
            assert b'r_requests_total{replica="a"' in text
        finally:
            http.shutdown()
            router.close()
            a.close()


# -- profile capture -------------------------------------------------------


@pytest.fixture()
def engine_server_factory(memory_storage):
    """Build a live EngineServer over the fake engine; returns
    ``(base_url, server)`` and tears the stack down after the test."""
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
    from predictionio_tpu.serving.config import ServerConfig
    from predictionio_tpu.serving.engine_server import EngineServer

    ctx = ComputeContext.create(batch="fed-test")
    engine = Engine(
        FakeDataSource, FakePreparator, FakeAlgorithm, FakeServing
    )
    params = EngineParams(
        data_source=("", FakeParams(id=1)),
        preparator=("", FakeParams(id=2)),
        algorithms=[("", FakeParams(id=3))],
        serving=("", FakeParams()),
    )
    run_train(
        engine, params, engine_id="fed", ctx=ctx,
        storage=memory_storage,
    )
    cleanup = []

    def factory(access_key=None):
        server_config = None
        if access_key is not None:
            server_config = ServerConfig(
                key_auth_enforced=True, access_key=access_key
            )
        es = EngineServer(
            engine,
            params,
            engine_id="fed",
            storage=memory_storage,
            ctx=ctx,
            warmup=False,
            server_config=server_config,
        )
        http = es.serve(host="127.0.0.1", port=0)
        http.start()
        cleanup.append((http, es))
        return f"http://127.0.0.1:{http.port}", es

    yield factory
    for http, es in cleanup:
        http.shutdown()
        es.close()


class TestEngineServerDeviceTelemetry:
    def test_warmup_buckets_feed_compile_tracker(
        self, engine_server_factory, memory_storage
    ):
        from fake_engine import (
            FakeAlgorithm,
            FakeDataSource,
            FakeParams,
            FakePreparator,
            FakeServing,
        )
        from predictionio_tpu.core import Engine, EngineParams
        from predictionio_tpu.obs import MetricRegistry
        from predictionio_tpu.parallel.mesh import ComputeContext
        from predictionio_tpu.serving.engine_server import EngineServer

        engine = Engine(
            FakeDataSource, FakePreparator, FakeAlgorithm, FakeServing
        )
        params = EngineParams(
            data_source=("", FakeParams(id=1)),
            preparator=("", FakeParams(id=2)),
            algorithms=[("", FakeParams(id=3))],
            serving=("", FakeParams()),
        )
        reg = MetricRegistry()
        es = EngineServer(
            engine,
            params,
            engine_id="fed",
            storage=memory_storage,
            ctx=ComputeContext.create(batch="fed-warm"),
            warmup=True,
            registry=reg,
        )
        try:
            data = reg.to_dict()
            compiles = fed.counter_total(
                data, "pio_jit_compiles_total", site="fed/algo0"
            )
            # one fresh compile per power-of-two warmup bucket, and
            # every bucket past the first counts as a retrace
            assert compiles >= 2
            retraces = fed.counter_total(
                data, "pio_jit_retraces_total", site="fed/algo0"
            )
            assert retraces == compiles - 1
            # the device sampler's gauges are registered up front
            assert "pio_device_live_array_bytes" in data
        finally:
            es.close()


class TestProfileCapture:
    @pytest.fixture()
    def fast_trace(self, monkeypatch):
        """jax.profiler startup costs ~10s of wall clock on CPU; unit
        tests stub the trace context and just materialize the dir."""
        import contextlib
        import os

        from predictionio_tpu.utils import profiling

        @contextlib.contextmanager
        def fake_trace(trace_dir=None):
            if trace_dir:
                os.makedirs(trace_dir, exist_ok=True)
                with open(
                    os.path.join(trace_dir, "trace.txt"), "w"
                ) as f:
                    f.write("stub")
            yield

        monkeypatch.setattr(profiling, "trace", fake_trace)
        return fake_trace

    def test_capture_writes_artifact(self, fast_trace, tmp_path):
        from predictionio_tpu.obs import tracing
        from predictionio_tpu.utils import profiling

        tracer = tracing.Tracer()
        with tracer.trace("unit-span"):
            pass
        manifest = profiling.capture(
            0.01,
            tracer=tracer,
            device_sample_fn=lambda: {"devices": {}},
            out_dir=str(tmp_path),
        )
        art = manifest["artifactDir"]
        assert art.startswith(str(tmp_path))
        assert sorted(manifest["files"]) == [
            "device.json",
            "jax_trace/",
            "manifest.json",
            "spans.json",
            "summary.json",
        ]
        with open(f"{art}/spans.json") as f:
            spans = json.load(f)
        # Perfetto-loadable chrome trace events from the same window
        assert any(
            e.get("name") == "unit-span"
            for e in spans.get("traceEvents", [])
        )
        with open(f"{art}/manifest.json") as f:
            assert json.load(f)["id"] == manifest["id"]

    def test_capture_survives_device_sampler_failure(
        self, fast_trace, tmp_path
    ):
        from predictionio_tpu.utils import profiling

        def boom():
            raise RuntimeError("no backend")

        manifest = profiling.capture(
            0.0, device_sample_fn=boom, out_dir=str(tmp_path)
        )
        assert "device.json" not in manifest["files"]

    def test_bundle_round_trips(self, fast_trace, tmp_path):
        from predictionio_tpu.utils import profiling

        manifest = profiling.capture(0.0, out_dir=str(tmp_path))
        raw = profiling.bundle(manifest["artifactDir"])
        with tarfile.open(
            fileobj=io.BytesIO(raw), mode="r:gz"
        ) as tar:
            names = tar.getnames()
        prefix = f"profile-{manifest['id']}"
        assert f"{prefix}/manifest.json" in names
        assert f"{prefix}/spans.json" in names
        assert any(n.startswith(f"{prefix}/jax_trace") for n in names)


class TestProfileEndpoint:
    @pytest.fixture()
    def server(self, engine_server_factory, monkeypatch, tmp_path):
        monkeypatch.setenv("PIO_PROFILE_DIR", str(tmp_path))
        import contextlib
        import os

        from predictionio_tpu.utils import profiling

        @contextlib.contextmanager
        def fake_trace(trace_dir=None):
            if trace_dir:
                os.makedirs(trace_dir, exist_ok=True)
            yield

        monkeypatch.setattr(profiling, "trace", fake_trace)
        return engine_server_factory()

    def test_profile_endpoint_returns_bundle(self, server):
        base, _srv = server
        status, body = _call(
            f"{base}/debug/profile",
            method="POST",
            body={"durationMs": 60},
            timeout=60,
        )
        assert status == 200, body
        payload = json.loads(body)
        manifest = payload["profile"]
        assert manifest["durationS"] >= 0.05
        raw = base64.b64decode(payload["bundle"])
        with tarfile.open(
            fileobj=io.BytesIO(raw), mode="r:gz"
        ) as tar:
            names = tar.getnames()
        assert any(n.endswith("manifest.json") for n in names)
        assert any(n.endswith("spans.json") for n in names)
        assert any("jax_trace" in n for n in names)

    def test_profile_rejects_bad_duration(self, server):
        base, _srv = server
        status, body = _call(
            f"{base}/debug/profile",
            method="POST",
            body={"durationMs": "soon"},
            timeout=60,
        )
        assert status == 400

    def test_profile_overlap_is_409(self, server):
        base, _srv = server
        results = []

        def fire(ms):
            results.append(
                _call(
                    f"{base}/debug/profile",
                    method="POST",
                    body={"durationMs": ms},
                    timeout=60,
                )[0]
            )

        t = threading.Thread(target=fire, args=(1500,))
        t.start()
        deadline = time.monotonic() + 5.0
        codes = set()
        while time.monotonic() < deadline:
            status, _ = _call(
                f"{base}/debug/profile",
                method="POST",
                body={"durationMs": 60},
                timeout=60,
            )
            codes.add(status)
            # either side may lose the race: if a 60 ms poll capture
            # reached the server first, the background 1500 ms request
            # is the one that draws the 409
            if 409 in codes or 409 in results:
                break
            time.sleep(0.05)
        t.join()
        assert 409 in codes or 409 in results
        assert 200 in codes or results == [200]

    def test_profile_duration_clamped_to_max(self, server, monkeypatch):
        monkeypatch.setenv("PIO_PROFILE_MAX_MS", "80")
        base, _srv = server
        t0 = time.monotonic()
        status, body = _call(
            f"{base}/debug/profile",
            method="POST",
            body={"durationMs": 60000},
            timeout=60,
        )
        assert status == 200
        assert time.monotonic() - t0 < 10.0
        manifest = json.loads(body)["profile"]
        assert manifest["durationS"] < 5.0

    def test_profile_key_gated(self, engine_server_factory, monkeypatch):
        import contextlib

        from predictionio_tpu.utils import profiling

        @contextlib.contextmanager
        def fake_trace(trace_dir=None):
            yield

        monkeypatch.setattr(profiling, "trace", fake_trace)
        base, _srv = engine_server_factory(access_key="sekrit")
        status, _ = _call(
            f"{base}/debug/profile",
            method="POST",
            body={"durationMs": 60},
            timeout=60,
        )
        assert status in (401, 403)
        status, _ = _call(
            f"{base}/debug/profile",
            method="POST",
            body={"durationMs": 60},
            headers={"X-PIO-Server-Key": "sekrit"},
            timeout=60,
        )
        assert status == 200


# -- tenant cost attribution federation ------------------------------------


class TestTenantFederation:
    """Tenant-labeled series federate like any other: counters sum per
    tenant label set, histograms bucket-merge per tenant — the fleet
    per-tenant cost rollup is exact, not re-estimated."""

    def _charge(self, replica, tenant, device_s, waits):
        replica.registry.counter(
            "pio_tenant_device_seconds_total", "h", ("tenant",)
        ).labels(tenant).inc(device_s)
        hist = replica.registry.histogram(
            "pio_tenant_queue_wait_seconds",
            "h",
            ("tenant",),
            buckets=(0.1, 0.5, 1.0),
        )
        for w in waits:
            hist.labels(tenant).observe(w)

    def test_tenant_histograms_and_counters_merge_per_tenant(self):
        a, b = MetricReplica("a"), MetricReplica("b")
        self._charge(a, "t1", 2.5, [0.05, 0.3])
        self._charge(a, "t2", 0.5, [0.05])
        self._charge(b, "t1", 1.5, [0.7])
        router = _make_router(a, b)
        try:
            fleet = router.federated_dict()["fleet"]
            device = {
                s["labels"]["tenant"]: s["value"]
                for s in fleet["pio_tenant_device_seconds_total"][
                    "samples"
                ]
            }
            assert device == {"t1": 4.0, "t2": 0.5}
            waits = {
                s["labels"]["tenant"]: s
                for s in fleet["pio_tenant_queue_wait_seconds"][
                    "samples"
                ]
            }
            # t1's histogram is the union of a's and b's observations
            assert waits["t1"]["count"] == 3
            assert waits["t1"]["buckets"]["0.1"] == 1
            assert waits["t1"]["buckets"]["0.5"] == 1
            assert waits["t1"]["buckets"]["1"] == 1
            assert waits["t2"]["count"] == 1
        finally:
            router.close()
            a.close()
            b.close()


# -- incident timeline -----------------------------------------------------


class TestTimelineMerge:
    """merge_timelines ordering semantics (unit level, controlled wall
    stamps — cross-process ordering must use the wall clock, with seq
    breaking ties within one replica)."""

    def _payload(self, *events):
        return {"dropped": 0, "events": [dict(e) for e in events]}

    def test_events_order_by_wall_across_replicas(self):
        a = self._payload(
            {"kind": "k1", "wall": 10.0, "seq": 1},
            {"kind": "k3", "wall": 30.0, "seq": 2},
        )
        b = self._payload({"kind": "k2", "wall": 20.0, "seq": 1})
        merged = timeline_mod.merge_timelines([("a", a), ("b", b)])
        assert [e["kind"] for e in merged["events"]] == [
            "k1", "k2", "k3",
        ]
        assert [e["replica"] for e in merged["events"]] == [
            "a", "b", "a",
        ]
        assert merged["replicas"] == ["a", "b"]

    def test_seq_breaks_same_tick_ties_within_replica(self):
        a = self._payload(
            {"kind": "second", "wall": 10.0, "seq": 2},
            {"kind": "first", "wall": 10.0, "seq": 1},
        )
        merged = timeline_mod.merge_timelines([("a", a)])
        assert [e["kind"] for e in merged["events"]] == [
            "first", "second",
        ]

    def test_none_payload_contributes_nothing(self):
        a = self._payload({"kind": "k", "wall": 1.0, "seq": 1})
        merged = timeline_mod.merge_timelines([("a", a), ("b", None)])
        assert merged["replicas"] == ["a"]
        assert len(merged["events"]) == 1

    def test_limit_keeps_newest_and_counts_dropped(self):
        a = self._payload(
            *(
                {"kind": f"k{i}", "wall": float(i), "seq": i}
                for i in range(5)
            )
        )
        merged = timeline_mod.merge_timelines([("a", a)], limit=2)
        assert [e["kind"] for e in merged["events"]] == ["k3", "k4"]
        assert merged["dropped"] == 3

    def test_ring_capacity_drops_oldest(self):
        ring = timeline_mod.Timeline(capacity=3)
        for i in range(5):
            ring.record(f"k{i}", "m")
        payload = ring.to_dict()
        assert payload["dropped"] == 2
        assert [e["kind"] for e in payload["events"]] == [
            "k2", "k3", "k4",
        ]


class TestRouterTimeline:
    def test_federated_timeline_merges_and_orders(self):
        a, b = MetricReplica("a"), MetricReplica("b")
        a.timeline.record("pool_eviction", "evicted t9", tenant="t9")
        time.sleep(0.01)
        b.timeline.record("breaker_transition", "breaker -> open")
        router = _make_router(a, b)
        try:
            merged = router.federated_timeline()
            assert set(merged["replicas"]) >= {"a", "b"}
            assert merged["stale"] == []
            kinds = [e["kind"] for e in merged["events"]]
            assert kinds.index("pool_eviction") < kinds.index(
                "breaker_transition"
            )
            walls = [e["wall"] for e in merged["events"]]
            assert walls == sorted(walls)
        finally:
            router.close()
            a.close()
            b.close()

    def test_killed_replica_is_stale_not_absent(self):
        a, b = MetricReplica("a"), MetricReplica("b")
        b.timeline.record("pool_load_timeout", "t3 cold load timed out")
        router = _make_router(a, b)
        try:
            first = router.federated_timeline()
            assert first["stale"] == []
            b.close()  # connection refused on the next scrape
            a.timeline.record("autoscaler_action", "grow to 3")
            second = router.federated_timeline()
            assert second["stale"] == ["b"]
            assert "b" in second["replicas"]
            kinds_by_replica = {
                (e["replica"], e["kind"]) for e in second["events"]
            }
            # the dead replica's LAST snapshot still contributes...
            assert ("b", "pool_load_timeout") in kinds_by_replica
            # ...beside events recorded after it died
            assert ("a", "autoscaler_action") in kinds_by_replica
            walls = [e["wall"] for e in second["events"]]
            assert walls == sorted(walls)
        finally:
            router.close()
            a.close()

    def test_router_serves_merged_timeline_endpoint(self):
        a = MetricReplica("a")
        a.timeline.record("canary_verdict", "promote g2", generation=2)
        router = _make_router(a)
        http = router.serve(host="127.0.0.1", port=0)
        http.start()
        try:
            status, body = _call(
                f"http://127.0.0.1:{http.port}/debug/timeline.json"
            )
            assert status == 200
            payload = json.loads(body)
            assert any(
                e["kind"] == "canary_verdict" and e["replica"] == "a"
                for e in payload["events"]
            )
            # the router's own ring is in the merge (swap_phase etc.
            # land there); its id is "router"
            assert "router" in payload["replicas"]
        finally:
            http.shutdown()
            router.close()
            a.close()

    def test_swap_phase_lands_in_router_timeline(self):
        router = _make_router()
        try:
            record = {"id": "s1", "generation": "g2"}
            router._set_swap_phase(record, "draining")
            events = router._timeline.events()
            assert any(
                e["kind"] == "swap_phase"
                and e["phase"] == "draining"
                and e["generation"] == "g2"
                for e in events
            )
        finally:
            router.close()
