"""The stage mechanism of obs/tracing (one histogram observation, one
profiler annotation, one child span a stage), the timeline summary of
utils/profiling on hand-made events, XLA's compile counter, the process
clocks, and the lint rule that keeps ``stage(`` inside a ``with``."""

from __future__ import annotations

import json
import os
import textwrap

import numpy as np
import pytest

from predictionio_tpu.analysis import analyze_modules
from predictionio_tpu.analysis.source import SourceModule
from predictionio_tpu.obs import tracing
from predictionio_tpu.obs.device import CompileWatch
from predictionio_tpu.obs.registry import (
    MetricRegistry,
    install_process_clocks,
)
from predictionio_tpu.utils import profiling

MS = 1_000_000  # nanoseconds


def _count(registry, stage):
    samples = registry.to_dict()["pio_stage_seconds"]["samples"]
    return next(s for s in samples if s["labels"] == {"stage": stage})["count"]


class _Recorder:
    """An annotation factory that records how it was entered."""

    def __init__(self):
        self.entered, self.exited = [], 0

    def __call__(self, name, **keywords):
        recorder = self

        class _Annotation:
            def __enter__(self):
                recorder.entered.append((name, keywords))

            def __exit__(self, *exc):
                recorder.exited += 1

        return _Annotation()


@pytest.fixture()
def bound():
    """A private registry's sink, bound to this context for one test."""
    registry = MetricRegistry()
    token = tracing._bound_stages.set(None)
    sink = tracing.StageSink(registry)
    yield registry, sink
    tracing._bound_stages.reset(token)


def test_unknown_stage_is_an_error(bound):
    with pytest.raises(ValueError, match="unknown stage"):
        tracing.stage("engine.decod")
    assert len(set(tracing.STAGES)) == len(tracing.STAGES) == 16


def _profiling(monkeypatch, recorder, active=True):
    """As if a profiler ran (or not) with ``recorder`` as the factory."""
    monkeypatch.setattr(tracing, "_annotation_factory", recorder)
    monkeypatch.setattr(tracing, "_annotation_active", lambda: active)


@pytest.mark.parametrize("factory", [None, "recorder"])
def test_stage_observes_with_tracer_disabled(bound, monkeypatch, factory):
    registry, sink = bound
    recorder = _Recorder() if factory else None
    if recorder is None:  # as obs/ is without JAX: nothing installed
        monkeypatch.setattr(tracing, "_annotation_factory", None)
        monkeypatch.setattr(tracing, "_annotation_active", tracing._no_profiler)
    else:
        _profiling(monkeypatch, recorder)
    sink.bind(request_id="r-1")
    tracer = tracing.Tracer(enabled=False)
    with tracer.trace("root") as root:
        assert root is None
        with tracing.stage(tracing.ENGINE_DECODE):
            pass
    assert _count(registry, tracing.ENGINE_DECODE) == 1
    assert _count(registry, tracing.ENGINE_SUBMIT) == 0
    assert tracer.traces() == []
    if recorder is not None:
        assert recorder.entered == [("engine.decode", {"request_id": "r-1"})]
        assert recorder.exited == 1


def test_unbound_context_observes_in_the_process_registry(monkeypatch):
    token = tracing._bound_stages.set(None)
    try:
        before = _count(tracing.get_registry(), tracing.PREDICT_PREP)
        with tracing.stage(tracing.PREDICT_PREP):
            pass
        assert _count(tracing.get_registry(), tracing.PREDICT_PREP) == before + 1
    finally:
        tracing._bound_stages.reset(token)


@pytest.mark.parametrize("keywords", [
    {"request_id": "abc-123"}, {"batch": 7, "n": 64},
])
def test_factory_is_entered_with_name_and_bound_keywords(
    bound, monkeypatch, keywords
):
    _registry, sink = bound
    recorder = _Recorder()
    _profiling(monkeypatch, recorder)
    sink.bind(**keywords)
    with tracing.stage(tracing.BATCH_SETTLE):
        with tracing.stage(tracing.PREDICT_DEVICE_GET):
            pass
    assert recorder.entered == [
        ("batch.settle", keywords), ("predict.device_get", keywords),
    ]
    assert recorder.exited == 2


def test_no_annotation_is_built_while_no_profiler_runs(bound, monkeypatch):
    registry, sink = bound
    recorder = _Recorder()
    _profiling(monkeypatch, recorder, active=False)
    sink.bind(request_id="r-2")
    with tracing.stage(tracing.HTTP_WRITE):
        pass
    assert recorder.entered == [] and recorder.exited == 0
    assert _count(registry, tracing.HTTP_WRITE) == 1


def test_the_installed_factory_is_the_profilers_annotation(tmp_path):
    import jax

    # utils/profiling installs both when it is imported, as every
    # serving and training entry point does
    assert tracing._annotation_factory is jax.profiler.TraceAnnotation
    assert tracing._annotation_active() is False
    with profiling.trace(str(tmp_path)):
        assert tracing._annotation_active() is True
        with tracing.stage(tracing.BATCH_WINDOW):
            pass
    assert tracing._annotation_active() is False
    stage_events, _device = profiling.load_trace_events(str(tmp_path))
    assert [name for name, _s, _e in stage_events] == ["batch.window"]


def test_stage_under_a_root_span_is_its_child_and_not_current(bound):
    registry, sink = bound
    sink.bind(request_id="t-1")
    tracer = tracing.Tracer()
    with tracer.trace("engine POST", trace_id="t-1") as root:
        with tracing.stage(tracing.ENGINE_SUBMIT):
            # what a stage encloses keeps hanging off the request's span
            assert tracing.current_span() is root
    (trace,) = tracer.traces()
    child = next(s for s in trace["spans"] if s["name"] == "engine.submit")
    assert child["parentId"] == root.span_id
    assert child["traceId"] == "t-1"
    assert root.start <= child["start"]
    assert child["durationMs"] <= root.duration * 1000 + 0.001
    assert _count(registry, tracing.ENGINE_SUBMIT) == 1


def test_stage_records_the_error_and_still_observes(bound):
    registry, sink = bound
    sink.bind()
    tracer = tracing.Tracer()
    with pytest.raises(KeyError):
        with tracer.trace("root"):
            with tracing.stage(tracing.ENGINE_SERVE):
                raise KeyError("boom")
    (trace,) = tracer.traces()
    child = next(s for s in trace["spans"] if s["name"] == "engine.serve")
    assert "KeyError" in child["attributes"]["error"]
    assert _count(registry, tracing.ENGINE_SERVE) == 1


# -- the timeline summary ---------------------------------------------------


def _stage(name, start_ms, end_ms):
    return (name, start_ms * MS, end_ms * MS)


def _op(scope, start_ms, end_ms, plane="/device:TPU:0"):
    return (plane, scope, start_ms * MS, end_ms * MS)


def test_summarize_gives_each_idle_instant_to_the_first_state_that_holds():
    # window 0..100 ms; the device runs 10..20 and 50..70
    device = [_op("score", 10, 20), _op("top_k", 50, 65), _op("top_k", 65, 70)]
    stages = [
        # 0..10: a request is read and submitted while the batcher's
        # window is open too: the window comes first in the order
        _stage(tracing.HTTP_READ, 0, 2),
        _stage(tracing.ENGINE_SUBMIT, 2, 10),
        _stage(tracing.BATCH_WINDOW, 4, 8),
        # 8..10 the launch, which beats the open submit
        _stage(tracing.PREDICT_ENQUEUE, 8, 10),
        # the handler waits from 10 to 80 (never a state of its own)
        _stage(tracing.ENGINE_AWAIT, 10, 80),
        # 20..30 device_get beats the backpressure open beside it
        _stage(tracing.PREDICT_DEVICE_GET, 20, 30),
        _stage(tracing.BATCH_BACKPRESSURE, 20, 40),
        # 30..35 materialize, 35..40 only backpressure, 40..50 nothing
        # named but a request in the server: unattributed
        _stage(tracing.PREDICT_MATERIALIZE, 30, 35),
        # 70..80 settle; 80..90 the response goes out; 90..100 nothing
        _stage(tracing.BATCH_SETTLE, 70, 80),
        _stage(tracing.HTTP_RESPOND, 80, 90),
        _stage(tracing.HTTP_WRITE, 85, 100),  # only stretches the window
    ]
    summary = profiling.summarize_events(stages, device)
    assert summary["window_s"] == pytest.approx(0.100)
    assert summary["device"]["busy_s"] == pytest.approx(0.030)
    assert summary["device"]["idle_share"] == pytest.approx(0.70)
    assert summary["device"]["by_scope"] == {
        "top_k": pytest.approx(0.020), "score": pytest.approx(0.010),
    }
    assert summary["idle"] == {
        "launch": pytest.approx(0.002),
        "device_get": pytest.approx(0.010),
        "materialize_settle": pytest.approx(0.015),
        "backpressure": pytest.approx(0.005),
        "batch_window": pytest.approx(0.004),
        "request_in": pytest.approx(0.004),
        "response_out": pytest.approx(0.010),
        "no_request": pytest.approx(0.010),
        "unattributed": pytest.approx(0.010),
    }
    assert sum(summary["idle"].values()) == pytest.approx(
        summary["device"]["idle_s"]
    )
    assert summary["idle_attributed_share"] == pytest.approx(1 - 0.010 / 0.070)
    assert summary["stages"]["engine.await"] == {
        "count": 1, "total_s": pytest.approx(0.070),
    }


def test_summarize_without_events_and_without_a_device():
    assert profiling.summarize_events([], []) == {}
    summary = profiling.summarize_events(
        [_stage(tracing.HTTP_RESPOND, 0, 4), _stage(tracing.ENGINE_AWAIT, 4, 10)],
        [],
    )
    assert summary["device"]["planes"] == 0
    assert summary["device"]["idle_share"] == 1.0
    assert summary["idle"]["response_out"] == pytest.approx(0.004)
    assert summary["idle"]["unattributed"] == pytest.approx(0.006)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_gather_top_k_dot_xla)/jit(_top_k_dot_xla)/score/dot_general", "score"),
    ("jit(_gather_rows_quant)/gather_dequant/jit(_take)/gather", "gather_dequant"),
    ("jit(run)/while/body/solve/gramian/dot_general", "while/body/solve/gramian"),
    ("factors", profiling.UNSCOPED),
    ("", profiling.UNSCOPED),
])
def test_scope_of_an_operation(op_name, scope):
    assert profiling.scope_of(op_name) == scope


def test_interval_arithmetic():
    cover = profiling._union([(5, 9), (0, 3), (2, 4), (9, 9)])
    assert cover == [(0, 4), (5, 9)]
    assert profiling._intersect(cover, [(3, 6), (8, 20)]) == [(3, 4), (5, 6), (8, 9)]
    assert profiling._subtract(cover, [(1, 2), (3, 7)]) == [(0, 1), (2, 3), (7, 9)]
    assert profiling._subtract([(0, 10)], []) == [(0, 10)]


def test_summarize_reads_scopes_and_stages_from_a_recorded_tpu_trace():
    """A trace of one f32 and one int8 batch recorded on a v5e (80 kB):
    the scopes come from the device plane's metadata, which
    ``ProfileData`` does not expose."""
    here = os.path.dirname(os.path.abspath(__file__))
    trace_dir = os.path.join(here, "data", "stages_trace")
    stage_events, device_events = profiling.load_trace_events(trace_dir)
    scopes = {scope for _p, scope, _s, _e in device_events}
    assert {"gather", "score", "top_k", "gather_dequant", "score_dequant"} <= scopes
    names = {name for name, _s, _e in stage_events}
    assert {"predict.enqueue", "predict.device_get"} <= names
    summary = profiling.summarize(trace_dir)
    assert 0 < summary["device"]["busy_s"] < summary["window_s"]
    assert max(summary["device"]["by_scope"], key=summary["device"]["by_scope"].get) == "top_k"
    assert summary["idle"]["launch"] > 0 and summary["idle"]["device_get"] > 0
    json.dumps(summary)


# -- XLA's compilations, the process clocks ---------------------------------


def test_compile_watch_counts_a_new_shape_once_and_a_warm_one_never():
    import jax

    registry = MetricRegistry()

    def compiles():
        samples = registry.to_dict()["pio_xla_compiles_total"]["samples"]
        return sum(s["value"] for s in samples)

    fn = jax.jit(lambda x: x * 3 + 1)
    a, b = np.ones(7, np.float32), np.ones(9, np.float32)
    with CompileWatch(registry):
        fn(a)
        assert compiles() == 1
        fn(a)
        assert compiles() == 1
        fn(b)
        assert compiles() == 2
    seconds = registry.to_dict()["pio_xla_compile_seconds"]["samples"]
    assert sum(s["count"] for s in seconds) == 2
    assert sum(s["sum"] for s in seconds) > 0
    fn(np.ones(11, np.float32))  # the watch is closed
    assert compiles() == 2


def test_compile_watches_share_one_listener_and_take_it_off(monkeypatch):
    from jax._src import monitoring

    from predictionio_tpu.obs import device

    def listeners():
        return len(monitoring.get_event_duration_listeners())

    # as in a process with no watch open: a server that an earlier test
    # of this worker never closed keeps its own claim on the listener
    monkeypatch.setattr(device, "_watched", {})
    base = listeners()
    registry = MetricRegistry()
    first, second = CompileWatch(registry), CompileWatch(MetricRegistry())
    third = CompileWatch(registry)
    assert listeners() == base + 1
    first.close()
    first.close()  # closing twice releases once
    second.close()
    assert listeners() == base + 1
    third.close()
    assert listeners() == base


def test_process_clocks_are_read_at_scrape_on_the_given_registry():
    registry = MetricRegistry()
    install_process_clocks(registry)
    first = registry.to_dict()
    sum(i * i for i in range(200_000))  # burn some CPU
    second = registry.to_dict()

    def value(snapshot, family):
        return snapshot[family]["samples"][0]["value"]

    cpu = value(second, "pio_process_cpu_seconds_total") - value(
        first, "pio_process_cpu_seconds_total"
    )
    clock = value(second, "pio_process_clock_seconds_total") - value(
        first, "pio_process_clock_seconds_total"
    )
    assert 0 < cpu and 0 < clock


# -- the lint rule ------------------------------------------------------------


def _rules(src):
    module = SourceModule("/fixture/mod.py", "mod.py", textwrap.dedent(src))
    return [f.rule for f in analyze_modules([module])]


def test_span_leak_rule_knows_stage():
    leaking = """
        from predictionio_tpu.obs import tracing

        def handle(body):
            tracing.stage(tracing.ENGINE_DECODE)
            return body
    """
    sound = """
        from predictionio_tpu.obs import tracing

        def handle(body):
            with tracing.stage(tracing.ENGINE_DECODE):
                return body
    """
    assert "span-leak" in _rules(leaking)
    assert "span-leak" not in _rules(sound)


def test_the_pool_s_stages_are_a_group_of_their_own(bound):
    """Eight names beside the sixteen of a request's and a batch's
    partition and the nested one: resolved by every sink, observed like
    the others, and in no tuple the idle states of a trace are built on."""
    registry, sink = bound
    assert len(tracing.POOL_STAGES) == len(set(tracing.POOL_STAGES)) == 8
    assert all(name.startswith("pool.") for name in tracing.POOL_STAGES)
    assert not set(tracing.POOL_STAGES) & set(
        tracing.STAGES + tracing.NESTED_STAGES
    )
    assert len(tracing.STAGES) == 16
    for names in profiling.IDLE_STATES:
        assert not set(names[1]) & set(tracing.POOL_STAGES)
    assert not set(profiling._HANDLER_STAGES) & set(tracing.POOL_STAGES)
    sink.bind(tenant="t007")
    for name in tracing.POOL_STAGES:
        with tracing.stage(name):
            pass
        assert _count(registry, name) == 1, name


def test_a_sink_of_some_stages_leaves_the_others_to_the_process(bound):
    """The model pool's loader binds a sink of `POOL_STAGES` alone: those
    observe in its registry; a warm-up's `predict.*` stages, its launch
    counts and a model's own counters stay the process's; a name that is
    no stage is still an error."""
    registry = MetricRegistry()  # `bound` only restores the context
    tracing.StageSink(registry, tracing.POOL_STAGES).bind(tenant="t007")
    process = tracing.get_registry()
    before = _count(process, tracing.PREDICT_ENQUEUE)

    def launches():
        found = process.to_dict()["pio_device_launch_calls_total"]["samples"]
        return sum(s["value"] for s in found)

    launched = launches()
    with tracing.stage(tracing.POOL_LOAD):
        with tracing.stage(tracing.PREDICT_ENQUEUE):
            tracing.launch_call()
    assert _count(registry, tracing.POOL_LOAD) == 1
    assert _count(process, tracing.PREDICT_ENQUEUE) == before + 1
    stages = registry.to_dict()["pio_stage_seconds"]["samples"]
    assert {s["labels"]["stage"] for s in stages} == set(tracing.POOL_STAGES)
    assert launches() == launched + 1
    assert tracing.bound_registry() is process
    with pytest.raises(ValueError, match="unknown stage"):
        tracing.stage("pool.loaded")
