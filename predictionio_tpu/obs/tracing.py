"""Span-based distributed tracing with a Perfetto-exportable flight
recorder.

PR 1's metrics answer *how much* and *how often*; this module answers
*why was this one request slow*: every request gets a span tree — root
HTTP span, storage-call spans, a batch-dispatch span linked to every
query it coalesced — keyed by trace ID = request ID, so the timeline a
TensorFlow-serving or Podracer operator reads off a step trace exists
here natively, without ``jax.profiler``.

Design constraints, in priority order:

* **near-free when off** — a disabled tracer costs the hot path one
  contextvar read (``current_span()`` returning ``None``) and nothing
  else: no span objects, no clock reads, no locks. ``span()`` and
  ``Tracer.trace`` return the shared :data:`NOOP` singleton.
* **hard memory bounds** — completed traces land in a ring buffer
  (``deque(maxlen=...)``); the flight recorder keeps only the N slowest
  request traces (min-heap on root duration); open traces are capped in
  count and in spans per trace. Nothing grows with traffic.
* **one clock** — every timestamp is ``_EPOCH + perf_counter()`` so
  parent/child intervals nest strictly within a process regardless of
  wall-clock adjustment.

Propagation: the trace ID rides the existing ``X-Request-ID``
contextvar/header; ``X-Parent-Span`` carries the caller's span ID on
outbound hops (client SDK, httpstore), so event-server → store-server
and engine → store calls join one distributed trace. Span trees are
keyed internally by root span ID, not trace ID — two servers in one
process handling the same distributed trace record two linked trees
instead of corrupting each other.

Stages: :func:`stage` times the fixed boundaries a request crosses
between the two sockets (read, admit, decode, submit, await, serve,
respond on the handler thread; window, backpressure, prep, enqueue on
the batcher's; device_get, materialize, settle on the completer's)
into ``pio_stage_seconds{stage}`` and, through a profiler annotation
of the same name, onto the clock of any ``jax.profiler`` trace, so a
device trace shows what the host was doing in every gap
(docs/observability.md "Stages"). While that profiler runs, and only
then, a stage also reads its thread's CPU clock: wall minus CPU is
the time the stage spent off the CPU.

Export: ``Tracer.chrome_trace()`` renders Chrome trace-event JSON that
loads directly in Perfetto (https://ui.perfetto.dev) — served at
``GET /debug/traces`` by every server, pulled by ``pio-tpu trace``.
"""

from __future__ import annotations

import contextvars
import heapq
import logging
import os
import threading
import time
from collections import OrderedDict, deque

from predictionio_tpu.obs.context import ID_OK, new_id
from predictionio_tpu.obs.registry import (
    STAGE_BUCKETS,
    MetricRegistry,
    get_registry,
)

logger = logging.getLogger(__name__)

#: the one clock: wall-clock anchor for the monotonic perf counter, so
#: timestamps are epoch-meaningful AND nest strictly. Exempt from the
#: wall-clock lint rule: time.time() is read exactly once, at import,
#: to anchor the epoch; every duration is measured by perf_counter
#: deltas on top of it, so an NTP step after import can never reorder
#: or stretch spans (it only offsets all absolute timestamps equally).
_EPOCH = time.time() - time.perf_counter()  # pio-lint: disable=wall-clock -- one-shot epoch anchor; durations use perf_counter

#: header carrying the caller's span ID on outbound hops (the trace ID
#: itself rides X-Request-ID)
PARENT_SPAN_HEADER = "X-Parent-Span"

_current_span: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "pio_span", default=None
)

#: stages are a closed set of 16 names and run once a request each; the
#: bound only keeps a span that lives for hours (``pio_train``) small
_MAX_STAGES_PER_SPAN = 64

#: key under which a finalized trace holds the spans whose stages have
#: not been made child spans yet (never exported: ``_snapshot`` pops it)
_STAGED = "stagedSpans"


def now() -> float:
    """Epoch seconds on the perf_counter clock (monotonic-consistent)."""
    return _EPOCH + time.perf_counter()


def new_span_id() -> str:
    return new_id()


def _json_safe(value, depth: int = 3):
    """Caller-supplied span attributes, coerced to plain JSON: non-str
    dict keys become strings, unknown types become ``str(value)``, and
    the depth bound makes circular structures harmless — one weird
    attribute must never make the recorder unscrapeable or fail a
    training run's timeline write."""
    if value is None or isinstance(value, (str, int, float, bool)):
        return value
    if depth <= 0:
        return str(value)
    if isinstance(value, (list, tuple)):
        return [_json_safe(v, depth - 1) for v in value]
    if isinstance(value, dict):
        return {
            str(k): _json_safe(v, depth - 1) for k, v in value.items()
        }
    return str(value)


def sanitize_id(raw: str | None) -> str | None:
    """A forwarded span/trace ID, or None when absent or malformed
    (same acceptance as request IDs — obs.context.ID_OK)."""
    if raw and ID_OK.match(raw):
        return raw
    return None


def current_span() -> "Span | None":
    """The active span for this context (one contextvar read — this is
    the entire hot-path cost when tracing is off)."""
    return _current_span.get()


class _NoopSpan:
    """Shared do-nothing context manager for the disabled path.

    ``__enter__`` returns ``None`` so instrumentation sites can guard
    attribute writes with ``if sp is not None``.
    """

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NOOP = _NoopSpan()


class Span:
    """One timed operation; also its own context manager.

    ``trace_id`` groups spans across processes (it is the request ID);
    ``trace_key`` (the local root's span ID) groups them within one
    tracer, so two local trees of the same distributed trace — e.g. an
    event server and a store server sharing a process — never collide.
    """

    __slots__ = (
        "tracer",
        "trace_id",
        "trace_key",
        "span_id",
        "parent_id",
        "name",
        "start",
        "duration",
        "attributes",
        "root",
        "stages",
        "_token",
    )

    def __init__(
        self,
        tracer: "Tracer",
        trace_id: str,
        name: str,
        parent_id: str | None = None,
        trace_key: str | None = None,
        attributes: dict | None = None,
        root: bool = False,
    ):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.trace_key = trace_key if trace_key is not None else self.span_id
        self.parent_id = parent_id
        self.name = name
        self.start = 0.0
        self.duration = 0.0
        self.attributes = dict(attributes) if attributes else {}
        self.root = root
        #: ``(name, perf_counter at start, seconds, error or None)`` of
        #: the stages that ran under this span; they become child spans
        #: when the trace is finalized (:func:`stage`)
        self.stages: list | None = None
        self._token = None

    def set(self, key: str, value) -> None:
        self.attributes[key] = value

    def add_stage(
        self, name: str, t0: float, seconds: float, error: str | None
    ) -> None:
        if self.stages is None:
            self.stages = []
        if len(self.stages) < _MAX_STAGES_PER_SPAN:
            self.stages.append((name, t0, seconds, error))

    def __enter__(self) -> "Span":
        self.start = now()
        if self.root:
            self.tracer._open(self.trace_key)
        self._token = _current_span.set(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        self.duration = now() - self.start
        if self._token is not None:
            _current_span.reset(self._token)
            self._token = None
        if exc_type is not None and "error" not in self.attributes:
            self.attributes["error"] = f"{exc_type.__name__}: {exc}"
        if self.root:
            # the root bypasses record()'s span cap — a capped trace
            # must still render its root bar
            self.tracer._finalize(self)
        else:
            self.tracer.record(self)
        return False

    def to_dict(self) -> dict:
        return {
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "name": self.name,
            "start": round(self.start, 6),
            "durationMs": round(self.duration * 1000, 3),
            "attributes": _json_safe(self.attributes),
        }

    def stage_dicts(self) -> list[dict]:
        """This span's stages as child spans, in :meth:`to_dict`'s form."""
        return [
            {
                "traceId": self.trace_id,
                "spanId": new_span_id(),
                "parentId": self.span_id,
                "name": name,
                "start": round(_EPOCH + t0, 6),
                "durationMs": round(seconds * 1000, 3),
                "attributes": {"error": error} if error else {},
            }
            for name, t0, seconds, error in self.stages or ()
        ]


class _TraceBuf:
    """Spans of one open (root not yet closed) trace, span-capped."""

    __slots__ = ("spans", "dropped")

    def __init__(self):
        self.spans: list[Span] = []
        self.dropped = 0


class Tracer:
    """Bounded per-process span recorder.

    * ``trace(...)`` opens a ROOT span: its completion finalizes the
      trace into the ring buffer and (if among the N slowest) the
      flight recorder.
    * child spans come from :func:`span`, which attaches to the
      *parent's* tracer — instrumentation sites never need a tracer
      reference, and per-server tracers (tests, multi-tenant) work.
    * ``record(...)`` accepts an externally-built finished span (the
      micro-batcher's dispatch span copies).
    """

    def __init__(
        self,
        max_traces: int = 128,
        flight_slots: int = 16,
        max_spans_per_trace: int = 256,
        max_open_traces: int = 512,
        enabled: bool = True,
    ):
        self.enabled = enabled
        self._max_spans = max_spans_per_trace
        self._max_open = max_open_traces
        self._flight_slots = flight_slots
        self._lock = threading.Lock()
        self._open_traces: OrderedDict[str, _TraceBuf] = OrderedDict()
        self._ring: deque[dict] = deque(maxlen=max_traces)
        #: min-heap of (root duration, seq, trace) — N slowest retained
        self._flight: list[tuple[float, int, dict]] = []
        self._seq = 0
        #: open traces evicted at the cap — their spans are lost; the
        #: count is surfaced so that loss is diagnosable, not silent
        self._abandoned = 0

    # -- span construction -------------------------------------------------

    def trace(
        self,
        name: str,
        trace_id: str | None = None,
        parent_id: str | None = None,
        attributes: dict | None = None,
    ):
        """Root-span context manager for a new local trace; the shared
        no-op when disabled. ``trace_id`` is the request ID;
        ``parent_id`` is a forwarded remote span (``X-Parent-Span``)."""
        if not self.enabled:
            return NOOP
        return Span(
            self,
            trace_id or new_span_id(),
            name,
            parent_id=parent_id,
            attributes=attributes,
            root=True,
        )

    def child(self, parent: Span, name: str, attributes: dict | None = None):
        return Span(
            self,
            parent.trace_id,
            name,
            parent_id=parent.span_id,
            trace_key=parent.trace_key,
            attributes=attributes,
        )

    # -- recording ---------------------------------------------------------

    def _open(self, trace_key: str) -> None:
        evicted = []
        with self._lock:
            self._open_traces.pop(trace_key, None)
            while len(self._open_traces) >= self._max_open:
                # oldest open trace is abandoned (a root that never
                # closes must not leak memory forever) — counted and
                # logged, because the oldest open trace can be a
                # long-lived one you care about (a pio_train root in a
                # trainer that also serves)
                evicted.append(self._open_traces.popitem(last=False)[0])
                self._abandoned += 1
            self._open_traces[trace_key] = _TraceBuf()
        for key in evicted:
            logger.debug(
                "abandoned open trace %s at the open-trace cap; its "
                "spans are lost", key,
            )

    def record(self, span: Span) -> None:
        """A finished span joins its open trace; spans whose root is
        gone (or never existed) are dropped — nothing orphaned leaks."""
        with self._lock:
            buf = self._open_traces.get(span.trace_key)
            if buf is None:
                return
            if len(buf.spans) >= self._max_spans:
                buf.dropped += 1
                return
            buf.spans.append(span)

    def _finalize(self, root: Span) -> None:
        with self._lock:
            buf = self._open_traces.pop(root.trace_key, None)
            if buf is None:
                return
            buf.spans.append(root)
            trace = {
                "traceId": root.trace_id,
                "rootSpanId": root.span_id,
                "root": root.name,
                "start": round(root.start, 6),
                "durationMs": round(root.duration * 1000, 3),
                "droppedSpans": buf.dropped,
                "spans": [s.to_dict() for s in buf.spans],
            }
            staged = [s for s in buf.spans if s.stages]
            if staged:
                # stages become child spans when the trace is READ
                # (_snapshot): every request pays for noting them, only
                # an export for building them
                trace[_STAGED] = staged
            self._ring.append(trace)
            self._seq += 1
            item = (root.duration, self._seq, trace)
            if len(self._flight) < self._flight_slots:
                heapq.heappush(self._flight, item)
            elif root.duration > self._flight[0][0]:
                heapq.heapreplace(self._flight, item)

    def clear(self) -> None:
        with self._lock:
            self._open_traces.clear()
            self._ring.clear()
            self._flight.clear()

    # -- export ------------------------------------------------------------

    def _snapshot(self) -> tuple[list[dict], list[dict]]:
        """(ring oldest-first, flight slowest-first) under one lock."""
        with self._lock:
            ring = list(self._ring)
            flight = [
                t for _d, _s, t in sorted(
                    self._flight, key=lambda it: -it[0]
                )
            ]
            for trace in (*ring, *flight):
                for span in trace.pop(_STAGED, ()):
                    trace["spans"].extend(span.stage_dicts())
        return ring, flight

    def traces(self) -> list[dict]:
        """Everything retained: ring (oldest first), then flight-only
        traces the ring has since evicted (slowest first)."""
        ring, flight = self._snapshot()
        seen = {t["rootSpanId"] for t in ring}
        return ring + [t for t in flight if t["rootSpanId"] not in seen]

    def to_dict(self) -> dict:
        """Raw spans (``GET /debug/traces.json``)."""
        ring, flight = self._snapshot()
        return {
            "traces": ring,
            "flight": flight,
            "abandonedOpenTraces": self._abandoned,
        }

    def chrome_trace(self, trace_id: str | None = None) -> dict:
        """Chrome trace-event JSON (Perfetto-loadable). Each retained
        trace renders as one "process" (pid) named after its trace ID;
        two local trees of one distributed trace share a pid. Spans
        within a trace are laid onto tracks (tid) so only strictly
        nested intervals share one — Perfetto's slice stack mis-renders
        partially-overlapping siblings on a single track (e.g. two
        algorithms' concurrent batch dispatches)."""
        records = self.traces()
        if trace_id is not None:
            records = [r for r in records if r["traceId"] == trace_id]
        events: list[dict] = []
        pid_by_trace: dict[str, int] = {}
        for rec in records:
            pid = pid_by_trace.get(rec["traceId"])
            if pid is None:
                pid = pid_by_trace[rec["traceId"]] = len(pid_by_trace) + 1
                events.append(
                    {
                        "name": "process_name",
                        "ph": "M",
                        "pid": pid,
                        "tid": 0,
                        "args": {
                            "name": (
                                f"trace {rec['traceId']} ({rec['root']})"
                            )
                        },
                    }
                )
            for s, tid in _assign_lanes(rec["spans"]):
                events.append(
                    {
                        "name": s["name"],
                        "cat": "pio",
                        "ph": "X",
                        "pid": pid,
                        "tid": tid,
                        "ts": round(s["start"] * 1e6, 3),
                        "dur": round(s["durationMs"] * 1000, 3),
                        "args": {
                            "traceId": s["traceId"],
                            "spanId": s["spanId"],
                            "parentId": s["parentId"],
                            **s["attributes"],
                        },
                    }
                )
        return {"traceEvents": events, "displayTimeUnit": "ms"}


#: lane-fit tolerance: span starts are exported at 1e-6 s precision and
#: durations at 1e-6 s (3 dp of ms), so rounding can displace an
#: interval edge by ~1 µs either way — anything tighter kicks truly
#: nested or back-to-back spans onto a spurious "concurrent" track
_LANE_EPS = 2e-6


def _assign_lanes(spans: list[dict]) -> list[tuple[dict, int]]:
    """Greedy flame-graph track assignment: a span shares a track with
    the spans it strictly nests inside; a partial overlap (concurrent
    siblings) opens the next track. Returns (span, tid) pairs."""
    ordered = sorted(
        spans, key=lambda s: (s["start"], -s["durationMs"])
    )
    #: per track, the stack of currently-open interval end times
    tracks: list[list[float]] = []
    out: list[tuple[dict, int]] = []
    for s in ordered:
        start = s["start"]
        end = start + s["durationMs"] / 1000.0
        tid = None
        for i, stack in enumerate(tracks):
            while stack and stack[-1] <= start + _LANE_EPS:
                stack.pop()
            if not stack or end <= stack[-1] + _LANE_EPS:
                stack.append(end)
                tid = i + 1
                break
        if tid is None:
            tracks.append([end])
            tid = len(tracks)
        out.append((s, tid))
    return out


def span(name: str, **attributes):
    """Child span of the current context span, recorded into the
    tracer that owns the current trace. Off-trace (no root open on this
    context) or with tracing disabled this is the shared no-op — the
    instrumentation cost is one contextvar read."""
    parent = _current_span.get()
    if parent is None:
        return NOOP
    return parent.tracer.child(parent, name, attributes or None)


# -- stages -------------------------------------------------------------------

HTTP_READ = "http.read"
HTTP_ADMIT = "http.admit"
ENGINE_DECODE = "engine.decode"
ENGINE_SUBMIT = "engine.submit"
ENGINE_AWAIT = "engine.await"
ENGINE_SERVE = "engine.serve"
HTTP_RESPOND = "http.respond"
HTTP_ENCODE = "http.encode"
HTTP_WRITE = "http.write"
BATCH_WINDOW = "batch.window"
BATCH_BACKPRESSURE = "batch.backpressure"
PREDICT_PREP = "predict.prep"
PREDICT_ENQUEUE = "predict.enqueue"
PREDICT_DEVICE_GET = "predict.device_get"
PREDICT_MATERIALIZE = "predict.materialize"
BATCH_SETTLE = "batch.settle"

#: the closed set: one child of ``pio_stage_seconds`` each, resolved
#: when a sink is built, never on the hot path
STAGES = (
    HTTP_READ, HTTP_ADMIT, ENGINE_DECODE, ENGINE_SUBMIT, ENGINE_AWAIT,
    ENGINE_SERVE, HTTP_RESPOND, HTTP_ENCODE, HTTP_WRITE, BATCH_WINDOW,
    BATCH_BACKPRESSURE, PREDICT_PREP, PREDICT_ENQUEUE,
    PREDICT_DEVICE_GET, PREDICT_MATERIALIZE, BATCH_SETTLE,
)

#: stages that run INSIDE a stage of :data:`STAGES` (``predict.rules``:
#: the e-commerce template's event-store lookups, inside
#: ``predict.prep``). Observed and annotated like the others, but no part
#: of the partition: the sums over :data:`STAGES` and the idle states
#: built on them already hold their time
PREDICT_RULES = "predict.rules"
NESTED_STAGES = (PREDICT_RULES,)

#: the model pool's stages (serving/modelpool.py; multi-tenant serving).
#: ``pool.wait`` runs on a handler's thread, between ``engine.decode``
#: and ``engine.submit``: the time a request whose tenant was not
#: resident spent inside ``ModelPool.pin`` until the cold load it asked
#: for (or joined) was in. It is a stage of the request's partition, so
#: the handler's stages still add up to the request; a lookup that hits
#: opens no stage. The others run on the pool's one loader thread and
#: are no part of any request: ``pool.load`` around a whole cold load,
#: and nested in it ``pool.read`` (the blob out of the model store,
#: checksum included), ``pool.deserialize`` (the unpickle, id maps
#: included), ``pool.promote`` (the tables to the device; the quantizer
#: where the server has one), ``pool.warmup`` (the compile buckets) and
#: ``pool.batchers``; ``pool.close`` around the ``close_fn`` of an
#: evicted or replaced generation. A group of their own: :data:`STAGES`
#: is the tuple the idle states of a device trace are built on, and no
#: load belongs to a batch
POOL_WAIT = "pool.wait"
POOL_LOAD = "pool.load"
POOL_READ = "pool.read"
POOL_DESERIALIZE = "pool.deserialize"
POOL_PROMOTE = "pool.promote"
POOL_WARMUP = "pool.warmup"
POOL_BATCHERS = "pool.batchers"
POOL_CLOSE = "pool.close"
POOL_STAGES = (
    POOL_WAIT, POOL_LOAD, POOL_READ, POOL_DESERIALIZE, POOL_PROMOTE,
    POOL_WARMUP, POOL_BATCHERS, POOL_CLOSE,
)

#: ``factory(name, **keywords)`` -> context manager that writes a host
#: event into a running profiler's trace, and ``active()`` -> whether a
#: profiler runs (a flag test; a stage builds no annotation while none
#: does). Installed once by the code that imports JAX (utils/profiling:
#: ``jax.profiler.TraceAnnotation`` and its ``is_enabled``); without
#: them ``obs/`` stays free of JAX and a stage is histogram-only.
_annotation_factory = None


def _no_profiler() -> bool:
    return False


_annotation_active = _no_profiler


def set_annotation_factory(factory, active) -> None:
    global _annotation_factory, _annotation_active
    _annotation_factory = factory
    _annotation_active = active


#: (children of pio_stage_seconds by stage, annotation keywords) bound
#: to this thread of a server: the handler binds its request ID, the
#: batcher and the completer their batch's number and size
_bound_stages: contextvars.ContextVar[tuple[dict, dict] | None] = (
    contextvars.ContextVar("pio_stages", default=None)
)


class _StageChildren(dict):
    """The children of ``pio_stage_seconds`` by stage, the registry they
    belong to (`bound_registry`), its launch counter (`launch_call`), and
    by stage the children of the two families a stage touches only while
    a profiler runs (`clocks`: ``pio_stage_cpu_seconds``,
    ``pio_stage_profiled_seconds_total``)."""

    __slots__ = ("registry", "launch_calls", "clocks")


def _clock_families(registry: MetricRegistry) -> tuple:
    """The two families a stage touches only while a profiler runs."""
    cpu = registry.histogram(
        "pio_stage_cpu_seconds",
        "Thread CPU time of one stage, observed only while a profiler "
        "runs (docs/observability.md \"Stages\")",
        ("stage",),
        buckets=STAGE_BUCKETS,
    )
    wall = registry.counter(
        "pio_stage_profiled_seconds_total",
        "Wall time of the stages pio_stage_cpu_seconds observed, the "
        "same instances: wall minus CPU is their time off the CPU",
        ("stage",),
    )
    return cpu, wall


def stage_clock_totals(
    registry: MetricRegistry | None = None,
) -> dict[str, tuple[int, float, float]]:
    """``{stage: (instances, CPU seconds, wall seconds)}`` that the
    profiled stages of ``registry`` (``None``: the process's) have added
    up so far; two readings a profile apart give that profile's."""
    cpu, wall = _clock_families(
        get_registry() if registry is None else registry
    )
    walls = {values[0]: child.value for values, child in wall.samples()}
    return {
        values[0]: (child.count, child.sum, walls.get(values[0], 0.0))
        for values, child in cpu.samples()
    }


class StageSink:
    """``pio_stage_seconds{stage}`` of one registry (``None``: the
    process's), and beside it ``pio_stage_cpu_seconds{stage}`` and
    ``pio_stage_profiled_seconds_total{stage}``, which count only while
    a profiler runs; every child resolved here. A server builds one at
    wiring time and each of its threads binds it (with the keywords its
    annotations carry) before the stages it runs. A sink of ``names``
    alone (the model pool's loader: :data:`POOL_STAGES`) takes those
    stages; what else its thread runs (a warm-up's ``predict.*``, its
    launches, a model's own counters) stays the process's, so that a
    server's series of served batches hold no warm-up."""

    __slots__ = ("_children",)

    def __init__(
        self, registry: MetricRegistry | None,
        names: tuple[str, ...] | None = None,
    ):
        if registry is None:
            registry = get_registry()
        family = registry.histogram(
            "pio_stage_seconds",
            "Time in one stage of a request, post or batch between "
            "the two sockets (docs/observability.md \"Stages\")",
            ("stage",),
            buckets=STAGE_BUCKETS,
        )
        stages = names or STAGES + NESTED_STAGES + POOL_STAGES
        self._children = _StageChildren(
            (name, family.labels(name)) for name in stages
        )
        cpu, wall = _clock_families(registry)
        self._children.clocks = {
            name: (cpu.labels(name), wall.labels(name)) for name in stages
        }
        if names is not None:
            registry = get_registry()
        self._children.registry = registry
        self._children.launch_calls = registry.counter(
            "pio_device_launch_calls_total",
            "Hand-overs to the runtime made by a predict launch: a "
            "jitted program, or an upload by a call of its own (over "
            "pio_batches_total: 1 where a launch is one call)",
        )

    def bind(self, **keywords) -> None:
        """Stages on this context observe here from now on, and their
        annotations carry ``keywords`` (request_id=, or batch= and n=)."""
        _bound_stages.set((self._children, keywords))


#: where a stage observes on a context no server has bound (a model's
#: predict called from an evaluation, a batcher built with no registry)
_default_sink = StageSink(None)


def _bound_children() -> _StageChildren:
    return (_bound_stages.get() or (_default_sink._children,))[0]


def bound_registry() -> MetricRegistry:
    """The registry of the server whose sink this context bound (its
    batcher's thread inside a predict, a handler's thread), else the
    process's: where a model's own counters belong."""
    return _bound_children().registry


def launch_call(calls: int = 1) -> None:
    """Count ``calls`` hand-overs to the runtime (a jitted program, or
    an upload made by a call of its own) of a predict launch, beside the
    call, on the registry this context is bound to. Each lets go of the
    interpreter lock on the batcher's thread and waits to get it back."""
    _bound_children().launch_calls.inc(calls)


def _clocks_of(name: str) -> tuple:
    """``(pio_stage_cpu_seconds, pio_stage_profiled_seconds_total)``
    children of ``name`` on the sink :func:`stage` took its
    ``pio_stage_seconds`` child from: looked up only while a profiler
    runs."""
    clocks = _bound_children().clocks.get(name)
    return clocks or _default_sink._children.clocks[name]


class _Stage:
    __slots__ = (
        "_name", "_child", "_keywords", "_annotation", "_t0", "_cpu0",
        "_clocks",
    )

    def __init__(self, name: str, child, keywords: dict):
        self._name = name
        self._child = child
        self._keywords = keywords
        self._annotation = None

    def __enter__(self) -> "_Stage":
        if _annotation_active():
            self._annotation = _annotation_factory(
                self._name, **self._keywords
            )
            self._annotation.__enter__()
            self._clocks = _clocks_of(self._name)
            self._t0 = time.perf_counter()
            self._cpu0 = time.thread_time()
            return self
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t0 = self._t0
        seconds = time.perf_counter() - t0
        # the thread clock is read only where __enter__ read it: while no
        # profiler runs (and for a stage it started inside) never
        annotation = self._annotation
        if annotation is not None:
            cpu = time.thread_time() - self._cpu0
        self._child.observe(seconds)
        parent = _current_span.get()
        if parent is not None and parent.tracer.enabled:
            # noted on the open span, made a child span when its trace
            # is finalized: the stage never becomes the context's
            # current span, so what it encloses (a batcher submit, a
            # store call) keeps hanging off the request's own span
            parent.add_stage(
                self._name, t0, seconds,
                f"{exc_type.__name__}: {exc}" if exc_type else None,
            )
        if annotation is not None:
            cpu_child, wall_child = self._clocks
            cpu_child.observe(cpu)
            wall_child.inc(seconds)
            annotation.__exit__(exc_type, exc, tb)
        return False


def stage(name: str) -> _Stage:
    """Context manager around one stage (a name of :data:`STAGES`,
    :data:`NESTED_STAGES` or :data:`POOL_STAGES`) of a
    request, a post or a batch — never of one query inside a post or
    of one item. Always observes ``pio_stage_seconds{stage}``; while a
    profiler runs (whoever started it) it is an annotation of the same
    name, so any profiler session sees the stage on the device trace's
    clock, and it reads the thread's CPU clock beside the wall into
    ``pio_stage_cpu_seconds{stage}`` and
    ``pio_stage_profiled_seconds_total{stage}``; and under an open span
    of an enabled tracer it is also that span's child in the finished
    trace."""
    children, keywords = _bound_stages.get() or (
        _default_sink._children, {}
    )
    child = children.get(name)
    if child is None:
        # a sink of some stages alone leaves the others to the process
        child = _default_sink._children.get(name)
        if child is None:
            raise ValueError(
                f"unknown stage {name!r}: stages are a closed set "
                "(obs.tracing.STAGES)"
            )
    return _Stage(name, child, keywords)


#: process-global tracer (every server defaults to it, like the default
#: metric registry); PIO_TRACING=0 disables it at startup
_default_tracer = Tracer(
    enabled=os.environ.get("PIO_TRACING", "1").lower()
    not in ("0", "false", "no")
)


def get_tracer() -> Tracer:
    return _default_tracer
