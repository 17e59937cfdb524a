"""Tracing / profiling subsystem.

The reference has no profiler beyond per-request latency counters and
the Spark UI (SURVEY.md §5 "Tracing / profiling"); the TPU build makes
this first-class:

* :class:`StepTimer` — per-step wall-clock records for training loops
  (ALS logs one record per alternating solve), queryable and
  JSON-serializable for run metadata.
* :func:`trace` — context manager around ``jax.profiler`` producing a
  Perfetto/TensorBoard trace when a directory is given (or the
  ``PIO_TRACE_DIR`` env var is set); no-op otherwise.
* :func:`summarize` — one timeline out of such a trace: device busy
  and idle time, device time by ``jax.named_scope``, and the idle time
  put down to what the host was doing (the stages of ``obs/tracing``,
  which ride the trace as annotations on the profiler's own clock).

Timing syncs through :func:`sync`, the one device barrier of the
package.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import io
import json
import logging
import os
import re
import tarfile
import tempfile
import time
import uuid
from collections import defaultdict

import jax

from predictionio_tpu.obs import tracing

logger = logging.getLogger(__name__)

# every stage of obs/tracing is an annotation from here on while a
# profiler runs (a flag test while none does): a host event on the
# device trace's clock in any profiler session, capture() below or one
# a caller started
tracing.set_annotation_factory(
    jax.profiler.TraceAnnotation, jax.profiler.TraceAnnotation.is_enabled
)


def sync(value) -> None:
    """Device barrier: wait until every array in ``value`` (any pytree;
    non-arrays pass through) is computed. ``block_until_ready`` does
    block on a host-attached TPU — timed on a v5e against a scalar
    fetch of the same result, both waited the full 1.89 s of a long
    matmul chain — so nothing is copied to the host."""
    jax.block_until_ready(value)


class StepTimer:
    """Named per-step wall-clock records."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.records: dict[str, list[float]] = defaultdict(list)

    @contextlib.contextmanager
    def step(self, name: str, sync_value=None):
        # each step is also a tracing span (no-op outside an open
        # trace), so `pio train` emits the same Perfetto timeline the
        # serving stack does
        if not self.enabled:
            yield
            return
        with tracing.span(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                if sync_value is not None:
                    sync(sync_value)
                self.records[name].append(time.perf_counter() - t0)

    def record(self, name: str, seconds: float) -> None:
        if self.enabled:
            self.records[name].append(seconds)

    def summary(self) -> dict[str, dict[str, float]]:
        out = {}
        for name, xs in self.records.items():
            out[name] = {
                "count": len(xs),
                "total_s": round(sum(xs), 6),
                "mean_s": round(sum(xs) / len(xs), 6),
                "max_s": round(max(xs), 6),
            }
        return out

    def to_json(self) -> str:
        return json.dumps(self.summary())

    def publish(self, registry, name: str = "pio_train_step_seconds"):
        """Fold the records into a shared metric registry
        (:class:`~predictionio_tpu.obs.MetricRegistry`) as a per-step
        labeled histogram — the bridge that makes train-time timing
        scrapeable from the same ``/metrics`` surface as serving."""
        from predictionio_tpu.obs import TRAIN_STEP_BUCKETS

        hist = registry.histogram(
            name,
            "Training-loop step wall clock (StepTimer records)",
            ("step",),
            buckets=TRAIN_STEP_BUCKETS,
        )
        for step, xs in self.records.items():
            child = hist.labels(step)
            for seconds in xs:
                child.observe(seconds)
        return hist

    def log_summary(self, prefix: str = "") -> None:
        for name, s in self.summary().items():
            logger.info(
                "%s%s: %d step(s), mean %.4fs, total %.2fs",
                prefix,
                name,
                s["count"],
                s["mean_s"],
                s["total_s"],
            )


@contextlib.contextmanager
def trace(trace_dir: str | None = None):
    """JAX profiler trace (Perfetto/TensorBoard) when a dir is given or
    PIO_TRACE_DIR is set; transparent otherwise. Python's own tracer
    stays off: it multiplies the host's work (the device of a serving
    cell read 85% idle under it and 58% without, PERF.md), which is
    what a trace is taken to measure, and the stages of obs/tracing
    name the host's side of the timeline without it."""
    trace_dir = trace_dir or os.environ.get("PIO_TRACE_DIR")
    if not trace_dir:
        yield
        return
    os.makedirs(trace_dir, exist_ok=True)
    logger.info("writing profiler trace to %s", trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=options):
        yield


def capture(
    duration_s: float,
    tracer: "tracing.Tracer | None" = None,
    device_sample_fn=None,
    out_dir: str | None = None,
) -> dict:
    """On-demand profile capture (the ``POST /debug/profile`` body of
    docs/observability.md): run a duration-bounded :func:`trace`
    (jax.profiler: XLA timeline and the stages' host events) and
    snapshot the same window's
    flight-recorder spans (Perfetto-loadable Chrome trace-event JSON)
    plus the current device gauges into ONE artifact directory:

    * ``jax_trace/`` — the jax.profiler output (TensorBoard/Perfetto)
    * ``summary.json`` — :func:`summarize` of that trace: what the
      device did, and what the host was doing while it idled
    * ``spans.json`` — the tracing flight recorder's chrome trace (its
      own clock; the stages in ``jax_trace/`` are on the profiler's)
    * ``device.json`` — HBM/live-array sample (when a sampler is given)
    * ``manifest.json`` — id, window, file list

    Returns the manifest. The artifact root is ``out_dir``, else
    ``PIO_PROFILE_DIR``, else a fresh temp dir."""
    art_id = uuid.uuid4().hex[:12]
    base = (
        out_dir
        or os.environ.get("PIO_PROFILE_DIR")
        or tempfile.mkdtemp(prefix="pio-profile-")
    )
    artifact_dir = os.path.join(base, f"profile-{art_id}")
    trace_dir = os.path.join(artifact_dir, "jax_trace")
    os.makedirs(trace_dir, exist_ok=True)
    t0 = time.perf_counter()
    with trace(trace_dir):
        time.sleep(max(0.0, duration_s))
    elapsed = time.perf_counter() - t0
    tracer = tracer if tracer is not None else tracing.get_tracer()
    with open(os.path.join(artifact_dir, "spans.json"), "w") as f:
        json.dump(tracer.chrome_trace(), f, default=str)
    with open(os.path.join(artifact_dir, "summary.json"), "w") as f:
        json.dump(summarize(trace_dir), f, indent=2)
    files = ["jax_trace/", "manifest.json", "spans.json", "summary.json"]
    if device_sample_fn is not None:
        try:
            sample = device_sample_fn()
        except Exception:  # noqa: BLE001 - capture must not fail on a flaky backend read
            sample = None
        if sample is not None:
            with open(
                os.path.join(artifact_dir, "device.json"), "w"
            ) as f:
                json.dump(sample, f)
            files.append("device.json")
    manifest = {
        "id": art_id,
        "durationS": round(elapsed, 6),
        "artifactDir": artifact_dir,
        "files": sorted(files),
    }
    with open(os.path.join(artifact_dir, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2)
    logger.info(
        "profile capture %s: %.2fs window -> %s",
        art_id, elapsed, artifact_dir,
    )
    return manifest


def bundle(artifact_dir: str) -> bytes:
    """One capture artifact as an in-memory ``tar.gz`` — the
    ``/debug/profile`` response ships it base64-encoded and
    ``pio-tpu profile`` extracts it locally."""
    buf = io.BytesIO()
    arcname = os.path.basename(artifact_dir.rstrip(os.sep))
    with tarfile.open(fileobj=buf, mode="w:gz") as tar:
        tar.add(artifact_dir, arcname=arcname)
    return buf.getvalue()


# -- one timeline -----------------------------------------------------------

#: what the host was doing while the device idled: every idle instant
#: goes to the FIRST state one of whose stages is open on any thread
IDLE_STATES = (
    ("launch", (tracing.PREDICT_PREP, tracing.PREDICT_ENQUEUE)),
    ("device_get", (tracing.PREDICT_DEVICE_GET,)),
    ("materialize_settle",
     (tracing.PREDICT_MATERIALIZE, tracing.BATCH_SETTLE)),
    ("backpressure", (tracing.BATCH_BACKPRESSURE,)),
    ("batch_window", (tracing.BATCH_WINDOW,)),
    ("request_in", (tracing.HTTP_READ, tracing.HTTP_ADMIT,
                    tracing.ENGINE_DECODE, tracing.ENGINE_SUBMIT)),
    ("response_out", (tracing.HTTP_RESPOND,)),
)
#: then, with no handler stage open at all: ``no_request``; what is
#: left (a request waits or is served, no named stage runs) is
#: ``unattributed``
_HANDLER_STAGES = (
    tracing.HTTP_READ, tracing.HTTP_ADMIT, tracing.ENGINE_DECODE,
    tracing.ENGINE_SUBMIT, tracing.ENGINE_AWAIT, tracing.ENGINE_SERVE,
    tracing.HTTP_RESPOND,
)
UNSCOPED = "(unscoped)"


def _union(intervals) -> list[tuple[int, int]]:
    """Sorted disjoint cover of ``(start, end)`` intervals."""
    out: list[tuple[int, int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def _intersect(xs, ys) -> list[tuple[int, int]]:
    """Of two sorted disjoint covers."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        start = max(xs[i][0], ys[j][0])
        end = min(xs[i][1], ys[j][1])
        if start < end:
            out.append((start, end))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(xs, ys) -> list[tuple[int, int]]:
    """``xs`` without ``ys``, both sorted disjoint covers."""
    out, j = [], 0
    for start, end in xs:
        while j < len(ys) and ys[j][1] <= start:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < end:
            if ys[k][0] > start:
                out.append((start, ys[k][0]))
            start = max(start, ys[k][1])
            k += 1
        if start < end:
            out.append((start, end))
    return out


def _seconds(cover) -> float:
    return sum(end - start for start, end in cover) / 1e9


def scope_of(op_name: str) -> str:
    """The ``jax.named_scope`` path of a device operation from the name
    XLA's metadata gives it: ``jit(f)/jit(g)/score/dot_general`` is
    ``score``. Transformation wrappers (``jit(...)``, ``vmap(...)``)
    and the trailing primitive drop out; no scope left is
    :data:`UNSCOPED`."""
    parts = [
        part for part in op_name.split("/")[:-1]
        if part and not part.endswith(")")
    ]
    return "/".join(parts) or UNSCOPED


def summarize_events(stage_events, device_events) -> dict:
    """The summary from events on ONE clock (nanoseconds):
    ``stage_events`` are ``(stage name, start, end)`` from any host
    thread, ``device_events`` ``(device plane, scope, start, end)`` of
    the operations that ran. The window runs from the first event's
    start to the last one's end; with several device planes the device
    numbers are their mean."""
    stage_events = list(stage_events)
    device_events = list(device_events)
    spans = [(s, e) for _n, s, e in stage_events] + [
        (s, e) for _p, _sc, s, e in device_events
    ]
    if not spans:
        return {}
    window = [(min(s for s, _e in spans), max(e for _s, e in spans))]
    by_stage: dict[str, list] = {}
    for name, start, end in stage_events:
        by_stage.setdefault(name, []).append((start, end))
    open_in = {
        state: _union(
            span for name in names for span in by_stage.get(name, ())
        )
        for state, names in IDLE_STATES
    }
    in_flight = _union(
        span for name in _HANDLER_STAGES for span in by_stage.get(name, ())
    )
    planes = sorted({p for p, _sc, _s, _e in device_events})
    busy_s, by_scope = 0.0, {}
    idle = {state: 0.0 for state, _names in IDLE_STATES}
    idle.update(no_request=0.0, unattributed=0.0)
    for plane in planes or [None]:  # no device: the whole window idles
        ops = [e for e in device_events if e[0] == plane]
        busy = _union((s, e) for _p, _sc, s, e in ops)
        busy_s += _seconds(busy)
        for _p, scope, start, end in ops:
            by_scope[scope] = by_scope.get(scope, 0.0) + (end - start) / 1e9
        left = _subtract(window, busy)
        for state, _names in IDLE_STATES:
            idle[state] += _seconds(_intersect(left, open_in[state]))
            left = _subtract(left, open_in[state])
        idle["unattributed"] += _seconds(_intersect(left, in_flight))
        idle["no_request"] += _seconds(_subtract(left, in_flight))
    n = max(1, len(planes))
    window_s = _seconds(window)
    idle = {state: seconds / n for state, seconds in idle.items()}
    idle_s = window_s - busy_s / n
    return {
        "window_s": window_s,
        "device": {
            "planes": len(planes),
            "busy_s": busy_s / n,
            "idle_s": idle_s,
            "idle_share": idle_s / window_s if window_s else 0.0,
            "by_scope": {
                scope: seconds / n for scope, seconds in sorted(
                    by_scope.items(), key=lambda kv: -kv[1]
                )
            },
        },
        "idle": idle,
        "idle_attributed_share": (
            1.0 - idle["unattributed"] / idle_s if idle_s > 0 else 1.0
        ),
        "stages": {
            name: {"count": len(spans), "total_s": _seconds(spans)}
            for name, spans in sorted(by_stage.items())
        },
    }


def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def _proto_fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field.
    The profiler's own reader (``jax.profiler.ProfileData``) gives
    events but not the metadata that carries an operation's
    ``jax.named_scope``; this walks just enough of the file for that."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        else:
            if kind == 2:
                size, at = _varint(buf, at)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"unsupported protobuf wire type {kind}")
            value = buf[at:at + size]
            at += size
        yield key >> 3, value


def _op_names(xplane_path: str) -> dict[tuple[str, int, str], str]:
    """``(device plane, program id, instruction text) -> op name`` for
    every operation a device plane's metadata describes: the ``tf_op``
    statistic, which holds ``jit(f)/scope/primitive``. Field numbers
    are those of tsl's ``xplane.proto``."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: dict[tuple[str, int, str], str] = {}
    for field, plane in _proto_fields(space):
        if field != 1:  # XSpace.planes
            continue
        name, stat_names, metadata = "", {}, []
        for field, value in _proto_fields(plane):
            if field == 2:  # XPlane.name
                name = bytes(value).decode()
            elif field == 4:  # event_metadata: map<int64, XEventMetadata>
                metadata.extend(
                    v for f, v in _proto_fields(value) if f == 2
                )
            elif field == 5:  # stat_metadata: map<int64, XStatMetadata>
                for f, v in _proto_fields(value):
                    if f == 2:
                        meta = dict(_proto_fields(v))
                        stat_names[meta.get(1, 0)] = bytes(
                            meta.get(2, b"")
                        ).decode()
        if not name.startswith("/device:"):
            continue
        for event in metadata:
            text, program, op_name = "", None, None
            for field, value in _proto_fields(event):
                if field == 2:  # XEventMetadata.name
                    text = bytes(value).decode()
                elif field == 5:  # XEventMetadata.stats
                    stat = dict(_proto_fields(value))
                    stat_name = stat_names.get(stat.get(1))
                    if stat_name == "program_id":
                        program = stat.get(3, stat.get(4))
                    elif stat_name == "tf_op":
                        # a string, or a reference to an interned one
                        op_name = (
                            bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat.get(7), "")
                        )
            if program is not None and op_name is not None:
                out[(name, program, text)] = op_name
    return out


_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def load_trace_events(trace_dir: str):
    """``(stage_events, device_events)`` for :func:`summarize_events`
    from the newest ``.xplane.pb`` under ``trace_dir``: the stage
    annotations of every host thread, and the device planes' ``XLA
    Ops`` with each operation's scope (resolved through the program of
    the ``XLA Modules`` event it ran under)."""
    from jax.profiler import ProfileData

    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        return [], []
    path = sorted(paths)[-1]
    op_names = _op_names(path)
    stage_names = set(tracing.STAGES)
    stage_events, device_events = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in stage_names:
                        start = int(ev.start_ns)
                        stage_events.append(
                            (ev.name, start, start + int(ev.duration_ns))
                        )
        elif plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            programs = sorted(
                (int(ev.start_ns), int(m.group(1)))
                for ev in (
                    lines["XLA Modules"].events
                    if "XLA Modules" in lines else ()
                )
                if (m := _PROGRAM_ID.search(ev.name))
            )
            starts = [start for start, _program in programs]
            for ev in lines["XLA Ops"].events:
                start = int(ev.start_ns)
                at = bisect.bisect_right(starts, start) - 1
                program = programs[at][1] if at >= 0 else None
                op_name = op_names.get((plane.name, program, ev.name), "")
                device_events.append((
                    plane.name, scope_of(op_name),
                    start, start + int(ev.duration_ns),
                ))
    return stage_events, device_events


def summarize(trace_dir: str) -> dict:
    """What a ``jax.profiler`` trace under ``trace_dir`` shows on one
    clock: the window, device busy and idle share, device time by
    named scope, and the idle time by what the host was doing
    (:data:`IDLE_STATES`). ``{}`` when the trace holds no event this
    reads (no stage ran, no device operation)."""
    return summarize_events(*load_trace_events(trace_dir))
