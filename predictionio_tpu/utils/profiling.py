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

Timing syncs through :func:`sync`, the one device barrier of the
package.
"""

from __future__ import annotations

import contextlib
import io
import json
import logging
import os
import tarfile
import tempfile
import time
import uuid
from collections import defaultdict

import jax

from predictionio_tpu.obs import tracing

logger = logging.getLogger(__name__)


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
    PIO_TRACE_DIR is set; transparent otherwise."""
    trace_dir = trace_dir or os.environ.get("PIO_TRACE_DIR")
    if not trace_dir:
        yield
        return
    os.makedirs(trace_dir, exist_ok=True)
    logger.info("writing profiler trace to %s", trace_dir)
    with jax.profiler.trace(trace_dir):
        yield


def capture(
    duration_s: float,
    tracer: "tracing.Tracer | None" = None,
    device_sample_fn=None,
    out_dir: str | None = None,
) -> dict:
    """On-demand profile capture (the ``POST /debug/profile`` body of
    docs/observability.md): run a duration-bounded :func:`trace`
    (jax.profiler, XLA timeline) and snapshot the same window's
    flight-recorder spans (Perfetto-loadable Chrome trace-event JSON)
    plus the current device gauges into ONE artifact directory:

    * ``jax_trace/`` — the jax.profiler output (TensorBoard/Perfetto)
    * ``spans.json`` — the tracing flight recorder's chrome trace
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
    files = ["jax_trace/", "manifest.json", "spans.json"]
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
