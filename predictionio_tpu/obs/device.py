"""Device runtime telemetry: background HBM/live-array sampler and
jit compile/retrace counters.

``DeviceSampler`` runs a daemon thread that periodically reads
``device.memory_stats()`` for every local accelerator and publishes

* ``pio_device_hbm_used_bytes{device}`` / ``pio_device_hbm_limit_bytes{device}``
* ``pio_device_live_array_bytes`` — bytes held by live jax arrays in
  this process (the host-side view of model + batch residency)

``CompileTracker`` counts jit compilation work at instrumented call
sites (the engine server's warm-up buckets, the trainer's step fn):
``pio_jit_compiles_total{site}`` on every new trace signature and
``pio_jit_retraces_total{site}`` when a site that already compiled
sees a *different* signature — the "shape churn is recompiling the
model" smell.

``CompileWatch`` counts what XLA itself compiled, wherever the call
came from: ``pio_xla_compiles_total{cache}`` and
``pio_xla_compile_seconds{cache}`` (``miss`` = the backend compiled,
``hit`` = the persistent cache had it), from one process-wide
``jax.monitoring`` listener that lives while any watch is open.

The module is import-safe without jax (``obs/`` stays stdlib-only at
import time): jax is imported lazily inside the sampler, and backends
without memory stats (CPU CI) degrade to a clean no-op — the thread
keeps its cadence but publishes nothing.
"""

from __future__ import annotations

import os
import threading
from typing import Callable

from predictionio_tpu.obs.registry import TRAIN_STEP_BUCKETS, MetricRegistry

_MIN_SAMPLE_S = 0.05

#: JAX 0.9.0 times every ``compile_or_get_cached`` under the first
#: event, persistent-cache hits included, and reports a hit by the
#: second just before (jax/_src/compiler.py, interpreters/pxla.py)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def sample_devices() -> dict:
    """One synchronous read of per-device HBM stats and live-array
    bytes. Returns ``{"devices": {label: {"used": .., "limit": ..}},
    "liveArrayBytes": float}`` — empty devices dict on backends
    without memory stats, ``{}`` entirely when jax is unavailable."""
    try:
        import jax
    except Exception:
        return {}
    devices = {}
    try:
        local = jax.local_devices()
    except Exception:
        local = []
    for device in local:
        try:
            stats = device.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        used = stats.get("bytes_in_use")
        limit = stats.get("bytes_limit") or stats.get(
            "bytes_reservable_limit"
        )
        if used is None:
            continue
        label = f"{device.platform}:{device.id}"
        devices[label] = {
            "used": float(used),
            "limit": float(limit) if limit is not None else None,
        }
    live = 0.0
    try:
        for arr in jax.live_arrays():
            live += float(getattr(arr, "nbytes", 0) or 0)
    except Exception:
        live = 0.0
    return {"devices": devices, "liveArrayBytes": live}


class DeviceSampler:
    """Daemon thread publishing device HBM gauges on a fixed cadence
    (``PIO_DEVICE_SAMPLE_S``, default 10 s, monotonic clock via
    ``Event.wait``). ``start`` takes an eager first sample so gauges
    are live before the first tick; ``stop`` joins the thread."""

    def __init__(
        self,
        registry: MetricRegistry,
        *,
        interval_s: float | None = None,
        sample_fn: Callable[[], dict] = sample_devices,
    ) -> None:
        self._interval_s = max(
            _MIN_SAMPLE_S,
            interval_s
            if interval_s is not None
            else _env_float("PIO_DEVICE_SAMPLE_S", 10.0),
        )
        self._sample_fn = sample_fn
        self._used = registry.gauge(
            "pio_device_hbm_used_bytes",
            "Device HBM bytes in use (device.memory_stats)",
            ("device",),
        )
        self._limit = registry.gauge(
            "pio_device_hbm_limit_bytes",
            "Device HBM capacity bytes (device.memory_stats)",
            ("device",),
        )
        self._live = registry.gauge(
            "pio_device_live_array_bytes",
            "Bytes held by live jax arrays in this process",
        )
        self._stopped = threading.Event()
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()
        self._last: dict = {}

    def sample_once(self) -> dict:
        """Take and publish one sample; returns what was read (the
        profile-capture artifact snapshots this)."""
        sample = self._sample_fn() or {}
        for label, stats in (sample.get("devices") or {}).items():
            self._used.labels(label).set(stats.get("used") or 0.0)
            if stats.get("limit") is not None:
                self._limit.labels(label).set(stats["limit"])
        if "liveArrayBytes" in sample:
            self._live.set(sample["liveArrayBytes"])
        with self._lock:
            self._last = sample
        return sample

    def last_sample(self) -> dict:
        with self._lock:
            return dict(self._last)

    def start(self) -> "DeviceSampler":
        with self._lock:
            if self._thread is not None:
                return self
            self._stopped.clear()
            thread = threading.Thread(
                target=self._run,
                name="pio-device-sampler",
                daemon=True,
            )
            self._thread = thread
        try:
            self.sample_once()
        except Exception:
            pass  # eager sample is best-effort; cadence still starts
        thread.start()
        return self

    def _run(self) -> None:
        while not self._stopped.wait(self._interval_s):
            try:
                self.sample_once()
            except Exception:
                continue  # a flaky backend read must not kill cadence

    def stop(self) -> None:
        self._stopped.set()
        with self._lock:
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=5.0)


class CompileTracker:
    """Counts jit compile work at named call sites. ``record(site,
    signature)`` increments ``pio_jit_compiles_total{site}`` for every
    signature the site has not traced before, and additionally
    ``pio_jit_retraces_total{site}`` when the site had already
    compiled a *different* signature (shape churn). Re-recording a
    known signature is a no-op — cache hits are free."""

    def __init__(self, registry: MetricRegistry) -> None:
        self._compiles = registry.counter(
            "pio_jit_compiles_total",
            "jit trace compilations per instrumented site",
            ("site",),
        )
        self._retraces = registry.counter(
            "pio_jit_retraces_total",
            "jit recompilations of an already-compiled site with a "
            "new signature",
            ("site",),
        )
        self._lock = threading.Lock()
        self._seen: dict[str, set] = {}

    def record(self, site: str, signature) -> bool:
        """Returns True when this (site, signature) compiled fresh."""
        key = repr(signature)
        with self._lock:
            seen = self._seen.setdefault(site, set())
            if key in seen:
                return False
            retrace = bool(seen)
            seen.add(key)
        self._compiles.labels(site).inc()
        if retrace:
            self._retraces.labels(site).inc()
        return True


class _CompileSink:
    """The two families' children in one registry, and how many open
    watches feed it (two servers on one registry count once)."""

    __slots__ = ("watches", "compiles", "seconds")

    def __init__(self, registry: MetricRegistry) -> None:
        self.watches = 0
        compiles = registry.counter(
            "pio_xla_compiles_total",
            "Programs XLA compiled (cache=miss) or loaded from the "
            "persistent compilation cache (cache=hit), process-wide",
            ("cache",),
        )
        seconds = registry.histogram(
            "pio_xla_compile_seconds",
            "Time of one backend compilation or persistent-cache load",
            ("cache",),
            buckets=TRAIN_STEP_BUCKETS,
        )
        self.compiles = {c: compiles.labels(c) for c in ("hit", "miss")}
        self.seconds = {c: seconds.labels(c) for c in ("hit", "miss")}


_watch_lock = threading.Lock()
#: the sink of every registry that an open watch feeds
_watched: dict[MetricRegistry, _CompileSink] = {}
_compile_thread = threading.local()


def _on_compile_event(event: str, duration_secs: float, **_kw) -> None:
    if event == _CACHE_RETRIEVAL_EVENT:
        # the compile event of this thread that follows was a hit
        _compile_thread.hit = True
        return
    if event != _BACKEND_COMPILE_EVENT:
        return
    cache = "hit" if getattr(_compile_thread, "hit", False) else "miss"
    _compile_thread.hit = False
    with _watch_lock:
        sinks = list(_watched.values())
    for sink in sinks:
        sink.compiles[cache].inc()
        sink.seconds[cache].observe(duration_secs)


class CompileWatch:
    """Counts XLA's compilations into ``registry`` until closed (or its
    ``with`` block ends): one owner's claim on the process-wide compile
    listener; closing the last one takes the listener off
    ``jax.monitoring`` again."""

    def __init__(self, registry: MetricRegistry) -> None:
        import jax.monitoring

        self._registry: MetricRegistry | None = registry
        with _watch_lock:
            if not _watched:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_compile_event
                )
            sink = _watched.get(registry)
            if sink is None:
                sink = _watched[registry] = _CompileSink(registry)
            sink.watches += 1

    def close(self) -> None:
        import jax.monitoring

        with _watch_lock:
            registry, self._registry = self._registry, None
            if registry is None:
                return  # closed before
            sink = _watched[registry]
            sink.watches -= 1
            if sink.watches == 0:
                del _watched[registry]
            if not _watched:
                jax.monitoring.unregister_event_duration_listener(
                    _on_compile_event
                )

    def __enter__(self) -> "CompileWatch":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
