"""Byte-budgeted device-resident model pool for multi-tenant serving.

One engine-server process holds MANY tenants' (quantized) factor
tables in a single chip's HBM. The pool is the residency authority:

* **budget** — explicit bytes, ``PIO_POOL_BUDGET_BYTES``, or a
  fraction (``PIO_POOL_HBM_FRACTION``) of the smallest device HBM
  limit reported by :func:`predictionio_tpu.obs.device.sample_devices`
  (the PR 16 gauges); CPU/CI backends without memory stats fall back
  to a fixed default so tests exercise real eviction.
* **LRU + pinning** — a request pins its tenant's entry for the life
  of the query; eviction only ever takes unpinned entries, so an
  eviction racing an in-flight query is lossless by construction. A
  ``/reload`` replace retires the old generation and closes it when
  its last pin drains.
* **cold loads off the hot path** — a miss enqueues a single-flight
  load on the pool's one loader thread (host staging + device
  promotion happen there); request threads just wait on the load
  event with a deadline, and concurrent requests for the same tenant
  share one load.
* **per-tenant metrics** — ``pio_pool_hits_total`` /
  ``pio_pool_misses_total`` / ``pio_pool_evictions_total`` /
  ``pio_pool_resident_bytes`` plus pool-wide
  ``pio_pool_budget_bytes`` / ``pio_pool_tenants_resident`` /
  ``pio_pool_loaded_bytes_total`` / ``pio_pool_load_queue``.
* **stages** (``obs.tracing.POOL_STAGES``) — the loader thread times
  ``pool.load`` around each cold load and ``pool.close`` around each
  evicted generation's ``close_fn``; a request thread times
  ``pool.wait`` around the wait a miss costs it. A hit opens no stage.

The pool stores opaque values: the engine server keeps whole staged
generations (models + batchers) in it, the density bench keeps bare
factor tables. A loader returns ``(value, nbytes, close_fn)`` —
whoever loaded knows how many device bytes it committed and how to
release them.
"""

from __future__ import annotations

import contextlib
import logging
import os
import queue
import threading
import time
from typing import Callable

from predictionio_tpu.obs import timeline as timeline_mod
from predictionio_tpu.obs import tracing

logger = logging.getLogger(__name__)

#: default budget when neither env nor device memory stats are
#: available (CPU CI) — small enough that tests see real evictions
_DEFAULT_BUDGET_BYTES = 256 * 1024 * 1024
_DEFAULT_HBM_FRACTION = 0.5

#: loader returns (value, device-bytes-committed, close-fn)
Loader = Callable[[], tuple[object, int, Callable[[], None] | None]]


class PoolLoadError(RuntimeError):
    """The tenant's loader raised; the cause is chained."""


class PoolLoadTimeout(TimeoutError):
    """Waiting on a cold load exceeded the caller's deadline."""


def default_budget_bytes() -> int:
    """Resolve the pool byte budget: ``PIO_POOL_BUDGET_BYTES`` wins;
    else ``PIO_POOL_HBM_FRACTION`` (default 0.5) of the smallest
    device HBM limit from the obs gauges; else a fixed CPU default."""
    raw = os.environ.get("PIO_POOL_BUDGET_BYTES")
    if raw and raw.strip():
        try:
            return max(1, int(raw))
        except ValueError:
            logger.warning(
                "ignoring non-integer PIO_POOL_BUDGET_BYTES=%r", raw
            )
    fraction = _DEFAULT_HBM_FRACTION
    raw = os.environ.get("PIO_POOL_HBM_FRACTION")
    if raw and raw.strip():
        try:
            fraction = min(1.0, max(0.01, float(raw)))
        except ValueError:
            logger.warning(
                "ignoring non-float PIO_POOL_HBM_FRACTION=%r", raw
            )
    try:
        from predictionio_tpu.obs.device import sample_devices

        limits = [
            d["limit"]
            for d in (sample_devices().get("devices") or {}).values()
            if d.get("limit")
        ]
    except Exception:
        limits = []
    if limits:
        return max(1, int(min(limits) * fraction))
    return _DEFAULT_BUDGET_BYTES


class _Entry:
    __slots__ = (
        "tenant", "value", "nbytes", "close_fn", "pins", "last_used",
        "retired", "hits", "charged_mono",
    )

    def __init__(self, tenant, value, nbytes, close_fn, last_used):
        self.tenant = tenant
        self.value = value
        self.nbytes = int(nbytes)
        self.close_fn = close_fn
        self.pins = 0
        self.last_used = last_used
        self.retired = False
        self.hits = 0
        #: residency charged up to this monotonic stamp — cost
        #: attribution charges elapsed x nbytes at every transition
        self.charged_mono = last_used


class _Load:
    __slots__ = ("tenant", "loader", "done", "error")

    def __init__(self, tenant, loader):
        self.tenant = tenant
        self.loader = loader
        self.done = threading.Event()
        self.error: BaseException | None = None


class _Close:
    __slots__ = ("entry",)

    def __init__(self, entry):
        self.entry = entry


_STOP = object()


class ModelPool:
    """LRU pool of device-resident per-tenant values under one byte
    budget. Thread-safe; all loads and closes run on the pool's single
    loader thread so device staging never blocks request threads on
    each other."""

    def __init__(
        self,
        budget_bytes: int | None = None,
        *,
        registry=None,
        timeline: "timeline_mod.Timeline | None" = None,
    ) -> None:
        self._budget = (
            int(budget_bytes)
            if budget_bytes is not None
            else default_budget_bytes()
        )
        if self._budget <= 0:
            raise ValueError(f"pool budget must be > 0: {self._budget}")
        self._lock = threading.Lock()
        self._entries: dict[str, _Entry] = {}
        self._loading: dict[str, _Load] = {}
        self._resident_bytes = 0  # includes retired-but-pinned bytes
        self._evictions = 0
        self._queued_loads = 0  # enqueued, not yet begun by the loader
        #: where the loader thread times the pool's stages
        self._stages = tracing.StageSink(registry, tracing.POOL_STAGES)
        self._closed = False
        self._jobs: queue.Queue = queue.Queue()
        # non-daemon on purpose: joined in close(), which owners call
        # from their own teardown (thread-lifecycle rule)
        self._worker = threading.Thread(
            target=self._run, name="pio-pool-loader"
        )
        self._worker.start()
        self._hits = self._misses = self._evicted = None
        self._loaded_bytes = None
        self._resident_gauge = None
        self._byte_seconds = None
        self._timeline = timeline
        if registry is not None:
            self._hits = registry.counter(
                "pio_pool_hits_total",
                "Model-pool lookups served by a resident entry",
                ("tenant",),
            )
            self._misses = registry.counter(
                "pio_pool_misses_total",
                "Model-pool lookups that triggered a cold load",
                ("tenant",),
            )
            self._evicted = registry.counter(
                "pio_pool_evictions_total",
                "Model-pool entries evicted to fit the byte budget",
                ("tenant",),
            )
            self._resident_gauge = registry.gauge(
                "pio_pool_resident_bytes",
                "Device bytes a tenant's pooled model holds (0 after "
                "eviction)",
                ("tenant",),
            )
            self._byte_seconds = registry.counter(
                "pio_tenant_resident_byte_seconds_total",
                "HBM residency charged to the tenant: bytes x seconds "
                "resident, accrued at touch/evict/replace/close "
                "transitions and at stats() snapshots",
                ("tenant",),
            )
            registry.gauge(
                "pio_pool_budget_bytes",
                "Model-pool device byte budget",
            ).set(float(self._budget))
            registry.gauge(
                "pio_pool_tenants_resident",
                "Tenants currently resident in the model pool",
            ).set_function(lambda: float(len(self._entries)))
            self._loaded_bytes = registry.counter(
                "pio_pool_loaded_bytes_total",
                "Device bytes committed by cold loads (and replaces) "
                "since the pool was built",
            )
            registry.gauge(
                "pio_pool_load_queue",
                "Cold loads waiting for the pool's one loader thread "
                "(the load it is running is not among them)",
            ).set_function(lambda: float(self._queued_loads))

    @property
    def budget_bytes(self) -> int:
        return self._budget

    def fits(self, nbytes: int) -> bool:
        """Whether ``nbytes`` more would go in without a victim."""
        with self._lock:
            return self._resident_bytes + int(nbytes) <= self._budget

    def _charge(self, entry, now: float | None = None) -> None:
        """Accrue the entry's residency since its last charge (bytes x
        seconds) to the tenant. The stamp advances with the charge, so
        overlapping charge sites (touch, evict, replace, close, stats)
        never double-count an interval."""
        if self._byte_seconds is None:
            return
        if now is None:
            now = time.monotonic()
        elapsed = now - entry.charged_mono
        if elapsed <= 0:
            return
        entry.charged_mono = now
        self._byte_seconds.labels(entry.tenant).inc(
            elapsed * entry.nbytes
        )

    def _emit(self, kind, message, *, severity=timeline_mod.INFO,
              tenant="", **fields) -> None:
        """Record a pool lifecycle event; a deque append, safe under
        the pool lock."""
        if self._timeline is not None:
            self._timeline.record(
                kind, message, severity=severity, tenant=tenant,
                **fields,
            )

    # -- hot path ----------------------------------------------------------

    @contextlib.contextmanager
    def pin(self, tenant: str, loader: Loader, timeout: float | None = None):
        """Context manager yielding the tenant's resident value, pinned
        for the duration (pinned entries are never closed under an
        in-flight request). A miss blocks on the single-flight cold
        load up to ``timeout`` seconds."""
        entry = self._acquire(tenant, loader, timeout)
        try:
            yield entry.value
        finally:
            self._unpin(entry)

    def _acquire(self, tenant, loader, timeout):
        # a lookup is a hit or a miss once, here; the pin taken after
        # waiting out a cold load is the same miss, not a new hit
        with self._lock:
            if self._closed:
                raise RuntimeError("model pool is closed")
            entry = self._entries.get(tenant)
            if entry is not None:
                entry.pins += 1
                entry.last_used = time.monotonic()
                self._charge(entry, entry.last_used)
                entry.hits += 1
        if entry is not None:
            if self._hits is not None:
                self._hits.labels(tenant).inc()
            return entry
        if self._misses is not None:
            self._misses.labels(tenant).inc()
        with tracing.stage(tracing.POOL_WAIT):
            return self._await_load(tenant, loader, timeout)

    def _await_load(self, tenant, loader, timeout):
        """After a miss: join the tenant's single-flight load (or
        enqueue one), wait it out, and pin what it inserted."""
        deadline = (
            None if timeout is None else time.monotonic() + timeout
        )
        while True:
            with self._lock:
                if self._closed:
                    raise RuntimeError("model pool is closed")
                entry = self._entries.get(tenant)
                if entry is not None:
                    # the pin, as `_acquire` takes it inline (a hit
                    # pays no call for it)
                    entry.pins += 1
                    entry.last_used = time.monotonic()
                    self._charge(entry, entry.last_used)
                    return entry
                load = self._loading.get(tenant)
                if load is None:
                    load = _Load(tenant, loader)
                    self._loading[tenant] = load
                    self._queued_loads += 1
                    self._jobs.put(load)
            remaining = (
                None
                if deadline is None
                else deadline - time.monotonic()
            )
            if (
                remaining is not None and remaining <= 0
            ) or not load.done.wait(remaining):
                self._emit(
                    "pool_load_timeout",
                    f"cold load for tenant {tenant!r} missed the "
                    "caller's deadline",
                    severity=timeline_mod.ERROR, tenant=tenant,
                )
                raise PoolLoadTimeout(
                    f"timed out waiting for tenant {tenant!r} to load"
                )
            if load.error is not None:
                raise PoolLoadError(
                    f"loading tenant {tenant!r} failed: {load.error}"
                ) from load.error
            # loop: the freshly inserted entry is pinned on the next
            # pass (or, under extreme pressure, re-loaded)

    def _unpin(self, entry) -> None:
        with self._lock:
            entry.pins -= 1
            if entry.pins or not (
                entry.retired or self._resident_bytes > self._budget
            ):
                return  # every hit in a pool inside its budget
            to_close: list[_Entry] = []
            if entry.retired:
                to_close.append(entry)
            else:
                self._shed_overcommit_locked(to_close)
        for stale in to_close:
            self._jobs.put(_Close(stale))

    def _shed_overcommit_locked(self, to_close: list) -> None:
        """A load that found every other tenant pinned left the pool
        over its budget; the first pin to drain pays that back, not the
        next load (which may never come). ``_resident_bytes`` also
        counts what is retired and waiting for its close, and that is
        no overcommit: the entries that are in are summed (only here,
        while the ledger reads over budget)."""
        live = sum(e.nbytes for e in self._entries.values())
        if live > self._budget:
            # "incoming" less the retired bytes: the loop then holds
            # the live entries alone to the budget
            self._evict_for_locked(live - self._resident_bytes, to_close)

    # -- lifecycle (loader thread) ----------------------------------------

    def _run(self) -> None:
        while True:
            job = self._jobs.get()
            if job is _STOP:
                break
            if isinstance(job, _Close):
                self._stages.bind(tenant=job.entry.tenant)
                self._close_entry(job.entry)
                continue
            self._do_load(job)

    def _do_load(self, load: _Load) -> None:
        with self._lock:
            self._queued_loads -= 1
        # the loader's stages go to this pool's registry, and carry
        # the tenant in a running profiler's trace
        self._stages.bind(tenant=load.tenant)
        to_close: list[_Entry] = []
        try:
            with tracing.stage(tracing.POOL_LOAD):
                value, nbytes, close_fn = load.loader()
                entry = _Entry(
                    load.tenant, value, nbytes, close_fn,
                    time.monotonic(),
                )
                with self._lock:
                    self._evict_for_locked(entry.nbytes, to_close)
                    old = self._entries.get(load.tenant)
                    if old is not None:  # a replace raced us; retire it
                        self._retire_locked(old, to_close)
                    self._entries[load.tenant] = entry
                    self._resident_bytes += entry.nbytes
                    self._loading.pop(load.tenant, None)
        except BaseException as exc:  # surfaced to every waiter
            with self._lock:
                self._loading.pop(load.tenant, None)
            self._emit(
                "pool_load_failed",
                f"cold load for tenant {load.tenant!r} failed: "
                f"{type(exc).__name__}: {exc}",
                severity=timeline_mod.ERROR, tenant=load.tenant,
            )
            load.error = exc
            load.done.set()
            return
        if self._resident_gauge is not None:
            self._resident_gauge.labels(load.tenant).set(
                float(entry.nbytes)
            )
        if self._loaded_bytes is not None:
            self._loaded_bytes.inc(float(entry.nbytes))
        # the waiters go on as soon as the entry is in: the victims'
        # closes (two thread joins a batcher) are the loader's to pay,
        # not the request's
        load.done.set()
        for stale in to_close:
            self._stages.bind(tenant=stale.tenant)
            self._close_entry(stale)
        with self._lock:
            resident = self._resident_bytes
        if resident > self._budget:
            logger.warning(
                "model pool over budget (%d resident > %d budget): "
                "every other tenant is pinned",
                resident, self._budget,
            )

    def _evict_for_locked(self, incoming: int, to_close: list) -> None:
        """Pop LRU *unpinned* entries until ``incoming`` fits the
        budget (caller holds the lock; closes happen after release).
        Victims' bytes count as reclaimed immediately — they are
        already queued for close — so one oversized insert never
        cascades into evicting more than it needs."""
        reclaimed = sum(e.nbytes for e in to_close)
        while self._resident_bytes - reclaimed + incoming > self._budget:
            victims = [
                e for e in self._entries.values() if e.pins == 0
            ]
            if not victims:
                return  # everything pinned: overcommit, warned above
            victim = min(victims, key=lambda e: e.last_used)
            del self._entries[victim.tenant]
            victim.retired = True
            to_close.append(victim)
            reclaimed += victim.nbytes
            self._evictions += 1
            self._charge(victim)
            self._emit(
                "pool_eviction",
                f"evicted tenant {victim.tenant!r} "
                f"({victim.nbytes} bytes) to fit the byte budget",
                severity=timeline_mod.WARN, tenant=victim.tenant,
            )
            if self._evicted is not None:
                self._evicted.labels(victim.tenant).inc()
            if self._resident_gauge is not None:
                self._resident_gauge.labels(victim.tenant).set(0.0)

    def _retire_locked(self, entry, to_close: list) -> None:
        self._charge(entry)
        entry.retired = True
        if entry.pins == 0:
            to_close.append(entry)

    def _close_entry(self, entry) -> None:
        try:
            if entry.close_fn is not None:
                with tracing.stage(tracing.POOL_CLOSE):
                    entry.close_fn()
        except Exception:
            logger.exception(
                "closing pooled model for tenant %r failed",
                entry.tenant,
            )
        # nobody is pinned on a closed entry: what it held (the staged
        # generation, through it the device tables) goes with this
        # reference, not with whoever still holds the entry
        entry.value = entry.close_fn = None
        with self._lock:
            # the retired-but-pinned tail still held HBM: charge it
            # through to the actual close
            self._charge(entry)
            self._resident_bytes -= entry.nbytes

    # -- management --------------------------------------------------------

    def evict(self, tenant: str) -> bool:
        """Drop a tenant now if it is resident and unpinned. Returns
        True when evicted."""
        with self._lock:
            entry = self._entries.get(tenant)
            if entry is None or entry.pins > 0:
                return False
            del self._entries[tenant]
            entry.retired = True
            self._evictions += 1
            self._charge(entry)
        self._emit(
            "pool_eviction",
            f"explicit evict of tenant {tenant!r} "
            f"({entry.nbytes} bytes)",
            severity=timeline_mod.WARN, tenant=tenant,
        )
        if self._evicted is not None:
            self._evicted.labels(tenant).inc()
        if self._resident_gauge is not None:
            self._resident_gauge.labels(tenant).set(0.0)
        self._jobs.put(_Close(entry))
        return True

    def replace(self, tenant: str, loader: Loader) -> None:
        """Load a NEW value for ``tenant`` (on the calling thread — the
        ``/reload`` admin path, not a request thread) and swap it in.
        The old entry closes immediately when unpinned, else when its
        last in-flight request drains — a reload never yanks a model
        out from under a query."""
        value, nbytes, close_fn = loader()
        entry = _Entry(tenant, value, nbytes, close_fn, time.monotonic())
        to_close: list[_Entry] = []
        with self._lock:
            if self._closed:
                raise RuntimeError("model pool is closed")
            old = self._entries.get(tenant)
            if old is not None:
                self._retire_locked(old, to_close)
                del self._entries[tenant]
            self._evict_for_locked(entry.nbytes, to_close)
            self._entries[tenant] = entry
            self._resident_bytes += entry.nbytes
        if self._resident_gauge is not None:
            self._resident_gauge.labels(tenant).set(float(entry.nbytes))
        if self._loaded_bytes is not None:
            self._loaded_bytes.inc(float(entry.nbytes))
        for stale in to_close:
            self._jobs.put(_Close(stale))

    def resident(self) -> list[str]:
        with self._lock:
            return sorted(self._entries)

    def stats(self) -> dict:
        """Status-route snapshot: budget, resident bytes, per-tenant
        residency (the CLI pool line renders the metric twins)."""
        with self._lock:
            # settle residency on every snapshot so a long-idle
            # resident keeps accruing byte-seconds between touches
            now = time.monotonic()
            for e in self._entries.values():
                self._charge(e, now)
            tenants = {
                t: {
                    "residentBytes": e.nbytes,
                    "pins": e.pins,
                    "hits": e.hits,
                }
                for t, e in self._entries.items()
            }
            return {
                "budgetBytes": self._budget,
                "residentBytes": self._resident_bytes,
                "tenantsResident": len(tenants),
                "evictions": self._evictions,
                "tenants": tenants,
            }

    def close(self) -> None:
        """Stop the loader thread and close every entry (pinned or
        not — process teardown)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            entries = list(self._entries.values())
            self._entries.clear()
        self._jobs.put(_STOP)
        self._worker.join(timeout=30.0)
        for entry in entries:
            self._close_entry(entry)
