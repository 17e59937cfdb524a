"""Micro-batching queue for serving.

The reference serves one query at a time per request thread and, for
RDD-backed models, pays a Spark job per query (CreateServer.scala:520,
SURVEY.md §3.2). The TPU answer is the opposite shape: concurrent
requests are coalesced into one fixed-shape batch dispatched to a
pre-compiled jitted program — XLA dispatch overhead amortizes across
the batch, which is what makes the ≥1k QPS target reachable.

Pipelined dispatch: the batcher is a two-stage pipeline (the Sebulba
move from the Podracer line of work — never let the accelerator wait
on host bookkeeping). A **collector** thread assembles batches
(max_batch/max_wait coalescing, cancellation, deadline drops) and
*enqueues* them to the device; a **completer** thread syncs the device
barrier and materializes results. ``pipeline_depth`` (1 or more) bounds
how many batches may be in flight past their enqueue (default 2 =
double buffering): batch N+1 is assembled and enqueued while batch N
is still computing, so the device never idles on host-side
assembly/JSON work and the host never idles on device compute.

``batch_fn`` is a pair: ``dispatch(items) -> handle`` (enqueue device
work, return immediately — lean on JAX async dispatch) runs on the
collector and ``collect(handle) -> results`` (device barrier + host
decode) on the completer, so the *enqueue* of batch N+1 overlaps the
*barrier* of batch N — see :class:`TwoPhaseBatchFn`. A plain callable
``(items) -> results`` is taken as the pair whose dispatch hands the
items on: it runs exactly once per batch, as the collect, with no
extra device barriers added around it.

A request's items enter, ride and leave as one **group**
(:class:`BatchGroup`, ``submit_group``): what they share — deadline,
criticality, tenant, request ID and span, arrival instant — is read
once, the queue's condition taken once, the collector notified once;
the completer stores a batch's outcomes into the groups they belong to
and wakes each group's one waiter when its last slot is in. Per slot
there is a pair in the queue going in and an outcome stored coming
out: no Future, lock or labelled metric. ``submit(item) -> Future`` is
the group of one behind a Future, on the same admission and the same
settle; every per-slot guarantee below holds slot by slot of a group.

Overload discipline (docs/robustness.md "Overload & backpressure"):
the wait queue is criticality- and deadline-aware. When backlog
exceeds one batch, the most-urgent slots (nearest ``X-PIO-Deadline``)
dispatch first so near-expiry work isn't served dead behind slack
work; when the queue-depth bound is hit, a submission of a HIGHER
criticality class evicts the lowest-class queued slot (shed accounting
in ``pio_shed_total{batcher,class}``) instead of being refused, so
``sheddable`` traffic absorbs overload before ``critical`` traffic
feels it. :meth:`MicroBatcher.retry_after_s` turns live queue state
into the cooperative-backpressure hint shed responses carry.

Telemetry: when built with a :class:`~predictionio_tpu.obs.MetricRegistry`
the batcher records batch occupancy, queue depth, device-dispatch time
(now split into ``pio_device_enqueue_seconds`` and
``pio_device_sync_seconds`` around the end-to-end
``pio_device_dispatch_seconds``), dispatched/shed/cancelled counts —
the queue instrumentation the Podracer line of work treats as a
prerequisite for scaling; built without a registry it counts into a
private one. Each group carries the submitting request's
ID (from the obs contextvar), so a slow or failing dispatch logs
exactly which requests rode in it.
"""

from __future__ import annotations

import heapq
import itertools
import logging
import math
import operator
import os
import queue
import threading
import time
from concurrent.futures import CancelledError, Future
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, Callable, NamedTuple, Sequence

from predictionio_tpu.obs import MetricRegistry, get_request_id
from predictionio_tpu.obs import timeline as timeline_mod
from predictionio_tpu.obs import tracing
from predictionio_tpu.obs.context import log_json
from predictionio_tpu.obs.registry import LATENCY_BUCKETS, OCCUPANCY_BUCKETS
from predictionio_tpu.serving import admission, resilience

logger = logging.getLogger(__name__)

#: batch numbers are process-wide, so that the ``batch=`` keyword joins
#: a batcher's and its completer's stage annotations in a profiler
#: trace of a pool of batchers
_BATCH_SEQ = itertools.count(1)

#: the adaptive window's arrival-gap estimate (MicroBatcher._gap_ewma).
#: Half the weight on the newest gap and no gap counted as more than
#: three windows: one long gap after a burst lifts the mean over the
#: window (3/2 of it), so an idle tenant's next query is not held, and
#: two close arrivals bring it back under (3/4), so a burst after an
#: idle hour coalesces from its third item on
_GAP_WEIGHT = 0.5
_GAP_CAP_WINDOWS = 3.0


#: what a ``pipeline_depth`` below 1 is answered with, wherever it is
#: given (here, ``EngineServer``, ``pio-tpu deploy --pipeline-depth``)
DEPTH_ZERO_GONE = (
    "pipeline_depth must be 1 or more, got {}: the serial batcher "
    "(depth 0, dispatch and collect on one thread) is gone — on the "
    "chip it lost or tied in every cell (PERF.md section 6, PR 30)"
)


class BatcherOverloaded(Exception):
    """Queue depth bound hit — shed the request instead of queuing it.

    Deliberately NOT a RuntimeError: callers distinguish overload
    (client should back off, 503 fast) from a closed batcher mid-reload
    (retry against the fresh set).
    """


class TwoPhaseBatchFn:
    """The ``batch_fn`` protocol: enqueue now, sync later.

    ``dispatch(items) -> handle`` must enqueue the device work and
    return without blocking on it (JAX async dispatch makes this the
    natural shape: launch the jitted program, return the un-fetched
    device arrays). ``collect(handle) -> results`` pays the device
    barrier and materializes one result per item, in order.

    The batcher duck-types on ``dispatch``/``collect`` attributes, so
    any object with both works; this class is the explicit spelling.
    """

    __slots__ = ("dispatch", "collect")

    def __init__(
        self,
        dispatch: Callable[[Sequence[Any]], Any],
        collect: Callable[[Any], Sequence[Any]],
    ):
        self.dispatch = dispatch
        self.collect = collect


class _State:
    """A slot's entry in its group's ``outcomes`` until an answer, or
    the exception that refused it, takes its place."""

    __slots__ = ("_name",)

    def __init__(self, name: str):
        self._name = name

    def __repr__(self) -> str:
        return self._name


_QUEUED = _State("<queued>")
_RUNNING = _State("<running>")

#: a queued slot is the pair (its group, its index in the group): what
#: the collector's rules read of a slot (deadline, criticality, arrival
#: order) they read of its group, and the buffer keeps arrival order
_group_of = operator.itemgetter(0)


class _Group:
    """One admission to a :class:`MicroBatcher`, as the batcher sees it:
    the slots of one ``submit_group`` (:class:`BatchGroup`), or the one
    slot of a ``submit`` (:class:`_SlotFuture`).

    The slots share what their request shares, read here once: one
    deadline, one criticality, one tenant, one request ID and span (so
    dispatch logs can name the requests in a slow or failed batch, and
    the dispatch span can link back to every request it coalesced; with
    tracing off that costs the ``current_span()`` read), and one arrival
    instant. What stays per slot is its place in ``outcomes``: the
    answer, or the exception that refused it (shed at the bound,
    evicted, expired, cancelled, its batch failed). A group may ride
    more than one batch; it is done, and ``_finish`` called once, when
    its last slot has an outcome.

    ``admitted`` slots entered the queue: the slots from ``admitted``
    on were shed at the bound and hold :class:`BatcherOverloaded`.
    """

    __slots__ = (
        "items", "outcomes", "admitted", "deadline", "criticality",
        "tenant", "request_id", "parent_span", "submitted_mono",
        "_pending", "_lock",
    )

    def __init__(self, items: Sequence[Any]):
        self.items = items
        self.outcomes: list = [_QUEUED] * len(items)
        self.admitted = 0
        self.deadline = resilience.get_deadline()
        self.criticality = admission.get_criticality()
        self.tenant = admission.get_tenant()
        self.request_id = get_request_id()
        self.parent_span = tracing.current_span()
        self.submitted_mono = 0.0
        #: admitted slots with no outcome yet (``_lock`` held to change):
        #: whoever brings it to 0 calls ``_finish``, so that happens once
        self._pending = 0
        #: orders a slot's leaving the queue (the cut-off at dispatch
        #: entry, an eviction, a cancel) and the count-down: taken once
        #: a group and batch, never once a slot
        self._lock = threading.Lock()

    def _finish(self) -> None:
        """Every slot has its outcome: wake whoever waits. Called once,
        under no lock."""
        raise NotImplementedError

    def _drop(self, indices, exc: Exception) -> int:
        """The slots at ``indices`` that still wait leave the queue
        unserved, with ``exc`` for outcome (cancelled, evicted);
        returns how many of the others are running."""
        outcomes = self.outcomes
        dropped = running = 0
        with self._lock:
            for i in indices:
                state = outcomes[i]
                if state is _QUEUED:
                    outcomes[i] = exc
                    dropped += 1
                elif state is _RUNNING:
                    running += 1
            self._pending -= dropped
            finished = dropped and not self._pending
        if finished:
            self._finish()
        return running

    def _start(self, indices: list) -> tuple[list, int, int]:
        """The cut-off at dispatch entry for the group's slots of one
        batch: ``(live, cancelled, expired)``. Cancelled slots drop out;
        past its deadline the group's queued slots are refused here
        (their waiter is gone or about to time out); the live ones are
        the device's from now on and no cancel reaches them."""
        outcomes = self.outcomes
        expired = 0
        with self._lock:
            live = [i for i in indices if outcomes[i] is _QUEUED]
            cancelled = len(indices) - len(live)
            finished = False
            if live and self.deadline is not None and self.deadline.expired:
                exc = resilience.DeadlineExceeded(
                    "deadline expired while queued for dispatch"
                )
                for i in live:
                    outcomes[i] = exc
                self._pending -= len(live)
                finished = not self._pending
                expired, live = len(live), []
            for i in live:
                outcomes[i] = _RUNNING
        if finished:
            self._finish()
        return live, cancelled, expired

    def _store(self, indices: list, values: Sequence[Any]) -> None:
        """A batch's outcomes for the group's slots that rode it. They
        are running, so the batcher owns them: no lock until the
        count-down."""
        first, n = indices[0], len(indices)
        if indices[-1] - first == n - 1:  # increasing, so: contiguous
            self.outcomes[first:first + n] = values
        else:
            outcomes = self.outcomes
            for i, value in zip(indices, values):
                outcomes[i] = value
        with self._lock:
            self._pending -= n
            finished = not self._pending
        if finished:
            self._finish()


class BatchGroup(_Group):
    """What ``submit_group`` returns: a request's slots, waited for
    once (``wait``) and read in the items' order (``result(i)``)."""

    __slots__ = ("_done",)

    def __init__(self, items: Sequence[Any]):
        super().__init__(items)
        #: a latch: held from birth, released once by whoever stores
        #: the last outcome, so the one waiter wakes on a single lock
        #: hand-over (an Event would wake it to queue for the Event's
        #: own lock next)
        self._done = threading.Lock()
        self._done.acquire()

    def _finish(self) -> None:
        self._done.release()

    def wait(self, timeout: float | None = None) -> bool:
        """Block until every slot has its outcome; False on time-out."""
        if not self._done.acquire(
            timeout=-1 if timeout is None else max(0.0, timeout)
        ):
            return False
        self._done.release()
        return True

    def result(self, index: int) -> Any:
        """Slot ``index``'s answer; raises the exception it was refused
        or failed with, and ``TimeoutError`` while it has neither."""
        outcome = self.outcomes[index]
        if outcome is _QUEUED or outcome is _RUNNING:
            raise FuturesTimeout()
        if isinstance(outcome, BaseException):
            raise outcome
        return outcome

    def cancel(self, indices: Sequence[int] | None = None) -> int:
        """Abandon the group's slots, or those at ``indices``: one
        still queued is dropped (the device never sees it; the
        collector counts it in ``pio_batch_cancelled_total`` when it
        reaches it) and holds ``CancelledError``; one with an outcome
        keeps it. Returns how many are already past the cut-off with no
        answer yet: device work under way that nobody will read."""
        return self._drop(
            range(self.admitted) if indices is None else indices,
            CancelledError(),
        )


class _SlotFuture(_Group, Future):
    """What ``submit`` returns: the group of one, as a ``Future``.
    ``cancel`` drops the slot while it still waits, and the slot's
    outcome resolves the future."""

    def __init__(self, item: Any):
        Future.__init__(self)
        _Group.__init__(self, (item,))

    def cancel(self) -> bool:
        self._drop((0,), CancelledError())
        return self.cancelled()

    def _finish(self) -> None:
        outcome = self.outcomes[0]
        if isinstance(outcome, CancelledError):
            Future.cancel(self)
            self.set_running_or_notify_cancel()
        elif isinstance(outcome, BaseException):
            self.set_exception(outcome)
        else:
            self.set_result(outcome)


class _Inflight(NamedTuple):
    """One enqueued batch riding the collector→completer handoff."""

    runs: list  # [(group, [index])]: the live slots, in batch order
    n: int  # how many they are
    handle: Any
    start_wall: float
    start_mono: float
    t0: float  # perf_counter at dispatch entry
    enqueue_s: float
    traced: bool
    seq: int  # the batch's number, for the completer's stage keywords


#: queue-wait budget a tenant's requests must beat for the tenant to
#: count as UNHARMED in the noisy-neighbor check; default is half the
#: default-class SLO latency (obs/slo.py). Override with
#: PIO_TENANT_WAIT_SLO_MS.
_DEFAULT_WAIT_SLO_S = 0.5

#: a tenant is a noisy-neighbor CANDIDATE when its device-seconds over
#: the rollup window exceed this multiple of the fair per-tenant share
_NOISY_SHARE_FACTOR = 1.5

#: noisy-neighbor rollup window (seconds): device share and queue-wait
#: breaches accumulate per window, the gauge updates at rollover
_NOISY_WINDOW_S = 15.0


def _wait_slo_s() -> float:
    raw = os.environ.get("PIO_TENANT_WAIT_SLO_MS")
    if not raw:
        return _DEFAULT_WAIT_SLO_S
    try:
        value = float(raw)
    except ValueError:
        return _DEFAULT_WAIT_SLO_S
    return value / 1000.0 if value > 0 else _DEFAULT_WAIT_SLO_S


class _NoisyRollup:
    """Per-window noisy-neighbor detection over the attribution stream.

    A tenant is flagged when BOTH hold over one window: it consumed
    more than ``_NOISY_SHARE_FACTOR`` x the fair per-tenant device
    share, and some OTHER tenant's queue wait breached the wait SLO —
    i.e. the overuse visibly harmed a neighbor. Advisory only (a gauge
    + timeline event beside the fair-share admission path, never an
    enforcement input). Callers hold no lock; all state is guarded by
    the owning ``_BatcherMetrics``' attribution lock."""

    __slots__ = (
        "noisy_gauge", "window_end", "device_s", "breached", "flagged",
        "wait_slo_s",
    )

    def __init__(self, noisy_gauge):
        self.noisy_gauge = noisy_gauge
        self.window_end = time.monotonic() + _NOISY_WINDOW_S
        self.device_s: dict[str, float] = {}
        self.breached: set[str] = set()
        self.flagged: set[str] = set()
        self.wait_slo_s = _wait_slo_s()

    def observe(self, tenant: str, device_s: float, wait_s: float) -> None:
        self.device_s[tenant] = (
            self.device_s.get(tenant, 0.0) + device_s
        )
        if wait_s > self.wait_slo_s:
            self.breached.add(tenant)
        now = time.monotonic()
        if now >= self.window_end:
            self._roll(now)

    def _roll(self, now: float) -> None:
        total = sum(self.device_s.values())
        tenants = set(self.device_s)
        fair = total / max(1, len(tenants))
        noisy = {
            t
            for t, used in self.device_s.items()
            if len(tenants) > 1
            and used > _NOISY_SHARE_FACTOR * fair
            and (self.breached - {t})
        }
        for t in noisy - self.flagged:
            self.noisy_gauge.labels(t).set(1)
            timeline_mod.get_timeline().record(
                "noisy_neighbor", f"tenant {t!r} over fair device share "
                "while neighbors breached their queue-wait SLO",
                severity=timeline_mod.WARN, tenant=t,
            )
        for t in self.flagged - noisy:
            self.noisy_gauge.labels(t).set(0)
            timeline_mod.get_timeline().record(
                "noisy_neighbor", f"tenant {t!r} back within fair share",
                tenant=t,
            )
        self.flagged = noisy
        self.device_s = {}
        self.breached = set()
        self.window_end = now + _NOISY_WINDOW_S


class _BatcherMetrics:
    """Bound registry children for one named batcher."""

    __slots__ = ("_depth", "_shed", "_shed_class", "_name", "_occupancy",
                 "_dispatch", "_enqueue", "_sync", "_batches",
                 "_groups", "_group_slots", "_windows_waited",
                 "_cancelled", "_expired", "_leaked",
                 "_tenant_device", "_tenant_wait", "_tenant_requests",
                 "_tenant_children",
                 "_attr_lock", "_noisy")

    def __init__(self, registry: MetricRegistry, name: str):
        self._name = name
        self._depth = registry.gauge(
            "pio_batch_queue_depth",
            "Items waiting in the micro-batch queue",
            ("batcher",),
        ).labels(name)
        self._shed = registry.counter(
            "pio_batch_shed_total",
            "Submissions refused at the queue-depth bound",
            ("batcher",),
        ).labels(name)
        self._shed_class = registry.counter(
            "pio_shed_total",
            "Work shed by the batcher under overload, by criticality "
            "class (refused at the bound, or evicted by a "
            "higher-criticality submission)",
            ("batcher", "class"),
        )
        self._occupancy = registry.histogram(
            "pio_batch_occupancy",
            "Queries per dispatched device batch",
            ("batcher",),
            buckets=OCCUPANCY_BUCKETS,
        ).labels(name)
        self._dispatch = registry.histogram(
            "pio_device_dispatch_seconds",
            "End-to-end wall clock of one batch: device enqueue "
            "through collected results",
            ("batcher",),
            buckets=LATENCY_BUCKETS,
        ).labels(name)
        self._enqueue = registry.histogram(
            "pio_device_enqueue_seconds",
            "Host time enqueuing one batch to the device (dispatch(); "
            "~0 where it only hands the items on)",
            ("batcher",),
            buckets=LATENCY_BUCKETS,
        ).labels(name)
        self._sync = registry.histogram(
            "pio_device_sync_seconds",
            "Device barrier, transfer to the host and result "
            "materialization of one batch (collect(); a batch_fn that "
            "is a plain callable runs here whole)",
            ("batcher",),
            buckets=LATENCY_BUCKETS,
        ).labels(name)
        self._batches = registry.counter(
            "pio_batches_total",
            "Device batches dispatched",
            ("batcher",),
        ).labels(name)
        self._groups = registry.counter(
            "pio_batch_groups_total",
            "Groups admitted by submit_group (a post's queries in one "
            "admission)",
            ("batcher",),
        ).labels(name)
        self._group_slots = registry.counter(
            "pio_batch_group_slots_total",
            "Slots that entered the queue in groups (over "
            "pio_batch_groups_total: slots a group; over "
            "pio_batch_occupancy's sum: the share of dispatched "
            "queries that arrived in groups)",
            ("batcher",),
        ).labels(name)
        self._windows_waited = registry.counter(
            "pio_batch_windows_waited_total",
            "Coalescing windows in which the collector slept for "
            "company (over pio_batches_total: the waited share)",
            ("batcher",),
        ).labels(name)
        self._cancelled = registry.counter(
            "pio_batch_cancelled_total",
            "Slots cancelled before dispatch (device work avoided)",
            ("batcher",),
        ).labels(name)
        self._expired = registry.counter(
            "pio_batch_deadline_expired_total",
            "Slots dropped before device dispatch because their "
            "deadline had already expired",
            ("batcher",),
        ).labels(name)
        self._leaked = registry.counter(
            "pio_batcher_leaked_threads_total",
            "Worker threads still alive after close() timed out "
            "joining them",
            ("batcher",),
        ).labels(name)
        # tenant cost attribution: families are UNBOUND (labelled per
        # settle) and shared across batchers — the registry get-or-create
        # makes repeat registration from every batcher/pool safe, and
        # fleet federation sums them per tenant across replicas
        self._tenant_device = registry.counter(
            "pio_tenant_device_seconds_total",
            "Measured device time (enqueue + sync) apportioned to the "
            "tenant's slots, by slot count per coalesced batch",
            ("tenant",),
        )
        self._tenant_wait = registry.histogram(
            "pio_tenant_queue_wait_seconds",
            "Per-slot wait between batch submit and device dispatch, "
            "by tenant",
            ("tenant",),
            buckets=LATENCY_BUCKETS,
        )
        self._tenant_requests = registry.counter(
            "pio_tenant_requests_total",
            "Batch slots settled per tenant, by outcome",
            ("tenant", "status"),
        )
        #: a tenant's three children by (tenant, status): resolved at
        #: its first settle, then hit directly (a batch of a lone query
        #: would pay three labels() lookups for one slot)
        self._tenant_children: dict = {}
        self._attr_lock = threading.Lock()
        self._noisy = _NoisyRollup(
            registry.gauge(
                "pio_tenant_noisy",
                "1 while the tenant exceeds its fair device share AND "
                "other tenants' queue waits breach the wait SLO "
                "(advisory; see docs/observability.md)",
                ("tenant",),
            )
        )

    def queue_depth(self, n: int) -> None:
        self._depth.set(n)

    def shed(self, criticality: str, n: int = 1) -> None:
        self._shed.inc(n)
        self._shed_class.labels(self._name, criticality).inc(n)

    def grouped(self, slots: int) -> None:
        self._groups.inc()
        self._group_slots.inc(slots)

    def window_waited(self) -> None:
        self._windows_waited.inc()

    def dispatched(self, occupancy: int, seconds: float) -> None:
        self._batches.inc()
        self._occupancy.observe(occupancy)
        self._dispatch.observe(seconds)

    def enqueued(self, seconds: float) -> None:
        self._enqueue.observe(seconds)

    def synced(self, seconds: float) -> None:
        self._sync.observe(seconds)

    def cancelled(self, n: int) -> None:
        self._cancelled.inc(n)

    def expired(self, n: int) -> None:
        self._expired.inc(n)

    def leaked(self) -> None:
        self._leaked.inc()

    def attributed(
        self, tenant: str, n: int, device_s: float, wait_s: float,
        status: str,
    ) -> None:
        """The share of a settled batch of one group's ``n`` slots that
        rode it: ``device_s`` a slot, after one ``wait_s`` (they arrived
        together). Conservation contract: the settle paths call this
        for EVERY live slot of the batch with exactly
        ``(enqueue_s + sync_s) / live`` a slot, success and failure
        alike, so the per-tenant sum equals the batcher's measured
        device time, and the wait histogram gets one observation a
        slot: a mean over queries (asserted in tests and
        scripts/metrics_smoke.py)."""
        children = self._tenant_children.get((tenant, status))
        if children is None:
            children = self._tenant_children[tenant, status] = (
                self._tenant_device.labels(tenant),
                self._tenant_wait.labels(tenant),
                self._tenant_requests.labels(tenant, status),
            )
        device, wait, requests = children
        device.inc(n * device_s)
        wait.observe(wait_s, n)
        requests.inc(n)
        # settlement runs on the completer AND the collector (a failed
        # dispatch); the rollup's read-modify-write needs its own tiny
        # guard
        with self._attr_lock:
            self._noisy.observe(tenant, n * device_s, wait_s)


class MicroBatcher:
    """Coalesce submitted items (``submit``: one, behind a Future;
    ``submit_group``: a request's, as one :class:`BatchGroup`) into
    batches for ``batch_fn``. A group longer than ``max_batch``, or one
    that meets a part-filled buffer, rides two batches and completes
    when its last slot settles, answers in the items' order.

    A batch is dispatched when ``max_batch`` items are waiting or the
    coalescing wait elapsed since the first queued item — the classic
    latency/throughput knob. With ``adaptive_wait`` (default on) the
    window is waited only while another arrival is expected inside it:
    ``submit`` keeps ``_gap_ewma``, an exponentially weighted mean of
    the gaps between consecutive arrivals (weight ``_GAP_WEIGHT``, each
    gap counted as at most ``_GAP_CAP_WINDOWS`` windows), and the
    collector skips the window when that mean is longer than
    ``max_wait_ms`` — waiting only adds latency when nothing will join
    (a group of n is n arrivals, n-1 of them at gap 0).
    Otherwise, and always with no history (fewer than two arrivals) or
    with ``adaptive_wait`` off, it waits up to ``max_wait_ms``. Under
    load the coalescing comes from backlog: what arrives while the
    collector dispatches leaves together. ``max_queue`` bounds queued
    items: beyond it, ``submit``
    raises :class:`BatcherOverloaded` so overload turns into fast
    shedding rather than client-side timeout hangs.

    ``pipeline_depth`` bounds batches in flight between device enqueue
    and collected results (default 2 = double buffering; at least 1:
    dispatch and collect always run on two threads).

    Returned futures and groups support ``cancel()`` up to the moment
    their batch is dispatched: a cancelled slot is dropped from the
    batch (its device work never happens) and counted in
    ``pio_batch_cancelled_total``. Callers that abandon accepted
    slots (e.g. a partially-overloaded multi-algorithm batch slot)
    should cancel them rather than leak the dispatch.

    Overload semantics: the wait queue is not strictly FIFO. When the
    backlog exceeds ``max_batch`` at selection time, the slots with the
    nearest deadlines dispatch first (work about to expire must not
    rot behind slack work); arrival order breaks ties and orders
    deadline-less slots. At the ``max_queue`` bound, a submission of a
    strictly higher criticality class (``X-PIO-Criticality``, read
    from the admission contextvar) evicts the lowest-class queued slot
    — the evicted slot fails with :class:`BatcherOverloaded` and the
    shed is accounted per class in ``pio_shed_total{batcher,class}``;
    a group at the bound is admitted as far as it fits (and evicts),
    and its other slots are shed.
    """

    def __init__(
        self,
        batch_fn: Callable[[Sequence[Any]], Sequence[Any]] | TwoPhaseBatchFn,
        max_batch: int = 64,
        max_wait_ms: float = 2.0,
        max_queue: int | None = None,
        registry: MetricRegistry | None = None,
        name: str = "default",
        close_join_timeout_s: float = 30.0,
        pipeline_depth: int = 2,
        adaptive_wait: bool = True,
    ):
        if pipeline_depth < 1:
            raise ValueError(DEPTH_ZERO_GONE.format(pipeline_depth))
        if not (
            hasattr(batch_fn, "dispatch") and hasattr(batch_fn, "collect")
        ):
            # a plain callable is the pair whose dispatch hands the
            # items on: it runs once a batch, as the collect stage, so
            # next-batch assembly still overlaps its compute
            batch_fn = TwoPhaseBatchFn(lambda items: items, batch_fn)
        self._dispatch_fn = batch_fn.dispatch
        self._collect_fn = batch_fn.collect
        self._max_batch = max_batch
        self._max_wait = max_wait_ms / 1000.0
        self._adaptive = adaptive_wait
        #: the arrival gap the window is held against (introspectable,
        #: seconds; cv held): 0 until two arrivals have been seen, so a
        #: batcher with no history waits
        self._gap_ewma = 0.0
        self._gap_cap = _GAP_CAP_WINDOWS * self._max_wait
        self._last_arrival: float | None = None
        self._close_join_timeout_s = close_join_timeout_s
        self._max_queue = (
            max_queue if max_queue is not None else 8 * max_batch
        )
        self.name = name
        # a batcher built without a registry counts into one of its own
        self._metrics = _BatcherMetrics(
            registry if registry is not None else MetricRegistry(), name
        )
        #: where both worker threads time their stages (and the model's
        #: predict.* stages that run on them)
        self._stages = tracing.StageSink(registry)
        #: wait queue + its condition: submit appends and notifies, the
        #: collector selects under the same lock. One lock, never held
        #: across dispatch or any blocking wait (Condition.wait excepted)
        self._cv = threading.Condition()
        self._buf: list[tuple[_Group, int]] = []
        self._closed = threading.Event()
        #: EWMA of end-to-end batch seconds — feeds retry_after_s().
        #: Guarded by the cv: the settle path runs on BOTH worker
        #: threads (completer normally, collector for dispatch-phase
        #: failures), so the read-modify-write would otherwise lose
        #: updates between them
        self._batch_ewma_s = 0.0
        self._pending: queue.Queue = queue.Queue()
        self._inflight = threading.Semaphore(pipeline_depth)
        self._completer = threading.Thread(
            target=self._complete_loop, daemon=True
        )
        self._completer.start()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def submit(self, item: Any) -> Future:
        """One item, answered through a ``Future``: a group of one.
        Raises what refused it at admission (closed, deadline expired,
        shed at the bound)."""
        future = _SlotFuture(item)
        self._admit(future)
        if not future.admitted:
            raise future.outcomes[0]
        return future

    def submit_group(self, items: Sequence[Any]) -> BatchGroup:
        """A request's items in one admission, answered through the
        :class:`BatchGroup`: ``wait`` once, then ``result(i)`` in the
        items' order. Raises where no slot can be admitted for a reason
        the whole request shares (closed, deadline expired); at the
        queue bound the slots that fit are admitted and the rest hold
        :class:`BatcherOverloaded` (``group.admitted`` says where they
        start)."""
        group = BatchGroup(items)
        self._admit(group)
        self._metrics.grouped(group.admitted)
        return group

    def _admit(self, group: _Group) -> None:
        """The one admission: the condition taken once, the closed
        check, the deadline check and the queue bound applied once over
        the group's slots, the collector notified once."""
        # a request whose budget already ran out must not take a
        # queue slot at all — the 504 costs nothing here but would
        # cost a dispatch slot at flush time. Checked BEFORE the
        # overload bound: doomed work must never trigger an eviction.
        deadline = group.deadline
        criticality = group.criticality
        n = len(group.items)
        fit, victims = n, ()
        # the cv orders admission against close(): once closed is set
        # under it, no new slot can slip into the buffer behind the drain
        with self._cv:
            if self._closed.is_set():
                raise RuntimeError("batcher is closed")
            if deadline is not None and deadline.expired:
                self._metrics.expired(n)
                raise resilience.DeadlineExceeded(
                    "deadline expired before batch submit"
                )
            if self._max_queue > 0:
                room = max(0, self._max_queue - len(self._buf))
                if room < n:
                    victims = self._pick_victims(criticality, n - room)
                    fit = room + len(victims)
                    if victims:
                        evicted = set(victims)
                        self._buf = [
                            s for s in self._buf if s not in evicted
                        ]
            if fit:
                # the arrival instant is stamped unconditionally (not
                # just under a trace): per-tenant queue-wait attribution
                # needs it, and the window rule the gap to the arrival
                # before it. A group is ``fit`` arrivals, all but the
                # first at gap 0: the folds of those, in closed form
                now = time.monotonic()
                if self._last_arrival is not None:
                    gap = min(now - self._last_arrival, self._gap_cap)
                    self._gap_ewma += _GAP_WEIGHT * (gap - self._gap_ewma)
                    self._gap_ewma *= (1.0 - _GAP_WEIGHT) ** (fit - 1)
                self._last_arrival = now
                group.submitted_mono = now
                # before the slots can be seen: a batch may settle them
                # before this thread runs again
                group.admitted = group._pending = fit
                self._buf += [(group, i) for i in range(fit)]
                self._metrics.queue_depth(len(self._buf))
                self._cv.notify()
        # outcomes are stored OUTSIDE the lock: a done-callback runs
        # inline and must not execute under the batcher's condition
        for victim, index in victims:
            self._metrics.shed(victim.criticality)
            victim._drop(
                (index,),
                BatcherOverloaded(
                    "shed: evicted by a higher-criticality submission "
                    "under overload"
                ),
            )
        if fit < n:
            self._metrics.shed(criticality, n - fit)
            group.outcomes[fit:] = [
                BatcherOverloaded(
                    f"batch queue at capacity ({self._max_queue})"
                )
            ] * (n - fit)
        if not fit:
            group._finish()  # nothing of it waits: done at once

    def _pick_victims(self, criticality: str, wanted: int) -> list:
        """cv held. The queued slots, at most ``wanted``, a full buffer
        sheds to admit a ``criticality``-class submission: strictly
        lower class only (equal class waits its turn — no churn),
        lowest class first, then the nearest deadline (the slot most
        likely to die unserved anyway loses the least goodput), then
        the latest arrival."""
        incoming = admission.CLASS_RANK.get(
            criticality, admission.CLASS_RANK[admission.DEFAULT]
        )
        candidates = []
        for i, slot in enumerate(self._buf):
            group, index = slot
            rank = admission.CLASS_RANK.get(group.criticality, 1)
            if rank >= incoming or group.outcomes[index] is not _QUEUED:
                continue  # no lower class, or cancelled
            candidates.append((
                rank,
                group.deadline.expires_mono
                if group.deadline is not None
                else math.inf,
                -i,
                slot,
            ))
        return [c[3] for c in heapq.nsmallest(wanted, candidates)]

    def __call__(self, item: Any, timeout: float | None = 30.0) -> Any:
        # the waiter must never outlive the budget it was admitted
        # under: a request deadline in context caps the result wait, so
        # an expired budget surfaces as a timeout now, not 30 s later
        deadline = resilience.get_deadline()
        if deadline is not None:
            timeout = deadline.cap(
                timeout
                if timeout is not None
                else resilience.Deadline.MAX_BUDGET_S
            )
        return self.submit(item).result(timeout=timeout)

    def retry_after_s(self) -> float:
        """Cooperative-backpressure hint from live queue state: about
        how long until the current backlog has drained through the
        device (queued batches × recent batch time), clamped to
        [0.05, 5] — what a shed response's ``Retry-After`` should say
        (docs/robustness.md)."""
        with self._cv:
            depth = len(self._buf)
            per_batch = max(self._batch_ewma_s, 0.001)
        batches_ahead = 1.0 + depth / max(1, self._max_batch)
        return min(5.0, max(0.05, batches_ahead * per_batch))

    def close(self) -> None:
        """Graceful, in pipeline order: the collector drains queued
        items through dispatch, in-flight dispatches complete,
        their futures resolve, then both threads exit. A worker stuck
        in a hung dispatch past the join timeout is reported
        (structured warning + ``pio_batcher_leaked_threads_total``)
        instead of silently leaked."""
        with self._cv:
            if self._closed.is_set():
                return
            self._closed.set()
            self._cv.notify_all()  # wake the collector to drain
        join_deadline = time.monotonic() + self._close_join_timeout_s
        self._thread.join(timeout=self._close_join_timeout_s)
        # the completer sentinel is sent by the collector alone (end of
        # its drain loop). If the collector is hung we do NOT inject
        # one here: it could overtake a batch the stuck collector is
        # still about to hand off, and an exited completer would strand
        # that batch's futures forever. Both threads are daemons — if
        # the collector ever unblocks it drains, sends the real
        # sentinel, and the futures resolve late instead of never.
        self._completer.join(
            timeout=max(0.1, join_deadline - time.monotonic())
        )
        if self._thread.is_alive() or self._completer.is_alive():
            self._metrics.leaked()
            log_json(
                logger, logging.WARNING, "batcher_thread_leaked",
                batcher=self.name,
                joinTimeoutS=self._close_join_timeout_s,
            )

    # -- collector stage ---------------------------------------------------
    def _select_batch(self) -> list:
        """cv held. Take up to ``max_batch`` slots out of the buffer —
        deadline-aware when over-full: the nearest-deadline slots go
        first so near-expiry work isn't served dead behind slack work;
        arrival order breaks ties (and orders deadline-less slots), and
        the dispatched batch itself keeps arrival order."""
        buf = self._buf
        if len(buf) <= self._max_batch:
            batch = buf
            self._buf = []
        else:
            order = sorted(
                range(len(buf)),
                key=lambda i: (
                    buf[i][0].deadline.expires_mono
                    if buf[i][0].deadline is not None
                    else math.inf,
                    i,
                ),
            )
            chosen = set(order[: self._max_batch])
            batch = [buf[i] for i in sorted(chosen)]
            self._buf = [
                slot for i, slot in enumerate(buf) if i not in chosen
            ]
        if not self._closed.is_set():
            # a closed batcher is a draining OLD generation — after
            # /reload its replacement shares the same gauge child, and
            # a final set() here would overwrite the live queue depth
            self._metrics.queue_depth(len(self._buf))
        return batch

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._buf and not self._closed.is_set():
                    self._cv.wait()
                if not self._buf:
                    break  # closed and fully drained
                seq = next(_BATCH_SEQ)
                # the window's n is what was queued when it opened
                self._stages.bind(batch=seq, n=len(self._buf))
                with tracing.stage(tracing.BATCH_WINDOW):
                    # coalesce only while company is expected: the
                    # arrivals' recent gap is within the window (or the
                    # window is fixed). A full batch, or close landing —
                    # a drain dispatches immediately — ends it early
                    slept = False
                    if (
                        not self._adaptive
                        or self._gap_ewma <= self._max_wait
                    ):
                        window_end = time.monotonic() + self._max_wait
                        while (
                            len(self._buf) < self._max_batch
                            and not self._closed.is_set()
                        ):
                            remaining = window_end - time.monotonic()
                            if remaining <= 0:
                                break
                            self._cv.wait(remaining)
                            slept = True
                    if slept:
                        self._metrics.window_waited()
                    batch = self._select_batch()
            self._stages.bind(batch=seq, n=len(batch))
            self._dispatch_batch(batch, seq)
        self._pending.put(None)  # completer drains in order, then exits

    def _dispatch_batch(self, batch, seq: int) -> None:
        # backpressure BEFORE the cancellation/deadline cutoff: while
        # the collector waits for a pipeline slot (device slow, depth
        # exhausted) waiters can still cancel and budgets can still
        # expire — the cutoff below must be the last word before the
        # device sees the work
        with tracing.stage(tracing.BATCH_BACKPRESSURE):
            self._inflight.acquire()
        # the cut-off, group by group (a group's slots lie together in
        # arrival order): cancelled slots drop out HERE, before the
        # device sees them — cancellation is how an abandoning caller
        # turns wasted dispatch into avoided dispatch. Expired-deadline
        # slots drop out the same way (the deadline re-check at dispatch
        # entry): their waiter is already gone (or about to time out),
        # so dispatching them would burn device time computing
        # unreceivable answers.
        runs = []
        items = []
        cancelled = expired = 0
        # dispatch-span bookkeeping only when at least one group was
        # submitted under an open trace — untraced traffic pays nothing
        traced = False
        for group, slots in itertools.groupby(batch, _group_of):
            live, dropped, late = group._start([i for _, i in slots])
            cancelled += dropped
            expired += late
            if live:
                runs.append((group, live))
                items += [group.items[i] for i in live]
                traced = traced or group.parent_span is not None
        if cancelled:
            self._metrics.cancelled(cancelled)
        if expired:
            self._metrics.expired(expired)
            log_json(
                logger, logging.DEBUG, "batch_slots_expired",
                batcher=self.name, expired=expired,
            )
        if not runs:
            self._inflight.release()
            return
        n = len(items)
        start_wall = tracing.now() if traced else 0.0
        # dispatch-start is stamped unconditionally: queue-wait
        # attribution (submit -> dispatch) covers untraced traffic too
        start_mono = time.monotonic()
        t0 = time.perf_counter()
        try:
            handle = self._dispatch_fn(items)
        except Exception as e:  # noqa: BLE001 - propagate to waiters
            self._inflight.release()
            enqueue_s = time.perf_counter() - t0
            self._metrics.enqueued(enqueue_s)
            self._settle(
                runs, n, e, time.perf_counter() - t0,
                start_wall, start_mono, traced,
                enqueue_s=enqueue_s, sync_s=0.0, phase="dispatch",
            )
            return
        enqueue_s = time.perf_counter() - t0
        self._metrics.enqueued(enqueue_s)
        self._pending.put(
            _Inflight(
                runs, n, handle, start_wall, start_mono, t0, enqueue_s,
                traced, seq,
            )
        )

    # -- completer stage ---------------------------------------------------
    def _complete_loop(self) -> None:
        while True:
            rec = self._pending.get()
            if rec is None:
                return
            self._stages.bind(batch=rec.seq, n=rec.n)
            try:
                t1 = time.perf_counter()
                sync_s = 0.0
                try:
                    # sync time is observed in the finally so a failed
                    # collect's device time lands in the histogram too
                    # — attribution charges exactly what was observed,
                    # success or failure (conservation)
                    try:
                        outcome = self._collect_fn(rec.handle)
                    finally:
                        sync_s = time.perf_counter() - t1
                        self._metrics.synced(sync_s)
                    if len(outcome) != rec.n:
                        raise RuntimeError(
                            f"batch_fn returned {len(outcome)} results "
                            f"for {rec.n} items"
                        )
                except Exception as e:  # noqa: BLE001 - to every waiter
                    outcome = e
                self._settle(
                    rec.runs, rec.n, outcome, time.perf_counter() - rec.t0,
                    rec.start_wall, rec.start_mono, rec.traced,
                    enqueue_s=rec.enqueue_s, sync_s=sync_s,
                    phase="collect",
                )
            finally:
                self._inflight.release()

    # -- shared settlement -------------------------------------------------
    def _observe_batch_time(self, elapsed: float) -> None:
        # feeds retry_after_s(). Settlement runs on the completer OR
        # the collector (dispatch-phase failure), so the EWMA fold
        # takes the cv — both writers and the retry_after_s() reader
        # agree on one guard
        with self._cv:
            self._batch_ewma_s = (
                elapsed
                if self._batch_ewma_s == 0.0
                else 0.8 * self._batch_ewma_s + 0.2 * elapsed
            )

    def _attribute(
        self, runs, n: int, start_mono: float, enqueue_s: float,
        sync_s: float, status: str,
    ) -> None:
        """Apportion the batch's measured device time across its slots
        by slot count — every live slot, on success AND failure paths,
        so per-tenant sums conserve the batcher's total device time.
        A group's slots of the batch are charged in one step."""
        share = (enqueue_s + sync_s) / n
        for group, live in runs:
            self._metrics.attributed(
                group.tenant,
                len(live),
                share,
                max(0.0, start_mono - group.submitted_mono),
                status,
            )

    def _settle(
        self, runs, n: int, outcome, elapsed: float, start_wall: float,
        start_mono: float, traced: bool, enqueue_s: float, sync_s: float,
        phase: str,
    ) -> None:
        """Store a batch's outcomes into the groups its ``n`` slots
        belong to: ``outcome`` is its results, one a slot, or the
        exception every slot gets (raised in ``phase``). A group whose
        last slot this was wakes its waiter, once."""
        failed = isinstance(outcome, Exception)
        error = f"{type(outcome).__name__}: {outcome}" if failed else None
        with tracing.stage(tracing.BATCH_SETTLE):
            self._observe_batch_time(elapsed)
            self._metrics.dispatched(n, elapsed)
            self._attribute(
                runs, n, start_mono, enqueue_s, sync_s,
                "error" if failed else "ok",
            )
            if traced:
                self._record_dispatch_spans(
                    runs, n, start_wall, start_mono, elapsed,
                    enqueue_s=enqueue_s, sync_s=sync_s, error=error,
                )
            if failed:
                log_json(
                    logger, logging.WARNING, "batch_dispatch_failed",
                    batcher=self.name, occupancy=n, phase=phase,
                    ms=round(elapsed * 1000, 3), error=error,
                    requestIds=self._request_ids(runs),
                )
            elif logger.isEnabledFor(logging.DEBUG):
                # asked first: the line's fields cost a lone query's
                # batch as much as storing its answer
                log_json(
                    logger, logging.DEBUG, "batch_dispatch",
                    batcher=self.name, occupancy=n,
                    ms=round(elapsed * 1000, 3),
                    enqueueMs=round(enqueue_s * 1000, 3),
                    requestIds=self._request_ids(runs),
                )
            at = 0
            for group, live in runs:
                end = at + len(live)
                group._store(
                    live,
                    [outcome] * len(live) if failed else outcome[at:end],
                )
                at = end

    @staticmethod
    def _request_ids(runs) -> list:
        return [g.request_id for g, _ in runs if g.request_id]

    def _record_dispatch_spans(
        self, runs, n: int, start_wall: float, start_mono: float,
        elapsed: float, enqueue_s: float = 0.0, sync_s: float = 0.0,
        error: str | None = None,
    ) -> None:
        """One device dispatch, seen from every trace that rode in it.

        The dispatch happens once but coalesces queries from many
        requests (= many traces), so each DISTINCT submitting span gets
        one child ``batch_dispatch`` span copy carrying the shared
        timing plus its queue wait, with ``links`` naming every
        coalesced query span — the cross-request join Perfetto can't
        infer. Distinct matters: a request that submits more than once
        under one span (a single query after another) must not overflow
        the per-trace span cap with duplicates."""
        parents: dict[str, tuple] = {}
        for group, _live in runs:
            span = group.parent_span
            if span is not None and span.span_id not in parents:
                parents[span.span_id] = (span, group.submitted_mono)
        links = [
            f"{p.trace_id}:{p.span_id}" for p, _t in parents.values()
        ]
        for parent, submitted_mono in parents.values():
            # retrospective span: built AFTER the interval it describes,
            # start/duration assigned below and recorded directly — it
            # is never entered, so it cannot sit in the open-trace
            # table, and there is no exit path on which it could leak
            # pio-lint: disable-next=span-leak -- retrospective: recorded complete, never opened
            dispatch = tracing.Span(
                parent.tracer,
                parent.trace_id,
                "batch_dispatch",
                parent_id=parent.span_id,
                trace_key=parent.trace_key,
                attributes={
                    "batcher": self.name,
                    "occupancy": n,
                    "queueWaitMs": round(
                        max(0.0, start_mono - submitted_mono) * 1000, 3
                    ),
                    "deviceDispatchMs": round(elapsed * 1000, 3),
                    "hostEnqueueMs": round(enqueue_s * 1000, 3),
                    "deviceMs": round(sync_s * 1000, 3),
                    "links": links,
                },
            )
            if error is not None:
                dispatch.attributes["error"] = error
            dispatch.start = start_wall
            dispatch.duration = elapsed
            parent.tracer.record(dispatch)
