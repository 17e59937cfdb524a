"""Pipelined micro-batching: two-phase dispatch, failure modes, the
adaptive coalescing window, and a plain callable taken as the pair
(docs/serving.md "Pipelined dispatch").

The pipeline's invariants under failure matter more than its happy
path: a dispatch-stage error must only poison its own batch (the one
already enqueued behind it still resolves), close() must drain
in-flight dispatches in order, and cancellation racing the
collector→dispatch handoff must end in exactly one terminal state.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import TimeoutError as FuturesTimeout

import pytest

from predictionio_tpu.obs import MetricRegistry
from predictionio_tpu.serving import resilience
from predictionio_tpu.serving.batching import (
    BatcherOverloaded,
    MicroBatcher,
    TwoPhaseBatchFn,
)


class _TwoPhase:
    """Scriptable two-phase batch_fn: blockable collect, per-batch
    dispatch failure injection, full call logs."""

    def __init__(self):
        self.release = threading.Event()
        self.release.set()
        self.dispatched: list[list] = []
        self.collected: list[list] = []
        self.lock = threading.Lock()

    def dispatch(self, items):
        if items and items[0] == "boom-dispatch":
            raise ValueError("injected dispatch failure")
        with self.lock:
            self.dispatched.append(list(items))
        return list(items)

    def collect(self, handle):
        if not self.release.wait(timeout=10):
            raise RuntimeError("collect never released")
        if handle and handle[0] == "boom-collect":
            raise ValueError("injected collect failure")
        with self.lock:
            self.collected.append(list(handle))
        return [str(i).upper() for i in handle]


class TestTwoPhaseProtocol:
    def test_results_in_order_through_both_stages(self):
        fn = _TwoPhase()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=4, max_wait_ms=5,
        )
        try:
            futures = [b.submit(f"q{i}") for i in range(10)]
            assert [f.result(5) for f in futures] == [
                f"Q{i}" for i in range(10)
            ]
            assert sum(len(d) for d in fn.dispatched) == 10
            assert fn.dispatched == fn.collected
        finally:
            b.close()

    def test_enqueue_overlaps_inflight_collect(self):
        """The pipelining claim itself: batch B's dispatch happens
        while batch A is still inside collect. Proved by deadlock
        avoidance — A's collect only unblocks once B has dispatched,
        so a serial batcher would hang here."""
        b_dispatched = threading.Event()

        class Fn:
            def dispatch(self, items):
                if items[0] == "b":
                    b_dispatched.set()
                return items

            def collect(self, handle):
                if handle[0] == "a":
                    assert b_dispatched.wait(timeout=5), (
                        "batch B never dispatched while A was in "
                        "collect — the stages are not overlapping"
                    )
                return [i * 2 for i in handle]

        fn = Fn()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=1, max_wait_ms=0.1, pipeline_depth=2,
        )
        try:
            fa = b.submit("a")
            fb = b.submit("b")
            assert fa.result(5) == "aa"
            assert fb.result(5) == "bb"
        finally:
            b.close()

    def test_pipeline_depth_bounds_inflight(self):
        """No more than pipeline_depth batches may sit between
        dispatch and collected results."""
        inflight = []
        peak = []
        lock = threading.Lock()
        gate = threading.Event()

        class Fn:
            def dispatch(self, items):
                with lock:
                    inflight.append(1)
                    peak.append(len(inflight))
                return items

            def collect(self, handle):
                gate.wait(10)
                with lock:
                    inflight.pop()
                return handle

        fn = Fn()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=1, max_wait_ms=0.1, pipeline_depth=2,
        )
        try:
            futures = [b.submit(i) for i in range(6)]
            time.sleep(0.3)  # give the collector every chance to overrun
            assert max(peak) <= 2
            gate.set()
            for f in futures:
                f.result(5)
            assert max(peak) <= 2
        finally:
            gate.set()
            b.close()


class TestPipelineFailureModes:
    def test_dispatch_raise_with_next_batch_enqueued(self):
        """A dispatch-stage error while another batch is already in
        flight: the failed batch's futures get the error immediately,
        the in-flight batch still resolves normally."""
        fn = _TwoPhase()
        fn.release.clear()  # hold batch A inside collect
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=1, max_wait_ms=0.1, pipeline_depth=2,
        )
        try:
            fa = b.submit("a")
            # wait until A is dispatched (in flight, uncollected)
            deadline = time.monotonic() + 5
            while not fn.dispatched and time.monotonic() < deadline:
                time.sleep(0.005)
            assert fn.dispatched == [["a"]]
            fboom = b.submit("boom-dispatch")
            with pytest.raises(ValueError, match="injected dispatch"):
                fboom.result(5)  # fails while A is STILL blocked
            assert not fa.done()
            fn.release.set()
            assert fa.result(5) == "A"
        finally:
            fn.release.set()
            b.close()

    def test_collect_raise_only_poisons_its_batch(self):
        fn = _TwoPhase()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=1, max_wait_ms=0.1, pipeline_depth=2,
        )
        try:
            fboom = b.submit("boom-collect")
            fok = b.submit("ok")
            with pytest.raises(ValueError, match="injected collect"):
                fboom.result(5)
            assert fok.result(5) == "OK"
        finally:
            b.close()

    def test_close_during_inflight_dispatch(self):
        """close() while a batch is inside collect: the batch resolves,
        both threads join, nothing leaks."""
        registry = MetricRegistry()
        fn = _TwoPhase()
        fn.release.clear()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=1, max_wait_ms=0.1, pipeline_depth=2,
            registry=registry, name="closing",
        )
        f1 = b.submit("x")
        f2 = b.submit("y")  # queued behind the blocked collect
        closed = threading.Event()

        def close():
            b.close()
            closed.set()

        t = threading.Thread(target=close)
        t.start()
        time.sleep(0.1)
        assert not closed.is_set()  # close is draining, not abandoning
        fn.release.set()
        t.join(timeout=10)
        assert closed.is_set()
        assert f1.result(1) == "X"
        assert f2.result(1) == "Y"
        leaked = registry.counter(
            "pio_batcher_leaked_threads_total", "", ("batcher",)
        ).labels("closing")
        assert leaked.value == 0

    def test_deadline_expiring_during_backpressure_wait_is_honored(self):
        """A budget that dies while the collector is blocked on the
        pipeline-depth semaphore must still drop the slot before the
        device sees it — the cutoff is the last word before dispatch."""
        gate = threading.Event()
        dispatched = []

        class Fn:
            def dispatch(self, items):
                dispatched.append(list(items))
                return items

            def collect(self, handle):
                gate.wait(10)
                return list(handle)

        b = MicroBatcher(
            TwoPhaseBatchFn(Fn().dispatch, Fn().collect),
            max_batch=1, max_wait_ms=0.1, pipeline_depth=1,
        )
        try:
            fa = b.submit("a")  # occupies the only pipeline slot
            deadline = time.monotonic() + 5
            while not dispatched and time.monotonic() < deadline:
                time.sleep(0.005)
            resilience.set_deadline(resilience.Deadline.after(0.15))
            fb = b.submit("b")
            resilience.set_deadline(None)
            time.sleep(0.4)  # budget dies while collector waits on slot
            gate.set()
            assert fa.result(5) == "a"
            with pytest.raises(resilience.DeadlineExceeded):
                fb.result(5)
            assert dispatched == [["a"]]  # "b" never reached the device
        finally:
            resilience.set_deadline(None)
            gate.set()
            b.close()

    def test_cancel_racing_the_handoff(self):
        """cancel() racing the collector→dispatch handoff: every
        future ends in exactly one terminal state, and a won cancel
        means the item NEVER reached dispatch."""
        fn = _TwoPhase()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=2, max_wait_ms=0.5, pipeline_depth=2,
        )
        try:
            outcomes = {"cancelled": 0, "served": 0}
            for i in range(60):
                f = b.submit(f"r{i}")
                if i % 2:
                    time.sleep(0.0005)  # land some cancels mid-handoff
                won = f.cancel()
                if won:
                    outcomes["cancelled"] += 1
                    assert f.cancelled()
                else:
                    assert f.result(5) == f"R{i}"
                    outcomes["served"] += 1
            with fn.lock:
                dispatched = [i for batch in fn.dispatched for i in batch]
            # a won cancel is a promise the device never saw the item
            assert len(dispatched) == outcomes["served"]
            assert outcomes["cancelled"] + outcomes["served"] == 60
        finally:
            b.close()


class TestSinglePhaseCompat:
    def test_zero_extra_barriers_exactly_one_call_per_batch(self):
        """The compat path must not add barriers around a plain
        batch_fn: exactly one call per dispatched batch, no wrapper
        invocations, counts matching pio_batches_total."""
        registry = MetricRegistry()
        calls: list[list] = []

        def batch_fn(items):
            calls.append(list(items))
            return [i * 2 for i in items]

        b = MicroBatcher(
            batch_fn, max_batch=8, max_wait_ms=5,
            registry=registry, name="compat",
        )
        try:
            futures = [b.submit(i) for i in range(24)]
            assert [f.result(5) for f in futures] == [
                i * 2 for i in range(24)
            ]
        finally:
            b.close()
        batches = registry.counter(
            "pio_batches_total", "", ("batcher",)
        ).labels("compat").value
        assert len(calls) == batches
        assert sum(len(c) for c in calls) == 24

    @pytest.mark.parametrize("depth", [0, -1])
    def test_depth_below_one_is_refused(self, depth):
        """The serial mode is gone: dispatch and collect always have a
        thread each, and asking for none says why."""
        with pytest.raises(ValueError, match="serial batcher .* is gone"):
            MicroBatcher(lambda items: items, pipeline_depth=depth)


#: the window and the two gaps of the arrival processes below: generous
#: real time, so that a loaded test machine cannot turn one into the other
_W = 0.1
_LONG = 3 * _W
_SHORT = _W / 20


class _Arrivals:
    """Drives one MicroBatcher through a script of arrivals and keeps what
    the window rule can be judged by: the batches as dispatched and the
    registry's view (batches, windows waited, ``batch.window`` stage)."""

    def __init__(self, **kwargs):
        self.registry = MetricRegistry()
        self.batches: list[list] = []
        self.futures: list = []
        self.batcher = MicroBatcher(
            self._record, max_wait_ms=1e3 * _W, registry=self.registry,
            name="rule", **kwargs,
        )

    def _record(self, items):
        self.batches.append(list(items))
        return list(items)

    def submit(self, n=1, gap=0.0, locked=False):
        """``n`` arrivals ``gap`` apart; ``locked`` holds the batcher's
        (re-entrant) condition so the collector sees them all at once."""
        if locked:
            with self.batcher._cv:
                return self.submit(n, gap)
        for i in range(n):
            if i and gap:
                time.sleep(gap)
            self.futures.append(self.batcher.submit(len(self.futures)))
        return self.futures[-1]

    def settle(self):
        return [f.result(5) for f in self.futures]

    def close(self):
        self.batcher.close()
        data = self.registry.to_dict()

        def value(family, field, **labels):
            [sample] = [
                s for s in data[family]["samples"] if s["labels"] == labels
            ]
            return sample[field]

        self.n_batches = value("pio_batches_total", "value", batcher="rule")
        self.waited = value(
            "pio_batch_windows_waited_total", "value", batcher="rule"
        )
        self.windows = value("pio_stage_seconds", "count", stage="batch.window")
        self.window_s = value("pio_stage_seconds", "sum", stage="batch.window")


def _lone(a):
    a.submit(5, gap=_LONG)
    a.settle()
    # the first has no history; every later gap is far over the window
    assert [len(b) for b in a.batches] == [1] * 5
    return 1


def _dense(a):
    a.submit(12, gap=_SHORT)
    a.settle()
    # 12 arrivals inside about 0.6 of a window leave together
    assert len(a.batches) <= 2 and len(a.batches[0]) >= 6
    return len(a.batches)


def _no_history(a):
    t0 = time.monotonic()
    a.submit().result(5)
    assert time.monotonic() - t0 >= 0.9 * _W
    return 1


def _burst_after_idleness(a):
    a.submit(2, gap=_LONG)
    time.sleep(_LONG)
    # the burst's first item is not held for company...
    a.submit().result(_W / 2)
    a.submit(7, gap=_SHORT)
    a.settle()
    # ...and the rest is not split into singles: two close arrivals are
    # enough to wait again
    burst = a.batches[2:]
    assert len(burst) <= 3 and len(burst[-1]) >= 6, a.batches
    return 2  # the arrival with no history, and the burst's rest


def _long_gap_after_burst(a):
    a.submit(8, gap=_SHORT)
    a.settle()
    time.sleep(_LONG)
    # one long gap is enough to stop waiting
    a.submit().result(_W / 2)
    assert a.batches[-1] == [8]
    return len(a.batches) - 1


def _full_batch(a):
    # no history, so the window would be waited: the fill ends it
    a.submit(4, locked=True).result(_W / 2)
    assert a.batches == [[0, 1, 2, 3]]
    return 0


def _backlog_over_full(a):
    a.submit(10, locked=True)
    a.settle()
    # a full batch leaves at once; the rest (no gap over the window
    # seen) waits for company as a partial batch always did
    assert [len(b) for b in a.batches] == [4, 4, 2]
    return 1


def _posts_after_idleness(a):
    # a post is back-to-back submits of one thread: the interpreter
    # lock keeps the collector out until the post is in, whatever the
    # rule thinks of the gap before it
    for _ in range(6):
        time.sleep(_LONG)
        a.submit(16)
    a.settle()
    assert len(a.batches) <= 9, [len(b) for b in a.batches]
    return None  # a split post's rest may wait or not


def _fixed_window(a):
    a.submit(3, gap=_LONG)
    a.settle()
    assert [len(b) for b in a.batches] == [1] * 3
    return 3


class TestAdaptiveWait:
    """The window follows the arrival gap the batcher observes
    (docs/serving.md "Adaptive fill window")."""

    @pytest.mark.parametrize(
        "process, kwargs, share",
        [
            (_lone, {}, (0.0, 0.25)),
            (_dense, {}, (0.99, 1.0)),
            (_no_history, {}, None),
            (_burst_after_idleness, {}, None),
            (_long_gap_after_burst, {}, None),
            (_full_batch, {"max_batch": 4}, None),
            (_backlog_over_full, {"max_batch": 4}, None),
            (_posts_after_idleness, {"max_batch": 16}, None),
            (_fixed_window, {"adaptive_wait": False}, (1.0, 1.0)),
        ],
        ids=lambda v: v.__name__.strip("_") if callable(v) else "",
    )
    def test_window_follows_the_arrival_process(self, process, kwargs, share):
        a = _Arrivals(**kwargs)
        try:
            waited = process(a)
        finally:
            a.close()
        assert a.settle() == list(range(len(a.futures)))
        # what reads the mechanism: one window observed a batch, waited
        # or not, and the counter holds exactly the windows slept
        assert a.windows == a.n_batches == len(a.batches)
        assert a.waited <= a.n_batches
        if waited is not None:
            assert a.waited == waited
            # a skipped window takes no time
            assert a.window_s < (waited + 1) * _W
        if share is not None:
            # the waited share tells the regimes apart
            assert share[0] <= a.waited / a.n_batches <= share[1]

    def test_gap_estimate_is_introspectable(self):
        a = _Arrivals()
        try:
            b = a.batcher
            assert b._gap_ewma == 0.0
            a.submit().result(5)
            assert b._gap_ewma == 0.0  # one arrival: no gap yet
            time.sleep(_LONG)
            a.submit().result(5)
            # a gap counts as three windows at most, at half the weight
            assert b._gap_ewma == pytest.approx(1.5 * _W)
            a.submit(2, locked=True)
            a.settle()
            assert b._gap_ewma < 0.5 * _W
        finally:
            a.close()


class TestPipelineTelemetry:
    def test_enqueue_and_sync_histograms_recorded(self):
        registry = MetricRegistry()
        fn = _TwoPhase()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=4, max_wait_ms=2, registry=registry, name="tele",
        )
        try:
            futures = [b.submit(i) for i in range(8)]
            [f.result(5) for f in futures]
        finally:
            b.close()
        data = registry.to_dict()
        for metric in (
            "pio_device_enqueue_seconds",
            "pio_device_sync_seconds",
            "pio_device_dispatch_seconds",
        ):
            [sample] = [
                s for s in data[metric]["samples"]
                if s["labels"] == {"batcher": "tele"}
            ]
            assert sample["count"] >= 1, metric
        # end-to-end dispatch time covers both phases
        total = data["pio_device_dispatch_seconds"]["samples"][0]["sum"]
        enq = data["pio_device_enqueue_seconds"]["samples"][0]["sum"]
        assert total >= enq


class TestCallDeadlineCap:
    def test_call_timeout_capped_by_context_deadline(self):
        """MicroBatcher.__call__ must not wait its full default 30 s
        when the admitting request's budget is smaller."""
        gate = threading.Event()
        b = MicroBatcher(
            lambda items: (gate.wait(10), list(items))[1],
            max_batch=1, max_wait_ms=0.1,
        )
        resilience.set_deadline(resilience.Deadline.after(0.3))
        try:
            t0 = time.perf_counter()
            with pytest.raises(FuturesTimeout):
                b({"q": 1})  # default timeout would be 30 s
            assert time.perf_counter() - t0 < 2.0
        finally:
            resilience.set_deadline(None)
            gate.set()
            b.close()

    def test_call_without_deadline_keeps_explicit_timeout(self):
        b = MicroBatcher(
            lambda items: [i * 2 for i in items],
            max_batch=1, max_wait_ms=0.1,
        )
        try:
            assert b(21, timeout=5) == 42
        finally:
            b.close()


class TestOverloadClassAwareQueue:
    """Criticality-aware eviction at the queue bound and deadline-aware
    batch selection (docs/robustness.md "Overload & backpressure")."""

    def _shed_count(self, registry, name, cls):
        for s in registry.to_dict().get(
            "pio_shed_total", {}
        ).get("samples", []):
            if s["labels"] == {"batcher": name, "class": cls}:
                return s["value"]
        return 0.0

    def test_higher_class_evicts_lowest_and_counts_shed_class(self):
        from predictionio_tpu.serving import admission

        registry = MetricRegistry()
        fn = _TwoPhase()
        fn.release.clear()  # hold the pipeline: batches park in collect
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=1, max_wait_ms=1, max_queue=2,
            pipeline_depth=1, registry=registry, name="evict",
        )
        try:
            f_w1 = b.submit("w1")  # dispatched, stuck in collect
            time.sleep(0.1)
            f_w2 = b.submit("w2")  # taken by the collector, waiting
            time.sleep(0.1)       # on the pipeline slot
            with admission.criticality(admission.SHEDDABLE):
                f_s1 = b.submit("s1")
                f_s2 = b.submit("s2")
            # the queue is at its bound (2): a critical submission
            # evicts a sheddable slot instead of being refused
            with admission.criticality(admission.CRITICAL):
                f_c1 = b.submit("c1")
            evicted = [f for f in (f_s1, f_s2) if f.done()]
            assert len(evicted) == 1
            with pytest.raises(BatcherOverloaded):
                evicted[0].result(0)
            assert self._shed_count(registry, "evict", "sheddable") == 1
            # equal class cannot evict: the bound refuses it, counted
            # against ITS class
            with admission.criticality(admission.CRITICAL):
                b.submit("c2")  # evicts the remaining sheddable
                with pytest.raises(BatcherOverloaded):
                    b.submit("c3")
            assert self._shed_count(registry, "evict", "sheddable") == 2
            assert self._shed_count(registry, "evict", "critical") == 1
            fn.release.set()
            # everything still queued is served
            assert f_w1.result(10) == "W1"
            assert f_w2.result(10) == "W2"
            assert f_c1.result(10) == "C1"
        finally:
            fn.release.set()
            b.close()

    def test_default_cannot_evict_default(self):
        fn = _TwoPhase()
        fn.release.clear()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=1, max_wait_ms=1, max_queue=1, pipeline_depth=1,
        )
        try:
            b.submit("w1")
            time.sleep(0.1)
            b.submit("w2")
            time.sleep(0.1)
            f_q = b.submit("q1")  # fills the queue
            with pytest.raises(BatcherOverloaded):
                b.submit("q2")
            assert not f_q.done()  # the queued peer was NOT evicted
        finally:
            fn.release.set()
            b.close()

    def test_near_deadline_slots_selected_first(self):
        """When the backlog exceeds one batch, the nearest-deadline
        slots dispatch first — urgent work must not rot behind slack
        work submitted earlier."""
        fn = _TwoPhase()
        fn.release.clear()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=2, max_wait_ms=1, max_queue=0, pipeline_depth=1,
        )
        try:
            b.submit("w1")
            time.sleep(0.1)
            b.submit("w2")
            time.sleep(0.1)
            # backlog of 3 > max_batch: two slack-deadline slots ahead
            # of one urgent slot in ARRIVAL order
            resilience.set_deadline(resilience.Deadline.after(60.0))
            f_far_a = b.submit("far_a")
            f_far_b = b.submit("far_b")
            resilience.set_deadline(resilience.Deadline.after(5.0))
            f_near = b.submit("near")
            resilience.set_deadline(None)
            fn.release.set()
            for f in (f_far_a, f_far_b, f_near):
                f.result(10)
            # third dispatched batch = the backlog selection: the
            # urgent slot jumped the slack one that arrived before it
            assert "near" in fn.dispatched[2]
            assert fn.dispatched[3] == ["far_b"]
        finally:
            fn.release.set()
            resilience.set_deadline(None)
            b.close()

    def test_fifo_preserved_without_deadlines(self):
        """Deadline-less traffic keeps strict arrival order even when
        the backlog exceeds one batch."""
        fn = _TwoPhase()
        fn.release.clear()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=2, max_wait_ms=1, max_queue=0, pipeline_depth=1,
        )
        try:
            b.submit("w1")
            time.sleep(0.1)
            b.submit("w2")
            time.sleep(0.1)
            futures = [b.submit(f"q{i}") for i in range(5)]
            fn.release.set()
            for f in futures:
                f.result(10)
            backlog_batches = fn.dispatched[2:]
            assert [i for batch in backlog_batches for i in batch] == [
                f"q{i}" for i in range(5)
            ]
        finally:
            fn.release.set()
            b.close()

    def test_retry_after_hint_tracks_backlog(self):
        fn = _TwoPhase()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=4, max_wait_ms=1,
        )
        try:
            assert 0.05 <= b.retry_after_s() <= 5.0
            for i in range(8):
                b.submit(i)
            idle_after = b.retry_after_s()
            assert 0.05 <= idle_after <= 5.0
        finally:
            b.close()


class TestBatchEwmaSettlement:
    """PR 12 regression: the retry-after EWMA fold runs under the cv —
    settlement happens on the completer OR the collector (dispatch
    failure / serial fallback), so the read-modify-write raced with
    itself and with retry_after_s() readers before the fix."""

    def test_concurrent_settlement_and_hint_reads(self):
        fn = _TwoPhase()
        b = MicroBatcher(
            TwoPhaseBatchFn(fn.dispatch, fn.collect),
            max_batch=4, max_wait_ms=1,
        )
        errors: list[BaseException] = []
        stop = threading.Event()

        def settle(value):
            try:
                while not stop.is_set():
                    b._observe_batch_time(value)
            except BaseException as e:  # noqa: BLE001 - fail the test
                errors.append(e)

        def read_hint():
            try:
                while not stop.is_set():
                    hint = b.retry_after_s()
                    assert 0.05 <= hint <= 5.0
            except BaseException as e:  # noqa: BLE001 - fail the test
                errors.append(e)

        threads = [
            threading.Thread(target=settle, args=(0.2,), daemon=True),
            threading.Thread(target=settle, args=(0.4,), daemon=True),
            threading.Thread(target=read_hint, daemon=True),
        ]
        try:
            [t.start() for t in threads]
            time.sleep(0.3)
            stop.set()
            [t.join(timeout=5) for t in threads]
            assert errors == []
            # the fold only ever mixes the two sample values, so the
            # EWMA must land between them — a torn/lost update pattern
            # that escapes the guard shows up as an out-of-range value
            assert 0.2 <= b._batch_ewma_s <= 0.4
        finally:
            b.close()


class TestTenantAttribution:
    """Per-tenant cost attribution (docs/observability.md "Cost
    attribution"): each batch's measured device time is apportioned
    across its slots by slot count, so the per-tenant counters sum to
    exactly the batcher's total measured device time."""

    def _attributed(self, data):
        fam = data.get("pio_tenant_device_seconds_total") or {}
        return {
            s["labels"]["tenant"]: s["value"]
            for s in fam.get("samples") or []
        }

    def _measured_total(self, data):
        # the exported histogram sums round at 1e-6: slot the batch fn
        # a couple ms of work so the 1% tolerance dominates rounding
        return (
            data["pio_device_enqueue_seconds"]["samples"][0]["sum"]
            + data["pio_device_sync_seconds"]["samples"][0]["sum"]
        )

    def test_device_seconds_conserved_across_tenants(self):
        from predictionio_tpu.serving import admission

        def batch_fn(items):
            time.sleep(0.002)
            return [i * 2 for i in items]

        reg = MetricRegistry()
        b = MicroBatcher(
            batch_fn, max_batch=4, max_wait_ms=2, registry=reg,
        )
        try:
            futures = []
            for i in range(24):
                with admission.tenant(f"t{i % 3}"):
                    futures.append(b.submit(i))
            assert [f.result(5) for f in futures] == [
                i * 2 for i in range(24)
            ]
        finally:
            b.close()
        data = reg.to_dict()
        per_tenant = self._attributed(data)
        assert set(per_tenant) == {"t0", "t1", "t2"}
        # conservation: attribution is an exact partition of the
        # measured device time, not a second measurement of it
        assert sum(per_tenant.values()) == pytest.approx(
            self._measured_total(data), rel=0.01
        )
        requests = {
            (s["labels"]["tenant"], s["labels"]["status"]): s["value"]
            for s in data["pio_tenant_requests_total"]["samples"]
        }
        assert sum(requests.values()) == 24.0
        assert all(status == "ok" for _, status in requests)
        waits = {
            s["labels"]["tenant"]: s["count"]
            for s in data["pio_tenant_queue_wait_seconds"]["samples"]
        }
        assert waits == {"t0": 8, "t1": 8, "t2": 8}

    def test_failed_batches_still_attributed(self):
        from predictionio_tpu.serving import admission

        def boom(items):
            time.sleep(0.002)
            raise ValueError("injected batch failure")

        reg = MetricRegistry()
        b = MicroBatcher(boom, max_batch=4, max_wait_ms=2, registry=reg)
        try:
            with admission.tenant("t-err"):
                futures = [b.submit(i) for i in range(4)]
            for f in futures:
                with pytest.raises(ValueError):
                    f.result(5)
        finally:
            b.close()
        data = reg.to_dict()
        per_tenant = self._attributed(data)
        # a failed batch burned real device/host time — it must be
        # charged, or the books don't balance
        assert set(per_tenant) == {"t-err"}
        assert sum(per_tenant.values()) == pytest.approx(
            self._measured_total(data), rel=0.01
        )
        requests = {
            s["labels"]["status"]: s["value"]
            for s in data["pio_tenant_requests_total"]["samples"]
        }
        assert requests == {"error": 4.0}

    def test_anonymous_requests_charge_the_empty_tenant(self):
        reg = MetricRegistry()
        b = MicroBatcher(
            lambda items: items, max_batch=2, max_wait_ms=2,
            registry=reg,
        )
        try:
            [f.result(5) for f in [b.submit(i) for i in range(2)]]
        finally:
            b.close()
        per_tenant = self._attributed(reg.to_dict())
        assert set(per_tenant) == {""}

    def test_noisy_neighbor_requires_overuse_and_harm(self):
        from predictionio_tpu.obs import timeline as timeline_mod
        from predictionio_tpu.serving.batching import _NoisyRollup

        reg = MetricRegistry()
        gauge = reg.gauge("pio_tenant_noisy", "h", ("tenant",))
        ring = timeline_mod.Timeline(capacity=16)
        previous = timeline_mod.set_timeline(ring)
        try:
            roll = _NoisyRollup(gauge)
            # hog takes ~5x the fair share AND the victim breaches its
            # queue-wait SLO -> flagged at window rollover
            roll.observe("hog", 5.0, 0.0)
            roll.observe("victim", 1.0, roll.wait_slo_s * 2)
            roll.window_end = 0.0  # force the rollover
            roll.observe("victim", 0.0, 0.0)
            flags = {
                s["labels"]["tenant"]: s["value"]
                for s in reg.to_dict()["pio_tenant_noisy"]["samples"]
            }
            assert flags.get("hog") == 1.0
            assert "victim" not in flags or flags["victim"] == 0.0
            kinds = [e["kind"] for e in ring.events()]
            assert "noisy_neighbor" in kinds
            # overuse with NO harmed neighbor (nobody breached the
            # wait SLO) clears the flag at the next rollover
            roll.observe("hog", 5.0, 0.0)
            roll.observe("victim", 1.0, 0.0)
            roll.window_end = 0.0
            roll.observe("victim", 0.0, 0.0)
            flags = {
                s["labels"]["tenant"]: s["value"]
                for s in reg.to_dict()["pio_tenant_noisy"]["samples"]
            }
            assert flags.get("hog") == 0.0
        finally:
            timeline_mod.set_timeline(previous)
