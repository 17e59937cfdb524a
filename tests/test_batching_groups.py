"""A request's items ride the micro-batcher as one group
(docs/serving.md "A post is one group"): one admission, one wake and one
settle, and every per-slot guarantee of ``submit`` kept as a per-slot
outcome of the group.

Every case runs on a stub ``batch_fn``. Where the order of events
matters the collector is parked inside a dispatch (`_Gated`): whatever
is submitted meanwhile stays in the queue until the test lets go, so no
case sleeps to get its interleaving.
"""

from __future__ import annotations

import threading
import time

import pytest

from predictionio_tpu.obs import MetricRegistry
from predictionio_tpu.obs import registry as registry_mod
from predictionio_tpu.serving import admission, batching, resilience
from predictionio_tpu.serving.batching import (
    BatcherOverloaded,
    MicroBatcher,
    TwoPhaseBatchFn,
)

_WAIT = 10.0


class _Gated:
    """A ``batch_fn`` pair: a batch that starts with an item named in
    ``hold`` parks the collector inside ``dispatch`` until released; a
    batch holding ``"boom"`` fails in ``collect``; answers are
    ``<item>``."""

    def __init__(self, *hold):
        self.parked = {item: threading.Event() for item in hold}
        self.release = {item: threading.Event() for item in hold}
        self.batches: list[list] = []

    def dispatch(self, items):
        first = items[0]
        if first in self.parked:
            self.parked[first].set()
            assert self.release[first].wait(_WAIT), "never released"
        self.batches.append(list(items))
        return list(items)

    def collect(self, handle):
        if "boom" in handle:
            raise ValueError("injected batch failure")
        return [f"<{i}>" for i in handle]

    def let_go(self):
        for event in self.release.values():
            event.set()


def _batcher(fn=None, **kwargs):
    fn = fn or _Gated()
    kwargs.setdefault("max_wait_ms", 1.0)
    b = MicroBatcher(TwoPhaseBatchFn(fn.dispatch, fn.collect), **kwargs)
    return b, fn


def _park(b, fn, item="plug"):
    """Park the collector on ``item``: the queue holds what follows."""
    future = b.submit(item)
    assert fn.parked[item].wait(_WAIT)
    return future


def _value(registry, name, **labels):
    for s in registry.to_dict().get(name, {}).get("samples", []):
        if s["labels"] == labels:
            return s.get("value", s.get("count"))
    return 0.0


class _Budget(resilience.Deadline):
    """A deadline that expires when the test says so."""

    def __init__(self):
        super().__init__(time.monotonic() + 3600.0)
        self.gone = False

    @property
    def expired(self) -> bool:
        return self.gone


class TestGroupRidesLikeItsSlots:
    def test_a_group_answers_what_its_submits_answer_in_order(self):
        b, _fn = _batcher(max_batch=64)
        try:
            items = [f"q{i}" for i in range(64)]
            futures = [b.submit(i) for i in items]
            group = b.submit_group(items)
            assert group.admitted == 64
            assert group.wait(_WAIT)
            assert [group.result(i) for i in range(64)] == [
                f.result(_WAIT) for f in futures
            ]
        finally:
            b.close()

    @pytest.mark.parametrize(
        "ahead, n, batches",
        [
            (0, 100, [64, 36]),  # longer than max_batch
            (3, 64, [64, 3]),  # meets a part-filled buffer
        ],
    )
    def test_a_group_rides_two_batches_and_completes_once(
        self, ahead, n, batches
    ):
        # the second batch starts with the group's item that did not
        # fit the first: the collector parks there, one batch settled
        second = f"q{64 - ahead}"
        b, fn = _batcher(_Gated("plug", second), max_batch=64)
        try:
            _park(b, fn)
            singles = [b.submit(f"s{i}") for i in range(ahead)]
            group = b.submit_group([f"q{i}" for i in range(n)])
            fn.release["plug"].set()
            assert fn.parked[second].wait(_WAIT)
            for f in singles:
                assert f.result(_WAIT)
            # its first batch is answered, its last slots are not: the
            # group is not done and nobody was woken
            assert not group.wait(0)
            with pytest.raises(TimeoutError):
                group.result(n - 1)
            fn.release[second].set()
            assert group.wait(_WAIT)
            assert [group.result(i) for i in range(n)] == [
                f"<q{i}>" for i in range(n)
            ]
            assert [len(x) for x in fn.batches[1:]] == batches
        finally:
            fn.let_go()
            b.close()

    def test_close_drains_a_queued_group(self):
        b, fn = _batcher(_Gated("plug"), max_batch=8)
        _park(b, fn)
        group = b.submit_group(list("abcdefghij"))
        closer = threading.Thread(target=b.close)
        closer.start()
        assert b._closed.wait(_WAIT)  # closed with the group queued
        fn.let_go()
        closer.join(_WAIT)
        assert not closer.is_alive()
        assert group.wait(0)
        assert [group.result(i) for i in range(10)] == [
            f"<{c}>" for c in "abcdefghij"
        ]
        with pytest.raises(RuntimeError):
            b.submit_group(["late"])

    def test_a_group_of_one_and_a_submit_are_settled_by_the_same_code(
        self, monkeypatch
    ):
        stored = []
        real = batching._Group._store

        def spy(self, indices, values):
            stored.append((type(self).__name__, list(indices)))
            return real(self, indices, values)

        monkeypatch.setattr(batching._Group, "_store", spy)
        b, fn = _batcher(_Gated("plug"), max_batch=8)
        try:
            _park(b, fn)
            future = b.submit("one")
            group = b.submit_group(["alone"])
            fn.let_go()
            assert future.result(_WAIT) == "<one>"
            assert group.wait(_WAIT) and group.result(0) == "<alone>"
            # the plug, then one batch with the two groups of one
            assert stored == [
                ("_SlotFuture", [0]), ("_SlotFuture", [0]),
                ("BatchGroup", [0]),
            ]
        finally:
            fn.let_go()
            b.close()


class TestPerSlotGuarantees:
    def test_at_the_bound_what_fits_is_admitted_and_the_rest_is_shed(self):
        registry = MetricRegistry()
        b, fn = _batcher(
            _Gated("plug"), max_batch=16, max_queue=8,
            registry=registry, name="bound",
        )
        try:
            _park(b, fn)
            group = b.submit_group([f"q{i}" for i in range(12)])
            assert group.admitted == 8
            assert _value(
                registry, "pio_batch_queue_depth", batcher="bound"
            ) == 8
            assert _value(
                registry, "pio_batch_shed_total", batcher="bound"
            ) == 4
            assert _value(
                registry, "pio_shed_total",
                **{"batcher": "bound", "class": admission.DEFAULT},
            ) == 4
            # a full queue sheds a lone submit through the same bound
            with pytest.raises(BatcherOverloaded):
                b.submit("late")
            # a group none of which fits is done at once, like one
            # that has nothing in it
            empty = b.submit_group(["x", "y"])
            assert empty.admitted == 0 and empty.wait(0)
            assert b.submit_group([]).wait(0)
            fn.let_go()
            assert group.wait(_WAIT)
            assert [group.result(i) for i in range(8)] == [
                f"<q{i}>" for i in range(8)
            ]
            for i in range(8, 12):
                with pytest.raises(BatcherOverloaded):
                    group.result(i)
            assert fn.batches[1] == [f"q{i}" for i in range(8)]
        finally:
            fn.let_go()
            b.close()

    def test_a_critical_submit_evicts_one_slot_of_a_sheddable_group(self):
        registry = MetricRegistry()
        b, fn = _batcher(
            _Gated("plug"), max_batch=16, max_queue=4,
            registry=registry, name="evict",
        )
        try:
            _park(b, fn)
            with admission.criticality(admission.SHEDDABLE):
                group = b.submit_group(["s0", "s1", "s2", "s3"])
            assert group.admitted == 4
            with admission.criticality(admission.CRITICAL):
                lone = b.submit("c0")
            # the latest arrival of the lowest class went, alone
            with pytest.raises(BatcherOverloaded):
                group.result(3)
            assert not group.wait(0)
            # a critical group makes its room the same way, slot by
            # slot, and is shed where no lower class is left
            with admission.criticality(admission.CRITICAL):
                pair = b.submit_group(["c1", "c2"])
                full = b.submit_group(["c3", "c4", "c5", "c6"])
            assert pair.admitted == 2 and full.admitted == 1
            assert _value(
                registry, "pio_shed_total",
                **{"batcher": "evict", "class": admission.SHEDDABLE},
            ) == 4
            assert _value(
                registry, "pio_shed_total",
                **{"batcher": "evict", "class": admission.CRITICAL},
            ) == 3
            assert group.wait(0)  # every slot evicted: done, unserved
            fn.let_go()
            assert lone.result(_WAIT) == "<c0>"
            assert pair.wait(_WAIT) and full.wait(_WAIT)
            assert fn.batches[1] == ["c0", "c1", "c2", "c3"]
        finally:
            fn.let_go()
            b.close()

    def test_an_expired_deadline_refuses_the_group_before_any_slot(self):
        registry = MetricRegistry()
        b, fn = _batcher(registry=registry, name="late")
        budget = _Budget()
        budget.gone = True
        resilience.set_deadline(budget)
        try:
            with pytest.raises(resilience.DeadlineExceeded):
                b.submit_group(["a", "b", "c"])
        finally:
            resilience.set_deadline(None)
            b.close()
        assert fn.batches == []
        assert _value(
            registry, "pio_batch_deadline_expired_total", batcher="late"
        ) == 3
        assert _value(
            registry, "pio_batch_groups_total", batcher="late"
        ) == 0

    def test_a_deadline_that_expires_in_the_queue_drops_what_waits(self):
        registry = MetricRegistry()
        b, fn = _batcher(
            _Gated("plug", "q0"), max_batch=4,
            registry=registry, name="dying",
        )
        budget = _Budget()
        try:
            _park(b, fn)
            resilience.set_deadline(budget)
            group = b.submit_group([f"q{i}" for i in range(6)])
            resilience.set_deadline(None)
            other = b.submit("tail")
            fn.release["plug"].set()
            # the nearest deadline goes first: q0-q3 are past the
            # cut-off (the collector sits in their dispatch) when the
            # budget dies; q4 and q5 still wait
            assert fn.parked["q0"].wait(_WAIT)
            budget.gone = True
            fn.release["q0"].set()
            assert group.wait(_WAIT)
            assert [group.result(i) for i in range(4)] == [
                f"<q{i}>" for i in range(4)
            ]
            for i in (4, 5):
                with pytest.raises(resilience.DeadlineExceeded):
                    group.result(i)
            assert other.result(_WAIT) == "<tail>"
            assert _value(
                registry, "pio_batch_deadline_expired_total",
                batcher="dying",
            ) == 2
            assert all("q4" not in x and "q5" not in x for x in fn.batches)
        finally:
            resilience.set_deadline(None)
            fn.let_go()
            b.close()

    def test_a_cancelled_group_never_reaches_the_device(self):
        registry = MetricRegistry()
        b, fn = _batcher(
            _Gated("plug"), max_batch=8, registry=registry, name="gone",
        )
        try:
            _park(b, fn)
            group = b.submit_group(["a", "b", "c"])
            kept = b.submit_group(["k0", "k1"])
            assert kept.cancel([1]) == 0  # one slot of a group
            assert group.cancel() == 0  # nothing was past the cut-off
            assert group.wait(0)  # done: nobody will answer it
            fn.let_go()
            assert kept.wait(_WAIT)
            assert kept.result(0) == "<k0>"
            assert fn.batches[1] == ["k0"]
        finally:
            fn.let_go()
            b.close()
        assert _value(
            registry, "pio_batch_cancelled_total", batcher="gone"
        ) == 4

    def test_cancelled_after_dispatch_counts_as_wasted(self):
        b, fn = _batcher(_Gated("a"), max_batch=2)
        try:
            group = b.submit_group(["a", "b", "c"])
            assert fn.parked["a"].wait(_WAIT)
            # a and b are with the device, c still waits
            assert group.cancel() == 2
            fn.let_go()
            assert group.wait(_WAIT)
            assert group.result(0) == "<a>" and group.result(1) == "<b>"
            assert fn.batches == [["a", "b"]]
        finally:
            fn.let_go()
            b.close()

    def test_a_failed_batch_fails_its_slots_and_no_others(self):
        b, fn = _batcher(_Gated("plug"), max_batch=3)
        try:
            _park(b, fn)
            group = b.submit_group(["a", "b", "c", "boom", "e", "f", "g"])
            fn.let_go()
            assert group.wait(_WAIT)
            assert [group.result(i) for i in (0, 1, 2, 6)] == [
                "<a>", "<b>", "<c>", "<g>"
            ]
            for i in (3, 4, 5):
                with pytest.raises(ValueError, match="injected"):
                    group.result(i)
        finally:
            fn.let_go()
            b.close()


class TestManyThreads:
    def test_every_slot_ends_in_one_outcome_under_contention(self):
        """More submitting threads than cores and a short switch
        interval, groups of every length against a small queue, half of
        them cancelled in flight: every group completes, a slot the
        device saw is answered, one it never saw is refused, and the
        device saw no slot twice."""
        import sys
        from concurrent.futures import CancelledError

        seen: list = []
        lock = threading.Lock()

        def batch_fn(items):
            with lock:
                seen.extend(items)
            return [("answer", i) for i in items]

        b = MicroBatcher(
            batch_fn, max_batch=16, max_wait_ms=0.2, max_queue=48
        )
        groups: list = []
        failures: list = []

        def client(c):
            try:
                for g in range(40):
                    n = 1 + (c * 7 + g * 3) % 23
                    cls = (
                        admission.CRITICAL if (c + g) % 5 == 0
                        else admission.SHEDDABLE
                    )
                    with admission.criticality(cls):
                        group = b.submit_group(
                            [(c, g, i) for i in range(n)]
                        )
                    if g % 2:
                        group.cancel(range(0, group.admitted, 2))
                    assert group.wait(_WAIT), "a group never completed"
                    with lock:
                        groups.append(group)
            except BaseException as exc:  # noqa: BLE001 - reported below
                failures.append(exc)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [
                threading.Thread(target=client, args=(c,))
                for c in range(24)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(interval)
            b.close()
        assert not failures, failures
        assert len(groups) == 24 * 40
        answered = []
        for group in groups:
            for item, outcome in zip(group.items, group.outcomes):
                if isinstance(outcome, tuple):
                    assert outcome == ("answer", item)
                    answered.append(item)
                else:
                    assert isinstance(
                        outcome, (BatcherOverloaded, CancelledError)
                    ), outcome
        # the device's log and the answers are the same slots, once each
        assert len(seen) == len(set(seen))
        assert sorted(seen) == sorted(answered)


class TestGroupAccounting:
    def test_attribution_is_conserved_and_counts_a_wait_a_query(self):
        registry = MetricRegistry()
        b, fn = _batcher(
            _Gated("plug"), max_batch=8, registry=registry, name="attr",
        )
        try:
            _park(b, fn)
            with admission.tenant("t0"):
                first = b.submit_group([f"a{i}" for i in range(10)])
            with admission.tenant("t1"):
                second = b.submit_group(["b0", "boom", "b2"])
            fn.let_go()
            assert first.wait(_WAIT) and second.wait(_WAIT)
        finally:
            fn.let_go()
            b.close()
        metrics = b._metrics
        measured = metrics._enqueue.sum + metrics._sync.sum
        charged = {
            t: metrics._tenant_device.labels(t).value
            for t in ("", "t0", "t1")
        }
        # the plug rode alone, anonymous; batches of 8 and 5 after it
        assert sum(charged.values()) == pytest.approx(measured, rel=1e-9)
        assert charged["t0"] > 0 and charged["t1"] > 0
        waits = {
            t: metrics._tenant_wait.labels(t).count for t in ("t0", "t1")
        }
        assert waits == {"t0": 10, "t1": 3}
        requests = {
            (s["labels"]["tenant"], s["labels"]["status"]): s["value"]
            for s in registry.to_dict()["pio_tenant_requests_total"][
                "samples"
            ]
        }
        # the second batch (a8, a9 and t1's three) failed as one
        assert requests == {
            ("", "ok"): 1.0, ("t0", "ok"): 8.0,
            ("t0", "error"): 2.0, ("t1", "error"): 3.0,
        }

    def test_a_group_of_n_is_n_arrivals_to_the_window_rule(
        self, monkeypatch
    ):
        class Clock:
            """The batchers' clock: it stands until the test moves it,
            so n submits can share one instant."""

            now = 100.0
            perf_counter = staticmethod(time.perf_counter)

            @classmethod
            def monotonic(cls):
                return cls.now

        monkeypatch.setattr(batching, "time", Clock)
        grouped, _ = _batcher(max_batch=64)
        lone, _ = _batcher(max_batch=64)
        try:
            for at, n in ((100.0, 1), (100.0015, 5), (100.0021, 64)):
                Clock.now = at
                grouped.submit_group(list(range(n)))
                for i in range(n):
                    lone.submit(i)
            assert grouped._gap_ewma > 0.0
            assert grouped._gap_ewma == pytest.approx(
                lone._gap_ewma, rel=1e-12
            )
            assert grouped._last_arrival == lone._last_arrival
        finally:
            grouped.close()
            lone.close()

    def test_the_counters_say_a_group_engaged(self):
        registry = MetricRegistry()
        b, _fn = _batcher(max_batch=64, registry=registry, name="counted")
        try:
            group = b.submit_group(list(range(40)))
            assert group.wait(_WAIT)
            b.submit("lone").result(_WAIT)  # no group: counts in neither
        finally:
            b.close()
        assert _value(
            registry, "pio_batch_groups_total", batcher="counted"
        ) == 1
        assert _value(
            registry, "pio_batch_group_slots_total", batcher="counted"
        ) == 40


class _CountingLock:
    """A lock that counts the ``with`` blocks that take it."""

    def __init__(self, counts, name):
        self._lock = threading.Lock()
        self._counts = counts
        self._name = name
        self.acquire = self._lock.acquire
        self.release = self._lock.release

    def __enter__(self):
        self._counts[self._name] = self._counts.get(self._name, 0) + 1
        return self._lock.acquire()

    def __exit__(self, *exc):
        self._lock.release()


def _count_one_group(monkeypatch, n: int) -> dict:
    """Ride one group of ``n`` through a batcher of its own and count,
    over the batcher's whole life, what a slot must not cost: Futures
    built, locks taken (the queue's condition, the group's own), lookups
    of labelled metrics and calls on metric children."""
    counts: dict = {}
    real_condition = threading.Condition

    def condition(lock=None):
        if lock is None:  # the batcher's own; a Queue or Event brings one
            lock = _CountingLock(counts, "queue condition")
        return real_condition(lock)

    class Group(batching.BatchGroup):
        __slots__ = ()

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self._lock = _CountingLock(counts, "group lock")

    with monkeypatch.context() as patch:
        patch.setattr(threading, "Condition", condition)
        b, fn = _batcher(max_batch=64, max_wait_ms=0.0)
        patch.setattr(threading, "Condition", real_condition)

        def counted(cls, method, name):
            real = getattr(cls, method)

            def wrapper(self, *args, **kwargs):
                counts[name] = counts.get(name, 0) + 1
                return real(self, *args, **kwargs)

            patch.setattr(cls, method, wrapper)

        patch.setattr(batching, "BatchGroup", Group)
        counted(batching._SlotFuture, "__init__", "futures")
        counted(registry_mod._Metric, "labels", "labels lookups")
        counted(registry_mod._CounterChild, "inc", "metric calls")
        counted(registry_mod._GaugeChild, "set", "metric calls")
        counted(registry_mod._HistogramChild, "observe", "metric calls")
        try:
            group = b.submit_group(list(range(n)))
            assert group.wait(_WAIT)
            assert [group.result(i) for i in range(n)] == [
                f"<{i}>" for i in range(n)
            ]
        finally:
            b.close()
    assert fn.batches == [list(range(n))]
    return counts


def test_no_future_lock_or_labelled_metric_a_slot(monkeypatch):
    """What a group costs does not depend on how many slots it has."""
    small = _count_one_group(monkeypatch, 2)
    large = _count_one_group(monkeypatch, 64)
    assert large == small
    assert "futures" not in large
    # once a group and batch: the cut-off and the count-down under the
    # group's lock, the tenant's three labelled children; the queue's
    # condition once for the admission (the rest are the collector's
    # two turns, the batch-time fold and close)
    assert large["group lock"] == 2
    assert large["labels lookups"] == 3
    assert large["queue condition"] == 5
