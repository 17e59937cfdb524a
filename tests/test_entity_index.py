"""Serve-time reads by entity (`EventStore.find_by_entity`,
`EventStore.entity_reader`) equal a linear scan of everything written,
after interleaved inserts, batch inserts, re-inserts under an old id and
deletes, on the `memory` backend (which keeps an entity index of its own
and answers `entity_targets` from it) and on `sqlite` (whose table is
indexed by entity; `entity_targets` there is the base class's walk over
`find`)."""

from __future__ import annotations

import datetime as _dt
import threading

import numpy as np
import pytest

from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.data.store import EventStore

T0 = _dt.datetime(2024, 5, 1, tzinfo=_dt.timezone.utc)


@pytest.fixture(params=["memory", "sqlite"])
def storage(request):
    return request.getfixturevalue(request.param + "_storage")


def _event(rng, n):
    kind = rng.random()
    at = T0 + _dt.timedelta(seconds=int(rng.integers(0, 500)), microseconds=n)
    if kind < 0.15:
        return Event(
            event="$set", entity_type="constraint", entity_id="unavailableItems",
            properties=DataMap({"items": [f"i{int(i)}" for i in rng.integers(0, 50, 3)]}),
            event_time=at, event_id=f"e{n}",
        )
    return Event(
        event=str(rng.choice(["view", "buy", "cart"])), entity_type="user",
        entity_id=f"u{int(rng.integers(0, 6))}", target_entity_type="item",
        target_entity_id=f"i{int(rng.integers(0, 50))}", event_time=at,
        event_id=f"e{n}",
    )


def _scan(mirror, entity_type, entity_id, names, limit=None):
    """What a linear scan of everything written gives, latest first."""
    found = sorted(
        (e for e in mirror.values()
         if e.entity_type == entity_type and e.entity_id == entity_id
         and (names is None or e.event in names)),
        key=lambda e: e.event_time, reverse=True,
    )
    return [e.event_id for e in found[:limit]]


def _scan_targets(mirror, entity_type, entity_id, names):
    """The targets a linear scan of everything written finds, sorted (the
    read promises no order)."""
    return sorted(
        e.target_entity_id for e in mirror.values()
        if e.entity_type == entity_type and e.entity_id == entity_id
        and e.event in names and e.target_entity_id is not None
    )


@pytest.mark.parametrize("seed", [0, 1])
def test_entity_reads_equal_a_linear_scan_after_interleaved_writes(storage, seed):
    app_id = storage.get_meta_data_apps().insert(App(id=0, name="shop"))
    backend = storage.get_events()
    backend.init(app_id)
    store = EventStore(storage)
    reader = store.entity_reader("shop")
    rng = np.random.default_rng(seed)
    mirror: dict[str, Event] = {}
    versions = {}
    n = 0
    for step in range(60):
        op = rng.random()
        if op < 0.45 or not mirror:
            event = _event(rng, n)
            n += 1
            assert backend.insert(event, app_id) == event.event_id
            mirror[event.event_id] = event
        elif op < 0.65:
            batch = [_event(rng, n + k) for k in range(int(rng.integers(1, 6)))]
            n += len(batch)
            assert backend.insert_batch(batch, app_id) == [e.event_id for e in batch]
            mirror.update((e.event_id, e) for e in batch)
        elif op < 0.8:
            # an old id again, perhaps under another entity
            old = str(rng.choice(sorted(mirror)))
            event = _event(rng, int(old[1:]))
            backend.insert(event, app_id)
            mirror[old] = event
        else:
            gone = str(rng.choice(sorted(mirror)))
            assert backend.delete(gone, app_id)
            del mirror[gone]
            assert not backend.delete(gone, app_id)
        # every entity, every read the template makes
        for u in range(6):
            want = _scan(mirror, "user", f"u{u}", {"view", "buy"})
            got = store.find_by_entity(
                "shop", "user", f"u{u}", event_names=["view", "buy"]
            )
            assert [e.event_id for e in got] == want, (step, u)
            assert [e.event_id for e in reader.find(
                "user", f"u{u}", event_names=["view"], limit=10
            )] == _scan(mirror, "user", f"u{u}", {"view"}, 10)
            assert sorted(
                reader.targets("user", f"u{u}", ("view", "buy"))
            ) == _scan_targets(mirror, "user", f"u{u}", {"view", "buy"}), (step, u)
        latest = reader.find("constraint", "unavailableItems", event_names=["$set"], limit=1)
        assert [e.event_id for e in latest] == _scan(
            mirror, "constraint", "unavailableItems", {"$set"}, 1
        )
        if latest:
            assert latest[0].properties.get("items") == mirror[
                latest[0].event_id
            ].properties.get("items")
        # a version that reads the same means nothing was written between
        for key in [("user", f"u{u}") for u in range(6)] + [("constraint", "unavailableItems")]:
            version = reader.version(*key)
            content = tuple(_scan(mirror, *key, None))
            if version is not None and key in versions and versions[key][0] == version:
                assert versions[key][1] == content, (step, key)
            versions[key] = (version, content)
    assert reader.find("user", "nobody") == []
    if isinstance(reader.version("user", "u0"), int):
        assert reader.version("user", "nobody") == 0
        assert backend.remove(app_id)
        assert reader.version("user", "u0") == 0 and reader.find("user", "u0") == []


def _view(n, user, item, name="view"):
    return Event(
        event=name, entity_type="user", entity_id=user,
        target_entity_type=None if item is None else "item",
        target_entity_id=item, event_time=T0 + _dt.timedelta(seconds=n),
        event_id=f"e{n}",
    )


def _insert(backend, app_id, mirror, event):
    backend.insert(event, app_id)
    mirror[event.event_id] = event


def _insert_batch_replacing_an_id(backend, app_id, mirror):
    # e1 comes again, under another user and with another item
    batch = [_view(1, "u2", "i9", "buy"), _view(10, "u1", "i10"), _view(11, "u2", "i1")]
    backend.insert_batch(batch, app_id)
    mirror.update((e.event_id, e) for e in batch)


def _delete(backend, app_id, mirror):
    assert backend.delete("e0", app_id)
    del mirror["e0"]
    assert backend.delete("e3", app_id)  # u3's only event
    del mirror["e3"]


def _write_from_another_thread(backend, app_id, mirror):
    event = _view(20, "u1", "i20", "buy")
    writer = threading.Thread(target=_insert, args=(backend, app_id, mirror, event))
    writer.start()
    writer.join(timeout=30)
    assert not writer.is_alive()


_TARGET_CASES = {
    "insert": lambda *a: _insert(*a, _view(12, "u1", "i12")),
    "insert_batch_with_a_replaced_event_id": _insert_batch_replacing_an_id,
    "delete": _delete,
    "event_name_outside_event_names": lambda *a: _insert(*a, _view(13, "u1", "i13", "cart")),
    "event_without_a_target": lambda *a: _insert(*a, _view(14, "u1", None)),
    "entity_with_no_events": lambda *a: None,
    "write_from_another_thread_that_has_returned": _write_from_another_thread,
}


@pytest.mark.parametrize("case", sorted(_TARGET_CASES))
def test_entity_targets_equal_a_linear_scan(storage, case):
    """`EventsBackend.entity_targets` and `EntityReader.targets` after each
    kind of write: the targets of the entity's events of the named kinds,
    in any order, nothing kept from before the write."""
    app_id = storage.get_meta_data_apps().insert(App(id=0, name="shop"))
    backend = storage.get_events()
    backend.init(app_id)
    reader = EventStore(storage).entity_reader("shop")
    mirror: dict[str, Event] = {}
    for n, (user, item, name) in enumerate([
        ("u1", "i0", "view"), ("u1", "i1", "buy"), ("u2", "i1", "view"),
        ("u3", "i3", "view"), ("u1", "i0", "view"), ("u1", "i5", "cart"),
    ]):
        _insert(backend, app_id, mirror, _view(n, user, item, name))
    names = ("view", "buy")

    def check():
        for user in ("u1", "u2", "u3", "nobody"):
            want = _scan_targets(mirror, "user", user, set(names))
            assert sorted(reader.targets("user", user, names)) == want, user
            assert sorted(backend.entity_targets(
                app_id, None, "user", user, iter(names)
            )) == want, user
        assert reader.targets("user", "u1", ()) == []
        assert reader.targets("item", "u1", names) == []  # another entity type

    check()  # read once before the write, so anything kept would show
    _TARGET_CASES[case](backend, app_id, mirror)
    check()
    assert sorted(reader.targets("user", "u1", ("cart",))) == _scan_targets(
        mirror, "user", "u1", {"cart"}
    )
    assert backend.entity_targets(app_id + 1, None, "user", "u1", names) == []


def test_memory_reads_of_an_entity_touch_its_events_only(memory_storage):
    """The cost follows the entity's events, not the app's: the index hands
    `find` the entity's own bucket."""
    app_id = memory_storage.get_meta_data_apps().insert(App(id=0, name="shop"))
    backend = memory_storage.get_events()
    backend.insert_batch([
        Event(event="view", entity_type="user", entity_id=f"u{k % 100}",
              target_entity_type="item", target_entity_id=f"i{k}", event_id=f"e{k}")
        for k in range(2000)
    ], app_id)
    table = backend._store[(app_id, None)]
    assert len(table.events) == 2000 and len(table.by_entity) == 100
    assert len(table.by_entity[("user", "u7")]) == 20
    event = backend.get("e7", app_id)
    assert event.with_id("e7") is event  # stamped once, never copied again
    assert event.with_id().event_id != "e7"
    assert len(list(backend.find(app_id, entity_type="user", entity_id="u7"))) == 20
    assert len(list(backend.find(app_id, entity_type="user"))) == 2000
