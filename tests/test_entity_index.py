"""Serve-time reads by entity (`EventStore.find_by_entity`,
`EventStore.entity_reader`) equal a linear scan of everything written,
after interleaved inserts, batch inserts, re-inserts under an old id and
deletes, on the `memory` backend (which keeps an entity index of its own)
and on `sqlite` (whose table is indexed by entity)."""

from __future__ import annotations

import datetime as _dt

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
