"""In-memory storage backend (tests / dev; reference's closest analogue is
the inline mock DAOs used by its HTTP specs, SegmentIOAuthSpec.scala:21-57).

Implements every DAO interface with plain dicts behind one lock, so a full
app → events → train → deploy cycle can run with zero external services.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import itertools
import operator
import threading
import uuid
from typing import Iterable, Iterator, Sequence

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import (
    AccessKey,
    AccessKeysBackend,
    App,
    AppsBackend,
    Channel,
    ChannelsBackend,
    EngineInstance,
    EngineInstancesBackend,
    EngineManifest,
    EngineManifestsBackend,
    EvaluationInstance,
    EvaluationInstancesBackend,
    EventsBackend,
    Model,
    ModelsBackend,
)


class MemoryApps(AppsBackend):
    def __init__(self, config=None):
        self._lock = threading.Lock()
        self._apps: dict[int, App] = {}
        self._next = itertools.count(1)

    def insert(self, app: App) -> int | None:
        with self._lock:
            app_id = app.id if app.id > 0 else next(self._next)
            if app_id in self._apps:
                return None
            if any(a.name == app.name for a in self._apps.values()):
                return None
            self._apps[app_id] = App(app_id, app.name, app.description)
            return app_id

    def get(self, app_id: int) -> App | None:
        return self._apps.get(app_id)

    def get_by_name(self, name: str) -> App | None:
        with self._lock:
            return next(
                (a for a in self._apps.values() if a.name == name), None
            )

    def get_all(self) -> list[App]:
        return sorted(self._apps.values(), key=lambda a: a.id)

    def update(self, app: App) -> bool:
        with self._lock:
            if app.id not in self._apps:
                return False
            self._apps[app.id] = app
            return True

    def delete(self, app_id: int) -> bool:
        with self._lock:
            return self._apps.pop(app_id, None) is not None


class MemoryAccessKeys(AccessKeysBackend):
    def __init__(self, config=None):
        self._lock = threading.Lock()
        self._keys: dict[str, AccessKey] = {}

    def insert(self, access_key: AccessKey) -> str | None:
        with self._lock:
            key = access_key.key or self.generate_key()
            if key in self._keys:
                return None
            self._keys[key] = AccessKey(
                key, access_key.appid, tuple(access_key.events)
            )
            return key

    def get(self, key: str) -> AccessKey | None:
        return self._keys.get(key)

    def get_all(self) -> list[AccessKey]:
        return list(self._keys.values())

    def get_by_app_id(self, app_id: int) -> list[AccessKey]:
        return [k for k in self._keys.values() if k.appid == app_id]

    def update(self, access_key: AccessKey) -> bool:
        with self._lock:
            if access_key.key not in self._keys:
                return False
            self._keys[access_key.key] = access_key
            return True

    def delete(self, key: str) -> bool:
        with self._lock:
            return self._keys.pop(key, None) is not None


class MemoryChannels(ChannelsBackend):
    def __init__(self, config=None):
        self._lock = threading.Lock()
        self._channels: dict[int, Channel] = {}
        self._next = itertools.count(1)

    def insert(self, channel: Channel) -> int | None:
        if not Channel.is_valid_name(channel.name):
            return None
        with self._lock:
            cid = channel.id if channel.id > 0 else next(self._next)
            if cid in self._channels:
                return None
            if any(
                c.appid == channel.appid and c.name == channel.name
                for c in self._channels.values()
            ):
                return None
            self._channels[cid] = Channel(cid, channel.name, channel.appid)
            return cid

    def get(self, channel_id: int) -> Channel | None:
        return self._channels.get(channel_id)

    def get_by_app_id(self, app_id: int) -> list[Channel]:
        return [c for c in self._channels.values() if c.appid == app_id]

    def delete(self, channel_id: int) -> bool:
        with self._lock:
            return self._channels.pop(channel_id, None) is not None


class MemoryEngineInstances(EngineInstancesBackend):
    def __init__(self, config=None):
        self._lock = threading.Lock()
        self._instances: dict[str, EngineInstance] = {}

    def insert(self, instance: EngineInstance) -> str:
        with self._lock:
            iid = instance.id or uuid.uuid4().hex
            self._instances[iid] = dataclasses.replace(instance, id=iid)
            return iid

    def get(self, instance_id: str) -> EngineInstance | None:
        return self._instances.get(instance_id)

    def get_all(self) -> list[EngineInstance]:
        return list(self._instances.values())

    def get_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> list[EngineInstance]:
        out = [
            i
            for i in self._instances.values()
            if i.status == "COMPLETED"
            and i.engine_id == engine_id
            and i.engine_version == engine_version
            and i.engine_variant == engine_variant
        ]
        return sorted(out, key=lambda i: i.start_time, reverse=True)

    def get_latest_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> EngineInstance | None:
        completed = self.get_completed(
            engine_id, engine_version, engine_variant
        )
        return completed[0] if completed else None

    def update(self, instance: EngineInstance) -> bool:
        with self._lock:
            if instance.id not in self._instances:
                return False
            self._instances[instance.id] = instance
            return True

    def delete(self, instance_id: str) -> bool:
        with self._lock:
            return self._instances.pop(instance_id, None) is not None


class MemoryEngineManifests(EngineManifestsBackend):
    def __init__(self, config=None):
        self._lock = threading.Lock()
        self._manifests: dict[tuple[str, str], EngineManifest] = {}

    def insert(self, manifest: EngineManifest) -> None:
        with self._lock:
            self._manifests[(manifest.id, manifest.version)] = manifest

    def get(self, manifest_id: str, version: str) -> EngineManifest | None:
        return self._manifests.get((manifest_id, version))

    def get_all(self) -> list[EngineManifest]:
        return list(self._manifests.values())

    def update(self, manifest: EngineManifest, upsert: bool = False) -> None:
        with self._lock:
            key = (manifest.id, manifest.version)
            if key not in self._manifests and not upsert:
                raise KeyError(f"engine manifest {key} not found")
            self._manifests[key] = manifest

    def delete(self, manifest_id: str, version: str) -> bool:
        with self._lock:
            return (
                self._manifests.pop((manifest_id, version), None) is not None
            )


class MemoryEvaluationInstances(EvaluationInstancesBackend):
    def __init__(self, config=None):
        self._lock = threading.Lock()
        self._instances: dict[str, EvaluationInstance] = {}

    def insert(self, instance: EvaluationInstance) -> str:
        with self._lock:
            iid = instance.id or uuid.uuid4().hex
            self._instances[iid] = dataclasses.replace(instance, id=iid)
            return iid

    def get(self, instance_id: str) -> EvaluationInstance | None:
        return self._instances.get(instance_id)

    def get_all(self) -> list[EvaluationInstance]:
        return list(self._instances.values())

    def get_completed(self) -> list[EvaluationInstance]:
        out = [
            i
            for i in self._instances.values()
            if i.status == "EVALCOMPLETED"
        ]
        return sorted(out, key=lambda i: i.start_time, reverse=True)

    def update(self, instance: EvaluationInstance) -> bool:
        with self._lock:
            if instance.id not in self._instances:
                return False
            self._instances[instance.id] = instance
            return True

    def delete(self, instance_id: str) -> bool:
        with self._lock:
            return self._instances.pop(instance_id, None) is not None


class MemoryModels(ModelsBackend):
    def __init__(self, config=None):
        self._models: dict[str, Model] = {}

    def insert(self, model: Model) -> None:
        self._models[model.id] = model

    def get(self, model_id: str) -> Model | None:
        return self._models.get(model_id)

    def delete(self, model_id: str) -> bool:
        return self._models.pop(model_id, None) is not None

    def list_ids(self) -> list[str] | None:
        return sorted(self._models)


_EVENT_TIME = operator.attrgetter("event_time")


class _EventTable:
    """One (app, channel): the events by id, and the same events by
    entity, each entity's in insertion order, with the version of the
    entity's last change. Mutated only under the backend's lock."""

    __slots__ = ("events", "by_entity", "versions")

    def __init__(self):
        self.events: dict[str, Event] = {}
        self.by_entity: dict[tuple[str, str], dict[str, Event]] = {}
        self.versions: dict[tuple[str, str], int] = {}

    def drop(self, event: Event, version: int) -> None:
        del self.events[event.event_id]
        key = (event.entity_type, event.entity_id)
        bucket = self.by_entity[key]
        del bucket[event.event_id]
        if not bucket:
            del self.by_entity[key]
        self.versions[key] = version


class MemoryEvents(EventsBackend):
    """Per-(app, channel) events behind one lock, indexed by id and by
    entity: a read that names an entity (`find(entity_type=, entity_id=)`,
    the serve-time ``find_by_entity``) looks at that entity's events
    only, and `insert` / `insert_batch` / `delete` keep the index, so a
    write that has returned is in the next read."""

    def __init__(self, config=None):
        self._lock = threading.Lock()
        self._store: dict[tuple[int, int | None], _EventTable] = {}
        # versions never repeat, across entities and dropped tables
        self._clock = itertools.count(1)

    def _key(self, app_id: int, channel_id: int | None):
        return (app_id, channel_id)

    def init(self, app_id: int, channel_id: int | None = None) -> bool:
        with self._lock:
            self._store.setdefault(
                self._key(app_id, channel_id), _EventTable()
            )
            return True

    def remove(self, app_id: int, channel_id: int | None = None) -> bool:
        with self._lock:
            return (
                self._store.pop(self._key(app_id, channel_id), None)
                is not None
            )

    def close(self) -> None:
        pass

    def insert(
        self, event: Event, app_id: int, channel_id: int | None = None
    ) -> str:
        return self.insert_batch([event], app_id, channel_id)[0]

    def insert_batch(
        self,
        events: Sequence[Event],
        app_id: int,
        channel_id: int | None = None,
    ) -> list[str]:
        stamped = [e.with_id(e.event_id) for e in events]
        with self._lock:
            table = self._store.setdefault(
                self._key(app_id, channel_id), _EventTable()
            )
            version = next(self._clock)
            # a bulk load is millions of these, and a run of one entity's
            # events shares its bucket
            by_id, by_entity = table.events, table.by_entity
            versions = table.versions
            last_type = last_id = bucket = None
            for e in stamped:
                event_id = e.event_id
                if event_id in by_id:
                    table.drop(by_id[event_id], version)
                    last_id = None  # the drop may have emptied the bucket
                by_id[event_id] = e
                # the same string objects again: the entity of the event
                # before (anything else looks its bucket up)
                if e.entity_id is not last_id or e.entity_type is not last_type:
                    last_type, last_id = e.entity_type, e.entity_id
                    entity = (last_type, last_id)
                    bucket = by_entity.get(entity)
                    if bucket is None:
                        bucket = by_entity[entity] = {}
                    versions[entity] = version
                bucket[event_id] = e
        return [e.event_id for e in stamped]

    def get(
        self, event_id: str, app_id: int, channel_id: int | None = None
    ) -> Event | None:
        table = self._store.get(self._key(app_id, channel_id))
        return table.events.get(event_id) if table else None

    def delete(
        self, event_id: str, app_id: int, channel_id: int | None = None
    ) -> bool:
        with self._lock:
            table = self._store.get(self._key(app_id, channel_id))
            event = table.events.get(event_id) if table else None
            if event is None:
                return False
            table.drop(event, next(self._clock))
            return True

    def entity_version(
        self,
        app_id: int,
        channel_id: int | None,
        entity_type: str,
        entity_id: str,
    ) -> int:
        table = self._store.get(self._key(app_id, channel_id))
        if table is None:
            return 0
        return table.versions.get((entity_type, entity_id), 0)

    def entity_targets(
        self,
        app_id: int,
        channel_id: int | None,
        entity_type: str,
        entity_id: str,
        event_names: Iterable[str],
    ) -> list[str]:
        names = frozenset(event_names)
        with self._lock:
            table = self._store.get(self._key(app_id, channel_id))
            bucket = table and table.by_entity.get((entity_type, entity_id))
            if not bucket:
                return []
            # the index's own bucket: no copy, no order, no `Event` kept
            return [
                e.target_entity_id
                for e in bucket.values()
                if e.event in names and e.target_entity_id is not None
            ]

    def find(
        self,
        app_id: int,
        channel_id: int | None = None,
        start_time: _dt.datetime | None = None,
        until_time: _dt.datetime | None = None,
        entity_type: str | None = None,
        entity_id: str | None = None,
        event_names: Sequence[str] | None = None,
        target_entity_type=...,
        target_entity_id=...,
        limit: int | None = None,
        reversed: bool = False,
    ) -> Iterator[Event]:
        with self._lock:
            table = self._store.get(self._key(app_id, channel_id))
            if table is None:
                events = []
            elif entity_type is not None and entity_id is not None:
                events = list(
                    table.by_entity.get((entity_type, entity_id), {}).values()
                )
                # the bucket's events are the entity's: nothing left to test
                entity_type = entity_id = None
            else:
                events = list(table.events.values())
        events.sort(key=_EVENT_TIME, reverse=reversed)
        # Naive bounds are UTC by convention (same rule as Event.__post_init__)
        if start_time is not None and start_time.tzinfo is None:
            start_time = start_time.replace(tzinfo=_dt.timezone.utc)
        if until_time is not None and until_time.tzinfo is None:
            until_time = until_time.replace(tzinfo=_dt.timezone.utc)
        names = set(event_names) if event_names is not None else None
        if limit is not None and limit == 0:
            return
        n = 0
        for e in events:
            if start_time is not None and e.event_time < start_time:
                continue
            if until_time is not None and e.event_time >= until_time:
                continue
            if entity_type is not None and e.entity_type != entity_type:
                continue
            if entity_id is not None and e.entity_id != entity_id:
                continue
            if names is not None and e.event not in names:
                continue
            if target_entity_type is not ... and (
                e.target_entity_type != target_entity_type
            ):
                continue
            if target_entity_id is not ... and (
                e.target_entity_id != target_entity_id
            ):
                continue
            yield e
            n += 1
            if limit is not None and 0 < limit <= n:
                return
