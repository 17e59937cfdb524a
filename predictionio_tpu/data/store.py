"""Engine-facing event stores (developer API).

Counterpart of the reference's ``data/.../store`` package:

* :class:`EventStore` ≈ ``PEventStore`` (store/PEventStore.scala:30-116) —
  bulk, training-time reads addressed by **app name** (+ optional channel
  name), resolved to ids through the metadata store
  (store/Common.appNameToId:28-49). Bulk results surface as
  :class:`~predictionio_tpu.data.eventframe.EventFrame` columnar batches
  instead of ``RDD[Event]``.
* The same class exposes ``find_by_entity`` ≈ ``LEventStore``
  (store/LEventStore.scala:30-142) — low-latency serve-time reads
  (latest-first), used by the e-commerce template's predict path.
"""

from __future__ import annotations

import datetime as _dt
from typing import Iterable, Iterator, Sequence

from predictionio_tpu.data.datamap import PropertyMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.eventframe import EventFrame
from predictionio_tpu.data.storage import Storage, get_storage


class EventStoreError(RuntimeError):
    pass


class EntityReader:
    """Serve-time reads of one app's entities, the app resolved once
    (`EventStore.entity_reader`): what a predict path calls many times a
    batch."""

    __slots__ = ("_events", "_app_id", "_channel_id")

    def __init__(self, events, app_id: int, channel_id: int | None):
        self._events = events
        self._app_id = app_id
        self._channel_id = channel_id

    def version(self, entity_type: str, entity_id: str) -> int | None:
        """`EventsBackend.entity_version` of the entity: equal to an
        earlier reading only if no write to the entity returned in
        between; None where the backend keeps none."""
        return self._events.entity_version(
            self._app_id, self._channel_id, entity_type, entity_id
        )

    def targets(
        self, entity_type: str, entity_id: str, event_names: Iterable[str]
    ) -> list[str]:
        """`EventsBackend.entity_targets` of the entity: the targets of
        its events of these names, in no promised order."""
        return self._events.entity_targets(
            self._app_id, self._channel_id, entity_type, entity_id,
            event_names,
        )

    def find(
        self,
        entity_type: str,
        entity_id: str,
        event_names: Sequence[str] | None = None,
        limit: int | None = None,
        latest: bool = True,
    ) -> list[Event]:
        """The entity's events, latest first (`find_by_entity`)."""
        return list(
            self._events.find(
                self._app_id,
                self._channel_id,
                entity_type=entity_type,
                entity_id=entity_id,
                event_names=event_names,
                limit=limit,
                reversed=latest,
            )
        )


class EventStore:
    """App-name-addressed event reads over the configured storage."""

    def __init__(self, storage: Storage | None = None):
        self._storage = storage or get_storage()

    # -- name→id resolution (reference store/Common.scala:28-49) ----------
    def _resolve(
        self, app_name: str, channel_name: str | None
    ) -> tuple[int, int | None]:
        app = self._storage.get_meta_data_apps().get_by_name(app_name)
        if app is None:
            raise EventStoreError(
                f"Invalid app name {app_name!r}: app does not exist."
            )
        if channel_name is None:
            return app.id, None
        channels = self._storage.get_meta_data_channels().get_by_app_id(
            app.id
        )
        for ch in channels:
            if ch.name == channel_name:
                return app.id, ch.id
        raise EventStoreError(
            f"Invalid channel name {channel_name!r} for app {app_name!r}."
        )

    # -- bulk (training-time) ---------------------------------------------
    def find(
        self,
        app_name: str,
        channel_name: str | None = None,
        start_time: _dt.datetime | None = None,
        until_time: _dt.datetime | None = None,
        entity_type: str | None = None,
        entity_id: str | None = None,
        event_names: Sequence[str] | None = None,
        target_entity_type=...,
        target_entity_id=...,
    ) -> Iterator[Event]:
        app_id, channel_id = self._resolve(app_name, channel_name)
        return self._storage.get_events().find(
            app_id,
            channel_id,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            entity_id=entity_id,
            event_names=event_names,
            target_entity_type=target_entity_type,
            target_entity_id=target_entity_id,
        )

    def frame(self, app_name: str, **kwargs) -> EventFrame:
        """Bulk columnar read — the device-staging path."""
        return EventFrame.from_events(self.find(app_name, **kwargs))

    def interactions(
        self,
        app_name: str,
        channel_name: str | None = None,
        event_names: Sequence[str] | None = None,
        value_key: str | None = None,
        default_value: float = 1.0,
    ):
        """Dense COO interactions for training reads.

        Dispatches to the backend's native columnar path when available
        (the C++ event log scans straight to dense-id arrays); otherwise
        falls back to the EventFrame conversion.
        """
        app_id, channel_id = self._resolve(app_name, channel_name)
        backend = self._storage.get_events()
        if hasattr(backend, "interactions"):
            return backend.interactions(
                app_id,
                channel_id,
                event_names=event_names,
                value_key=value_key,
                default_value=default_value,
            )
        frame = self.frame(
            app_name, channel_name=channel_name, event_names=event_names
        )
        return frame.to_interactions(
            value_key=value_key, default_value=default_value
        )

    def aggregate_properties(
        self,
        app_name: str,
        entity_type: str,
        channel_name: str | None = None,
        start_time: _dt.datetime | None = None,
        until_time: _dt.datetime | None = None,
        required: Sequence[str] | None = None,
    ) -> dict[str, PropertyMap]:
        """Reference PEventStore.aggregateProperties:70-116."""
        app_id, channel_id = self._resolve(app_name, channel_name)
        return self._storage.get_events().aggregate_properties(
            app_id,
            channel_id,
            entity_type=entity_type,
            start_time=start_time,
            until_time=until_time,
            required=required,
        )

    def extract_entity_map(
        self,
        app_name: str,
        entity_type: str,
        channel_name: str | None = None,
        start_time: _dt.datetime | None = None,
        until_time: _dt.datetime | None = None,
        required: Sequence[str] | None = None,
    ):
        """Aggregated entity properties as an
        :class:`~predictionio_tpu.utils.bimap.EntityMap` — string id ↔
        dense index ↔ PropertyMap (reference PEvents.extractEntityMap,
        storage/PEvents.scala:96-130)."""
        from predictionio_tpu.utils.bimap import EntityMap

        props = self.aggregate_properties(
            app_name,
            entity_type,
            channel_name=channel_name,
            start_time=start_time,
            until_time=until_time,
            required=required,
        )
        return EntityMap(props)

    # -- serve-time (reference LEventStore) -------------------------------
    def entity_reader(
        self, app_name: str, channel_name: str | None = None
    ) -> EntityReader:
        app_id, channel_id = self._resolve(app_name, channel_name)
        return EntityReader(
            self._storage.get_events(), app_id, channel_id
        )

    def find_by_entity(
        self,
        app_name: str,
        entity_type: str,
        entity_id: str,
        channel_name: str | None = None,
        event_names: Sequence[str] | None = None,
        target_entity_type=...,
        target_entity_id=...,
        start_time: _dt.datetime | None = None,
        until_time: _dt.datetime | None = None,
        limit: int | None = None,
        latest: bool = True,
    ) -> list[Event]:
        """Latest-first entity scan for predict-time business rules
        (reference LEventStore.findByEntity:36-85)."""
        app_id, channel_id = self._resolve(app_name, channel_name)
        return list(
            self._storage.get_events().find(
                app_id,
                channel_id,
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                entity_id=entity_id,
                event_names=event_names,
                target_entity_type=target_entity_type,
                target_entity_id=target_entity_id,
                limit=limit,
                reversed=latest,
            )
        )
