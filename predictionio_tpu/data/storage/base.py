"""Storage SPIs: metadata records + DAO interfaces.

Capability parity with the reference's storage trait layer
(``data/.../storage``): ``Apps.scala:29-57``, ``AccessKeys.scala:32-68``,
``Channels.scala:29-78``, ``EngineInstances.scala:43-94``,
``EvaluationInstances.scala:39-78``, ``Models.scala:30-48``,
``LEvents.scala:37-489``. Backends implement these interfaces and are
wired by the env-var registry in
:mod:`predictionio_tpu.data.storage` (reference ``Storage.scala:114-403``).

Differences from the reference, by design:

* DAOs are synchronous (callers thread as needed) — no Future wrappers.
* There is no separate Spark-flavored ``PEvents``: bulk access is
  :meth:`EventsBackend.find` plus the columnar
  :class:`~predictionio_tpu.data.eventframe.EventFrame` conversion, which is
  the device-staging path.
"""

from __future__ import annotations

import abc
import dataclasses
import datetime as _dt
import secrets
from typing import Iterable, Iterator, Sequence

from predictionio_tpu.data.datamap import PropertyMap
from predictionio_tpu.data.event import Event


class StorageError(RuntimeError):
    """Reference ``StorageClientException`` (Storage.scala:46-48): raised
    for unreachable backends, missing drivers, unknown backend types, and
    unbound repositories. Defined here (not the package ``__init__``) so
    backend modules can import it without a circular import."""


# Reference-spelled alias
StorageClientException = StorageError


class PartialBatchError(StorageError):
    """A batch insert failed partway; ``inserted_ids`` are the events
    durably stored BEFORE the failure (append-only backends cannot roll
    them back). Callers report per-event success so client retries can
    resend only the unsaved suffix."""

    def __init__(self, message: str, inserted_ids: list[str]):
        super().__init__(message)
        self.inserted_ids = inserted_ids

# --------------------------------------------------------------------------
# Metadata records
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class App:
    """Reference Apps.scala:29-35."""

    id: int
    name: str
    description: str | None = None


@dataclasses.dataclass(frozen=True)
class AccessKey:
    """Reference AccessKeys.scala:32-40; empty ``events`` = allow all."""

    key: str
    appid: int
    events: tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class Channel:
    """Reference Channels.scala:29-49 (name: 1-16 word chars)."""

    id: int
    name: str
    appid: int

    @staticmethod
    def is_valid_name(name: str) -> bool:
        return (
            0 < len(name) <= 16
            and all(c.isalnum() or c in "-_" for c in name)
        )


@dataclasses.dataclass(frozen=True)
class EngineManifest:
    """A registered engine build (reference EngineManifests.scala:34-50).

    ``files`` holds the engine's source paths (the reference stores
    assembly-jar paths; here it is the template directory / module files).
    """

    id: str
    version: str
    name: str
    description: str | None = None
    files: tuple[str, ...] = ()
    engine_factory: str = ""


@dataclasses.dataclass(frozen=True)
class EngineInstance:
    """A train/deploy run record (reference EngineInstances.scala:43-69)."""

    id: str
    status: str  # INIT | TRAINING | COMPLETED | FAILED
    start_time: _dt.datetime
    end_time: _dt.datetime
    engine_id: str
    engine_version: str
    engine_variant: str
    engine_factory: str
    batch: str = ""
    env: dict[str, str] = dataclasses.field(default_factory=dict)
    mesh_conf: dict[str, str] = dataclasses.field(default_factory=dict)
    data_source_params: str = "{}"
    preparator_params: str = "{}"
    algorithms_params: str = "[]"
    serving_params: str = "{}"


@dataclasses.dataclass(frozen=True)
class EvaluationInstance:
    """Reference EvaluationInstances.scala:39-61."""

    id: str
    status: str  # INIT | EVALUATING | EVALCOMPLETED
    start_time: _dt.datetime
    end_time: _dt.datetime
    evaluation_class: str = ""
    engine_params_generator_class: str = ""
    batch: str = ""
    env: dict[str, str] = dataclasses.field(default_factory=dict)
    evaluator_results: str = ""
    evaluator_results_html: str = ""
    evaluator_results_json: str = ""


@dataclasses.dataclass(frozen=True)
class Model:
    """Serialized model blob (reference Models.scala:30-35)."""

    id: str
    models: bytes


# --------------------------------------------------------------------------
# DAO interfaces
# --------------------------------------------------------------------------


class AppsBackend(abc.ABC):
    """Reference Apps.scala:37-57."""

    @abc.abstractmethod
    def insert(self, app: App) -> int | None:
        """Insert; ``app.id == 0`` means auto-assign. Returns assigned id."""

    @abc.abstractmethod
    def get(self, app_id: int) -> App | None: ...

    @abc.abstractmethod
    def get_by_name(self, name: str) -> App | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[App]: ...

    @abc.abstractmethod
    def update(self, app: App) -> bool: ...

    @abc.abstractmethod
    def delete(self, app_id: int) -> bool: ...


class AccessKeysBackend(abc.ABC):
    """Reference AccessKeys.scala:42-68."""

    @abc.abstractmethod
    def insert(self, access_key: AccessKey) -> str | None:
        """Insert; empty ``key`` means generate one. Returns the key."""

    @abc.abstractmethod
    def get(self, key: str) -> AccessKey | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[AccessKey]: ...

    @abc.abstractmethod
    def get_by_app_id(self, app_id: int) -> list[AccessKey]: ...

    @abc.abstractmethod
    def update(self, access_key: AccessKey) -> bool: ...

    @abc.abstractmethod
    def delete(self, key: str) -> bool: ...

    @staticmethod
    def generate_key() -> str:
        """Reference AccessKeys.generateKey (64 url-safe random chars)."""
        return secrets.token_urlsafe(48)


class ChannelsBackend(abc.ABC):
    """Reference Channels.scala:51-78."""

    @abc.abstractmethod
    def insert(self, channel: Channel) -> int | None: ...

    @abc.abstractmethod
    def get(self, channel_id: int) -> Channel | None: ...

    @abc.abstractmethod
    def get_by_app_id(self, app_id: int) -> list[Channel]: ...

    @abc.abstractmethod
    def delete(self, channel_id: int) -> bool: ...


class EngineManifestsBackend(abc.ABC):
    """Reference EngineManifests.scala:52-70 (keyed by (id, version))."""

    @abc.abstractmethod
    def insert(self, manifest: EngineManifest) -> None: ...

    @abc.abstractmethod
    def get(self, manifest_id: str, version: str) -> EngineManifest | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[EngineManifest]: ...

    @abc.abstractmethod
    def update(self, manifest: EngineManifest, upsert: bool = False) -> None: ...

    @abc.abstractmethod
    def delete(self, manifest_id: str, version: str) -> bool: ...


class EngineInstancesBackend(abc.ABC):
    """Reference EngineInstances.scala:71-94."""

    @abc.abstractmethod
    def insert(self, instance: EngineInstance) -> str:
        """Insert; empty ``id`` means auto-assign. Returns the id."""

    @abc.abstractmethod
    def get(self, instance_id: str) -> EngineInstance | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[EngineInstance]: ...

    @abc.abstractmethod
    def get_latest_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> EngineInstance | None:
        """Latest COMPLETED instance — what ``deploy`` picks up
        (reference EngineInstances.scala:79-87)."""

    @abc.abstractmethod
    def get_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> list[EngineInstance]: ...

    @abc.abstractmethod
    def update(self, instance: EngineInstance) -> bool: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> bool: ...


class EvaluationInstancesBackend(abc.ABC):
    """Reference EvaluationInstances.scala:63-78."""

    @abc.abstractmethod
    def insert(self, instance: EvaluationInstance) -> str: ...

    @abc.abstractmethod
    def get(self, instance_id: str) -> EvaluationInstance | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_completed(self) -> list[EvaluationInstance]: ...

    @abc.abstractmethod
    def update(self, instance: EvaluationInstance) -> bool: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> bool: ...


class ModelsBackend(abc.ABC):
    """Blob store for trained models (reference Models.scala:37-48).

    ``insert`` must be atomic per blob: a reader never observes a
    partially-written model (localfs: unique tmp file + fsync + rename
    in the same directory). Integrity across blobs is layered on top by
    the generation manifests in
    :mod:`predictionio_tpu.core.persistence`.
    """

    @abc.abstractmethod
    def insert(self, model: Model) -> None: ...

    @abc.abstractmethod
    def get(self, model_id: str) -> Model | None: ...

    @abc.abstractmethod
    def delete(self, model_id: str) -> bool: ...

    def list_ids(self) -> list[str] | None:
        """Enumerate stored blob ids, or ``None`` when the backend
        cannot (a plain KV store with no scan). Anti-entropy
        (:mod:`predictionio_tpu.data.storage.replicated`) uses this to
        diff model sets between peers; ``None`` just disables the
        model-repair pass for that backend, it is not an error."""
        return None

    def quarantine(self, model_id: str) -> bool:
        """Move a corrupt blob aside so no later read can pick it up,
        keeping the bytes for forensics. Default emulation re-inserts
        under a ``quarantined/`` id and deletes the original; backends
        with a native rename (localfs) override with an atomic move.
        Returns False when the blob does not exist."""
        record = self.get(model_id)
        if record is None:
            return False
        self.insert(
            Model(id=f"quarantined/{model_id}", models=record.models)
        )
        self.delete(model_id)
        return True


class EventsBackend(abc.ABC):
    """Event DAO (reference LEvents.scala:37-489).

    All methods take ``(app_id, channel_id)``; ``channel_id=None`` is the
    default channel, mirroring the reference's table-per-(app, channel)
    layout without mandating it on backends.
    """

    @abc.abstractmethod
    def init(self, app_id: int, channel_id: int | None = None) -> bool:
        """Initialize storage for an (app, channel) — ``pio app new``."""

    @abc.abstractmethod
    def remove(self, app_id: int, channel_id: int | None = None) -> bool:
        """Drop all events of an (app, channel) — ``pio app data-delete``."""

    @abc.abstractmethod
    def close(self) -> None: ...

    @abc.abstractmethod
    def insert(
        self, event: Event, app_id: int, channel_id: int | None = None
    ) -> str:
        """Insert one event; returns the assigned event id."""

    def insert_batch(
        self,
        events: Sequence[Event],
        app_id: int,
        channel_id: int | None = None,
    ) -> list[str]:
        return [self.insert(e, app_id, channel_id) for e in events]

    @abc.abstractmethod
    def get(
        self, event_id: str, app_id: int, channel_id: int | None = None
    ) -> Event | None: ...

    @abc.abstractmethod
    def delete(
        self, event_id: str, app_id: int, channel_id: int | None = None
    ) -> bool: ...

    @abc.abstractmethod
    def find(
        self,
        app_id: int,
        channel_id: int | None = None,
        start_time: _dt.datetime | None = None,
        until_time: _dt.datetime | None = None,
        entity_type: str | None = None,
        entity_id: str | None = None,
        event_names: Sequence[str] | None = None,
        target_entity_type: str | None | type(...) = ...,
        target_entity_id: str | None | type(...) = ...,
        limit: int | None = None,
        reversed: bool = False,
    ) -> Iterator[Event]:
        """Filtered scan, time-ascending (descending when ``reversed``).

        ``target_entity_type``/``target_entity_id`` use tri-state semantics
        mirroring the reference's ``Option[Option[String]]``
        (LEvents.scala:338-345): ``...`` (Ellipsis) = no filter, ``None`` =
        must be absent, a string = must match.
        """

    def entity_version(
        self,
        app_id: int,
        channel_id: int | None,
        entity_type: str,
        entity_id: str,
    ) -> int | None:
        """A number that changes with every insert or delete of an event
        of this entity that has returned, or ``None`` where the backend
        keeps none. A serve-time reader may keep what it derived from an
        entity's events beside the version it read and use it again only
        while the version reads the same: no write is missed, no time
        bound is involved. ``None`` means: read the events every time."""
        return None

    def entity_targets(
        self,
        app_id: int,
        channel_id: int | None,
        entity_type: str,
        entity_id: str,
        event_names: Iterable[str],
    ) -> list[str]:
        """The ``target_entity_id`` of every event of the entity whose
        name is in ``event_names`` and that has a target, in no promised
        order: what a serve-time rule needs of "the items this user has
        seen". A backend with an entity index answers from it."""
        return [
            e.target_entity_id
            for e in self.find(
                app_id,
                channel_id,
                entity_type=entity_type,
                entity_id=entity_id,
                event_names=list(event_names),
            )
            if e.target_entity_id is not None
        ]

    def aggregate_properties(
        self,
        app_id: int,
        channel_id: int | None = None,
        *,
        entity_type: str,
        start_time: _dt.datetime | None = None,
        until_time: _dt.datetime | None = None,
        required: Iterable[str] | None = None,
    ) -> dict[str, PropertyMap]:
        """Fold ``$set/$unset/$delete`` → entity properties
        (reference LEvents.futureAggregateProperties:389-425)."""
        if not entity_type:
            raise ValueError("entity_type is required for aggregation")
        from predictionio_tpu.data.aggregation import aggregate_properties

        events = self.find(
            app_id,
            channel_id,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            event_names=["$set", "$unset", "$delete"],
        )
        result = aggregate_properties(events)
        if required is not None:
            req = list(required)
            result = {
                eid: pm
                for eid, pm in result.items()
                if all(k in pm for k in req)
            }
        return result
