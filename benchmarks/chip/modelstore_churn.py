"""Seeded tenants for the churn cell: every tenant's model published into
the model store as the bytes a train would publish, and read back from
them by the server's own loader each time the tenant comes in.

A tenant's blob is what `core/persistence.serialize_models` writes for the
`ALSRecModel` of a finished train (two float32 numpy factor tables and the
two id maps, about 35 MB at MovieLens 20M widths), committed by
`publish_generation` with its checksum manifest, as `run_train` commits.
Nothing builds itself on load and no table originates on the device: a
reload is the read (checksum included), the unpickle of the tenant's own
two maps, and the copy to the device. The factors are `modelstore.py`'s
draw for (seed, tenant), fetched to the host once for the publish; the
reference reads the same tables with `modelstore.host_factors`.

Ids. The generator that is there (`loadgen.py`) asks for users ``u<i>``, so
a tenant's users keep that form; each blob carries its own copy of the map
and a loaded tenant shares no map object with another. A tenant's items are
``<tenant>.i<j>`` (`item_id`): an answer served from another tenant's maps
shows in its ids. The server is handed the tenants in an order shuffled by
the seed (`handover_order`): a deployment's dictionary says nothing of
which app is busy.
"""

from __future__ import annotations

import concurrent.futures
import datetime as _dt

import numpy as np

import modelstore

#: host memory kept free beside what a run is known to need next
_HEADROOM_BYTES = 3 * 2**30
#: host memory a resident tenant's unpickled id maps take, as a share of
#: its blob (30.7 MB of 34.9, and the pool holds four tenants of five)
_RESIDENT_SHARE = 0.75
_PUBLISH_THREADS = 4


def item_id(tenant: int, item: int) -> str:
    return f"{modelstore.tenant_name(tenant)}.i{item}"


def handover_order(seed: int, n_tenants: int) -> list[int]:
    """The order in which the server is handed its tenants."""
    rng = np.random.default_rng([seed, 0xC4A7])
    return [int(t) for t in rng.permutation(n_tenants)]


def host_model(seed, tenant, n_users, n_items, rank, user_map):
    """The `ALSRecModel` a train of this tenant would hand to
    `serialize_models`: host tables, the shared form of the user ids, the
    tenant's own item ids."""
    from predictionio_tpu.models.recommendation import ALSRecModel
    from predictionio_tpu.utils.bimap import BiMap

    users, items = modelstore.host_factors(seed, tenant, n_users, n_items, rank)
    return ALSRecModel(
        user_factors=users, item_factors=items, user_map=user_map,
        item_map=BiMap([item_id(tenant, j) for j in range(n_items)]),
    )


def _need_memory(nbytes: float, what: str) -> None:
    """Ends the run with a message where the host has not ``nbytes`` and
    the headroom left, rather than let the machine run out."""
    try:
        with open("/proc/meminfo") as f:
            available = next(
                int(line.split()[1]) * 1024 for line in f
                if line.startswith("MemAvailable:")
            )
    except (OSError, StopIteration):
        return
    if available < nbytes + _HEADROOM_BYTES:
        raise SystemExit(
            f"the host has {available / 2**30:.1f} GiB of memory left {what}: "
            "the `memory` model store and the resident tenants' id maps do "
            "not fit here"
        )


def publish_tenants(engine_id, seed, n_tenants, n_users, n_items, rank):
    """A `memory` storage holding one COMPLETED instance and one committed
    generation a tenant; returns ``(storage, {tenant: variant} in the
    hand-over order, bytes published)``."""
    from predictionio_tpu.core.persistence import (
        publish_generation, serialize_models,
    )
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import EngineInstance
    from predictionio_tpu.models.recommendation import ALSAlgorithm, ALSParams
    from predictionio_tpu.utils.bimap import BiMap

    storage = Storage(env={
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    instances = storage.get_meta_data_engine_instances()
    models = storage.get_model_data_models()
    now = _dt.datetime.now(_dt.timezone.utc)
    algorithms = [ALSAlgorithm(ALSParams(rank=rank))]
    user_map = BiMap([f"u{i}" for i in range(n_users)])
    ids = [
        instances.insert(EngineInstance(
            id="", status="COMPLETED", start_time=now, end_time=now,
            engine_id=engine_id, engine_version="1",
            engine_variant=modelstore.tenant_name(t),
            engine_factory="recommendation",
        ))
        for t in range(n_tenants)
    ]

    def blob_of(t: int) -> bytes:
        return serialize_models(
            ids[t], algorithms,
            [host_model(seed, t, n_users, n_items, rank, user_map)],
        )

    published = 0
    # the draw, the fetch and the copies let go of the interpreter lock
    pool = concurrent.futures.ThreadPoolExecutor(_PUBLISH_THREADS)
    try:
        for t, blob in enumerate(pool.map(blob_of, range(n_tenants))):
            publish_generation(models, ids[t], blob)
            published += len(blob)
            if t % 32 == 31:
                _need_memory(0, f"after {t + 1} of {n_tenants} tenants")
    finally:
        # a run that ends here publishes nothing more
        pool.shutdown(cancel_futures=True)
    _need_memory(
        _RESIDENT_SHARE * published,
        f"beside {published / 2**30:.1f} GiB of published models",
    )
    tenants = {
        modelstore.tenant_name(t): modelstore.tenant_name(t)
        for t in handover_order(seed, n_tenants)
    }
    return storage, tenants, published
