"""Seeded tenants for the serve cells: factors made on the device, models
published into a `memory` storage as blobs that build themselves on load.

A tenant's blob is a few hundred bytes: unpickling it (which the server's
own loader does, `_tenant_loader` -> `load_deployment` ->
`deserialize_models`) calls :func:`build_model`, which draws that tenant's
two factor tables in one jitted call from (seed, tenant) and hands every
tenant the same two id maps. So the host keeps no 208 MB blob per tenant
and unpickles no 1.6M-entry map per tenant; `stage_model` passes the device
arrays through. The reference reads the same tables back with
:func:`host_factors` after the window: they are the benchmark's, not the
program's.
"""

from __future__ import annotations

import datetime as _dt
import functools
import pickle

import jax
import jax.numpy as jnp
import numpy as np

#: item norms are log-normal with this sigma (popular items have longer
#: factors, as trained ALS factors do); users are unit normals scaled
ITEM_LOG_SIGMA = 0.5
USER_SCALE = 0.25

def _key(seed: int, tenant: int):
    # seeds pass 2**31: split into two uint32 words, no int32 anywhere
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(words), tenant)


@functools.partial(jax.jit, static_argnames=("n_users", "n_items", "rank"))
def _draw(key, n_users: int, n_items: int, rank: int):
    ku, kv, ks = jax.random.split(key, 3)
    users = USER_SCALE * jax.random.normal(ku, (n_users, rank), jnp.float32)
    norms = jnp.exp(ITEM_LOG_SIGMA * jax.random.normal(ks, (n_items, 1)))
    items = norms * jax.random.normal(kv, (n_items, rank), jnp.float32)
    return users, items


def device_factors(seed: int, tenant: int, n_users: int, n_items: int, rank: int):
    """Tenant ``tenant``'s (user, item) f32 tables, on the device."""
    return _draw(_key(seed, tenant), n_users, n_items, rank)


def host_factors(seed: int, tenant: int, n_users: int, n_items: int, rank: int):
    """The same two tables as numpy arrays, for the reference."""
    users, items = device_factors(seed, tenant, n_users, n_items, rank)
    return jax.device_get((users, items))


@functools.lru_cache(maxsize=1)
def id_maps(n_users: int, n_items: int):
    """The two id maps every tenant shares: user ``u<i>``, item ``i<j>``."""
    from predictionio_tpu.utils.bimap import BiMap

    return (
        BiMap([f"u{i}" for i in range(n_users)]),
        BiMap([f"i{j}" for j in range(n_items)]),
    )


def build_model(seed: int, tenant: int, n_users: int, n_items: int, rank: int):
    """What unpickling a tenant's blob returns: the `ALSRecModel` a train
    would have published, its factors already on the device."""
    from predictionio_tpu.models.recommendation import ALSRecModel

    users, items = device_factors(seed, tenant, n_users, n_items, rank)
    user_map, item_map = id_maps(n_users, n_items)
    return ALSRecModel(
        user_factors=users, item_factors=items,
        user_map=user_map, item_map=item_map,
    )


class _LazyModel:
    """Pickles to a call of :func:`build_model`."""

    def __init__(self, *args):
        self.args = args

    def __reduce__(self):
        return build_model, self.args


def engine_params(rank: int):
    from predictionio_tpu.core.engine import EngineParams
    from predictionio_tpu.models.recommendation import (
        ALSParams, RecDataSourceParams, RecPreparatorParams,
    )

    return EngineParams(
        data_source=("", RecDataSourceParams(app_name="chipbench")),
        preparator=("", RecPreparatorParams()),
        algorithms=[("als", ALSParams(rank=rank))],
    )


def tenant_name(index: int) -> str:
    return f"t{index:03d}"


def publish_tenants(
    engine_id: str, seed: int, n_tenants: int,
    n_users: int, n_items: int, rank: int,
):
    """A `memory` storage holding one COMPLETED instance and one lazy blob
    per tenant; returns ``(storage, {tenant: variant})``."""
    from predictionio_tpu.core.persistence import _FORMAT_VERSION
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import EngineInstance, Model

    storage = Storage(env={
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    instances = storage.get_meta_data_engine_instances()
    models = storage.get_model_data_models()
    now = _dt.datetime.now(_dt.timezone.utc)
    tenants = {}
    for t in range(n_tenants):
        name = tenant_name(t)
        iid = instances.insert(EngineInstance(
            id="", status="COMPLETED", start_time=now, end_time=now,
            engine_id=engine_id, engine_version="1", engine_variant=name,
            engine_factory="recommendation",
        ))
        blob = pickle.dumps({
            "version": _FORMAT_VERSION,
            "entries": [("auto", _LazyModel(seed, t, n_users, n_items, rank))],
        })
        models.insert(Model(id=iid, models=blob))
        tenants[name] = name
    return storage, tenants
