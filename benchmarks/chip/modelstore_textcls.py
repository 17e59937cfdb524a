"""The seeded model of the text-classification cell: a naive-Bayes table
drawn on the device, published into a `memory` storage as a blob that
builds itself on load.

The blob is a few hundred bytes. Unpickling it (the server's own load:
`load_deployment` -> `deserialize_models`) calls :func:`build_model`,
which draws the [d, C] f32 table on the device in row blocks of
``ROW_BLOCK`` written into one buffer, so that no 12.6 GB array passes
through the host and no whole-table draw needs its random bits beside it.
Each entry is a function of (seed, row, class) alone (:func:`normals`):
the reference redraws the rows it needs in numpy float64
(`reference_textcls.normals`), the same arithmetic written again, and
never reads the program's table.

The model: ``theta_t[h, c] = -ln d + z(h, c)`` and ``pi = log_softmax(z(d,
.))``, z a standard normal by Box-Muller from two 24-bit uniforms, each a
32-bit integer hash (``lowbias32``) of the seed, the row and the class.
Un-normalised over the buckets, as a trained table is not: what is
compared is the arithmetic of a query's score, not the training.
"""

from __future__ import annotations

import datetime as _dt
import functools
import math
import pickle

import jax
import jax.numpy as jnp

from roofline_textcls import tiled_classes

ENGINE_ID = "chipbench-textcls"
#: rows of the table drawn by one step of the drawing loop: 8,192 rows of
#: 3,008 classes are 98.6 MB beside the 12.6 GB table
ROW_BLOCK = 8192


def label(c: int) -> str:
    return f"c{c:04d}"


def seed_words(seed: int) -> tuple[int, int]:
    """The seed as two uint32 words (seeds pass 2**31; no int32 anywhere)."""
    return (seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF


def _mix(x):
    """lowbias32 (C. Wellons): a 32-bit integer hash, uint32 in and out."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def normals(words, rows, n_classes: int):
    """[len(rows), n_classes] f32 standard normals of (seed, row, class);
    ``words`` is the seed as a uint32 [2] array (:func:`seed_words`)."""
    s0, s1 = words[0], words[1]
    base = _mix(_mix(rows.astype(jnp.uint32) ^ _mix(s0)) + s1)[:, None]
    cols = 2 * jnp.arange(n_classes, dtype=jnp.uint32)
    u1 = _mix(base ^ _mix(cols)[None, :]) >> 8
    u2 = _mix(base ^ _mix(cols + 1)[None, :]) >> 8
    scale = jnp.float32(2.0 ** -24)
    a = (u1 + 1).astype(jnp.float32) * scale  # (0, 1]
    b = u2.astype(jnp.float32) * scale        # [0, 1)
    return jnp.sqrt(-2.0 * jnp.log(a)) * jnp.cos(jnp.float32(2 * math.pi) * b)


@functools.partial(jax.jit, static_argnames=("n_features", "n_classes", "block"))
def draw_table(words, n_features: int, n_classes: int, block: int):
    """theta_t [d, C'] f32, drawn ``block`` rows a step into one buffer;
    C' is C tiled to the lanes, as the template keeps its table, and the
    padding classes are 0."""
    offset = jnp.float32(-math.log(n_features))
    block = min(block, n_features)
    real = jnp.arange(tiled_classes(n_classes)) < n_classes

    def step(b, table):
        rows = b * block + jnp.arange(block, dtype=jnp.int32)
        part = offset + normals(words, rows, tiled_classes(n_classes))
        part = jnp.where(real[None, :], part, 0.0)
        return jax.lax.dynamic_update_slice(table, part, (b * block, 0))

    table = jnp.zeros((n_features, tiled_classes(n_classes)), jnp.float32)
    return jax.lax.fori_loop(0, n_features // block, step, table)


@functools.partial(jax.jit, static_argnames=("n_features", "n_classes"))
def draw_prior(words, n_features: int, n_classes: int):
    """pi [C'] f32: log_softmax of the normals of row ``d`` (past the
    table), the padding classes at -inf."""
    z = normals(words, jnp.full((1,), n_features, jnp.int32), n_classes)[0]
    pad = jnp.full(tiled_classes(n_classes) - n_classes, -jnp.inf, jnp.float32)
    return jnp.concatenate([jax.nn.log_softmax(z), pad])


def device_model(seed: int, n_features: int, n_classes: int):
    """(pi, theta_t) on the device."""
    if n_features % min(ROW_BLOCK, n_features):
        raise ValueError(f"n_features {n_features} is not a multiple of {ROW_BLOCK}")
    words = jnp.asarray(seed_words(seed), jnp.uint32)
    theta_t = draw_table(words, n_features, n_classes, ROW_BLOCK)
    return draw_prior(words, n_features, n_classes), theta_t


def build_model(seed: int, n_features: int, n_classes: int):
    """What unpickling the blob returns: the `TextNBModel` a train would
    have published, its table already on the device."""
    from predictionio_tpu.models.textclassification import TextNBModel
    from predictionio_tpu.utils.bimap import BiMap

    pi, theta_t = device_model(seed, n_features, n_classes)
    return TextNBModel(
        pi=pi, theta_t=theta_t,
        label_map=BiMap([label(c) for c in range(n_classes)]),
        n_features=n_features,
    )


class _LazyModel:
    """Pickles to a call of :func:`build_model`."""

    def __init__(self, *args):
        self.args = args

    def __reduce__(self):
        return build_model, self.args


def engine_params(n_features: int):
    from predictionio_tpu.core.engine import EngineParams
    from predictionio_tpu.models.textclassification import (
        TextDataSourceParams, TextNBParams, TextPreparatorParams,
    )

    return EngineParams(
        data_source=("", TextDataSourceParams(app_name="chipbench")),
        preparator=("", TextPreparatorParams(n_features=n_features)),
        algorithms=[("nb", TextNBParams())],
    )


def publish(seed: int, n_features: int, n_classes: int):
    """A `memory` storage holding one COMPLETED instance of the
    single-tenant engine (variant ``default``) and its lazy blob."""
    from predictionio_tpu.core.persistence import _FORMAT_VERSION
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import EngineInstance, Model

    storage = Storage(env={
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    now = _dt.datetime.now(_dt.timezone.utc)
    iid = storage.get_meta_data_engine_instances().insert(EngineInstance(
        id="", status="COMPLETED", start_time=now, end_time=now,
        engine_id=ENGINE_ID, engine_version="1", engine_variant="default",
        engine_factory="textclassification",
    ))
    blob = pickle.dumps({
        "version": _FORMAT_VERSION,
        "entries": [("auto", _LazyModel(seed, n_features, n_classes))],
    })
    storage.get_model_data_models().insert(Model(id=iid, models=blob))
    return storage
