"""The seeded model of the complementary-purchase cell: a pair-count table
drawn on the device, published into a `memory` storage as a blob that
builds itself on load.

The blob is a few hundred bytes. Unpickling it (the server's own load:
`load_deployment` -> `deserialize_models`) calls :func:`build_model`,
which draws the table in the template's panel layout (f32 [n_panels,
n_items, width], `ops/cooccurrence.layout`) on the device, ``ROW_BLOCK``
rows a step into one buffer, so that no 9 GB array passes through the
host. Each entry is a function of (seed, min(i, j), max(i, j)) and the
two items' popularity, in 32-bit integer arithmetic alone
(:func:`pair_counts`): the reference redraws the rows it needs in numpy
(`reference_cpurchase.SeededTable.rows`), the same integers written
again, and never reads the program's table.

The counts. Item i (0 the most popular) is in ``c_i`` baskets, Zipf over
the items and scaled to the published order-product rows
(:func:`popularity`). A pair's count is the count independence gives,
``c_i c_j / N``, times a factor drawn from the pair's hash, ``m/64 *
2^e`` (m uniform on 0..63, e 0 with chance 1/2, 1 with 1/4, 2 and 3 with
1/8 each), at most ``min(c_i, c_j)``; the diagonal is ``c_i``. So the
table is symmetric, integer and no entry of a row passes its diagonal.
``c_i c_j / N`` is taken as ``(u_i u_j) >> 6`` sixteenths, ``u_i =
rint(32 c_i / sqrt(N))`` (:func:`scales`), so every step fits 32 bits.
"""

from __future__ import annotations

import datetime as _dt
import functools
import math
import pickle

import jax
import jax.numpy as jnp
import numpy as np

ENGINE_ID = "chipbench-cpurchase"
#: rows of the table drawn by one step of the drawing loop: 1,024 rows of
#: 49,920 lanes are 204 MB beside the 9.9 GB table
ROW_BLOCK = 1024


def item_name(i: int) -> str:
    return f"p{i:05d}"


def seed_words(seed: int) -> tuple[int, int]:
    """The seed as two uint32 words (seeds pass 2**31; no int32 anywhere)."""
    return (seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF


def popularity(n_items: int, n_rows: int, exponent: float) -> np.ndarray:
    """int64 basket count of each item: ``n_rows`` order-product rows
    shared Zipf(``exponent``) over the items by rank, at least 1 each."""
    w = 1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** exponent
    return np.maximum(1, np.rint(n_rows * w / w.sum())).astype(np.int64)


def scales(counts: np.ndarray, n_baskets: int) -> np.ndarray:
    """uint32 ``rint(32 c / sqrt(N))``, at least 1, under 2^16."""
    u = np.maximum(1, np.rint(32.0 * counts / math.sqrt(n_baskets)))
    if u.max() >= 2**16:
        raise ValueError("an item's count is too large for 32-bit pair counts")
    return u.astype(np.uint32)


def _mix(x):
    """lowbias32 (C. Wellons): a 32-bit integer hash, uint32 in and out."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def pair_counts(words, counts, u, rows, cols, n_items: int):
    """f32 [len(rows), len(cols)] table entries; ``counts`` and ``u``
    uint32 [n_items]; columns past the items are 0. Only the hash needs
    which of i and j is the smaller: the product and the least count are
    symmetric, so each takes the row's and the column's values as they
    are (no gather of a value an entry)."""
    last = n_items - 1
    c_row, u_row = counts[rows][:, None], u[rows][:, None]
    c_col = counts[jnp.minimum(cols, last)][None, :]
    u_col = u[jnp.minimum(cols, last)][None, :]
    lo = jnp.minimum(rows[:, None], cols[None, :]).astype(jnp.uint32)
    hi = jnp.minimum(jnp.maximum(rows[:, None], cols[None, :]), last)
    h = _mix(_mix(_mix(lo ^ _mix(words[0])) + words[1]) ^ _mix(hi.astype(jnp.uint32)))
    m = h & jnp.uint32(63)
    b = (h >> 6) & jnp.uint32(7)
    e = jnp.where(b == 0, 3, jnp.where(b == 1, 2, jnp.where(b < 4, 1, 0)))
    t = (u_row * u_col) >> 6
    v = ((t * m) >> 10) << e.astype(jnp.uint32)
    v = jnp.minimum(v, jnp.minimum(c_row, c_col))
    v = jnp.where(rows[:, None] == cols[None, :], c_row, v)
    return jnp.where(cols[None, :] < n_items, v, 0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_items", "block"))
def draw_table(words, counts, u, n_items: int, block: int):
    """The table, f32 [n_panels, n_items, width], drawn ``block`` rows a
    step (the last step's rows overlap the one before, and write the same
    values again)."""
    from predictionio_tpu.ops.cooccurrence import layout

    n_panels, width = layout(n_items)
    block = min(block, n_items)

    def step(b, table):
        start = jnp.minimum(b * block, n_items - block)
        rows = start + jnp.arange(block, dtype=jnp.int32)
        for p in range(n_panels):
            cols = p * width + jnp.arange(width, dtype=jnp.int32)
            part = pair_counts(words, counts, u, rows, cols, n_items)
            table = jax.lax.dynamic_update_slice(table, part[None], (p, start, 0))
        return table

    table = jnp.zeros((n_panels, n_items, width), jnp.float32)
    return jax.lax.fori_loop(0, -(-n_items // block), step, table)


def device_table(seed: int, n_items: int, n_rows: int, n_baskets: int, exponent: float):
    counts = popularity(n_items, n_rows, exponent)
    if counts.max() > n_baskets:
        raise ValueError("an item is in more baskets than there are")
    return draw_table(
        jnp.asarray(seed_words(seed), jnp.uint32),
        jnp.asarray(counts, jnp.uint32), jnp.asarray(scales(counts, n_baskets)),
        n_items, ROW_BLOCK,
    )


def build_model(seed: int, n_items: int, n_rows: int, n_baskets: int, exponent: float):
    """What unpickling the blob returns: the `CPModel` a train would have
    published, its table already on the device."""
    from predictionio_tpu.models.complementarypurchase import CPModel
    from predictionio_tpu.utils.bimap import BiMap

    return CPModel(
        table=device_table(seed, n_items, n_rows, n_baskets, exponent),
        n_baskets=n_baskets,
        item_map=BiMap([item_name(i) for i in range(n_items)]),
    )


class _LazyModel:
    """Pickles to a call of :func:`build_model`."""

    def __init__(self, *args):
        self.args = args

    def __reduce__(self):
        return build_model, self.args


def engine_params(metric: str, min_support: int):
    from predictionio_tpu.core.engine import EngineParams
    from predictionio_tpu.models.complementarypurchase import (
        CPAlgoParams, CPDataSourceParams,
    )

    return EngineParams(
        data_source=("", CPDataSourceParams(app_name="chipbench")),
        preparator=("", None),
        algorithms=[("cooccurrence", CPAlgoParams(metric=metric, min_support=min_support))],
    )


def publish(seed: int, config: dict):
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
        engine_factory="complementarypurchase",
    ))
    blob = pickle.dumps({
        "version": _FORMAT_VERSION,
        "entries": [("auto", _LazyModel(
            seed, config["n_items"], config["n_order_rows"], config["n_baskets"],
            config["item_zipf_exponent"],
        ))],
    })
    storage.get_model_data_models().insert(Model(id=iid, models=blob))
    return storage
