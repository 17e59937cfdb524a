"""What the templates that serve through
:func:`predictionio_tpu.ops.similarity.rules_top_k` have in common
(e-commerce, similar-product): what `stage_model` keeps on the device
beside the factors, the steps of a launch and a collect that know item
ids and the response's shape, and one set of counters a registry.
``ops/similarity.py`` below it knows arrays only."""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from predictionio_tpu.ops import similarity

#: the item rows of a query that names none
NO_ROWS = np.empty(0, np.int32)
NO_ROWS.setflags(write=False)


@dataclasses.dataclass
class StagedRules:
    """What `stage_model` keeps on the device beside the factors, one
    entry per row of the (padded) item table; charged to the tenant by
    ``quantize.model_resident_bytes`` through its array fields."""

    categories: jax.Array     # [C, rows] int32 category ids, -1 = none
    unavailable: jax.Array    # [rows] bool: phantom rows (+ the constraint)
    inv_norm: jax.Array       # [rows] f32, 0 for a zero row
    popularity: "jax.Array | None"  # [rows] f32; None: no POPULAR branch
    category_ids: dict        # category name -> id
    #: e-commerce: version and event id of the ``$set`` `unavailable` was
    #: built from
    constraint: tuple = (None, None)
    #: e-commerce: user -> (the store's version of the user, seen item rows)
    seen: dict = dataclasses.field(default_factory=dict)
    #: similar-product: a byte an item row on the host, 1 where the row is
    #: zero (the item has no event of the algorithm's kind)
    zero_rows: bytes = b""

    @property
    def catalog(self) -> similarity.CatalogRules:
        return similarity.CatalogRules(
            self.categories, self.unavailable, self.inv_norm,
            self.popularity,
        )


def padded_rows(ctx, n_items: int) -> int:
    """Rows of a staged item table: whole blocks for the fused kernel
    (``similarity.CATALOG_ROW_MULTIPLE``) and whole shards of the mesh."""
    multiple = np.lcm(
        similarity.CATALOG_ROW_MULTIPLE, max(ctx.model_parallelism, 1)
    )
    return -(-n_items // multiple) * multiple


def stage(model, item_f, sharding=None, popularity=None) -> StagedRules:
    """One entry per row of ``item_f`` (the padded item table): category
    ids, 1/norm, the phantom rows marked unavailable, and ``popularity``
    ([I] interaction counts) for a template with a POPULAR branch."""
    rows, n_items = item_f.shape[0], len(model.item_map)
    ids, categories = encode_categories(model)
    put = lambda x: jax.device_put(x, sharding)  # noqa: E731
    if popularity is not None:
        popularity = put(
            similarity.pad_rows(popularity, rows).astype(np.float32)
        )
    return StagedRules(
        categories=put(similarity.pad_rows(categories.T, rows, -1).T),
        unavailable=put(np.arange(rows) >= n_items),
        inv_norm=put(similarity.inverse_norms(item_f)),
        popularity=popularity,
        category_ids=ids,
    )


def encode_categories(model):
    """``(name -> id, [C, I] int32 host or device array, -1 = none)`` of a
    model with an ``item_map`` and either ``item_categories`` (item id ->
    names) or the same already encoded (``category_names``,
    ``category_rows``)."""
    if model.category_rows is not None:
        names = model.category_names or ()
        return {n: i for i, n in enumerate(names)}, model.category_rows
    ids: dict[str, int] = {}
    per_item = []
    for item, cats in model.item_categories.items():
        row = model.item_map.get(item, -1)
        if row >= 0 and cats:
            per_item.append(
                (row, [ids.setdefault(c, len(ids)) for c in cats])
            )
    width = max((len(c) for _, c in per_item), default=1)
    rows = np.full((width, len(model.item_map)), -1, np.int32)
    for row, cats in per_item:
        rows[: len(cats), row] = cats
    return ids, rows


def listed_rows(get, black, white, seen: np.ndarray) -> np.ndarray:
    """The item rows of one query's list: what to leave out (``seen`` and
    the blackList), or with a whiteList what alone may come back (white -
    black - seen). ``get`` maps an item id to its row; an id the model
    does not know is dropped."""
    out = [get(str(x), -1) for x in black]
    if not white:
        return np.concatenate(
            [seen, np.array([r for r in out if r >= 0], np.int32)]
        )
    rows = {get(str(x), -1) for x in white}
    rows.difference_update(out, seen.tolist(), (-1,))
    return np.fromiter(rows, np.int32, len(rows))


def served_lists(scores, items, nums, inverse) -> list[dict]:
    """The step's host outputs as one prediction a query: the slots that
    hold a candidate (score above -inf), at most the query's ``num`` of
    them, never padded. ``inverse`` maps an item row to its id."""
    filled = (scores > -np.inf).sum(axis=1)
    return [
        {
            "itemScores": [
                {
                    "item": inverse(int(items[i, j])),
                    "score": float(scores[i, j]),
                }
                for j in range(min(num, int(filled[i])))
            ]
        }
        for i, num in enumerate(nums)
    ]


class RegistryCounters:
    """A template's counters, made once a registry: a subclass registers
    its own in ``__init__(self, registry)``."""

    _by_registry: dict

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        cls._by_registry = {}

    @classmethod
    def of(cls, registry):
        found = cls._by_registry.get(id(registry))
        if found is None or found[0] is not registry:
            found = (registry, cls(registry))
            cls._by_registry[id(registry)] = found
        return found[1]
