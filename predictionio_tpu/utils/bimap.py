"""BiMap — immutable bidirectional string↔dense-index mapping.

Capability parity with the reference's ``data/.../storage/BiMap.scala:25-163``
(``BiMap.stringInt/stringLong``), the primitive every ALS template uses to
turn string entity ids into dense matrix row indices.

TPU-first difference: the reference builds the map with
``RDD[String].distinct.collect`` (BiMap.scala:116-135), which SURVEY.md §7
flags as unscalable. Here construction is vectorized host-side via
``np.unique(return_inverse=True)`` — one C-speed pass that yields both the
vocabulary and the dense codes, which is what actually gets shipped to the
device mesh.
"""

from __future__ import annotations

from collections.abc import Iterable, Sequence

import numpy as np


class BiMap:
    """Immutable bijection ``str -> int`` with O(1) inverse lookup.

    A map holds its keys, their sorted view (the order that sorts them,
    and the keys in that order) and a dictionary ``str(key) -> index``.
    A map built here has all three from birth: the view is what the
    uniqueness check's sort gives. It pickles as its keys and the order
    (:meth:`__reduce__`), and a restored map builds **no dictionary**
    until a per-key caller (:meth:`get`, :meth:`getter`,
    :meth:`__call__`, ``in``, :meth:`to_dict`) asks for one: a batch's
    lookups (:meth:`rows`, :meth:`encode`) search the view. Each
    dictionary built counts in ``pio_idmap_index_builds_total{origin}``.
    Two older forms still load: the key array alone (its dictionary built
    at restore) and the instance's whole ``__dict__``
    (:meth:`__setstate__`); either builds its view on the first
    :meth:`encode`.
    """

    def __init__(self, keys: Sequence[str] | np.ndarray):
        keys = np.asarray(keys)
        sorted_keys, order = np.unique(keys, return_index=True)
        if len(sorted_keys) != len(keys):
            raise ValueError("BiMap keys must be unique")
        self._keys = keys
        # of unique keys, the first place of each sorted key is its place
        self._sorted_view: tuple[np.ndarray, np.ndarray] | None = (
            order.astype(np.int32), sorted_keys
        )
        self._index: dict[str, int] | None = _index_of(keys, "built")

    def _dict(self) -> dict[str, int]:
        index = self._index
        if index is None:
            # threads that race here build equal dicts; either is kept
            index = self._index = _index_of(self._keys, "restored")
        return index

    def _view(self) -> tuple[np.ndarray, np.ndarray]:
        """(int32 order that sorts the keys, the keys in that order)."""
        view = self._sorted_view
        if view is None:
            # a map of an older form; threads that race build equal views
            order = np.argsort(self._keys).astype(np.int32)
            view = self._sorted_view = (order, self._keys[order])
        return view

    def __reduce__(self):
        return _from_keys, (self._keys, self._view()[0])

    def __setstate__(self, state: dict) -> None:
        """The form pickled before maps travelled as their keys: the
        instance's ``__dict__`` (``_keys``, ``_index``, ``_order``,
        ``_sorted_keys``). Its dict is kept; the sorted view is built again
        on the first :meth:`encode`."""
        self._keys = state["_keys"]
        self._index = state["_index"]
        self._sorted_view = None

    # -- construction -----------------------------------------------------
    @staticmethod
    def string_int(values: Iterable[str] | np.ndarray) -> "BiMap":
        """Distinct values → dense [0, n) codes (reference stringInt)."""
        arr = np.asarray(list(values) if not isinstance(values, np.ndarray) else values)
        uniq = np.unique(arr)
        return BiMap(uniq)

    @staticmethod
    def string_int_with_codes(
        values: np.ndarray,
    ) -> tuple["BiMap", np.ndarray]:
        """One-pass build + encode: returns (bimap, int32 codes)."""
        uniq, inverse = np.unique(values, return_inverse=True)
        return BiMap(uniq), inverse.astype(np.int32)

    # -- lookup -----------------------------------------------------------
    def __call__(self, key: str) -> int:
        return self._dict()[str(key)]

    def get(self, key: str, default: int | None = None) -> int | None:
        return self._dict().get(str(key), default)

    def getter(self):
        """``get(key, default)`` of the underlying dict itself, for loops
        that look up many ``str`` keys (no ``str()`` of the key, no Python
        frame a call)."""
        return self._dict().get

    def rows(self, ids: Iterable[object], missing: int = -1) -> np.ndarray:
        """int32 index of ``str(id)`` for each of ``ids`` (as :meth:`get`
        reads an id), ``missing`` where the map lacks it: a batch's
        lookups. By the dictionary where the map has one, else by binary
        search over the sorted view, so a restored map answers a batch
        without building its dictionary. A map of non-``str`` keys is
        sorted as its own type, not as the ``str`` of it: it always asks
        for the dictionary."""
        index = self._index
        if index is None and self._keys.dtype.kind != "U":
            index = self._dict()
        if index is not None:
            get = index.get
            return np.asarray([get(str(k), missing) for k in ids], np.int32)
        # searched at the keys' own width: a wider array would have numpy
        # copy the whole view to its width. An id longer than that is no key
        values = [str(k) for k in ids]
        width = self._keys.dtype.itemsize // 4  # 4 B a character
        longer = [i for i, v in enumerate(values) if len(v) > width]
        for i in longer:
            values[i] = ""
        found = self.encode(np.asarray(values, self._keys.dtype), missing)
        found[longer] = missing
        return found

    def inverse(self, idx: int) -> str:
        return str(self._keys[idx])

    def encode(self, values: np.ndarray, missing: int = -1) -> np.ndarray:
        """Vectorized str→int; unknown keys map to ``missing``."""
        arr = np.asarray(values)
        if len(self._keys) == 0:
            return np.full(arr.shape, missing, dtype=np.int32)
        order, sorted_keys = self._view()
        pos = np.searchsorted(sorted_keys, arr)
        pos = np.clip(pos, 0, len(sorted_keys) - 1)
        ok = sorted_keys[pos] == arr
        return np.where(ok, order[pos], missing).astype(np.int32)

    def decode(self, codes: np.ndarray) -> np.ndarray:
        return self._keys[np.asarray(codes)]

    def keys(self) -> np.ndarray:
        return self._keys

    def __len__(self) -> int:
        return len(self._keys)

    def __contains__(self, key: object) -> bool:
        return str(key) in self._dict()

    def to_dict(self) -> dict[str, int]:
        return dict(self._dict())


def _index_of(keys: np.ndarray, origin: str) -> dict[str, int]:
    """``str(key) -> index`` over ``keys`` (unique) in one pass, counted
    by ``origin``: ``built`` for a map made from its keys, ``restored``
    for one that came out of a pickle."""
    from predictionio_tpu.obs import tracing

    tracing.bound_registry().counter(
        "pio_idmap_index_builds_total",
        "Id-map dictionaries built: origin=built by a map made from its "
        "keys, origin=restored by an unpickled map a per-key lookup asked",
        ("origin",),
    ).labels(origin).inc()
    if keys.dtype.kind == "U":
        # tolist() gives the very strs str() would, at C speed
        return dict(zip(keys.tolist(), range(len(keys))))
    return {str(k): i for i, k in enumerate(keys)}


def _from_keys(keys: np.ndarray, order: np.ndarray | None = None) -> BiMap:
    """A pickled map's restore (named in every published model blob, so
    never renamed): the keys were unique when the map was built, so the
    check is not run again. With ``order`` (the keys' int32 argsort) the
    sorted view is one gather and no dictionary is built; without it (the
    form that shipped the keys alone) the dictionary is built here."""
    m = BiMap.__new__(BiMap)
    m._keys = keys
    if order is None:
        m._sorted_view = None
        m._index = _index_of(keys, "restored")
    else:
        m._sorted_view = (order, keys[order])
        m._index = None
    return m


class EntityMap:
    """String entity id ↔ dense index ↔ payload.

    Capability parity with the reference's experimental
    ``data/.../storage/EntityMap.scala`` (``EntityIdIxMap`` +
    ``EntityMap[A]``): a :class:`BiMap` over the entity ids plus a data
    payload per entity, so engines can move between the string-id world
    (events, queries) and the dense-index world (device arrays) without
    bookkeeping.
    """

    def __init__(self, id_to_data: dict[str, object]):
        self._data = dict(id_to_data)
        self.id_to_ix = BiMap(np.asarray(sorted(self._data)))

    # -- EntityIdIxMap surface --------------------------------------------
    def index(self, entity_id: str) -> int:
        return self.id_to_ix(entity_id)

    def entity_id(self, ix: int) -> str:
        return self.id_to_ix.inverse(ix)

    def get(self, entity_id: str, default: int | None = None) -> int | None:
        return self.id_to_ix.get(entity_id, default)

    def __contains__(self, entity_id: object) -> bool:
        return entity_id in self.id_to_ix

    def __len__(self) -> int:
        return len(self._data)

    # -- EntityMap[A] surface ---------------------------------------------
    def data(self, key: str | int) -> object:
        """Payload by entity id (str) or dense index (int)."""
        if isinstance(key, (int, np.integer)):
            key = self.entity_id(int(key))
        return self._data[str(key)]

    def get_data(self, entity_id: str) -> object | None:
        return self._data.get(str(entity_id))

    def take(self, n: int) -> "EntityMap":
        keep = [self.entity_id(i) for i in range(min(n, len(self)))]
        return EntityMap({k: self._data[k] for k in keep})

    def to_dict(self) -> dict[str, object]:
        return dict(self._data)
