"""A BiMap travels as its key array and the order that sorts it: pickling
writes the two, loading gathers the sorted view from them and builds no
dictionary until a per-key lookup asks for one, and a batch's lookups
(`rows`, `encode`) search the view. Maps pickled in the two older forms
(the key array alone; the instance's whole ``__dict__``) still load."""

import contextlib
import copyreg
import pickle
import pickletools
import sys
import threading

import numpy as np
import pytest

from predictionio_tpu.obs import tracing
from predictionio_tpu.obs.registry import get_registry
from predictionio_tpu.utils import bimap as bimap_module
from predictionio_tpu.utils.bimap import BiMap, EntityMap

#: ``pickle.dumps(BiMap(["b", "a", "c"]), protocol=5)`` as the parent of
#: PR 38 wrote it: ``_keys``, ``_index``, ``_order`` and ``_sorted_keys``
PARENT_PICKLE = (
    b"\x80\x05\x95b\x01\x00\x00\x00\x00\x00\x00\x8c\x1cpredictionio_tpu.utils"
    b".bimap\x94\x8c\x05BiMap\x94\x93\x94)\x81\x94}\x94(\x8c\x05_keys\x94\x8c"
    b"\x13numpy._core.numeric\x94\x8c\x0b_frombuffer\x94\x93\x94(\x96\x0c\x00"
    b"\x00\x00\x00\x00\x00\x00b\x00\x00\x00a\x00\x00\x00c\x00\x00\x00\x94\x8c"
    b"\x05numpy\x94\x8c\x05dtype\x94\x93\x94\x8c\x02U1\x94\x89\x88\x87\x94R"
    b"\x94(K\x03\x8c\x01<\x94NNNK\x04K\x04K\x08t\x94bK\x03\x85\x94\x8c\x01C"
    b"\x94t\x94R\x94\x8c\x06_index\x94}\x94(\x8c\x01b\x94K\x00\x8c\x01a\x94K"
    b"\x01\x8c\x01c\x94K\x02u\x8c\x06_order\x94h\x08(\x96\x18\x00\x00\x00\x00"
    b"\x00\x00\x00\x01\x00\x00\x00\x00\x00\x00\x00\x00\x00\x00\x00\x00\x00\x00"
    b"\x00\x02\x00\x00\x00\x00\x00\x00\x00\x94h\x0c\x8c\x02i8\x94\x89\x88\x87"
    b"\x94R\x94(K\x03h\x10NNNJ\xff\xff\xff\xffJ\xff\xff\xff\xffK\x00t\x94bK"
    b"\x03\x85\x94h\x13t\x94R\x94\x8c\x0c_sorted_keys\x94h\x08(\x96\x0c\x00"
    b"\x00\x00\x00\x00\x00\x00a\x00\x00\x00b\x00\x00\x00c\x00\x00\x00\x94h"
    b"\x0fK\x03\x85\x94h\x13t\x94R\x94ub."
)

#: the same map in the key-array form: ``_from_keys`` over the keys alone
KEYS_PICKLE = (
    b"\x80\x05\x95\xac\x00\x00\x00\x00\x00\x00\x00\x8c\x1cpredictionio_tpu.utils"
    b".bimap\x94\x8c\n_from_keys\x94\x93\x94\x8c\x13numpy._core.numeric\x94\x8c"
    b"\x0b_frombuffer\x94\x93\x94(\x96\x0c\x00\x00\x00\x00\x00\x00\x00b\x00\x00"
    b"\x00a\x00\x00\x00c\x00\x00\x00\x94\x8c\x05numpy\x94\x8c\x05dtype\x94\x93"
    b"\x94\x8c\x02U1\x94\x89\x88\x87\x94R\x94(K\x03\x8c\x01<\x94NNNK\x04K\x04K"
    b"\x08t\x94bK\x03\x85\x94\x8c\x01C\x94t\x94R\x94\x85\x94R\x94."
)


def _parent_reduce(m: BiMap):
    """How the parent of PR 38 pickled a map: object's own reduce over the
    instance's four fields."""
    order = np.argsort(m._keys)
    return copyreg.__newobj__, (BiMap,), {
        "_keys": m._keys,
        "_index": {str(k): i for i, k in enumerate(m._keys)},
        "_order": order,
        "_sorted_keys": m._keys[order],
    }


def _keys_reduce(m: BiMap):
    """The key-array form of a map: ``_from_keys`` over the keys alone."""
    return bimap_module._from_keys, (m._keys,)


@contextlib.contextmanager
def parent_form():
    """Inside it, every BiMap pickles as the parent of PR 38 pickled it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(BiMap, "__reduce__", _parent_reduce)
        yield


@contextlib.contextmanager
def keys_form():
    """Inside it, every BiMap pickles in the key-array form."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(BiMap, "__reduce__", _keys_reduce)
        yield


#: how a map reaches its reader: built here, or unpickled from one of the
#: three forms a blob may hold
FORMS = {
    "fresh": contextlib.nullcontext,
    "restored": contextlib.nullcontext,
    "keys": keys_form,
    "parent": parent_form,
}


def in_form(m: BiMap, form: str) -> BiMap:
    if form == "fresh":
        return m
    with FORMS[form]():
        blob = pickle.dumps(m, protocol=pickle.HIGHEST_PROTOCOL)
    return pickle.loads(blob)


@pytest.fixture(autouse=True)
def unbound():
    """No server's sink bound on the test's thread: its maps count in the
    process's registry, as its helper threads' do."""
    token = tracing._bound_stages.set(None)
    yield
    tracing._bound_stages.reset(token)


def builds(origin: str) -> float:
    """``pio_idmap_index_builds_total{origin}`` of the process's registry
    (where a map built outside any server counts)."""
    family = get_registry().to_dict().get(
        "pio_idmap_index_builds_total", {"samples": []}
    )
    return sum(
        s["value"] for s in family["samples"] if s["labels"]["origin"] == origin
    )


#: (keys, ids no map holds) a case
CASES = {
    "str": (
        ["u3", "u1", "u10", "u2", "t0.i7"], ["u4", "zz", "", "u", "u3-and-more"]
    ),
    "int": (np.array([30, 10, 20, -5]), [99, 0]),
    # non-str ids that name a str key: an int user in a query's JSON
    "digits": (["12", "7", "007", "u1"], [3, 70, "x", 0.5]),
    "one": (["only"], ["other"]),
    # "" is a key, and an id longer than every key is none of them
    "blank": (["", "a"], ["an-id-longer-than-every-key", "b"]),
    "empty": ([], ["x", 1]),
}


def _probe(a: BiMap, unknown) -> list:
    """Every key of ``a``, as it is and as ``str``, its ints where a key
    is digits, and the unknown ids."""
    probe = list(a.keys().tolist()) + [str(k) for k in a.keys().tolist()]
    probe += [int(k) for k in probe if isinstance(k, str) and k.isdigit()]
    return probe + list(unknown)


def _assert_same(a: BiMap, b: BiMap, unknown) -> None:
    """``b`` answers every lookup as ``a`` does. The batch lookups run
    first, so a restored map answers them from its sorted view."""
    keys = a.keys()
    probe = _probe(a, unknown)
    assert len(b) == len(a)
    assert b.keys().dtype == keys.dtype
    np.testing.assert_array_equal(b.keys(), keys)
    want = [a.get(k, -1) for k in probe]
    for missing in (-1, -3):
        got = b.rows(probe, missing=missing)
        assert got.dtype == np.int32
        assert got.tolist() == [missing if w == -1 else w for w in want]
    assert b.rows([]).tolist() == []
    for values in (keys, np.asarray(unknown), keys[::-1]):
        np.testing.assert_array_equal(b.encode(values), a.encode(values))
        np.testing.assert_array_equal(
            b.encode(values, missing=-3), a.encode(values, missing=-3)
        )
    codes = np.arange(len(a))[::-1]
    np.testing.assert_array_equal(b.decode(codes), a.decode(codes))
    for i in range(len(a)):
        assert b.inverse(i) == a.inverse(i)
    # the per-key lookups
    assert b.to_dict() == a.to_dict()
    for k in probe:
        assert b.get(k) == a.get(k)
        assert b.get(k, -7) == a.get(k, -7)
        assert (k in b) == (k in a)
    for i in range(len(a)):
        assert b(a.inverse(i)) == i
    with pytest.raises(KeyError):
        b(unknown[0])
    getter = b.getter()
    assert [getter(str(k)) for k in probe] == [a.get(k) for k in probe]


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_every_form_answers_as_a_fresh_map(case, form):
    keys, unknown = CASES[case]
    _assert_same(BiMap(keys), in_form(BiMap(keys), form), unknown)


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_map_round_trips_through_pickle(case):
    keys, unknown = CASES[case]
    m = BiMap(keys)
    back = pickle.loads(pickle.dumps(m, protocol=pickle.HIGHEST_PROTOCOL))
    assert type(back) is BiMap
    _assert_same(m, back, unknown)
    # and through the oldest protocol a pickle may be asked for
    _assert_same(m, pickle.loads(pickle.dumps(m, protocol=2)), unknown)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_pickled_state_is_the_key_array_alone(case):
    """The keys and the int32 order that sorts them; no dictionary."""
    m = BiMap(CASES[case][0])
    restore, args = m.__reduce__()
    assert restore is bimap_module._from_keys
    assert len(args) == 2 and args[0] is m.keys()
    order = args[1]
    assert order.dtype == np.int32 and len(order) == len(m)
    assert sorted(order.tolist()) == list(range(len(m)))
    assert m.keys()[order].tolist() == sorted(m.keys().tolist())
    ops = {op.name for op, _, _ in pickletools.genops(pickle.dumps(m, 5))}
    # no dictionary: the two arrays' reconstruct and the call to restore
    assert not ops & {"EMPTY_DICT", "DICT", "SETITEM", "SETITEMS"}


def test_a_map_s_bytes_are_its_keys_and_a_few_hundred_of_framing():
    """Its keys, 4 B a key of order, and a few hundred bytes around them."""
    m = BiMap([f"u{i}" for i in range(1000)])
    blob = pickle.dumps(m, protocol=pickle.HIGHEST_PROTOCOL)
    payload = m.keys().nbytes + 4 * len(m)
    assert payload <= len(blob) <= payload + 300
    with keys_form():
        keys_only = pickle.dumps(m, protocol=pickle.HIGHEST_PROTOCOL)
    assert len(blob) - len(keys_only) <= 4 * len(m) + 300
    with parent_form():
        parent = pickle.dumps(m, protocol=pickle.HIGHEST_PROTOCOL)
    # the parent's carried the index, the order and the keys twice
    assert len(parent) > 2 * m.keys().nbytes + 8 * len(m)


@pytest.mark.parametrize("form", ["fresh", "restored"])
def test_a_map_carries_its_sorted_view_from_birth(form, monkeypatch):
    """A map built here takes its view from the uniqueness check's sort,
    and a restored one gathers it: neither ever argsorts."""
    keys = [f"k{i}" for i in range(50)][::-1]
    m = in_form(BiMap(keys), form)
    order, sorted_keys = m._sorted_view
    assert order.dtype == np.int32
    assert sorted_keys.tolist() == sorted(keys)

    def refused(*args, **kwargs):
        raise AssertionError("no argsort")

    monkeypatch.setattr(np, "argsort", refused)
    np.testing.assert_array_equal(
        m.encode(np.array(["k3", "k9", "zz"])), [46, 40, -1]
    )
    assert m.rows(["k3", "zz"]).tolist() == [46, -1]
    assert m._sorted_view[0] is order
    pickle.dumps(m, 5)  # ships the order it holds


@pytest.mark.parametrize("restored", [False, True])
def test_no_sorted_view_is_built_until_encode(restored):
    """A map of an older form (``restored``: the key array alone; else
    the whole ``__dict__``) builds its view on the first `encode`."""
    m = in_form(BiMap(["c", "a", "b"]), "keys" if restored else "parent")
    assert m._sorted_view is None
    m.get("a"), m.getter()("b"), m.inverse(1), "c" in m, len(m), m.to_dict()
    m.decode(np.array([0, 2]))
    assert m.rows(["b", "z"]).tolist() == [2, -1]  # by its dictionary
    assert m._sorted_view is None
    np.testing.assert_array_equal(m.encode(np.array(["b", "z"])), [2, -1])
    view = m._sorted_view
    assert view is not None
    m.encode(np.array(["a"]))
    assert m._sorted_view is view  # cached on the instance
    # and it travels on in the new form
    assert pickle.dumps(m, 5) == pickle.dumps(BiMap(["c", "a", "b"]), 5)


@pytest.mark.parametrize(
    "call", ["get", "getter", "call", "contains", "to_dict"]
)
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_restored_map_builds_its_dictionary_at_the_first_per_key_call(
    case, call
):
    keys, _ = CASES[case]
    m = BiMap(keys)
    restored0 = builds("restored")
    back = in_form(m, "restored")
    assert back._index is None
    # what needs no dictionary
    probe = list(m.keys().tolist()) + ["nope"]
    len(back), back.keys(), back.decode(np.arange(len(m)))
    [back.inverse(i) for i in range(len(m))]
    back.encode(np.asarray(m.keys()))
    back.rows(probe)
    if back.keys().dtype.kind == "U":
        assert back._index is None
        assert builds("restored") == restored0
    else:
        # keys sorted as ints are no view of their strs: rows() asks
        assert builds("restored") == restored0 + 1
        back._index = None
        restored0 += 1
    first = str(probe[0])
    ask = {
        "get": lambda: back.get(first),
        "getter": lambda: back.getter()(first),
        "call": lambda: back(first) if len(m) else back.get(first),
        "contains": lambda: first in back,
        "to_dict": back.to_dict,
    }[call]
    ask()
    assert back._index is not None
    assert builds("restored") == restored0 + 1
    # kept: every per-key call after it uses the same one
    back.get(first), back.getter(), first in back, back.to_dict()
    back.rows(probe)
    assert builds("restored") == restored0 + 1
    assert back.to_dict() == m.to_dict()


def test_each_form_counts_the_dictionaries_it_builds():
    """One `built` a map made here; one `restored` a map of the key-array
    form, at its restore; none for the parent's form (its dict was
    pickled) or the new form until a per-key call."""
    before = {o: builds(o) for o in ("built", "restored")}
    m = BiMap(["b", "a", "c"])
    assert builds("built") == before["built"] + 1
    loaded = {form: in_form(m, form) for form in ("restored", "keys", "parent")}
    assert builds("built") == before["built"] + 1
    assert builds("restored") == before["restored"] + 1  # the keys form's
    assert loaded["restored"]._index is None
    assert loaded["keys"]._index is not None
    assert loaded["parent"]._index is not None


def test_threads_encoding_a_just_restored_map_agree():
    m = BiMap([f"k{i}" for i in range(5000)][::-1])
    values = np.array([f"k{i}" for i in range(0, 6000, 7)])
    want = m.encode(values)
    for _ in range(5):
        back = pickle.loads(pickle.dumps(m, 5))
        start = threading.Barrier(4)
        got = [None] * 4

        def run(i):
            start.wait()
            got[i] = back.encode(values)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for g in got:
            np.testing.assert_array_equal(g, want)
        np.testing.assert_array_equal(back.encode(values), want)


@pytest.mark.parametrize("form", ["restored", "keys"])
def test_threads_looking_up_a_just_restored_map_agree(form):
    """Racing per-key and batch callers on a just-restored map: every
    answer is the fresh map's; the racers' dictionaries are equal, one of
    them kept, at most one built a thread."""
    m = BiMap([f"k{i}" for i in range(5000)][::-1])
    ids = [f"k{i}" for i in range(0, 6000, 7)]
    want = [m.get(k, -1) for k in ids]
    n = 8
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(5):
            back = in_form(m, form)
            restored0 = builds("restored")
            start = threading.Barrier(n)
            got = [None] * n

            def run(i):
                start.wait(timeout=10)
                if i % 2:
                    got[i] = back.rows(ids).tolist()
                else:
                    get = back.getter()
                    got[i] = [get(k, -1) for k in ids]

            threads = [
                threading.Thread(target=run, args=(i,)) for i in range(n)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
                assert not t.is_alive()
            assert got == [want] * n
            assert back.to_dict() == m.to_dict()
            assert builds("restored") - restored0 <= n // 2
    finally:
        sys.setswitchinterval(interval)


def test_the_parent_s_pickle_loads_into_an_equal_map():
    m = BiMap(["b", "a", "c"])
    # the helper writes the parent's very bytes
    with parent_form():
        assert pickle.dumps(m, protocol=5) == PARENT_PICKLE
    back = pickle.loads(PARENT_PICKLE)
    assert back._sorted_view is None  # the parent's two fields are dropped
    _assert_same(m, back, ["d"])
    # and travels on in the new form
    assert pickle.dumps(back, 5) == pickle.dumps(m, 5)


def test_the_key_array_form_s_pickle_loads_into_an_equal_map():
    m = BiMap(["b", "a", "c"])
    # the helper writes that form's very bytes
    with keys_form():
        assert pickle.dumps(m, protocol=5) == KEYS_PICKLE
    restored0 = builds("restored")
    back = pickle.loads(KEYS_PICKLE)
    # its dictionary built at restore, as that form's restore built it
    assert back._index == {"b": 0, "a": 1, "c": 2}
    assert builds("restored") == restored0 + 1
    assert back._sorted_view is None
    _assert_same(m, back, ["d", 1])
    assert pickle.dumps(back, 5) == pickle.dumps(m, 5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_case_pickled_in_the_parent_s_form_loads_equal(case):
    keys, unknown = CASES[case]
    m = BiMap(keys)
    with parent_form():
        blob = pickle.dumps(m, protocol=pickle.HIGHEST_PROTOCOL)
    _assert_same(m, pickle.loads(blob), unknown)


def test_an_entity_map_round_trips_with_its_map():
    e = EntityMap({"b": 2.0, "a": 1.0})
    back = pickle.loads(pickle.dumps(e, 5))
    assert back.to_dict() == e.to_dict()
    assert [back.index(k) for k in "ab"] == [e.index(k) for k in "ab"]
    assert back.data(1) == e.data(1) == 2.0
