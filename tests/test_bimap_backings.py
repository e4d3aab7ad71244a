"""``BiMap``'s two backings answer alike: a map built from an id table in
row order (the codec's blob and offsets, as the event store's read hands
them over) against one built from a mapping of the same ids. The
array-backed map makes a string when asked for that id, builds its forward
dict once, at the first forward lookup, builds no inverse dict at all, and
persists as two buffers; the dict form of older artifacts still loads."""

import pickle
import threading
import time

import numpy as np
import pytest

from incubator_predictionio_tpu.data.storage.bimap import (
    BiMap,
    IdentityBiMap,
    extend_bimap,
)
from incubator_predictionio_tpu.native import IdTable

IDS = ["u3", "üser-✓", "a", "商品7", "u10", "Łódź", "B00004TKVY", ""]


def table_of(ids) -> IdTable:
    raw = [s.encode("utf-8") for s in ids]
    offs = np.zeros(len(raw) + 1, np.int64)
    np.cumsum([len(b) for b in raw], out=offs[1:])
    return IdTable(b"".join(raw), offs)


@pytest.fixture()
def maps():
    """(array-backed, mapping-backed) over the same ids."""
    arrays = BiMap(table_of(IDS))
    mapping = BiMap({s: k for k, s in enumerate(IDS)})
    assert arrays._table is not None and arrays._fwd is None
    assert mapping._table is None
    return arrays, mapping


def raised(fn, *args):
    try:
        return ("returned", fn(*args))
    except Exception as e:  # noqa: BLE001 - the kind is what is compared
        return ("raised", type(e))


def _len(a, m):
    assert len(a) == len(m) == len(IDS)
    assert a._fwd is None
    assert len(BiMap(table_of([]))) == len(BiMap({})) == 0


def _get(a, m):
    for key in IDS + ["nobody", 4, None]:
        assert a.get(key) == m.get(key)
        assert a.get(key, -7) == m.get(key, -7)


def _call_and_its_key_error(a, m):
    for key in IDS:
        assert a(key) == m(key)
    for key in ("nobody", 4):
        assert raised(a, key) == raised(m, key) == ("raised", KeyError)


def _contains(a, m):
    for key in IDS + ["nobody", 4]:
        assert (key in a) == (key in m) == a.contains(key) == m.contains(key)


def _inverse_and_its_key_error(a, m):
    for v in list(range(len(IDS))) + [np.int32(3), np.int64(5)]:
        assert a.inverse(v) == m.inverse(v)
    for v in (-1, len(IDS), 10**12, "3", None, 2.5):
        assert raised(a.inverse, v) == raised(m.inverse, v) == (
            "raised", KeyError)


def _inverse_get(a, m):
    for v in list(range(len(IDS))) + [-1, len(IDS), "3", None]:
        assert a.inverse_get(v) == m.inverse_get(v)
        assert a.inverse_get(v, "dflt") == m.inverse_get(v, "dflt")


def _inverse_array(a, m):
    for values in ([], [0], [5, 1, 1, 7], np.asarray([2, 6], np.int32),
                   np.arange(len(IDS))):
        assert a.inverse_array(values) == m.inverse_array(values)
    assert raised(a.inverse_array, [0, 99]) == raised(
        m.inverse_array, [0, 99]) == ("raised", KeyError)
    assert a._fwd is None


def _map_array(a, m):
    keys = ["a", "商品7", "a", ""]
    got, want = a.map_array(keys), m.map_array(keys)
    assert got.dtype == want.dtype == np.int32 and (got == want).all()
    assert raised(a.map_array, ["nobody"]) == raised(
        m.map_array, ["nobody"]) == ("raised", KeyError)


def _keys_iterated_twice(a, m):
    keys = a.keys()
    assert list(keys) == list(keys) == list(m.keys()) == IDS
    assert len(keys) == len(m.keys())
    assert ("Łódź" in keys, "nobody" in keys) == (True, False)
    assert next(iter(BiMap(table_of(IDS)).keys())) == "u3"
    # and after the forward dict is there
    a.get("a")
    assert list(a.keys()) == IDS


def _to_dict(a, m):
    assert a.to_dict() == m.to_dict()
    assert list(a.to_dict()) == IDS
    d = a.to_dict()
    d["new"] = 99          # a copy: the map does not see it
    assert "new" not in a


def _extend_bimap(a, m):
    new = ["fresh", "a", "fresh", "商品8"]
    (ea, added_a), (em, added_m) = extend_bimap(a, new), extend_bimap(m, new)
    assert added_a == added_m == ["fresh", "商品8"]
    assert ea.to_dict() == em.to_dict()
    assert ea.inverse(len(IDS)) == "fresh" and len(a) == len(IDS)
    assert extend_bimap(a, ["a"]) == (a, [])


def _pickle_of_the_object(a, m):
    a.get("a")             # a built dict is not what gets written
    blob = pickle.dumps(a, protocol=pickle.HIGHEST_PROTOCOL)
    back = pickle.loads(blob)
    assert back._table is not None and back._fwd is None
    assert back.to_dict() == pickle.loads(pickle.dumps(m)).to_dict()
    assert back.inverse(3) == "商品7"


def _persisted_round_trip_arrays(a, m):
    form = a.to_persisted()
    assert list(form) == ["__id_table__"] and a._fwd is None
    blob, offs = form["__id_table__"]
    assert isinstance(blob, bytes) and offs.dtype == np.int64
    back = BiMap.from_persisted(pickle.loads(pickle.dumps(
        form, protocol=pickle.HIGHEST_PROTOCOL)))
    assert back._table is not None and back._fwd is None
    assert back.to_dict() == m.to_dict()
    assert [back.inverse(k) for k in range(len(back))] == IDS


def _persisted_round_trip_mapping(a, m):
    form = m.to_persisted()
    assert form == {s: k for k, s in enumerate(IDS)}
    back = BiMap.from_persisted(pickle.loads(pickle.dumps(form)))
    assert back._table is None and back.to_dict() == a.to_dict()
    assert BiMap.from_persisted(m) is m and BiMap.from_persisted(a) is a
    ident = BiMap.from_persisted(IdentityBiMap(5).to_persisted())
    assert isinstance(ident, IdentityBiMap) and len(ident) == 5


def _a_dict_form_artifact_of_today_loads(a, m):
    # what every artifact before the second backing holds: the forward
    # dict itself, or a pickled map whose state has no ``_table``
    back = BiMap.from_persisted({s: k for k, s in enumerate(IDS)})
    assert back._table is None and back.inverse(1) == "üser-✓"
    old = BiMap.__new__(BiMap)
    old.__dict__.update(_fwd={"x": 0, "y": 1}, _inv={0: "x", 1: "y"})
    old = pickle.loads(pickle.dumps(old))
    assert (len(old), old.get("y"), old.inverse(0)) == (2, 1, "x")
    assert list(old.keys()) == ["x", "y"] and old.to_persisted() == {
        "x": 0, "y": 1}
    # one id that spells the marker is a map of one id, not a table
    assert BiMap.from_persisted({"__id_table__": 0}).get("__id_table__") == 0


def _two_threads_racing_the_first_get_build_one_dict(a, m, monkeypatch):
    built = []
    tolist = IdTable.tolist

    def slow_tolist(self):
        built.append(threading.get_ident())
        time.sleep(0.05)
        return tolist(self)

    monkeypatch.setattr(IdTable, "tolist", slow_tolist)
    got, seen = [], []
    go = threading.Barrier(2)

    def first_get():
        go.wait()
        got.append(a.get("商品7"))
        seen.append(a._fwd)

    threads = [threading.Thread(target=first_get) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == [3, 3] and len(built) == 1
    assert seen[0] is seen[1] is a._fwd


def _inverse_builds_no_dict(a, m):
    for k in range(len(IDS)):
        a.inverse(k), a.inverse_get(k)
    a.inverse_array(range(len(IDS))), len(a), next(iter(a.keys()))
    a.to_persisted()
    assert a._fwd is None and not hasattr(a, "_inv")
    a.get("a")
    assert a._fwd is not None and not hasattr(a, "_inv")
    assert a.inverse(2) == "a"


_CASES = {
    "len": _len,
    "get": _get,
    "call-and-its-key-error": _call_and_its_key_error,
    "contains": _contains,
    "inverse-and-its-key-error": _inverse_and_its_key_error,
    "inverse-get": _inverse_get,
    "inverse-array": _inverse_array,
    "map-array": _map_array,
    "keys-iterated-twice": _keys_iterated_twice,
    "to-dict": _to_dict,
    "extend-bimap": _extend_bimap,
    "pickle-of-the-object": _pickle_of_the_object,
    "persisted-round-trip-arrays": _persisted_round_trip_arrays,
    "persisted-round-trip-mapping": _persisted_round_trip_mapping,
    "a-dict-form-artifact-of-today-loads":
        _a_dict_form_artifact_of_today_loads,
    "two-threads-racing-the-first-get-build-one-dict":
        _two_threads_racing_the_first_get_build_one_dict,
    "inverse-builds-no-dict": _inverse_builds_no_dict,
}


@pytest.mark.parametrize("case", list(_CASES.values()), ids=list(_CASES))
def test_the_two_backings_answer_alike(case, maps, monkeypatch):
    if case is _two_threads_racing_the_first_get_build_one_dict:
        case(*maps, monkeypatch)
    else:
        case(*maps)
