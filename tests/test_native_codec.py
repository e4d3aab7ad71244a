"""Native event codec + JSONL backend fast path.

The C++ parser (native/src/event_codec.cc) must agree bit-for-bit with the
pure-Python oracle, and PEventStore.find_ratings must give the same
training triples through the columnar fast path (JSONL backend) as through
the row-based slow path (memory backend)."""

import datetime as dt
import json

import numpy as np
import pytest

from incubator_predictionio_tpu import native
from incubator_predictionio_tpu.data.storage import Storage
from incubator_predictionio_tpu.data.storage.base import AccessKey, App
from incubator_predictionio_tpu.data.storage.event import Event
from incubator_predictionio_tpu.data.store.p_event_store import PEventStore

EVENTS = [
    {"event": "rate", "entityType": "user", "entityId": "u1",
     "targetEntityType": "item", "targetEntityId": "i1",
     "properties": {"rating": 4.5, "note": 'café "q" \\ slash'},
     "eventTime": "2014-09-09T16:17:42.937-08:00", "eventId": "e1"},
    {"event": "$set", "entityType": "user", "entityId": "u2",
     "properties": {"age": 3, "tags": ["a", "b"], "nested": {"x": 1}},
     "eventTime": "2024-01-01T00:00:00Z", "eventId": "e2"},
    {"event": "view", "entityType": "user", "entityId": "u1",
     "targetEntityType": "item", "targetEntityId": "i2",
     "eventTime": "2024-02-29T12:00:00.5+05:30", "eventId": "e3"},
    {"__tombstone__": "e1"},
    {"event": "buy", "entityType": "user", "entityId": "emoji \U0001f600",
     "targetEntityType": "item", "targetEntityId": "i1",
     "properties": {"rating": 2}, "eventTime": "1999-12-31T23:59:59.999999Z",
     "eventId": "e4"},
]
BUF = ("\n".join(json.dumps(e) for e in EVENTS) + "\n").encode()


def _columns_equal(a, b):
    for f in ("event", "etype", "eid", "tetype", "teid", "event_id",
              "time_us", "props", "span"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert np.array_equal(np.isnan(a.rating), np.isnan(b.rating))
    assert np.allclose(np.nan_to_num(a.rating), np.nan_to_num(b.rating))
    assert a.tables == b.tables
    assert a.tombstones == b.tombstones
    assert np.array_equal(a.tombstone_pos, b.tombstone_pos)


def test_python_oracle_semantics():
    c = native.parse_events_jsonl_py(BUF)
    assert len(c) == 4
    assert c.tombstones == ["e1"]
    assert c.tombstone_pos.tolist() == [3]  # three records precede it
    expect = int(dt.datetime(
        2014, 9, 9, 16, 17, 42, 937000,
        tzinfo=dt.timezone(dt.timedelta(hours=-8))).timestamp() * 1e6)
    assert c.time_us[0] == expect
    assert c.properties_dict(0)["note"] == 'café "q" \\ slash'
    assert c.record_dict(3)["entityId"] == "emoji \U0001f600"
    assert np.isnan(c.rating[1]) and c.rating[3] == 2.0
    assert c.properties_dict(2) == {}  # no properties key


def test_tfidf_native_matches_python():
    """The C++ tokenizer+hasher (pio_tfidf_tf) must match the Python
    token loop bit-for-bit: same ASCII token class, same lowercasing,
    same FNV-1a buckets, same n-gram joins — across unicode text,
    apostrophes, empty docs, and non-pow2 feature counts."""
    import random

    from incubator_predictionio_tpu import native as pionative
    from incubator_predictionio_tpu.ops.tfidf import TfIdfVectorizer

    if not pionative.available():
        pytest.skip("no C++ toolchain")
    docs = ["Hello WORLD don't stop", "", "   ", "naïve café déjà-vu 123abc",
            "a b c d e f", "x'y'z 'quoted' ''", "ABC abc AbC",
            "tab\tsep\nline", "ü漢字mixedASCII99"]
    rng = random.Random(1)
    alphabet = "abcXYZ019'@ü漢 \t\n-_.,"
    docs += ["".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 300)))
             for _ in range(100)]
    for ngram in (1, 2, 3):
        for n_features in (512, 300):  # pow2 mask path + modulo path
            v = TfIdfVectorizer(n_features=n_features, ngram=ngram)
            ref = v.term_frequencies(docs, use_native=False)
            nat = v.term_frequencies(docs, use_native=True)
            assert np.array_equal(ref, nat), (ngram, n_features)
            # df accumulated by the native first-touch counter must
            # equal count_nonzero — incl. in-doc hash collisions and
            # unigram/n-gram same-bucket hits (tiny n_features forces
            # plenty of both)
            nat2, df = v.term_frequencies(docs, use_native=True,
                                          want_df=True)
            assert np.array_equal(nat2, ref)
            assert np.array_equal(df, np.count_nonzero(ref, axis=0)), \
                (ngram, n_features)
    v = TfIdfVectorizer(n_features=16, ngram=3)  # collision-heavy
    ref = v.term_frequencies(docs, use_native=False)
    _, df = v.term_frequencies(docs, use_native=True, want_df=True)
    assert np.array_equal(df, np.count_nonzero(ref, axis=0))


def test_native_matches_oracle():
    if not native.available():
        pytest.skip("no C++ toolchain")
    _columns_equal(native.parse_events_jsonl(BUF), native.parse_events_jsonl_py(BUF))


def test_native_matches_oracle_fuzz():
    if not native.available():
        pytest.skip("no C++ toolchain")
    import random

    random.seed(42)
    rows = []
    for n in range(500):
        e = {
            "event": random.choice(["rate", "buy", "$set", "über-event"]),
            "entityType": "user",
            "entityId": "u%d" % random.randrange(50),
            "eventTime": "20%02d-%02d-%02dT%02d:%02d:%02d.%03dZ" % (
                random.randrange(100), random.randrange(1, 13),
                random.randrange(1, 28), random.randrange(24),
                random.randrange(60), random.randrange(60),
                random.randrange(1000)),
            "eventId": "id%d" % n,
        }
        if random.random() < 0.7:
            e["targetEntityType"] = "item"
            e["targetEntityId"] = "i%d" % random.randrange(30)
        if random.random() < 0.6:
            e["properties"] = {"rating": random.choice(
                [1, 2.5, -3, 1e10, 0.1, "3.5", " 2 ", "n/a", "1_0",
                 "1", "0x10", "inf", "1e999", 1e999,
                 True, False, None, ["4"], {"v": 4}]),
                "s": random.choice(["plain", 'esc"\\', "unié€"])}
        if random.random() < 0.05:
            e = {"__tombstone__": "id%d" % random.randrange(max(n, 1))}
        rows.append(json.dumps(e, ensure_ascii=random.random() < 0.5))
    buf = ("\n".join(rows) + "\n").encode()
    _columns_equal(native.parse_events_jsonl(buf),
                   native.parse_events_jsonl_py(buf))


def test_native_parse_error():
    if not native.available():
        pytest.skip("no C++ toolchain")
    with pytest.raises(native.EventParseError):
        native.parse_events_jsonl(b'{"event": "x", \n')


def _storage(kind, tmp_path):
    if kind == "jsonl":
        env = {
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "LOG",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
            "PIO_STORAGE_SOURCES_M_TYPE": "MEMORY",
            "PIO_STORAGE_SOURCES_LOG_TYPE": "JSONL",
            "PIO_STORAGE_SOURCES_LOG_PATH": str(tmp_path / "events"),
        }
    else:
        env = {
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
            "PIO_STORAGE_SOURCES_M_TYPE": "MEMORY",
        }
    return Storage(env)


def _seed_app(s, ratings):
    app_id = s.get_meta_data_apps().insert(App(0, "fastpath", None))
    s.get_l_events().init(app_id)
    s.get_meta_data_access_keys().insert(AccessKey("K", app_id, ()))
    events = []
    for n, (u, i, r) in enumerate(ratings):
        props = {"rating": r} if r is not None else {}
        obj = {
            "event": "rate" if r is not None else "buy",
            "entityType": "user", "entityId": u,
            "properties": props,
            "eventTime": "2024-01-%02dT00:00:00Z" % (1 + n % 28),
        }
        if i is not None:
            obj["targetEntityType"] = "item"
            obj["targetEntityId"] = i
        events.append(Event.from_json(obj))
    s.get_l_events().insert_batch(events, app_id)
    return app_id


# -- the split parse: pieces cut at newlines, parsed side by side, merged ------


def _split_corpus() -> bytes:
    """480 events that no cut leaves alone: ``u-everywhere`` is first seen
    in the first piece and again in every later one, event ids e0..e299
    come round again after 300 lines, a tombstone lies in each half,
    ``targetEntityId`` is null, absent or an id with escapes and
    non-ASCII, some lines are empty and the last has no newline."""
    lines = []
    for n in range(480):
        e = {"event": "rate" if n % 3 else "buy", "entityType": "user",
             "entityId": "u-everywhere" if n % 5 == 0 else "u%d" % (n % 37),
             "eventTime": "2024-03-%02dT10:00:00.%03dZ" % (1 + n % 28, n),
             "eventId": "e%d" % (n % 300)}
        if n % 4 == 0:
            e["targetEntityType"], e["targetEntityId"] = "item", None
        elif n % 4 != 1:  # n % 4 == 1: no target at all
            e["targetEntityType"] = "item"
            e["targetEntityId"] = ('i%d' % (n % 11) if n % 8 < 4
                                   else 'ü漢 "q" \\ %d \U0001f600' % (n % 6))
        if n % 7 == 0:
            e["properties"] = {"rating": 1 + n % 5, "s": 'esc"\\ é'}
        lines.append(json.dumps(e, ensure_ascii=bool(n % 2)))
        if n in (100, 350):
            lines.append(json.dumps({"__tombstone__": "e%d" % (n - 50)}))
        if n % 50 == 0:
            lines.append("")
    return "\n".join(lines).encode()


def _same_scan(a, b):
    _columns_equal(a, b)
    assert np.array_equal(a.rating, b.rating, equal_nan=True)
    assert a.raw == b.raw


def _split_equals_one_pass(pieces):
    def case(tmp_path, monkeypatch):
        buf = _split_corpus()
        one = native.parse_events_jsonl(buf, pieces=1)
        assert one.parse_stats == {"mode": "whole", "pieces": 1,
                                   "threads": 1, "merge_ms": 0.0}
        assert len(one) == 480 and len(one.tombstones) == 2
        got = native.parse_events_jsonl(buf, pieces=pieces)
        _same_scan(got, one)
        if pieces > 1:
            assert got.parse_stats["mode"] == "split"
            assert got.parse_stats["pieces"] == pieces
            assert 2 <= got.parse_stats["threads"] <= pieces
            # the first piece's table comes first, whole and in its order
            assert one.table(2)[0] == "u-everywhere"
    return case


def _empty_buffer(tmp_path, monkeypatch):
    for pieces in (None, 1, 16):
        got = native.parse_events_jsonl(b"", pieces=pieces)
        assert len(got) == 0 and got.tables == [[]] * 6
        assert got.parse_stats["mode"] == "whole"


def _falls_back(make_line):
    """Every record holds a newline, so some cut of seven falls inside one:
    that piece fails and the one pass gives the result."""
    def case(tmp_path, monkeypatch):
        buf = "".join(make_line(n) for n in range(120)).encode()
        one = native.parse_events_jsonl(buf, pieces=1)
        assert len(one) == 120
        got = native.parse_events_jsonl(buf, pieces=7)
        assert got.parse_stats["mode"] == "fallback"
        assert got.parse_stats["pieces"] == 7
        _same_scan(got, one)
    return case


def _two_line_record(n):
    return ('{"event": "rate", "entityType": "user", "entityId": "u%d", '
            '"eventId": "two-lines-%d",\n "targetEntityType": "item", '
            '"targetEntityId": "i%d"}\n' % (n % 9, n, n % 4))


def _raw_newline_in_a_string(n):
    return ('{"event": "rate", "entityType": "user", "entityId": "u%d", '
            '"eventId": "raw-newline-%d", "properties": {"rating": 3, '
            '"note": "first\nsecond"}}\n' % (n % 9, n))


def _malformed_in_the_third_of_five(tmp_path, monkeypatch):
    lines = [json.dumps({"event": "rate", "entityType": "user",
                         "entityId": "u%03d" % n, "eventId": "e%03d" % n})
             for n in range(500)]
    lines[250] = lines[250][:-1] + ' "oops"}'
    buf = ("\n".join(lines) + "\n").encode()
    assert 2 / 5 < buf.index(b"oops") / len(buf) < 3 / 5
    texts = []
    for pieces in (1, 5):
        with pytest.raises(native.EventParseError) as err:
            native.parse_events_jsonl(buf, pieces=pieces)
        texts.append(str(err.value))
    assert texts[0] == texts[1]
    assert texts[0].endswith("at byte %d (record 250)" % buf.index(b'"oops"'))


def _under_the_floor_starts_no_thread(tmp_path, monkeypatch):
    lib = native._load()
    started = lib.pio_threads_started()
    got = native.parse_events_jsonl(_split_corpus())
    assert got.parse_stats == {"mode": "whole", "pieces": 1, "threads": 1,
                               "merge_ms": 0.0}
    assert lib.pio_threads_started() == started
    native.parse_events_jsonl(_split_corpus(), pieces=3)
    assert lib.pio_threads_started() > started


def _python_oracle_agrees(tmp_path, monkeypatch):
    buf = _split_corpus()
    oracle = native.parse_events_jsonl_py(buf)
    assert oracle.parse_stats["mode"] == "whole"
    _columns_equal(native.parse_events_jsonl(buf, pieces=7), oracle)


def _find_ratings_over_a_split_log(tmp_path, monkeypatch):
    import functools
    import random

    from incubator_predictionio_tpu.data.storage import jsonl

    random.seed(11)
    ratings = [("u%d" % random.randrange(40), "i%d" % random.randrange(15),
                random.choice([None, 1.0, 2.0, 5.0])) for _ in range(300)]
    s = _storage("jsonl", tmp_path)
    _seed_app(s, ratings)
    s.close()
    seen = []

    def split(buf):
        cols = native.parse_events(buf, pieces=5)
        seen.append(cols.parse_stats["mode"])
        return cols

    out = []
    for parse in (functools.partial(native.parse_events, pieces=1), split):
        monkeypatch.setattr(jsonl, "parse_events", parse)
        s = _storage("jsonl", tmp_path)  # a store that has scanned nothing
        s.get_meta_data_apps().insert(App(0, "fastpath", None))
        u, i, r, users, items = PEventStore.find_ratings(
            "fastpath", event_names=["rate", "buy"],
            event_default_ratings={"buy": 4.0}, storage=s)
        out.append((u, i, r, users.to_dict(), items.to_dict()))
        s.close()
    assert seen == ["split"]
    assert len(out[0][0]) == 300
    for a, b in zip(out[0][:3], out[1][:3]):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert out[0][3:] == out[1][3:]
    assert list(out[0][3]) == list(out[1][3])  # the maps' order too


_SPLIT_CASES = {
    **{"equals-one-pass-%d-pieces" % n: _split_equals_one_pass(n)
       for n in (1, 2, 3, 7, 16)},
    "empty-buffer": _empty_buffer,
    "record-over-two-lines-falls-back": _falls_back(_two_line_record),
    "raw-newline-in-a-string-falls-back":
        _falls_back(_raw_newline_in_a_string),
    "malformed-in-third-of-five-gives-the-one-pass-error":
        _malformed_in_the_third_of_five,
    "under-the-floor-whole-and-no-thread": _under_the_floor_starts_no_thread,
    "python-oracle-agrees": _python_oracle_agrees,
    "find-ratings-over-a-split-log": _find_ratings_over_a_split_log,
}


@pytest.mark.parametrize("case", list(_SPLIT_CASES.values()),
                         ids=list(_SPLIT_CASES))
def test_split_parse(case, tmp_path, monkeypatch):
    """A large buffer is cut at newlines and parsed as pieces on threads
    (native/src/event_codec.cc ``parse_split``); whatever the cuts, the
    result is the one pass's, field by field, or the one pass's error."""
    if not native.available():
        pytest.skip("no C++ toolchain")
    case(tmp_path, monkeypatch)


def test_find_ratings_fast_equals_slow(tmp_path):
    import random

    random.seed(7)
    # Includes present-but-unusable ratings (bool/"n/a"/underscore string):
    # both paths must coerce those to default_rating, NOT the event default.
    ratings = [("u%d" % random.randrange(20), "i%d" % random.randrange(10),
                random.choice([None, 1.0, 2.0, 5.0, "3.5",
                               True, "n/a", "1_0"])) for _ in range(200)]
    # a user whose only event has no target: must still get a BiMap slot
    ratings.append(("u_lonely", None, 2.0))
    out = {}
    for kind in ("memory", "jsonl"):
        s = _storage(kind, tmp_path)
        _seed_app(s, ratings)
        u, i, r, users, items = PEventStore.find_ratings(
            "fastpath", event_names=["rate", "buy"],
            event_default_ratings={"buy": 4.0}, storage=s,
        )
        triples = [
            (users.inverse(int(a)), items.inverse(int(b)), float(c))
            for a, b, c in zip(u, i, r)
        ]
        out[kind] = (sorted(triples), users.to_dict(), items.to_dict())
        s.close()
    # identical triples AND identical BiMap membership + index assignment
    assert out["memory"] == out["jsonl"]
    assert len(out["jsonl"][0]) == 200
    assert "u_lonely" in out["jsonl"][1]


def test_jsonl_delete_and_dedupe(tmp_path):
    s = _storage("jsonl", tmp_path)
    app_id = _seed_app(s, [("u1", "i1", 5.0), ("u2", "i2", 3.0)])
    le = s.get_l_events()
    events = list(le.find(app_id))
    assert len(events) == 2
    # delete via tombstone append
    assert le.delete(events[0].event_id, app_id)
    assert le.get(events[0].event_id, app_id) is None
    assert len(list(le.find(app_id))) == 1
    # client-supplied id overwrite: same eventId, new rating wins
    e = events[1]
    updated = Event.from_json({**e.to_json(), "properties": {"rating": 1.0}})
    le.insert(updated, app_id)
    got = le.get(e.event_id, app_id)
    assert got.properties.get("rating") == 1.0
    assert len(list(le.find(app_id))) == 1
    # compaction drops tombstones and stale duplicates
    live = le.compact(app_id)
    assert live == 1
    assert len(list(le.find(app_id))) == 1
    s.close()


def test_jsonl_reinsert_after_delete(tmp_path):
    """A delete only kills records appended before it: re-inserting the
    same eventId afterwards must be visible (upsert-backend parity) and
    must survive compaction."""
    s = _storage("jsonl", tmp_path)
    app_id = _seed_app(s, [("u1", "i1", 5.0)])
    le = s.get_l_events()
    e = next(iter(le.find(app_id)))
    assert le.delete(e.event_id, app_id)
    assert le.get(e.event_id, app_id) is None
    # re-insert with the SAME eventId
    le.insert(e, app_id)
    got = le.get(e.event_id, app_id)
    assert got is not None and got.entity_id == "u1"
    assert len(list(le.find(app_id))) == 1
    # compaction must keep the re-inserted record
    assert le.compact(app_id) == 1
    assert le.get(e.event_id, app_id) is not None
    # ...and a fresh Storage over the same files agrees (cold scan path)
    s2 = _storage("jsonl", tmp_path)
    le2 = s2.get_l_events()
    assert le2.get(e.event_id, app_id) is not None
    s2.close()
    s.close()


def test_jsonl_batch_delete(tmp_path):
    s = _storage("jsonl", tmp_path)
    app_id = _seed_app(s, [("u%d" % n, "i1", 1.0) for n in range(10)])
    le = s.get_l_events()
    ids = [e.event_id for e in le.find(app_id)]
    out = le.delete_batch(ids[:6] + ["missing-id"], app_id)
    assert out == [True] * 6 + [False]
    assert len(list(le.find(app_id))) == 4
    # repeated delete of an already-dead id reports False
    assert le.delete_batch([ids[0]], app_id) == [False]
    s.close()


def test_jsonl_reversed_order_tie_semantics(tmp_path):
    """Equal-timestamp events in reversed_order must come back in
    insertion order (stable descending), matching the memory backend."""
    same_time = "2024-03-01T00:00:00Z"
    events = [Event.from_json({
        "event": "rate", "entityType": "user", "entityId": "u%d" % n,
        "targetEntityType": "item", "targetEntityId": "i",
        "properties": {"rating": 1.0}, "eventTime": same_time,
    }) for n in range(5)]
    orders = {}
    for kind in ("memory", "jsonl"):
        s = _storage(kind, tmp_path / kind)
        app_id = s.get_meta_data_apps().insert(App(0, "ties", None))
        le = s.get_l_events()
        le.init(app_id)
        le.insert_batch(events, app_id)
        orders[kind] = [e.entity_id
                        for e in le.find(app_id, reversed_order=True)]
        s.close()
    assert orders["memory"] == orders["jsonl"]


def test_native_pair_dedupe_matches_numpy():
    """pio_pair_dedupe (counting-sort + per-user sorts) must emit the
    exact (user, item)-sorted distinct pairs + per-user counts that the
    packed-key np.unique path produces, incl. out-of-range drops."""
    import numpy as np
    import pytest

    native = pytest.importorskip("incubator_predictionio_tpu.native")
    try:
        native._load()
    except native.NativeUnavailable:
        pytest.skip("no toolchain")

    rng = np.random.default_rng(3)
    n_users, n_items = 300, 90
    u = rng.integers(-5, n_users + 5, 20_000).astype(np.int32)
    i = rng.integers(-5, n_items + 5, 20_000).astype(np.int32)
    u[:4000] = 7  # heavy user with many duplicate pairs

    du, di, per_user = native.pair_dedupe(u, i, n_users, n_items)

    uu, ii = u.astype(np.int64), i.astype(np.int64)
    valid = (ii >= 0) & (ii < n_items) & (uu >= 0) & (uu < n_users)
    key = np.unique(uu[valid] * n_items + ii[valid])
    np.testing.assert_array_equal(du, (key // n_items).astype(np.int32))
    np.testing.assert_array_equal(di, (key % n_items).astype(np.int32))
    np.testing.assert_array_equal(
        per_user, np.bincount(du, minlength=n_users))
    # empty input
    e_u, e_i, e_pu = native.pair_dedupe(
        np.zeros(0, np.int32), np.zeros(0, np.int32), 10, 10)
    assert len(e_u) == 0 and len(e_i) == 0 and e_pu.sum() == 0


def test_native_pair_dedupe_int64_ids_never_wrap():
    """64-bit ids out of int32 range must be DROPPED (as the numpy path
    drops them), never wrapped into the valid range by the cast."""
    import numpy as np
    import pytest

    native = pytest.importorskip("incubator_predictionio_tpu.native")
    try:
        native._load()
    except native.NativeUnavailable:
        pytest.skip("no toolchain")

    u = np.array([1, 2**32 + 7, 3], np.int64)  # wraps to 7 if cast unsafely
    i = np.array([0, 1, 2], np.int64)
    du, di, per_user = native.pair_dedupe(u, i, n_users=100, n_items=10)
    assert du.tolist() == [1, 3] and di.tolist() == [0, 2]
    assert per_user[7] == 0  # the phantom pair must not exist
