"""The training read of the JSONL event store under the program's spans
and counters: a cold ``PEventStore.find_ratings`` beneath a train's root
leaves ``store.scan`` (with ``store.parse`` inside), ``store.select`` (the
masks, then the order) and ``store.index`` with their tags; a second read in
the process says ``cached``; an appended tail is parsed alone; a compacted
log loads from its snapshot; ``pio_store_scan_bytes_total`` and
``pio_store_scan_events_total{source}`` move by the log's bytes and events,
``pio_store_parse_total{mode}`` by one a parse (these logs are under the
codec's floor: ``whole``, one piece, one thread);
with metrics off nothing is recorded and the triple is the same. The
columnar read equals the row read, rows and ids, whatever form the scan's
id tables have; a plain read decodes no event id and no entity id
(``pio_store_id_strings_total{table}``) and says ``ids=arrays``."""

import datetime
import os
import time

import numpy as np
import pytest

from incubator_predictionio_tpu.common import telemetry
from incubator_predictionio_tpu.data.storage import base
from incubator_predictionio_tpu.data.storage.datamap import DataMap
from incubator_predictionio_tpu.data.storage.event import Event
from incubator_predictionio_tpu.data.storage.registry import Storage
from incubator_predictionio_tpu.data.store.p_event_store import (
    PEventStore,
    ratings_matrix,
)

APP = "SpanShop"
T0 = datetime.datetime(2014, 7, 1, tzinfo=datetime.timezone.utc)
#: how the codec says it ran on a read under its floor
ONE_PASS = {"mode": "whole", "pieces": 1, "threads": 1, "merge_ms": 0.0}
STORE_SPANS = ("store.scan", "store.parse", "store.select", "store.index")


def events(lo: int, hi: int) -> list[Event]:
    """Events lo..hi-1: user k % 7 rates or (every fifth) buys item k % 5."""
    out = []
    for k in range(lo, hi):
        buy = k % 5 == 4
        out.append(Event(
            event="buy" if buy else "rate", entity_type="user",
            entity_id=f"u{k % 7}", target_entity_type="item",
            target_entity_id=f"i{k % 5}",
            properties=DataMap({} if buy else {"rating": 1 + k % 5}),
            event_time=T0 + datetime.timedelta(milliseconds=k // 2)))
    return out


@pytest.fixture()
def shop(tmp_path):
    storage = Storage({
        "PIO_STORAGE_SOURCES_LOG_TYPE": "JSONL",
        "PIO_STORAGE_SOURCES_LOG_PATH": str(tmp_path),
        "PIO_STORAGE_SOURCES_APPS_TYPE": "MEMORY",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "LOG",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "t_eventdata",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "APPS",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "t_metadata"})
    app_id = storage.get_meta_data_apps().insert(base.App(0, APP, None))
    store = storage.get_l_events()
    store.init(app_id)
    store.insert_batch(events(0, 40), app_id)
    return storage, store, app_id, store._path(app_id, None)


def read(storage):
    return PEventStore.find_ratings(
        APP, event_names=["rate", "buy"],
        event_default_ratings={"buy": 4.0}, storage=storage)


def traced_read(storage, instance: str):
    """(the triple and maps, the store's spans of this read, the root)."""
    t0 = time.perf_counter_ns()
    with telemetry.span("train.run", trace_id=instance):
        got = read(storage)
    mine = [s for s in telemetry.spans_snapshot() if s.t0_ns >= t0]
    root = next(s for s in mine if s.name == "train.run")
    assert root.trace_id == instance
    return got, [s for s in mine if s.name in STORE_SPANS], root


def counters() -> dict:
    fams = {f.name: f for f in telemetry.registry().collect()}
    out = {"bytes": fams["pio_store_scan_bytes_total"].labels().value()}
    for source in ("parse", "snapshot", "cached"):
        out[source] = fams["pio_store_scan_events_total"].labels(
            source).value()
    for mode in ("whole", "split", "fallback"):
        out["parses:" + mode] = fams["pio_store_parse_total"].labels(
            mode).value()
    return out


def moved(before: dict) -> dict:
    now = counters()
    return {k: now[k] - before[k] for k in now if now[k] != before[k]}


def test_a_cold_read_leaves_each_span_once_with_its_tags(shop):
    storage, _store, _app_id, path = shop
    size = os.path.getsize(path)
    before = counters()
    (u, i, r, users, items), spans, root = traced_read(storage, "inst-cold")
    assert len(u) == len(i) == len(r) == 40
    assert (len(users), len(items)) == (7, 5)
    assert sorted(s.name for s in spans) == [
        "store.index", "store.parse", "store.scan", "store.select",
        "store.select"]
    assert all(s.trace_id == "inst-cold" for s in spans)
    by = {(s.name, (s.tags or {}).get("step")): s for s in spans}
    scan, parse = by["store.scan", None], by["store.parse", None]
    assert scan.tags == {"source": "parse", "bytes": size, "events": 40}
    assert parse.parent_id == scan.span_id
    assert parse.tags == {"bytes": size, **ONE_PASS}
    assert scan.t0_ns <= parse.t0_ns and parse.t1_ns <= scan.t1_ns
    mask, order = by["store.select", "mask"], by["store.select", "order"]
    assert mask.tags == {"step": "mask", "events": 40, "selected": 40}
    index = by["store.index", None]
    assert index.tags == {"users": 7, "items": 5, "ids": "arrays"}
    for s in (scan, mask, order, index):
        assert s.parent_id == root.span_id
    # in the order of the work, none overlapping the next
    assert scan.t1_ns <= mask.t0_ns <= mask.t1_ns <= order.t0_ns
    assert order.t1_ns <= index.t0_ns <= index.t1_ns <= root.t1_ns
    assert moved(before) == {"bytes": size, "parse": 40, "parses:whole": 1}


def test_a_second_read_in_the_process_says_cached(shop):
    storage, _store, _app_id, _path = shop
    first, _spans, _root = traced_read(storage, "inst-1")
    before = counters()
    second, spans, _root = traced_read(storage, "inst-2")
    assert [s.name for s in spans if s.name in ("store.scan", "store.parse")
            ] == ["store.scan"]
    scan = next(s for s in spans if s.name == "store.scan")
    assert scan.tags == {"source": "cached", "bytes": 0, "events": 40}
    assert sorted(s.name for s in spans) == [
        "store.index", "store.scan", "store.select", "store.select"]
    assert moved(before) == {"cached": 40}
    for a, b in zip(first[:3], second[:3]):
        assert (a == b).all()


def test_an_appended_tail_is_parsed_alone(shop):
    storage, store, app_id, path = shop
    traced_read(storage, "inst-1")
    size = os.path.getsize(path)
    store.insert_batch(events(40, 52), app_id)
    before = counters()
    (u, _i, _r, _users, _items), spans, _root = traced_read(storage, "inst-2")
    assert len(u) == 52
    scan = next(s for s in spans if s.name == "store.scan")
    grown = os.path.getsize(path) - size
    assert scan.tags == {"source": "parse", "bytes": grown, "events": 12}
    parse = next(s for s in spans if s.name == "store.parse")
    assert parse.tags == {"bytes": grown, **ONE_PASS}
    assert moved(before) == {"bytes": grown, "parse": 12, "parses:whole": 1}


def test_a_compacted_log_loads_from_its_snapshot(shop, tmp_path):
    from incubator_predictionio_tpu.data.api import event_log

    storage, store, app_id, path = shop
    want = read(storage)
    assert event_log.compact_log(path) is not None
    store.insert_batch(events(40, 44), app_id)
    size = os.path.getsize(path)
    with store._meta:
        store._scans.clear()           # what a new process starts with
    before = counters()
    (u, i, _r, _users, _items), spans, _root = traced_read(storage, "inst-s")
    scan = next(s for s in spans if s.name == "store.scan")
    assert scan.tags == {"source": "snapshot", "bytes": size, "events": 44}
    # the tail past the snapshot is the only JSON parsed
    parse = [s for s in spans if s.name == "store.parse"]
    assert len(parse) == 1 and 0 < parse[0].tags["bytes"] < size
    assert moved(before) == {"bytes": size, "snapshot": 44, "parses:whole": 1}
    assert (u[:40] == want[0]).all() and (i[:40] == want[1]).all()


def test_with_metrics_off_nothing_is_recorded(shop):
    storage, _store, _app_id, _path = shop
    before = counters()
    telemetry.set_metrics_enabled(False)
    try:
        t0 = time.perf_counter_ns()
        with telemetry.span("train.run", trace_id="inst-off"):
            off = read(storage)
        assert not [s for s in telemetry.spans_snapshot() if s.t0_ns >= t0]
        assert moved(before) == {}
    finally:
        telemetry.set_metrics_enabled(True)
    on = read(storage)
    for a, b in zip(off[:3], on[:3]):
        assert (a == b).all() and a.dtype == b.dtype
    assert off[3].to_dict() == on[3].to_dict()
    assert off[4].to_dict() == on[4].to_dict()
    assert (off[0].dtype, off[1].dtype, off[2].dtype) == (
        np.int32, np.int32, np.float32)


def test_a_train_nests_the_stores_spans_under_dase_read(shop, monkeypatch):
    """Through the stock DataSource and ``Engine.train``: the four lie
    beneath ``dase.read``, which they cover but for the app's lookup."""
    from incubator_predictionio_tpu.controller.base import doer
    from incubator_predictionio_tpu.models.recommendation import (
        RecommendationDataSource,
    )
    from incubator_predictionio_tpu.workflow.context import WorkflowContext

    storage, _store, _app_id, _path = shop
    source = doer(RecommendationDataSource,
                  {"appName": APP, "eventNames": ["rate", "buy"]})
    t0 = time.perf_counter_ns()
    with telemetry.span("train.run", trace_id="inst-dase"):
        with telemetry.span("dase.read"):
            td = source.read_training(WorkflowContext(storage=storage))
    assert len(td.rating) == 40 and set(td.rating.tolist()) <= {
        1.0, 2.0, 3.0, 4.0, 5.0}
    mine = [s for s in telemetry.spans_snapshot() if s.t0_ns >= t0]
    read_span = next(s for s in mine if s.name == "dase.read")
    direct = sorted(s.name for s in mine if s.parent_id == read_span.span_id)
    assert direct == ["store.index", "store.scan", "store.select",
                      "store.select"]


# -- the columnar read against the row read -----------------------------------

NAMES = ["rate", "buy"]
DEFAULTS = {"buy": 4.0}


def id_strings() -> dict:
    fam = {f.name: f for f in telemetry.registry().collect()}[
        "pio_store_id_strings_total"]
    return {t: fam.labels(t).value() for t in (
        "event", "entityType", "entityId", "targetEntityType",
        "targetEntityId", "eventId")}


def row_read(storage, **window):
    """``ratings_matrix`` of the row scan, the event's default rating put
    in where the properties carry none (``find_ratings``' own row path)."""
    batch = PEventStore.find_batch(APP, event_names=NAMES, storage=storage,
                                   **window)
    for j, ev in enumerate(batch.event):
        if ev in DEFAULTS and "rating" not in batch.properties[j]:
            batch.properties[j] = {**batch.properties[j],
                                   "rating": DEFAULTS[ev]}
    return ratings_matrix(batch)


def cold(store) -> None:
    """Forget the cached scans: what a new process starts with."""
    with store._meta:
        store._scans.clear()


def rated(k: int, user: str, item, rating, event_id=None) -> Event:
    return Event(
        event="rate", entity_type="user", entity_id=user,
        target_entity_type=None if item is None else "item",
        target_entity_id=item, properties=DataMap({"rating": rating}),
        event_time=T0 + datetime.timedelta(milliseconds=k),
        event_id=event_id)


def _plain(shop, tmp_path):
    return {"ids": "arrays", "quiet": ("eventId", "entityId")}


def _events_without_a_target(shop, tmp_path):
    _storage, store, app_id, _path = shop
    # the first event of all and one in the middle: a user of no item
    store.insert_batch([rated(-5, "lonely", None, 2.0),
                        rated(7, "u3", None, 1.0),
                        rated(9, "late-lonely", None, 1.0)], app_id)
    cold(store)
    return {"ids": "arrays", "users": 9, "items": 5, "triples": 40}


def _a_record_without_an_entity_id(shop, tmp_path):
    """The row path refuses such a record, so it reads the log without
    it; the columnar read of the log WITH it must give the same."""
    storage, store, app_id, path = shop
    want = row_read(storage)
    with open(path, "rb") as f:
        lines = f.read().splitlines(keepends=True)
    bad = (b'{"event":"rate","entityType":"user","targetEntityType":"item",'
           b'"targetEntityId":"i-of-nobody","eventId":"no-entity",'
           b'"eventTime":"2014-07-01T00:00:00.001Z",'
           b'"properties":{"rating":5}}\n')
    store.remove(app_id)
    store.init(app_id)
    store.insert_canonical_lines(
        b"".join(lines[:11] + [bad] + lines[11:]), app_id)
    return {"ids": "arrays", "want": want, "items": 5}


def _an_event_id_written_twice(shop, tmp_path):
    _storage, store, app_id, _path = shop
    # the later record wins, and it names a NEW user and item, EARLIER in
    # time than everything else: the rows shift by one on both sides
    store.insert_batch([rated(3, "u1", "i1", 1.0, "twice"),
                        rated(-9, "first-now", "i-new", 5.0, "twice")],
                       app_id)
    cold(store)
    return {"ids": "arrays", "quiet": ("eventId", "entityId"),
            "first": ("first-now", "i-new", 5.0), "triples": 41}


def _a_delete_and_a_reinsert_after_it(shop, tmp_path):
    _storage, store, app_id, _path = shop
    store.insert_batch([rated(-3, "gone", "i-gone", 2.0, "del-1"),
                        rated(-2, "back", "i-back", 3.0, "del-2")], app_id)
    assert store.delete_batch(["del-1", "del-2"], app_id) == [True, True]
    store.insert(rated(-2, "back", "i-back", 1.0, "del-2"), app_id)
    cold(store)      # the tombstones are parsed with the log: arrays
    return {"ids": "arrays", "first": ("back", "i-back", 1.0),
            "absent": "gone", "triples": 41}


def _a_scan_grown_by_extend(shop, tmp_path):
    storage, store, app_id, _path = shop
    read(storage)
    store.insert_batch(events(40, 52) + [rated(60, "tail-user", "i-tail",
                                               2.0)], app_id)
    return {"ids": "strings", "users": 8, "items": 6, "triples": 53}


def _a_committed_snapshot_and_its_tail(shop, tmp_path):
    from incubator_predictionio_tpu.data.api import event_log

    _storage, store, app_id, path = shop
    assert event_log.compact_log(path) is not None
    store.insert_batch(events(40, 44), app_id)
    cold(store)
    return {"ids": "strings", "source": "snapshot", "triples": 44}


def _a_time_window(shop, tmp_path):
    return {"ids": "arrays", "triples": 12, "window": {
        "start_time": T0 + datetime.timedelta(milliseconds=4),
        "until_time": T0 + datetime.timedelta(milliseconds=10)}}


def _non_ascii_ids(shop, tmp_path):
    _storage, store, app_id, _path = shop
    store.insert_batch([rated(-1, "üser-✓", "商品7", 5.0),
                        rated(5, "u2", "商品7", 1.0),
                        rated(6, "Łódź", "i1", 2.0)], app_id)
    cold(store)
    return {"ids": "arrays", "first": ("üser-✓", "商品7", 5.0),
            "users": 9, "items": 6, "triples": 43}


_PARITY_CASES = {
    "plain": _plain,
    "events-without-a-target": _events_without_a_target,
    "a-record-without-an-entity-id": _a_record_without_an_entity_id,
    "an-event-id-written-twice": _an_event_id_written_twice,
    "a-delete-and-a-reinsert-after-it": _a_delete_and_a_reinsert_after_it,
    "a-scan-grown-by-extend": _a_scan_grown_by_extend,
    "a-committed-snapshot-and-its-tail": _a_committed_snapshot_and_its_tail,
    "a-time-window": _a_time_window,
    "non-ascii-ids": _non_ascii_ids,
}


@pytest.mark.parametrize("case", list(_PARITY_CASES.values()),
                         ids=list(_PARITY_CASES))
def test_the_columnar_read_equals_the_row_read(case, shop, tmp_path):
    """``find_ratings`` through ``scan_columnar`` against ``ratings_matrix``
    of the row scan: same ``u``, ``i``, ``r`` and the same id at every row
    of both maps, whatever the log holds and whichever form its id tables
    have (the codec's arrays, or lists after ``_extend`` or a snapshot)."""
    storage = shop[0]
    expect = case(shop, tmp_path)
    window = expect.get("window", {})
    before = id_strings()
    t0 = time.perf_counter_ns()
    with telemetry.span("train.run", trace_id="inst-parity"):
        u, i, r, users, items = PEventStore.find_ratings(
            APP, event_names=NAMES, event_default_ratings=DEFAULTS,
            storage=storage, **window)
    after = id_strings()
    spans = {s.name: s for s in telemetry.spans_snapshot()
             if s.t0_ns >= t0 and s.name in ("store.scan", "store.index")}
    wu, wi, wr, want_users, want_items = (
        expect.get("want") or row_read(storage, **window))
    for got, want in ((u, wu), (i, wi), (r, wr)):
        assert got.dtype == want.dtype and (got == want).all()
    for got, want in ((users, want_users), (items, want_items)):
        assert len(got) == len(want)
        assert [got.inverse(k) for k in range(len(got))] == [
            want.inverse(k) for k in range(len(want))]
        assert got.to_dict() == want.to_dict()
    assert spans["store.index"].tags == {
        "users": len(users), "items": len(items), "ids": expect["ids"]}
    assert (users._table is not None) == (expect["ids"] == "arrays")
    for table in expect.get("quiet", ()):
        assert after[table] == before[table], table
    if "source" in expect:
        assert spans["store.scan"].tags["source"] == expect["source"]
    for key, got in (("users", len(users)), ("items", len(items)),
                     ("triples", len(r))):
        assert expect.get(key, got) == got, key
    if "first" in expect:
        user, item, rating = expect["first"]
        assert (users.inverse(0), items.inverse(0)) == (user, item)
        k = int(np.nonzero((u == 0) & (i == 0))[0][0])
        assert r[k] == rating
    if "absent" in expect:
        assert expect["absent"] not in users


@pytest.mark.parametrize("form", ["arrays", "the-dict-of-an-older-artifact"])
def test_the_reads_maps_through_the_artifact_and_warm_up(shop, form):
    """The stock ALS model over the read's maps: persisted, pickled,
    restored, warmed up, queried. The arrays form reaches the artifact as
    two buffers and builds its users' dict inside ``warm_up``, so the first
    query builds none; the dict form an older artifact holds loads and
    answers the same."""
    import pickle

    from incubator_predictionio_tpu.controller.base import doer
    from incubator_predictionio_tpu.models.recommendation import (
        ALSAlgorithm,
        ALSModel,
    )
    from incubator_predictionio_tpu.ops.als import ALSFactors

    _u, _i, _r, users, items = read(shop[0])
    rng = np.random.default_rng(5)
    k = 4
    algo = doer(ALSAlgorithm, {"rank": k, "lambda": 0.1})
    stored = algo.prepare_model_for_persistence(ALSModel(
        factors=ALSFactors(
            rng.normal(size=(len(users), k)).astype(np.float32),
            rng.normal(size=(len(items), k)).astype(np.float32),
            len(users), len(items)),
        users=users, items=items))
    assert list(stored["users"]) == list(stored["items"]) == ["__id_table__"]
    assert users._fwd is None and items._fwd is None
    if form != "arrays":
        stored = dict(stored, users=users.to_dict(), items=items.to_dict())
    model = algo.restore_model(pickle.loads(pickle.dumps(
        stored, protocol=pickle.HIGHEST_PROTOCOL)), None)
    assert (model.users._table is not None) == (form == "arrays")
    if form == "arrays":
        assert model.users._fwd is None
    model.warm_up()
    built = model.users._fwd
    assert built is not None
    assert hasattr(model.users, "_inv") == (form != "arrays")
    answer = algo.predict(model, {"user": "u3", "num": 3})
    assert model.users._fwd is built
    want = np.argsort(-(model.factors.item_factors
                        @ model.factors.user_factors[users("u3")]),
                      kind="stable")[:3]
    assert [s["item"] for s in answer["itemScores"]] == [
        items.inverse(int(j)) for j in want]
    assert algo.predict(model, {"user": "nobody", "num": 3}) == {
        "itemScores": []}


# -- the property replay ------------------------------------------------------


def test_the_replay_says_what_it_parsed_dropped_and_folded(shop):
    """``store.aggregate`` on a small log: ``parsed`` counts the spans of
    the one parse, ``dropped`` the events at or before an entity's last
    ``$delete``, ``folded`` the entities of more than one surviving event;
    an ``$unset`` before the first ``$set`` is neither, another entity
    type's events count in ``events`` alone;
    ``pio_store_aggregate_events_total{step}`` moves by the two counts."""
    storage, store, app_id, _path = shop

    def prop(k: int, event: str, entity: str, props=None, kind="item"):
        return Event(event=event, entity_type=kind, entity_id=entity,
                     properties=DataMap(props or {}),
                     event_time=T0 + datetime.timedelta(seconds=k))

    store.insert_batch([
        prop(0, "$set", "i1", {"a": 1}),      # i1: three parsed, folded
        prop(1, "$set", "i1", {"b": 2}),
        prop(2, "$unset", "i1", {"a": 0}),
        prop(0, "$set", "i2", {"a": 1}),      # i2: one parsed
        prop(0, "$set", "i3", {"a": 1}),      # i3: two dropped, one parsed
        prop(1, "$delete", "i3"),
        prop(2, "$set", "i3", {"c": 3}),
        prop(0, "$set", "i4", {"a": 1}),      # i4: two dropped, gone
        prop(1, "$delete", "i4"),
        prop(0, "$unset", "i5", {"a": 0}),    # i5: ignored, one parsed
        prop(1, "$set", "i5", {"e": 5}),
        prop(0, "$set", "u1", {"u": 1}, kind="user"),
    ], app_id)
    fam = {f.name: f for f in telemetry.registry().collect()}[
        "pio_store_aggregate_events_total"]
    before = {s: fam.labels(s).value() for s in ("parsed", "dropped")}
    t0 = time.perf_counter_ns()
    got = PEventStore.aggregate_properties(APP, "item", storage=storage)
    spans = [s for s in telemetry.spans_snapshot()
             if s.t0_ns >= t0 and s.name == "store.aggregate"]
    assert {k: dict(v) for k, v in got.items()} == {
        "i1": {"b": 2}, "i2": {"a": 1}, "i3": {"c": 3}, "i5": {"e": 5}}
    assert len(spans) == 1
    assert spans[0].tags == {
        "events": 12, "entities": 4, "source": "parse",
        "parsed": 6, "dropped": 4, "folded": 1}
    assert {s: fam.labels(s).value() - before[s]
            for s in ("parsed", "dropped")} == {"parsed": 6, "dropped": 4}
