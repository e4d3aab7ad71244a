"""The training read of the JSONL event store under the program's spans
and counters: a cold ``PEventStore.find_ratings`` beneath a train's root
leaves ``store.scan`` (with ``store.parse`` inside), ``store.select`` (the
masks, then the order) and ``store.index`` with their tags; a second read in
the process says ``cached``; an appended tail is parsed alone; a compacted
log loads from its snapshot; ``pio_store_scan_bytes_total`` and
``pio_store_scan_events_total{source}`` move by the log's bytes and events,
``pio_store_parse_total{mode}`` by one a parse (these logs are under the
codec's floor: ``whole``, one piece, one thread);
with metrics off nothing is recorded and the triple is the same."""

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
from incubator_predictionio_tpu.data.store.p_event_store import PEventStore

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
    assert index.tags == {"users": 7, "items": 5}
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
