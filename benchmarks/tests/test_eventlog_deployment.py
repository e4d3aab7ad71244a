"""The event-log deployment under the harness: its cell's rehearsal is
``correct`` and reports the read's four metrics beside the sibling's from
files alone; every control and planted fault reads over a limit at the
rehearsal size; the read of a log that also holds what a live log holds
(a ``$set``, an event with no target, an event of another name, a deleted
event's tombstone, times out of order and equal) is the posted events and
nothing else, and one ``rate`` event removed reads 1; a fault planted under
the timed path makes ``correct`` false; the generator's request bodies say
what its arrays say."""

import collections
import datetime
import json
import os
import types

import numpy as np
import pytest

import run as bench

from conftest import BENCH, ROOT

import datagen
import datagen_eventlog
import program_spans
import reference_eventlog
import store_spans

CELL = "retrain-electronics-eventlog"
SIBLING = "retrain-electronics-r128"
NEW_FILES = (
    "cells/retrain-electronics-eventlog.json",
    "configs/amazon-electronics-eventlog-als128.json",
    "deployments/recommendation-eventlog.py",
    "engines/bench_eventlog_engine.py", "lib/datagen_eventlog.py",
    "lib/reference_eventlog.py", "lib/store_spans.py",
    "metrics/dase.read_s.py", "metrics/store.scan_s.py",
    "metrics/store.index_s.py", "metrics/store.scan_mb_per_s.py",
    "tests/test_eventlog_deployment.py")
NEW_METRICS = ("dase.read_s", "store.scan_s", "store.index_s",
               "store.scan_mb_per_s")
COMPARED = {"triple_diff", "ids_wrong", "user_fro", "item_fro"}
CONTROLS = ("control_lower_precision", "fault_one_event_dropped",
            "fault_buy_rated_1", "fault_user_under_two_rows")

Span = collections.namedtuple(
    "Span", "trace_id span_id parent_id name t0_ns t1_ns tags")


def run_cell(capsys, trace=0, seed=123):
    rc = bench.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "1", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def rehearsal():
    _cell, cfg, traffic = bench.load_cell(CELL, rehearse=True)
    return cfg, traffic, bench.load_module("deployments", cfg["deployment"])


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_rehearsal_is_correct_and_reports_its_metrics(capsys):
    line = run_cell(capsys, trace=1, seed=2_147_483_659)
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == COMPARED
    assert line["compared"]["triple_diff"] == {"value": 0, "limit": 0}
    assert line["compared"]["ids_wrong"] == {"value": 0, "limit": 0}
    assert line["attempted"] >= 1 and line["failed"] == 0
    for name in NEW_METRICS + (
            "dase.outside_als_s", "layout.fill_s", "als.init_s",
            "als.upload_s", "als.loop_s", "als.readback_s", "dase.persist_s",
            "train.window_compiles"):
        assert line["metrics"][name]["value"] >= 0, name
    assert line["metrics"]["store.scan_mb_per_s"]["value"] > 0
    assert line["metrics"]["dase.read_s"]["value"] >= \
        line["metrics"]["store.scan_s"]["value"]
    if line["device"]["count"] == 1:
        # on a mesh each shard's share of a bucket follows the store's
        # first-seen row order, so another day's log is another plan
        assert line["metrics"]["train.window_compiles"]["value"] == 0
    assert {"retrain_s", "setup_s"} == set(line["end_to_end_seen"])


def test_the_cell_went_in_by_files_and_appended_entries():
    for rel in NEW_FILES:
        assert os.path.exists(os.path.join(BENCH, rel)), rel
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "amazon-electronics-eventlog-als128", "retrain-fresh", 1)
    sibling = {p["name"] for p in m["per_layer"]
               if SIBLING in p.get("workloads", ())}
    reports = {p["name"] for p in m["per_layer"]
               if CELL in p.get("workloads", ())}
    # every metric of the sibling reads here too, and the read's four
    assert reports == sibling | set(NEW_METRICS)
    assert "train.step_mfu" in reports
    for p in m["per_layer"]:
        if p["name"] in NEW_METRICS:
            assert (p["layer"], p["moves"], p["workloads"]) == (
                "event_store", "retrain_s", [CELL])
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0


def test_the_full_size_is_the_issues_and_the_siblings():
    _cell, cfg, traffic = bench.load_cell(CELL)
    _cell, sib, _ = bench.load_cell(SIBLING)
    assert (cfg["n_users"], cfg["n_items"], cfg["n_ratings"]) == (
        4_201_696, 476_002, 7_824_482)
    for key in ("n_users", "n_items", "n_ratings", "rank", "numIterations",
                "lambda", "seed", "gather_dtype", "shape_seed",
                "user_degree_sigma", "item_degree_sigma", "lambda_scaling"):
        assert cfg[key] == sib[key], key
    assert cfg["eventNames"] == ["rate", "buy"]
    assert (cfg["buy_share"], cfg["buy_rating"]) == (0.1, 4.0)
    assert cfg["limits"]["triple_diff"] == cfg["limits"]["ids_wrong"] == 0
    assert traffic["kind"] == "retrain" and len(cfg["guarantees"]) == 4


@pytest.fixture(scope="module")
def controls():
    cfg, traffic, deployment = rehearsal()
    return cfg["limits"], deployment.control(traffic["kind"], cfg, traffic, 5)


@pytest.mark.parametrize("name", CONTROLS)
def test_every_control_reads_over_a_limit(controls, name):
    lim, got = controls
    assert set(got) == set(CONTROLS)
    assert set(got[name]) == COMPARED
    assert any(got[name][k] > lim[k] for k in lim), got[name]


def test_what_the_frobenius_gaps_alone_would_miss(controls):
    lim, got = controls
    dropped = got["fault_one_event_dropped"]
    assert dropped["triple_diff"] == 1 and dropped["ids_wrong"] == 0
    assert dropped["user_fro"] <= lim["user_fro"]
    assert dropped["item_fro"] <= lim["item_fro"]
    assert got["fault_user_under_two_rows"]["ids_wrong"] == 1
    assert got["fault_user_under_two_rows"]["triple_diff"] == 0
    low = got["control_lower_precision"]
    assert low["triple_diff"] == 0 and low["ids_wrong"] == 0
    assert got["fault_buy_rated_1"]["triple_diff"] > 0


def test_a_fault_under_the_timed_path_is_not_correct(capsys, monkeypatch):
    """The DataSource loses the last event it read: the model is trained
    without it, and the read after the window shows it."""
    import bench_eventlog_engine as engine_file

    stock = engine_file.EventLogDataSource.read_training

    def lossy(self, ctx):
        td = stock(self, ctx)
        td.user_idx, td.item_idx, td.rating = (
            td.user_idx[:-1], td.item_idx[:-1], td.rating[:-1])
        return td

    monkeypatch.setattr(engine_file.EventLogDataSource, "read_training",
                        lossy)
    line = run_cell(capsys, seed=11)
    assert line["correct"] is False
    assert line["compared"]["triple_diff"]["value"] == 1


# -- a log that holds what a live log holds --------------------------------------


def read_as_generator_rows(cfg, deployment, key):
    td = deployment._read_again(cfg, key)
    read_u, read_i, wrong = deployment._generator_rows(td.users, td.items)
    return (read_u[td.user_idx], read_i[td.item_idx], td.rating), wrong


@pytest.fixture()
def live_log():
    """The rehearsal's log of one seed plus, through the store's own
    ``insert``: a ``$set`` of a user, a ``rate`` with no target, a ``view``,
    a ``rate`` that is deleted again (its tombstone stays in the log), and
    one more ``rate`` that arrives LAST with the earliest eventTime of all.
    Returns what a train must read: the generator's triples and that one."""
    from incubator_predictionio_tpu.data.storage.datamap import DataMap
    from incubator_predictionio_tpu.data.storage.event import Event

    cfg, _traffic, deployment = rehearsal()
    [key] = deployment.train_inputs(cfg, [77], lambda msg: None)
    st, d = deployment.STATE, deployment.INPUTS[key]
    store, app_id, ev = st["storage"].get_l_events(), d["app_id"], d["events"]
    users, items = st["users"], st["items"]
    early = datetime.datetime(2014, 6, 30, tzinfo=datetime.timezone.utc)

    def event(name, user, item=None, when=early, **props):
        return Event(event=name, entity_type="user", entity_id=users[user],
                     target_entity_type="item" if item is not None else None,
                     target_entity_id=items[item] if item is not None
                     else None, properties=DataMap(props), event_time=when)

    # a pair that no event has
    user = int(ev["user"][0])
    item = int(np.setdiff1d(np.arange(cfg["n_items"]),
                            ev["item"][ev["user"] == user])[0])
    store.insert(event("$set", 0, plan="gold"), app_id)
    store.insert(event("rate", 1, rating=5), app_id)
    store.insert(event("view", 2, 0), app_id)
    gone = store.insert(event("rate", 3, item, rating=1), app_id)
    assert store.delete(gone, app_id)
    store.insert(event("rate", user, item, rating=2,
                       when=early - datetime.timedelta(days=1)), app_id)
    want = (np.append(ev["user"], user), np.append(ev["item"], item),
            np.append(ev["rating"], np.float32(2.0)))
    assert (np.diff(ev["time_ms"]) == 0).any()        # equal eventTimes
    yield cfg, deployment, key, want
    deployment.release(key)


def test_the_read_of_a_live_log_is_the_posted_events(live_log):
    cfg, deployment, key, want = live_log
    got, ids_wrong = read_as_generator_rows(cfg, deployment, key)
    assert ids_wrong == 0
    assert reference_eventlog.triple_diff(got, want, cfg["n_items"]) == 0
    # the late arrival with the earliest time is the first row of its user
    td = deployment._read_again(cfg, key)
    assert td.users.inverse(0) == deployment.STATE["users"][want[0][-1]]


def test_one_rate_event_removed_reads_one(live_log):
    cfg, deployment, key, want = live_log
    store = deployment.STATE["storage"].get_l_events()
    app_id = deployment.INPUTS[key]["app_id"]
    victim = next(iter(store.find(app_id, event_names=["rate"], limit=1)))
    assert store.delete(victim.event_id, app_id)
    got, _wrong = read_as_generator_rows(cfg, deployment, key)
    assert reference_eventlog.triple_diff(got, want, cfg["n_items"]) == 1


def test_release_removes_the_log_and_its_cached_scan():
    cfg, _traffic, deployment = rehearsal()
    [key] = deployment.train_inputs(cfg, [78], lambda msg: None)
    store = deployment.STATE["storage"].get_l_events()
    deployment._read_again(cfg, key)
    path = store._path(deployment.INPUTS[key]["app_id"], None)
    assert os.path.getsize(path) == deployment.INPUTS[key]["bytes"]
    assert path in store._scans
    deployment.release(key)
    assert not os.path.exists(path) and path not in store._scans
    assert key not in deployment.INPUTS


# -- the generator -----------------------------------------------------------------


def test_ids_have_the_data_sets_shape_and_are_bijections():
    users = datagen_eventlog.as_strings(datagen_eventlog.user_ids(100_000))
    items = datagen_eventlog.as_strings(datagen_eventlog.item_ids(50_000))
    assert len(set(users)) == 100_000 and len(set(items)) == 50_000
    assert all(len(s) == 14 and s[0] == "A" and s.isalnum() for s in users)
    assert all(len(s) == 10 and s[:2] == "B0" and s.isalnum() for s in items)
    # a function of the row alone: a larger table starts with the smaller
    assert datagen_eventlog.as_strings(
        datagen_eventlog.user_ids(10)) == users[:10]


@pytest.mark.parametrize("seed", [1, 2_147_483_659, 4_000_000_007])
def test_events_keep_the_degrees_and_no_pair_twice(seed):
    cfg, _traffic, _deployment = rehearsal()
    du, di = datagen.degrees(cfg)
    ev = datagen_eventlog.events(cfg, seed, (du, di))
    assert (np.bincount(ev["user"], minlength=cfg["n_users"]) == du).all()
    assert (np.bincount(ev["item"], minlength=cfg["n_items"]) == di).all()
    pair = ev["user"].astype(np.int64) * cfg["n_items"] + ev["item"]
    assert len(np.unique(pair)) == len(pair) == cfg["n_ratings"]
    assert (np.diff(ev["time_ms"]) >= 0).all()
    assert 0.07 < ev["buy"].mean() < 0.13
    assert (ev["rating"][ev["buy"]] == 4.0).all()
    assert set(np.unique(ev["rating"][~ev["buy"]])) == {1, 2, 3, 4, 5}
    again = datagen_eventlog.events(cfg, seed, (du, di))
    assert all((ev[k] == again[k]).all() for k in ev)


def test_the_bodies_say_what_the_arrays_say_and_the_log_is_the_codecs():
    """Each generated request body is JSON for the events of the arrays,
    and what lands in the log is ``native.ingest_batch``'s canonical
    lines of those bodies: the generator formats no line itself."""
    cfg, _traffic, deployment = rehearsal()
    st = deployment._state(cfg)
    ev = datagen_eventlog.events(cfg, 9, st["degrees"])
    got = []
    for body, n in datagen_eventlog.bodies(ev, st["uid"], st["iid"], 4096):
        rows = json.loads(body)
        assert len(rows) == n <= 4096
        got += rows
    assert len(got) == cfg["n_ratings"]
    for k in (0, 1, 4095, 4096, len(got) - 1):
        row, buy = got[k], bool(ev["buy"][k])
        assert row["event"] == ("buy" if buy else "rate")
        assert row["entityId"] == st["users"][ev["user"][k]]
        assert row["targetEntityId"] == st["items"][ev["item"][k]]
        assert (row["entityType"], row["targetEntityType"]) == (
            "user", "item")
        assert row.get("properties", {}) == (
            {} if buy else {"rating": int(ev["stars"][k])})
        when = datetime.datetime.fromisoformat(
            row["eventTime"].replace("Z", "+00:00"))
        assert int(when.timestamp() * 1000) == ev["time_ms"][k]
    [key] = deployment.train_inputs(cfg, [9], lambda msg: None)
    store = st["storage"].get_l_events()
    with open(store._path(deployment.INPUTS[key]["app_id"], None)) as f:
        lines = [json.loads(line) for line in f]
    deployment.release(key)
    assert len(lines) == len(got)
    for line, row in zip(lines[:50] + lines[-50:], got[:50] + got[-50:]):
        assert len(line.pop("eventId")) == 32 and line.pop("creationTime")
        assert line == dict(row, properties=row.get("properties", {}))


# -- the comparison's own arithmetic -----------------------------------------------


def test_rows_of_counts_missing_unknown_absent_and_repeated_ids():
    ids = ["a", "b", "c", "d"]
    rows, wrong = reference_eventlog.rows_of(ids, ["c", "a", "d", "b"])
    assert rows.tolist() == [2, 0, 3, 1] and wrong == 0
    assert reference_eventlog.rows_of(ids, ["c", "a", "d"])[1] == 1
    assert reference_eventlog.rows_of(ids, ["c", "a", "d", "x"])[1] == 2
    assert reference_eventlog.rows_of(ids, ["c", "a", "d", None])[1] == 2
    assert reference_eventlog.rows_of(ids, ["c", "a", "d", "b", "a"])[1] == 1


def test_triple_diff_is_the_symmetric_difference_of_multisets():
    u, i = np.array([0, 0, 1, 2]), np.array([1, 2, 1, 0])
    r = np.array([4.0, 1.0, 5.0, 3.0], np.float32)
    diff = reference_eventlog.triple_diff
    assert diff((u, i, r), (u[::-1], i[::-1], r[::-1]), 3) == 0
    assert diff((u[1:], i[1:], r[1:]), (u, i, r), 3) == 1
    assert diff((u, i, np.where(r == 4.0, 1.0, r)), (u, i, r), 3) == 2
    twice = (np.append(u, 0), np.append(i, 1), np.append(r, 4.0))
    assert diff(twice, (u, i, r), 3) == 1
    unknown = (np.array([-1, 0, 1, 2]), i, r)
    assert diff(unknown, (u, i, r), 3) == 2
    assert reference_eventlog.first_seen_rows(
        np.array([3, 1, 3, 0, 1])).tolist() == [2, 1, -1, 0]


def sp(sid, parent, name, t0_s, t1_s, **tags):
    return Span("t", sid, parent, name, int(t0_s * 1e9), int(t1_s * 1e9),
                tags or None)


def test_the_reads_metrics_on_a_hand_written_train(monkeypatch):
    tree = [sp(1, None, "train.run", 10.0, 30.0),
            sp(2, 1, "dase.read", 10.0, 20.0),
            sp(3, 2, "store.scan", 10.5, 16.5, source="parse",
               bytes=3_000_000_000, events=7),
            sp(4, 3, "store.parse", 11.0, 16.0, bytes=3_000_000_000),
            sp(5, 2, "store.select", 16.5, 17.0, step="mask"),
            sp(6, 2, "store.select", 17.0, 18.0, step="order"),
            sp(7, 2, "store.index", 18.0, 19.5, users=4, items=2)]
    monkeypatch.setattr(program_spans, "snapshot", lambda: list(tree))
    record = types.SimpleNamespace(window_spans=[("run_train", 9.0, 31.0)])
    read = lambda name: bench.load_module("metrics", name).read(record)
    assert read("dase.read_s") == pytest.approx(10.0)
    assert read("store.scan_s") == pytest.approx(6.0)
    assert read("store.index_s") == pytest.approx(3.0)
    seconds, share, source, parts = store_spans.read_coverage(tree)
    assert (seconds, source) == (pytest.approx(10.0), "parse")
    assert share == pytest.approx(0.9)
    assert parts == pytest.approx({
        "store.scan": 6.0, "store.parse": 5.0, "store.select.mask": 0.5,
        "store.select.order": 1.0, "store.index": 1.5})
    import bench_eventlog_engine as engine_file

    monkeypatch.setitem(engine_file.STORE, "bytes_before_window", 1e9)
    monkeypatch.setattr(store_spans, "counter_value", lambda name: 4e9)
    assert read("store.scan_mb_per_s") == pytest.approx(500.0)


def test_a_program_without_the_spans_leaves_the_metrics_out(monkeypatch):
    """The parent's checkout: a train with ``dase.read`` and nothing
    beneath it, no counter. Nothing is raised, three metrics read nothing."""
    tree = [sp(1, None, "train.run", 10.0, 30.0),
            sp(2, 1, "dase.read", 10.0, 20.0)]
    assert store_spans.counter_value("pio_no_such_counter_total") is None
    monkeypatch.setattr(program_spans, "snapshot", lambda: list(tree))
    monkeypatch.setattr(store_spans, "counter_value", lambda name: None)
    record = types.SimpleNamespace(window_spans=[("run_train", 9.0, 31.0)])
    read = lambda name: bench.load_module("metrics", name).read(record)
    assert read("dase.read_s") == pytest.approx(10.0)
    assert read("store.scan_s") is None and read("store.index_s") is None
    assert read("store.scan_mb_per_s") is None
    assert store_spans.read_coverage(tree) is None
