"""The Similar-Product deployment under the harness, after
``test_eventlog_deployment.py``: its cell's rehearsal is ``correct`` and
reports the two new metrics beside the event-log sibling's from files alone;
every control and planted fault reads over a limit at the rehearsal size,
"one entry an event" (the program before its pairs were counted) among
them; a fault planted under the timed path makes ``correct`` false; the
store's replay of a generated log is the generator's own; the generator's
request bodies say what its arrays say; the reference imports nothing of
the program."""

import collections
import json
import os
import re
import types

import numpy as np
import pytest

import run as bench

from conftest import BENCH, ROOT

import datagen
import datagen_views
import program_spans
import reference_implicit

CELL = "retrain-electronics-views-implicit"
CONFIG = "amazon-electronics-views-simprod128"
SIBLING = "retrain-electronics-eventlog"
NEW_FILES = (
    "cells/retrain-electronics-views-implicit.json",
    "configs/amazon-electronics-views-simprod128.json",
    "deployments/similarproduct-views.py", "engines/bench_simprod_engine.py",
    "lib/datagen_views.py", "lib/reference_implicit.py",
    "metrics/prep.pair_count_s.py", "metrics/store.aggregate_s.py",
    "tests/test_simprod_deployment.py")
NEW_METRICS = {"prep.pair_count_s": "dase", "store.aggregate_s": "event_store"}
COMPARED = {"pairs_diff", "ids_wrong", "categories_wrong", "user_fro",
            "item_fro", "leak", "rank_gap"}
CONTROLS = ("control_lower_precision", "fault_one_entry_an_event",
            "fault_explicit", "fault_no_yty", "fault_alpha_half",
            "fault_one_event_dropped", "fault_reset_ignored")

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
    for exact in ("pairs_diff", "ids_wrong", "categories_wrong", "leak"):
        assert line["compared"][exact] == {"value": 0, "limit": 0}
    assert line["attempted"] >= 1 and line["failed"] == 0
    for name in tuple(NEW_METRICS) + (
            "dase.read_s", "store.scan_s", "store.index_s",
            "dase.outside_als_s", "layout.fill_s", "als.init_s",
            "als.upload_s", "als.loop_s", "als.readback_s", "dase.persist_s",
            "train.window_compiles"):
        assert line["metrics"][name]["value"] >= 0, name
    assert line["metrics"]["store.scan_mb_per_s"]["value"] > 0
    assert line["metrics"]["dase.read_s"]["value"] >= (
        line["metrics"]["store.scan_s"]["value"]
        + line["metrics"]["prep.pair_count_s"]["value"]
        + line["metrics"]["store.aggregate_s"]["value"])
    assert {"retrain_s", "setup_s"} == set(line["end_to_end_seen"])


def test_the_cell_went_in_by_files_and_appended_entries():
    for rel in NEW_FILES:
        assert os.path.exists(os.path.join(BENCH, rel)), rel
    m = manifest()
    assert m["configs"][-1]["name"] == CONFIG
    assert m["configs"][-1]["reduced"] == ["numIterations"]
    cell = m["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, "retrain-fresh", 1)
    assert [p["name"] for p in m["per_layer"][-2:]] == list(NEW_METRICS)
    for p in m["per_layer"][-2:]:
        assert (p["layer"], p["moves"], p["workloads"], p["source"]) == (
            NEW_METRICS[p["name"]], "retrain_s", [CELL], "program_span")
    # appended, and nothing else, to every list the event-log sibling is in
    for x in m["end_to_end"] + m["per_layer"][:-2]:
        cells = x.get("workloads", ())
        assert (CELL in cells) == (SIBLING in cells), x["name"]
        if CELL in cells:
            assert cells[-1] == CELL and cells[-2] == SIBLING
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0


def test_the_full_size_is_the_issues():
    _cell, cfg, traffic = bench.load_cell(CELL)
    _cell, sib, _ = bench.load_cell(SIBLING)
    assert (cfg["n_users"], cfg["n_items"], cfg["n_ratings"]) == (
        4_201_696, 476_002, 7_824_482)
    for key in ("n_users", "n_items", "n_ratings", "rank", "numIterations",
                "lambda", "seed", "gather_dtype", "shape_seed",
                "user_degree_sigma", "item_degree_sigma", "lambda_scaling"):
        assert cfg[key] == sib[key], key
    assert cfg["eventNames"] == ["view"] and cfg["alpha"] == 1.0
    assert (cfg["view_repeat_p"], cfg["view_count_cap"]) == (0.2, 20)
    assert (cfg["reset_share"], cfg["unset_share"], cfg["delete_share"]) == (
        0.02, 0.005, 0.001)
    assert cfg["queries"] == 256 and len(datagen_views.CATEGORIES) == 24
    for exact in ("pairs_diff", "ids_wrong", "categories_wrong", "leak"):
        assert cfg["limits"][exact] == 0
    assert set(cfg["limits"]) == COMPARED
    assert traffic["kind"] == "retrain" and len(cfg["guarantees"]) == 5
    # the counts are the configuration's alone: every seed's log is as long
    counts = datagen_views.view_counts(dict(cfg, n_ratings=200_000))
    assert 1.24 < counts.mean() < 1.26 and counts.max() <= 20
    assert abs((counts == 1).mean() - 0.8) < 0.01


@pytest.fixture(scope="module")
def simprod_controls():
    cfg, traffic, deployment = rehearsal()
    return cfg["limits"], deployment.control(traffic["kind"], cfg, traffic, 5)


@pytest.mark.parametrize("name", CONTROLS)
def test_every_control_reads_over_a_limit(simprod_controls, name):
    lim, got = simprod_controls
    assert set(got) == set(CONTROLS)
    assert set(got[name]) == COMPARED - {"leak", "rank_gap"}
    assert any(v > lim[k] for k, v in got[name].items()), got[name]


def test_what_each_number_alone_would_miss(simprod_controls):
    lim, got = simprod_controls
    # today's program: the read shows it, and so do the factors
    as_given = got["fault_one_entry_an_event"]
    assert as_given["pairs_diff"] > 1000
    assert as_given["user_fro"] > 3 * lim["user_fro"]
    assert as_given["item_fro"] > 3 * lim["item_fro"]
    # one view less in 18,738 moves no factor over a limit: the count does
    dropped = got["fault_one_event_dropped"]
    assert dropped["pairs_diff"] == 1
    # a replay that keeps the first $set trains the same factors
    kept = got["fault_reset_ignored"]
    assert kept["categories_wrong"] > 0
    assert (kept["pairs_diff"], kept["user_fro"], kept["item_fro"]) == (
        0, 0.0, 0.0)
    for name in ("control_lower_precision", "fault_explicit", "fault_no_yty",
                 "fault_alpha_half"):
        assert got[name]["pairs_diff"] == got[name]["categories_wrong"] == 0
        assert max(got[name]["user_fro"] / lim["user_fro"],
                   got[name]["item_fro"] / lim["item_fro"]) > 1, name


def test_a_fault_under_the_timed_path_is_not_correct(capsys, monkeypatch):
    """The DataSource as it was before this configuration: one entry an
    event. The model is trained on them, and both the read after the
    window and the factors show it."""
    import bench_simprod_engine as engine_file
    from incubator_predictionio_tpu.models import similar_product

    monkeypatch.setattr(similar_product, "count_pairs",
                        lambda u, i, r, n_items: (u, i, r))
    line = run_cell(capsys, seed=11)
    assert engine_file.REQUIRES
    assert line["correct"] is False
    assert line["compared"]["pairs_diff"]["value"] > 1000
    assert line["compared"]["item_fro"]["value"] > \
        line["compared"]["item_fro"]["limit"]
    assert line["compared"]["ids_wrong"]["value"] == 0


# -- the generator against the store ----------------------------------------------


@pytest.fixture(scope="module")
def views_log():
    cfg, _traffic, deployment = rehearsal()
    [key] = deployment.train_inputs(cfg, [77], lambda msg: None)
    yield cfg, deployment, key
    deployment.release(key)


def test_the_stores_replay_is_the_generators(views_log):
    """``aggregate_properties`` over the generated log against
    ``final_members``: the last ``$set`` wins, an ``$unset`` item has no
    categories, a ``$delete``d item has those of its ``$set`` anew."""
    from incubator_predictionio_tpu.data.store.p_event_store import (
        PEventStore,
    )

    cfg, deployment, key = views_log
    ev = deployment.INPUTS[key]["events"]
    assert len(ev["reset"]) == 30 and len(ev["unset"]) == 8
    assert len(ev["deleted"]) == 2
    props = PEventStore.aggregate_properties(
        key, "item", storage=deployment.STATE["storage"])
    got = {k: set(v.get_opt("categories") or ()) for k, v in props.items()}
    members = datagen_views.final_members(ev)
    assert deployment.categories_wrong(
        {k: v for k, v in got.items() if v}, members) == 0
    ids = deployment.STATE["items"]
    for row in ev["unset"]:
        assert got[ids[row]] == set() and not members[row].any()
    names = np.asarray(datagen_views.CATEGORIES, object)
    for row in np.concatenate([ev["reset"], ev["deleted"]]):
        again = ev["again"][row]
        assert got[ids[row]] == set(names[again[again >= 0]])
    # and the control's replay differs on the re-$set items alone
    kept = datagen_views.final_members(ev, ignore_reset=True)
    differs = np.nonzero((kept != members).any(axis=1))[0]
    assert set(differs) <= set(ev["reset"]) and len(differs) > 20
    assert deployment.categories_wrong(
        {k: v for k, v in got.items() if v}, kept) == len(differs)


def test_the_read_is_the_counted_pairs_in_first_seen_order(views_log):
    cfg, deployment, key = views_log
    ev = deployment.INPUTS[key]["events"]
    td = deployment._read_again(cfg, key)
    row_of_user, row_of_item = deployment._first_seen(ev)
    read_u, wrong_u = deployment._rows_wrong(
        deployment.STATE["users"], td.users, row_of_user)
    read_i, wrong_i = deployment._rows_wrong(
        deployment.STATE["items"], td.items, row_of_item)
    assert wrong_u == wrong_i == 0
    assert len(td.rating) == cfg["n_ratings"] < int(ev["pairs"][2].sum())
    got = (read_u[td.user_idx], read_i[td.item_idx], td.rating)
    assert reference_implicit.pairs_diff(got, ev["pairs"],
                                         cfg["n_items"]) == 0
    vu, vi = datagen_views.views_of(ev)
    assert reference_implicit.pairs_diff(
        (vu, vi, np.ones(len(vu), np.float32)), ev["pairs"],
        cfg["n_items"]) == int((ev["pairs"][2] > 1).sum())
    pu, pi, pc = reference_implicit.pair_counts(vu, vi, cfg["n_items"])
    assert reference_implicit.pairs_diff((pu, pi, pc), ev["pairs"],
                                         cfg["n_items"]) == 0


@pytest.mark.parametrize("seed", [1, 2_147_483_659, 4_000_000_007])
def test_events_keep_the_degrees_and_every_item_is_set_before_its_view(seed):
    cfg, _traffic, _deployment = rehearsal()
    du, di = datagen.degrees(cfg)
    ev = datagen_views.events(cfg, seed, (du, di))
    pu, pi, pc = ev["pairs"]
    assert (np.bincount(pu, minlength=cfg["n_users"]) == du).all()
    assert (np.bincount(pi, minlength=cfg["n_items"]) == di).all()
    assert len(np.unique(pu.astype(np.int64) * cfg["n_items"] + pi)) == \
        len(pu) == cfg["n_ratings"]
    assert (pc == datagen_views.view_counts(cfg)).all()
    kind, item = ev["kind"], ev["item"]
    assert int((kind == datagen_views.VIEW).sum()) == int(pc.sum())
    assert (np.diff(ev["time_ms"]) >= 0).all()
    first_set = np.full(cfg["n_items"], len(kind))
    sets = np.nonzero(kind == datagen_views.SET)[0][::-1]
    first_set[item[sets]] = sets
    first_view = np.full(cfg["n_items"], len(kind))
    at = np.nonzero(kind == datagen_views.VIEW)[0][::-1]
    first_view[item[at]] = at
    assert (first_set < first_view).all()
    for rows, what in ((ev["unset"], datagen_views.UNSET),
                       (ev["deleted"], datagen_views.DELETE)):
        at = np.nonzero(kind == what)[0]
        assert sorted(item[at]) == sorted(rows)
        assert (at > first_view[item[at]]).all()
    again = datagen_views.events(cfg, seed, (du, di))
    assert all((ev[k] == again[k]).all() for k in ("kind", "user", "item",
                                                   "combo", "time_ms"))


def test_the_bodies_say_what_the_arrays_say():
    cfg, _traffic, deployment = rehearsal()
    st = deployment._state(cfg)
    ev = datagen_views.events(cfg, 9, st["degrees"])
    got = []
    for body, n in datagen_views.bodies(ev, st["uid"], st["iid"], 4096):
        rows = json.loads(body)
        assert len(rows) == n <= 4096
        got += rows
    assert len(got) == len(ev["kind"])
    names = np.asarray(datagen_views.CATEGORIES, object)
    event_of = {datagen_views.VIEW: "view", datagen_views.SET: "$set",
                datagen_views.UNSET: "$unset", datagen_views.DELETE: "$delete"}
    special = np.nonzero(ev["kind"] != datagen_views.VIEW)[0]
    for k in [0, 1, 4095, 4096, len(got) - 1] + special[:40].tolist() \
            + special[-40:].tolist():
        row, kind = got[k], int(ev["kind"][k])
        assert row["event"] == event_of[kind]
        item_id = st["items"][ev["item"][k]]
        if kind == datagen_views.VIEW:
            assert (row["entityType"], row["entityId"]) == (
                "user", st["users"][ev["user"][k]])
            assert (row["targetEntityType"], row["targetEntityId"]) == (
                "item", item_id)
            assert "properties" not in row
            continue
        assert (row["entityType"], row["entityId"]) == ("item", item_id)
        assert "targetEntityId" not in row
        combo = ev["combo"][k]
        assert row.get("properties") == {
            datagen_views.SET: {"categories": list(names[combo[combo >= 0]])},
            datagen_views.UNSET: {"categories": None},
            datagen_views.DELETE: None}[kind]


# -- the reference's own arithmetic ------------------------------------------------


def test_the_reference_imports_nothing_of_the_program():
    for rel in ("lib/reference_implicit.py", "lib/datagen_views.py"):
        with open(os.path.join(BENCH, rel)) as f:
            source = f.read()
        assert not re.search(r"^\s*(import|from)\s+incubator_predictionio_tpu",
                             source, re.M), rel
        assert "open(" not in source, rel
    doc = reference_implicit.__doc__
    for departure in ("gather_dtype", "lambda is plain", "default_rng"):
        assert departure in doc


def test_pairs_diff_counts_pairs_that_are_counted_differently():
    u, i = np.array([0, 0, 1, 2]), np.array([1, 2, 1, 0])
    c = np.array([3.0, 1.0, 2.0, 1.0], np.float32)
    diff = reference_implicit.pairs_diff
    assert diff((u[::-1], i[::-1], c[::-1]), (u, i, c), 3) == 0
    assert diff((u[1:], i[1:], c[1:]), (u, i, c), 3) == 1
    assert diff((u, i, np.array([2.0, 1.0, 2.0, 1.0])), (u, i, c), 3) == 1
    # three entries of 1 are not one entry of 3
    apart = (np.array([0, 0, 0, 0, 1, 2]), np.array([1, 1, 1, 2, 1, 0]),
             np.array([1.0, 1.0, 1.0, 1.0, 2.0, 1.0]))
    assert diff(apart, (u, i, c), 3) == 1
    assert diff((np.array([-1, 0, 1, 2]), i, c), (u, i, c), 3) == 2


def test_similar_gaps_sees_a_leak_a_worse_item_and_a_short_answer():
    rng = np.random.default_rng(0)
    unit = reference_implicit.unit_rows(rng.standard_normal((400, 8)))
    members = rng.random((400, 24)) < 0.1
    q = {"items": np.array([5, 9]), "num": 4, "categories": [2],
         "white": None, "black": np.array([7])}
    ref = reference_implicit.top_similar(unit, members, q)
    assert not set(ref["items"]) & {5, 7, 9}
    assert members[ref["items"], 2].all()
    assert (np.diff(ref["scores"]) <= 0).all()
    good = {"items": ref["items"][:4].tolist(),
            "scores": ref["scores"][:4].tolist()}
    gaps = reference_implicit.similar_gaps
    assert gaps(unit, members, [q], [good]) == {
        "leak": 0, "rank_gap": 0.0, "compared": 1}
    outside = int(np.nonzero(~members[:, 2])[0][0])
    for bad in (7, 5, outside):
        leaky = dict(good, items=good["items"][:3] + [bad])
        assert gaps(unit, members, [q], [leaky])["leak"] == 1
    worse = dict(good, items=good["items"][:3] + [int(ref["items"][6])])
    assert gaps(unit, members, [q], [worse])["rank_gap"] > 1e-3
    short = {"items": good["items"][:3], "scores": good["scores"][:3]}
    assert gaps(unit, members, [q], [short])["rank_gap"] == float("inf")
    unknown = dict(good, items=good["items"][:3] + [-1])
    assert gaps(unit, members, [q], [unknown])["leak"] == 1


def sp(sid, parent, name, t0_s, t1_s, **tags):
    return Span("t", sid, parent, name, int(t0_s * 1e9), int(t1_s * 1e9),
                tags or None)


def test_the_new_metrics_on_a_hand_written_train(monkeypatch):
    _cfg, _traffic, deployment = rehearsal()
    tree = [sp(1, None, "train.run", 10.0, 40.0),
            sp(2, 1, "dase.read", 10.0, 30.0),
            sp(3, 2, "store.scan", 10.0, 16.0, source="parse"),
            sp(4, 3, "store.parse", 11.0, 15.0),
            sp(5, 2, "store.select", 16.0, 17.0, step="mask"),
            sp(6, 2, "store.select", 17.0, 18.0, step="order"),
            sp(7, 2, "store.index", 18.0, 22.0, users=4, items=2),
            sp(8, 2, "prep.pair_counts", 22.0, 24.0, events=9, pairs=7),
            sp(9, 2, "store.aggregate", 24.5, 29.0, events=3, entities=2,
               source="cached"),
            sp(10, 9, "store.scan", 24.5, 24.6, source="cached"),
            sp(11, 9, "store.select", 24.6, 25.0, step="mask"),
            sp(12, 1, "store.scan", 31.0, 32.0, source="cached")]
    monkeypatch.setattr(program_spans, "snapshot", lambda: list(tree))
    record = types.SimpleNamespace(window_spans=[("run_train", 9.0, 41.0)])
    read = lambda name: bench.load_module("metrics", name).read(record)
    assert read("prep.pair_count_s") == pytest.approx(2.0)
    assert read("store.aggregate_s") == pytest.approx(4.5)
    seconds, share, parts = deployment.read_coverage(tree)
    assert seconds == pytest.approx(20.0)
    # the union: the aggregate's own scan and mask lie inside it, the
    # scan after the read is not the read's
    assert share == pytest.approx(0.925)
    assert parts["store.aggregate.cached"] == pytest.approx(4.5)
    assert parts["store.scan.cached"] == pytest.approx(0.1)
    assert parts["prep.pair_counts"] == pytest.approx(2.0)


def test_a_program_without_the_spans_leaves_the_metrics_out(monkeypatch):
    """The parent's checkout under this PR's benchmark files: a train with
    no ``prep.pair_counts`` and no ``store.aggregate``. Nothing is raised,
    the two metrics read nothing."""
    tree = [sp(1, None, "train.run", 10.0, 30.0),
            sp(2, 1, "dase.read", 10.0, 20.0),
            sp(3, 2, "store.scan", 10.0, 16.0, source="parse")]
    monkeypatch.setattr(program_spans, "snapshot", lambda: list(tree))
    record = types.SimpleNamespace(window_spans=[("run_train", 9.0, 31.0)])
    read = lambda name: bench.load_module("metrics", name).read(record)
    assert read("prep.pair_count_s") is None
    assert read("store.aggregate_s") is None
    monkeypatch.setattr(program_spans, "snapshot", lambda: [])
    assert read("prep.pair_count_s") is None
    assert read("store.aggregate_s") is None
