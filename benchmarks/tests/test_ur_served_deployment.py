"""The served Universal Recommender deployment under the harness: its cell's
rehearsal is ``correct`` and reports its metrics from files alone, the
block-wise reference agrees with the definition written out densely, each
control and planted fault reads over a limit at the rehearsal size, a fault
planted under the timed path makes ``correct`` false, the shape of a request
is its place in the mix's arrival cycle, the work of a user's query is the
same in every seed, and the new metrics read nothing where the program has
nothing to read. What the manifest holds is checked by MEMBERSHIP, never by
position, so that a later cell reds nothing here."""

import collections
import json
import os
import types

import numpy as np
import pytest

import run as bench

from conftest import BENCH, ROOT

CELL = "serve-ur9m-history-p4"
CONFIG = "amazon-catalog9m-ur50"
NEW_FILES = {
    "cells/serve-ur9m-history-p4.json", "traffic/queries-ur-p4.json",
    "configs/amazon-catalog9m-ur50.json", "deployments/ur-served.py",
    "engines/bench_ur_serve_engine.py", "lib/datagen_ur_serve.py",
    "lib/reference_ur_serve.py", "lib/work_ur.py",
    "metrics/ur.score_call_ms.py", "metrics/ur.score_roofline.py",
    "metrics/ur.serve_step_mfu.py", "metrics/ur.backfill_ms.py",
    "tests/test_ur_served_deployment.py"}
NEW_METRICS = ("ur.score_call_ms", "ur.score_roofline", "ur.serve_step_mfu",
               "ur.backfill_ms")
#: the serving metrics the benchmark had that read in this cell too
SHARED_METRICS = (
    "serve.store_read_ms", "serve.admit_wait_ms", "serve.host_ms",
    "serve.busy_host_share", "serve.window_compiles",
    "device.idle_share.serve", "loadgen.late_ms", "serve.loop_lag_ms",
    "serve.stall_ms", "serve.gc_pause_ms", "serve.device_wait_ms",
    "serve.mask_build_ms", "serve.mask_put_ms")
#: those that cannot: they read the ALS scan
NOT_HERE = ("serve.step_mfu", "topk_roofline", "serve.topk_call_ms")
COMPARED = {"rank_gap", "score_gap", "leak", "fill_gap", "malformed",
            "unanswered"}

Span = collections.namedtuple(
    "Span", "trace_id span_id parent_id name t0_ns t1_ns tags")


def sp(trace, sid, parent, name, t0_ms, t1_ms, **tags):
    return Span(trace, sid, parent, name, int(t0_ms * 1e6), int(t1_ms * 1e6),
                tags or None)


def request(trace, sid, t0_s):
    """An answered query of the recommendation template: an ALS scan, no
    span of the Universal Recommender's."""
    t0 = t0_s * 1e3
    return [sp(trace, sid, None, "http POST /queries.json", t0, t0 + 60,
               status=200),
            sp(trace, sid + 1, sid, "query.predict", t0 + 5, t0 + 52),
            sp(trace, sid + 2, sid + 1, "topk.dispatch", t0 + 6, t0 + 10),
            sp(trace, sid + 3, sid + 1, "topk.wait", t0 + 10, t0 + 50)]


def run_cell(capsys, trace=0, seed=123):
    rc = bench.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "2", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def rehearsal():
    _cell, cfg, traffic = bench.load_cell(CELL, rehearse=True)
    return cfg, traffic, bench.load_module("deployments", cfg["deployment"])


def test_rehearsal_is_correct_and_reports_its_metrics(capsys):
    line = run_cell(capsys, trace=1, seed=2_147_483_659)
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == COMPARED
    assert line["attempted"] == 80 and line["failed"] == 0
    # the trace-fed two and the share of a peak need a chip
    for name in ("ur.score_call_ms", "ur.backfill_ms", "serve.store_read_ms",
                 "serve.device_wait_ms", "serve.host_ms",
                 "serve.admit_wait_ms", "serve.busy_host_share",
                 "serve.mask_build_ms", "serve.window_compiles",
                 "loadgen.late_ms"):
        assert line["metrics"][name]["value"] >= 0, name
    assert line["metrics"]["serve.window_compiles"]["value"] == 0
    assert not set(NOT_HERE) & set(line["metrics"])
    # the program's own count of the postings it read since the bodies
    # were made is the deployment's count of what the requests need, to the
    # posting: the window's, and the load generator's first eight again,
    # which it sends over its connections before the window
    import store_spans
    import work_ur

    needed = work_ur.WINDOW["postings"]
    assert (store_spans.counter_value(work_ur.POSTINGS_READ)
            - work_ur.WINDOW["program_postings_before"]
            == sum(needed) + sum(needed[:8]) > 0)
    assert {"query_p50_ms", "query_p95_ms", "setup_s"} == set(
        line["end_to_end_seen"])


def test_the_cell_went_in_by_files_and_appended_entries():
    """By membership: every new file is there, the manifest holds the
    cell, its configuration and its four metrics, and the cell is on the
    list of every serving metric that reads in it and of none that
    cannot."""
    for rel in NEW_FILES:
        assert os.path.exists(os.path.join(BENCH, rel)), rel
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL]["config"] == CONFIG and cells[CELL]["chips"] == 1
    assert CONFIG in {c["name"] for c in manifest["configs"]}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
    for name in SHARED_METRICS:
        assert CELL in by_name[name]["workloads"], name
    for name in NOT_HERE:
        assert CELL not in by_name[name]["workloads"], name
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for name in ("query_p50_ms", "query_p95_ms"):
        assert CELL in e2e[name]["workloads"]
    assert by_name["ur.score_roofline"]["layer"] == "kernels"


def test_the_full_size_is_the_issues():
    _cell, cfg, traffic = bench.load_cell(CELL)
    assert (cfg["n_items"], cfg["maxCorrelatorsPerItem"], cfg["score_dtype"],
            cfg["eventNames"]) == (9_400_000, 50, "float32", ["buy", "view"])
    assert cfg["reduced"] == ["n_users"] and cfg["n_users"] == 1_000_000
    assert (cfg["events_per_user"], cfg["buy_share"], cfg["categories"]) == (
        3.9, 0.2, 24)
    assert traffic["shape_shares"] == {
        "user": 0.55, "filter": 0.15, "boost": 0.05, "item": 0.15,
        "blacklist": 0.05, "unknown": 0.05}
    assert traffic["unknown_user_share"] == 0.05
    assert (traffic["black_list_len"], traffic["black_list_top"]) == (
        [1, 20], 20)
    assert "hot_users" not in cfg and "hot_top" not in cfg
    assert traffic["num_shares"] == [[20, 0.8], [4, 0.2]]
    assert (traffic["connections"], traffic["compared_requests"]) == (64, 256)
    # a quarter of the knee, or the one allowed step to 0.15 of it
    assert round(traffic["rate_qps"] / traffic["knee_qps"], 2) in (0.25, 0.15)
    # 7.52 GB of indicators: 46% of the chip with what lies beside them
    slots = cfg["n_items"] * cfg["maxCorrelatorsPerItem"] * len(
        cfg["eventNames"])
    assert slots * 8 == 7_520_000_000


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_span_reads_nothing(name, monkeypatch):
    import program_spans
    import work_ur

    record = types.SimpleNamespace(
        window={"summary": {"attempted": 2}}, trace=None, peaks={})
    monkeypatch.setattr(program_spans, "snapshot",
                        lambda: request(1, 10, 0.0) + request(2, 20, 0.5))
    monkeypatch.setattr(work_ur, "WINDOW", {})
    assert bench.load_module("metrics", name).read(record) is None
    record.window = {}
    assert bench.load_module("metrics", name).read(record) is None


def test_new_metrics_read_the_programs_spans_and_the_needed_work(monkeypatch):
    import program_spans
    import work
    import work_ur

    ring = request(1, 10, 0.0) + request(2, 30, 0.5) + request(3, 50, 1.0) + [
        sp(1, 17, 11, "ur.score", 6.0, 9.0, rows=3, postings=1000),
        sp(2, 37, 31, "ur.backfill", 506.0, 507.5, k=20),
        sp(3, 57, 51, "ur.score", 1006.0, 1007.0, rows=1, postings=10)]
    monkeypatch.setattr(program_spans, "snapshot", lambda: ring)
    peaks = work.peaks_for("TPU v5 lite")
    import store_spans

    monkeypatch.setattr(work_ur, "WINDOW", {
        "postings": [1_000_000, 0, 3_000_000],
        "path": ["history", "backfill", "history"],
        "program_postings_before": 500})
    read_by_program = {work_ur.POSTINGS_READ: 500 + 4_000_000}
    monkeypatch.setattr(store_spans, "counter_value", read_by_program.get)
    record = types.SimpleNamespace(
        peaks=peaks, window={
            "summary": {"attempted": 3}, "wall_s": 2.0,
            "job": {"num": [20, 20, 4]},
            "result": {"status": [200, 200, 200]}},
        trace={"module_seconds": {"jit__ur_score(1)": 0.004,
                                  "jit__ur_rank(2)": 0.003,
                                  "jit__topk_scores(3)": 1.0},
               "module_counts": {"jit__ur_score(1)": 2, "jit__ur_rank(2)": 3,
                                 "jit__topk_scores(3)": 9}})
    read = lambda name: bench.load_module("metrics", name).read(record)
    assert read("ur.score_call_ms") == pytest.approx(2.0)
    assert read("ur.backfill_ms") == pytest.approx(1.5)
    a_posting = 8 / peaks["hbm_bytes_per_s"]
    least = (4_000_000 * 8 + (20 + 20 + 4) * 8) / peaks["hbm_bytes_per_s"]
    assert read("ur.serve_step_mfu") == pytest.approx(100 * least / 2.0)
    # a call is one sum (2 ms) and one selection (1 ms)
    scored = (2_000_000 + 12 * 8 / 8) * a_posting
    assert read("ur.score_roofline") == pytest.approx(
        100 * scored / 0.003, rel=1e-6)
    assert 0 < read("ur.score_roofline") < 100
    # a program that read more than the answers need: the needed count
    read_by_program[work_ur.POSTINGS_READ] += 1_000_000
    assert read("ur.serve_step_mfu") == pytest.approx(100 * least / 2.0)
    # a request that failed needs nothing, and the program read nothing
    record.window["result"]["status"][2] = 503
    read_by_program[work_ur.POSTINGS_READ] = 500 + 1_000_000
    assert read("ur.serve_step_mfu") == pytest.approx(
        100 * (1_000_000 * 8 + 40 * 8) / peaks["hbm_bytes_per_s"] / 2.0)
    # work the program did not do is not counted either
    read_by_program[work_ur.POSTINGS_READ] = 500 + 600_000
    assert read("ur.serve_step_mfu") == pytest.approx(
        100 * (600_000 * 8 + 40 * 8) / peaks["hbm_bytes_per_s"] / 2.0)
    # a program without the counter
    del read_by_program[work_ur.POSTINGS_READ]
    assert read("ur.serve_step_mfu") is None


def test_needed_work_counts_postings_and_answers_alone():
    import work
    import work_ur

    peaks = work.peaks_for("TPU v5 lite")
    w = work_ur.query_work(1000, 20)
    assert w == {"bytes": 8 * 1000 + 8 * 20, "flops": 2000.0}
    assert work_ur.least_seconds(1000, 20, peaks) == pytest.approx(
        w["bytes"] / peaks["hbm_bytes_per_s"])     # the bytes bind
    assert "merely chooses" in work_ur.__doc__


# -- the generator -------------------------------------------------------------


def test_a_shape_is_a_place_in_the_arrival_cycle_whatever_the_seed():
    import datagen_ur_serve
    import loadgen

    _cfg, traffic, _dep = rehearsal()
    by_gap = None
    for seed, rate in ((1, 48.0), (2_900_000_777, 48.0), (2**31 + 5, 48.0),
                       (77, 120.0)):
        mix = dict(traffic, rate_qps=rate)
        seconds = 30.0 * 48.0 / rate            # 1,440 rows at every rate
        sched = loadgen.schedule(mix, 2000, seed, seconds)
        shapes = datagen_ur_serve.shapes_of(mix, sched)
        assert collections.Counter(shapes) == {
            "user": 792, "filter": 216, "boost": 72, "item": 216,
            "blacklist": 72, "unknown": 72}
        assert all((s == "unknown") == (not u.isdigit())
                   for s, u in zip(shapes, sched["user"]))
        gaps = np.round(np.diff(sched["due"]) * rate, 6)
        seen = dict(zip(gaps.tolist(), shapes[1:]))
        if by_gap is None:
            by_gap = seen
        shared = set(seen) & set(by_gap)
        assert len(shared) >= len(gaps) - 1
        assert all(seen[g] == by_gap[g] for g in shared)


def test_every_blacklist_is_of_its_users_own_best_twenty():
    """Whoever the schedule gives the blacklist shape, hot or not: the ids
    are 1-20 of what ``{user, num: 20}`` has to answer, by the definition
    written out densely; and the pass over the indicators drawn again (a
    run's) gives what the pass over the held arrays (a control's) gives."""
    import datagen_ur_serve
    import loadgen
    import reference_ur_serve as ref

    cfg, traffic, dep = rehearsal()
    seed = 2_147_483_999
    g = dep.generate(cfg, seed)
    sched = loadgen.schedule(traffic, cfg["n_users"], seed, 30.0)
    fields, _slots = dep.fields_of(g, traffic, sched,
                                   dep.held(cfg, g["indicators"]))
    again, _slots = dep.fields_of(g, traffic, sched,
                                  datagen_ur_serve.each_block(cfg, seed))
    asked = [q for q in fields if q["shape"] == "blacklist"]
    assert len(asked) == round(0.05 * len(sched["due"]))
    assert len({q["user"] for q in asked}) > 10       # not the hottest alone
    for q, same in zip(asked, (q for q in again if q["shape"] == "blacklist")):
        np.testing.assert_array_equal(q["blacklist"], same["blacklist"])
        own, _scores = ref.dense_top(
            cfg, g["indicators"], g["popularity"], g["cats"],
            dep.request_of(g, {"user": q["user"]}, 20))
        lo, hi = traffic["black_list_len"]
        assert lo <= len(q["blacklist"]) <= hi
        assert len(set(q["blacklist"].tolist())) == len(q["blacklist"])
        assert set(q["blacklist"].tolist()) <= set(own[:20].tolist())


def test_the_work_of_a_users_query_is_the_seeds_in_ids_only():
    """Counts, buy flags and popularity ranks are the shape's; the seed
    moves every id (a bijection of the ranks) and every correlator."""
    import datagen_ur_serve

    cfg, _traffic, _dep = rehearsal()
    ev = datagen_ur_serve.user_events(cfg)
    counts = np.diff(ev["offsets"])
    assert counts.min() >= 1 and counts.max() <= cfg["seen_limit"]
    assert abs(counts.mean() - cfg["events_per_user"]) < 0.3
    assert 0.17 < ev["buy"].mean() < 0.23
    again = datagen_ur_serve.user_events(cfg)
    np.testing.assert_array_equal(ev["rank"], again["rank"])
    ranks = np.arange(cfg["n_items"])
    a, b = (datagen_ur_serve.items_of(ranks, cfg, s) for s in (1, 2**31 + 9))
    assert sorted(a.tolist()) == ranks.tolist() == sorted(b.tolist())
    assert (a != b).mean() > 0.99
    # popular ranks are asked for most: the first hundredth of the ranks
    # holds over a fifth of the events at s = 0.75
    assert (ev["rank"] < cfg["n_items"] // 100).mean() > 0.2


def test_indicator_rows_are_distinct_padded_and_fall():
    import datagen_ur_serve

    cfg, _traffic, _dep = rehearsal()
    forward, named = datagen_ur_serve.indicators(cfg, 2**31 + 3)
    assert set(forward) == set(cfg["eventNames"])
    for name, (idx, score) in forward.items():
        assert idx.shape == (cfg["n_items"], cfg["maxCorrelatorsPerItem"])
        assert idx.dtype == np.int32 and score.dtype == np.float32
        live = idx >= 0
        # -1 only at a row's end, and a stated share of short rows
        assert (live[:, :-1] >= live[:, 1:]).all()
        short = 1.0 - live.all(axis=1).mean()
        assert cfg["short_row_share"] - 0.05 < short < 0.65
        for row in idx[:200]:
            row = row[row >= 0]
            assert len(set(row.tolist())) == len(row)
        assert (score[live] > 0).all() and (score[~live] == 0).all()
        assert (np.diff(score, axis=1)[live[:, 1:]] <= 0).all()
        # how many rows name an item: as skewed as its popularity
        np.testing.assert_array_equal(
            named[name], np.bincount(idx[live], minlength=cfg["n_items"]))
        assert named[name].max() > 50 * np.median(named[name]) > 0
    again = list(datagen_ur_serve.indicator_blocks(cfg, 2**31 + 3, 1))
    np.testing.assert_array_equal(
        np.concatenate([b[1] for b in again]), forward["view"][0])


# -- the reference -------------------------------------------------------------


def small_case(seed=21, n=3000, k=12):
    rng = np.random.default_rng(seed)
    cfg = {"n_items": n, "eventNames": ["buy", "view"]}
    forward = {}
    for e in cfg["eventNames"]:
        idx = rng.integers(-1, n, (n, k)).astype(np.int32)
        idx[:, k // 2:][rng.random((n, k - k // 2)) < 0.4] = -1
        forward[e] = (idx, (rng.integers(1, 64, (n, k)) / 8).astype(
            np.float32))
    pop = rng.integers(0, 9, n).astype(np.float32)
    cats = rng.integers(0, 4, n).astype(np.uint8)
    requests = [
        {"history": {"buy": [5, 9, 9], "view": [7, 100, 2000]}, "item": None,
         "category": None, "bias": -1.0, "blacklist": (), "num": 20},
        {"history": {}, "item": 17, "category": None, "bias": -1.0,
         "blacklist": (), "num": 4},
        {"history": {"buy": [], "view": [3, 4, 5, 6]}, "item": 11,
         "category": 2, "bias": -1.0, "blacklist": (), "num": 20},
        {"history": {"buy": [1], "view": [2]}, "item": None, "category": 1,
         "bias": 2.0, "blacklist": (), "num": 20},
        {"history": {"buy": [1, 8], "view": [2]}, "item": None,
         "category": None, "bias": -1.0, "blacklist": (), "num": 20},
        {"history": {}, "item": None, "category": None, "bias": -1.0,
         "blacklist": (), "num": 20},
        {"history": {}, "item": None, "category": 3, "bias": -1.0,
         "blacklist": (), "num": 4},
    ]
    return cfg, forward, pop, cats, requests


def blocks_of(forward, cfg, rows=700):
    import reference_ur_serve as ref

    def blocks(number):
        idx, score = forward[cfg["eventNames"][number]]
        for lo in range(0, len(idx), rows):
            yield lo, idx[lo:lo + rows], score[lo:lo + rows]
    return ref.in_turn(blocks, cfg["eventNames"])


def test_reference_in_blocks_against_the_definition_written_out():
    import reference_ur_serve as ref

    cfg, forward, pop, cats, requests = small_case()
    best, _scores = ref.dense_top(cfg, forward, pop, cats, requests[4])
    requests[4]["blacklist"] = best[:6:2]
    slots = ref.gather(cfg, blocks_of(forward, cfg), requests)
    for q in requests:
        want_items, want_scores = ref.dense_top(cfg, forward, pop, cats, q)
        got = ref.top(cfg, slots, pop, cats, q)
        np.testing.assert_array_equal(got["items"], want_items)
        np.testing.assert_allclose(got["scores"], want_scores, rtol=1e-12)
        assert len(got["items"]) > 0
    assert len(ref.top(cfg, slots, pop, cats, requests[0])["items"]) == \
        ref.MAX_NUM


def test_gaps_see_each_kind_of_wrong_answer():
    import reference_ur_serve as ref

    cfg, forward, pop, cats, requests = small_case()
    requests[4]["blacklist"] = np.array([40, 41])
    slots = ref.gather(cfg, blocks_of(forward, cfg), requests)
    tops = [ref.top(cfg, slots, pop, cats, q) for q in requests]
    right = [ref.answer_of(t, q["num"]) for t, q in zip(tops, requests)]

    def read(served):
        got = ref.gaps(cfg, slots, pop, cats, requests, served)
        return {k: v for k, v in got.items() if v and k != "compared"}

    assert read(right) == {}
    assert ref.gaps(cfg, slots, pop, cats, requests, right)["compared"] == 7

    def altered(k, **change):
        out = [dict(a) for a in right]
        out[k].update(change)
        return out

    cut = {"items": right[0]["items"][:-1], "scores": right[0]["scores"][:-1]}
    assert read(altered(0, **cut)) == {"fill_gap": 1}
    worse = right[0]["items"][:-1] + [int(tops[0]["items"][-1])]
    assert set(read(altered(0, items=worse))) == {"rank_gap", "score_gap"}
    off = [s * 1.001 for s in right[0]["scores"]]
    assert set(read(altered(0, scores=off))) == {"score_gap"}
    bought = [5] + right[0]["items"][1:]      # the user's own buy
    assert read(altered(0, items=bought)).get("leak") == 1
    outside = int(np.flatnonzero(cats != 2)[0])
    assert read(altered(2, items=[outside] + right[2]["items"][1:])
                ).get("leak") == 1
    listed = [40] + right[4]["items"][1:]
    assert read(altered(4, items=listed)).get("leak") == 1
    twice = [right[0]["items"][0]] * 2 + right[0]["items"][2:]
    assert read(altered(0, items=twice)) == {"malformed": 1}
    none = [None] * len(right)
    assert ref.gaps(cfg, slots, pop, cats, requests, none)["compared"] == 0


def test_every_control_reads_over_a_limit_at_the_rehearsal_size():
    cfg, traffic, dep = rehearsal()
    lim = cfg["limits"]
    for seed in (5, 2_147_483_700):
        got = dep.control("queries", cfg, traffic, seed)
        assert set(got) == {
            "control_lower_precision", "control_event_type_dropped",
            "control_stale_history"} | {
            f"fault_{what}_ignored" for what in dep.FAULTS}
        for what, nums in got.items():
            over = [k for k in lim if nums[k] > lim[k]]
            assert over, (seed, what, nums)
        # by one of the cell's limits, not by each
        assert got["control_lower_precision"]["leak"] == 0
        assert got["control_stale_history"]["leak"] > 0
        for what in dep.FAULTS:
            assert got[f"fault_{what}_ignored"]["leak"] > 0


def test_a_fault_under_the_timed_path_makes_correct_false(capsys,
                                                         monkeypatch):
    """The served model forgets the rule that keeps a category page to its
    category (set-up's own answers cannot tell): the comparison of the
    timed path's answers sees it."""
    from incubator_predictionio_tpu.models import universal_recommender as ur

    honest = ur.build_exclude
    monkeypatch.setattr(ur, "build_exclude", lambda *a, **kw: honest(
        *a, **dict(kw, fields=None)))
    line = run_cell(capsys, seed=11)
    assert line["correct"] is False
    assert line["compared"]["leak"]["value"] > 0


def test_a_cache_of_the_stores_answers_fails_set_up(monkeypatch):
    """Set-up writes a buy after one answer and the next must leave the
    item out: a model that remembers a user's history does not get to the
    window."""
    from incubator_predictionio_tpu.models import universal_recommender as ur

    honest, remembered = ur.URModel._history, {}

    def cached(self, user):
        if user not in remembered:
            remembered[user] = honest(self, user)
        return {e: list(rows) for e, rows in remembered[user].items()}

    monkeypatch.setattr(ur.URModel, "_history", cached)
    with pytest.raises(RuntimeError, match="warm-up query"):
        bench.main(["--workload", CELL, "--seed", "3", "--seconds", "1",
                    "--trace", "0", "--rehearse"])
