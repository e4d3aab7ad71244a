"""The e-commerce deployment under the harness: its cell's rehearsal is
``correct`` and reports its metrics from files alone, the plain reference
agrees with a brute-force count, each control and planted fault reads over a
limit at the rehearsal size, a fault planted under the timed path makes
``correct`` false, a cache of the store's answers fails set-up, and the rule
of a request is its place in the mix's arrival cycle, whatever the seed."""

import collections
import json
import os
import re
import types

import numpy as np
import pytest

import run as bench

from conftest import BENCH, ROOT

CELL = "serve-ecomm9m-rules-p4"
NEW_FILES = {
    "cells/serve-ecomm9m-rules-p4.json", "traffic/queries-rules-p4.json",
    "configs/amazon-catalog9m-ecomm128.json", "deployments/ecommerce-als.py",
    "engines/bench_ecomm_engine.py", "lib/datagen_ecomm.py",
    "lib/reference_ecomm.py", "metrics/serve.store_read_ms.py",
    "metrics/serve.mask_build_ms.py", "metrics/serve.mask_put_ms.py",
    "tests/test_ecomm_deployment.py"}
NEW_METRICS = ("serve.store_read_ms", "serve.mask_build_ms",
               "serve.mask_put_ms")
COMPARED = {"rank_gap", "score_gap", "leak", "fill_gap", "malformed",
            "unanswered"}


Span = collections.namedtuple(
    "Span", "trace_id span_id parent_id name t0_ns t1_ns tags")


def sp(trace, sid, parent, name, t0_ms, t1_ms, **tags):
    return Span(trace, sid, parent, name, int(t0_ms * 1e6), int(t1_ms * 1e6),
                tags or None)


def request(trace, sid, t0_s):
    """An answered query of the recommendation template: no store read, no
    mask built, a resident mask."""
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
    for name in NEW_METRICS + (
            "serve.topk_call_ms", "serve.device_wait_ms", "serve.host_ms",
            "serve.admit_wait_ms", "serve.busy_host_share",
            "serve.window_compiles", "loadgen.late_ms"):
        assert line["metrics"][name]["value"] >= 0, name
    assert line["metrics"]["serve.window_compiles"]["value"] == 0
    assert {"query_p50_ms", "query_p95_ms", "setup_s"} == set(
        line["end_to_end_seen"])


def test_the_cell_went_in_by_files_alone():
    """No file the benchmark had names the deployment: what runs the cell
    is the new files and the manifest's appended entries."""
    for folder, _dirs, files in os.walk(BENCH):
        for f in files:
            path = os.path.join(folder, f)
            rel = os.path.relpath(path, BENCH)
            if rel in NEW_FILES or "__pycache__" in rel:
                continue
            with open(path, errors="replace") as fh:
                # "recommendation" is every other file's word
                assert not re.search(r"(?i)(?<!r)ecomm", fh.read()), rel
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == "amazon-catalog9m-ecomm128"
    reports = {m["name"] for m in manifest["per_layer"]
               if CELL in m.get("workloads", ())}
    assert reports == set(NEW_METRICS) | {
        "serve.topk_call_ms", "serve.step_mfu", "topk_roofline",
        "device.idle_share.serve", "loadgen.late_ms", "serve.admit_wait_ms",
        "serve.device_wait_ms", "serve.host_ms", "serve.busy_host_share",
        "serve.window_compiles"}
    assert [m["name"] for m in manifest["per_layer"][-3:]] == list(
        NEW_METRICS)


def test_the_full_size_is_the_issues():
    _cell, cfg, traffic = bench.load_cell(CELL)
    assert (cfg["n_items"], cfg["rank"], cfg["catalog_dtype"]) == (
        9_400_000, 128, "float32")
    assert cfg["reduced"] == ["n_users"] and cfg["numIterations"] == 0
    assert traffic["rate_qps"] in (48.0, 36.0)
    assert traffic["rule_shares"] == {"none": 0.6, "categories": 0.25,
                                      "blackList": 0.1, "whiteList": 0.05}
    assert traffic["num_shares"] == [[10, 0.8], [4, 0.2]]
    assert (traffic["connections"], traffic["compared_requests"]) == (64, 256)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_span_reads_nothing(name, monkeypatch):
    import program_spans

    record = types.SimpleNamespace(window={"summary": {"attempted": 2}})
    monkeypatch.setattr(program_spans, "snapshot",
                        lambda: request(1, 10, 0.0) + request(2, 20, 0.5))
    assert bench.load_module("metrics", name).read(record) is None
    record.window = {}
    assert bench.load_module("metrics", name).read(record) is None


def test_new_metrics_sum_a_requests_spans(monkeypatch):
    import program_spans

    ring = request(1, 10, 0.0) + request(2, 30, 0.5) + [
        sp(1, 17, 11, "query.store_read", 5.1, 5.4, what="seen"),
        sp(1, 18, 11, "query.store_read", 5.4, 5.6, what="unavailable"),
        sp(1, 19, 11, "query.mask_build", 5.6, 5.9),
        sp(1, 20, 11, "topk.mask_put", 5.9, 6.0)]
    monkeypatch.setattr(program_spans, "snapshot", lambda: ring)
    record = types.SimpleNamespace(window={"summary": {"attempted": 2}})
    read = lambda name: bench.load_module("metrics", name).read(record)
    # the second request (an unknown user, say) has none: left out
    assert read("serve.store_read_ms") == pytest.approx(0.5)
    assert read("serve.mask_build_ms") == pytest.approx(0.3)
    assert read("serve.mask_put_ms") == pytest.approx(0.1)


# -- the generator -------------------------------------------------------------


def test_a_rule_is_a_place_in_the_arrival_cycle_whatever_the_seed():
    import datagen_ecomm
    import loadgen

    _cfg, traffic, _dep = rehearsal()
    traffic = dict(traffic, rate_qps=48.0)
    by_gap = None
    for seed, rate in ((1, 48.0), (2_900_000_777, 48.0), (2**31 + 5, 48.0),
                       (77, 120.0)):
        mix = dict(traffic, rate_qps=rate)
        seconds = 30.0 * 48.0 / rate            # 1,440 rows at every rate
        sched = loadgen.schedule(mix, 2000, seed, seconds)
        rules = datagen_ecomm.rules_of(mix, sched["due"])
        assert collections.Counter(rules) == {
            "none": 864, "categories": 360, "blackList": 144, "whiteList": 72}
        # a row's gap (in units of the mean gap) names its place in the cycle
        gaps = np.round(np.diff(sched["due"]) * rate, 6)
        assert len(set(gaps.tolist())) == len(gaps)
        seen = dict(zip(gaps.tolist(), rules[1:]))
        if by_gap is None:
            by_gap = seen
        shared = set(seen) & set(by_gap)
        assert len(shared) >= len(gaps) - 1
        assert all(seen[g] == by_gap[g] for g in shared)
        ranks = datagen_ecomm.cycle_ranks(sched["due"])
        assert sorted(ranks.tolist()) == list(range(len(ranks)))


def test_row_zero_may_hold_the_longest_gap():
    import datagen_ecomm

    n, rate = 200, 48.0
    u = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    order = np.random.default_rng(3).permutation(n)
    for first in (n - 1, 0, 57):
        order = np.concatenate([[first], order[order != first]])
        due = np.cumsum(u[order])
        np.testing.assert_array_equal(
            datagen_ecomm.cycle_ranks(due - due[0]), order)


def test_hot_users_are_the_ones_the_mix_asks_for_most():
    import datagen_ecomm
    import loadgen

    cfg, traffic, _dep = rehearsal()
    sched = loadgen.schedule(dict(traffic, rate_qps=2000.0, user_zipf_s=1.0),
                             cfg["n_users"], 9, 30.0)
    asked = collections.Counter(u for u in sched["user"] if u.isdigit())
    hot = [str(u) for u in datagen_ecomm.hot_users(cfg).tolist()]
    assert [u for u, _n in asked.most_common(3)] == hot[:3]
    share = sum(asked[u] for u in hot) / len(sched["user"])
    assert 0.5 < share < 0.65       # 64 of 2,000 here; 4,096 of 1M: 62%


def test_the_stores_contents_put_forbidden_items_in_the_answers_way():
    import datagen_ecomm

    cfg, _traffic, _dep = rehearsal()
    rng = np.random.default_rng(5)
    hot_top = np.stack([rng.permutation(cfg["n_items"])[:datagen_ecomm.TOP]
                        for _ in range(cfg["hot_users"])]).astype(np.int32)
    ev = datagen_ecomm.events(cfg, 2**31 + 11, hot_top)
    counts = np.diff(ev["offsets"])
    assert counts.min() >= 1 and counts.max() <= cfg["seen_limit"]
    assert abs(counts.mean() - cfg["events_per_user"]) < 0.3
    assert len(ev["withdrawn"]) == cfg["unavailable_items"]
    for j, user in enumerate(datagen_ecomm.hot_users(cfg).tolist()):
        seen = datagen_ecomm.seen_of(ev, user)
        own = np.isin(seen, hot_top[j])
        assert own.sum() == min(len(seen), datagen_ecomm.TOP - (
            j < cfg["withdrawn_hot_users"]))
        if j < cfg["withdrawn_hot_users"]:
            gone = np.intersect1d(hot_top[j], ev["withdrawn"])
            assert len(gone) >= 1 and not np.isin(gone, seen).all()
    rows = list(datagen_ecomm.store_rows(ev, 7, 1_700_000_000_000_000))
    assert len(rows) == len(ev["item"]) and len({r[0] for r in rows}) == \
        len(rows)
    assert {r[1] for r in rows} == {"buy", "view"}


def test_top_items_is_the_float32_top():
    import datagen_ecomm

    rng = np.random.default_rng(8)
    items = rng.normal(size=(5000, 16)).astype(np.float32)
    vecs = rng.normal(size=(7, 16)).astype(np.float32)
    want = np.argsort(-(vecs @ items.T), axis=1, kind="stable")
    for block in (1 << 17, 1024):      # one padded block; five, the last cut
        got = datagen_ecomm.top_items(items, vecs, block=block)
        np.testing.assert_array_equal(got, want[:, :datagen_ecomm.TOP])


# -- the reference -------------------------------------------------------------


def test_reference_against_a_brute_force_count():
    import reference_ecomm

    rng = np.random.default_rng(21)
    n = 4000
    items = rng.normal(size=(n, 8)).astype(np.float32)
    users = rng.normal(size=(5, 8)).astype(np.float32)
    cats = rng.integers(0, 4, n).astype(np.uint8)
    requests = [
        {"row": 0, "num": 10, "categories": None, "white": None,
         "forbidden": np.array([], np.int64)},
        {"row": 1, "num": 4, "categories": [2], "white": None,
         "forbidden": rng.integers(0, n, 300)},
        {"row": 2, "num": 10, "categories": None,
         "white": rng.choice(n, 60, replace=False),
         "forbidden": rng.integers(0, n, 2000)},
        {"row": 3, "num": 10, "categories": [0, 1],
         "white": np.array([5, 9, 3999]), "forbidden": np.array([9])},
        {"row": None, "num": 10, "categories": None, "white": None,
         "forbidden": np.array([], np.int64)}]
    for block in (1 << 18, 512):
        got = reference_ecomm.top_allowed(items, users, cats, requests,
                                          block=block)
        assert got[4] is None
        for q, ref in zip(requests[:4], got):
            s = items @ users[q["row"]]
            gone = set(q["forbidden"].tolist())
            white = None if q["white"] is None else set(q["white"].tolist())
            ok = np.array([j not in gone
                           and (q["categories"] is None
                                or cats[j] in q["categories"])
                           and (white is None or j in white)
                           for j in range(n)])
            order = [j for j in np.lexsort((np.arange(n), -s)) if ok[j]]
            np.testing.assert_array_equal(
                ref["items"], order[:reference_ecomm.MAX_NUM])
            np.testing.assert_allclose(ref["scores"], s[ref["items"]],
                                       rtol=1e-5)
            assert ref["spread"] == pytest.approx(float(s.std()), rel=1e-4)
    served = [reference_ecomm.answer_of(r, q["num"])
              for r, q in zip(got, requests)]
    clean = reference_ecomm.gaps(items, users, cats, requests, served)
    assert clean == {"rank_gap": 0.0, "score_gap": pytest.approx(0, abs=1e-6),
                     "leak": 0, "fill_gap": 0, "malformed": 0, "compared": 5}
    # a forbidden item served, an answer cut short, a repeated id, an answer
    # for a user nobody knows
    served[1]["items"][0] = int(requests[1]["forbidden"][0])
    served[0] = {"items": served[0]["items"][:9],
                 "scores": served[0]["scores"][:9]}
    served[2]["items"][1] = served[2]["items"][0]
    served[4] = {"items": [1], "scores": [0.5]}
    bad = reference_ecomm.gaps(items, users, cats, requests, served)
    assert (bad["leak"], bad["fill_gap"], bad["malformed"]) == (1, 1, 2)


CONTROLS = ("control_lower_precision", "fault_seen_ignored",
            "fault_withdrawn_ignored", "fault_categories_ignored",
            "fault_whiteList_ignored", "fault_blackList_ignored")


@pytest.fixture(scope="module")
def ecomm_control_readings():
    cfg, traffic, deployment = rehearsal()
    return cfg["limits"], deployment.control(traffic["kind"], cfg, traffic, 5)


@pytest.mark.parametrize("what", CONTROLS)
def test_each_control_reads_over_a_limit(ecomm_control_readings, what):
    limits, got = ecomm_control_readings
    assert set(got) == set(CONTROLS)
    over = {k for k, v in got[what].items() if v > limits.get(k, 0)}
    if what == "control_lower_precision":
        # rounding moves scores and with them the order; it forbids nothing
        assert {"score_gap"} <= over <= {"score_gap", "rank_gap"}
    else:
        assert "leak" in over, got[what]


# -- faults under the timed path -----------------------------------------------


def test_fault_the_seen_filter_off_under_the_timed_path(capsys, monkeypatch):
    from incubator_predictionio_tpu.models.ecommerce import ECommerceModel

    real = ECommerceModel._seen_items
    hottest = {"n": 0}

    def forgetful(self, user):
        # the hottest user's reads stay whole, so that set-up's own
        # read-your-write check passes and the window's comparison decides
        if user == "0":
            hottest["n"] += 1
            return real(self, user)
        return set()

    monkeypatch.setattr(ECommerceModel, "_seen_items", forgetful)
    line = run_cell(capsys)
    assert hottest["n"] > 0
    assert line["correct"] is False
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    assert "leak" in over and over <= {"leak", "rank_gap"}


def test_a_cache_of_the_stores_answers_fails_the_warm_up(monkeypatch):
    """The warm-up's bodies against the stock model without a server: every
    check holds; with the seen items remembered from a user's first query,
    the read-your-write check does not."""
    import bench_ecomm_engine
    from incubator_predictionio_tpu.controller.base import doer
    from incubator_predictionio_tpu.models.ecommerce import ECommerceModel

    cfg, traffic, deployment = rehearsal()
    key = deployment.serve_inputs(cfg, 9)
    algo = doer(bench_ecomm_engine.SeededECommerceAlgorithm,
                {"appName": deployment.APP, "seenEvents": cfg["seenEvents"]})
    model = algo.train(None, bench_ecomm_engine.InputParams(key))
    deployment.release(key)
    checks = [ok(algo.predict(model, body))
              for body, ok in deployment.warmup(traffic)]
    assert len(checks) == 2 + 24 + 2 + 2 and all(checks)

    real, cache = ECommerceModel._seen_items, {}
    monkeypatch.setattr(
        ECommerceModel, "_seen_items",
        lambda self, user: cache.setdefault(user, real(self, user)))
    checks = [ok(algo.predict(model, body))
              for body, ok in deployment.warmup(traffic)]
    assert checks == [True] * (len(checks) - 1) + [False]
    deployment.STATE.pop("storage").close()
