"""The arithmetic over the program's spans, on a small hand-written list,
and the two cells' rehearsals printing all eleven span-fed metrics."""

import collections
import json
import os
import subprocess
import sys
import types

import pytest

from conftest import BENCH, ROOT

import program_spans as ps

Span = collections.namedtuple(
    "Span", "trace_id span_id parent_id name t0_ns t1_ns tags")
S = 10**9  # one second of perf_counter_ns


def sp(trace, sid, parent, name, t0_s, t1_s, **tags):
    return Span(trace, sid, parent, name, int(t0_s * S), int(t1_s * S),
                tags or None)


def train(trace, sid, t0):
    """One train.run of 10 s from ``t0`` with the table's spans beneath."""
    return [
        sp(trace, sid, None, "train.run", t0, t0 + 10.0, instance=trace),
        sp(trace, sid + 1, sid, "dase.read", t0, t0 + 0.5),
        sp(trace, sid + 2, sid, "dase.algo_train", t0 + 0.5, t0 + 8.0),
        sp(trace, sid + 3, sid + 2, "als.init", t0 + 1.0, t0 + 3.0),
        sp(trace, sid + 4, sid + 2, "als.pack", t0 + 3.0, t0 + 3.25),
        sp(trace, sid + 5, sid + 2, "als.upload", t0 + 3.25, t0 + 3.75),
        sp(trace, sid + 6, sid + 2, "als.loop", t0 + 3.75, t0 + 7.0),
        sp(trace, sid + 7, sid + 2, "als.readback", t0 + 7.0, t0 + 8.0),
        sp(trace, sid + 8, sid, "dase.serialize", t0 + 8.0, t0 + 8.5),
        sp(trace, sid + 9, sid, "dase.persist", t0 + 8.5, t0 + 9.75,
           bytes=7),
    ]


def request(trace, sid, t0, wait=(0.010, 0.050), status=200):
    """A query: root 60 ms, admitted at +2, picked up at +4, featurize 1 ms,
    predict to +52 with topk.dispatch and topk.wait (``wait``), serve 1 ms."""
    m = 1e-3
    out = [sp(trace, sid, None, ps.REQUEST_ROOT, t0, t0 + 60 * m,
              status=status)]
    if status != 200:
        return out
    return out + [
        sp(trace, sid + 1, sid, "query.admit_wait", t0 + 2 * m, t0 + 4 * m,
           pending=1),
        sp(trace, sid + 2, sid, "query.featurize", t0 + 4 * m, t0 + 5 * m),
        sp(trace, sid + 3, sid, "query.predict", t0 + 5 * m, t0 + 52 * m),
        sp(trace, sid + 4, sid + 3, "topk.dispatch", t0 + 6 * m,
           t0 + wait[0]),
        sp(trace, sid + 5, sid + 3, "topk.wait", t0 + wait[0], t0 + wait[1]),
        sp(trace, sid + 6, sid, "query.serve", t0 + 52 * m, t0 + 53 * m),
    ]


def record_of(window_spans=(), attempted=0):
    return types.SimpleNamespace(
        window_spans=list(window_spans),
        window={"summary": {"attempted": attempted}} if attempted else {})


@pytest.fixture()
def ring(monkeypatch):
    spans: list = []
    monkeypatch.setattr(ps, "snapshot", lambda: list(spans))
    return spans


def test_window_trains_are_the_roots_inside_the_harness_spans(ring):
    ring += train("warm", 100, 5.0)            # set-up: before the window
    ring += train("a", 200, 20.0) + train("b", 300, 31.0)
    ring += train("traced", 400, 45.0)         # after the window
    ring.append(sp("a", 250, 206, "xla.compile", 23.0, 24.0, seconds=1.0))
    rec = record_of([("run_train", 19.9, 30.1), ("run_train", 30.9, 41.2),
                     ("train_als", 20.5, 28.0)])
    trees = ps.train_trees(rec)
    assert [t[0].trace_id for t in trees] == ["a", "b"]
    assert all(t[0].name == "train.run" for t in trees)
    assert ps.mean_train_seconds(rec, "als.init") == pytest.approx(2.0)
    assert ps.mean_train_seconds(rec, "als.pack", "als.upload") == \
        pytest.approx(0.75)
    assert ps.mean_train_seconds(rec, "dase.serialize", "dase.persist") == \
        pytest.approx(1.75)
    assert ps.mean_train_seconds(rec, "no.such.span") is None
    assert sum(len(ps.named(t, ps.COMPILE)) for t in trees) == 1
    # the work spans cover the root but for what lies between them: [0.5,
    # 1.0] of dase.algo_train before als.init, and the last quarter second;
    # a compile beneath als.loop changes nothing
    assert ps.covered_share(trees[0]) == pytest.approx(0.925)
    assert ps.covered_share(trees[1]) == pytest.approx(0.925)
    # a checkout without the ring: nothing, and no error
    assert ps.mean_train_seconds(record_of(), "als.init") is None


def test_self_time_is_duration_less_the_union_of_children():
    tree = request(1, 10, 0.0)
    root = tree[0]
    # 60 ms less [2, 53] ms of admit_wait, featurize, predict, serve
    assert ps.self_seconds(root, tree) == pytest.approx(0.009)
    predict = ps.named(tree, "query.predict")[0]
    assert ps.self_seconds(predict, tree) == pytest.approx(0.003)
    # overlapping children count once; a child past its parent is cut
    tree.append(sp(1, 99, 10, "query.extra", 0.050, 0.075))
    assert ps.self_seconds(root, tree) == pytest.approx(0.002)
    assert ps.host_seconds(request(1, 10, 0.0)) == pytest.approx(0.011)


def test_window_requests_are_the_last_attempted_roots(ring):
    ring += request(1, 10, 0.0) + request(2, 20, 0.5)      # warm-up
    ring += request(3, 30, 2.0) + request(4, 40, 2.02, wait=(0.012, 0.051))
    ring += request(5, 50, 3.0, status=503)
    ring.append(sp(6, 60, None, "http GET /healthz", 1.9, 1.91, status=200))
    rec = record_of(attempted=3)
    trees = ps.request_trees(rec)
    assert [t[0].trace_id for t in trees] == [3, 4, 5]
    # the shed request has no wait, no stages: left out of the medians
    waits = ps.request_span_ms(rec, "topk.wait")
    assert sorted(waits) == pytest.approx([39.0, 40.0])
    assert ps.request_values_ms(rec, ps.host_seconds) == \
        pytest.approx([11.0, 11.0])
    assert ps.request_trees(record_of()) == []


def test_busy_host_share_on_two_overlapping_requests(ring):
    # A: root [0, 60] ms, topk.wait [10, 50]; B: root [20, 80], wait
    # [32, 71]; a 503 at [100, 160] ms with no wait at all.
    ring += request(1, 10, 0.0) + request(2, 20, 0.020, wait=(0.012, 0.051))
    ring += request(3, 30, 0.100, status=503)
    trees = ps.request_trees(record_of(attempted=3))
    # house [0, 80] + [100, 160] = 140 ms; waiting [10, 71] = 61 ms; the
    # window runs from 0 to 160 ms
    assert ps.busy_host_share_percent(trees) == pytest.approx(
        100.0 * (140 - 61) / 160)
    assert ps.busy_host_share_percent([]) is None


def test_compiles_between(ring):
    ring += [sp(9, 1, None, "xla.compile", 0.5, 0.9, seconds=0.4),
             sp(9, 2, None, "xla.compile", 1.5, 2.5, seconds=1.0),
             sp(9, 3, None, "topk.wait", 1.5, 2.5)]
    assert ps.compiles_between(ps.snapshot(), 1 * S, 2 * S) == 1
    assert ps.compiles_between(ps.snapshot(), 0, 3 * S) == 2
    assert ps.compiles_between(ps.snapshot(), 3 * S, 4 * S) == 0


NEW = {
    "retrain-electronics-r128": {
        "als.init_s", "als.upload_s", "als.loop_s", "als.readback_s",
        "dase.persist_s", "train.window_compiles"},
    "serve-catalog9m-steady": {
        "serve.admit_wait_ms", "serve.device_wait_ms", "serve.host_ms",
        "serve.busy_host_share", "serve.window_compiles"},
}


def test_manifest_lists_the_eleven_with_one_cell_each():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for cell, names in NEW.items():
        for name in names:
            assert per_layer[name]["workloads"] == [cell]
            assert os.path.exists(
                os.path.join(BENCH, "metrics", name + ".py"))


@pytest.mark.parametrize("cell", sorted(NEW))
def test_rehearsal_prints_the_new_metrics(cell):
    """``--rehearse --trace 1`` on the CPU: every span-fed metric of the
    cell is in the line (CPU numbers: plumbing only)."""
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert NEW[cell] <= set(line["metrics"]), sorted(line["metrics"])
    for name in NEW[cell]:
        assert line["metrics"][name]["value"] >= 0
    compiles = next(n for n in NEW[cell] if n.endswith("window_compiles"))
    assert line["metrics"][compiles]["value"] == 0
