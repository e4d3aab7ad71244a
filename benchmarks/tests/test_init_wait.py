"""``als.init_wait_s`` (PR 39): what of the ALS init is still on a train's
critical path once a worker thread draws it beside the layout and the pack.
The reader on a hand-written ring, the manifest's entry, and the cell's
rehearsal printing it, or leaving it out where the init ran in turn."""

import collections
import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

from conftest import BENCH, ROOT

import program_spans as ps

NAME = "als.init_wait_s"
CELLS = ["retrain-electronics-r128", "retrain-electronics-eventlog"]
Span = collections.namedtuple(
    "Span", "trace_id span_id parent_id name t0_ns t1_ns tags")


def sp(trace, sid, parent, name, t0_s, t1_s, **tags):
    return Span(trace, sid, parent, name, int(t0_s * 10**9),
                int(t1_s * 10**9), tags or None)


def record_of(window_spans=()):
    return types.SimpleNamespace(window_spans=list(window_spans), window={})


def reader():
    spec = importlib.util.spec_from_file_location(
        "metric_als_init_wait_s", os.path.join(BENCH, "metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def train(trace, sid, t0, wait=None):
    """A train.run of 10 s from ``t0``: the layout 1 s, the pack a quarter,
    the init 2 s long. ``wait`` None: the init in turn after the layout;
    else it runs from the train's start beside them and the calling thread
    waits ``wait`` seconds for it after the pack."""
    spans = [
        sp(trace, sid, None, "train.run", t0, t0 + 10.0, instance=trace),
        sp(trace, sid + 1, sid, "dase.algo_train", t0 + 0.5, t0 + 8.0),
        sp(trace, sid + 2, sid + 1, "als.layout", t0 + 0.5, t0 + 1.5),
    ]
    if wait is None:
        return spans + [
            sp(trace, sid + 3, sid + 1, "als.init", t0 + 1.5, t0 + 3.5,
               users="dropped", overlap="none"),
            sp(trace, sid + 4, sid + 1, "als.pack", t0 + 3.5, t0 + 3.75)]
    return spans + [
        sp(trace, sid + 3, sid + 1, "als.init", t0 + 0.5, t0 + 2.5,
           users="dropped", overlap="layout"),
        sp(trace, sid + 4, sid + 1, "als.pack", t0 + 1.5, t0 + 1.75),
        sp(trace, sid + 5, sid + 1, "als.init_wait", t0 + 1.75,
           t0 + 1.75 + wait)]


@pytest.fixture()
def ring(monkeypatch):
    spans: list = []
    monkeypatch.setattr(ps, "snapshot", lambda: list(spans))
    return spans


def test_it_is_the_mean_wait_of_the_windows_trains(ring):
    read = reader()
    ring += train("warm", 100, 5.0, wait=9.0)      # set-up: not the window's
    ring += train("a", 200, 20.0, wait=0.75) + train("b", 300, 31.0, wait=0.0)
    rec = record_of([("run_train", 19.9, 30.1), ("run_train", 30.9, 41.2)])
    assert read(rec) == pytest.approx(0.375)       # a wait of 0 ms counts
    # the worker's own wall is als.init_s still, whatever overlapped it
    assert ps.mean_train_seconds(rec, "als.init") == pytest.approx(2.0)


def test_nothing_where_no_train_left_the_span(ring):
    read = reader()
    assert read(record_of()) is None                # no ring at all
    ring += train("a", 200, 20.0)                   # the init ran in turn
    rec = record_of([("run_train", 19.9, 30.1)])
    assert read(rec) is None
    assert ps.mean_train_seconds(rec, "als.init") == pytest.approx(2.0)


def test_the_manifest_names_the_two_als_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = [p for p in m["per_layer"] if p["name"] == NAME]
    assert entry == [{
        "name": NAME, "unit": "s", "better": "lower",
        "source": "program_span", "layer": "host_init", "moves": "retrain_s",
        "workloads": CELLS}]
    sibling = next(p for p in m["per_layer"] if p["name"] == "als.init_s")
    assert sibling["layer"] == "host_init"
    assert set(CELLS) <= set(sibling["workloads"])


@pytest.mark.parametrize("pipeline", ["auto", "off"])
def test_the_rehearsal_prints_it_or_leaves_it_out(pipeline):
    """``--rehearse --trace 1`` on the CPU (plumbing only, no device
    number): with the overlap the line carries the metric; with
    PIO_PIPELINE=off no train leaves the span, as on a checkout from before
    it, and the line leaves the metric out without failing."""
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "2147483693", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PIO_PIPELINE=pipeline))
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["als.init_s"]["value"] > 0
    if pipeline == "off":
        assert NAME not in line["metrics"]
    else:
        assert line["metrics"][NAME]["value"] >= 0
        assert line["metrics"][NAME]["unit"] == "s"
