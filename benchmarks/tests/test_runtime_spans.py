"""The four ``runtime`` metrics on a small hand-written ring: what each
reads, nothing on a ring without the process's spans, 0.0 where the loop
beat and never stalled; the process's roots change nothing that the readers
of ``test_program_spans.py`` return on that file's ring; the manifest's four
entries; a serve and a retrain cell's rehearsal printing them."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

import program_spans as ps
import runtime_spans as rs


def _sibling(stem: str):
    """A test file beside this one, for its hand-written ring (its
    directory is on no import path)."""
    spec = importlib.util.spec_from_file_location(
        "runtime_spans_" + stem,
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     stem + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ring = _sibling("test_program_spans")
S, sp, train, request, record_of = (
    _ring.S, _ring.sp, _ring.train, _ring.request, _ring.record_of)

#: the program's fixed trace id of its process-level roots
PROCESS = "pio.process"
MS = 1e-3
NEW = {"serve.loop_lag_ms": "query_p50_ms", "serve.stall_ms": "query_p95_ms",
       "serve.gc_pause_ms": "query_p95_ms", "train.gc_pause_s": "retrain_s"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def read(name, record):
    import run as bench

    return bench.load_module("metrics", name).read(record)


def beat(sid, t0, lag_med_ms, gc_ms=0.0, loop="engine"):
    return sp(PROCESS, sid, None, rs.BEAT, t0, t0 + 1.0, loop=loop, ticks=20,
              lag_med_ms=lag_med_ms, lag_max_ms=2 * lag_med_ms, cpu_ms=30.0,
              loop_cpu_ms=4.0, gc_ms=gc_ms)


def stall(sid, t0, lag_ms, loop="engine", **tags):
    return sp(PROCESS, sid, None, rs.STALL, t0, t0 + lag_ms * MS, loop=loop,
              lag_ms=lag_ms, **tags)


@pytest.fixture()
def ring(monkeypatch):
    spans: list = []
    monkeypatch.setattr(ps, "snapshot", lambda: list(spans))
    return spans


def serve_ring(ring):
    """A window of three requests, [10.0, 13.56] s, the third a 503; warm-up
    before it. Beats at 8.5 (before), 9.5 (straddles the start), 10.5, 11.5,
    12.5 (inside), 13.5 (straddles the end); the event server's loop beats
    too."""
    ring += request(1, 10, 5.0)                                  # warm-up
    ring += request(2, 20, 10.0) + request(3, 30, 11.0)
    ring += request(4, 40, 13.5, status=503)
    ring += [beat(100 + i, 8.5 + i, lag_med_ms=0.1 * (i + 1),
                  gc_ms=float(i)) for i in range(6)]
    ring.append(beat(200, 10.5, lag_med_ms=50.0, gc_ms=999.0, loop="event"))
    return record_of(attempted=3)


def test_loop_lag_is_the_median_of_the_beats_inside_the_window(ring):
    rec = serve_ring(ring)
    assert rs.serve_window(rec) == (10 * S, int(13.56 * S))
    assert [s.span_id for s in rs.window_beats(rec)] == [102, 103, 104]
    assert read("serve.loop_lag_ms", rec) == pytest.approx(0.4)
    assert read("serve.gc_pause_ms", rec) == pytest.approx(2 + 3 + 4)


def test_stall_ms_sums_the_stalls_cut_to_the_window(ring):
    rec = serve_ring(ring)
    assert read("serve.stall_ms", rec) == 0.0       # beats, and no stall
    ring += [stall(300, 9.0, 80.0),                 # set-up's: not counted
             stall(301, 9.95, 100.0),               # 50 of its 100 ms inside
             stall(302, 11.2, 77.0, gc_ms=75.0),
             stall(303, 13.5, 2000.0),              # 60 ms to the last answer
             stall(304, 12.0, 500.0, loop="event")]
    assert read("serve.stall_ms", rec) == pytest.approx(50 + 77 + 60)


def test_nothing_without_the_processes_spans(ring):
    """A checkout from before the spans: requests and trains, no beat, no
    tag; and an empty ring."""
    for name in NEW:
        assert read(name, record_of(attempted=3)) is None
        assert read(name, record_of([("run_train", 0.0, 50.0)])) is None
    ring += request(2, 20, 10.0) + request(3, 30, 11.0) + train("a", 200, 20.0)
    ring.append(stall(300, 10.5, 80.0))             # a stall, but no beat
    for name in NEW:
        assert read(name, record_of([("run_train", 19.0, 31.0)],
                                    attempted=2)) is None


def test_train_gc_pause_is_the_mean_of_the_roots_tag(ring):
    a, b, warm = train("a", 200, 20.0), train("b", 300, 31.0), \
        train("warm", 100, 5.0)
    a[0] = a[0]._replace(tags=dict(a[0].tags, gc_ms=1500.0,
                                   gc_collections=70))
    b[0] = b[0]._replace(tags=dict(b[0].tags, gc_ms=500.0,
                                   gc_collections=3))
    warm[0] = warm[0]._replace(tags=dict(warm[0].tags, gc_ms=90000.0))
    ring += warm + a + b
    rec = record_of([("run_train", 19.9, 30.1), ("run_train", 30.9, 41.2)])
    assert read("train.gc_pause_s", rec) == pytest.approx(1.0)
    b[0] = b[0]._replace(tags={"instance": "b"})    # one root without it
    ring[:] = warm + a + b
    assert read("train.gc_pause_s", rec) == pytest.approx(1.5)


def test_the_processes_roots_are_in_no_tree(ring):
    """What ``window_requests``, ``window_trains``, ``covered_share`` and
    ``host_seconds`` return is the same with the process's roots in the
    ring, wherever they lie."""
    ring += request(1, 10, 0.0) + request(2, 20, 0.5)
    ring += request(3, 30, 2.0) + request(4, 40, 2.02, wait=(0.012, 0.051))
    ring += request(5, 50, 3.0, status=503)
    ring += train("a", 200, 20.0) + train("b", 300, 31.0)
    windows = [("run_train", 19.9, 30.1), ("run_train", 30.9, 41.2)]

    def readings():
        rec = record_of(windows, attempted=3)
        requests, trains = ps.request_trees(rec), ps.train_trees(rec)
        return (requests, trains,
                [ps.covered_share(t) for t in trains],
                [ps.host_seconds(t) for t in requests],
                ps.busy_host_share_percent(requests),
                ps.request_span_ms(rec, "topk.wait"),
                ps.mean_train_seconds(rec, "als.init"))

    before = readings()
    ring += [sp(PROCESS, 900, None, "py.gc", 2.01, 2.03, generation=2,
                collected=5, uncollectable=0, thread=1),
             sp(PROCESS, 901, None, "py.gc", 22.0, 24.0, generation=2,
                collected=0, uncollectable=0, thread=1),
             stall(902, 2.0, 70.0), stall(903, 21.0, 5000.0),
             beat(904, 1.5, 0.2), beat(905, 2.5, 0.2), beat(906, 25.0, 0.3)]
    assert readings() == before
    assert len(before[0]) == 3 and len(before[1]) == 2


def test_manifest_lists_the_four_in_cells_that_report_what_they_move():
    """By membership, not position: a later PR appends its own."""
    m = manifest()
    per_layer = {p["name"]: p for p in m["per_layer"]}
    cells = {w["name"] for w in m["workloads"]}
    moved = {e["name"]: set(e.get("workloads", cells))
             for e in m["end_to_end"]}
    for name, moves in NEW.items():
        entry = per_layer[name]
        assert (entry["layer"], entry["source"], entry["better"],
                entry["moves"]) == ("runtime", "program_span", "lower", moves)
        assert entry["workloads"] and set(entry["workloads"]) <= moved[moves]
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    assert os.path.exists(os.path.join(BENCH, "lib", "runtime_spans.py"))


@pytest.mark.parametrize("cell", ["serve-catalog9m-steady",
                                  "retrain-ml20m-ur"])
def test_rehearsal_prints_the_runtime_metrics(cell):
    """``--rehearse --trace 1`` on the CPU of one serve and one retrain
    cell (CPU numbers: plumbing only)."""
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    want = {p["name"] for p in manifest()["per_layer"]
            if p["name"] in NEW and cell in p["workloads"]}
    assert want and want <= set(line["metrics"]), sorted(line["metrics"])
    for name in want:
        assert line["metrics"][name]["value"] >= 0
