import pytest

import trace_reduce as tr


def test_merge_and_gaps():
    busy = tr.merge_intervals([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (4.0, 4.0)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.gaps_of(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]


def test_idle_goes_to_the_innermost_span_open_piece_by_piece():
    spans = [("outer", 0.0, 10.0), ("inner", 2.0, 5.0), ("late", 8.0, 9.0)]
    got = tr.idle_by_span([(1.0, 6.0), (9.5, 12.0)], spans)
    assert got == pytest.approx(
        {"outer": 1.0 + 1.0 + 0.5, "inner": 3.0, "host": 2.0})


def test_reduce_synthetic_trace():
    trace = {
        "devices": {"/device:TPU:0": [
            ("%while.9 = (s32[]) while(...)", 1.0, 3.0),
            ("%fusion.1 = f32[8] fusion(...)", 1.0, 2.0),
            ("%solve.2 = f32[4] custom-call(...)", 2.0, 2.75),
            ("%fusion.1 = f32[8] fusion(...)", 6.0, 7.0),
            ("%before_window = f32[] add()", -2.0, -1.0)]},
        "modules": {"/device:TPU:0": [("jit_packed(1)", 1.0, 7.0)]},
        "spans": [("traced", 0.0, 10.0), ("run_train", 0.0, 10.0),
                  ("plan_and_fill_both", 3.0, 6.0)],
        "lines": {},
    }
    r = tr.reduce_trace(trace, "traced")
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(3.0)          # [1,3] and [6,7]
    assert r["op_seconds"]["fusion.1"] == pytest.approx(2.0)
    assert r["op_seconds"]["solve.2"] == pytest.approx(0.75)
    assert r["op_seconds"]["while.9"] == pytest.approx(0.25)   # self time
    assert sum(r["op_seconds"].values()) == pytest.approx(r["busy_s"])
    assert r["op_counts"] == {"fusion.1": 2, "solve.2": 1, "while.9": 1}
    assert r["module_seconds"]["jit_packed(1)"] == pytest.approx(6.0)
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["plan_and_fill_both"] == pytest.approx(3.0)   # [3,6]
    assert gaps["run_train"] == pytest.approx(4.0)            # [0,1] + [7,10]
    assert r["device_ops"][0][0] == "fusion.1"
    assert tr.idle_share_percent(r) == pytest.approx(70.0)
    assert tr.idle_share_percent(None) is None


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_trace({"devices": {}, "modules": {}, "lines": {},
                         "spans": [("traced", 0.0, 1.0)]}, "traced")
