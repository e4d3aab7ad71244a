import pytest

import work


def test_als_counts_by_hand():
    # 10 ratings, 3 users, 2 items, rank 4
    w = work.als_sweep_flops(10, 3, 2, 4)
    assert w["gram"] == 2 * 2 * 10 * 16
    assert w["rhs"] == 2 * 2 * 10 * 4
    assert w["solve"] == 5 * (64 / 3 + 32)
    assert w["total"] == w["gram"] + w["rhs"] + w["solve"]
    assert work.als_solve_bytes(3, 2, 4) == 5 * (16 + 8) * 4


def test_topk_counts_and_roofline():
    assert work.topk_scan_flops(1000, 128) == 256000
    assert work.topk_scan_bytes(1000, 128) == 512000
    peaks = work.peaks_for("TPU v5 lite")
    r = work.roofline_seconds(256000, 512000, peaks)
    assert r["binds"] == "bytes"
    assert r["seconds"] == pytest.approx(512000 / 819e9)
    assert work.roofline_seconds(1e15, 1.0, peaks)["binds"] == "operations"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks_for("TPU v99")
