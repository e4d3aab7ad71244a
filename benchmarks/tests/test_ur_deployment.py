"""The Universal Recommender deployment under the harness: its cell's
rehearsal is ``correct`` and reports its metrics, each control and planted
fault reads over a limit at the rehearsal size, a fault planted under the
timed path makes ``correct`` false, the least work agrees with a brute-force
count, and the trace-fed metric readers find the two executables."""

import json
import types

import numpy as np
import pytest

import run as bench

CELL = "retrain-ml20m-ur"


def run_cell(capsys, trace=0, seed=123):
    rc = bench.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "1", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def rehearsal():
    _cell, cfg, traffic = bench.load_cell(CELL, rehearse=True)
    return cfg, traffic, bench.load_module("deployments", cfg["deployment"])


def test_rehearsal_is_correct_and_reports_its_metrics(capsys):
    line = run_cell(capsys, trace=1, seed=2_900_000_777)
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {
        f"{gap}.{event}" for event in ("buy", "view")
        for gap in ("score_gap", "rank_gap", "fill_gap", "malformed")}
    assert line["attempted"] >= 1 and line["failed"] == 0
    # the span-fed ones; the CPU's trace has no device plane, so the three
    # that read it are covered by test_trace_fed_metrics_read_the_two_executables
    for name in ("cco.prep_s", "cco.device_s", "dase.persist_s",
                 "train.window_compiles"):
        assert name in line["metrics"], name
    assert line["metrics"]["cco.device_s"]["value"] > 0
    assert "retrain_s" in line["end_to_end_seen"]


def test_events_repeat_no_pair_and_keep_one_layout_for_every_seed():
    import datagen_ur

    cfg, _traffic, _dep = rehearsal()
    degs = datagen_ur.degrees(cfg)
    a, b = (datagen_ur.events(cfg, seed, degs)
            for seed in (2_900_000_777, 2**31 + 5))
    hist = cfg["star_histogram"]
    bought = sum(c for s, c in hist.items()
                 if float(s) >= cfg["buy_min_stars"]) / sum(hist.values())
    for name, share in (("view", 1.0), ("buy", bought)):
        keys = [u.astype(np.int64) * cfg["n_items"] + i
                for u, i in (a[name], b[name])]
        assert all(len(np.unique(k)) == len(k) for k in keys)
        assert abs(len(keys[0]) - share * cfg["n_ratings"]) <= len(hist)
        # each user's and each item's count of ratings, and each user's
        # count of buys, are the same for every seed; the pairs are not
        np.testing.assert_array_equal(
            np.bincount(a[name][0], minlength=cfg["n_users"]),
            np.bincount(b[name][0], minlength=cfg["n_users"]))
        assert len(np.intersect1d(*keys)) < 0.5 * len(keys[0])
    np.testing.assert_array_equal(
        np.bincount(a["view"][1], minlength=cfg["n_items"]), degs[1])
    assert degs[0].max() > 16 * 1.5 * cfg["n_ratings"] / cfg["n_users"]


def test_work_spans_cover_the_train(capsys):
    import program_spans

    run_cell(capsys)
    roots = [s for s in program_spans.snapshot()
             if s.name == program_spans.TRAIN_ROOT]
    tree = program_spans.trees(program_spans.snapshot(), roots[-1:])[0]
    names = {s.name for s in tree}
    assert {"cco.dedupe", "cco.partition", "cco.device", "cco.gather",
            "ur.popularity", "dase.algo_train", "dase.serialize",
            "dase.persist"} <= names
    device = program_spans.named(tree, "cco.device")[0]
    assert device.tags["path"] == "fused" and device.tags["n_sec"] == 2
    # on a mesh the range axes are padded to a multiple of its devices
    assert device.tags["ranges"] == 16 and device.tags["heavy_ranges"] >= 1
    algo = program_spans.named(tree, "dase.algo_train")[0]
    for s in program_spans.named(tree, "cco.dedupe", "cco.partition",
                                 "cco.device", "cco.gather",
                                 "ur.popularity"):
        assert algo.t0_ns <= s.t0_ns and s.t1_ns <= algo.t1_ns
    assert program_spans.covered_share(tree) > 0.9   # 99.9% on the chip


CONTROLS = ("control_counts_bf16", "control_llr_bf16", "fault_heavy_dropped",
            "fault_range_dropped", "fault_swapped")


@pytest.fixture(scope="module")
def control_readings():
    cfg, traffic, deployment = rehearsal()
    got = deployment.control(traffic["kind"], cfg, traffic, 5)
    return (lambda gap: deployment.limit_of(cfg, gap)), got


@pytest.mark.parametrize("what", CONTROLS)
def test_each_control_reads_over_a_limit(control_readings, what):
    limit_of, got = control_readings
    assert set(got) == set(CONTROLS)
    over = {k: v for k, v in got[what].items() if v > limit_of(k)}
    assert over, got[what]
    if what == "fault_swapped":
        # the self pair's counts are symmetric: only the cross pair shows it
        assert all(k.endswith(".view") for k in over)


def test_fault_a_user_range_left_out_of_the_counts(capsys, monkeypatch):
    from incubator_predictionio_tpu.ops import llr

    real = llr._mk_multi_body

    def one_range_short(self_flags, n_items, chunk_rows):
        body = real(self_flags, n_items, chunk_rows)

        def short(cs, chunk):
            # the slab of the range's primary events is emptied where the
            # first event's local offset is 0: one range in a few
            keep = (chunk[0][0] != 0).astype(chunk[0].dtype)
            dropped = (chunk[0] * keep + (1 - keep) * chunk_rows,) + \
                tuple(chunk[1:])
            return body(cs, dropped)

        return short

    # the scan body is traced into the fused executables (one device, or a
    # mesh where the process has several): none compiled before may be used,
    # and none compiled here may stay
    fused = (llr._cco_count_multi,)
    monkeypatch.setattr(llr, "_mk_multi_body", one_range_short)
    for fn in fused:
        fn.clear_cache()
    try:
        line = run_cell(capsys)
    finally:
        monkeypatch.undo()
        for fn in fused:
            fn.clear_cache()
    assert line["correct"] is False
    assert any(v["value"] > v["limit"] for k, v in line["compared"].items()
               if k.startswith("score_gap"))


def test_fault_scores_altered_where_they_are_persisted(capsys, monkeypatch):
    from incubator_predictionio_tpu.models import universal_recommender as ur

    real = ur.cco_indicators_multi

    def scaled(*a, **kw):
        out = real(*a, **kw)
        out["view"].score = out["view"].score * np.float32(1.2)
        return out

    monkeypatch.setattr(ur, "cco_indicators_multi", scaled)
    line = run_cell(capsys)
    assert line["correct"] is False
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    assert over == {"score_gap.view"}


def test_least_work_against_a_brute_force_count():
    import work_cco

    rng = np.random.default_rng(4)
    n_users, n_items = 40, 12
    a = {name: (rng.random((n_users, n_items)) < p).astype(np.int64)
         for name, p in (("buy", 0.2), ("view", 0.5))}
    per_user = {name: m.sum(axis=1) for name, m in a.items()}
    got = work_cco.least_work(per_user, "buy", n_items)
    # one multiply-add for every (user, primary item, secondary item) that
    # is really there = the sum of the count matrix's entries, twice
    brute = sum(2 * int((a["buy"].T @ m).sum()) for m in a.values())
    assert got["ops"] == brute
    pairs = sum(int(m.sum()) for m in a.values())
    assert got["bytes"] == pairs * work_cco.PAIR_BYTES \
        + 2 * (n_items * n_items * 4) * 2
    peaks = {"int8_ops_per_s": 1e3, "hbm_bytes_per_s": 1e9}
    assert work_cco.roofline_seconds(got, peaks) == {
        "seconds": brute / 1e3, "binds": "operations"}
    peaks = {"int8_ops_per_s": 1e15, "hbm_bytes_per_s": 1e3}
    assert work_cco.roofline_seconds(got, peaks)["binds"] == "bytes"


def test_trace_fed_metrics_read_the_two_executables(monkeypatch):
    """A recorded reduction of one traced train."""
    import bench_ur_engine

    trace = {"module_seconds": {"jit__cco_count_multi(123)": 7.5,
                                "jit__cco_select(456)": 2.5,
                                "jit_convert_element_type(7)": 0.5}}
    record = types.SimpleNamespace(
        trace=trace, seed=7, peaks={"int8_ops_per_s": 1e12,
                                    "hbm_bytes_per_s": 1e9},
        window_span_seconds=lambda name: [20.0, 30.0])
    monkeypatch.setitem(bench_ur_engine.INPUTS, "events-7",
                        {"work": {"ops": 1e9, "bytes": 3e9}})   # 3 s least
    metric = lambda name: bench.load_module("metrics", name).read(record)
    assert metric("cco.select_s") == 2.5
    assert metric("cco.count_roofline") == pytest.approx(100 * 3.0 / 7.5)
    assert metric("cco.step_mfu") == pytest.approx(100 * 3.0 / 25.0)
    # a program with one fused executable (the parent), no trace, no work
    # counted: nothing is read, and nothing raises
    trace["module_seconds"] = {"jit__full_cco_topk_multi(123)": 10.0}
    assert metric("cco.select_s") is None
    assert metric("cco.count_roofline") is None
    record.trace = None
    assert metric("cco.select_s") is None
    monkeypatch.delitem(bench_ur_engine.INPUTS, "events-7")
    assert metric("cco.step_mfu") is None
