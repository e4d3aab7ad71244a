"""``ops/llr.py`` against the benchmark's plain reference
(``benchmarks/lib/reference_cco.py``: counts by the sparse definition, G² by
Mahout's formula in float64): every accumulation strategy, with and without
heavy users, with slab widths exact and up the ladder; and one whole
``run_train`` -> ``load_deployment`` -> ``predict`` against plain scoring."""

import datetime as dt
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "lib"))

import reference_cco  # noqa: E402

from incubator_predictionio_tpu.common import telemetry  # noqa: E402
from incubator_predictionio_tpu.ops import llr  # noqa: E402

N_USERS, N_ITEMS, U_CHUNK, K = 1500, 200, 128, 20


def seeded_events(heavy: bool):
    """Three events over one item space; with ``heavy`` two users did nearly
    everything (over 16 times the mean and over 256 distinct pairs)."""
    rng = np.random.default_rng(5)
    events = {}
    for name, n in (("buy", 8000), ("view", 20000), ("cart", 6000)):
        u = rng.integers(0, N_USERS, n).astype(np.int32)
        i = (rng.zipf(1.3, n) % N_ITEMS).astype(np.int32)
        if heavy:
            for who in (7, 900):
                mine = rng.permutation(N_ITEMS)[:150].astype(np.int32)
                u = np.concatenate([u, np.full(len(mine), who, np.int32)])
                i = np.concatenate([i, mine])
        events[name] = (u, i)
    return events


def span_mark() -> int:
    """The highest span id handed out so far: a cursor that still works
    once the ring is full (its length then stands still; another test
    file of the same worker may have filled it)."""
    return max((s.span_id for s in telemetry.spans_snapshot()), default=0)


def cco_device_spans(since: int):
    return [s for s in telemetry.spans_snapshot()
            if s.span_id > since and s.name == "cco.device"]


@pytest.mark.parametrize("laddered", [False, True],
                         ids=["exact_E", "laddered_E"])
@pytest.mark.parametrize("heavy", [False, True], ids=["light", "heavy"])
@pytest.mark.parametrize("path", ["fused", "pair_full", "striped"])
def test_indicators_match_the_plain_reference(monkeypatch, path, heavy,
                                              laddered):
    cap = {"fused": 10 * N_ITEMS ** 2, "pair_full": N_ITEMS ** 2,
           "striped": 1}[path]
    monkeypatch.setenv("PIO_UR_FULL_MATRIX_ELEMS", str(cap))
    if not laddered:
        # the widths as they were: the widest range's own count (the NumPy
        # layout; the native one has the ladder built in)
        from incubator_predictionio_tpu import native

        monkeypatch.setattr(llr, "_ladder", lambda n: max(int(n), 1))
        monkeypatch.setattr(native, "cco_partition",
                            lambda *a, **kw: 1 / 0)
    events = seeded_events(heavy)
    primary = events["buy"]
    seen = span_mark()
    got = llr.cco_indicators_multi(
        *primary, events, n_users=N_USERS, n_items=N_ITEMS,
        max_correlators=K, u_chunk=U_CHUNK, item_block=64)

    spans = cco_device_spans(seen)
    assert {s.tags["path"] for s in spans} == {path}
    assert len(spans) == (1 if path == "fused" else len(events))
    assert all((s.tags["heavy_ranges"] > 0) == heavy for s in spans)
    if laddered:
        assert all(s.tags["E"] == llr._ladder(s.tags["E"]) for s in spans)

    sides = {n: reference_cco.Side(*ui, N_USERS, N_ITEMS)
             for n, ui in events.items()}
    if heavy:
        assert set(reference_cco.heavy_users(
            sides.values() if path == "fused" else
            [sides["buy"], sides["view"]], N_USERS)) == {7, 900}
    rows = np.arange(N_ITEMS)
    for name, ind in got.items():
        ref = reference_cco.reference_scores(
            sides["buy"], sides[name], rows, N_USERS, N_ITEMS)
        gaps = reference_cco.compare(ind.idx, ind.score, ref, rows, K,
                                     N_USERS)
        assert gaps["malformed"] == 0 and gaps["fill_gap"] == 0, (name, gaps)
        # float32 G² against float64: some tens of float32 spacings of the
        # largest term N ln N (0.0013 here) over a floor of 10.7
        assert gaps["score_gap"] < 1e-3 and gaps["rank_gap"] < 1e-3, (name,
                                                                      gaps)


def test_native_and_numpy_layouts_agree_up_the_ladder():
    native = pytest.importorskip("incubator_predictionio_tpu.native")
    events = seeded_events(heavy=True)
    u, i, per_user = llr._dedupe_pair(*events["view"], N_USERS, N_ITEMS)
    rank, h_ranges = np.full(N_USERS, -1, np.int64), 4
    rank[[7, 900]] = [0, 1]
    n_ranges = -(-N_USERS // U_CHUNK)
    try:
        light, heavy, counts = native.cco_partition(
            u, i, rank, N_USERS, U_CHUNK, n_ranges, N_ITEMS,
            llr._HEAVY_RANGE, h_ranges)
    except native.NativeUnavailable as e:
        pytest.skip(str(e))
    hm = rank[u] >= 0
    want_light = llr._partition_by_user(u[~hm], i[~hm], U_CHUNK, n_ranges,
                                        N_ITEMS, assume_sorted=True)
    want_heavy = llr._partition_by_user(
        rank[u[hm]].astype(np.int32), i[hm], llr._HEAVY_RANGE, h_ranges,
        N_ITEMS, assume_sorted=True)
    for got, want in zip(light + heavy, want_light + want_heavy):
        np.testing.assert_array_equal(got, want)
    assert light[0].shape[1] == llr._ladder(light[0].shape[1])
    assert heavy[0].shape == (4, llr._ladder(heavy[0].shape[1]))
    np.testing.assert_array_equal(counts, np.bincount(i, minlength=N_ITEMS))


@pytest.mark.parametrize("n,rung", [(0, 1), (1, 1), (16, 16), (17, 18),
                                    (1000, 1024), (1025, 1152),
                                    (250_743, 262_144), (262_145, 294_912)])
def test_ladder_rungs(n, rung):
    assert llr._ladder(n) == rung
    assert llr._ladder(rung) == rung and rung <= max(n, 1) * 1.125


# -- the whole path: events -> run_train -> artifact -> deployment -> predict -


def _plain_answer(ref_ind: dict, history: dict, exclude, num: int):
    """Plain scoring of a UR query: per event the sum over an item's
    correlators of score x (is the correlator in the history), summed over
    the events; the ``num`` best items not excluded, positive scores only."""
    total = np.zeros(len(exclude))
    for name, (idx, score) in ref_ind.items():
        member = np.where(idx >= 0, history[name][np.maximum(idx, 0)], 0.0)
        total += (score.astype(np.float64) * member).sum(axis=1)
    total[exclude] = -np.inf
    best = np.argsort(-total, kind="stable")[:num]
    return [(int(j), float(total[j])) for j in best if total[j] > 0]


def test_run_train_deploy_and_predict_match_plain_scoring(memory_storage):
    from incubator_predictionio_tpu.controller import EngineParams
    from incubator_predictionio_tpu.data.storage import App, Event
    from incubator_predictionio_tpu.models.universal_recommender import (
        UniversalRecommenderEngine,
    )
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import (
        load_deployment, run_train,
    )

    app_id = memory_storage.get_meta_data_apps().insert(App(0, "ccoref"))
    le = memory_storage.get_l_events()
    le.init(app_id)
    rng = np.random.default_rng(9)
    n_users, n_items, t0 = 60, 30, dt.datetime(2024, 1, 1,
                                               tzinfo=dt.timezone.utc)
    raw = {"buy": [], "view": []}
    for u in range(n_users):
        lo = 0 if u % 2 == 0 else n_items // 2
        for name, n in (("buy", 5), ("view", 9)):
            for j in rng.integers(lo, lo + n_items // 2, n):
                raw[name].append((u, int(j)))
    events = [Event(name, "user", f"u{u}", "item", f"i{j}",
                    event_time=t0 + dt.timedelta(seconds=k))
              for name, pairs in raw.items()
              for k, (u, j) in enumerate(pairs)]
    le.insert_batch(events, app_id)

    engine = UniversalRecommenderEngine()()
    ctx = WorkflowContext(app_name="ccoref", storage=memory_storage)
    params = EngineParams.from_json({
        "datasource": {"params": {"appName": "ccoref",
                                  "eventNames": ["buy", "view"]}},
        "algorithms": [{"name": "ur", "params": {
            # every correlator is kept: a cut at k would fall on ties, which
            # the two sides may break differently
            "appName": "ccoref", "maxCorrelatorsPerItem": n_items,
            "user_chunk": 16}}]})
    seen = span_mark()
    instance = run_train(engine, params, ctx)
    names = {s.name for s in telemetry.spans_snapshot()
             if s.span_id > seen}
    assert {"cco.dedupe", "cco.partition", "cco.device", "cco.gather",
            "ur.popularity"} <= names
    dep, _inst, _ = load_deployment(engine, instance, ctx)
    model = dep.models[0]

    # the plain side, in the model's own numbering of users and items
    def numbered(pairs):
        u, i = zip(*pairs)
        return (np.array([model.users(f"u{x}") for x in u]),
                np.array([model.items(f"i{x}") for x in i]))

    sides = {n: reference_cco.Side(*numbered(p), n_users, n_items)
             for n, p in raw.items()}
    rows = np.arange(n_items)
    ref_ind = {n: reference_cco.top_k(reference_cco.reference_scores(
        sides["buy"], s, rows, n_users, n_items), n_items)
        for n, s in sides.items()}

    def history_of(user=None, items=()):
        out = {}
        for name, side in sides.items():
            h = np.zeros(n_items)
            if user is not None:
                h[side.item[side.user == model.users(user)]] = 1.0
            h[[model.items(x) for x in items]] = 1.0
            out[name] = h
        return out

    def check(query, history, exclude):
        got = dep.query(query)["itemScores"]
        want = _plain_answer(ref_ind, history, exclude, query["num"])
        assert len(got) == len(want) > 0
        # the same scores in the same order; items may swap where scores tie
        np.testing.assert_allclose([g["score"] for g in got],
                                   [s for _, s in want], rtol=2e-4)
        assert {g["item"] for g in got[:-1]} <= {
            model.items.inverse(j) for j, _ in _plain_answer(
                ref_ind, history, exclude, query["num"] + 3)}

    h = history_of(user="u4")
    check({"user": "u4", "num": 5}, h, h["buy"] > 0)
    h = history_of(items=("i3", "i7"))
    excluded = np.zeros(n_items, bool)
    excluded[[model.items("i3"), model.items("i7")]] = True
    check({"itemSet": ["i3", "i7"], "num": 5}, h, excluded)
