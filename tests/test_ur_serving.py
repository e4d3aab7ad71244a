"""The Universal Recommender served from resident indicators, against a
plain reference.

The reference below is the query's definition in plain numpy: float64
sums, a loop over event types, the forward ``[I, K]`` arrays, no index and
no device. ``URModel.recommend`` (through ``URAlgorithm.predict``) reads
the same history from the event store, ships it as rows and reads only the
postings that name them; the two have to agree on every query shape, at
the published width (50 correlators an item), with rows shorter than 50
and with tied scores."""

import numpy as np
import pytest

from incubator_predictionio_tpu.data.storage import App, Event
from incubator_predictionio_tpu.data.storage.bimap import IdentityBiMap
from incubator_predictionio_tpu.models.universal_recommender import (
    URAlgorithm, URAlgorithmParams, URModel,
)
from incubator_predictionio_tpu.ops import llr
from incubator_predictionio_tpu.ops.llr import Indicators

N_ITEMS, K, N_CATS = 2000, 50, 5
EVENTS = ("buy", "view")
APP = "urserve"
#: never a correlator of any row: a history of it alone matches nothing
LONELY = N_ITEMS - 1
#: user id -> {event name: item rows} as written to the store
HISTORIES = {
    "u-buyer": {"buy": [3, 17, 170, 3], "view": [5, 17, 900, 41, 42]},
    "u-viewer": {"buy": [], "view": [0, 1, 2, 3, 4, 5, 6, 7]},
    "u-lonely": {"buy": [LONELY], "view": [LONELY]},
}


def _indicators(seed: int) -> dict[str, Indicators]:
    """Seeded indicators: a row's correlators distinct, drawn with a
    popularity skew; a third of the rows shorter than K (padded with -1,
    as `cco_indicators_multi` leaves rare items); scores positive,
    descending along a row and multiples of 1/8, so that float32 sums are
    exact and ties are ties in any order of summation."""
    rng = np.random.default_rng(seed)
    weights = 1.0 / np.arange(1, LONELY + 1) ** 0.75
    weights /= weights.sum()
    out = {}
    for name in EVENTS:
        idx = np.full((N_ITEMS, K), -1, np.int32)
        score = np.zeros((N_ITEMS, K), np.float32)
        for i in range(N_ITEMS):
            n = K if rng.random() > 1 / 3 else int(rng.integers(0, K))
            idx[i, :n] = rng.choice(LONELY, n, replace=False, p=weights)
            score[i, :n] = np.sort(rng.integers(1, 40, n))[::-1] / 8.0
        out[name] = Indicators(idx, score)
    return out


@pytest.fixture(scope="module")
def served():
    """(algorithm, model, storage): the model over a MEMORY event store
    holding `HISTORIES`, warmed up."""
    from incubator_predictionio_tpu.data.storage import Storage

    storage = Storage({
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "MEMORY"})
    app_id = storage.get_meta_data_apps().insert(App(0, APP))
    storage.get_l_events().init(app_id)
    storage.get_l_events().insert_batch([
        Event(name, "user", user, "item", str(row))
        for user, history in HISTORIES.items()
        for name, rows in history.items() for row in rows], app_id)
    rng = np.random.default_rng(11)
    model = URModel(
        indicators=_indicators(5), users=IdentityBiMap(10),
        items=IdentityBiMap(N_ITEMS),
        item_categories={str(i): {f"c{i % N_CATS}"} for i in range(N_ITEMS)},
        app_name=APP, event_names=EVENTS,
        popularity=rng.integers(0, 30, N_ITEMS).astype(np.float32))
    model._storage = storage
    model.warm_up()
    return URAlgorithm(URAlgorithmParams(app_name=APP)), model, storage, app_id


# -- the plain reference -----------------------------------------------------


def reference(model: URModel, history: dict, query: dict) -> list[tuple]:
    """[(item row, score)] best first: ``score_i = boost_i * sum over
    event types e of sum over slots s of score_e[i, s] * [idx_e[i, s] in
    history_e]``, padding slots count nothing, query items join every
    type's history, a field with a bias under 0 filters and one over 0
    multiplies, the blacklist, the query items and the primary event's
    history never appear, only scores over 0 are answers, and no history
    at all means the popularity ranking under the same rules."""
    items = [int(i) for i in (query.get("itemSet") or (
        [query["item"]] if "item" in query else []))]
    rows = {e: set(history.get(e, ())) | set(items) for e in EVENTS}
    if any(rows.values()):
        total = np.zeros(N_ITEMS, np.float64)
        for e in EVENTS:
            member = np.zeros(N_ITEMS + 1, np.float64)
            member[sorted(rows[e])] = 1.0
            ind = model.indicators[e]
            hit = np.where(ind.idx >= 0, member[ind.idx], 0.0)
            total += (ind.score.astype(np.float64) * hit).sum(axis=1)
    else:
        total = model.popularity.astype(np.float64)
    category = np.arange(N_ITEMS) % N_CATS
    allowed = np.ones(N_ITEMS, bool)
    for f in query.get("fields", ()):
        match = np.isin(category, [int(v[1:]) for v in f["values"]])
        if f.get("bias", -1) < 0:
            allowed &= match
        else:
            total = total * np.where(match, f["bias"], 1.0)
    forbidden = (set(items) | rows[EVENTS[0]]
                 | {int(i) for i in query.get("blacklistItems", ())})
    allowed[sorted(forbidden)] = False
    total[~allowed] = -np.inf
    order = np.argsort(-total, kind="stable")[:query["num"]]
    return [(int(j), float(total[j])) for j in order if total[j] > 0]


FILTER = {"name": "categories", "values": ["c1", "c3"], "bias": -1}
BOOST = {"name": "categories", "values": ["c2"], "bias": 2.0}
SHAPES = {
    "user": {"user": "u-buyer"},
    "item": {"item": "17"},
    "itemSet": {"itemSet": ["3", "5", "900", "1234"]},
    "user+item": {"user": "u-viewer", "item": "41"},
    "filter": {"user": "u-buyer", "fields": [FILTER]},
    "boost": {"user": "u-buyer", "fields": [BOOST]},
    "filter+boost": {"user": "u-viewer", "fields": [FILTER, BOOST]},
    "blacklist": {"user": "u-buyer", "blacklistItems": None},  # see below
    "unknown_user": {"user": "nobody"},
    "matches_nothing": {"user": "u-lonely"},
}


@pytest.mark.parametrize("num", [4, 20])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_recommend_agrees_with_the_plain_reference(served, shape, num):
    algo, model, _storage, _app = served
    query = dict(SHAPES[shape], num=num)
    history = {e: set(HISTORIES.get(query.get("user"), {}).get(e, ()))
               for e in EVENTS}
    if shape == "blacklist":
        # the rule has to bite: the user's own unfiltered best items
        best = reference(model, history, {"num": 20})
        query["blacklistItems"] = [str(j) for j, _s in best[:num:2]]
    want = reference(model, history, query)
    got = algo.predict(model, query)["itemScores"]
    assert [int(s["item"]) for s in got] == [j for j, _s in want]
    np.testing.assert_allclose([s["score"] for s in got],
                               [s for _j, s in want], rtol=1e-6)
    if shape == "matches_nothing":
        assert got == []
    elif shape in ("unknown_user", "user", "boost"):
        assert len(got) == num
    if shape == "blacklist":
        assert not {s["item"] for s in got} & set(query["blacklistItems"])


def test_the_reference_sees_ties_and_short_rows(served):
    """What the cases above rest on: rows shorter than K, and answers in
    which a tie was broken."""
    _algo, model, _storage, _app = served
    short = sum(int((ind.idx < 0).any(axis=1).sum())
                for ind in model.indicators.values())
    assert short > N_ITEMS // 2
    best = reference(model, HISTORIES["u-buyer"], {"num": 20})
    scores = [s for _j, s in best]
    assert len(set(scores)) < len(scores)


# -- the state is resident ---------------------------------------------------


def test_a_query_after_warm_up_ships_rows_not_the_model(served, monkeypatch):
    """After warm-up a query puts its history rows, its rule rows and
    scalars: nothing as long as the catalog, and the indicators are not
    placed again."""
    import jax

    algo, model, _storage, _app = served
    shipped = []
    put, score, rank = jax.device_put, llr._ur_score, llr._ur_rank

    def host_bytes(args):
        return [a.nbytes for a in jax.tree_util.tree_leaves(args)
                if isinstance(a, np.ndarray)]

    monkeypatch.setattr(jax, "device_put", lambda x, *a, **kw: (
        shipped.extend(host_bytes(x)), put(x, *a, **kw))[1])
    monkeypatch.setattr(llr, "_ur_score", lambda *a, **kw: (
        shipped.extend(host_bytes(a)), score(*a, **kw))[1])
    monkeypatch.setattr(llr, "_ur_rank", lambda *a, **kw: (
        shipped.extend(host_bytes(a)), rank(*a, **kw))[1])
    monkeypatch.setattr(llr, "place_indicators", lambda *a, **kw: (
        _ for _ in ()).throw(AssertionError("the model was placed again")))
    for shape in ("user", "filter+boost", "blacklist", "unknown_user"):
        query = dict(SHAPES[shape], num=20)
        if shape == "blacklist":
            query["blacklistItems"] = ["7", "8"]
        assert algo.predict(model, query)["itemScores"]
    assert shipped, "the query's rows did cross"
    # the packed rule rows (2 x 4,096 + 1 int32) and the history rows (two
    # event types x the ladder's first step): shapes the catalog's length
    # is no part of, and under a fortieth of the indicators' bytes
    assert set(shipped) <= {4 * (2 * 4096 + 1), 4 * len(EVENTS) * 16}
    assert 40 * max(shipped) < model.resident().nbytes


def test_an_event_written_between_two_queries_changes_the_second(served):
    """Read-your-write: no cache of the store's answers."""
    algo, model, storage, app_id = served
    query = {"user": "u-fresh", "num": 4}
    before = algo.predict(model, query)["itemScores"]   # backfill
    storage.get_l_events().insert(
        Event("buy", "user", "u-fresh", "item", "17"), app_id)
    after = algo.predict(model, query)["itemScores"]
    want = reference(model, {"buy": {17}, "view": set()}, {"num": 4})
    assert [int(s["item"]) for s in after] == [j for j, _s in want]
    assert after != before
    assert "17" not in {s["item"] for s in after}


def test_histories_of_any_length_share_the_ladders_executables(served):
    """Histories of 1 to 500 rows compile no more executables than the
    history ladder has steps (here: none, warm-up compiled them)."""
    _algo, model, _storage, _app = served
    resident = model.resident()
    rng = np.random.default_rng(2)
    compiled = llr._ur_score._cache_size()
    for n in (1, 2, 15, 16, 17, 100, 128, 129, 333, 500):
        rows = rng.choice(N_ITEMS, n, replace=False)
        llr.score_rows(resident, {"buy": rows, "view": rows[:n // 2]}, 20)
    assert llr._ur_score._cache_size() == compiled
    # and from cold: a step each, whatever ``num`` and the rules
    llr._ur_score.clear_cache()
    for n in (1, 2, 15, 16, 17, 100, 128, 129, 333, 500):
        rows = rng.choice(N_ITEMS, n, replace=False)
        llr.score_rows(resident, {"buy": rows}, 20)
    assert llr._ur_score._cache_size() == len(llr._HISTORY_LADDER)
