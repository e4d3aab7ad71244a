"""A user's repeated views are counted as upstream counts them: the
Similar-Product and E-Commerce templates hand implicit ALS ONE entry a
(user, item) pair whose value is the number of its events
(``reduceByKey(_ + _)`` before ``ALS.trainImplicit``), and the factors are
Hu-Koren-Volinsky's at confidence 1 + alpha x count, held here against the
benchmark's plain reference (``benchmarks/lib/reference_implicit.py``, which
imports nothing of the program)."""

import datetime
import os
import sys

import numpy as np
import pytest

from incubator_predictionio_tpu.common import telemetry
from incubator_predictionio_tpu.controller import EngineParams
from incubator_predictionio_tpu.data.storage import DataMap, Event
from incubator_predictionio_tpu.models import ecommerce, similar_product
from incubator_predictionio_tpu.models.similar_product import count_pairs
from incubator_predictionio_tpu.ops.als import ALSParams, train_als
from incubator_predictionio_tpu.parallel.mesh import mesh_from_devices
from incubator_predictionio_tpu.workflow.context import WorkflowContext
from incubator_predictionio_tpu.workflow.core_workflow import run_train

LIB = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "lib")
if LIB not in sys.path:
    sys.path.insert(0, LIB)

import reference  # noqa: E402
import reference_implicit  # noqa: E402

N_USERS, N_ITEMS, RANK, SWEEPS = 260, 90, 8, 3
#: between the program's own gap to the reference (under 0.01 here) and the
#: least that one entry an event reads (over 0.1)
LIMIT = 0.03


def views(repeats: bool, seed: int = 7):
    """(user, item) of seeded view events in arrival order; with
    ``repeats`` a fifth of the pairs are viewed again and again."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, N_USERS, 2400).astype(np.int32)
    i = (rng.zipf(1.4, 2400) % N_ITEMS).astype(np.int32)
    key = u.astype(np.int64) * N_ITEMS + i
    first = np.sort(np.unique(key, return_index=True)[1])
    u, i = u[first], i[first]
    if repeats:
        times = np.minimum(rng.geometric(0.8, len(u)), 20)
        order = rng.permutation(int(times.sum()))
        u, i = np.repeat(u, times)[order], np.repeat(i, times)[order]
    return u, i


def test_count_pairs_sums_a_pair_and_keeps_first_seen_order():
    u = np.array([3, 1, 3, 0, 1, 3, 2], np.int32)
    i = np.array([5, 2, 5, 0, 2, 4, 5], np.int32)
    r = np.array([1, 1, 1, 1, 2, 1, 1], np.float32)
    cu, ci, cr = count_pairs(u, i, r, 6)
    assert cu.tolist() == [3, 1, 0, 3, 2] and ci.tolist() == [5, 2, 0, 4, 5]
    assert cr.tolist() == [2.0, 3.0, 1.0, 1.0, 1.0] and cr.dtype == np.float32
    # nothing repeated: the arrays come back as they are, all ones still
    ones = np.ones(5, np.float32)
    back = count_pairs(cu, ci, ones, 6)
    assert back[0] is cu and back[1] is ci and back[2] is ones
    empty = np.empty(0, np.int32)
    assert len(count_pairs(empty, empty, np.empty(0, np.float32), 6)[2]) == 0
    span = [s for s in telemetry.spans_snapshot()
            if s.name == "prep.pair_counts"][-3]
    assert span.tags == {"events": 7, "pairs": 5}
    # the reference's own reduction agrees
    ru, ri, rc = reference_implicit.pair_counts(u, i, 6)
    assert (ru.tolist(), ri.tolist()) == (cu.tolist(), ci.tolist())
    assert rc.tolist() == [2.0, 2.0, 1.0, 1.0, 1.0]    # events, not ratings


@pytest.mark.parametrize("n_dev", [1, 8], ids=["one-device", "mesh8"])
@pytest.mark.parametrize("repeats", [True, False],
                         ids=["value-slab", "binary_ratings"])
def test_implicit_als_is_the_references_on_counted_pairs(repeats, n_dev):
    """The system, from events to factors, against the plain reference on
    the generator's counts; with repeated views the value slab carries the
    counts, without them the data is all ones and the value slabs go."""
    import jax

    u, i = views(repeats)
    ones = np.ones(len(u), np.float32)
    cu, ci, cr = count_pairs(u, i, ones, N_ITEMS)
    assert (len(cu) < len(u)) == repeats
    params = ALSParams(rank=RANK, num_iterations=SWEEPS, reg=0.01,
                       implicit_prefs=True, alpha=1.0, seed=3,
                       compute_dtype="bfloat16")
    mesh = mesh_from_devices(devices=jax.devices()[:n_dev])
    got = train_als(cu, ci, cr, N_USERS, N_ITEMS, params, mesh=mesh)
    loop = [s for s in telemetry.spans_snapshot() if s.name == "als.loop"][-1]
    assert loop.tags == {"implicit": True, "binary": not repeats,
                         "solve": "cholesky"}
    wu, wi, wc = reference_implicit.pair_counts(u, i, N_ITEMS)
    want = reference_implicit.implicit_als_reference(
        wu, wi, wc, N_USERS, N_ITEMS, RANK, 0.01, 1.0, 3, SWEEPS, "bfloat16")
    gaps = reference.als_compare(got.user_factors, got.item_factors, *want,
                                 {"user_fro": LIMIT, "item_fro": LIMIT})
    assert gaps["user_fro"][0] < LIMIT and gaps["item_fro"][0] < LIMIT, gaps
    if not repeats:
        return
    # the fault stays caught: one entry an event is another model
    as_given = train_als(u, i, ones, N_USERS, N_ITEMS, params, mesh=mesh)
    fault = reference.als_compare(as_given.user_factors,
                                  as_given.item_factors, *want, {})["_seen"]
    assert fault["user_fro"] > LIMIT and fault["item_fro"] > LIMIT, fault


def test_the_reference_is_the_definition_written_out():
    """The c x c form the reference solves small rows through against one
    Cholesky a row of YtY + Yt(C - I)Y + lambda I in float64."""
    u, i = views(True, seed=11)
    pu, pi, pc = reference_implicit.pair_counts(u, i, N_ITEMS)
    args = (pu, pi, pc, N_USERS, N_ITEMS, RANK, 0.01, 1.0, 3, SWEEPS)
    fast = reference_implicit.implicit_als_reference(*args, "float32")
    dense = reference_implicit.implicit_als_dense(*args, "float32")
    for a, b in zip(fast, dense):
        assert reference.factor_gaps(a, b)["fro"] < 1e-4
    # both forms ran: rows over the rank take the direct elimination
    assert np.bincount(pi).max() > RANK > np.bincount(pu).min()


def test_a_shrunken_rows_cosine_is_its_cosine():
    """Implicit ALS shrinks the row of an item that only one-item users
    view by orders of magnitude a sweep; its cosine is still its cosine
    (the reference's ``cosine`` answers 0 for a zero vector alone)."""
    from incubator_predictionio_tpu.ops.topk import (
        normalize_rows, similar_items,
    )

    rng = np.random.default_rng(0)
    catalog = rng.standard_normal((300, 16)).astype(np.float32)
    catalog[7] = catalog[3] * np.float32(1e-12)      # shrunken, same way
    catalog[9] = 0.0
    unit = normalize_rows(catalog)
    norms = np.linalg.norm(unit.astype(np.float64), axis=1)
    assert np.allclose(np.delete(norms, 9), 1.0, atol=1e-6)
    assert (unit[9] == 0).all() and np.isfinite(unit).all()
    scores, idx = similar_items(catalog[[3]], unit, 3)
    assert idx.tolist()[:2] == [3, 7] and scores[1] > 0.999
    want = reference_implicit.unit_rows(catalog)
    assert np.abs(unit - want).max() < 1e-6


def _post_views(storage, app_name: str):
    from incubator_predictionio_tpu.data.storage import base

    app_id = storage.get_meta_data_apps().insert(base.App(0, app_name, None))
    u, i = views(True, seed=5)
    t0 = datetime.datetime(2014, 7, 1, tzinfo=datetime.timezone.utc)
    step = datetime.timedelta(seconds=1)
    events = [Event("view", "user", f"u{a}", "item", f"i{b}",
                    event_time=t0 + k * step)
              for k, (a, b) in enumerate(zip(u.tolist(), i.tolist()))]
    events += [Event("$set", "item", f"i{b}",
                     properties=DataMap({"categories": ["c"]}),
                     event_time=t0) for b in range(N_ITEMS)]
    storage.get_l_events().insert_batch(events, app_id)
    return u, i


@pytest.mark.parametrize("template", ["similar_product", "ecommerce"])
def test_the_templates_train_sees_the_counted_pairs(memory_storage,
                                                    monkeypatch, template):
    """Both templates read through the one DataSource: what reaches
    ``train_als`` is one entry a pair, its value the number of views."""
    module = {"similar_product": similar_product, "ecommerce": ecommerce}[
        template]
    u, i = _post_views(memory_storage, "shop")
    seen = {}
    stock = module.train_als

    def spy(user_idx, item_idx, rating, **kw):
        seen.update(user=user_idx, item=item_idx, rating=rating,
                    implicit=kw["params"].implicit_prefs)
        return stock(user_idx, item_idx, rating, **kw)

    monkeypatch.setattr(module, "train_als", spy)
    engine = {"similar_product": similar_product.SimilarProductEngine,
              "ecommerce": ecommerce.ECommerceEngine}[template]()()
    # the e-commerce algorithm reads the store at serve time, by app
    name, own = {"similar_product": ("als", {}),
                 "ecommerce": ("ecomm", {"appName": "shop"})}[template]
    ep = EngineParams.from_json({
        "datasource": {"params": {"appName": "shop",
                                  "eventNames": ["view"]}},
        "algorithms": [{"name": name, "params": {
            "rank": 4, "numIterations": 1, **own}}]})
    run_train(engine, ep, WorkflowContext(app_name="shop",
                                          storage=memory_storage),
              engine_factory_name=template)
    assert seen["implicit"] is True
    pair = seen["user"].astype(np.int64) * N_ITEMS + seen["item"]
    assert len(np.unique(pair)) == len(pair) < len(u)
    assert seen["rating"].sum() == len(u) and seen["rating"].max() > 1
    _, _, want = reference_implicit.pair_counts(u, i, N_ITEMS)
    # rows are numbered as ids are first seen, and so are the pairs
    assert seen["rating"].tolist() == want.tolist()
