"""Serve-time sharded models (the PAlgorithm serving analog).

Reference: core/.../controller/PAlgorithm.scala — batchPredict: models
that stay distributed at serve time. Here: item-factor catalogs sharded
over every device of the 8-CPU virtual mesh, queried via per-shard top-k
+ k-candidate all_gather merge (ops/sharded_topk.py). The invariant under
test is bit-identity with the single-device kernels for the matvec and
similarity paths, and identical indices/ordering (scores ≤2 ULP — gemm
output-shape blocking, documented in the module) for the batched path.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from incubator_predictionio_tpu.ops.sharded_topk import (  # noqa: E402
    put_sharded_catalog,
    sharded_batch_top_k,
    sharded_similar_items,
    sharded_top_k_items,
    should_shard_serving,
)
from incubator_predictionio_tpu.ops.topk import (  # noqa: E402
    batch_top_k,
    similar_items,
    top_k_items,
)
from incubator_predictionio_tpu.parallel.mesh import (  # noqa: E402
    mesh_from_devices,
)


@pytest.fixture(scope="module")
def catalog():
    rng = np.random.default_rng(7)
    n_items, rank = 1003, 16  # deliberately not a multiple of 8 (padding)
    items = rng.normal(size=(n_items, rank)).astype(np.float32)
    return items


@pytest.fixture(scope="module")
def mesh8():
    return mesh_from_devices()  # 1-D over the 8 virtual CPU devices


# -- kernel-level identity --------------------------------------------------


def test_single_query_bit_identical(catalog, mesh8):
    cat = put_sharded_catalog(catalog, mesh8)
    rng = np.random.default_rng(1)
    for _ in range(3):
        uv = rng.normal(size=(catalog.shape[1],)).astype(np.float32)
        s0, i0 = top_k_items(uv, catalog, 10)
        s1, i1 = sharded_top_k_items(uv, cat, 10)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(s0, s1)  # bitwise


def test_single_query_with_exclude_bit_identical(catalog, mesh8):
    cat = put_sharded_catalog(catalog, mesh8)
    rng = np.random.default_rng(2)
    uv = rng.normal(size=(catalog.shape[1],)).astype(np.float32)
    excl = np.zeros(catalog.shape[0], bool)
    excl[rng.integers(0, catalog.shape[0], 300)] = True
    s0, i0 = top_k_items(uv, catalog, 25, exclude=excl)
    s1, i1 = sharded_top_k_items(uv, cat, 25, exclude=excl)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


def test_similarity_bit_identical(catalog, mesh8):
    from incubator_predictionio_tpu.ops.topk import normalize_rows

    normed = normalize_rows(catalog)
    cat = put_sharded_catalog(normed, mesh8)
    qv = catalog[[3, 77, 500]]
    excl = np.zeros(catalog.shape[0], bool)
    excl[[3, 77, 500]] = True
    s0, i0 = similar_items(qv, normed, 9, exclude=excl)
    s1, i1 = sharded_similar_items(qv, cat, 9, exclude=excl)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


def test_batch_identical_selection(catalog, mesh8):
    cat = put_sharded_catalog(catalog, mesh8)
    rng = np.random.default_rng(3)
    uvs = rng.normal(size=(13, catalog.shape[1])).astype(np.float32)
    s0, i0 = batch_top_k(uvs, catalog, 7)
    s1, i1 = sharded_batch_top_k(uvs, cat, 7)
    np.testing.assert_array_equal(i0, i1)  # same items, same order
    np.testing.assert_allclose(s0, s1, rtol=0, atol=4e-6)


def test_2d_mesh_matches_1d(catalog):
    """The (d, m)=(4, 2) ALX mesh serves the same answers as the 1-D
    mesh and as a single device — sharding layout is invisible."""
    mesh2 = mesh_from_devices(shape=(4, 2), axis_names=("d", "m"))
    cat = put_sharded_catalog(catalog, mesh2)
    rng = np.random.default_rng(4)
    uv = rng.normal(size=(catalog.shape[1],)).astype(np.float32)
    s0, i0 = top_k_items(uv, catalog, 12)
    s1, i1 = sharded_top_k_items(uv, cat, 12)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


def test_k_larger_than_shard_rows(mesh8):
    """k greater than a shard's local row count: every shard contributes
    all of its rows and the merge is still exact."""
    rng = np.random.default_rng(5)
    items = rng.normal(size=(40, 4)).astype(np.float32)  # 5 rows/shard
    cat = put_sharded_catalog(items, mesh8)
    uv = rng.normal(size=(4,)).astype(np.float32)
    s0, i0 = top_k_items(uv, items, 20)
    s1, i1 = sharded_top_k_items(uv, cat, 20)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


def test_tie_break_matches_lax_top_k(mesh8):
    """Duplicate scores across shards: the merge must pick the lowest
    global index first, exactly like lax.top_k on the unsharded row."""
    items = np.zeros((64, 2), np.float32)
    items[:, 0] = np.repeat([5.0, 4.0, 3.0, 2.0], 16)  # many exact ties
    cat = put_sharded_catalog(items, mesh8)
    uv = np.array([1.0, 0.0], np.float32)
    s0, i0 = top_k_items(uv, items, 24)
    s1, i1 = sharded_top_k_items(uv, cat, 24)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


# -- sharding policy --------------------------------------------------------


def test_should_shard_policy(mesh8, monkeypatch):
    assert not should_shard_serving(10**6, 64, None, "always")
    assert not should_shard_serving(10**6, 64, mesh8, "never")
    assert should_shard_serving(100, 4, mesh8, "always")
    monkeypatch.setenv("PIO_SHARDED_SERVING_BYTES", "1000000")
    assert should_shard_serving(10**6, 64, mesh8, "auto")
    assert not should_shard_serving(100, 4, mesh8, "auto")
    single = mesh_from_devices(devices=jax.devices()[:1])
    assert not should_shard_serving(10**9, 128, single, "always")
    with pytest.raises(ValueError):
        should_shard_serving(1, 1, mesh8, "sometimes")


# -- template-level: sharded deployment answers like a single chip ----------


def _train_recommendation(memory_storage, sharded: str):
    import datetime as dt

    from incubator_predictionio_tpu.controller import EngineParams
    from incubator_predictionio_tpu.data.storage import App, DataMap, Event
    from incubator_predictionio_tpu.models.recommendation import (
        RecommendationEngine,
    )
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import (
        load_deployment,
        run_train,
    )

    name = f"shardapp-{sharded}"
    app_id = memory_storage.get_meta_data_apps().insert(App(0, name))
    le = memory_storage.get_l_events()
    le.init(app_id)
    rng = np.random.default_rng(11)
    t0 = dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc)
    events = []
    for n in range(600):
        u, i = int(rng.integers(0, 40)), int(rng.integers(0, 60))
        events.append(
            Event("rate", "user", str(u), "item", str(i),
                  properties=DataMap({"rating": float(1 + (u * i) % 5)}),
                  event_time=t0 + dt.timedelta(seconds=n)))
    le.insert_batch(events, app_id)

    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name=name, storage=memory_storage)
    ep = EngineParams.from_json({
        "datasource": {"params": {"appName": name}},
        "algorithms": [{"name": "als", "params": {
            "rank": 8, "numIterations": 3, "computeDtype": "float32",
            "shardedServing": sharded}}],
    })
    iid = run_train(engine, ep, ctx, engine_factory_name=f"rec-{sharded}")
    dep, _, _ = load_deployment(
        engine, iid, WorkflowContext(storage=memory_storage),
        engine_factory_name=f"rec-{sharded}")
    return dep


def test_recommendation_template_sharded_matches_single(memory_storage):
    dep_plain = _train_recommendation(memory_storage, "never")
    dep_shard = _train_recommendation(memory_storage, "always")
    model = dep_shard.models[0]
    assert model.serving_mesh is not None, "always → sharded deployment"
    for user in ("1", "7", "23", "unknown-user"):
        q = {"user": user, "num": 5}
        assert dep_shard.query(q) == dep_plain.query(q)
    # batched path (the serving micro-batch / pio batchpredict surface)
    qs = [{"user": str(u), "num": 4} for u in (0, 3, 9, 31, 39)]
    out_s = dep_shard.batch_query(qs)
    out_p = dep_plain.batch_query(qs)
    for a, b in zip(out_s, out_p):
        assert [x["item"] for x in a["itemScores"]] == [
            x["item"] for x in b["itemScores"]]
        np.testing.assert_allclose(
            [x["score"] for x in a["itemScores"]],
            [x["score"] for x in b["itemScores"]], rtol=0, atol=4e-6)


def test_similar_product_template_sharded_matches_single(memory_storage):
    import datetime as dt

    from incubator_predictionio_tpu.controller import EngineParams
    from incubator_predictionio_tpu.data.storage import App, DataMap, Event
    from incubator_predictionio_tpu.models.similar_product import (
        SimilarProductEngine,
    )
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import (
        load_deployment,
        run_train,
    )

    name = "spshard"
    app_id = memory_storage.get_meta_data_apps().insert(App(0, name))
    le = memory_storage.get_l_events()
    le.init(app_id)
    rng = np.random.default_rng(13)
    t0 = dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc)
    events = [
        Event("view", "user", str(int(rng.integers(0, 30))),
              "item", str(int(rng.integers(0, 50))),
              event_time=t0 + dt.timedelta(seconds=n))
        for n in range(400)
    ]
    le.insert_batch(events, app_id)

    engine = SimilarProductEngine()()
    ctx = WorkflowContext(app_name=name, storage=memory_storage)
    deps = {}
    for mode in ("never", "always"):
        ep = EngineParams.from_json({
            "datasource": {"params": {"appName": name}},
            "algorithms": [{"name": "als", "params": {
                "rank": 8, "numIterations": 3, "computeDtype": "float32",
                "shardedServing": mode}}],
        })
        iid = run_train(engine, ep, ctx, engine_factory_name=f"sp-{mode}")
        deps[mode], _, _ = load_deployment(
            engine, iid, WorkflowContext(storage=memory_storage),
            engine_factory_name=f"sp-{mode}")
    assert deps["always"].models[0].serving_mesh is not None
    for q in ({"items": ["1"], "num": 5},
              {"items": ["2", "9"], "num": 7},
              {"items": ["3"], "num": 5, "blackList": ["4", "5"]}):
        assert deps["always"].query(q) == deps["never"].query(q)


def test_identity_bimap_semantics():
    """IdentityBiMap (huge-catalog serving) must behave exactly like a
    materialized str(i)->i BiMap on every surface models touch."""
    from incubator_predictionio_tpu.data.storage.bimap import (
        BiMap, IdentityBiMap,
    )

    real = BiMap({str(j): j for j in range(10)})
    lazy = IdentityBiMap(10)
    assert len(lazy) == len(real)
    for k in ("0", "7", "9", "10", "-1", "07", "+3", " 5", "x", None):
        assert lazy.get(k) == real.get(k), k
        assert (k in lazy) == (k in real), k
    for v in range(10):
        assert lazy.inverse(v) == real.inverse(v)
    assert lazy.inverse_get(10) is None
    assert list(lazy.keys()) == list(real.keys())
    assert lazy.to_dict() == real.to_dict()
    np = __import__("numpy")
    assert np.array_equal(lazy.map_array(["3", "1"]),
                          real.map_array(["3", "1"]))
    assert lazy.inverse_array([2, 5]) == real.inverse_array([2, 5])


def test_identity_bimap_persistence_round_trip(memory_storage):
    """An IdentityBiMap-backed model persists as a compact marker and
    restores as IdentityBiMap — never materializing the huge dict."""
    from incubator_predictionio_tpu.controller.base import doer
    from incubator_predictionio_tpu.data.storage.bimap import (
        BiMap, IdentityBiMap,
    )
    from incubator_predictionio_tpu.models.recommendation import (
        ALSAlgorithm, ALSModel,
    )
    from incubator_predictionio_tpu.ops.als import ALSFactors

    rng = np.random.default_rng(0)
    model = ALSModel(
        factors=ALSFactors(rng.random((4, 3)).astype(np.float32),
                           rng.random((6, 3)).astype(np.float32), 4, 6),
        users=BiMap({str(j): j for j in range(4)}),
        items=IdentityBiMap(6),
    )
    algo = doer(ALSAlgorithm, {})
    stored = algo.prepare_model_for_persistence(model)
    assert stored["items"] == {"__identity_n__": 6}  # compact, not 6 entries
    restored = algo.restore_model(stored, None)
    assert isinstance(restored.items, IdentityBiMap)
    assert restored.items.inverse(5) == "5"
    assert isinstance(restored.users, BiMap)
    assert restored.users("2") == 2


def test_identity_bimap_rejects_non_str_keys_like_dict_bimap():
    from incubator_predictionio_tpu.data.storage.bimap import (
        BiMap, IdentityBiMap,
    )

    real = BiMap({str(j): j for j in range(10)})
    lazy = IdentityBiMap(10)
    for k in (4, np.int32(4), 4.0, True):
        assert lazy.get(k) == real.get(k) is None, k
    ks = lazy.keys()
    assert len(ks) == 10
    assert list(ks) == list(ks)  # re-iterable, unlike a generator
    assert "7" in ks and "10" not in ks


def test_big_catalog_demo_smoke(monkeypatch):
    """tools/big_catalog_demo.py at toy scale: the capability script must
    keep running end to end (its recorded 17.2 GiB run is only credible
    while the script works)."""
    import importlib.util
    import os

    monkeypatch.setenv("PIO_DEMO_ITEMS", "8000")
    monkeypatch.setenv("PIO_DEMO_RANK", "8")
    spec = importlib.util.spec_from_file_location(
        "big_catalog_demo",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "big_catalog_demo.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main() == 0


# -- host-sharded (stacked-scan) kernel identity ----------------------------
# ISSUE 17: PIO_SERVE_SHARD_ITEMS stacks the catalog [S, rows, rank] on
# ONE device and scans a per-shard partial top-k; exactness contract is
# the same as the mesh path — bitwise identical on the matvec/similarity
# paths, identical indices (scores ≤2 ULP) on the batched gemm path.

from incubator_predictionio_tpu.models._sharded_serving import (  # noqa: E402
    ShardedCatalog,
    ShardedIndicators,
)
from incubator_predictionio_tpu.ops.llr import (  # noqa: E402
    Indicators,
    score_user,
)
from incubator_predictionio_tpu.ops.sharded_topk import (  # noqa: E402
    host_sharded_batch_top_k,
    host_sharded_score_user,
    host_sharded_similar_items,
    host_sharded_top_k_items,
    put_host_sharded_catalog,
    put_host_sharded_indicators,
)
from incubator_predictionio_tpu.ops.topk import normalize_rows  # noqa: E402


def _rows_for(n_items: int, shards: int) -> int:
    return -(-n_items // shards)


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_host_sharded_single_query_bit_identical(catalog, shards):
    cat = put_host_sharded_catalog(catalog, _rows_for(len(catalog), shards))
    assert cat.n_shards == shards
    rng = np.random.default_rng(11)
    for k in (1, 10, 37):
        uv = rng.normal(size=(catalog.shape[1],)).astype(np.float32)
        s0, i0 = top_k_items(uv, catalog, k)
        s1, i1 = host_sharded_top_k_items(uv, cat, k)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(s0, s1)  # bitwise


@pytest.mark.parametrize("shards", [2, 4])
def test_host_sharded_exclude_bit_identical(catalog, shards):
    cat = put_host_sharded_catalog(catalog, _rows_for(len(catalog), shards))
    rng = np.random.default_rng(12)
    uv = rng.normal(size=(catalog.shape[1],)).astype(np.float32)
    exclude = rng.random(len(catalog)) < 0.5
    s0, i0 = top_k_items(uv, catalog, 10, exclude=exclude)
    s1, i1 = host_sharded_top_k_items(uv, cat, 10, exclude=exclude)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)
    assert not exclude[np.asarray(i1)].any()


def test_host_sharded_all_filtered_shard(catalog):
    """An entirely business-rule-excluded shard contributes only -inf
    fillers and the merge still reproduces the unsharded answer."""
    rows = _rows_for(len(catalog), 4)
    cat = put_host_sharded_catalog(catalog, rows)
    rng = np.random.default_rng(13)
    uv = rng.normal(size=(catalog.shape[1],)).astype(np.float32)
    exclude = np.zeros(len(catalog), bool)
    exclude[rows:2 * rows] = True  # shard 1 fully suppressed
    s0, i0 = top_k_items(uv, catalog, 10, exclude=exclude)
    s1, i1 = host_sharded_top_k_items(uv, cat, 10, exclude=exclude)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


def test_host_sharded_k_larger_than_shard_rows(catalog):
    """k > rows-per-shard: per-shard partials are clamped to the shard
    and the merge still assembles the exact global top-k."""
    cat = put_host_sharded_catalog(catalog, 7)  # 144 shards of 7 rows
    rng = np.random.default_rng(14)
    uv = rng.normal(size=(catalog.shape[1],)).astype(np.float32)
    s0, i0 = top_k_items(uv, catalog, 50)
    s1, i1 = host_sharded_top_k_items(uv, cat, 50)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


def test_host_sharded_duplicate_scores_tie_break(catalog):
    """Duplicate scores across shard boundaries: the two-key merge sort
    must reproduce lax.top_k's tie order (lowest global index first)."""
    items = np.ones((64, 4), np.float32)  # every item scores identically
    uv = np.ones(4, np.float32)
    for shards in (2, 4):
        cat = put_host_sharded_catalog(items, _rows_for(64, shards))
        s0, i0 = top_k_items(uv, items, 9)
        s1, i1 = host_sharded_top_k_items(uv, cat, 9)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(s0, s1)


@pytest.mark.parametrize("shards", [2, 4])
def test_host_sharded_similarity_bit_identical(catalog, shards):
    normed = normalize_rows(catalog)
    cat = put_host_sharded_catalog(normed, _rows_for(len(catalog), shards))
    rng = np.random.default_rng(15)
    qvecs = catalog[rng.integers(0, len(catalog), size=3)]
    exclude = np.zeros(len(catalog), bool)
    exclude[:5] = True
    s0, i0 = similar_items(qvecs, normed, 10, exclude=exclude)
    s1, i1 = host_sharded_similar_items(qvecs, cat, 10, exclude=exclude)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


@pytest.mark.parametrize("shards", [2, 4])
def test_host_sharded_batch_identical_selection(catalog, shards):
    cat = put_host_sharded_catalog(catalog, _rows_for(len(catalog), shards))
    rng = np.random.default_rng(16)
    uvecs = rng.normal(size=(5, catalog.shape[1])).astype(np.float32)
    s0, i0 = batch_top_k(uvecs, catalog, 10)
    s1, i1 = host_sharded_batch_top_k(uvecs, cat, 10)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_allclose(s0, s1, rtol=0, atol=4e-6)  # gemm ULPs


def _toy_indicators(rng, n_items: int, kc: int = 6) -> Indicators:
    idx = rng.integers(-1, n_items, size=(n_items, kc)).astype(np.int32)
    score = rng.random((n_items, kc)).astype(np.float32)
    return Indicators(idx=idx, score=score)


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_host_sharded_ur_score_user_bit_identical(shards):
    """Universal-recommender scoring: the per-type correlator tables
    shard the same way and the merged answer is bitwise identical
    (row-wise einsum reduction is row-count-invariant)."""
    rng = np.random.default_rng(17)
    n_items = 101
    rows = _rows_for(n_items, shards)
    inds = {"view": _toy_indicators(rng, n_items),
            "buy": _toy_indicators(rng, n_items, kc=3)}
    membership = {n: (rng.random(n_items) < 0.3).astype(np.float32)
                  for n in inds}
    boost = np.where(rng.random(n_items) < 0.1, 2.0, 1.0).astype(np.float32)
    exclude = rng.random(n_items) < 0.2
    plain = [(inds[n], membership[n], b)
             for n, b in (("view", 1.0), ("buy", 2.0))]
    s0, i0 = score_user(plain, 10, exclude=exclude, item_boost=boost)
    sharded = [(put_host_sharded_indicators(inds[n], rows), membership[n], b)
               for n, b in (("view", 1.0), ("buy", 2.0))]
    s1, i1 = host_sharded_score_user(sharded, 10, n_items,
                                     exclude, boost)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_sharded_catalog_facade_layout_selection(catalog, monkeypatch):
    """ShardedCatalog picks flat with the knob unset, host when the
    knob is smaller than the vocabulary, flat when it is not."""
    monkeypatch.delenv("PIO_SERVE_SHARD_ITEMS", raising=False)
    assert ShardedCatalog(catalog).layout == "flat"
    monkeypatch.setenv("PIO_SERVE_SHARD_ITEMS", "100")
    cat = ShardedCatalog(catalog)
    assert cat.layout == "host" and cat.n_shards == 11
    s0, i0 = top_k_items(np.ones(catalog.shape[1], np.float32), catalog, 10)
    s1, i1 = cat.top_k(np.ones(catalog.shape[1], np.float32), 10)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)
    monkeypatch.setenv("PIO_SERVE_SHARD_ITEMS", str(len(catalog) + 1))
    assert ShardedCatalog(catalog).layout == "flat"


def test_sharded_indicators_facade_layout_selection(monkeypatch):
    rng = np.random.default_rng(18)
    inds = {"view": _toy_indicators(rng, 40)}
    monkeypatch.delenv("PIO_SERVE_SHARD_ITEMS", raising=False)
    assert ShardedIndicators(inds, 40).layout == "flat"
    monkeypatch.setenv("PIO_SERVE_SHARD_ITEMS", "16")
    si = ShardedIndicators(inds, 40)
    assert si.layout == "host"
    m = (rng.random(40) < 0.4).astype(np.float32)
    s0, i0 = score_user([(inds["view"], m, 1.0)], 5,
                        exclude=None, item_boost=None)
    s1, i1 = si.score_user([("view", m, 1.0)], 5,
                           exclude=None, item_boost=None)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


@pytest.mark.parametrize("k,excluded", [(10, False), (4, True)])
def test_three_layouts_bit_identical_across_block_selection(
        mesh8, monkeypatch, k, excluded):
    """A catalog large enough that the flat layout selects by blocks
    (ops/topk._select_topk) instead of ``lax.top_k`` of the whole row:
    flat, mesh and host still answer bit for bit alike, through the
    facade, with and without a business-rule mask, on heavy ties too."""
    from incubator_predictionio_tpu.ops import topk

    n_items, rank = 4099, 16  # not a multiple of 8, of 128 or of the shards
    assert topk._select_block_len(n_items, k) == 128
    rng = np.random.default_rng(19)
    items = rng.normal(size=(n_items, rank)).astype(np.float32)
    items[rng.integers(0, n_items, 600)] = items[7]  # duplicate rows: ties
    exclude = (rng.random(n_items) < 0.4) if excluded else None
    monkeypatch.delenv("PIO_SERVE_SHARD_ITEMS", raising=False)
    flat = ShardedCatalog(items)
    mesh = ShardedCatalog(items, serving_mesh=mesh8)
    monkeypatch.setenv("PIO_SERVE_SHARD_ITEMS", "1000")
    host = ShardedCatalog(items)
    assert (flat.layout, mesh.layout, host.layout) == ("flat", "mesh", "host")
    blocks = topk._M_SELECT.labels("blocks")
    before = blocks.value()
    for uv in (rng.normal(size=rank).astype(np.float32), items[7]):
        s0, i0 = flat.top_k(uv, k, exclude=exclude)
        for other in (mesh, host):
            s1, i1 = other.top_k(uv, k, exclude=exclude)
            np.testing.assert_array_equal(i0, i1)
            np.testing.assert_array_equal(s0, s1)  # bitwise
    assert blocks.value() == before + 2  # the flat calls, and only they
