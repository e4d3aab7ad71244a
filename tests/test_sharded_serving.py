"""Serve-time sharded models (the PAlgorithm serving analog).

Reference: core/.../controller/PAlgorithm.scala — batchPredict: models
that stay distributed at serve time. Here: item-factor catalogs sharded
over a mesh of 2, 4 or 8 of the virtual CPU devices, queried via per-shard
top-k + k-candidate all_gather merge (ops/sharded_topk.py). The invariant under
test is bit-identity with the single-device kernels for the matvec and
similarity paths, and identical indices/ordering (scores ≤2 ULP — gemm
output-shape blocking, documented in the module) for the batched path.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from incubator_predictionio_tpu.models._sharded_serving import (  # noqa: E402
    ShardedCatalog,
)
from incubator_predictionio_tpu.ops import sharded_topk  # noqa: E402
from incubator_predictionio_tpu.ops.sharded_topk import (  # noqa: E402
    put_sharded_catalog,
    sharded_batch_top_k,
    sharded_similar_items,
    sharded_top_k_items,
    should_shard_serving,
)
from incubator_predictionio_tpu.ops.topk import (  # noqa: E402
    batch_top_k,
    normalize_rows,
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


@pytest.fixture(scope="module", params=[2, 4, 8])
def mesh(request):
    """1-D mesh over the first 2, 4 or 8 virtual CPU devices: the
    per-shard cut and the merge are held to the flat answer at several
    shard counts (1003 rows pad differently under each)."""
    return mesh_from_devices(devices=jax.devices()[:request.param])


# -- kernel-level identity --------------------------------------------------


def test_single_query_bit_identical(catalog, mesh):
    cat = put_sharded_catalog(catalog, mesh)
    assert cat.n_shards == mesh.size
    rng = np.random.default_rng(1)
    for k in (1, 10, 37):
        uv = rng.normal(size=(catalog.shape[1],)).astype(np.float32)
        s0, i0 = top_k_items(uv, catalog, k)
        s1, i1 = sharded_top_k_items(uv, cat, k)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(s0, s1)  # bitwise


def test_single_query_with_exclude_bit_identical(catalog, mesh):
    cat = put_sharded_catalog(catalog, mesh)
    rng = np.random.default_rng(2)
    uv = rng.normal(size=(catalog.shape[1],)).astype(np.float32)
    excl = np.zeros(catalog.shape[0], bool)
    excl[rng.integers(0, catalog.shape[0], 300)] = True
    s0, i0 = top_k_items(uv, catalog, 25, exclude=excl)
    s1, i1 = sharded_top_k_items(uv, cat, 25, exclude=excl)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)
    assert not excl[np.asarray(i1)].any()


def test_all_filtered_shard(catalog, mesh):
    """A shard whose every row a business rule excludes contributes only
    -inf fillers and the merge still reproduces the unsharded answer."""
    cat = put_sharded_catalog(catalog, mesh)
    rows = cat.padded_rows // cat.n_shards
    rng = np.random.default_rng(13)
    uv = rng.normal(size=(catalog.shape[1],)).astype(np.float32)
    excl = np.zeros(len(catalog), bool)
    excl[rows:2 * rows] = True  # shard 1 fully suppressed
    s0, i0 = top_k_items(uv, catalog, 10, exclude=excl)
    s1, i1 = sharded_top_k_items(uv, cat, 10, exclude=excl)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


def test_similarity_bit_identical(catalog, mesh):
    normed = normalize_rows(catalog)
    cat = put_sharded_catalog(normed, mesh)
    qv = catalog[[3, 77, 500]]
    excl = np.zeros(catalog.shape[0], bool)
    excl[[3, 77, 500]] = True
    s0, i0 = similar_items(qv, normed, 9, exclude=excl)
    s1, i1 = sharded_similar_items(qv, cat, 9, exclude=excl)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


def test_batch_identical_selection(catalog, mesh):
    cat = put_sharded_catalog(catalog, mesh)
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


def test_k_larger_than_shard_rows(mesh):
    """k greater than a shard's local row count: every shard contributes
    all of its rows and the merge is still exact."""
    rng = np.random.default_rng(5)
    items = rng.normal(size=(40, 4)).astype(np.float32)  # 5-20 rows/shard
    cat = put_sharded_catalog(items, mesh)
    uv = rng.normal(size=(4,)).astype(np.float32)
    s0, i0 = top_k_items(uv, items, 30)
    s1, i1 = sharded_top_k_items(uv, cat, 30)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


def test_tie_break_matches_lax_top_k(mesh):
    """Duplicate scores across shards: the merge must pick the lowest
    global index first, exactly like lax.top_k on the unsharded row."""
    items = np.zeros((64, 2), np.float32)
    items[:, 0] = np.repeat([5.0, 4.0, 3.0, 2.0], 16)  # many exact ties
    cat = put_sharded_catalog(items, mesh)
    uv = np.array([1.0, 0.0], np.float32)
    s0, i0 = top_k_items(uv, items, 24)
    s1, i1 = sharded_top_k_items(uv, cat, 24)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


# -- sharding policy --------------------------------------------------------


def test_should_shard_policy(mesh8):
    assert not should_shard_serving(10**6, 64, None, "always")
    assert not should_shard_serving(10**6, 64, mesh8, "never")
    assert should_shard_serving(100, 4, mesh8, "always")
    assert not should_shard_serving(100, 4, mesh8, "auto")
    single = mesh_from_devices(devices=jax.devices()[:1])
    assert not should_shard_serving(10**9, 128, single, "always")
    with pytest.raises(ValueError):
        should_shard_serving(1, 1, mesh8, "sometimes")


def test_auto_follows_device_memory(mesh8, monkeypatch):
    """``auto`` shards when the float32 catalog is over a quarter of
    what the device reports, and nothing but ``shardedServing`` moves
    that line."""
    n_items, rank = 10**6, 64  # 256,000,000 bytes
    monkeypatch.setattr(sharded_topk, "device_memory_bytes",
                        lambda: 4 * n_items * rank * 4)
    assert not should_shard_serving(n_items, rank, mesh8, "auto")
    assert should_shard_serving(n_items + 1, rank, mesh8, "auto")
    assert not should_shard_serving(n_items + 1, rank, mesh8, "never")


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


# -- the facade: two layouts, one answer -------------------------------------


def test_sharded_catalog_facade_layout_selection(catalog, mesh):
    """ShardedCatalog is ``mesh`` when a serving mesh was assigned and
    ``flat`` otherwise; every scoring call answers alike through it."""
    flat = ShardedCatalog(catalog)
    sharded = ShardedCatalog(catalog, serving_mesh=mesh)
    assert (flat.layout, flat.n_shards) == ("flat", 1)
    assert (sharded.layout, sharded.n_shards) == ("mesh", mesh.size)
    rng = np.random.default_rng(18)
    uv = rng.normal(size=(catalog.shape[1],)).astype(np.float32)
    excl = rng.random(len(catalog)) < 0.5
    for a, b in (
            (flat.top_k(uv, 10), sharded.top_k(uv, 10)),
            (flat.top_k(uv, 10, exclude=excl),
             sharded.top_k(uv, 10, exclude=excl))):
        np.testing.assert_array_equal(a[1], b[1])
        np.testing.assert_array_equal(a[0], b[0])
    uvs = rng.normal(size=(5, catalog.shape[1])).astype(np.float32)
    (s0, i0), (s1, i1) = flat.batch_top_k(uvs, 10), sharded.batch_top_k(uvs, 10)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_allclose(s0, s1, rtol=0, atol=4e-6)  # gemm ULPs
    normed = normalize_rows(catalog)
    (s0, i0), (s1, i1) = (
        c.similar(catalog[[3, 77]], 9, exclude=excl)
        for c in (ShardedCatalog(normed),
                  ShardedCatalog(normed, serving_mesh=mesh)))
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


@pytest.mark.parametrize("k,excluded", [(10, False), (4, True)])
def test_two_layouts_bit_identical_across_block_selection(
        mesh8, k, excluded):
    """A catalog large enough that the flat layout selects by blocks
    (ops/topk.select_topk) instead of ``lax.top_k`` of the whole row:
    flat and mesh still answer bit for bit alike, through the facade,
    with and without a business-rule mask, on heavy ties too."""
    from incubator_predictionio_tpu.ops import topk

    n_items, rank = 4099, 16  # not a multiple of 8, of 128 or of the shards
    assert topk.select_block_len(n_items, k) == 128
    rng = np.random.default_rng(19)
    items = rng.normal(size=(n_items, rank)).astype(np.float32)
    items[rng.integers(0, n_items, 600)] = items[7]  # duplicate rows: ties
    exclude = (rng.random(n_items) < 0.4) if excluded else None
    flat = ShardedCatalog(items)
    mesh = ShardedCatalog(items, serving_mesh=mesh8)
    assert (flat.layout, mesh.layout) == ("flat", "mesh")
    blocks = topk._M_SELECT.labels("blocks")
    before = blocks.value()
    for uv in (rng.normal(size=rank).astype(np.float32), items[7]):
        s0, i0 = flat.top_k(uv, k, exclude=exclude)
        s1, i1 = mesh.top_k(uv, k, exclude=exclude)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(s0, s1)  # bitwise
    assert blocks.value() == before + 2  # the flat calls, and only they
