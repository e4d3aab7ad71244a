"""Partition-local training feeds (ISSUE 15).

The partitioned event log is the training data plane: gang worker *i*
feeds from shard ``j % N == i`` of the canonical shard order as
sequential colseg-snapshot scans (tail-only JSON parsing), id maps are
allgathered once, and the data-parallel trainers all-reduce — so gang
training reads ZERO bytes through the merged JSON view (asserted here
with a poisoned ``_merged_scan``, and enforced statically by the
``train-feed-confinement`` lint rule).

Coverage:
- shard assignment partitions the canonical list exactly once;
- per-shard scans are bit-identical to a full JSON parse while
  consuming the committed colseg snapshot for the covered prefix and
  parsing only the uncovered tail (mid-train appends past the snapshot
  generation);
- the UNION of every worker's feed equals the merged-view read — same
  events, same derived rating triples and labeled examples — including
  id-global tombstones that cross partitions;
- the partition-local (gram all-reduce) ALS trainer matches the slab
  trainer within float32 tolerance, across explicit/implicit and
  both lambda scalings;
- template read_training rides the feed (partition_local TrainingData)
  without ever touching the merged view; non-JSONL stores fall back;
- a REAL 2-process supervised gang trains recommendation (sharded
  ALS), classification NB and process-local LR off a prepared
  partitioned log — with the merged view poisoned in every worker —
  and the persisted models match single-process merged-feed references.
"""

import os
import pickle
import sys

import numpy as np
import pytest

from incubator_predictionio_tpu.data.api import partition_feed as pfeed
from incubator_predictionio_tpu.data.storage import jsonl as jsonl_mod
from incubator_predictionio_tpu.data.storage.base import App
from incubator_predictionio_tpu.data.storage.datamap import DataMap
from incubator_predictionio_tpu.data.storage.event import Event
from incubator_predictionio_tpu.data.storage.jsonl import JSONLEvents
from incubator_predictionio_tpu.data.storage.registry import Storage
from incubator_predictionio_tpu.data.api import event_log
from incubator_predictionio_tpu.workflow import train_feed

pytestmark = [pytest.mark.trainfeed]

HERE = os.path.dirname(os.path.abspath(__file__))
APP = 1


def _dt(seconds):
    import datetime as dt

    return (dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
            + dt.timedelta(seconds=int(seconds)))


def _rate(user, item, rating, t, event="rate", eid=None):
    return Event(event=event, entity_type="user", entity_id=str(user),
                 target_entity_type="item", target_entity_id=str(item),
                 properties=DataMap({"rating": float(rating)}
                                    if rating is not None else {}),
                 event_time=_dt(t), event_id=eid)


def _set(user, props, t):
    return Event(event="$set", entity_type="user", entity_id=str(user),
                 properties=DataMap(props), event_time=_dt(t))


def _store_for_partition(events_dir, partition, monkeypatch):
    if partition is None:
        monkeypatch.delenv("PIO_EVENT_PARTITION", raising=False)
    else:
        monkeypatch.setenv("PIO_EVENT_PARTITION", str(partition))
    st = JSONLEvents(events_dir)
    monkeypatch.delenv("PIO_EVENT_PARTITION", raising=False)
    return st


def _build_partitioned_log(events_dir, monkeypatch, seed=7,
                           n_events=160, with_sets=True):
    """Base log + partitions p0/p1/p2; two shards compacted, then
    appended past the snapshot (the mid-train uncovered tail); one
    within-shard delete and one CROSS-partition delete (tombstone in a
    different shard than its victim's records)."""
    rng = np.random.default_rng(seed)
    victims = []
    for part in (None, 0, 1, 2):
        st = _store_for_partition(events_dir, part, monkeypatch)
        evs = [_rate(rng.integers(0, 25), rng.integers(0, 18),
                     rng.integers(1, 6), rng.integers(0, 5000))
               for _ in range(n_events // 4)]
        # one rating-less event per shard: the codec NaN sentinel must
        # resolve to the event-default in BOTH read paths
        evs.append(_rate(rng.integers(0, 25), rng.integers(0, 18),
                         None, 5001))
        ids = st.insert_batch(evs, APP)
        victims.append(ids[3])
        if with_sets and part in (None, 0, 2):
            st.insert_batch(
                [_set(f"c{part}_{j}",
                      {"attr0": int(j % 3), "attr1": int(j % 2),
                       "attr2": int(j % 4), "plan": float(j % 2)},
                      6000 + j) for j in range(8)], APP)
        if with_sets:
            # a few view events + item category metadata (the
            # similar-product read shape)
            st.insert_batch(
                [_rate(rng.integers(0, 25), rng.integers(0, 18),
                       None, 7000 + j, event="view")
                 for j in range(5)], APP)
            st.insert_batch(
                [Event(event="$set", entity_type="item",
                       entity_id=str(rng.integers(0, 18)),
                       properties=DataMap(
                           {"categories": ["a", f"p{part}"]}),
                       event_time=_dt(7100)) ], APP)
    # within-shard delete (tombstone lands in the victim's own shard)
    st0 = _store_for_partition(events_dir, 0, monkeypatch)
    st0.delete_batch([victims[1]], APP)
    # compact base + p1, then append more (uncovered tails)
    for name in ("events_1.jsonl", "events_1.p1.jsonl"):
        assert event_log.compact_log(os.path.join(events_dir, name))
    st1 = _store_for_partition(events_dir, 1, monkeypatch)
    tail_ids = st1.insert_batch(
        [_rate(100 + j, 200 + j, 3, 9000 + j) for j in range(6)], APP)
    # CROSS-partition delete: tombstone appended to p2, victim lives in
    # p1's uncovered tail — only the id-global exchange can see it
    st2 = _store_for_partition(events_dir, 2, monkeypatch)
    st2.delete_batch([tail_ids[0]], APP)
    return events_dir


@pytest.fixture()
def jsonl_storage(tmp_path):
    storage = Storage({
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "JL",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "MEMORY",
        "PIO_STORAGE_SOURCES_JL_TYPE": "JSONL",
        "PIO_STORAGE_SOURCES_JL_PATH": str(tmp_path / "events"),
    })
    storage.get_meta_data_apps().insert(App(id=APP, name="feedapp"))
    yield storage


def _events_dir(storage) -> str:
    return storage.get_l_events().events_dir


# ---------------------------------------------------------------------------
# shard assignment + per-shard scan
# ---------------------------------------------------------------------------

def test_assignment_partitions_canonical_order_exactly_once(
        tmp_path, monkeypatch):
    events_dir = _build_partitioned_log(
        str(tmp_path / "ev"), monkeypatch, with_sets=False)
    canonical = jsonl_mod.shard_paths(events_dir, APP)
    assert len(canonical) == 4
    for n in (1, 2, 3, 4, 7):
        union = []
        for w in range(n):
            mine = pfeed.assigned_shards(events_dir, APP, None, w, n)
            # worker w holds positions w, w+n, ... in canonical order
            assert mine == canonical[w::n]
            union += mine
        assert sorted(union) == sorted(canonical)
    with pytest.raises(ValueError):
        pfeed.assigned_shards(events_dir, APP, None, 2, 2)
    with pytest.raises(ValueError):
        pfeed.assigned_shards(events_dir, APP, None, 0, 0)


def test_scan_shard_snapshot_covers_prefix_tail_parsed(
        tmp_path, monkeypatch):
    events_dir = _build_partitioned_log(
        str(tmp_path / "ev"), monkeypatch, with_sets=False)
    from incubator_predictionio_tpu.native import parse_events

    compacted = os.path.join(events_dir, "events_1.p1.jsonl")
    plain = os.path.join(events_dir, "events_1.p0.jsonl")
    shard = pfeed.scan_shard(compacted)
    # the covered prefix came from the snapshot, only the appended tail
    # was JSON-parsed
    assert shard.snapshot_bytes > 0 and shard.tail_bytes > 0
    assert shard.snapshot_bytes + shard.tail_bytes == \
        os.path.getsize(compacted)
    # bit-identity against the full JSON parse
    with open(compacted, "rb") as f:
        ref = parse_events(f.read())
    assert len(shard.cols) == len(ref)
    for i in range(len(ref)):
        assert shard.cols.record_dict(i) == ref.record_dict(i)
    # un-compacted shard: everything is tail
    shard2 = pfeed.scan_shard(plain)
    assert shard2.snapshot_bytes == 0
    assert shard2.tail_bytes == os.path.getsize(plain)


# ---------------------------------------------------------------------------
# bit-identity: union of partition-local feeds == merged-view read
# ---------------------------------------------------------------------------

def _merged_ratings_triples(storage, bimaps=None):
    """Reference triples via the merged-view read path."""
    from incubator_predictionio_tpu.data.store.p_event_store import (
        PEventStore)

    u, i, r, users, items = PEventStore.find_ratings(
        "feedapp", event_names=["rate", "buy"],
        event_default_ratings={"buy": 4.0}, storage=storage)
    return sorted(
        (users.inverse(int(uu)), items.inverse(int(ii)), float(rr))
        for uu, ii, rr in zip(u, i, r))


def _feed_ratings_triples(events_dir, num_workers):
    """Union of every worker's partition-local feed, as id triples —
    the same two-phase flow train_feed runs, emulated in-process."""
    per_worker = []
    all_tombs = set()
    for w in range(num_workers):
        feed = pfeed.PartitionFeed(events_dir, APP, None, w, num_workers)
        shards = [pfeed.scan_shard(p) for p in feed.shard_list()]
        all_tombs |= set(feed.local_tombstones(shards))
        per_worker.append(shards)
    triples = []
    for shards in per_worker:
        for shard in shards:
            sr = pfeed.PartitionFeed.shard_ratings(
                shard, ["rate", "buy"], frozenset(all_tombs),
                event_default_ratings={"buy": 4.0})
            for j in range(len(sr.rating)):
                triples.append((sr.user_ids[int(sr.u[j])],
                                sr.item_ids[int(sr.i[j])],
                                float(sr.rating[j])))
    return sorted(triples)


def test_feed_union_equals_merged_view_with_tails_and_tombstones(
        jsonl_storage, monkeypatch):
    events_dir = _events_dir(jsonl_storage)
    _build_partitioned_log(events_dir, monkeypatch)
    ref = _merged_ratings_triples(jsonl_storage)
    assert len(ref) > 100
    for n in (1, 2, 3):
        got = _feed_ratings_triples(events_dir, n)
        assert got == ref, f"num_workers={n}"


def test_partition_ratings_single_process_matches_merged(
        jsonl_storage, monkeypatch):
    """train_feed.partition_ratings (worker 0 of 1 — the whole log)
    yields the same rating multiset and vocabulary as the merged read,
    and the template read marks it partition_local."""
    events_dir = _events_dir(jsonl_storage)
    _build_partitioned_log(events_dir, monkeypatch)
    monkeypatch.setenv("PIO_TRAIN_FEED", "partition")
    u, i, r, users, items = train_feed.partition_ratings(
        "feedapp", event_names=["rate", "buy"],
        event_default_ratings={"buy": 4.0}, storage=jsonl_storage)
    got = sorted((users.inverse(int(uu)), items.inverse(int(ii)),
                  float(rr)) for uu, ii, rr in zip(u, i, r))
    assert got == _merged_ratings_triples(jsonl_storage)


def test_template_read_training_feeds_zero_merged_bytes(
        jsonl_storage, monkeypatch):
    """The acceptance assertion: with the feed armed, the template
    read path never touches the merged JSON view (poisoned here), and
    returns partition-local training data."""
    from incubator_predictionio_tpu.controller.base import doer
    from incubator_predictionio_tpu.models.recommendation import (
        RecommendationDataSource)
    from incubator_predictionio_tpu.models.classification import (
        ClassificationDataSource)
    from incubator_predictionio_tpu.workflow.context import (
        WorkflowContext)

    events_dir = _events_dir(jsonl_storage)
    _build_partitioned_log(events_dir, monkeypatch)
    # merged-view reference for the category parity check BELOW, taken
    # BEFORE the merged view gets poisoned
    from incubator_predictionio_tpu.data.store.p_event_store import (
        PEventStore)

    ref_cats = {
        iid: set(pm.get_opt("categories"))
        for iid, pm in PEventStore.aggregate_properties(
            "feedapp", "item", storage=jsonl_storage).items()
        if pm.get_opt("categories")}
    monkeypatch.setenv("PIO_TRAIN_FEED", "partition")

    def boom(self, *a, **kw):
        raise AssertionError("merged-view scan reached from the "
                             "partition-feed read path")

    monkeypatch.setattr(JSONLEvents, "_merged_scan", boom)
    ctx = WorkflowContext(app_name="feedapp", storage=jsonl_storage)
    td = doer(RecommendationDataSource,
              {"appName": "feedapp"}).read_training(ctx)
    assert td.partition_local and len(td.rating) > 100
    assert len(td.users) and len(td.items)
    tdc = doer(ClassificationDataSource,
               {"appName": "feedapp"}).read_training(ctx)
    assert tdc.partition_local and tdc.n_global > 0
    assert len(tdc.features) == tdc.n_global  # worker 0 of 1 holds all
    # the similar-product read (view events + item categories) rides
    # the same feed; categories match the merged aggregate
    from incubator_predictionio_tpu.models.similar_product import (
        SimilarProductDataSource)

    tds = doer(SimilarProductDataSource,
               {"appName": "feedapp"}).read_training(ctx)
    assert tds.partition_local and len(tds.rating) > 0
    assert tds.item_categories
    assert tds.item_categories == ref_cats
    # merged mode still works (and DOES use the merged view)
    monkeypatch.setenv("PIO_TRAIN_FEED", "merged")
    with pytest.raises(AssertionError, match="merged-view scan"):
        doer(RecommendationDataSource,
             {"appName": "feedapp"}).read_training(ctx)


def test_partition_feed_inactive_without_jsonl_backend(memory_storage,
                                                       monkeypatch):
    monkeypatch.setenv("PIO_TRAIN_FEED", "partition")
    assert not train_feed.partition_feed_active(memory_storage)
    monkeypatch.setenv("PIO_TRAIN_FEED", "merged")
    monkeypatch.delenv("PIO_TRAIN_FEED", raising=False)


# ---------------------------------------------------------------------------
# classification examples
# ---------------------------------------------------------------------------

def test_partition_examples_match_merged_read(jsonl_storage,
                                              monkeypatch):
    from incubator_predictionio_tpu.controller.base import doer
    from incubator_predictionio_tpu.models.classification import (
        ClassificationDataSource)
    from incubator_predictionio_tpu.workflow.context import (
        WorkflowContext)

    events_dir = _events_dir(jsonl_storage)
    _build_partitioned_log(events_dir, monkeypatch)
    ctx = WorkflowContext(app_name="feedapp", storage=jsonl_storage)
    ref = doer(ClassificationDataSource,
               {"appName": "feedapp"}).read_training(ctx)
    ref_rows = sorted(
        (tuple(f), float(ref.label_values[y]))
        for f, y in zip(ref.features.tolist(), ref.labels.tolist()))
    # emulate a 2-worker gang's exchange: each worker's per-shard
    # replays (with the union tombstone set) gather into the SAME
    # merged map; each then takes its strided slice
    attrs = ["attr0", "attr1", "attr2"]
    per_worker_parts, all_tombs = [], set()
    feeds = [pfeed.PartitionFeed(events_dir, APP, None, w, 2)
             for w in range(2)]
    scans = [[pfeed.scan_shard(p) for p in f.shard_list()]
             for f in feeds]
    for f, shards in zip(feeds, scans):
        all_tombs |= set(f.local_tombstones(shards))
    for f, shards in zip(feeds, scans):
        pos = f.canonical_positions()
        per_worker_parts.append([
            (pos[s.path], {
                eid: [props, int(first), int(last)]
                for eid, (props, first, last) in
                pfeed.PartitionFeed.shard_properties(
                    s, "user", frozenset(all_tombs)).items()})
            for s in shards])
    merged = train_feed._merge_property_parts(per_worker_parts)
    rows = []
    label_values = None
    for w in range(2):
        feats, y, lv, n_global = train_feed._examples_from_map(
            merged, attrs, "plan", w, 2)
        assert n_global == len(ref.labels)
        label_values = lv
        rows += [(tuple(f), float(lv[yy]))
                 for f, yy in zip(feats.tolist(), y.tolist())]
    assert sorted(rows) == ref_rows
    assert np.array_equal(np.asarray(label_values), ref.label_values)
    # and the wired single-process path (worker 0 of 1) end to end
    monkeypatch.setenv("PIO_TRAIN_FEED", "partition")
    feats, y, lv, n_global = train_feed.partition_examples(
        "feedapp", "user", attrs, "plan", storage=jsonl_storage)
    assert n_global == len(ref.labels)
    got = sorted((tuple(f), float(lv[yy]))
                 for f, yy in zip(feats.tolist(), y.tolist()))
    assert got == ref_rows


# ---------------------------------------------------------------------------
# the data-parallel trainers (single-process kernels)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("implicit,scaling", [
    (False, "plain"), (False, "nratings"), (True, "plain")])
def test_dp_als_matches_slab_trainer(implicit, scaling):
    """The gram all-reduce kernel solves the identical normal
    equations as the bucketed slab trainer — forced onto a 2-device
    mesh so the psum/all-gather path actually runs."""
    import jax
    from incubator_predictionio_tpu.ops.als import (
        ALSParams, train_als, train_als_partition_local)
    from incubator_predictionio_tpu.parallel.mesh import (
        mesh_from_devices)

    rng = np.random.default_rng(11)
    n_users, n_items, nnz = 40, 30, 600
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    r = (rng.integers(1, 11, nnz) / 2.0).astype(np.float32)
    params = ALSParams(rank=4, num_iterations=6, seed=5, reg=0.05,
                       implicit_prefs=implicit, alpha=0.8,
                       lambda_scaling=scaling)
    ref = train_als(u, i, r, n_users, n_items, params,
                    mesh=mesh_from_devices(devices=jax.devices()[:1]))
    dp = train_als_partition_local(
        u, i, r, n_users, n_items, params,
        mesh=mesh_from_devices(devices=jax.devices()[:2]),
        force_dp=True)
    # two float32 formulations of the same normal equations (event
    # segment-sums vs row slabs) through 6 alternating solves: on jax
    # 0.9's CPU backend the worst of 280 entries differs by 4.4e-4
    np.testing.assert_allclose(dp.user_factors, ref.user_factors,
                               rtol=2e-3, atol=1e-3)
    np.testing.assert_allclose(dp.item_factors, ref.item_factors,
                               rtol=2e-3, atol=1e-3)


def test_dp_als_rejects_model_axis_mesh():
    import jax
    from incubator_predictionio_tpu.ops.als import (
        ALSParams, train_als_partition_local)
    from incubator_predictionio_tpu.parallel.mesh import (
        mesh_from_devices)

    mesh = mesh_from_devices(shape=(1, 2), axis_names=("d", "m"),
                             devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="1-D data mesh"):
        train_als_partition_local(
            np.zeros(1, np.int32), np.zeros(1, np.int32),
            np.ones(1, np.float32), 1, 1, ALSParams(rank=2),
            mesh=mesh, force_dp=True)


def test_process_local_nb_lr_single_process_fallback():
    """With one process the process-local entry points delegate to the
    plain trainers — bit-identical models."""
    from incubator_predictionio_tpu.ops.linear import (
        train_logistic_regression, train_logistic_regression_process_local,
        train_naive_bayes, train_naive_bayes_process_local)

    rng = np.random.default_rng(3)
    x = rng.integers(0, 5, (60, 3)).astype(np.float32)
    y = rng.integers(0, 2, 60).astype(np.int32)
    a = train_naive_bayes(x, y, 2, smoothing=0.7)
    b = train_naive_bayes_process_local(x, y, 2, smoothing=0.7)
    np.testing.assert_array_equal(a.log_prior, b.log_prior)
    np.testing.assert_array_equal(a.log_likelihood, b.log_likelihood)
    la = train_logistic_regression(x, y, 2, reg=0.01, max_iters=30)
    lb = train_logistic_regression_process_local(x, y, 2, reg=0.01,
                                                 max_iters=30)
    np.testing.assert_array_equal(la.weights, lb.weights)
    np.testing.assert_array_equal(la.intercept, lb.intercept)


# ---------------------------------------------------------------------------
# the REAL 2-process gang off a partitioned log (merged view poisoned)
# ---------------------------------------------------------------------------

@pytest.mark.gang
def test_two_worker_gang_trains_off_partition_feed(tmp_path,
                                                   monkeypatch):
    """A REAL supervised 2-worker gang runs the full training workflow
    (leader/follower, run_train) over a prepared partitioned event log
    with `_merged_scan` poisoned in every worker: recommendation ALS,
    classification NB, and process-local LR all complete, and the
    persisted models match single-process merged-feed references at
    the gang contract (ALS 2e-4 rtol; NB exact)."""
    from incubator_predictionio_tpu.parallel.supervisor import (
        COMPLETED, GangConfig, Supervisor)

    events_dir = str(tmp_path / "events" / "pio_eventdata")
    os.makedirs(events_dir)
    _build_partitioned_log(events_dir, monkeypatch)
    env = {
        **os.environ,
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "JL",
        "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
        "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "meta.sqlite"),
        "PIO_STORAGE_SOURCES_JL_TYPE": "JSONL",
        "PIO_STORAGE_SOURCES_JL_PATH": str(tmp_path / "events"),
        "PIO_TRAIN_FEED": "partition",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla_cache"),
    }
    env.pop("PIO_FAULT_SPEC", None)
    storage = Storage({k: v for k, v in env.items()
                       if k.startswith("PIO_STORAGE")})
    storage.get_meta_data_apps().insert(App(id=APP, name="feedapp"))

    out_dir = str(tmp_path / "out")
    os.makedirs(out_dir)
    worker = os.path.join(HERE, "gang_feed_worker.py")
    sup = Supervisor(
        [sys.executable, worker, out_dir], num_workers=2, env=env,
        config=GangConfig(num_workers=2, heartbeat_ms=250.0,
                          stall_ms=60_000.0, init_grace_ms=300_000.0,
                          max_restarts=0, poll_ms=50.0),
        gang_instance_id="feedgang-1",
        run_dir=str(tmp_path / "run"))
    outcome = sup.run()
    logs = "\n".join(
        open(os.path.join(str(tmp_path / "run"), f"worker_{i}.log"),
             errors="replace").read() for i in range(2))
    assert outcome == COMPLETED, logs

    # --- references from the merged view, single process -------------
    from incubator_predictionio_tpu.data.store.p_event_store import (
        PEventStore)
    from incubator_predictionio_tpu.ops.als import ALSParams, train_als
    from incubator_predictionio_tpu.ops.linear import train_naive_bayes
    from incubator_predictionio_tpu.workflow import model_artifact
    import jax

    with open(os.path.join(out_dir, "ids.txt")) as f:
        rec_id, cls_id = f.read().split()

    # ALS: compare factors PER ID against a merged-feed train with the
    # same params (init is drawn in global row order, so the per-id
    # comparison is meaningful across differing index assignments)
    stored = pickle.loads(model_artifact.read_model(storage, rec_id))[0]
    g_users = stored["users"]
    g_items = stored["items"]
    u, i, r, m_users, m_items = PEventStore.find_ratings(
        "feedapp", event_names=["rate", "buy"],
        event_default_ratings={"buy": 4.0}, storage=storage)
    # re-index the merged triple through the GANG's global maps so the
    # reference train sees identical row numbering
    from incubator_predictionio_tpu.data.storage.bimap import BiMap

    gu = BiMap.from_persisted(g_users)
    gi = BiMap.from_persisted(g_items)
    assert set(gu.keys()) == set(m_users.keys())
    assert set(gi.keys()) == set(m_items.keys())
    ru = np.asarray([gu(m_users.inverse(int(x))) for x in u], np.int32)
    ri = np.asarray([gi(m_items.inverse(int(x))) for x in i], np.int32)
    params = ALSParams(rank=4, num_iterations=6, seed=5, reg=0.05)
    from incubator_predictionio_tpu.parallel.mesh import (
        mesh_from_devices)

    ref = train_als(ru, ri, r, len(gu), len(gi), params,
                    mesh=mesh_from_devices(devices=jax.devices()[:1]))
    np.testing.assert_allclose(stored["user_factors"],
                               ref.user_factors, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(stored["item_factors"],
                               ref.item_factors, rtol=2e-4, atol=2e-4)

    # NB: sufficient statistics are exact — the gang model must equal
    # the merged-feed train bit-for-bit on its log params
    cls_model = pickle.loads(
        model_artifact.read_model(storage, cls_id))[0]
    from incubator_predictionio_tpu.models.classification import (
        ClassificationDataSource)
    from incubator_predictionio_tpu.workflow.context import (
        WorkflowContext)

    ctx = WorkflowContext(app_name="feedapp", storage=storage)
    from incubator_predictionio_tpu.controller.base import doer

    td = doer(ClassificationDataSource,
              {"appName": "feedapp"}).read_training(ctx)
    nb_ref = train_naive_bayes(td.features, td.labels,
                               n_classes=len(td.label_values),
                               smoothing=0.7)
    np.testing.assert_allclose(cls_model.inner.log_prior,
                               nb_ref.log_prior, rtol=1e-6)
    np.testing.assert_allclose(cls_model.inner.log_likelihood,
                               nb_ref.log_likelihood, rtol=1e-6)
    assert np.array_equal(cls_model.label_values, td.label_values)

    # LR: data-parallel L-BFGS over mask-padded shards converges to
    # the same optimum as the single-process fit (same loss surface)
    from incubator_predictionio_tpu.ops.linear import (
        train_logistic_regression)

    lr = np.load(os.path.join(out_dir, "lr.npz"))
    lr_ref = train_logistic_regression(
        td.features, td.labels, n_classes=len(td.label_values),
        reg=0.01, max_iters=40)
    pred_ref = np.argmax(
        td.features @ lr_ref.weights + lr_ref.intercept, axis=1)
    pred_gang = np.argmax(
        td.features @ lr["weights"] + lr["intercept"], axis=1)
    assert np.array_equal(pred_ref, pred_gang)
    assert np.allclose(lr["weights"], lr_ref.weights, rtol=5e-2,
                       atol=5e-2)

    # the poison never fired: no worker touched the merged view
    assert "merged-view scan reached" not in logs


def test_trainfeed_marker_registered():
    with open(os.path.join(os.path.dirname(HERE),
                           "pyproject.toml")) as f:
        assert "trainfeed:" in f.read()
