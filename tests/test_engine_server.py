"""Engine (deploy) server over real HTTP: /queries.json hot path, status
page, /reload hot-swap (reference: SURVEY.md §3.2)."""

import pytest
import requests

from incubator_predictionio_tpu.controller import EngineParams
from incubator_predictionio_tpu.models.recommendation import RecommendationEngine
from incubator_predictionio_tpu.workflow.context import WorkflowContext
from incubator_predictionio_tpu.workflow.core_workflow import run_train
from incubator_predictionio_tpu.workflow.create_server import EngineServer

from server_utils import ServerThread
from test_dase_train_e2e import ENGINE_PARAMS, _seed_ratings


def test_engine_server_query_and_reload(memory_storage):
    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    run_train(engine, ENGINE_PARAMS, ctx, engine_factory_name="rec")

    server = EngineServer(engine, engine_factory_name="rec", storage=memory_storage)
    with ServerThread(server.app) as st:
        # status page
        r = requests.get(st.base + "/")
        assert r.status_code == 200
        status = r.json()
        assert status["status"] == "alive"
        # which device answers, as JAX reports it (tests run on the
        # 8-device virtual CPU platform)
        assert (status["platform"], status["deviceKind"],
                status["deviceCount"]) == ("cpu", "cpu", 8)
        first_instance = status["engineInstanceId"]

        # the hot path
        r = requests.post(st.base + "/queries.json", json={"user": "1", "num": 4})
        assert r.status_code == 200, r.text
        scores = r.json()["itemScores"]
        assert len(scores) == 4
        assert scores[0]["score"] >= scores[-1]["score"]

        # malformed body / missing field
        r = requests.post(st.base + "/queries.json", data="}{",
                          headers={"Content-Type": "application/json"})
        assert r.status_code == 400
        r = requests.post(st.base + "/queries.json", json={"num": 4})
        assert r.status_code == 400
        assert "user" in r.json()["message"]

        # train a second instance, /reload hot-swaps to it
        iid2 = run_train(engine, ENGINE_PARAMS, ctx, engine_factory_name="rec")
        r = requests.get(st.base + "/reload")
        assert r.status_code == 200
        assert r.json()["engineInstanceId"] == iid2
        assert requests.get(st.base + "/").json()["engineInstanceId"] != first_instance

        # queries still served after reload
        r = requests.post(st.base + "/queries.json", json={"user": "2", "num": 2})
        assert r.status_code == 200
        assert len(r.json()["itemScores"]) == 2


def test_engine_server_plugins(memory_storage):
    from incubator_predictionio_tpu.workflow.plugins import (
        EngineServerPlugin,
        EngineServerPluginContext,
    )

    class Capper(EngineServerPlugin):
        name = "capper"

        def process(self, query, result):
            result["itemScores"] = result["itemScores"][:1]
            return result

    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    run_train(engine, ENGINE_PARAMS, ctx, engine_factory_name="rec")
    server = EngineServer(
        engine, engine_factory_name="rec", storage=memory_storage,
        plugins=EngineServerPluginContext([Capper()]),
    )
    with ServerThread(server.app) as st:
        assert requests.get(st.base + "/plugins.json").json() == {"plugins": ["capper"]}
        r = requests.post(st.base + "/queries.json", json={"user": "1", "num": 5})
        assert len(r.json()["itemScores"]) == 1


def test_engine_server_micro_batching(memory_storage):
    """batch_window_ms coalesces concurrent queries into one vectorized
    Deployment.batch_query dispatch; results must match the per-query
    path exactly (SURVEY.md §7 hard part 1 — batching window at QPS)."""
    import concurrent.futures

    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    run_train(engine, ENGINE_PARAMS, ctx, engine_factory_name="rec")

    plain = EngineServer(engine, engine_factory_name="rec",
                         storage=memory_storage)
    batched = EngineServer(engine, engine_factory_name="rec",
                           storage=memory_storage,
                           batch_window_ms=10.0, max_batch=8)
    queries = [{"user": str(u), "num": 3} for u in range(6)] + [{"num": 3}]
    with ServerThread(plain.app) as sp:
        expected = [requests.post(sp.base + "/queries.json", json=q)
                    for q in queries]
    with ServerThread(batched.app) as sb:
        # concurrent burst: all queries inside one window
        with concurrent.futures.ThreadPoolExecutor(max_workers=7) as ex:
            got = list(ex.map(
                lambda q: requests.post(sb.base + "/queries.json", json=q),
                queries))
    for q, e, g in zip(queries, expected, got):
        assert g.status_code == e.status_code, (q, g.text)
        if e.status_code == 200:
            ej, gj = e.json(), g.json()
            # same items in the same order; scores ulp-tolerant — under
            # CPU contention the burst can split across batch windows,
            # and different batch shapes round differently in f32
            assert [s["item"] for s in gj["itemScores"]] == \
                   [s["item"] for s in ej["itemScores"]], q
            assert [s["score"] for s in gj["itemScores"]] == pytest.approx(
                [s["score"] for s in ej["itemScores"]], rel=1e-5), q


def test_product_ranking_query_mode(memory_storage):
    """Query with "items" ranks the GIVEN candidates for the user
    (ecosystem parity: predictionio-template-product-ranking): ranked
    by the user's affinity, unknown items last, unknown user returns
    the list unreordered with isOriginal=true."""
    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    run_train(engine, ENGINE_PARAMS, ctx, engine_factory_name="rank")
    server = EngineServer(engine, engine_factory_name="rank",
                          storage=memory_storage)
    with ServerThread(server.app) as st:
        plain = requests.post(st.base + "/queries.json",
                              json={"user": "1", "num": 50}).json()
        order = [s["item"] for s in plain["itemScores"]]
        assert len(order) >= 3
        candidates = [order[2], order[0], "no-such-item", order[1]]
        r = requests.post(st.base + "/queries.json",
                          json={"user": "1", "items": candidates})
        assert r.status_code == 200, r.text
        out = r.json()
        got = [s["item"] for s in out["itemScores"]]
        # affinity order restored; unknown item ranks last
        assert got == [order[0], order[1], order[2], "no-such-item"]
        assert out["isOriginal"] is False
        scores = [s["score"] for s in out["itemScores"]]
        assert scores[:3] == sorted(scores[:3], reverse=True)

        # unknown user: candidates back in sent order, flagged original
        r = requests.post(st.base + "/queries.json",
                          json={"user": "ghost", "items": candidates})
        out = r.json()
        assert [s["item"] for s in out["itemScores"]] == candidates
        assert out["isOriginal"] is True


def test_product_ranking_through_micro_batch_and_batch_predict(memory_storage):
    """Ranking-mode queries must return identical results through the
    per-query path, the micro-batching server path, and batch_predict
    (review finding: the batched paths bypassed the ranking mode)."""
    import concurrent.futures

    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    run_train(engine, ENGINE_PARAMS, ctx, engine_factory_name="rankb")
    server = EngineServer(engine, engine_factory_name="rankb",
                          storage=memory_storage,
                          batch_window_ms=10.0, max_batch=8)
    direct = EngineServer(engine, engine_factory_name="rankb",
                          storage=memory_storage)
    queries = [{"user": "1", "items": ["5", "9", "ghost", "2"]},
               {"user": "2", "num": 3},  # catalog query mixed in
               {"user": "zzz", "items": ["5", "9"]},
               {"user": "3", "items": []}]
    want = [direct.deployment.query(q) for q in queries]
    with ServerThread(server.app) as st:
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            got = list(pool.map(
                lambda q: requests.post(st.base + "/queries.json",
                                        json=q, timeout=30).json(),
                queries))
    # ranking-mode queries share the exact numpy path → bit-identical;
    # the catalog query's batched matmul may differ by float ULPs from
    # the single-query matvec, so compare it by items + approx scores
    assert got[0] == want[0] and got[2] == want[2] and got[3] == want[3]
    assert ([s["item"] for s in got[1]["itemScores"]]
            == [s["item"] for s in want[1]["itemScores"]])
    for a, b in zip(got[1]["itemScores"], want[1]["itemScores"]):
        assert abs(a["score"] - b["score"]) < 1e-4
    assert want[3] == {"itemScores": [], "isOriginal": False}
    assert want[2]["isOriginal"] is True


def test_healthz_readyz_and_degraded_reload(memory_storage):
    """Liveness (/healthz) is unconditional; readiness (/readyz) means
    model loaded + no open storage breaker; a failed /reload keeps the
    last-good model serving and flips /status into degraded mode."""
    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    run_train(engine, ENGINE_PARAMS, ctx, engine_factory_name="rec")
    server = EngineServer(engine, engine_factory_name="rec",
                          storage=memory_storage)
    with ServerThread(server.app) as st:
        assert requests.get(st.base + "/healthz").json() == {"status": "alive"}
        r = requests.get(st.base + "/readyz")
        assert r.status_code == 200
        ready = r.json()
        assert ready["ready"] is True and ready["modelLoaded"] is True
        assert ready["openBreakers"] == []
        status = requests.get(st.base + "/").json()
        assert status["degraded"] is False
        assert status["droppedFeedback"] == 0

        # make the next reload fail: no COMPLETED instance left to load
        insts = memory_storage.get_meta_data_engine_instances()
        for inst in insts.get_all():
            insts.delete(inst.id)
        r = requests.get(st.base + "/reload")
        assert r.status_code == 500
        assert r.json()["degraded"] is True

        # degraded, but the last-good model still serves
        status = requests.get(st.base + "/").json()
        assert status["degraded"] is True
        assert "reload failed" in status["degradedReason"]
        r = requests.post(st.base + "/queries.json",
                          json={"user": "1", "num": 3})
        assert r.status_code == 200 and r.json()["itemScores"]
        # a loaded model with healthy storage is still READY (the
        # degraded flag is telemetry, not a rotation signal)
        assert requests.get(st.base + "/readyz").status_code == 200


def test_feedback_write_failure_counts_dropped(memory_storage):
    """The feedback self-log is async; a failing event store must not
    fail the query, but the failure may not vanish either — it is
    logged and counted on /status (droppedFeedback)."""
    import time as _time

    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    run_train(engine, ENGINE_PARAMS, ctx, engine_factory_name="rec")
    server = EngineServer(engine, engine_factory_name="rec",
                          storage=memory_storage, feedback=True,
                          feedback_app_name="testapp")

    class _DeadLEvents:
        def insert(self, *a, **k):
            raise RuntimeError("event store down")

    memory_storage.get_l_events = lambda: _DeadLEvents()  # instance shadow
    with ServerThread(server.app) as st:
        r = requests.post(st.base + "/queries.json",
                          json={"user": "1", "num": 2})
        assert r.status_code == 200, r.text  # query unaffected
        dropped = 0
        deadline = _time.time() + 10
        while _time.time() < deadline:
            dropped = requests.get(st.base + "/").json()["droppedFeedback"]
            if dropped:
                break
            _time.sleep(0.05)
        assert dropped >= 1


def test_probe_latency_measures_and_persists(memory_storage):
    """pio deploy --probe-latency: the startup probe measures the
    full-path p50/p99 decomposition against the LIVE server and persists
    it to the EngineInstance row (VERDICT r4 next #4 — the <10ms claim
    must be a measurement, not arithmetic)."""
    import json

    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    iid = run_train(engine, ENGINE_PARAMS, ctx, engine_factory_name="rec")

    server = EngineServer(engine, engine_factory_name="rec",
                          storage=memory_storage)
    with ServerThread(server.app) as st:
        result = server.probe_and_record(st.base, n=12)
        # surfaced live on the status page (same serving session — an
        # aiohttp app cannot be restarted once cleaned up)
        status = requests.get(st.base + "/").json()
    assert result is not None
    assert status["probeLatency"]["http_p50_ms"] == result["http_p50_ms"]
    # decomposition is roughly consistent — independently sampled
    # distributions on a contended 1-core host need slack, not equality
    assert result["predict_p50_ms"] > 0
    assert result["http_p50_ms"] * 1.5 >= result["predict_p50_ms"]
    assert result["http_p99_ms"] >= result["http_p50_ms"]
    assert result["overhead_p50_ms"] >= 0
    assert result["dispatch_rtt_p50_ms"] is not None
    assert result["attachment"].startswith("cpu")
    # persisted to the instance row for the dashboard / ops to read back
    row = memory_storage.get_meta_data_engine_instances().get(iid)
    stored = json.loads(row.runtime_conf["probe_latency"])
    assert stored["http_p50_ms"] == result["http_p50_ms"]
    assert stored["n"] == 12


def test_forged_probe_marker_still_counts(memory_storage):
    """The X-Pio-Probe queryCount/feedback bypass is gated on a
    per-process random token: an external client sending a bare
    "X-Pio-Probe: 1" must be accounted like any real query."""
    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    run_train(engine, ENGINE_PARAMS, ctx, engine_factory_name="rec")
    server = EngineServer(engine, engine_factory_name="rec",
                          storage=memory_storage)
    with ServerThread(server.app) as st:
        r = requests.post(st.base + "/queries.json",
                          json={"user": "1", "num": 2},
                          headers={"X-Pio-Probe": "1"})
        assert r.status_code == 200, r.text
        assert requests.get(st.base + "/").json()["queryCount"] == 1
        # the real token (same process) IS excluded
        r = requests.post(st.base + "/queries.json",
                          json={"user": "1", "num": 2},
                          headers={"X-Pio-Probe": server._probe_token})
        assert r.status_code == 200, r.text
        assert requests.get(st.base + "/").json()["queryCount"] == 1
