"""Model-lifecycle chaos harness (ISSUE 9).

A corrupt or unvalidated model must NEVER serve a query:

- bit-flipped / truncated / garbage blobs are refused by the verifying
  loader (workflow/model_artifact.py) with per-kind counters, and the
  latest-completed walk falls back to an older COMPLETED instance —
  the bad blob is kept, never deleted
- a COMPLETED row without a model (the crash-mid-persist window,
  proven with a real `model.insert:crash:1` subprocess SIGKILL) is
  skipped, not served
- the swap validation gate (nan_guard + warm-up + golden-query smoke
  predict, `swap.validate` fault point) keeps a failed reload on the
  last-good model while live queries keep answering 200
- a poisoned hot-swap auto-rolls back within the watch window — in
  process and in a REAL subprocess engine server with the continuous
  refresh loop driving the swap — while every client query answers 200
- checksum metadata round-trips identically through the memory, sqlite
  and localfs model stores; pre-upgrade rows are legacy-accepted with
  a warning counter
- `pio models list|verify|gc` and the workflow/ single-reader AST
  guard
"""

import json
import os
import pickle
import subprocess
import sys
import threading
import time

import pytest
import requests

import lifecycle_engine
from incubator_predictionio_tpu.common import faultinject
from incubator_predictionio_tpu.data.storage.base import Model
from incubator_predictionio_tpu.workflow import model_artifact
from incubator_predictionio_tpu.workflow.context import WorkflowContext
from incubator_predictionio_tpu.workflow.core_workflow import (
    load_deployment, run_train)
from incubator_predictionio_tpu.workflow.create_server import EngineServer

from server_utils import ServerThread, free_port

pytestmark = [pytest.mark.lifecycle, pytest.mark.chaos]

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def chaos(monkeypatch):
    def arm(spec):
        monkeypatch.setenv("PIO_FAULT_SPEC", spec)
        faultinject.reset()
    yield arm
    monkeypatch.delenv("PIO_FAULT_SPEC", raising=False)
    faultinject.reset()


def _train(storage, tag, mode="good"):
    ctx = WorkflowContext(app_name="lifeapp", storage=storage)
    iid = run_train(lifecycle_engine.engine_factory(),
                    lifecycle_engine.engine_params(tag, mode), ctx,
                    engine_factory_name="lifecycle")
    time.sleep(0.002)  # strictly ordered start_times for the next train
    return iid


def _failures(kind) -> int:
    return model_artifact._INTEGRITY_FAILURES.labels(kind).value()


def _post(base, user, timeout=30):
    return requests.post(base + "/queries.json", json={"user": user},
                         timeout=timeout)


# ---------------------------------------------------------------------------
# envelope unit coverage
# ---------------------------------------------------------------------------

def test_envelope_roundtrip_and_tamper_kinds():
    payload = pickle.dumps([{"weights": list(range(100))}])
    blob = model_artifact.wrap(payload)
    assert model_artifact.unwrap_verified(blob, "i") == payload
    d = model_artifact.describe(blob)
    assert d["ok"] and d["format"] == "v1" and d["size"] == len(payload)
    assert d["sha256"] == model_artifact.compute_sha256(payload)

    # bit-flip inside the payload → checksum
    flipped = bytearray(blob)
    flipped[-10] ^= 0x40
    before = _failures("checksum")
    with pytest.raises(model_artifact.ModelIntegrityError) as ei:
        model_artifact.unwrap_verified(bytes(flipped), "i")
    assert ei.value.kind == "checksum"
    assert _failures("checksum") == before + 1

    # truncation → size
    with pytest.raises(model_artifact.ModelIntegrityError) as ei:
        model_artifact.unwrap_verified(blob[:-7], "i")
    assert ei.value.kind == "size"

    # neither envelope nor pickle → header (a damaged envelope can NOT
    # demote to legacy-accept)
    for garbage in (b"garbage-bytes", b"PIOM\xff\xff\xff\xff", b"PIOM",
                    b""):
        with pytest.raises(model_artifact.ModelIntegrityError) as ei:
            model_artifact.unwrap_verified(garbage, "i")
        assert ei.value.kind == "header", garbage

    # newer format version → version
    import struct
    header = json.dumps({"v": 99, "sha256": "x", "size": 1}).encode()
    newer = b"PIOM" + struct.pack(">I", len(header)) + header + b"\x80"
    with pytest.raises(model_artifact.ModelIntegrityError) as ei:
        model_artifact.unwrap_verified(newer, "i")
    assert ei.value.kind == "version"

    # pre-upgrade bare pickle → accepted, counted as legacy
    before = model_artifact._LEGACY_LOADS.labels().value()
    assert model_artifact.unwrap_verified(payload, "i") == payload
    assert model_artifact._LEGACY_LOADS.labels().value() == before + 1
    assert model_artifact.describe(payload)["format"] == "legacy"


# ---------------------------------------------------------------------------
# Models backend parity (satellite: sqlite / memory / localfs round-trip)
# ---------------------------------------------------------------------------

class _OneDaoStorage:
    def __init__(self, dao):
        self._dao = dao

    def get_model_data_models(self):
        return self._dao


def _model_backends(tmp_path):
    from incubator_predictionio_tpu.data.storage.base import (
        StorageClientConfig)
    from incubator_predictionio_tpu.data.storage.localfs import (
        LocalFSModels)
    from incubator_predictionio_tpu.data.storage.memory import MemoryModels
    from incubator_predictionio_tpu.data.storage.sqlite import SQLiteClient

    sqlite_client = SQLiteClient(StorageClientConfig(
        properties={"PATH": str(tmp_path / "models.sqlite")}))
    return {
        "memory": MemoryModels(),
        "sqlite": sqlite_client.models(),
        "localfs": LocalFSModels(str(tmp_path / "fs_models")),
    }


def test_models_backend_parity_roundtrip(tmp_path):
    """Checksum metadata rides INSIDE the blob, so it must round-trip
    bit-identically through every backend; pre-upgrade rows (bare
    pickle) are legacy-accepted with a warning counter, not a
    failure."""
    payload = pickle.dumps([lifecycle_engine.LifecycleModel(
        "parity", "good", __import__("numpy").ones(4))])
    wrapped = model_artifact.wrap(payload)
    stored = {}
    for name, dao in _model_backends(tmp_path).items():
        storage = _OneDaoStorage(dao)
        model_artifact.write_model(storage, "inst-1", payload)
        row = dao.get("inst-1")
        assert row is not None, name
        stored[name] = bytes(row.models)
        # verifying read returns the exact payload
        assert model_artifact.read_model(storage, "inst-1") == payload, name
        d = model_artifact.describe(row.models)
        assert d["ok"] and d["format"] == "v1", name
        assert d["sha256"] == model_artifact.compute_sha256(payload)

        # legacy row written by pre-upgrade code: accepted + counted
        dao.insert(Model("old-1", payload))
        before = model_artifact._LEGACY_LOADS.labels().value()
        assert model_artifact.read_model(storage, "old-1") == payload, name
        assert model_artifact._LEGACY_LOADS.labels().value() == before + 1

        # corrupt row: refused, NOT deleted
        bad = bytearray(wrapped)
        bad[-3] ^= 0x01
        dao.insert(Model("bad-1", bytes(bad)))
        with pytest.raises(model_artifact.ModelIntegrityError):
            model_artifact.read_model(storage, "bad-1")
        assert bytes(dao.get("bad-1").models) == bytes(bad), name
    # identical envelope bytes through every backend
    assert stored["memory"] == stored["sqlite"] == stored["localfs"] \
        == wrapped


# ---------------------------------------------------------------------------
# verifying loader walk-back
# ---------------------------------------------------------------------------

def test_walkback_on_corrupt_latest(memory_storage):
    iid1 = _train(memory_storage, "one")
    iid2 = _train(memory_storage, "two")
    dao = memory_storage.get_model_data_models()
    tampered = bytearray(dao.get(iid2).models)
    tampered[-5] ^= 0x10
    dao.insert(Model(iid2, bytes(tampered)))

    before = _failures("checksum")
    ctx = WorkflowContext(storage=memory_storage)
    dep, inst, _ = load_deployment(
        lifecycle_engine.engine_factory(), None, ctx,
        engine_factory_name="lifecycle")
    assert inst.id == iid1                       # walked back
    assert dep.query({"user": "u"})["tag"] == "one"
    assert _failures("checksum") == before + 1
    # the bad blob is evidence, never deleted or repaired
    assert bytes(dao.get(iid2).models) == bytes(tampered)

    # explicit target never walks back: the operator asked for THAT one
    with pytest.raises(model_artifact.ModelIntegrityError):
        load_deployment(lifecycle_engine.engine_factory(), iid2,
                        WorkflowContext(storage=memory_storage),
                        engine_factory_name="lifecycle")


def test_walkback_restores_ctx_app_name(memory_storage):
    """A rejected candidate must not leak its appName into the context
    the older instance is restored under."""
    iid1 = _train(memory_storage, "one")
    instances = memory_storage.get_meta_data_engine_instances()
    import dataclasses as dc

    good = instances.get(iid1)
    newer = dc.replace(
        good, id="newer-otherapp",
        start_time=good.start_time
        + __import__("datetime").timedelta(seconds=5),
        env={**good.env, "appName": "other-app"})
    instances.insert(newer)
    # valid envelope, unpicklable payload → rejected at deserialize,
    # AFTER the loop bound ctx to this candidate
    memory_storage.get_model_data_models().insert(
        Model("newer-otherapp",
              model_artifact.wrap(b"\x80not really a pickle")))
    ctx = WorkflowContext(storage=memory_storage)
    _, inst, _ = load_deployment(
        lifecycle_engine.engine_factory(), None, ctx,
        engine_factory_name="lifecycle")
    assert inst.id == iid1
    assert ctx.app_name == good.env.get("appName", "")


def test_initial_deploy_walks_back_past_validation_failure(memory_storage):
    """At initial deploy there is no last-good model: a NaN-poisoned
    (checksum-valid) newest instance must be pinned and the walk must
    land on the older healthy one, not crash `pio deploy`."""
    iid1 = _train(memory_storage, "one")
    nan_iid = _train(memory_storage, "broken", mode="nan")
    server = EngineServer(lifecycle_engine.engine_factory(),
                          engine_factory_name="lifecycle",
                          storage=memory_storage)
    assert server.instance.id == iid1
    lc = server.lifecycle_snapshot()
    assert lc["pinned"] == {nan_iid: "validate"}
    assert lc["validateFailures"] == 1
    assert server.deployment.query({"user": "u"})["tag"] == "one"


def test_slow_canary_times_out_into_rollback(memory_storage, chaos):
    """A swapped-in model that makes every query overrun its deadline
    (stage = compute, not queueing) must trip the watch and roll back —
    504s are failures too, even though there is no budget left to
    hedge."""
    iid1 = _train(memory_storage, "one")
    server = EngineServer(lifecycle_engine.engine_factory(),
                          engine_factory_name="lifecycle",
                          storage=memory_storage,
                          query_deadline_ms=150,
                          swap_watch_ms=60_000,
                          swap_max_error_rate=0.3)
    iid2 = _train(memory_storage, "two")
    with ServerThread(server.app) as st:
        r = requests.get(st.base + "/reload")
        assert r.status_code == 200 and r.json()["engineInstanceId"] == iid2
        chaos("query.predict:latency:4:1.0")
        codes = [_post(st.base, f"u{i}").status_code for i in range(2)]
        assert codes == [504, 504], codes
        lc = requests.get(st.base + "/status").json()["lifecycle"]
        assert lc["rollbacks"] == {"error-rate": 1}, lc
        assert lc["instance"] == iid1
        assert lc["pinned"] == {iid2: "error-rate"}


def test_query_stage_faults_surface_as_500(memory_storage, chaos):
    """The featurize and serve stage fault points fire through the
    REAL query path: a fail-injected stage answers 500 (no watch
    window, so no hedge), and once the rule is spent the next query
    serves normally. The overload/watch harnesses lean on
    query.predict; these two close fault-point-coverage for the
    remaining DASE stages."""
    _train(memory_storage, "one")
    server = EngineServer(lifecycle_engine.engine_factory(),
                          engine_factory_name="lifecycle",
                          storage=memory_storage)
    with ServerThread(server.app) as st:
        chaos("query.featurize:fail:1")
        assert _post(st.base, "u1").status_code == 500
        assert _post(st.base, "u1").status_code == 200
        chaos("query.serve:fail:1")
        assert _post(st.base, "u2").status_code == 500
        assert _post(st.base, "u2").status_code == 200


def test_completed_row_without_model_skipped(memory_storage):
    """The crash-mid-persist state: a COMPLETED row whose model never
    landed must be skipped by the latest walk — and an engine server
    deploys the older good instance."""
    import dataclasses as dc
    import datetime as dt

    iid1 = _train(memory_storage, "one")
    instances = memory_storage.get_meta_data_engine_instances()
    good = instances.get(iid1)
    orphan = dc.replace(good, id="orphan-completed",
                        start_time=good.start_time
                        + dt.timedelta(seconds=5))
    instances.insert(orphan)

    before = _failures("missing")
    ctx = WorkflowContext(storage=memory_storage)
    _, inst, _ = load_deployment(
        lifecycle_engine.engine_factory(), None, ctx,
        engine_factory_name="lifecycle")
    assert inst.id == iid1
    assert _failures("missing") == before + 1

    server = EngineServer(lifecycle_engine.engine_factory(),
                          engine_factory_name="lifecycle",
                          storage=memory_storage)
    assert server.instance.id == iid1
    # with ONLY the orphan row, loading must fail — never serve nothing
    instances.delete(iid1)
    with pytest.raises(RuntimeError, match="No deployable"):
        load_deployment(lifecycle_engine.engine_factory(), None,
                        WorkflowContext(storage=memory_storage),
                        engine_factory_name="lifecycle")


# ---------------------------------------------------------------------------
# model.insert crash window (subprocess SIGKILL)
# ---------------------------------------------------------------------------

def _sqlite_env(tmp_path, **extra):
    env = {
        **os.environ,
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
        "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
        "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "meta.sqlite"),
        "PIO_STORAGE_SOURCES_MEM_TYPE": "MEMORY",
        # keep the jax-free subprocesses jax-free (the compilation-cache
        # hook would import jax just to configure it)
        "PIO_COMPILATION_CACHE": "0",
        "JAX_PLATFORMS": "cpu",
    }
    env.pop("PIO_FAULT_SPEC", None)
    env.update(extra)
    return env


def _storage_for(env):
    from incubator_predictionio_tpu.data.storage import Storage

    return Storage({k: v for k, v in env.items()
                    if k.startswith("PIO_STORAGE")})


def test_model_insert_crash_leaves_no_completed_row(tmp_path):
    """`model.insert:crash:1` SIGKILLs the train inside the persistence
    window. Because the Model row lands BEFORE the COMPLETED stamp, the
    crash leaves a RUNNING row and no model — nothing a `/reload` could
    deploy — and a rerun trains clean."""
    env = _sqlite_env(tmp_path,
                      PIO_FAULT_SPEC="model.insert:crash:1")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "lifecycle_train.py"),
         "crashy"],
        env=env, capture_output=True, timeout=120)
    assert proc.returncode in (-9, 137), proc.stderr.decode()[-2000:]

    storage = _storage_for(env)
    try:
        instances = storage.get_meta_data_engine_instances()
        rows = instances.get_all()
        assert len(rows) == 1
        assert rows[0].status == "RUNNING"      # never stamped COMPLETED
        assert storage.get_model_data_models().get(rows[0].id) is None
        assert instances.get_completed("lifecycle", "1", "default") == []
    finally:
        storage.close()

    # rerun without the fault: trains and deploys clean
    env2 = _sqlite_env(tmp_path)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "lifecycle_train.py"), "ok"],
        env=env2, capture_output=True, timeout=120)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    storage = _storage_for(env2)
    try:
        ctx = WorkflowContext(storage=storage)
        _, inst, _ = load_deployment(
            lifecycle_engine.engine_factory(), None, ctx,
            engine_factory_name="lifecycle")
        assert inst.status == "COMPLETED"
    finally:
        storage.close()


# ---------------------------------------------------------------------------
# explicit-instance reload + manual rollback
# ---------------------------------------------------------------------------

def test_reload_explicit_instance_and_manual_rollback(memory_storage):
    iid1 = _train(memory_storage, "one")
    iid2 = _train(memory_storage, "two")
    server = EngineServer(lifecycle_engine.engine_factory(),
                          engine_factory_name="lifecycle",
                          storage=memory_storage)
    assert server.instance.id == iid2
    with ServerThread(server.app) as st:
        # explicit operator rollback to a known-good version
        r = requests.get(st.base + f"/reload?instance={iid1}")
        assert r.status_code == 200 and r.json()["engineInstanceId"] == iid1
        assert _post(st.base, "u").json()["tag"] == "one"
        lc = requests.get(st.base + "/status").json()["lifecycle"]
        assert lc["instance"] == iid1 and lc["previous"] == iid2

        # unknown target → 500 + degraded, still serving iid1
        r = requests.get(st.base + "/reload?instance=nope")
        assert r.status_code == 500
        assert requests.get(st.base + "/status").json()["degraded"]
        assert _post(st.base, "u").status_code == 200

        # back to latest, then /rollback swaps to previous and PINS it
        assert requests.get(st.base + "/reload").status_code == 200
        r = requests.post(st.base + "/rollback")
        assert r.status_code == 200
        assert r.json()["engineInstanceId"] == iid1
        lc = requests.get(st.base + "/status").json()["lifecycle"]
        assert lc["instance"] == iid1
        assert lc["pinned"] == {iid2: "manual"}
        assert lc["rollbacks"] == {"manual": 1}

        # pinned: reload-latest does NOT re-pick iid2
        r = requests.get(st.base + "/reload")
        assert r.status_code == 200 and r.json()["engineInstanceId"] == iid1

        # no previous left → 409
        assert requests.post(st.base + "/rollback").status_code == 409

        # explicit reload of the pinned instance un-pins it
        r = requests.get(st.base + f"/reload?instance={iid2}")
        assert r.status_code == 200 and r.json()["engineInstanceId"] == iid2
        lc = requests.get(st.base + "/status").json()["lifecycle"]
        assert lc["pinned"] == {}


# ---------------------------------------------------------------------------
# swap validation gate under live query fire
# ---------------------------------------------------------------------------

def test_swap_validate_failure_under_query_fire(memory_storage, chaos):
    """A reload whose validation gate fails stays on last-good with
    degraded mode set while concurrent queries keep answering 200 —
    the PR 6 hot-swap-under-fire pattern pointed at the gate."""
    iid1 = _train(memory_storage, "one")
    server = EngineServer(lifecycle_engine.engine_factory(),
                          engine_factory_name="lifecycle",
                          storage=memory_storage)
    _train(memory_storage, "two")
    stop = threading.Event()
    codes: list[int] = []

    with ServerThread(server.app) as st:
        def fire():
            while not stop.is_set():
                codes.append(_post(st.base, "u1").status_code)

        threads = [threading.Thread(target=fire) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            chaos("swap.validate:fail:1")
            r = requests.get(st.base + "/reload", timeout=60)
            assert r.status_code == 500
            assert "swap validation" in r.json()["message"]
            status = requests.get(st.base + "/status").json()
            assert status["degraded"] is True
            assert status["engineInstanceId"] == iid1    # last-good live
            assert status["lifecycle"]["validateFailures"] == 1
            # gate cleared → the same reload now lands
            r = requests.get(st.base + "/reload", timeout=60)
            assert r.status_code == 200
            assert r.json()["engineInstanceId"] != iid1
        finally:
            stop.set()
            for t in threads:
                t.join(30)
    assert codes and set(codes) == {200}, set(codes)


def test_reload_whose_warm_up_finds_no_room_stays_on_last_good(
        memory_storage, monkeypatch):
    """A model whose resident state cannot be placed beside the live
    model's (a 7.6 GB index on a 16 GB chip: the Universal Recommender at a
    shop's catalog size, docs/operations.md) fails inside ``warm_up``; the
    gate refuses the swap, the last-good model keeps answering, and a
    restart (a new server, nothing resident) deploys the new instance."""
    iid1 = _train(memory_storage, "one")
    server = EngineServer(lifecycle_engine.engine_factory(),
                          engine_factory_name="lifecycle",
                          storage=memory_storage)
    iid2 = _train(memory_storage, "two")

    def no_room(self):
        raise RuntimeError("RESOURCE_EXHAUSTED: Error allocating device "
                           "buffer: Attempting to allocate 7.00G")

    monkeypatch.setattr(lifecycle_engine.LifecycleModel, "warm_up", no_room,
                        raising=False)
    with ServerThread(server.app) as st:
        r = requests.get(st.base + "/reload", timeout=60)
        assert r.status_code == 500
        assert "warm-up failed" in r.json()["message"]
        assert "RESOURCE_EXHAUSTED" in r.json()["message"]
        status = requests.get(st.base + "/status").json()
        assert status["degraded"] is True
        assert status["engineInstanceId"] == iid1
        assert _post(st.base, "u1").json()["tag"] == "one"
    monkeypatch.undo()
    restarted = EngineServer(lifecycle_engine.engine_factory(),
                             engine_factory_name="lifecycle",
                             storage=memory_storage)
    with ServerThread(restarted.app) as st:
        assert requests.get(st.base + "/status").json()[
            "engineInstanceId"] == iid2
        assert _post(st.base, "u1").json()["tag"] == "two"


def test_nan_model_refused_by_gate_and_pinned_by_refresh(memory_storage):
    """A NaN-poisoned retrain must never go live: the refresh loop's
    validated swap hits the nan_guard, stays on last-good, pins the
    instance, and the next polls don't retry it."""
    iid1 = _train(memory_storage, "one")
    server = EngineServer(lifecycle_engine.engine_factory(),
                          engine_factory_name="lifecycle",
                          storage=memory_storage,
                          model_refresh_ms=80)
    with ServerThread(server.app) as st:
        nan_iid = _train(memory_storage, "broken", mode="nan")
        deadline = time.monotonic() + 15
        lc = {}
        while time.monotonic() < deadline:
            lc = requests.get(st.base + "/status").json()["lifecycle"]
            if lc["pinned"]:
                break
            time.sleep(0.05)
        assert lc["pinned"] == {nan_iid: "validate"}, lc
        assert lc["instance"] == iid1
        assert lc["validateFailures"] >= 1
        status = requests.get(st.base + "/status").json()
        assert status["degraded"] is True
        assert "non-finite" in status["degradedReason"]
        assert _post(st.base, "u1").status_code == 200
        # a GOOD retrain heals: refresh swaps to it and clears degraded
        good2 = _train(memory_storage, "fresh")
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            doc = requests.get(st.base + "/status").json()
            if doc["engineInstanceId"] == good2:
                break
            time.sleep(0.05)
        assert doc["engineInstanceId"] == good2
        assert doc["degraded"] is False
        assert doc["lifecycle"]["refreshSwaps"] >= 1
        assert _post(st.base, "u1").json()["tag"] == "fresh"


def test_auto_rollback_on_error_rate_in_process(memory_storage):
    """A poisoned model that PASSES the gate (golden query works) but
    fails real traffic rolls back automatically inside the watch
    window — and the failing queries are hedged onto the retained
    last-good deployment, so clients never see the canary's 500s."""
    iid1 = _train(memory_storage, "one")
    server = EngineServer(lifecycle_engine.engine_factory(),
                          engine_factory_name="lifecycle",
                          storage=memory_storage,
                          swap_watch_ms=60_000,
                          swap_max_error_rate=0.3)
    bad = _train(memory_storage, "bad", mode="poison")
    with ServerThread(server.app) as st:
        r = requests.get(st.base + "/reload")
        assert r.status_code == 200 and r.json()["engineInstanceId"] == bad
        results = [_post(st.base, f"u{i}") for i in range(6)]
        assert [r.status_code for r in results] == [200] * 6, \
            [r.text for r in results]
        # every answer came from a model that works — i.e. last-good
        assert {r.json()["tag"] for r in results} == {"one"}
        lc = requests.get(st.base + "/status").json()["lifecycle"]
        assert lc["instance"] == iid1
        assert lc["pinned"] == {bad: "error-rate"}
        assert lc["rollbacks"] == {"error-rate": 1}
        metrics = requests.get(st.base + "/metrics").text
        assert 'pio_engine_rollbacks_total{reason="error-rate"} 1' \
            in metrics
        # rolled-back model stays pinned: reload-latest keeps last-good
        r = requests.get(st.base + "/reload")
        assert r.status_code == 200 and r.json()["engineInstanceId"] == iid1


def test_watch_straggler_after_rollback_served_not_500(memory_storage):
    """The seed-5 soak's raw-500 leak, leg 1 (regression): a query
    dispatched to the poisoned canary BEFORE the error-rate rollback
    whose failure lands AFTER it (the rollback cleared the watch and
    dropped the previous deployment) must be retried on the restored
    live model — not answered with the retired canary's raw 500."""
    _train(memory_storage, "one")
    server = EngineServer(lifecycle_engine.engine_factory(),
                          engine_factory_name="lifecycle",
                          storage=memory_storage,
                          swap_watch_ms=60_000,
                          swap_max_error_rate=0.3)
    bad = _train(memory_storage, "bad", mode="poison")
    with ServerThread(server.app) as st:
        r = requests.get(st.base + "/reload")
        assert r.status_code == 200 and r.json()["engineInstanceId"] == bad
        # trip the rollback with two fast failing queries (hedged 200s)
        fast = [_post(st.base, f"u{i}") for i in range(2)]
        assert [r.status_code for r in fast] == [200, 200], \
            [r.text for r in fast]
        lc = requests.get(st.base + "/status").json()["lifecycle"]
        assert lc["rollbacks"] == {"error-rate": 1}
        # the straggler condition, deterministically: a failure lands
        # attributed to a deployment that is NO LONGER the live one,
        # with the watch already cleared by the rollback — before the
        # fix, _watched_failure returned None here and the client got
        # the retired canary's raw 500
        import asyncio

        class _RetiredCanary:
            def query(self, q):
                raise RuntimeError("late canary failure")

        fut = asyncio.run_coroutine_threadsafe(
            server._watched_failure(_RetiredCanary(), {"user": "s"},
                                    None), st._loop)
        out = fut.result(timeout=30)
        assert out is not None and out["tag"] == "one", out
        # and end-to-end: fresh traffic serves 200 from last-good
        r2 = _post(st.base, "u-after")
        assert r2.status_code == 200 and r2.json()["tag"] == "one"


def test_hedge_overrun_answers_504_not_500(memory_storage):
    """The seed-5 soak's raw-500 leak, leg 2 (regression): when the
    HEDGE dispatch itself runs out of deadline budget, the client gets
    the overload verdict (504) — not the canary's raw 500 — and the
    overrun never counts against the watch window."""
    _train(memory_storage, "one")
    server = EngineServer(lifecycle_engine.engine_factory(),
                          engine_factory_name="lifecycle",
                          storage=memory_storage,
                          swap_watch_ms=60_000,
                          swap_max_error_rate=0.3)
    bad = _train(memory_storage, "bad", mode="poison")
    with ServerThread(server.app) as st:
        r = requests.get(st.base + "/reload")
        assert r.status_code == 200 and r.json()["engineInstanceId"] == bad
        # the canary raises instantly (poison checks before sleeping);
        # the hedge lands on last-good which sleeps past the remaining
        # budget → the hedge dispatch raises DeadlineExceeded
        r = requests.post(
            st.base + "/queries.json",
            json={"user": "u-slow", "sleepS": 2.0},
            headers={"X-Pio-Deadline-Ms": "700"}, timeout=30)
        assert r.status_code == 504, (r.status_code, r.text)
        status = requests.get(st.base + "/status").json()
        assert status["overload"]["deadlineExceeded"] >= 1
        # the overrun was the server's verdict, not canary evidence:
        # no rollback happened and the canary stays live
        lc = status["lifecycle"]
        assert lc["instance"] == bad
        assert lc["rollbacks"] == {}
        # the watch counted at most the hedge-skipped nothing: a plain
        # failing query afterwards still hedges to 200
        r2 = _post(st.base, "u-after")
        assert r2.status_code == 200 and r2.json()["tag"] == "one"


# ---------------------------------------------------------------------------
# subprocess e2e: poisoned retrain auto-rolls back under live fire
# ---------------------------------------------------------------------------

def test_poisoned_retrain_rolls_back_e2e_subprocess(tmp_path):
    # jax-free subprocess: whole e2e runs in seconds, inside the tier-1
    # budget (the >20s slow-mark rule doesn't trigger)
    """The acceptance headline in one REAL server: continuous refresh
    hot-swaps a poisoned retrain through the validated gate, the
    post-swap watch rolls it back, and every client query answers 200
    throughout. A corrupt older instance seeded before startup also
    proves the integrity walk-back + counter in the live process."""
    env = _sqlite_env(tmp_path,
                      PIO_MODEL_REFRESH_MS="150",
                      PIO_SWAP_WATCH_MS="30000",
                      PIO_SWAP_MAX_ERROR_RATE="0.3")
    storage = _storage_for(env)
    corrupt_iid = _train(storage, "corrupt-seed")
    good_iid = _train(storage, "good")
    # bit-flip the OLDER instance's blob: startup must count it only if
    # walked; instead corrupt the NEWEST pre-start so startup walks back
    dao = storage.get_model_data_models()
    newest_bad = _train(storage, "newest-corrupt")
    t = bytearray(dao.get(newest_bad).models)
    t[-4] ^= 0x08
    dao.insert(Model(newest_bad, bytes(t)))
    del corrupt_iid

    port = free_port()
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "lifecycle_server.py"),
         str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    base = f"http://127.0.0.1:{port}"
    try:
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    "server died: "
                    + proc.stdout.read().decode(errors="replace")[-3000:])
            try:
                doc = requests.get(base + "/status", timeout=2).json()
                break
            except requests.RequestException:
                time.sleep(0.2)
        else:
            raise AssertionError("server not ready")
        # startup walked back over the corrupt newest instance
        assert doc["engineInstanceId"] == good_iid

        stop = threading.Event()
        codes: list[int] = []
        tags: set = set()

        def client():
            while not stop.is_set():
                try:
                    r = _post(base, "u-client", timeout=10)
                    codes.append(r.status_code)
                    if r.status_code == 200:
                        tags.add(r.json()["tag"])
                except requests.RequestException:
                    if not stop.is_set():
                        codes.append(-1)
                time.sleep(0.02)

        th = threading.Thread(target=client)
        th.start()
        try:
            time.sleep(0.5)                     # steady-state 200s first
            bad_iid = _train(storage, "poisoned", mode="poison")
            deadline = time.monotonic() + 30
            lc = {}
            while time.monotonic() < deadline:
                lc = requests.get(base + "/status",
                                  timeout=5).json()["lifecycle"]
                if lc["rollbacks"]:
                    break
                time.sleep(0.1)
        finally:
            stop.set()
            th.join(30)
        assert lc.get("rollbacks") == {"error-rate": 1}, lc
        assert lc["pinned"].get(bad_iid) == "error-rate"
        # the refresh loop pinned the corrupt candidate instead of
        # re-walking (and re-counting) it every poll
        assert lc["pinned"].get(newest_bad) == "integrity:checksum"
        assert lc["instance"] == good_iid
        # EVERY client query answered 200 — before, during and after
        # the poisoned swap + rollback
        assert codes and set(codes) == {200}, sorted(set(codes))
        assert tags == {"good"}
        # give the refresh loop two more ticks: the pin holds
        time.sleep(0.5)
        doc = requests.get(base + "/status", timeout=5).json()
        assert doc["engineInstanceId"] == good_iid
        # both acceptance metric families visible on /metrics
        metrics = requests.get(base + "/metrics", timeout=5).text
        assert 'pio_engine_rollbacks_total{reason="error-rate"} 1' \
            in metrics
        assert 'pio_model_integrity_failures_total{kind="checksum"}' \
            in metrics
        # ... and in `pio status --engine-url` (no scrape needed)
        from incubator_predictionio_tpu.tools.commands.management import (
            _print_engine_overload)
        import io
        from contextlib import redirect_stdout

        buf = io.StringIO()
        with redirect_stdout(buf):
            _print_engine_overload(base)
        out = buf.getvalue()
        assert "rollbacks=1" in out
        assert "error-rate" in out
        # exactly 2: one at startup walk-back, one on the first refresh
        # poll (then the pin stops the re-walking)
        assert "integrityFailures={'checksum': 2}" in out
        # clean SIGTERM drain
        proc.send_signal(__import__("signal").SIGTERM)
        assert proc.wait(timeout=30) == 0
    finally:
        storage.close()
        if proc.poll() is None:
            proc.kill()
        proc.communicate()


# ---------------------------------------------------------------------------
# pio models CLI
# ---------------------------------------------------------------------------

def test_pio_models_cli_list_verify_gc(tmp_path, capsys, monkeypatch):
    env = _sqlite_env(tmp_path)
    for k, v in env.items():
        if k.startswith("PIO_STORAGE"):
            monkeypatch.setenv(k, v)
    from incubator_predictionio_tpu.data.storage import Storage

    storage = Storage.reset_instance(
        {k: v for k, v in env.items() if k.startswith("PIO_STORAGE")})
    try:
        iids = [_train(storage, f"t{i}") for i in range(4)]
        dao = storage.get_model_data_models()
        # corrupt one; strip the NEWEST one's blob (crash-window row —
        # it must not consume the GC keep window below)
        t = bytearray(dao.get(iids[1]).models)
        t[-2] ^= 0x04
        dao.insert(Model(iids[1], bytes(t)))
        dao.delete(iids[3])

        from incubator_predictionio_tpu.tools.console import main as pio

        assert pio(["models", "list"]) == 0
        out = capsys.readouterr().out
        assert "CORRUPT (checksum)" in out
        assert "no model (crash window" in out
        assert out.count("verified") == 2

        assert pio(["models", "verify"]) == 1       # corruption → rc 1
        capsys.readouterr()

        # GC keeps the newest --keep BLOB-BEARING models (the model-less
        # newest row must not consume the keep window), deletes the
        # rest; dry-run deletes nothing
        assert pio(["models", "gc", "--keep", "1", "--dry-run"]) == 0
        assert "would delete" in capsys.readouterr().out
        assert dao.get(iids[2]) is not None
        assert pio(["models", "gc", "--keep", "1"]) == 0
        capsys.readouterr()
        assert dao.get(iids[2]) is not None    # newest WITH a blob kept
        assert dao.get(iids[1]) is None        # beyond keep: gone
        assert dao.get(iids[0]) is None
        # GC'd rows are COMPLETED-without-model, which must NOT fail a
        # cron'd verify — its nonzero exit is reserved for corruption
        assert pio(["models", "verify"]) == 0
        assert "0 corrupt" in capsys.readouterr().out
    finally:
        Storage.reset_instance({
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
            "PIO_STORAGE_SOURCES_MEM_TYPE": "MEMORY",
        })


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------

def test_guard_workflow_reads_models_only_via_artifact_loader():
    """Nothing under workflow/ may touch the Models DAO except the
    verifying loader (model_artifact.py) — a future `storage.
    get_model_data_models().get(...)` elsewhere would bypass checksum
    verification and reopen the corrupt-model-serves-production hole
    (the PR 3/6/8 single-path-guard pattern). Enforced by the shared
    `pio lint` engine."""
    from incubator_predictionio_tpu.tools.lint import assert_rule_clean

    assert_rule_clean("models-dao-confinement")


def test_lifecycle_marker_registered():
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent
    toml = (root / "pyproject.toml").read_text()
    assert "lifecycle:" in toml
