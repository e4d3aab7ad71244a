"""Network storage end-to-end: many hosts, one shared store.

The deployment shape the embedded backends cannot give: a `pio
storageserver` node holds the data; training,
serving, and ops hosts — each with its OWN empty PIO_FS_BASEDIR — point
TYPE=HTTP at it. Proves (a) `pio status` connectivity checking, (b) the
full app/import/train lifecycle over the wire, and (c) the HDFS/S3-role
remote model store: a host that never trained deploys the model from the
network and serves queries (reference: storage/hbase + jdbc + Models-on-
HDFS roles, SURVEY.md §2.1).
"""

import json
import os
import socket
import subprocess
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIO = os.path.join(REPO, "bin", "pio")

# The whole e2e runs AUTHENTICATED (reference posture: every network
# surface behind KeyAuthentication, SURVEY.md §1 row 9).
SECRET = "e2e-shared-secret"


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_pio(args, env, check=True):
    r = subprocess.run(
        [PIO, *args], capture_output=True, text=True, env=env, timeout=300
    )
    if check and r.returncode != 0:
        raise AssertionError(
            f"pio {' '.join(args)} failed ({r.returncode}):\n{r.stdout}\n{r.stderr}"
        )
    return r


def _http_env(base_dir, port):
    env = dict(os.environ)
    env.update({
        "PIO_FS_BASEDIR": str(base_dir),
        "JAX_PLATFORMS": "cpu",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "NET",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "NET",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "NET",
        "PIO_STORAGE_SOURCES_NET_TYPE": "HTTP",
        "PIO_STORAGE_SOURCES_NET_HOSTS": "127.0.0.1",
        "PIO_STORAGE_SOURCES_NET_PORTS": str(port),
        "PIO_STORAGE_SOURCES_NET_SECRET": SECRET,
    })
    return env


@pytest.fixture()
def storage_server(tmp_path):
    port = free_port()
    server_env = dict(os.environ)
    server_env["PIO_FS_BASEDIR"] = str(tmp_path / "server_store")
    server_env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [PIO, "storageserver", "--ip", "127.0.0.1", "--port", str(port),
         "--secret", SECRET],
        env=server_env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=2
            ) as r:
                assert json.loads(r.read())["status"] == "ok"
                break
        except OSError:
            if proc.poll() is not None:
                raise AssertionError(
                    f"storageserver died: {proc.stdout.read()}")
            time.sleep(0.5)
    else:
        raise AssertionError("storageserver never became healthy")
    yield port
    proc.terminate()
    proc.wait(timeout=30)


def test_shared_store_lifecycle_and_remote_deploy(storage_server, tmp_path):
    port = storage_server

    # Host A: ingest + train. Its basedir starts empty.
    env_a = _http_env(tmp_path / "host_a", port)
    r = run_pio(["status"], env_a)
    assert "ready to go" in r.stdout  # connectivity verified over HTTP

    run_pio(["app", "new", "NetApp"], env_a)
    events = tmp_path / "events.jsonl"
    rng = np.random.default_rng(0)
    with open(events, "w") as f:
        for k in range(300):
            f.write(json.dumps({
                "event": "rate", "entityType": "user",
                "entityId": f"u{rng.integers(0, 20)}",
                "targetEntityType": "item",
                "targetEntityId": f"i{rng.integers(0, 12)}",
                "properties": {"rating": int(rng.integers(1, 6))},
                "eventTime": f"2024-01-01T00:{k // 60:02d}:{k % 60:02d}.000Z",
            }) + "\n")
    r = run_pio(["import", "--app-name", "NetApp", "--input", str(events)],
                env_a)
    assert "Imported 300 events" in r.stdout

    # server-side aggregate_properties over the wire: replayed result
    # matches the $set stream just imported
    props_file = tmp_path / "props.jsonl"
    with open(props_file, "w") as f:
        f.write(json.dumps({
            "event": "$set", "entityType": "item", "entityId": "i1",
            "properties": {"category": "a", "price": 3},
            "eventTime": "2024-01-02T00:00:00.000Z"}) + "\n")
        f.write(json.dumps({
            "event": "$set", "entityType": "item", "entityId": "i1",
            "properties": {"price": 5},
            "eventTime": "2024-01-03T00:00:00.000Z"}) + "\n")
    run_pio(["import", "--app-name", "NetApp", "--input", str(props_file)],
            env_a)
    from incubator_predictionio_tpu.data.storage import Storage as _S

    s_http = _S({k: v for k, v in env_a.items()
                 if k.startswith("PIO_STORAGE")})
    agg = s_http.get_p_events().aggregate_properties(1, "item")
    assert set(agg) == {"i1"}
    assert agg["i1"].to_dict() == {"category": "a", "price": 5}
    assert agg["i1"].first_updated.isoformat().startswith("2024-01-02")
    assert agg["i1"].last_updated.isoformat().startswith("2024-01-03")

    proj = str(tmp_path / "engine")
    run_pio(["template", "get", "recommendation", proj], env_a)
    ej = os.path.join(proj, "engine.json")
    with open(ej) as f:
        e = json.load(f)
    e["datasource"]["params"]["appName"] = "NetApp"
    e["algorithms"][0]["params"]["numIterations"] = 3
    with open(ej, "w") as f:
        json.dump(e, f)
    r = run_pio(["train", "--engine-dir", proj], env_a)
    assert "Training completed" in r.stdout

    # Host B: NEVER trained, EMPTY basedir — deploys the model from the
    # shared store and answers queries (remote model store).
    env_b = _http_env(tmp_path / "host_b", port)
    port_b = free_port()
    server = subprocess.Popen(
        [PIO, "deploy", "--engine-dir", proj, "--port", str(port_b)],
        env=env_b, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        deadline = time.time() + 120
        body = None
        while time.time() < deadline:
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port_b}/queries.json",
                    data=json.dumps({"user": "u1", "num": 3}).encode(),
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=5) as resp:
                    body = json.loads(resp.read())
                break
            except OSError:
                if server.poll() is not None:
                    raise AssertionError(
                        f"deploy died: {server.stdout.read()}")
                time.sleep(1)
        assert body is not None, "server never answered"
        assert len(body["itemScores"]) == 3
        # host_b's own disk must hold no model blob — it came off the wire.
        for root, _dirs, files in os.walk(tmp_path / "host_b"):
            assert not any(f.endswith((".sqlite", ".bin")) for f in files), (
                root, files)
    finally:
        server.terminate()
        server.wait(timeout=30)


def test_auth_rejects_bad_or_missing_secret(storage_server):
    port = storage_server

    def post(path, headers=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps({"namespace": "pio_metadata",
                             "args": {}}).encode(),
            headers={"Content-Type": "application/json", **(headers or {})},
        )
        try:
            with urllib.request.urlopen(req, timeout=5) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code

    # /health stays open (liveness probes don't carry secrets)
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/health", timeout=5
    ) as r:
        assert r.status == 200
    assert post("/rpc/apps/get_all") == 401
    assert post("/rpc/apps/get_all",
                {"Authorization": "Bearer wrong"}) == 401
    assert post("/rpc/apps/get_all",
                {"Authorization": f"Bearer {SECRET}"}) == 200
    # non-wire DAO methods are not remotely callable (allowlist)
    assert post("/rpc/l_events/compact",
                {"Authorization": f"Bearer {SECRET}"}) == 404


def test_nonloopback_bind_requires_secret(tmp_path):
    env = dict(os.environ)
    env["PIO_FS_BASEDIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PIO_STORAGESERVER_SECRET", None)
    r = subprocess.run(
        [PIO, "storageserver", "--ip", "0.0.0.0", "--port",
         str(free_port())],
        capture_output=True, text=True, env=env, timeout=60,
    )
    assert r.returncode != 0
    assert "refusing" in (r.stdout + r.stderr).lower()


def test_all_network_backend_topology():
    """Production-shaped topology with EVERY repository on a network
    protocol: metadata on MySQL (wire protocol), events on
    Elasticsearch (REST, sliced PIT training reads), models on S3
    (SigV4) — full lifecycle: app, ingest, train, persist, deploy from
    a cold registry, query."""
    import datetime as dt

    from es_mock import build_es_app
    from mysql_mock import MockMySQLServer
    from s3_mock import build_s3_app
    from server_utils import ServerThread

    from incubator_predictionio_tpu.controller import EngineParams
    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.data.storage.base import App
    from incubator_predictionio_tpu.data.storage.datamap import DataMap
    from incubator_predictionio_tpu.data.storage.event import Event
    from incubator_predictionio_tpu.models.recommendation import (
        RecommendationEngine,
    )
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import (
        load_deployment, run_train,
    )

    with MockMySQLServer(user="pio", password="piosecret") as my, \
            ServerThread(build_es_app()) as es, \
            ServerThread(build_s3_app("AK", "sk")) as s3:
        env = {
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MY",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "ES",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "OBJ",
            "PIO_STORAGE_SOURCES_MY_TYPE": "MYSQL",
            "PIO_STORAGE_SOURCES_MY_HOST": "127.0.0.1",
            "PIO_STORAGE_SOURCES_MY_PORT": str(my.port),
            "PIO_STORAGE_SOURCES_MY_USERNAME": "pio",
            "PIO_STORAGE_SOURCES_MY_PASSWORD": "piosecret",
            "PIO_STORAGE_SOURCES_ES_TYPE": "ELASTICSEARCH",
            "PIO_STORAGE_SOURCES_ES_HOSTS": "127.0.0.1",
            "PIO_STORAGE_SOURCES_ES_PORTS": str(es.port),
            "PIO_STORAGE_SOURCES_OBJ_TYPE": "S3",
            "PIO_STORAGE_SOURCES_OBJ_ENDPOINT":
                f"http://127.0.0.1:{s3.port}",
            "PIO_STORAGE_SOURCES_OBJ_BUCKET": "pio-models",
            "PIO_STORAGE_SOURCES_OBJ_ACCESS_KEY": "AK",
            "PIO_STORAGE_SOURCES_OBJ_SECRET_KEY": "sk",
        }
        storage = Storage(env)
        aid = storage.get_meta_data_apps().insert(App(0, "netapp"))
        rng = np.random.default_rng(5)
        evs = []
        t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
        for k in range(800):
            evs.append(Event(
                "rate", "user", str(int(rng.integers(0, 40))),
                "item", f"i{int(rng.integers(0, 25))}",
                DataMap({"rating": int(rng.integers(1, 6))}),
                t0 + dt.timedelta(seconds=k)))
        storage.get_l_events().insert_batch(evs, aid)

        engine = RecommendationEngine()()
        ep = EngineParams.from_json({
            "datasource": {"params": {"appName": "netapp"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 8, "numIterations": 5, "lambda": 0.05}}],
        })
        ctx = WorkflowContext(app_name="netapp", storage=storage)
        iid = run_train(engine, ep, ctx, engine_factory_name="net")
        storage.close()

        # cold start: a FRESH registry (new connections to all three
        # services) must find the instance in MySQL, the model in S3,
        # and serve — the deploy-on-a-different-host story
        storage2 = Storage(env)
        dep, _, _ = load_deployment(
            engine, iid, WorkflowContext(storage=storage2),
            engine_factory_name="net")
        out = dep.query({"user": "3", "num": 4})
        assert len(out["itemScores"]) == 4
        assert all(s["item"].startswith("i") for s in out["itemScores"])
        storage2.close()


def test_topology_with_hbase_rpc_event_store():
    """Second production topology, exercising the NATIVE HBase RPC
    transport as the event store of record (pre-split table → real
    region routing), metadata on PostgreSQL (wire protocol), models on
    WebHDFS — full lifecycle incl. a cold-registry deploy."""
    import datetime as dt

    from hbase_rpc_mock import MockHBaseRpcServer
    from hdfs_mock import build_hdfs_app
    from pg_mock import MockPGServer
    from server_utils import ServerThread

    from incubator_predictionio_tpu.controller import EngineParams
    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.data.storage.base import App
    from incubator_predictionio_tpu.data.storage.datamap import DataMap
    from incubator_predictionio_tpu.data.storage.event import Event
    from incubator_predictionio_tpu.models.recommendation import (
        RecommendationEngine,
    )
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import (
        load_deployment, run_train,
    )

    splits = {"pio_eventdata_1": [b"t:80007"]}
    with MockPGServer(user="pio", password="piosecret") as pg, \
            MockHBaseRpcServer(split_keys=splits) as hb, \
            ServerThread(build_hdfs_app()) as dfs:
        env = {
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "PG",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "HB",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DFS",
            "PIO_STORAGE_SOURCES_PG_TYPE": "PGSQL",
            "PIO_STORAGE_SOURCES_PG_HOST": "127.0.0.1",
            "PIO_STORAGE_SOURCES_PG_PORT": str(pg.port),
            "PIO_STORAGE_SOURCES_PG_USERNAME": "pio",
            "PIO_STORAGE_SOURCES_PG_PASSWORD": "piosecret",
            "PIO_STORAGE_SOURCES_HB_TYPE": "HBASE",
            "PIO_STORAGE_SOURCES_HB_HOSTS": "127.0.0.1",
            "PIO_STORAGE_SOURCES_HB_PORTS": str(hb.port),
            "PIO_STORAGE_SOURCES_HB_PROTOCOL": "rpc",
            "PIO_STORAGE_SOURCES_DFS_TYPE": "HDFS",
            "PIO_STORAGE_SOURCES_DFS_HOSTS": "127.0.0.1",
            "PIO_STORAGE_SOURCES_DFS_PORTS": str(dfs.port),
            "PIO_STORAGE_SOURCES_DFS_PATH": "/pio/models",
        }
        storage = Storage(env)
        aid = storage.get_meta_data_apps().insert(App(0, "hbapp"))
        assert aid == 1  # the pre-split table name assumes it
        rng = np.random.default_rng(6)
        evs = []
        t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
        for k in range(600):
            evs.append(Event(
                "rate", "user", str(int(rng.integers(0, 30))),
                "item", f"i{int(rng.integers(0, 20))}",
                DataMap({"rating": int(rng.integers(1, 6))}),
                t0 + dt.timedelta(seconds=k)))
        storage.get_l_events().insert_batch(evs, aid)

        engine = RecommendationEngine()()
        ep = EngineParams.from_json({
            "datasource": {"params": {"appName": "hbapp"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 8, "numIterations": 4, "lambda": 0.05}}],
        })
        ctx = WorkflowContext(app_name="hbapp", storage=storage)
        iid = run_train(engine, ep, ctx, engine_factory_name="hbnet")
        storage.close()

        storage2 = Storage(env)
        dep, _, _ = load_deployment(
            engine, iid, WorkflowContext(storage=storage2),
            engine_factory_name="hbnet")
        out = dep.query({"user": "3", "num": 4})
        assert len(out["itemScores"]) == 4
        storage2.close()
