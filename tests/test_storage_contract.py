"""Storage-backend contract tests, parametrized over backends — the analog
of the reference's LEventsSpec/PEventsSpec run against HBase/JDBC/ES
(SURVEY.md §4: same DAO behaviour across backends)."""

import datetime as dt

import pytest

from incubator_predictionio_tpu.data.storage import (
    AccessKey,
    App,
    Channel,
    DataMap,
    EngineInstance,
    EvaluationInstance,
    Event,
    Model,
    PropertyMap,
    Storage,
)


def _make_storage(kind, tmp_path):
    if kind == "memory":
        env = {
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "S",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "S",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "S",
            "PIO_STORAGE_SOURCES_S_TYPE": "MEMORY",
        }
    elif kind == "sqlite":
        env = {
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "S",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "S",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "S",
            "PIO_STORAGE_SOURCES_S_TYPE": "SQLITE",
            "PIO_STORAGE_SOURCES_S_PATH": str(tmp_path / f"{kind}.sqlite"),
        }
    elif kind == "jsonl":  # metadata/models sqlite, events JSONL log
        env = {
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "LOG",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
            "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
            "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "meta.sqlite"),
            "PIO_STORAGE_SOURCES_LOG_TYPE": "JSONL",
            "PIO_STORAGE_SOURCES_LOG_PATH": str(tmp_path / "events"),
        }
    elif kind == "mixed":  # metadata+events sqlite, models localfs
        env = {
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
            "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
            "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "mixed.sqlite"),
            "PIO_STORAGE_SOURCES_FS_TYPE": "LOCALFS",
            "PIO_STORAGE_SOURCES_FS_PATH": str(tmp_path / "models"),
        }
    return Storage(env)


BACKENDS = ["memory", "sqlite", "mixed", "jsonl", "http", "s3",
            "elasticsearch", "pgsql", "mysql", "hbase", "hbase_rpc", "hdfs"]


@pytest.fixture(params=BACKENDS)
def storage(request, tmp_path):
    if request.param == "mysql":
        # All three repositories over the REAL MySQL client/server
        # protocol: caching_sha2_password challenge-response verified
        # server-side, parameters via the prepared-statement binary
        # protocol — the MySQL half of the reference's JDBC assembly
        # (mysql_mock.py).
        from mysql_mock import MockMySQLServer

        with MockMySQLServer(user="pio", password="piosecret") as srv:
            env = {
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MY",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MY",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MY",
                "PIO_STORAGE_SOURCES_MY_TYPE": "MYSQL",
                "PIO_STORAGE_SOURCES_MY_HOST": "127.0.0.1",
                "PIO_STORAGE_SOURCES_MY_PORT": str(srv.port),
                "PIO_STORAGE_SOURCES_MY_USERNAME": "pio",
                "PIO_STORAGE_SOURCES_MY_PASSWORD": "piosecret",
            }
            s = Storage(env)
            yield s
            s.close()
        return
    if request.param == "pgsql":
        # All three repositories over the REAL Postgres wire protocol
        # (v3 + SCRAM-SHA-256): the in-process server verifies the
        # client's SCRAM proof against the configured password and runs
        # the extended-protocol conversation — the reference's JDBC
        # assembly scope with wire-level parity (pg_mock.py).
        from pg_mock import MockPGServer

        with MockPGServer(user="pio", password="piosecret") as srv:
            env = {
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "PG",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PG",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "PG",
                "PIO_STORAGE_SOURCES_PG_TYPE": "PGSQL",
                "PIO_STORAGE_SOURCES_PG_HOST": "127.0.0.1",
                "PIO_STORAGE_SOURCES_PG_PORT": str(srv.port),
                "PIO_STORAGE_SOURCES_PG_USERNAME": "pio",
                "PIO_STORAGE_SOURCES_PG_PASSWORD": "piosecret",
            }
            s = Storage(env)
            yield s
            s.close()
        return
    if request.param == "hdfs":
        # Model blobs over the WebHDFS REST protocol incl. the real
        # 307 NameNode->DataNode CREATE redirect (hdfs_mock.py) — the
        # reference's storage/hdfs assembly scope; metadata+events on
        # sqlite.
        from hdfs_mock import build_hdfs_app
        from server_utils import ServerThread

        with ServerThread(build_hdfs_app()) as srv:
            env = {
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DFS",
                "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
                "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "hdfsmeta.sqlite"),
                "PIO_STORAGE_SOURCES_DFS_TYPE": "HDFS",
                "PIO_STORAGE_SOURCES_DFS_HOSTS": "127.0.0.1",
                "PIO_STORAGE_SOURCES_DFS_PORTS": str(srv.port),
                "PIO_STORAGE_SOURCES_DFS_PATH": "/pio/models",
            }
            s = Storage(env)
            yield s
            s.close()
        return
    if request.param == "hbase_rpc":
        # Event data over HBase's NATIVE RPC protocol: protobuf-framed
        # calls, hbase:meta region routing, Multi-batched puts, Filter
        # protos pushed down, reversed scanners (hbase_rpc_mock.py) —
        # the reference's own transport family; metadata+models on
        # sqlite.  The event table is PRE-SPLIT so the contract runs
        # against real multi-region routing, not a single region.
        from hbase_rpc_mock import MockHBaseRpcServer

        splits = {f"pio_eventdata_{app}": [b"t:8"] for app in range(1, 9)}
        with MockHBaseRpcServer(split_keys=splits) as srv:
            env = {
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "HB",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
                "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
                "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "hbmeta.sqlite"),
                "PIO_STORAGE_SOURCES_HB_TYPE": "HBASE",
                "PIO_STORAGE_SOURCES_HB_HOSTS": "127.0.0.1",
                "PIO_STORAGE_SOURCES_HB_PORTS": str(srv.port),
                "PIO_STORAGE_SOURCES_HB_PROTOCOL": "rpc",
            }
            s = Storage(env)
            yield s
            s.close()
        return
    if request.param == "hbase":
        # Event data over the HBase REST gateway protocol (schema CRUD,
        # base64 row/cell JSON, stateful scanners) — the reference's
        # "event store of record" role with wire parity against the
        # `hbase rest` service (hbase_mock.py); metadata+models on sqlite.
        from hbase_mock import build_hbase_app
        from server_utils import ServerThread

        with ServerThread(build_hbase_app()) as srv:
            env = {
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "HB",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
                "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
                "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "hbmeta.sqlite"),
                "PIO_STORAGE_SOURCES_HB_TYPE": "HBASE",
                "PIO_STORAGE_SOURCES_HB_HOSTS": "127.0.0.1",
                "PIO_STORAGE_SOURCES_HB_PORTS": str(srv.port),
            }
            s = Storage(env)
            yield s
            s.close()
        return
    if request.param == "elasticsearch":
        # Metadata + events on an Elasticsearch-compatible store over the
        # REAL ES REST protocol (index/doc CRUD, _bulk NDJSON, _search
        # DSL with search_after, the ESSequences _version trick) — the
        # reference's ES assembly scope; models ride sqlite.
        from es_mock import build_es_app
        from server_utils import ServerThread

        with ServerThread(build_es_app()) as srv:
            env = {
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "ES",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "ES",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
                "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
                "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "esmeta.sqlite"),
                "PIO_STORAGE_SOURCES_ES_TYPE": "ELASTICSEARCH",
                "PIO_STORAGE_SOURCES_ES_HOSTS": "127.0.0.1",
                "PIO_STORAGE_SOURCES_ES_PORTS": str(srv.port),
            }
            s = Storage(env)
            yield s
            s.close()
        return
    if request.param == "s3":
        # Model blobs on an S3-compatible object store over the REAL S3
        # REST protocol: the in-process server INDEPENDENTLY re-derives
        # every request's AWS SigV4 signature and 403s mismatches, so
        # this proves wire-level protocol parity (reference:
        # storage/s3/.../S3Models.scala — model-data only; metadata and
        # events ride sqlite, like the reference's mixed deployments).
        from s3_mock import build_s3_app
        from server_utils import ServerThread

        with ServerThread(build_s3_app("AKPIOTEST", "s3cr3t")) as srv:
            env = {
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "OBJ",
                "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
                "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "s3meta.sqlite"),
                "PIO_STORAGE_SOURCES_OBJ_TYPE": "S3",
                "PIO_STORAGE_SOURCES_OBJ_ENDPOINT": f"http://127.0.0.1:{srv.port}",
                "PIO_STORAGE_SOURCES_OBJ_BUCKET": "pio-models",
                "PIO_STORAGE_SOURCES_OBJ_ACCESS_KEY": "AKPIOTEST",
                "PIO_STORAGE_SOURCES_OBJ_SECRET_KEY": "s3cr3t",
            }
            s = Storage(env)
            yield s
            s.close()
        return
    if request.param == "http":
        # Client-server: a storage server (sqlite-backed) in a thread,
        # the Storage under test speaking TYPE=HTTP to it — the network
        # backend runs the IDENTICAL contract as the embedded ones
        # (reference: LEventsSpec against HBase/JDBC/ES).
        from incubator_predictionio_tpu.data.api.storage_server import build_app
        from server_utils import ServerThread

        backing = _make_storage("sqlite", tmp_path)
        with ServerThread(build_app(backing)) as srv:
            env = {
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "NET",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "NET",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "NET",
                "PIO_STORAGE_SOURCES_NET_TYPE": "HTTP",
                "PIO_STORAGE_SOURCES_NET_HOSTS": "127.0.0.1",
                "PIO_STORAGE_SOURCES_NET_PORTS": str(srv.port),
            }
            s = Storage(env)
            yield s
            s.close()
            backing.close()
        return
    s = _make_storage(request.param, tmp_path)
    yield s
    s.close()


def _ts(i):
    return dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc) + dt.timedelta(minutes=i)


def test_apps_crud(storage):
    apps = storage.get_meta_data_apps()
    app_id = apps.insert(App(0, "myapp", "desc"))
    assert app_id
    assert apps.get(app_id).name == "myapp"
    assert apps.get_by_name("myapp").id == app_id
    assert apps.insert(App(0, "myapp")) is None  # duplicate name
    apps.update(App(app_id, "myapp", "newdesc"))
    assert apps.get(app_id).description == "newdesc"
    assert len(apps.get_all()) == 1
    apps.delete(app_id)
    assert apps.get(app_id) is None


def test_access_keys_crud(storage):
    keys = storage.get_meta_data_access_keys()
    k = keys.insert(AccessKey("", appid=3, events=("rate",)))
    assert k
    got = keys.get(k)
    assert got.appid == 3 and tuple(got.events) == ("rate",)
    assert keys.get_by_appid(3)[0].key == k
    keys.delete(k)
    assert keys.get(k) is None


def test_channels_crud(storage):
    channels = storage.get_meta_data_channels()
    cid = channels.insert(Channel(0, "ch1", appid=7))
    assert cid
    assert channels.insert(Channel(0, "bad name!", appid=7)) is None
    assert channels.get(cid).name == "ch1"
    assert [c.id for c in channels.get_by_appid(7)] == [cid]
    channels.delete(cid)
    assert channels.get(cid) is None


def test_engine_instances(storage):
    dao = storage.get_meta_data_engine_instances()
    i1 = EngineInstance(
        id="", status="RUNNING", start_time=_ts(0), end_time=None,
        engine_id="e", engine_version="1", engine_variant="default",
        engine_factory="my.Factory",
    )
    iid = dao.insert(i1)
    assert dao.get(iid).status == "RUNNING"
    done = dao.get(iid).with_status("COMPLETED", _ts(1))
    dao.update(done)
    assert dao.get_latest_completed("e", "1", "default").id == iid
    # a later completed run wins
    iid2 = dao.insert(
        EngineInstance(
            id="", status="COMPLETED", start_time=_ts(5), end_time=_ts(6),
            engine_id="e", engine_version="1", engine_variant="default",
            engine_factory="my.Factory",
        )
    )
    assert dao.get_latest_completed("e", "1", "default").id == iid2
    assert len(dao.get_completed("e", "1", "default")) == 2
    dao.delete(iid2)
    assert dao.get(iid2) is None


def test_evaluation_instances(storage):
    dao = storage.get_meta_data_evaluation_instances()
    iid = dao.insert(
        EvaluationInstance(
            id="", status="EVALCOMPLETED", start_time=_ts(0), end_time=_ts(1),
            evaluation_class="my.Eval", engine_params_generator_class="my.Gen",
            evaluator_results="mse=0.5",
        )
    )
    assert dao.get(iid).evaluator_results == "mse=0.5"
    assert dao.get_completed()[0].id == iid


def test_models_blob(storage):
    models = storage.get_model_data_models()
    models.insert(Model("m1", b"\x00\x01binary"))
    assert models.get("m1").models == b"\x00\x01binary"
    models.delete("m1")
    assert models.get("m1") is None


def test_levents_crud_and_find(storage):
    le = storage.get_l_events()
    assert le.init(1)
    events = [
        Event("rate", "user", "u1", "item", "i1", DataMap({"rating": 3.0}), _ts(0)),
        Event("rate", "user", "u1", "item", "i2", DataMap({"rating": 5.0}), _ts(1)),
        Event("buy", "user", "u2", "item", "i1", DataMap(), _ts(2)),
    ]
    ids = [le.insert(e, 1) for e in events]
    assert len(set(ids)) == 3
    got = le.get(ids[0], 1)
    assert got.properties.require("rating") == 3.0
    assert got.event_id == ids[0]

    assert len(list(le.find(1))) == 3
    assert len(list(le.find(1, event_names=["rate"]))) == 2
    assert len(list(le.find(1, entity_id="u1"))) == 2
    assert len(list(le.find(1, target_entity_id="i1"))) == 2
    assert len(list(le.find(1, start_time=_ts(1)))) == 2
    assert len(list(le.find(1, until_time=_ts(1)))) == 1
    assert len(list(le.find(1, limit=2))) == 2
    rev = list(le.find(1, reversed_order=True))
    assert rev[0].event == "buy"

    assert le.delete(ids[2], 1)
    assert not le.delete(ids[2], 1)
    assert len(list(le.find(1))) == 2
    # channels are isolated
    le.init(1, 5)
    le.insert(events[0], 1, 5)
    assert len(list(le.find(1))) == 2
    assert len(list(le.find(1, channel_id=5))) == 1
    assert le.remove(1, 5)


def test_levents_reinsert_after_delete(storage):
    """Delete only hides what came before it: re-inserting the same
    eventId afterwards is visible on every backend (upsert parity)."""
    le = storage.get_l_events()
    le.init(9)
    e = Event("rate", "user", "u1", "item", "i1", DataMap({"rating": 4.0}),
              _ts(0), event_id="re-1")
    le.insert(e, 9)
    assert le.delete("re-1", 9)
    assert le.get("re-1", 9) is None
    le.insert(e, 9)
    got = le.get("re-1", 9)
    assert got is not None and got.properties.require("rating") == 4.0
    assert len(list(le.find(9))) == 1


def test_levents_delete_batch(storage):
    le = storage.get_l_events()
    le.init(10)
    ids = [le.insert(
        Event("view", "user", f"u{n}", "item", "i", DataMap(), _ts(n)), 10)
        for n in range(6)]
    out = le.delete_batch(ids[:4] + ["nope"], 10)
    assert out == [True] * 4 + [False]
    assert len(list(le.find(10))) == 2


def test_levents_reversed_tie_order(storage):
    """Equal-timestamp events come back in insertion order under
    reversed_order (stable descending) on every backend."""
    le = storage.get_l_events()
    le.init(11)
    for n in range(4):
        le.insert(Event("e", "u", f"u{n}", None, None, DataMap(), _ts(0)), 11)
    order = [e.entity_id for e in le.find(11, reversed_order=True)]
    assert order == ["u0", "u1", "u2", "u3"]


def test_levents_upsert_moves_to_tie_end(storage):
    """Re-inserting an existing eventId moves it to the END of its
    equal-timestamp tie group — identical on every backend (the JSONL log
    re-appends; SQLite REPLACE re-inserts; memory pops+appends)."""
    le = storage.get_l_events()
    le.init(12)
    le.insert(Event("e", "u", "a", None, None, DataMap({"v": 1}), _ts(0),
                    event_id="ua"), 12)
    le.insert(Event("e", "u", "b", None, None, DataMap(), _ts(0),
                    event_id="ub"), 12)
    le.insert(Event("e", "u", "a", None, None, DataMap({"v": 2}), _ts(0),
                    event_id="ua"), 12)  # upsert
    got = list(le.find(12))
    assert [e.entity_id for e in got] == ["b", "a"]
    assert got[1].properties.require("v") == 2
    assert len(got) == 2


def test_aggregate_properties(storage):
    le = storage.get_l_events()
    le.init(2)
    le.insert(Event("$set", "item", "i1", properties=DataMap({"a": 1, "b": 2}), event_time=_ts(0)), 2)
    le.insert(Event("$set", "item", "i1", properties=DataMap({"b": 3, "c": 4}), event_time=_ts(1)), 2)
    le.insert(Event("$unset", "item", "i1", properties=DataMap({"a": 0}), event_time=_ts(2)), 2)
    le.insert(Event("$set", "item", "i2", properties=DataMap({"a": 9}), event_time=_ts(3)), 2)
    le.insert(Event("$delete", "item", "i3", event_time=_ts(4)), 2)
    le.insert(Event("$set", "item", "i3", properties=DataMap({"z": 1}), event_time=_ts(3)), 2)

    props = le.aggregate_properties(2, "item")
    assert set(props) == {"i1", "i2"}  # i3 deleted after its $set
    assert props["i1"] == {"b": 3, "c": 4}
    assert props["i1"].first_updated == _ts(0)
    assert props["i1"].last_updated == _ts(2)
    # required-field filter
    assert set(le.aggregate_properties(2, "item", required=["c"])) == {"i1"}


def test_pevents_write_and_find(storage):
    pe = storage.get_p_events()
    events = [
        Event("view", "user", f"u{i}", "item", f"i{i % 3}", DataMap(), _ts(i))
        for i in range(10)
    ]
    pe.write(events, 9)
    assert len(list(pe.find(9))) == 10
    assert len(list(pe.find(9, target_entity_id="i0"))) == 4


def test_verify_all_data_objects(storage):
    assert storage.verify_all_data_objects() == []


def test_insert_without_init_autocreates(storage):
    """Cross-backend contract: insert before init must work (review fix)."""
    le = storage.get_l_events()
    eid = le.insert(Event("view", "user", "u1", event_time=_ts(0)), 42)
    assert le.get(eid, 42) is not None
    assert not le.delete("nonexistent", 4242)  # missing table → False, no raise


@pytest.mark.parametrize(
    "backend", ["jsonl", "sqlite", "pgsql", "mysql", "elasticsearch"])
def test_fast_aggregate_matches_generic(tmp_path, backend):
    """Every fast aggregate_properties path — JSONL columnar replay,
    SQLite raw-row replay, PG/MySQL raw-row replay, ES raw-hit replay —
    must be result-identical (keys, values, first/last times) to the
    generic Event-replay over find() — fuzzed with ties, windows,
    tombstones, mixed entity types, and the required filter."""
    import contextlib

    from incubator_predictionio_tpu.data.storage.base import (
        StorageClientConfig,
    )

    with contextlib.ExitStack() as stack:
        if backend == "jsonl":
            from incubator_predictionio_tpu.data.storage.jsonl import (
                JSONLEvents,
            )

            le = JSONLEvents(str(tmp_path))
        elif backend == "sqlite":
            from incubator_predictionio_tpu.data.storage.sqlite import (
                SQLiteClient,
            )

            le = SQLiteClient(StorageClientConfig(properties={
                "PATH": str(tmp_path / "agg.sqlite")})).l_events()
        elif backend == "pgsql":
            from pg_mock import MockPGServer

            from incubator_predictionio_tpu.data.storage.postgres import (
                PGClient,
            )

            srv = stack.enter_context(
                MockPGServer(user="pio", password="piosecret"))
            client = PGClient(StorageClientConfig(properties={
                "HOST": "127.0.0.1", "PORT": str(srv.port),
                "USERNAME": "pio", "PASSWORD": "piosecret"}))
            stack.callback(client.close)
            le = client.l_events()
        elif backend == "mysql":
            from mysql_mock import MockMySQLServer

            from incubator_predictionio_tpu.data.storage.mysql import (
                MySQLClient,
            )

            srv = stack.enter_context(
                MockMySQLServer(user="pio", password="piosecret"))
            client = MySQLClient(StorageClientConfig(properties={
                "HOST": "127.0.0.1", "PORT": str(srv.port),
                "USERNAME": "pio", "PASSWORD": "piosecret"}))
            stack.callback(client.close)
            le = client.l_events()
        else:
            from es_mock import build_es_app
            from server_utils import ServerThread

            from incubator_predictionio_tpu.data.storage.elasticsearch import (
                ESClient,
            )

            srv = stack.enter_context(ServerThread(build_es_app()))
            client = ESClient(StorageClientConfig(properties={
                "HOSTS": "127.0.0.1", "PORTS": str(srv.port)}))
            stack.callback(client.close)
            le = client.l_events()
        _fuzz_aggregate_identity(le, raw_lines=backend == "jsonl")


def _fuzz_aggregate_identity(le, raw_lines=False):
    import random

    from incubator_predictionio_tpu.data.storage.base import (
        aggregate_property_events,
    )
    rng = random.Random(4)
    base_t = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    evs = []
    for _ in range(3000):
        kind = rng.choices(["$set", "$unset", "$delete", "view"],
                           [0.5, 0.2, 0.1, 0.2])[0]
        if kind == "$unset":
            props = {f"a{rng.randrange(4)}": rng.randrange(9)
                     for _ in range(rng.randrange(1, 3))}
        elif kind == "$delete":
            props = {}
        else:
            props = {f"a{rng.randrange(4)}": rng.randrange(9)
                     for _ in range(rng.randrange(0, 3))}
        evs.append(Event(
            event=kind, entity_type=rng.choice(["user", "item"]),
            entity_id=str(rng.randrange(120)), properties=DataMap(props),
            event_time=base_t + dt.timedelta(
                seconds=rng.randrange(0, 400))))  # many ties
    le.insert_batch(evs, 1)
    ids = [e.event_id for e in le.find(1, limit=40)]
    le.delete_batch([i for i in ids if i], 1)

    def generic(entity_type, st=None, ut=None, req=None):
        return aggregate_property_events(
            le.find(1, None, st, ut, entity_type, None,
                    ["$set", "$unset", "$delete"]), required=req)

    cases = [
        ("user", None, None, None),
        ("item", None, None, None),
        ("user", base_t + dt.timedelta(seconds=80),
         base_t + dt.timedelta(seconds=300), None),
        ("user", None, None, ["a0", "a1"]),
        ("ghost", None, None, None),
    ]
    for et, st, ut, req in cases:
        g = generic(et, st, ut, req)
        c = le.aggregate_properties(1, et, None, st, ut, req)
        assert set(g) == set(c), et
        for k in g:
            assert g[k].to_dict() == c[k].to_dict(), k
            assert g[k].first_updated == c[k].first_updated, k
            assert g[k].last_updated == c[k].last_updated, k
    if raw_lines:
        _fuzz_aggregate_raw_lines(le, base_t)


def _raw_event(event, entity_type, entity_id, t=None, props=None):
    """One canonical line as an external writer may leave it: the
    ``eventTime`` and the ``properties`` only where given."""
    import json

    rec = {"event": event, "entityType": entity_type, "entityId": entity_id}
    if t is not None:
        rec["eventTime"] = t.isoformat(timespec="milliseconds").replace(
            "+00:00", "Z")
    if props is not None:
        rec["properties"] = props
    return json.dumps(rec).encode() + b"\n"


def _fuzz_aggregate_raw_lines(le, base_t):
    """The JSONL replay on lines the Event path cannot write: an
    ``$unset`` before any ``$set``, an ``$unset`` and a ``$set`` after a
    ``$delete``, a ``$delete`` and a ``$set`` at one time (both orders), an
    entity whose last event is a ``$delete``, a ``$set`` without
    ``properties``, events without an ``eventTime`` (they come last, in file
    order, at the read's "now"), and ids shared by two entity types —
    written out, then fuzzed; each read equals the generic replay."""
    import random

    from incubator_predictionio_tpu.data.storage.base import (
        aggregate_property_events,
    )

    def t(s):
        return base_t + dt.timedelta(seconds=s)

    lines = [
        _raw_event("$unset", "item", "unset-first", t(1), {"a": None}),
        _raw_event("$set", "item", "unset-first", t(2), {"a": 1, "b": 2}),
        _raw_event("$set", "item", "unset-first", t(3), {"c": 3}),
        _raw_event("$set", "item", "after-delete", t(1), {"a": 1}),
        _raw_event("$delete", "item", "after-delete", t(2)),
        _raw_event("$unset", "item", "after-delete", t(3), {"a": None}),
        _raw_event("$set", "item", "after-delete", t(4), {"b": 2}),
        _raw_event("$unset", "item", "after-delete", t(5), {"b": None}),
        _raw_event("$set", "item", "after-delete", t(6), {"c": 1}),
        _raw_event("$set", "item", "tie-gone", t(5), {"a": 1}),
        _raw_event("$delete", "item", "tie-gone", t(5)),
        _raw_event("$set", "item", "tie-back", t(1), {"z": 9}),
        _raw_event("$delete", "item", "tie-back", t(5)),
        _raw_event("$set", "item", "tie-back", t(5), {"a": 1}),
        _raw_event("$set", "item", "ends-deleted", t(1), {"a": 1}),
        _raw_event("$set", "item", "ends-deleted", t(2), {"b": 1}),
        _raw_event("$delete", "item", "ends-deleted", t(3)),
        _raw_event("$set", "item", "bare", t(1)),
        _raw_event("$set", "item", "bare-later", t(1), {"a": 1}),
        _raw_event("$set", "item", "bare-later", t(2)),
        _raw_event("$set", "item", "no-time", None, {"a": 1}),
        _raw_event("$set", "item", "mixed-time", t(1), {"a": 1}),
        _raw_event("$unset", "item", "mixed-time", None, {"a": None}),
        _raw_event("$set", "item", "mixed-time", None, {"b": 2}),
        _raw_event("$set", "user", "shared", t(1), {"u": 1}),
        _raw_event("$set", "item", "shared", t(2), {"i": 1}),
        _raw_event("$delete", "item", "shared", t(3)),
        _raw_event("$set", "user", "shared-both", t(1), {"u": 1}),
        _raw_event("$set", "item", "shared-both", t(1), {"i": 2}),
    ]
    le.insert_canonical_lines(b"".join(lines), 3)
    before = dt.datetime.now(dt.timezone.utc)
    got = le.aggregate_properties(3, "item")
    after = dt.datetime.now(dt.timezone.utc)
    want = {
        "unset-first": ({"a": 1, "b": 2, "c": 3}, t(2), t(3)),
        "after-delete": ({"c": 1}, t(4), t(6)),
        "tie-back": ({"a": 1}, t(5), t(5)),
        "bare": ({}, t(1), t(1)),
        "bare-later": ({"a": 1}, t(1), t(2)),
        "shared-both": ({"i": 2}, t(1), t(1)),
    }
    assert set(got) == set(want) | {"no-time", "mixed-time"}
    for k, (props, first, last) in want.items():
        assert got[k] == props, k
        assert (got[k].first_updated, got[k].last_updated) == (first, last)
    assert got["no-time"] == {"a": 1}
    assert before <= got["no-time"].first_updated <= after
    assert got["no-time"].first_updated == got["no-time"].last_updated
    assert got["mixed-time"] == {"b": 2}
    assert got["mixed-time"].first_updated == t(1)
    assert before <= got["mixed-time"].last_updated <= after
    assert dict(le.aggregate_properties(3, "user")) == {
        "shared": {"u": 1}, "shared-both": {"u": 1}}

    # the same forms at random: few ids of two types, many ties
    rng = random.Random(43)
    lines = []
    for _ in range(2000):
        kind = rng.choices(["$set", "$unset", "$delete"], [0.55, 0.3, 0.15])[0]
        when = None if rng.random() < 0.05 else t(rng.randrange(60))
        props = None  # a $set without properties 1 in 10 times
        if kind == "$unset" or kind == "$set" and rng.random() < 0.9:
            props = {f"a{rng.randrange(4)}": rng.randrange(9)
                     for _ in range(rng.randrange(kind == "$unset", 3))}
        lines.append(_raw_event(kind, rng.choice(["user", "item"]),
                                str(rng.randrange(50)), when, props))
    le.insert_canonical_lines(b"".join(lines), 4)
    for et, req in (("user", None), ("item", None), ("item", ["a1"])):
        before = dt.datetime.now(dt.timezone.utc)
        g = aggregate_property_events(
            le.find(4, None, None, None, et, None,
                    ["$set", "$unset", "$delete"]), required=req)
        c = le.aggregate_properties(4, et, required=req)
        assert set(g) == set(c), et
        for k in g:
            assert g[k].to_dict() == c[k].to_dict(), k
            for a, b in ((g[k].first_updated, c[k].first_updated),
                         (g[k].last_updated, c[k].last_updated)):
                # a time read as "now" by each: after ``before``
                assert a == b or (a >= before and b >= before), k


#: ``PropertyMap({"categories": ["Books"]}, T, T + 1 s)`` pickled (protocol 4)
#: by the tree before the replay's maps kept their times as microseconds
_PROPERTY_MAP_PICKLE = (
    b"\x80\x04\x95\xfd\x00\x00\x00\x00\x00\x00\x00\x8c/incubator_predictionio"
    b"_tpu.data.storage.datamap\x94\x8c\x0bPropertyMap\x94\x93\x94)\x81\x94N}"
    b"\x94(\x8c\rfirst_updated\x94\x8c\x08datetime\x94\x8c\x08datetime\x94"
    b"\x93\x94C\n\x07\xde\x07\x01\x00\x00\x00\x00\x00\x00\x94h\x06\x8c\x08"
    b"timezone\x94\x93\x94h\x06\x8c\ttimedelta\x94\x93\x94K\x00K\x00K\x00\x87"
    b"\x94R\x94\x85\x94R\x94\x86\x94R\x94\x8c\x0clast_updated\x94h\x08C\n\x07"
    b"\xde\x07\x01\x00\x00\x01\x00\x00\x00\x94h\x11\x86\x94R\x94\x8c\x07"
    b"_fields\x94}\x94\x8c\ncategories\x94]\x94\x8c\x05Books\x94asu\x86\x94b.")


def test_a_replayed_property_map_is_one_built_the_public_way(tmp_path):
    """The JSONL replay's maps own their parsed dict and keep their times
    as microseconds until read; each still equals, hashes, reprs and
    pickles (byte for byte) as ``PropertyMap(dict(m), first, last)``, goes
    through the storage server's encoder as one, and reads a pickle of
    the class as it was."""
    import json
    import pickle

    from incubator_predictionio_tpu.data.api import storage_server
    from incubator_predictionio_tpu.data.storage import http_backend
    from incubator_predictionio_tpu.data.storage.jsonl import JSONLEvents

    le = JSONLEvents(str(tmp_path))
    t0 = dt.datetime(2014, 7, 1, tzinfo=dt.timezone.utc)
    le.insert_batch([
        Event("$set", "item", "one", properties=DataMap(
            {"categories": ["Books"], "price": 9.5}), event_time=t0),
        Event("$set", "item", "two", properties=DataMap({"a": 1}),
              event_time=t0),
        Event("$set", "item", "two", properties=DataMap({"b": [1, 2]}),
              event_time=t0 + dt.timedelta(milliseconds=1)),
        Event("$unset", "item", "two", properties=DataMap({"a": None}),
              event_time=t0 + dt.timedelta(days=400, milliseconds=7)),
    ], 1)
    got = le.aggregate_properties(1, "item")
    assert set(got) == {"one", "two"}
    blobs = {k: pickle.dumps(m, protocol=4) for k, m in got.items()}
    for k, m in got.items():
        public = PropertyMap(dict(m), m.first_updated, m.last_updated)
        assert type(m) is PropertyMap
        assert m == public and hash(m) == hash(public)
        assert repr(m) == repr(public)
        assert blobs[k] == pickle.dumps(public, protocol=4)
        back = pickle.loads(blobs[k])
        assert back == public and repr(back) == repr(public)
        assert (back.first_updated, back.last_updated) == (
            public.first_updated, public.last_updated)
    assert got["two"] == {"b": [1, 2]}
    assert got["two"].first_updated == t0
    assert got["two"].last_updated == t0 + dt.timedelta(days=400,
                                                        milliseconds=7)

    wire = json.loads(json.dumps(
        storage_server._encode_result("l_events", got)))
    assert wire == {k: http_backend.property_map_to_json(
        PropertyMap(dict(m), m.first_updated, m.last_updated))
        for k, m in got.items()}
    for k, m in got.items():
        back = http_backend.property_map_from_json(wire[k])
        assert repr(back) == repr(m)

    old = pickle.loads(_PROPERTY_MAP_PICKLE)
    assert old == {"categories": ["Books"]}
    assert (old.first_updated, old.last_updated) == (
        t0, t0 + dt.timedelta(seconds=1))
    assert pickle.dumps(old, protocol=4) == _PROPERTY_MAP_PICKLE


def test_hbase_filter_pushdown_only_transfers_matches(tmp_path):
    """Filtered finds must evaluate server-side (Stargate filter spec):
    only matching rows cross the wire — the reference's HBEventsUtil
    filter-list behavior — while results stay identical to the generic
    client-side semantics (event_matches backstop)."""
    from hbase_mock import build_hbase_app
    from server_utils import ServerThread

    from incubator_predictionio_tpu.data.storage.base import (
        StorageClientConfig,
    )
    from incubator_predictionio_tpu.data.storage.hbase import HBaseClient

    app = build_hbase_app()
    with ServerThread(app) as srv:
        le = HBaseClient(StorageClientConfig(properties={
            "HOSTS": "127.0.0.1", "PORTS": str(srv.port)})).l_events()
        evs = []
        for k in range(60):
            evs.append(Event("view", "user", str(k % 7), "item",
                             str(k % 5), DataMap(), _ts(k)))
        for k in range(8):
            evs.append(Event("$set", "item", f"i{k}",
                             properties=DataMap({"a": k}),
                             event_time=_ts(100 + k)))
        le.insert_batch(evs, 77)

        app["rows_served"] = 0
        got = list(le.find(77, entity_type="item", event_names=["$set"]))
        assert len(got) == 8
        assert app["rows_served"] == 8  # 60 view rows never crossed

        app["rows_served"] = 0
        got = list(le.find(77, target_entity_id="3", event_names=["view"]))
        assert {e.target_entity_id for e in got} == {"3"}
        assert app["rows_served"] == len(got) == 12

        # multi-name OR + entity filter compose server-side
        app["rows_served"] = 0
        got = list(le.find(77, entity_type="user", entity_id="2",
                           event_names=["view", "buy"]))
        assert app["rows_served"] == len(got) > 0

        # empty event_names: no scanner is even opened
        app["rows_served"] = 0
        assert list(le.find(77, event_names=[])) == []
        assert app["rows_served"] == 0

        # aggregate rides the same pushdown (only $set/$unset/$delete)
        app["rows_served"] = 0
        props = le.aggregate_properties(77, "item")
        assert set(props) == {f"i{k}" for k in range(8)}
        assert app["rows_served"] == 8

        # Rows written BEFORE the filterable cells existed (json-only
        # format) must stay visible to filtered finds: ifMissing=False
        # passes them server-side for the client backstop to judge —
        # not silently drop them (review finding).
        import base64 as _b64mod
        import json as _json

        legacy = Event("$set", "item", "legacy0",
                       properties=DataMap({"a": 99}),
                       event_time=_ts(300), event_id="legacyev")
        key = le._data_key(le._time_us(legacy.event_time), 1)
        tbl = le._table(77, None)
        app["tables"][tbl][key] = {
            "e:json": _json.dumps(legacy.to_json()).encode()}
        got = list(le.find(77, entity_type="item", event_names=["$set"]))
        assert "legacy0" in {e.entity_id for e in got}
        props = le.aggregate_properties(77, "item")
        assert props["legacy0"]["a"] == 99


def test_empty_event_names_matches_nothing(storage):
    """event_names=[] must match nothing on every backend (review fix)."""
    le = storage.get_l_events()
    le.init(43)
    le.insert(Event("view", "user", "u1", event_time=_ts(0)), 43)
    assert list(le.find(43, event_names=[])) == []
    assert len(list(le.find(43, event_names=None))) == 1


def test_namespace_isolation(tmp_path):
    """Two configs with different _NAMEs must not collide (review fix)."""
    def env(name):
        return {
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "S",
            "PIO_STORAGE_REPOSITORIES_METADATA_NAME": name,
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "S",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": name + "_ev",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "S",
            "PIO_STORAGE_SOURCES_S_TYPE": "SQLITE",
            "PIO_STORAGE_SOURCES_S_PATH": str(tmp_path / "shared.sqlite"),
        }

    s1, s2 = Storage(env("ns_a")), Storage(env("ns_b"))
    s1.get_meta_data_apps().insert(App(0, "only-in-a"))
    assert s2.get_meta_data_apps().get_by_name("only-in-a") is None
    s1.get_l_events().insert(Event("x", "u", "1", event_time=_ts(0)), 1)
    assert list(s2.get_l_events().find(1)) == []
    assert len(list(s1.get_l_events().find(1))) == 1
    s1.close()  # shared connection-per-Storage; close both
    s2.close()


def test_creation_time_roundtrip():
    """Export→import must preserve creationTime (review fix)."""
    e = Event.from_json(
        {"event": "x", "entityType": "u", "entityId": "1",
         "eventTime": "2024-01-01T00:00:00.000Z",
         "creationTime": "2024-01-01T00:00:01.000Z"}
    )
    assert e.to_json()["creationTime"] == "2024-01-01T00:00:01.000Z"


def test_non_string_json_fields_rejected():
    """Bad client types must raise EventValidationError, not crash (review fix)."""
    from incubator_predictionio_tpu.data.storage import EventValidationError
    import pytest as _pytest

    for bad in (
        {"event": 5, "entityType": "u", "entityId": "1"},
        {"event": "x", "entityType": ["u"], "entityId": "1"},
        {"event": "x", "entityType": "u", "entityId": "1", "eventTime": 12345},
        {"event": "x", "entityType": "u", "entityId": "1", "targetEntityType": 3,
         "targetEntityId": "4"},
    ):
        with _pytest.raises(EventValidationError):
            Event.from_json(bad)


def test_s3_signature_rejected_on_bad_secret(tmp_path):
    """A client signing with the wrong secret must be refused by the
    server's independent SigV4 verification (and surface as a storage
    error, not silent data loss)."""
    from s3_mock import build_s3_app
    from server_utils import ServerThread

    from incubator_predictionio_tpu.data.storage.s3 import (
        S3Client, S3StorageError,
    )
    from incubator_predictionio_tpu.data.storage.base import StorageClientConfig

    with ServerThread(build_s3_app("AKPIOTEST", "rightsecret")) as srv:
        client = S3Client(StorageClientConfig(properties={
            "ENDPOINT": f"http://127.0.0.1:{srv.port}",
            "BUCKET": "b", "ACCESS_KEY": "AKPIOTEST",
            "SECRET_KEY": "WRONGsecret",
        }))
        models = client.models()
        with pytest.raises(S3StorageError):
            models.insert(Model("m1", b"blob"))


def test_s3_source_serves_models_only(tmp_path):
    from s3_mock import build_s3_app
    from server_utils import ServerThread

    from incubator_predictionio_tpu.data.storage.s3 import S3Client
    from incubator_predictionio_tpu.data.storage.base import StorageClientConfig

    with ServerThread(build_s3_app("AK", "sk")) as srv:
        client = S3Client(StorageClientConfig(properties={
            "ENDPOINT": f"http://127.0.0.1:{srv.port}",
            "BUCKET": "b", "ACCESS_KEY": "AK", "SECRET_KEY": "sk",
        }))
        with pytest.raises(NotImplementedError):
            client.l_events()
        with pytest.raises(NotImplementedError):
            client.apps()


def test_s3_key_with_reserved_characters(tmp_path):
    """Model ids with spaces / reserved chars must sign correctly (the
    canonical URI is the as-sent percent-encoded path; double-encoding
    breaks real S3 stores)."""
    from s3_mock import build_s3_app
    from server_utils import ServerThread

    from incubator_predictionio_tpu.data.storage.s3 import S3Client
    from incubator_predictionio_tpu.data.storage.base import StorageClientConfig

    with ServerThread(build_s3_app("AK", "sk")) as srv:
        client = S3Client(StorageClientConfig(properties={
            "ENDPOINT": f"http://127.0.0.1:{srv.port}",
            "BUCKET": "b", "ACCESS_KEY": "AK", "SECRET_KEY": "sk",
        }))
        models = client.models("name space+ns")
        models.insert(Model("id with space+plus", b"\x01blob"))
        assert models.get("id with space+plus").models == b"\x01blob"
        models.delete("id with space+plus")
        assert models.get("id with space+plus") is None


def test_pgsql_scram_rejects_wrong_password():
    """The server verifies the SCRAM proof; a wrong password must fail
    authentication, not silently connect."""
    from pg_mock import MockPGServer

    from incubator_predictionio_tpu.data.storage.pgwire import (
        PGConnection, PGError,
    )

    with MockPGServer(user="pio", password="rightpw") as srv:
        with pytest.raises(PGError) as e:
            PGConnection("127.0.0.1", srv.port, "pio", "wrongpw", "pio")
        assert "authentication" in str(e.value).lower()


def test_pgsql_scram_server_signature_verified():
    """The client verifies the server's SCRAM signature (mutual auth):
    a server that doesn't know the password is rejected client-side."""
    import base64 as b64
    import struct as st

    from pg_mock import MockPGServer, _Handler

    from incubator_predictionio_tpu.data.storage.pgwire import (
        PGConnection, PGProtocolError,
    )

    class LyingHandler(_Handler):
        def _send(self, t, payload):
            if t == b"R" and len(payload) > 4 and \
                    st.unpack("!I", payload[:4])[0] == 12:
                payload = st.pack("!I", 12) + b"v=" + b64.b64encode(b"x" * 32)
            super()._send(t, payload)

    srv = MockPGServer(user="pio", password="pw")
    srv.RequestHandlerClass = LyingHandler
    with srv:
        with pytest.raises(PGProtocolError, match="signature"):
            PGConnection("127.0.0.1", srv.port, "pio", "pw", "pio")


def test_hdfs_key_with_reserved_characters(tmp_path):
    """WebHDFS paths with spaces / reserved chars must survive the
    NameNode→DataNode redirect without double-decoding."""
    from hdfs_mock import build_hdfs_app
    from server_utils import ServerThread

    from incubator_predictionio_tpu.data.storage.hdfs import HDFSClient
    from incubator_predictionio_tpu.data.storage.base import StorageClientConfig

    with ServerThread(build_hdfs_app()) as srv:
        client = HDFSClient(StorageClientConfig(properties={
            "HOSTS": "127.0.0.1", "PORTS": str(srv.port),
            "PATH": "/pio/models",
        }))
        models = client.models("name space+ns")
        models.insert(Model("id with space+plus", b"\x02blob"))
        assert models.get("id with space+plus").models == b"\x02blob"
        models.delete("id with space+plus")
        assert models.get("id with space+plus") is None


def test_hbase_rpc_pushdown_multiregion_and_reversed(tmp_path):
    """The native-RPC transport: filter protos evaluate server-side
    (only matches cross the wire), rows route across a PRE-SPLIT
    table's regions via hbase:meta, and reversed finds stream through
    the native reversed scanner with the contract order preserved
    (time DESC, ties in insertion ASC order)."""
    from hbase_rpc_mock import MockHBaseRpcServer

    from incubator_predictionio_tpu.data.storage.base import (
        StorageClientConfig,
    )
    from incubator_predictionio_tpu.data.storage.event import event_time_us
    from incubator_predictionio_tpu.data.storage.hbase import (
        HBaseClient, HBLEvents,
    )

    split = HBLEvents._data_key(event_time_us(_ts(30)), 0)
    with MockHBaseRpcServer(
            split_keys={"pio_eventdata_77": [split]}) as srv:
        client = HBaseClient(StorageClientConfig(properties={
            "HOSTS": "127.0.0.1", "PORTS": str(srv.port),
            "PROTOCOL": "rpc"}))
        le = client.l_events()
        evs = []
        for k in range(60):
            evs.append(Event("view", "user", str(k % 7), "item",
                             str(k % 5), DataMap(), _ts(k)))
        for k in range(8):
            evs.append(Event("$set", "item", f"i{k}",
                             properties=DataMap({"a": k}),
                             event_time=_ts(100 + k)))
        le.insert_batch(evs, 77)

        # the split actually distributed data rows over BOTH regions
        t = srv.tables["pio_eventdata_77"]
        data_counts = [
            sum(1 for k in t.region_rows(name) if k.startswith(b"t:"))
            for _s, _e, name in t.regions]
        assert all(c > 0 for c in data_counts), data_counts

        # unfiltered find crosses the region boundary in time order
        got = list(le.find(77))
        assert len(got) == 68
        times = [e.event_time for e in got]
        assert times == sorted(times)

        # pushdown: only the 8 matching rows cross the wire
        srv.rows_served = 0
        got = list(le.find(77, entity_type="item", event_names=["$set"]))
        assert len(got) == 8
        assert srv.rows_served == 8

        srv.rows_served = 0
        got = list(le.find(77, target_entity_id="3", event_names=["view"]))
        assert {e.target_entity_id for e in got} == {"3"}
        assert srv.rows_served == len(got) == 12

        # reversed find: time DESC overall...
        got = list(le.find(77, reversed_order=True))
        times = [e.event_time for e in got]
        assert times == sorted(times, reverse=True)
        # ...and ties (same event_time) in INSERTION order — the native
        # reversed scanner yields seq DESC; the streaming tie-group flip
        # must restore the contract without materializing the window
        ties = [Event("tie", "u", str(i), properties=DataMap(),
                      event_time=_ts(200)) for i in range(5)]
        le.insert_batch(ties, 77)
        got = list(le.find(77, event_names=["tie"], reversed_order=True))
        assert [e.entity_id for e in got] == ["0", "1", "2", "3", "4"]

        # reversed + limit only transfers about a batch, not the window
        got = list(le.find(77, reversed_order=True, limit=3))
        assert len(got) == 3
        assert got[0].event_time == _ts(200)

        # small-batch scans page through next-calls: the per-region
        # loop must terminate on more_results_in_region (f8) — the mock
        # keeps more_results (f3) TRUE while the scan continues in the
        # neighboring region, like real servers
        rows = [k for k, _ in client._transport.scan(
            "pio_eventdata_77", b"t:", b"t;", batch=7)]
        assert len(rows) == 73 and rows == sorted(rows)
        rows_r = [k for k, _ in client._transport.scan(
            "pio_eventdata_77", b"t:", b"t;", batch=7, reverse=True)]
        assert rows_r == list(reversed(rows))
        client.close()


def test_hbase_rpc_region_retry_and_typed_errors(tmp_path):
    """Stale-region retries are transparent (no loss, no duplication);
    hard server faults surface as typed errors, never silent
    truncation or hangs."""
    import pytest as _pytest
    from hbase_rpc_mock import MockHBaseRpcServer

    from incubator_predictionio_tpu.data.storage.base import (
        StorageClientConfig,
    )
    from incubator_predictionio_tpu.data.storage.hbase import (
        HBaseClient, HBaseError,
    )
    from incubator_predictionio_tpu.data.storage.hbase_rpc import (
        HBaseRpcError,
    )

    with MockHBaseRpcServer() as srv:
        client = HBaseClient(StorageClientConfig(properties={
            "HOSTS": "127.0.0.1", "PORTS": str(srv.port),
            "PROTOCOL": "rpc"}))
        le = client.l_events()
        evs = [Event("view", "user", str(k), "item", str(k % 3),
                     DataMap(), _ts(k)) for k in range(40)]
        ids = le.insert_batch(evs, 5)
        assert len(ids) == 40

        # region "moves": every region answers NotServingRegionException
        # to its next data op — the client must relocate+retry and still
        # return every event exactly once
        srv.notserving_once("pio_eventdata_5")
        got = list(le.find(5))
        assert len(got) == 40
        assert len({e.event_id for e in got}) == 40

        # ...same for point ops
        srv.notserving_once("pio_eventdata_5")
        assert le.get(ids[7], 5) is not None

        # a mid-conversation UnknownScannerException is a typed error
        srv.fail_next("Scan",
                      "org.apache.hadoop.hbase.UnknownScannerException",
                      do_not_retry=True)
        with _pytest.raises(HBaseError, match="UnknownScanner"):
            list(le.find(5))

        # a malformed frame: the scan-level retry reconnects (the
        # poisoned connection is evicted) and the find still completes
        srv.garbage_frame_next()
        assert len(list(le.find(5))) == 40
        # ...and the replacement connection keeps working
        assert len(list(le.find(5))) == 40

        # non-region write faults propagate typed with the Java class
        # (an insert is a data+index Multi; a row delete is a Mutate)
        srv.fail_next("Multi",
                      "org.apache.hadoop.hbase.RegionTooBusyException")
        with _pytest.raises(HBaseError, match="RegionTooBusy"):
            le.insert(Event("view", "user", "x", "item", "y",
                            DataMap(), _ts(99)), 5)
        srv.fail_next("Mutate",
                      "org.apache.hadoop.hbase.RegionTooBusyException")
        with _pytest.raises(HBaseError, match="RegionTooBusy"):
            le.delete(ids[0], 5)
        client.close()


def test_self_cleaning_write_back_contract_10k(storage):
    """SelfCleaningDataSource write-back at 10k-event scale on EVERY
    backend (reference: core/.../core/SelfCleaningDataSource.scala run
    against each storage assembly): dedupe of re-imported events +
    property-stream compaction must preserve find/aggregate semantics
    through the real DAO round-trip."""
    from incubator_predictionio_tpu.controller.self_cleaning import (
        SelfCleaningDataSource,
    )
    from incubator_predictionio_tpu.workflow.context import WorkflowContext

    apps = storage.get_meta_data_apps()
    app_id = apps.insert(App(0, "cleanscale"))
    le = storage.get_l_events()
    le.init(app_id)
    t0 = dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc)

    def ts(n):
        return t0 + dt.timedelta(seconds=n)

    events = []
    # 8,000 unique views
    for n in range(8000):
        events.append(Event("view", "user", str(n % 400), "item",
                            str(n % 250), event_time=ts(n)))
    # 500 views re-imported 3x (the dedupe target): 1,500 rows → 500
    for n in range(500):
        for _ in range(3):
            events.append(Event("buy", "user", str(n % 400), "item",
                                str(n % 250), event_time=ts(n)))
    # 200 items × 5-event property streams: 1,000 rows → 200 snapshots
    for item in range(200):
        for step in range(5):
            events.append(Event(
                "$set", "item", f"i{item}",
                properties=DataMap({f"p{step}": step, "last": item}),
                event_time=ts(100_000 + item * 10 + step)))
    le.insert_batch(events, app_id)  # 10,500 total
    assert len(list(le.find(app_id))) == 10_500

    before_props = le.aggregate_properties(app_id, "item")

    ds = SelfCleaningDataSource()
    removed = ds.clean_persisted_data(
        WorkflowContext(storage=storage), "cleanscale")
    # 1,000 duplicate buys + (1,000 property rows - 200 snapshots)
    assert removed == 1_000 + 800

    remaining = list(le.find(app_id))
    assert len(remaining) == 8_000 + 500 + 200
    # dedupe kept exactly one copy per content key
    keys = [(e.event, e.entity_id, e.target_entity_id, e.event_time)
            for e in remaining if e.event == "buy"]
    assert len(keys) == len(set(keys)) == 500
    # compaction preserved aggregate semantics bit-for-bit
    after_props = le.aggregate_properties(app_id, "item")
    assert after_props == before_props
    assert len(after_props) == 200
    # idempotent: a second pass finds nothing to clean
    assert ds.clean_persisted_data(
        WorkflowContext(storage=storage), "cleanscale") == 0
