"""Full-lifecycle CLI integration (reference: tests/pio_tests/scenarios/
quickstart_test.py — drives the real `pio` binary against real storage).

Subprocess-based: each command is a fresh process sharing a temp
PIO_FS_BASEDIR (sqlite), exactly how a user runs the quickstart.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIO = os.path.join(REPO, "bin", "pio")


def run_pio(args, env, check=True):
    r = subprocess.run(
        [PIO, *args], capture_output=True, text=True, env=env, timeout=300
    )
    if check and r.returncode != 0:
        raise AssertionError(
            f"pio {' '.join(args)} failed ({r.returncode}):\n{r.stdout}\n{r.stderr}"
        )
    return r


@pytest.fixture()
def cli_env(tmp_path):
    env = dict(os.environ)
    env["PIO_FS_BASEDIR"] = str(tmp_path / "store")
    # CPU platform for subprocesses (they don't load tests/conftest.py).
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _write_events_file(path, n_users=25, n_items=15, seed=0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        k = 0
        for u in range(n_users):
            for i in range(n_items):
                if rng.random() < 0.5:
                    r = int(rng.integers(1, 6))
                    f.write(json.dumps({
                        "event": "rate", "entityType": "user", "entityId": str(u),
                        "targetEntityType": "item", "targetEntityId": f"i{i}",
                        "properties": {"rating": r},
                        "eventTime": f"2024-01-01T00:{k // 60:02d}:{k % 60:02d}.000Z",
                    }) + "\n")
                    k += 1
    return k


def test_quickstart_lifecycle(cli_env, tmp_path):
    # pio status
    r = run_pio(["status"], cli_env)
    assert "ready to go" in r.stdout

    # pio app new
    r = run_pio(["app", "new", "MyApp1"], cli_env)
    assert "Access Key" in r.stdout

    # duplicate app fails cleanly
    r = run_pio(["app", "new", "MyApp1"], cli_env, check=False)
    assert r.returncode == 1

    # import events
    events_file = tmp_path / "events.jsonl"
    n = _write_events_file(events_file)
    r = run_pio(["import", "--app-name", "MyApp1", "--input", str(events_file)], cli_env)
    assert f"Imported {n} events" in r.stdout

    # pio build (validation)
    tpl = os.path.join(REPO, "templates", "recommendation")
    r = run_pio(["build", "--engine-dir", tpl], cli_env)
    assert "ready" in r.stdout

    # pio train
    r = run_pio(["train", "--engine-dir", tpl], cli_env)
    assert "Training completed" in r.stdout
    # the completion line says where it trained
    assert "platform=cpu deviceKind='cpu' deviceCount=8" in r.stdout

    # --device=tpu where JAX found no TPU: an error, not a CPU train
    r = run_pio(["train", "--engine-dir", tpl, "--device", "tpu"], cli_env,
                check=False)
    assert r.returncode != 0
    assert "--device=tpu" in r.stderr and "platform 'cpu'" in r.stderr
    assert "Training completed" not in r.stdout

    # pio export round-trips
    out_file = tmp_path / "export.jsonl"
    r = run_pio(["export", "--app-name", "MyApp1", "--output", str(out_file)], cli_env)
    assert f"Exported {n} events" in r.stdout
    lines = [json.loads(l) for l in open(out_file)]
    assert len(lines) == n and all("eventId" in l for l in lines)

    # pio batchpredict
    queries = tmp_path / "queries.jsonl"
    with open(queries, "w") as f:
        for u in range(5):
            f.write(json.dumps({"user": str(u), "num": 3}) + "\n")
    preds = tmp_path / "preds.jsonl"
    r = run_pio(
        ["batchpredict", "--engine-dir", tpl, "--input", str(queries),
         "--output", str(preds)],
        cli_env,
    )
    out = [json.loads(l) for l in open(preds)]
    assert len(out) == 5
    assert all(len(o["prediction"]["itemScores"]) == 3 for o in out)

    # app list shows the app
    r = run_pio(["app", "list"], cli_env)
    assert "MyApp1" in r.stdout

    # unknown command → usage, exit 1
    r = run_pio(["bogus"], cli_env, check=False)
    assert r.returncode == 1 and "usage" in r.stderr


def test_runtime_passthrough_tier(cli_env, tmp_path):
    """`pio train -- --mesh=4x2 --xla_...` (reference: the post-`--`
    spark-submit passthrough, SURVEY.md §5.6c): runtime args after the
    bare -- configure the mesh/XLA/JAX runtime, not the verb."""
    env = dict(cli_env)
    env["XLA_FLAGS"] = ""  # passthrough must provide the device count
    _write_events_file(tmp_path / "events.json")
    run_pio(["app", "new", "ptapp"], env)
    run_pio(["import", "--appid", "1", "--input",
             str(tmp_path / "events.json")], env)
    eng = tmp_path / "eng"
    eng.mkdir()
    (eng / "engine.json").write_text(json.dumps({
        "id": "pt", "version": "1",
        "engineFactory": "incubator_predictionio_tpu.models."
                         "recommendation.RecommendationEngine",
        "datasource": {"params": {"appName": "ptapp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 4, "numIterations": 2,
                                   "lambda": 0.05}}],
    }))
    r = run_pio(["train", "--engine-dir", str(eng), "--",
                 "--mesh=4x2",
                 "--xla_force_host_platform_device_count=8"], env)
    assert "Training completed" in r.stdout

    # unknown passthrough flags are rejected with a clear error
    r = run_pio(["train", "--engine-dir", str(eng), "--",
                 "--definitely-not-a-flag"], env, check=False)
    assert r.returncode != 0
    assert "runtime passthrough" in (r.stdout + r.stderr)


def test_mesh_shape_env_parses():
    from incubator_predictionio_tpu.parallel.mesh import _mesh_shape_from_env

    os.environ.pop("PIO_MESH_SHAPE", None)
    assert _mesh_shape_from_env() is None
    os.environ["PIO_MESH_SHAPE"] = "8"
    try:
        assert _mesh_shape_from_env() == (8,)
        os.environ["PIO_MESH_SHAPE"] = "4x2"
        assert _mesh_shape_from_env() == (4, 2)
        os.environ["PIO_MESH_SHAPE"] = "bogus"
        with pytest.raises(ValueError):
            _mesh_shape_from_env()
    finally:
        os.environ.pop("PIO_MESH_SHAPE", None)


def test_parquet_export_import_roundtrip(cli_env, tmp_path):
    """`pio export --format parquet` → `pio import` must reproduce the
    event stream exactly (ids, times, properties, tie order) —
    reference parity: EventsToFile wrote json or parquet."""
    run_pio(["app", "new", "PqApp"], cli_env)
    events_file = tmp_path / "events.jsonl"
    n = _write_events_file(events_file, seed=3)
    # tags + prId must survive the parquet round trip (review finding)
    with open(events_file, "a") as f:
        f.write(json.dumps({
            "event": "rate", "entityType": "user", "entityId": "tagged",
            "targetEntityType": "item", "targetEntityId": "i0",
            "properties": {"rating": 5}, "tags": ["a", "b"],
            "prId": "pr-77", "eventTime": "2024-02-01T00:00:00.000Z",
        }) + "\n")
    n += 1
    run_pio(["import", "--app-name", "PqApp", "--input",
             str(events_file)], cli_env)

    pq_file = tmp_path / "events.parquet"
    r = run_pio(["export", "--app-name", "PqApp", "--output",
                 str(pq_file)], cli_env)  # format auto-detected
    assert f"Exported {n} events" in r.stdout and "(parquet)" in r.stdout

    run_pio(["app", "new", "PqApp2"], cli_env)
    r = run_pio(["import", "--app-name", "PqApp2", "--input",
                 str(pq_file)], cli_env)
    assert f"Imported {n} events" in r.stdout

    back = tmp_path / "back.jsonl"
    run_pio(["export", "--app-name", "PqApp2", "--output",
             str(back), "--format", "jsonl"], cli_env)
    run_pio(["export", "--app-name", "PqApp", "--output",
             str(tmp_path / "orig.jsonl"), "--format", "jsonl"], cli_env)
    a = [json.loads(x) for x in open(tmp_path / "orig.jsonl")]
    b = [json.loads(x) for x in open(back)]
    assert a == b


def test_pio_shell_scripted(cli_env, tmp_path):
    """`pio shell -c` runs a statement with pypio init()-ed against the
    configured storage (reference: bin/pio-shell, the REPL wired to the
    platform)."""
    r = run_pio(["shell", "-c",
                 "aid, key = pypio.new_app('shellapp'); "
                 "print('created', aid)"], cli_env)
    assert "created" in r.stdout
    # state persisted through the real storage config
    r = run_pio(["app", "list"], cli_env)
    assert "shellapp" in r.stdout


def test_app_data_delete_clean(cli_env, tmp_path):
    """`pio app data-delete --clean`: the standalone self-cleaning pass
    (dedupe + compaction; TTL age-out gated behind -f). Reference:
    SelfCleaningDataSource run outside a training workflow."""
    run_pio(["app", "new", "cleanapp"], cli_env)
    # events file with duplicate rows + a property stream
    events = []
    for n in range(20):
        ev = {"event": "view", "entityType": "user", "entityId": str(n % 5),
              "targetEntityType": "item", "targetEntityId": str(n % 7),
              "eventTime": f"2024-01-01T00:00:{n:02d}.000Z"}
        events.append(ev)
        if n < 10:
            events.append(dict(ev))  # exact duplicate (re-import)
    for step in range(4):
        events.append({"event": "$set", "entityType": "item", "entityId": "i1",
                       "properties": {f"p{step}": step},
                       "eventTime": f"2024-01-02T00:00:{step:02d}.000Z"})
    path = tmp_path / "ev.jsonl"
    path.write_text("\n".join(json.dumps(e) for e in events) + "\n")
    run_pio(["import", "--appid", "1", "--input", str(path)], cli_env)

    # TTL requested without -f → refused
    r = run_pio(["app", "data-delete", "cleanapp", "--clean",
                 "--ttl-days", "1"], cli_env, check=False)
    assert r.returncode == 1 and "-f" in r.stderr

    # --clean is default-channel-only: combining with --channel must
    # refuse rather than silently clean the wrong channel
    r = run_pio(["app", "data-delete", "cleanapp", "--clean",
                 "--channel", "live"], cli_env, check=False)
    assert r.returncode == 1 and "default channel" in r.stderr

    r = run_pio(["app", "data-delete", "cleanapp", "--clean"], cli_env)
    # 10 duplicates + (4 property events → 1 snapshot) = 13 removed
    assert "removed 13 events" in r.stdout
    # wipe still works and still needs -f
    assert run_pio(["app", "data-delete", "cleanapp"], cli_env,
                   check=False).returncode == 1
    run_pio(["app", "data-delete", "cleanapp", "-f"], cli_env)
