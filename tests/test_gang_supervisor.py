"""Gang-supervised multi-host training (ISSUE 7 acceptance).

The headline chaos test runs a REAL 2-worker gang training merged-feed ALS
under parallel/supervisor.Supervisor, SIGKILLs one worker mid-sweep
(deterministic `train.sweep:crash` fault), then SIGSTOPs a worker in the
relaunched gang to simulate a hang (heartbeat stall) — and asserts the
job still completes with factors matching an uninterrupted run, with the
restart/liveness counters visible through the telemetry registry.

Plus: drain-on-SIGTERM semantics, `pio train --num-workers` CLI e2e,
initialize_distributed timeout knobs (a worker joining a dead
coordinator must error within the bound, not hang), envknobs semantics,
and the single-spawn-path AST guard.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "gang_als_worker.py")

N_ITERS = 6


# ---------------------------------------------------------------------------
# envknobs (satellite: the consolidated parser)
# ---------------------------------------------------------------------------

class TestEnvKnobs:
    def test_int_malformed_and_overflow_fall_back(self, monkeypatch):
        from incubator_predictionio_tpu.common.envknobs import env_int

        for bad in ("bananas", "inf", "-inf", "nan", "1e999", "3.5", ""):
            monkeypatch.setenv("PIO_X", bad)
            assert env_int("PIO_X", 7) == 7, bad
        monkeypatch.delenv("PIO_X")
        assert env_int("PIO_X", 7) == 7

    def test_int_float_ok_accepts_scientific(self, monkeypatch):
        from incubator_predictionio_tpu.common.envknobs import env_int

        monkeypatch.setenv("PIO_X", "1e3")
        assert env_int("PIO_X", 7, float_ok=True) == 1000
        monkeypatch.setenv("PIO_X", "1e999")  # overflow still falls back
        assert env_int("PIO_X", 7, float_ok=True) == 7

    def test_int_clamps_parsed_value_not_default(self, monkeypatch):
        from incubator_predictionio_tpu.common.envknobs import env_int

        monkeypatch.setenv("PIO_X", "1000000")
        assert env_int("PIO_X", 2, lo=1, hi=64) == 64
        monkeypatch.setenv("PIO_X", "0")
        assert env_int("PIO_X", 2, lo=1, hi=64) == 1

    def test_warn_flag_emits_userwarning(self, monkeypatch):
        from incubator_predictionio_tpu.common.envknobs import env_int

        monkeypatch.setenv("PIO_X", "junk")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert env_int("PIO_X", 7, warn=True) == 7
        assert any("PIO_X" in str(x.message) for x in w)

    def test_float_rejects_nonfinite_by_default(self, monkeypatch):
        from incubator_predictionio_tpu.common.envknobs import env_float

        monkeypatch.setenv("PIO_X", "inf")
        assert env_float("PIO_X", 1.5) == 1.5
        monkeypatch.setenv("PIO_X", "2.5")
        assert env_float("PIO_X", 1.5) == 2.5

    def test_ms_returns_seconds(self, monkeypatch):
        from incubator_predictionio_tpu.common.envknobs import env_ms

        monkeypatch.setenv("PIO_X", "2500")
        assert env_ms("PIO_X", 1000.0) == 2.5
        monkeypatch.delenv("PIO_X")
        assert env_ms("PIO_X", 1000.0) == 1.0

    def test_legacy_callers_delegate_here(self):
        """The three divergent copies must be gone: each module's
        `_env_int` is a documented-semantics wrapper over envknobs."""
        import inspect

        from incubator_predictionio_tpu.data.api import ingest_buffer
        from incubator_predictionio_tpu.workflow import (create_server,
                                                         input_pipeline)

        for mod in (create_server, ingest_buffer, input_pipeline):
            src = inspect.getsource(mod._env_int)
            assert "envknobs.env_int" in src, mod.__name__


# ---------------------------------------------------------------------------
# distributed timeout knobs (satellite)
# ---------------------------------------------------------------------------

class TestDistributedTimeouts:
    def test_defaults(self, monkeypatch):
        from incubator_predictionio_tpu.parallel.distributed import (
            resolve_distributed_timeouts)

        for k in ("PIO_COORDINATOR_TIMEOUT_MS", "PIO_DIST_HEARTBEAT_MS",
                  "PIO_DIST_MAX_MISSING_HEARTBEATS"):
            monkeypatch.delenv(k, raising=False)
        t = resolve_distributed_timeouts()
        assert t == {"initialization_timeout": 300,
                     "heartbeat_interval": 10,
                     "max_missing_heartbeats": 10}

    def test_ms_to_seconds_with_floor(self, monkeypatch):
        from incubator_predictionio_tpu.parallel.distributed import (
            resolve_distributed_timeouts)

        monkeypatch.setenv("PIO_COORDINATOR_TIMEOUT_MS", "2500")
        monkeypatch.setenv("PIO_DIST_HEARTBEAT_MS", "1")  # floored
        monkeypatch.setenv("PIO_DIST_MAX_MISSING_HEARTBEATS", "3")
        t = resolve_distributed_timeouts()
        assert t["initialization_timeout"] == 2  # rounded to whole seconds
        assert t["heartbeat_interval"] == 1
        assert t["max_missing_heartbeats"] == 3

    def test_malformed_values_fall_back(self, monkeypatch):
        from incubator_predictionio_tpu.parallel.distributed import (
            resolve_distributed_timeouts)

        monkeypatch.setenv("PIO_COORDINATOR_TIMEOUT_MS", "soon")
        monkeypatch.setenv("PIO_DIST_HEARTBEAT_MS", "inf")
        monkeypatch.setenv("PIO_DIST_MAX_MISSING_HEARTBEATS", "-4")
        t = resolve_distributed_timeouts()
        assert t["initialization_timeout"] == 300
        assert t["heartbeat_interval"] == 10
        assert t["max_missing_heartbeats"] == 2  # clamped floor

    @pytest.mark.gang
    def test_dead_coordinator_errors_within_bound(self, tmp_path):
        """A worker pointed at a coordinator nobody serves must ERROR
        within PIO_COORDINATOR_TIMEOUT_MS — not retry forever. (This is
        what lets the supervisor see a half-started gang as worker
        failures instead of an eternal hang.)"""
        with socket.socket() as s:  # reserve a port nobody will serve
            s.bind(("127.0.0.1", 0))
            dead_port = s.getsockname()[1]
        env = {
            **os.environ,
            "PIO_COORDINATOR_ADDRESS": f"127.0.0.1:{dead_port}",
            "PIO_NUM_PROCESSES": "2",
            "PIO_PROCESS_ID": "1",  # joiner, not the coordinator host
            "PIO_COORDINATOR_TIMEOUT_MS": "3000",
            "JAX_PLATFORMS": "cpu",
        }
        t0 = time.monotonic()
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax; jax.config.update('jax_platforms', 'cpu')\n"
             "from incubator_predictionio_tpu.parallel.distributed import "
             "initialize_distributed\n"
             "initialize_distributed()"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
        took = time.monotonic() - t0
        assert r.returncode != 0, r.stdout + r.stderr
        # 3s budget + interpreter/jax import overhead; the point is it's
        # nowhere near the 300s default, let alone forever.
        assert took < 90, f"dead-coordinator join took {took:.0f}s"


# ---------------------------------------------------------------------------
# supervisor unit behavior
# ---------------------------------------------------------------------------

class TestSupervisorUnits:
    def test_gang_config_from_env_and_floors(self, monkeypatch):
        from incubator_predictionio_tpu.parallel.supervisor import GangConfig

        monkeypatch.setenv("PIO_NUM_WORKERS", "4")
        monkeypatch.setenv("PIO_WORKER_STALL_MS", "junk")
        monkeypatch.setenv("PIO_TRAIN_MAX_RESTARTS", "2")
        cfg = GangConfig.from_env()
        assert cfg.num_workers == 4
        assert cfg.stall_ms == 120_000.0  # malformed → default
        assert cfg.max_restarts == 2
        # floors: stall can't undercut 2 heartbeats; grace can't
        # undercut stall
        cfg2 = GangConfig(heartbeat_ms=1000, stall_ms=1, init_grace_ms=1)
        assert cfg2.stall_ms == 2000.0
        assert cfg2.init_grace_ms == cfg2.stall_ms

    def test_beat_creates_and_touches_file(self, tmp_path, monkeypatch):
        from incubator_predictionio_tpu.parallel import supervisor

        hb = tmp_path / "w.hb"
        monkeypatch.setenv(supervisor.ENV_HEARTBEAT_FILE, str(hb))
        monkeypatch.setenv("PIO_WORKER_HEARTBEAT_MS", "40")
        monkeypatch.setattr(supervisor, "_hb_last", 0.0)
        monkeypatch.setattr(supervisor, "_hb_interval", None)
        supervisor.beat()
        assert hb.exists()
        m0 = hb.stat().st_mtime
        time.sleep(0.05)  # > the 20ms throttle (40/2)
        supervisor.beat()
        assert hb.stat().st_mtime >= m0

    def test_beat_noop_without_env(self, monkeypatch):
        from incubator_predictionio_tpu.parallel import supervisor

        monkeypatch.delenv(supervisor.ENV_HEARTBEAT_FILE, raising=False)
        supervisor.beat()  # must not raise or create anything

    def test_drain_flag_roundtrip(self):
        from incubator_predictionio_tpu.parallel import supervisor

        supervisor.reset_drain()
        assert not supervisor.drain_requested()
        supervisor.request_drain()
        assert supervisor.drain_requested()
        # non-gang process: the global check is the local flag
        assert supervisor.drain_requested_global()
        supervisor.reset_drain()
        assert not supervisor.drain_requested_global()

    def test_gang_marker_registered(self):
        with open(os.path.join(REPO, "pyproject.toml")) as f:
            doc = f.read()
        assert '"gang: ' in doc, "gang pytest marker not registered"


# ---------------------------------------------------------------------------
# AST guard: the supervisor is the only training-worker spawner
# ---------------------------------------------------------------------------

def test_no_subprocess_spawns_outside_supervisor():
    """Everything under parallel/ and workflow/ must route process
    spawning through parallel/supervisor.py (the PR 3/6
    single-dispatch-path pattern): a side-channel worker launch would
    escape liveness monitoring, restart accounting, and drain.
    Enforced by the shared `pio lint` engine."""
    from incubator_predictionio_tpu.tools.lint import assert_rule_clean

    assert_rule_clean("spawn-confinement")


# ---------------------------------------------------------------------------
# chaos harness: real subprocess gangs
# ---------------------------------------------------------------------------

def _gang_env(tmp_path, devices_per_worker=1):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS":
            f"--xla_force_host_platform_device_count={devices_per_worker}",
        # relaunches recompile from cache — keeps 3-launch chaos cheap
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla_cache"),
    }
    env.pop("PIO_FAULT_SPEC", None)
    env.pop("PIO_NUM_WORKERS", None)
    return env


def _reference_factors(n_iters=N_ITERS, n_devices=2):
    import jax

    from incubator_predictionio_tpu.ops.als import ALSParams, train_als
    from incubator_predictionio_tpu.parallel.mesh import mesh_from_devices

    sys.path.insert(0, HERE)
    try:
        from gang_als_worker import _data
    finally:
        sys.path.remove(HERE)
    u, i, r, n_users, n_items = _data()
    mesh = mesh_from_devices(devices=jax.devices()[:n_devices])
    return train_als(u, i, r, n_users, n_items,
                     ALSParams(rank=4, num_iterations=n_iters, seed=5),
                     mesh=mesh)


def _run_supervisor_in_thread(sup):
    box = {}

    def _go():
        try:
            box["outcome"] = sup.run()
        except BaseException as e:  # pragma: no cover - surfaced in test
            box["error"] = e

    t = threading.Thread(target=_go, daemon=True)
    t.start()
    return t, box


@pytest.mark.gang
@pytest.mark.chaos
def test_gang_survives_sigkill_and_sigstop(tmp_path):
    """The headline acceptance: a 2-worker merged-feed ALS gang loses one
    worker to SIGKILL mid-sweep (attempt 0), gang-restarts from the
    checkpoint, loses another to SIGSTOP (attempt 1, detected as a
    heartbeat stall), gang-restarts again, and FINISHES with factors
    matching an uninterrupted single-process run. Liveness/restart
    telemetry must be visible in the registry."""
    from incubator_predictionio_tpu.common import telemetry
    from incubator_predictionio_tpu.parallel.supervisor import (
        COMPLETED, GangConfig, Supervisor)

    out_path = str(tmp_path / "factors.npz")
    ckpt_dir = str(tmp_path / "ckpt")

    def chaos(attempt, idx):
        # Attempt 0: worker 1 SIGKILLs itself inside its 3rd sweep
        # (checkpoints of sweeps 1-2 exist); the latency rule slows
        # every gang sweep (collectives are lockstep) so the kill is
        # genuinely mid-run. Attempt 1: still slowed, giving the test a
        # window to SIGSTOP a worker. Attempt 2: clean and fast.
        if attempt == 0 and idx == 1:
            return {"PIO_FAULT_SPEC": "train.sweep:crash:3"}
        if attempt <= 1 and idx == 0:
            return {"PIO_FAULT_SPEC": "train.sweep:latency:1000:0.4"}
        return {}

    sup = Supervisor(
        [sys.executable, WORKER, out_path, ckpt_dir, str(N_ITERS)],
        num_workers=2,
        env=_gang_env(tmp_path),
        per_worker_env=chaos,
        # stall threshold: sweeps are ~0.4s (latency fault) but a chunk
        # dispatch or an orbax save can stretch past 3s under full-suite
        # CPU contention — 8s keeps the detector honest without false
        # positives, and the SIGSTOP below stalls forever anyway.
        config=GangConfig(num_workers=2, heartbeat_ms=250.0, stall_ms=8000.0,
                          init_grace_ms=300_000.0, max_restarts=3,
                          poll_ms=50.0),
        run_dir=str(tmp_path / "run"),
    )
    t, box = _run_supervisor_in_thread(sup)

    # Wait for the relaunched gang (attempt 1), then SIGSTOP worker 1
    # once it starts beating (= it is past compile, mid-training).
    deadline = time.monotonic() + 600
    start1 = None
    while time.monotonic() < deadline and not box:
        start1 = next((e for e in list(sup.events)
                       if e["type"] == "gangStart" and e["attempt"] == 1),
                      None)
        if start1:
            break
        time.sleep(0.05)
    assert start1, f"no restart observed: {sup.events} {box}"
    hb1 = os.path.join(sup.run_dir, "worker_1.hb")
    stopped = False
    while time.monotonic() < deadline and not box:
        if next((e for e in list(sup.events)
                 if e["type"] == "gangStart" and e["attempt"] > 1), None):
            break  # attempt 1 already over — too late to stop a worker
        if os.path.exists(hb1):
            try:
                os.kill(start1["pids"][1], signal.SIGSTOP)
                stopped = True
            except OSError:
                pass
            break
        time.sleep(0.02)

    t.join(timeout=600)
    assert not t.is_alive(), f"supervisor wedged: {sup.events}"
    assert "error" not in box, box.get("error")
    assert box["outcome"] == COMPLETED, sup.events

    reasons = [e["reason"] for e in sup.events if e["type"] == "failure"]
    assert reasons and reasons[0] == "exit", sup.events
    if stopped:
        assert "stall" in reasons, sup.events
        assert sup.restarts >= 2
    else:  # the resumed gang outran the stopper (heavily loaded host)
        assert sup.restarts >= 1

    # resumed, not retrained: every relaunch after the first ran --resume
    assert all(e["resume"] for e in sup.events
               if e["type"] == "gangStart" and e["attempt"] > 0)

    # the gang's factors match an uninterrupted single-process run
    assert os.path.exists(out_path)
    got = np.load(out_path)
    ref = _reference_factors()
    np.testing.assert_allclose(got["user"], ref.user_factors,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got["item"], ref.item_factors,
                               rtol=2e-4, atol=2e-5)

    # liveness/restart families are in the process registry (the same
    # substrate /metrics renders)
    text = telemetry.render_all()
    assert 'pio_train_restarts_total{reason="exit"}' in text
    if stopped:
        assert 'pio_train_restarts_total{reason="stall"}' in text
    assert "pio_train_worker_alive" in text
    assert "pio_train_worker_heartbeat_age_seconds" in text

    # the status file a foreign process would watch
    doc = json.load(open(os.path.join(sup.run_dir, "supervisor.json")))
    assert doc["state"] == "completed"
    assert doc["restarts"] == sup.restarts


@pytest.mark.gang
@pytest.mark.chaos
@pytest.mark.slow
def test_gang_drain_on_stop_then_resume(tmp_path):
    """SIGTERM-path drain: request_stop() mid-training SIGTERMs the
    workers, every process checkpoints at the SAME sweep boundary
    (allgathered drain flag) and exits; nothing is restarted. A fresh
    `--resume` gang then finishes the run and matches the
    uninterrupted reference."""
    from incubator_predictionio_tpu.parallel.supervisor import (
        COMPLETED, DRAINED, GangConfig, Supervisor)

    out_path = str(tmp_path / "factors.npz")
    ckpt_dir = str(tmp_path / "ckpt")
    cfg = dict(num_workers=2, heartbeat_ms=250.0, stall_ms=10_000.0,
               init_grace_ms=300_000.0, max_restarts=1, poll_ms=50.0,
               drain_ms=60_000.0)

    sup = Supervisor(
        [sys.executable, WORKER, out_path, ckpt_dir, str(N_ITERS)],
        num_workers=2,
        env=_gang_env(tmp_path),
        per_worker_env=lambda a, i: (
            {"PIO_FAULT_SPEC": "train.sweep:latency:1000:0.4"}
            if i == 0 else {}),
        config=GangConfig(**cfg),
        run_dir=str(tmp_path / "run"),
    )
    t, box = _run_supervisor_in_thread(sup)
    # Stop at the FIRST heartbeat — that is sweep 1 of 6, with the rest
    # of the run still ahead (checkpoint dirs can commit asynchronously,
    # too late to be a reliable mid-run trigger).
    hb0 = os.path.join(sup.run_dir, "worker_0.hb")
    deadline = time.monotonic() + 600
    while time.monotonic() < deadline and not box:
        if os.path.exists(hb0):
            break
        time.sleep(0.02)
    sup.request_stop()
    t.join(timeout=600)
    assert not t.is_alive() and "error" not in box, box
    if box["outcome"] == COMPLETED or os.path.exists(out_path):
        pytest.skip("gang finished before the stop landed (loaded host); "
                    "drain not observable this run")
    assert box["outcome"] == DRAINED, sup.events
    assert sup.restarts == 0
    drain_done = [e for e in sup.events if e["type"] == "drainDone"]
    assert drain_done and not drain_done[0]["stragglers"], \
        "workers had to be SIGKILLed instead of draining cleanly"
    steps = [int(d) for d in os.listdir(ckpt_dir) if d.isdigit()]
    assert steps, "drain left no checkpoint behind"

    # resume in a fresh supervisor run → completes and matches
    sup2 = Supervisor(
        [sys.executable, WORKER, out_path, ckpt_dir, str(N_ITERS),
         "--resume"],
        num_workers=2,
        env=_gang_env(tmp_path),
        config=GangConfig(**cfg),
        run_dir=str(tmp_path / "run2"),
    )
    assert sup2.run() == COMPLETED, sup2.events
    got = np.load(out_path)
    ref = _reference_factors()
    np.testing.assert_allclose(got["user"], ref.user_factors,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got["item"], ref.item_factors,
                               rtol=2e-4, atol=2e-5)


@pytest.mark.gang
@pytest.mark.slow
def test_pio_train_num_workers_cli_e2e(tmp_path):
    """`pio train --num-workers 2` end to end through the real CLI:
    the supervisor spawns two `pio train` worker processes over a
    shared store, the gang leader owns the one EngineInstance row, and
    the trained model serves batchpredict like a single-process run."""
    events_file = tmp_path / "events.jsonl"
    from test_cli_integration import _write_events_file, run_pio

    env = dict(os.environ)
    env["PIO_FS_BASEDIR"] = str(tmp_path / "store")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORMS"] = "cpu"  # workers pick gloo collectives
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla_cache")
    env.pop("PIO_FAULT_SPEC", None)

    r = run_pio(["app", "new", "MyApp1"], env)
    n = _write_events_file(events_file)
    run_pio(["import", "--app-name", "MyApp1", "--input",
             str(events_file)], env)
    tpl = os.path.join(REPO, "templates", "recommendation")
    r = run_pio(["train", "--engine-dir", tpl, "--num-workers", "2",
                 "--checkpoint-every", "2"], env)
    assert "Gang training completed" in r.stdout, r.stdout

    # exactly one COMPLETED instance row — followers must not write
    from incubator_predictionio_tpu.data.storage import Storage

    # the CLI's PIO_DEFAULT source = $PIO_FS_BASEDIR/pio.sqlite
    storage = Storage({
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
        "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
        "PIO_STORAGE_SOURCES_DB_PATH":
            os.path.join(env["PIO_FS_BASEDIR"], "pio.sqlite"),
    })
    try:
        rows = [i for i in
                storage.get_meta_data_engine_instances().get_all()
                if i.status == "COMPLETED"]
        assert len(rows) == 1, [(i.id, i.status) for i in rows]
        assert storage.get_model_data_models().get(rows[0].id) is not None
    finally:
        storage.close()

    queries = tmp_path / "queries.jsonl"
    with open(queries, "w") as f:
        for u in range(3):
            f.write(json.dumps({"user": str(u), "num": 3}) + "\n")
    preds = tmp_path / "preds.jsonl"
    run_pio(["batchpredict", "--engine-dir", tpl, "--input", str(queries),
             "--output", str(preds)], env)
    out = [json.loads(line) for line in open(preds)]
    assert len(out) == 3
    assert all(len(o["prediction"]["itemScores"]) == 3 for o in out)
