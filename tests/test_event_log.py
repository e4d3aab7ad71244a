"""Partitioned event log (ISSUE 8): fenced multi-worker ownership,
crash-safe compaction, corruption scrubbing, ENOSPC shed.

Chaos acceptance (data/api/event_log.py):
- a rival claimant on a held partition is refused at claim time, and a
  stolen lease epoch fences the old owner BEFORE any byte lands (zero
  writes from the fenced side);
- SIGKILL at any compaction instruction leaves either the old snapshot
  or the complete new one active (manifest commit record), and a rerun
  converges;
- a bit-flipped snapshot is quarantined (moved, counted, warned) while
  the partition keeps serving from the JSONL bytes;
- ENOSPC-class append faults shed 503 + jittered Retry-After without
  corrupting the log tail, and the partition recovers when the disk
  does;
- `pio eventserver --workers N`: real worker subprocesses own disjoint
  partitions behind the front splice; SIGKILL mid-group-commit →
  per-worker restart replays every acked event exactly once while the
  service keeps answering.
"""

import errno
import json
import os
import signal
import subprocess
import sys
import time

import pytest
import requests

from incubator_predictionio_tpu.common import faultinject
from incubator_predictionio_tpu.data.api import event_log
from incubator_predictionio_tpu.data.api.event_server import EventServer
from incubator_predictionio_tpu.data.storage import Storage
from incubator_predictionio_tpu.data.storage.base import AccessKey, App
from incubator_predictionio_tpu.data.storage.jsonl import JSONLEvents
from incubator_predictionio_tpu.data.store.p_event_store import PEventStore

from server_utils import ServerThread, free_port

pytestmark = [pytest.mark.partition, pytest.mark.chaos]

T = "2026-01-01T00:00:00.000Z"
HERE = os.path.dirname(os.path.abspath(__file__))


def _ev(i, **kw):
    d = {"event": "view", "entityType": "user", "entityId": f"u{i}",
         "eventTime": T}
    d.update(kw)
    return d


def _storage(tmp_path, name="ev"):
    env = {
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
        "PIO_STORAGE_SOURCES_M_TYPE": "MEMORY",
        "PIO_STORAGE_SOURCES_EV_TYPE": "JSONL",
        "PIO_STORAGE_SOURCES_EV_PATH": str(tmp_path / name),
    }
    storage = Storage(env)
    app_id = storage.get_meta_data_apps().insert(App(0, "partapp"))
    key = storage.get_meta_data_access_keys().insert(
        AccessKey("", app_id, ()))
    return storage, app_id, key


# ---------------------------------------------------------------------------
# lease fencing
# ---------------------------------------------------------------------------

def test_rival_process_cannot_claim_held_partition(tmp_path):
    """The headline fencing property, against a REAL second process: a
    subprocess tries to claim the partition this process holds — it
    must fail with PartitionHeldError and land zero writes."""
    lease = event_log.claim_partition(str(tmp_path), 0)
    marker = tmp_path / "rival_wrote"
    code = (
        "import sys\n"
        "from incubator_predictionio_tpu.data.api import event_log\n"
        f"try:\n"
        f"    event_log.claim_partition({str(tmp_path)!r}, 0)\n"
        "except event_log.PartitionHeldError:\n"
        "    sys.exit(42)\n"
        f"open({str(marker)!r}, 'w').write('rival claimed + would "
        "write')\n"
    )
    rc = subprocess.run([sys.executable, "-c", code],
                        capture_output=True, timeout=60).returncode
    assert rc == 42, "rival process claimed a held partition"
    assert not marker.exists(), "rival landed a write"
    lease.verify()  # we still own it
    lease.release()


def test_stolen_lease_fences_old_owner_before_any_byte(tmp_path,
                                                       monkeypatch):
    """Epoch fencing end-to-end through a live server: steal the lease
    (force-claim bumps the epoch) and the old owner's next write group
    is refused BEFORE any WAL/store append — the log byte count does
    not move, and the client gets the 503 shed contract."""
    monkeypatch.setenv("PIO_EVENT_PARTITION", "0")
    storage, app_id, key = _storage(tmp_path)
    server = EventServer(storage)
    assert server.lease is not None and server.lease.partition == 0
    log_dir = storage.get_l_events()._dir
    log_path = os.path.join(log_dir, "events_1.p0.jsonl")

    with ServerThread(server.app) as st:
        r = requests.post(f"{st.base}/events.json?accessKey={key}",
                          json=_ev(1), timeout=30)
        assert r.status_code == 201
        size_before = os.path.getsize(log_path)
        # rival steals the partition (epoch bump past our flock)
        rival = event_log.claim_partition(log_dir, 0, force=True)
        assert rival.epoch == server.lease.epoch + 1
        r = requests.post(f"{st.base}/events.json?accessKey={key}",
                          json=_ev(2), timeout=30)
        assert r.status_code == 503, r.text
        assert int(r.headers["Retry-After"]) >= 1
        assert os.path.getsize(log_path) == size_before, \
            "fenced worker landed bytes"
        rival.release()
    # exactly the pre-fence event exists
    names = [e.entity_id for e in storage.get_l_events().find(app_id)]
    assert names == ["u1"]


# ---------------------------------------------------------------------------
# crash-safe compaction
# ---------------------------------------------------------------------------

def _fill(tmp_path, n=200):
    storage, app_id, key = _storage(tmp_path)
    from incubator_predictionio_tpu.data.storage.event import Event

    le = storage.get_l_events()
    le.insert_batch([Event.from_json(_ev(i)) for i in range(n)], app_id)
    return storage, app_id, key, os.path.join(le._dir, "events_1.jsonl")


def test_compaction_scan_is_bit_identical_and_skips_json_parse(tmp_path):
    """Acceptance: find_batches over the compacted format is
    bit-identical to the JSONL scan, and the snapshot is actually USED
    (the loads counter moves)."""
    storage, app_id, key, log_path = _fill(tmp_path)
    ref = [e.to_json() for e in storage.get_l_events().find(app_id)]
    cols_ref, rows_ref = storage.get_l_events().scan_columnar(app_id)

    assert event_log.compact_log(log_path) is not None
    before = event_log._M_SNAP_LOADS.value()
    fresh = JSONLEvents(os.path.dirname(log_path))
    got = [e.to_json() for e in fresh.find(app_id)]
    assert got == ref
    assert event_log._M_SNAP_LOADS.value() == before + 1, \
        "scan did not load the snapshot"
    cols, rows = fresh.scan_columnar(app_id)
    assert cols.raw == cols_ref.raw
    assert (rows == rows_ref).all()
    assert (cols.time_us == cols_ref.time_us).all()
    assert cols.tables == cols_ref.tables

    # appends past the snapshot ride the incremental tail parse
    from incubator_predictionio_tpu.data.storage.event import Event

    fresh.insert(Event.from_json(_ev(999)), app_id)
    fresh2 = JSONLEvents(os.path.dirname(log_path))
    got2 = [e.entity_id for e in fresh2.find(app_id)]
    assert len(got2) == len(ref) + 1 and "u999" in got2


def test_find_batches_parity_over_compacted_log(tmp_path):
    """The training read path (PEventStore.find_batches → the PR 2
    input pipeline's iterator) over a compacted log equals the pure
    JSONL scan field-for-field."""
    storage, app_id, key, log_path = _fill(tmp_path, n=300)
    batches = list(PEventStore.find_batches(
        "partapp", storage=storage, chunk_size=128))
    assert event_log.compact_log(log_path) is not None
    # a FRESH storage instance scans via the snapshot
    storage2 = Storage({
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
        "PIO_STORAGE_SOURCES_M_TYPE": "MEMORY",
        "PIO_STORAGE_SOURCES_EV_TYPE": "JSONL",
        "PIO_STORAGE_SOURCES_EV_PATH": str(tmp_path / "ev"),
    })
    storage2.get_meta_data_apps().insert(App(0, "partapp"))
    batches2 = list(PEventStore.find_batches(
        "partapp", storage=storage2, chunk_size=128))
    assert len(batches) == len(batches2)
    for a, b in zip(batches, batches2):
        assert a.event == b.event
        assert a.entity_id == b.entity_id
        assert a.target_entity_id == b.target_entity_id
        assert a.properties == b.properties
        assert (a.event_time_us == b.event_time_us).all()


def test_compaction_crash_at_every_point_converges(tmp_path, monkeypatch):
    """Kill (exception-style) compaction at each named fault point: the
    committed state stays valid after every failure, scans still serve,
    and a clean rerun converges to a fresh snapshot."""
    storage, app_id, key, log_path = _fill(tmp_path)
    ref = [e.to_json() for e in storage.get_l_events().find(app_id)]
    for point in ("compact.write", "compact.rename", "compact.manifest"):
        monkeypatch.setenv("PIO_FAULT_SPEC", f"{point}:fail:1")
        faultinject.reset()
        with pytest.raises(Exception):
            event_log.compact_log(log_path)
        monkeypatch.delenv("PIO_FAULT_SPEC")
        faultinject.reset()
        # state after the crash point is still servable + correct
        fresh = JSONLEvents(os.path.dirname(log_path))
        assert [e.to_json() for e in fresh.find(app_id)] == ref
    # rerun converges
    m = event_log.compact_log(log_path)
    assert m is not None
    got = event_log.load_snapshot(log_path)
    assert got is not None and len(got[0]) == len(ref)
    # exactly one generation survives on disk (gc removed the rest)
    segs = [n for n in os.listdir(os.path.dirname(log_path))
            if n.endswith(".colseg")]
    assert segs == [m["file"]]


def test_mid_compaction_sigkill_converges(tmp_path):
    """REAL SIGKILL mid-compaction (between the snapshot rename and the
    manifest commit) via `pio eventlog compact` in a subprocess: the
    old state stays active, nothing is lost, and a rerun converges."""
    storage, app_id, key, log_path = _fill(tmp_path)
    ref = [e.to_json() for e in storage.get_l_events().find(app_id)]
    env = {
        **os.environ,
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
        "PIO_STORAGE_SOURCES_M_TYPE": "MEMORY",
        "PIO_STORAGE_SOURCES_EV_TYPE": "JSONL",
        "PIO_STORAGE_SOURCES_EV_PATH": str(tmp_path / "ev"),
        "PIO_FAULT_SPEC": "compact.rename:crash:1",
    }
    proc = subprocess.run(
        [sys.executable, "-m", "incubator_predictionio_tpu.tools.console",
         "eventlog", "compact"],
        env=env, capture_output=True, timeout=120)
    assert proc.returncode in (-signal.SIGKILL, 137), \
        (proc.returncode, proc.stdout, proc.stderr)
    # no manifest was committed; a scan ignores the orphan snapshot
    assert event_log.load_snapshot(log_path) is None
    fresh = JSONLEvents(os.path.dirname(log_path))
    assert [e.to_json() for e in fresh.find(app_id)] == ref
    # rerun WITHOUT the fault: converges to a committed snapshot
    env.pop("PIO_FAULT_SPEC")
    proc2 = subprocess.run(
        [sys.executable, "-m", "incubator_predictionio_tpu.tools.console",
         "eventlog", "compact"],
        env=env, capture_output=True, timeout=120)
    assert proc2.returncode == 0, proc2.stderr
    got = event_log.load_snapshot(log_path)
    assert got is not None and len(got[0]) == len(ref)


def test_bitflipped_snapshot_quarantined_partition_keeps_serving(
        tmp_path):
    """Acceptance: a bit-flipped compacted segment is quarantined (not
    deleted) with the counter bumped, while scans keep serving the same
    answers from the JSONL bytes."""
    storage, app_id, key, log_path = _fill(tmp_path)
    ref = [e.to_json() for e in storage.get_l_events().find(app_id)]
    m = event_log.compact_log(log_path)
    snap_path = os.path.join(os.path.dirname(log_path), m["file"])
    blob = bytearray(open(snap_path, "rb").read())
    blob[len(blob) // 2] ^= 0x10
    open(snap_path, "wb").write(bytes(blob))

    from incubator_predictionio_tpu.data.api import ingest_wal

    qcounter = ingest_wal._M_QUARANTINED.labels("colseg")
    before = qcounter.value()
    fresh = JSONLEvents(os.path.dirname(log_path))
    assert [e.to_json() for e in fresh.find(app_id)] == ref, \
        "partition stopped serving after snapshot corruption"
    assert qcounter.value() == before + 1
    qdir = os.path.join(os.path.dirname(log_path), "quarantine")
    assert os.path.isdir(qdir) and m["file"] in os.listdir(qdir)
    assert not os.path.exists(snap_path)
    # a later compaction pass rebuilds a healthy snapshot
    m2 = event_log.compact_log(log_path)
    assert m2 is not None and event_log.load_snapshot(log_path) is not None
    report = event_log.scrub_log_dir(os.path.dirname(log_path))
    assert report == {"checked": 1, "ok": 1, "quarantined": 0, "stale": 0}


def test_merged_partitioned_scan_seeds_from_snapshots(tmp_path,
                                                      monkeypatch):
    """The partitioned (merged) read path must not waste the
    compactor's work: a cold merged build seeds each shard from its
    committed snapshot (loads counter moves per shard) and is
    field-identical to the pure JSON parse."""
    from incubator_predictionio_tpu.data.storage.event import Event

    storage, app_id, key = _storage(tmp_path)
    ev_dir = storage.get_l_events()._dir
    for part in (0, 1):
        monkeypatch.setenv("PIO_EVENT_PARTITION", str(part))
        le = JSONLEvents(ev_dir)
        le.insert_batch(
            [Event.from_json(_ev(part * 1000 + i)) for i in range(40)],
            app_id)
        le.close()
    monkeypatch.delenv("PIO_EVENT_PARTITION")
    ref = sorted(e.entity_id for e in JSONLEvents(ev_dir).find(app_id))
    for part in (0, 1):
        assert event_log.compact_log(
            os.path.join(ev_dir, f"events_1.p{part}.jsonl")) is not None
    before = event_log._M_SNAP_LOADS.value()
    fresh = JSONLEvents(ev_dir)
    got = sorted(e.entity_id for e in fresh.find(app_id))
    assert got == ref
    assert event_log._M_SNAP_LOADS.value() == before + 2, \
        "merged cold build did not seed from the shard snapshots"
    # incremental growth after the snapshot-seeded build stays correct
    monkeypatch.setenv("PIO_EVENT_PARTITION", "0")
    le0 = JSONLEvents(ev_dir)
    le0.insert(Event.from_json(_ev(7777)), app_id)
    monkeypatch.delenv("PIO_EVENT_PARTITION")
    got2 = sorted(e.entity_id for e in fresh.find(app_id))
    assert got2 == sorted(ref + ["u7777"])


def test_stale_snapshot_discarded_not_quarantined(tmp_path):
    """A log REWRITE (tombstone compaction) makes the snapshot stale,
    which is not corruption: it is silently discarded and rebuilt, and
    nothing lands in quarantine."""
    storage, app_id, key, log_path = _fill(tmp_path, n=50)
    le = storage.get_l_events()
    ids = [e.event_id for e in le.find(app_id)]
    event_log.compact_log(log_path)
    le.delete_batch(ids[:10], app_id)
    le.compact(app_id)  # tombstone-compacting rewrite
    fresh = JSONLEvents(os.path.dirname(log_path))
    got = [e.to_json() for e in fresh.find(app_id)]
    assert len(got) == 40
    assert not os.path.isdir(
        os.path.join(os.path.dirname(log_path), "quarantine"))


# ---------------------------------------------------------------------------
# ENOSPC-class degradation
# ---------------------------------------------------------------------------

def test_enospc_append_sheds_503_and_recovers(tmp_path, monkeypatch):
    """Satellite + acceptance: a disk-full append error returns 503 +
    jittered Retry-After (not 500), bumps
    pio_ingest_append_errors_total{kind=enospc}, flips the partition to
    shed mode (later requests refused without touching the disk), and
    the partition recovers once the window expires and the disk is
    healthy — with the log tail intact throughout."""
    from incubator_predictionio_tpu.data.api.ingest_buffer import (
        _M_APPEND_ERRORS)

    monkeypatch.setenv("PIO_INGEST_SHED_MS", "400")
    storage, app_id, key = _storage(tmp_path)
    server = EventServer(storage)
    log_path = os.path.join(storage.get_l_events()._dir, "events_1.jsonl")
    before = _M_APPEND_ERRORS.labels("enospc").value()
    with ServerThread(server.app) as st:
        r = requests.post(f"{st.base}/events.json?accessKey={key}",
                          json=_ev(1), timeout=30)
        assert r.status_code == 201
        tail_before = open(log_path, "rb").read()
        monkeypatch.setenv("PIO_FAULT_SPEC",
                           f"jsonl.append:oserr:1:{errno.ENOSPC}")
        faultinject.reset()
        r = requests.post(f"{st.base}/events.json?accessKey={key}",
                          json=_ev(2), timeout=30)
        assert r.status_code == 503, r.text
        assert int(r.headers["Retry-After"]) >= 1
        assert _M_APPEND_ERRORS.labels("enospc").value() == before + 1
        # shed mode: the next request is refused WITHOUT touching disk
        # (the oserr rule is spent — only shed mode can refuse now)
        monkeypatch.delenv("PIO_FAULT_SPEC")
        faultinject.reset()
        r = requests.post(f"{st.base}/events.json?accessKey={key}",
                          json=_ev(3), timeout=30)
        assert r.status_code == 503, "shed window not honoured"
        # tail uncorrupted: exactly the pre-fault bytes
        assert open(log_path, "rb").read() == tail_before
        # after the window the partition recovers (half-open probe)
        time.sleep(0.6)
        r = requests.post(f"{st.base}/events.json?accessKey={key}",
                          json=_ev(4), timeout=30)
        assert r.status_code == 201, "partition did not recover"
    names = sorted(e.entity_id for e in storage.get_l_events().find(app_id))
    assert names == ["u1", "u4"]


# ---------------------------------------------------------------------------
# supervised service workers (restart_scope="worker")
# ---------------------------------------------------------------------------

def test_service_supervisor_restarts_one_worker(tmp_path):
    """parallel/supervisor.py generalized past training gangs: in
    worker scope, killing ONE worker relaunches only it — the peer
    process keeps running undisturbed — and per-worker restart budgets
    give up after max_restarts."""
    from incubator_predictionio_tpu.parallel.supervisor import (
        GangConfig, Supervisor)

    script = (
        "import os, sys, time\n"
        "open(os.path.join(sys.argv[1], 'pid_%s' % "
        "os.environ['PIO_PROCESS_ID']), 'a').write(str(os.getpid()) + "
        "'\\n')\n"
        "hb = os.environ.get('PIO_WORKER_HEARTBEAT_FILE')\n"
        "while True:\n"
        "    open(hb, 'a').close(); os.utime(hb, None)\n"
        "    time.sleep(0.05)\n"
    )
    cfg = GangConfig(num_workers=2, heartbeat_ms=100.0, stall_ms=2000.0,
                     init_grace_ms=15000.0, max_restarts=2, poll_ms=50.0)
    sup = Supervisor([sys.executable, "-c", script, str(tmp_path)],
                     num_workers=2, config=cfg,
                     run_dir=str(tmp_path / "run"),
                     wire_coordinator=False, restart_scope="worker",
                     resume_argv=())
    import threading
    t = threading.Thread(target=sup.run, daemon=True)
    t.start()
    def _pids_recorded(idx):
        try:
            return open(tmp_path / f"pid_{idx}").read().split()
        except OSError:
            return []

    def _wait(cond, what, timeout=30):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            time.sleep(0.05)
        raise AssertionError(f"timed out waiting for {what}")

    try:
        # both workers must have REACHED their loop (interpreter
        # startup is slower than Popen) before the chaos starts
        _wait(lambda: len(_pids_recorded(0)) == 1
              and len(_pids_recorded(1)) == 1, "workers running")
        pids = sup.worker_pids()
        assert all(p is not None for p in pids), "workers not up"
        peer_pid = pids[1]
        os.kill(pids[0], signal.SIGKILL)
        _wait(lambda: len(_pids_recorded(0)) == 2, "worker 0 relaunch")
        new_pids = sup.worker_pids()
        assert new_pids[0] not in (None, pids[0]), "worker 0 not relaunched"
        assert new_pids[1] == peer_pid, "peer was disturbed"
        assert sup.restarts == 1
        assert len(_pids_recorded(1)) == 1, "peer was relaunched too"
    finally:
        sup.request_stop()
        t.join(timeout=30)
    assert sup.state == "drained"


# ---------------------------------------------------------------------------
# multi-worker event server e2e (front + 2 partitions + SIGKILL)
# ---------------------------------------------------------------------------

def _make_mw_env(tmp_path, **extra):
    env = {
        **os.environ,
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
        "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
        "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "meta.sqlite"),
        "PIO_STORAGE_SOURCES_EV_TYPE": "JSONL",
        "PIO_STORAGE_SOURCES_EV_PATH": str(tmp_path / "events"),
        "PIO_WAL": "1",
        "PIO_WAL_DIR": str(tmp_path / "wal"),
        "JAX_PLATFORMS": "cpu",
        # fast detection for the harness (defaults are production-lazy)
        "PIO_SUPERVISOR_POLL_MS": "50",
        "PIO_WORKER_STALL_MS": "30000",
    }
    env.pop("PIO_FAULT_SPEC", None)
    env.pop("PIO_EVENT_PARTITION", None)
    env.update(extra)
    return env


def _prepare_metadata(env) -> str:
    storage = Storage({k: v for k, v in env.items()
                       if k.startswith("PIO_STORAGE")})
    app_id = storage.get_meta_data_apps().insert(App(0, "mwapp"))
    key = storage.get_meta_data_access_keys().insert(
        AccessKey("", app_id, ()))
    storage.close()
    return key


def _wait_ready(proc, base, timeout=90):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            out = proc.stdout.read().decode(errors="replace")
            raise AssertionError(
                f"front died before ready (rc={proc.returncode}):\n"
                f"{out[-3000:]}")
        try:
            if requests.get(base + "/", timeout=2).status_code == 200:
                return
        except requests.RequestException:
            time.sleep(0.1)
    proc.kill()
    raise AssertionError("front not ready in time")


def _wait_partitions(proc, base, partitions, timeout=90):
    """Wait until every worker in ``partitions`` answers through the
    front. The front answers as soon as ONE worker listens, and skips a
    worker that still refuses connects, so traffic sent before the rest
    bind would all land on the first one. Each ``GET /`` here opens a
    fresh connection, which the front hands to the next worker in turn."""
    deadline = time.monotonic() + timeout
    seen: set = set()
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            out = proc.stdout.read().decode(errors="replace")
            raise AssertionError(
                f"front died before workers {sorted(partitions)} "
                f"answered (rc={proc.returncode}):\n{out[-3000:]}")
        try:
            seen.add(requests.get(base + "/", timeout=2).json()
                     .get("partition"))
        except (requests.RequestException, ValueError):
            pass
        if set(partitions) <= seen:
            return
        time.sleep(0.05)
    proc.kill()
    raise AssertionError(f"workers answered {sorted(seen, key=str)}, "
                         f"expected {sorted(partitions)}")


def _supervisor_doc(tmp_path, front_pid):
    path = os.path.join(str(tmp_path), "pio_store", "gang",
                        f"pid{front_pid}", "supervisor.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def test_multiworker_smoke_disjoint_partitions_and_merged_reads(tmp_path):
    """Fast (no-chaos) multi-worker e2e: `pio eventserver --workers 2`
    serves through the front splice; writes land in per-worker shards
    under held leases, reads through ANY worker see the merged view,
    and SIGTERM drains the service cleanly (rc 0)."""
    env = _make_mw_env(tmp_path,
                       PIO_FS_BASEDIR=str(tmp_path / "pio_store"))
    key = _prepare_metadata(env)
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    proc = subprocess.Popen(
        [sys.executable, "-m", "incubator_predictionio_tpu.tools.console",
         "eventserver", "--workers", "2", "--ip", "127.0.0.1",
         "--port", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        _wait_ready(proc, base)
        _wait_partitions(proc, base, (0, 1))
        acked = []
        # sessions pin a connection → a backend; two sessions land on
        # different workers (round-robin), proving disjoint ownership
        for s in (requests.Session(), requests.Session()):
            for i in range(10):
                r = s.post(f"{base}/events.json?accessKey={key}",
                           json=_ev(len(acked)), timeout=15)
                assert r.status_code == 201, r.text
                acked.append(r.json()["eventId"])
        r = requests.get(f"{base}/events.json?accessKey={key}&limit=-1",
                         timeout=30)
        got = [e["eventId"] for e in r.json()]
        assert sorted(got) == sorted(acked), "merged read lost events"
        ev_dir = os.path.join(str(tmp_path), "events", "pio_eventdata")
        shards = sorted(n for n in os.listdir(ev_dir)
                        if n.endswith(".jsonl"))
        assert shards == ["events_1.p0.jsonl", "events_1.p1.jsonl"], shards
        for p in (0, 1):
            info = event_log.lease_info(ev_dir, p)
            assert info is not None and info["held"], info
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
        assert proc.returncode == 0, out.decode(errors="replace")[-2000:]
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()


@pytest.mark.slow  # ~26s: 3 interpreter startups + 2 injected crashes
def test_multiworker_kill_midcommit_replays_acked_exactly_once(tmp_path):
    """The ISSUE 8 headline harness: `pio eventserver --workers 2`,
    REAL subprocesses; the chaos hook SIGKILLs each worker inside its
    3rd group commit (first launch only); the per-worker supervisor
    relaunches them (startup replays their OWN WAL partition); after
    the dust settles every acked event is present exactly once and the
    service answered throughout (the surviving worker held the fort)."""
    env = _make_mw_env(
        tmp_path,
        PIO_INGEST_ACK="enqueue",
        PIO_INGEST_GROUP_MS="40",
        PIO_EVENT_WORKER_FAULT_SPEC="ingest.commit:crash:3",
        PIO_FS_BASEDIR=str(tmp_path / "pio_store"),
    )
    key = _prepare_metadata(env)
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    proc = subprocess.Popen(
        [sys.executable, "-m", "incubator_predictionio_tpu.tools.console",
         "eventserver", "--workers", "2", "--ip", "127.0.0.1",
         "--port", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        _wait_ready(proc, base)
        acked = []
        deadline = time.monotonic() + 120
        i = 0
        # drive until the supervisor reports BOTH workers crashed and
        # relaunched (the injected crash:3 fires per worker), with a
        # hard wall-clock bound
        while time.monotonic() < deadline:
            try:
                r = requests.post(f"{base}/events.json?accessKey={key}",
                                  json=_ev(i), timeout=10)
                if r.status_code == 201:
                    acked.append(r.json()["eventId"])
            except requests.RequestException:
                pass  # the spliced backend died mid-request: not acked
            i += 1
            if i % 50 == 0:
                doc = _supervisor_doc(tmp_path, proc.pid)
                if doc is not None:
                    failures = {e.get("worker") for e in doc["events"]
                                if e["type"] == "workerFailure"}
                    if failures >= {0, 1} and len(acked) >= 60:
                        break
            time.sleep(0.005)
        doc = _supervisor_doc(tmp_path, proc.pid)
        assert doc is not None, "supervisor never published status"
        failures = {e.get("worker") for e in doc["events"]
                    if e["type"] == "workerFailure"}
        assert failures >= {0, 1}, (
            f"injected SIGKILL did not fire on both workers: {failures}")
        restarts = [e for e in doc["events"]
                    if e["type"] == "workerRestart"]
        assert restarts, "supervisor never relaunched a worker"
        assert len(acked) >= 30, "service never made progress"
        # quiesce: give restarts + replays time to finish, then read
        # everything back through the front (merged view)
        deadline = time.monotonic() + 60
        got = None
        while time.monotonic() < deadline:
            try:
                r = requests.get(
                    f"{base}/events.json?accessKey={key}&limit=-1",
                    timeout=30)
                if r.status_code == 200:
                    got = [e["eventId"] for e in r.json()]
                    if all(got.count(a) == 1 for a in acked):
                        break
            except requests.RequestException:
                pass
            time.sleep(0.5)
        assert got is not None, "service unreadable after chaos"
        missing = [a for a in acked if got.count(a) == 0]
        dupes = [a for a in acked if got.count(a) > 1]
        assert not missing, f"{len(missing)} acked event(s) lost"
        assert not dupes, f"acked event(s) duplicated: {dupes[:3]}"
        assert len(got) == len(set(got)), "non-acked duplicates"
        # both partitions actually took writes (disjoint ownership)
        ev_dir = os.path.join(str(tmp_path), "events", "pio_eventdata")
        shards = sorted(n for n in os.listdir(ev_dir)
                        if n.endswith(".jsonl"))
        assert "events_1.p0.jsonl" in shards
        assert "events_1.p1.jsonl" in shards
    finally:
        proc.terminate()
        try:
            proc.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------

def test_partition_marker_registered():
    import pathlib

    import incubator_predictionio_tpu

    pyproject = (pathlib.Path(incubator_predictionio_tpu.__file__)
                 .parent.parent / "pyproject.toml").read_text()
    assert "partition:" in pyproject


def test_guard_only_event_log_modules_open_log_artifacts():
    """AST guard (satellite): only data/api/event_log.py and
    data/api/ingest_wal.py may open ``.wal`` / ``.colseg`` /
    ``.manifest`` files — every other module under data/ and workflow/
    must go through them, or segment lifecycle (leases, quarantine,
    manifest commits) silently forks. Enforced by the shared
    `pio lint` engine."""
    from incubator_predictionio_tpu.tools.lint import assert_rule_clean

    assert_rule_clean("wal-suffix-confinement")
