"""Gang supervision for multi-worker training — worker liveness,
heartbeats, and checkpoint gang-restart.

Production SPMD training treats worker death as routine: every process
participates in every collective, so ONE dead or wedged worker leaves the
survivors blocked in the next all-reduce forever. The recovery model is
the TensorFlow one (arxiv 1605.08695) — supervise the gang, and on any
failure kill ALL of it and relaunch from the last checkpoint — rather
than lineage recomputation. This module is that supervisor:

- :class:`Supervisor` spawns N worker processes with the
  coordinator/process-id env wiring (``PIO_COORDINATOR_ADDRESS``,
  ``PIO_NUM_PROCESSES``, ``PIO_PROCESS_ID``), watches process liveness
  AND per-worker heartbeat files, and on a nonzero exit, worker death,
  or heartbeat stall kills the whole gang and relaunches it with
  ``--resume`` — bounded by ``PIO_TRAIN_MAX_RESTARTS`` with jittered
  exponential backoff (common/resilience.RetryPolicy). SIGTERM on the
  supervisor drains the gang cleanly instead (workers checkpoint at the
  next sweep boundary and exit; the run stays ``--resume``-able).
- Workers call :func:`beat` between ALS sweeps (hooked in ``ops/als.py``
  and ``workflow/core_workflow.py``): a cheap mtime touch of
  ``PIO_WORKER_HEARTBEAT_FILE``. A worker that is alive-but-wedged
  (SIGSTOP, deadlocked collective, hung storage read) stops beating and
  the stall detector catches what ``poll()`` cannot.
- Drain is collective: :func:`drain_requested_global` allgathers the
  local SIGTERM flag across the gang at each sweep boundary, so every
  process takes the drain branch at the SAME iteration and the
  checkpoint barrier cannot deadlock against a peer that missed the
  signal by one sweep.

Telemetry (PR 4 registry): ``pio_train_restarts_total{reason}``,
``pio_train_worker_alive{worker}``,
``pio_train_worker_heartbeat_age_seconds{worker}``,
``pio_train_gang_state``. The same numbers (plus an event log with
timestamps — what the gang bench bracket reads) are mirrored to
``<run_dir>/supervisor.json`` so a foreign process can watch a live gang.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Callable, Optional, Sequence

from ..common import envknobs, telemetry

log = logging.getLogger("pio.supervisor")

__all__ = [
    "GangConfig", "GangDrainRequested", "Supervisor", "beat",
    "beat_while", "drain_requested", "drain_requested_global",
    "gang_active", "install_worker_signal_handlers", "request_drain",
    "reset_drain",
]

# env the supervisor sets on every worker
ENV_HEARTBEAT_FILE = "PIO_WORKER_HEARTBEAT_FILE"
ENV_GANG_WORKER = "PIO_GANG_WORKER"
ENV_GANG_INSTANCE_ID = "PIO_GANG_INSTANCE_ID"

# terminal states Supervisor.run() can land in
COMPLETED, DRAINED, FAILED = "completed", "drained", "failed"

#: exit code of a worker that checkpointed and exited at a drain request
#: (GangDrainRequested). NOT a failure: a worker can be drained without
#: the supervisor's stop flag being set (operator SIGTERMs a worker
#: directly — the allgathered flag drains the whole gang), and restarting
#: a run the operator just stopped would burn the restart budget on
#: exactly the wrong thing.
DRAIN_EXIT_CODE = 3


# ---------------------------------------------------------------------------
# worker-side hooks (heartbeat + drain flag)
# ---------------------------------------------------------------------------

_hb_lock = threading.Lock()
_hb_last = 0.0
_hb_interval: Optional[float] = None
_drain_event = threading.Event()


def gang_active() -> bool:
    """True inside a supervised training worker."""
    return os.environ.get(ENV_GANG_WORKER) == "1"


def beat() -> None:
    """Touch this worker's heartbeat file (no-op outside a gang).

    Called between training sweeps; throttled to half the configured
    heartbeat interval so a microsecond-sweep loop doesn't turn into an
    utime storm. The file is created on the first call — the supervisor
    treats creation as 'worker reached the training loop' and only then
    arms the stall detector.
    """
    path = os.environ.get(ENV_HEARTBEAT_FILE)
    if not path:
        return
    global _hb_last, _hb_interval
    now = time.monotonic()
    with _hb_lock:
        if _hb_interval is None:
            _hb_interval = max(
                0.01, envknobs.env_ms("PIO_WORKER_HEARTBEAT_MS", 1000.0,
                                      lo_ms=20.0) / 2.0)
        if now - _hb_last < _hb_interval:
            return
        _hb_last = now
    try:
        with open(path, "a"):
            pass
        os.utime(path, None)
    except OSError:  # heartbeat dir vanished: the supervisor is gone
        log.debug("heartbeat touch failed for %s", path, exc_info=True)


class beat_while:
    """Context manager: background thread beats every ``interval`` while
    the body runs. For phases with no natural beat points — the gang
    leader's model persistence (device_get + pickle + storage insert can
    dwarf the stall threshold at scale, and a training job whose TRAINING
    succeeded must not be gang-killed while saving the result). Storage
    hangs inside the block are not masked forever: egress runs under
    resilience retry/deadline budgets, and the supervisor's drain SIGKILL
    remains the backstop. No-op outside a gang."""

    def __init__(self, interval: float = 5.0):
        self.interval = interval
        self._stop: Optional[threading.Event] = None
        self._t: Optional[threading.Thread] = None

    def __enter__(self):
        if not os.environ.get(ENV_HEARTBEAT_FILE):
            return self
        self._stop = threading.Event()

        def _pump(stop):
            while not stop.wait(self.interval):
                beat()

        self._t = threading.Thread(
            target=_pump, args=(self._stop,), daemon=True)
        self._t.start()
        return self

    def __exit__(self, *exc):
        if self._stop is not None:
            self._stop.set()
            self._t.join(timeout=5)
        return False


def request_drain(signum=None, frame=None) -> None:
    """SIGTERM handler body: ask the training loop to checkpoint and
    exit at the next sweep boundary."""
    _drain_event.set()


def drain_requested() -> bool:
    return _drain_event.is_set()


def reset_drain() -> None:
    _drain_event.clear()


def drain_requested_global() -> bool:
    """Gang-consistent drain flag, checked between sweeps.

    Multi-process gangs allgather the local flag so every process sees
    the SAME answer at the SAME sweep boundary — otherwise the process
    that caught SIGTERM a sweep earlier would enter the checkpoint
    barrier while its peers enter the next training collective, and the
    gang would deadlock (the supervisor's drain deadline would SIGKILL
    it, losing the drain checkpoint). Single-process runs read the local
    flag directly; non-gang runs never pay the collective.
    """
    if not gang_active():
        return _drain_event.is_set()
    import jax

    if jax.process_count() <= 1:
        return _drain_event.is_set()
    import numpy as np
    from jax.experimental import multihost_utils

    flags = multihost_utils.process_allgather(
        np.int32(1 if _drain_event.is_set() else 0))
    return bool(np.asarray(flags).max())


def install_worker_signal_handlers() -> None:
    """Route SIGTERM (and SIGINT, which the supervisor's process group
    forwards on Ctrl-C) to the drain flag instead of killing the worker
    mid-sweep. Main-thread only — signal.signal requires it."""
    signal.signal(signal.SIGTERM, request_drain)
    signal.signal(signal.SIGINT, request_drain)


class GangDrainRequested(Exception):
    """Raised by a training loop after it checkpointed at a drain
    request; the worker exits and the supervisor stops without
    restarting (the run resumes later with ``--resume``)."""

    def __init__(self, step: int):
        super().__init__(f"gang drain requested; checkpointed at step {step}")
        self.step = int(step)


# ---------------------------------------------------------------------------
# supervisor config
# ---------------------------------------------------------------------------

class GangConfig:
    """Resolved supervision knobs (all overridable via environment).

    - ``PIO_NUM_WORKERS`` — gang size (``pio train --num-workers`` wins)
    - ``PIO_WORKER_HEARTBEAT_MS`` — worker touch cadence (default 1s)
    - ``PIO_WORKER_STALL_MS`` — heartbeat age that declares a live
      process wedged (default 120s: stalls are judged against sweep
      cadence, and a saturated host can stretch a sweep a lot further
      than it can stretch a poll)
    - ``PIO_WORKER_INIT_GRACE_MS`` — budget from spawn to FIRST beat
      (default 600s: covers jax.distributed init + XLA compile, which
      beat nothing)
    - ``PIO_TRAIN_MAX_RESTARTS`` — gang relaunch budget (default 3)
    - ``PIO_TRAIN_DRAIN_MS`` — SIGTERM→SIGKILL grace during drain
      (default 30s)
    - ``PIO_SUPERVISOR_POLL_MS`` — monitor cadence (default 200ms)
    """

    __slots__ = ("num_workers", "heartbeat_ms", "stall_ms", "init_grace_ms",
                 "max_restarts", "drain_ms", "poll_ms")

    def __init__(self, num_workers: int = 1, heartbeat_ms: float = 1000.0,
                 stall_ms: float = 120_000.0, init_grace_ms: float = 600_000.0,
                 max_restarts: int = 3, drain_ms: float = 30_000.0,
                 poll_ms: float = 200.0):
        self.num_workers = max(1, int(num_workers))
        self.heartbeat_ms = max(20.0, float(heartbeat_ms))
        self.stall_ms = max(self.heartbeat_ms * 2, float(stall_ms))
        self.init_grace_ms = max(self.stall_ms, float(init_grace_ms))
        self.max_restarts = max(0, int(max_restarts))
        self.drain_ms = max(0.0, float(drain_ms))
        self.poll_ms = min(max(10.0, float(poll_ms)), self.heartbeat_ms)

    @classmethod
    def from_env(cls, num_workers: Optional[int] = None) -> "GangConfig":
        return cls(
            num_workers=(num_workers if num_workers is not None
                         else envknobs.env_int("PIO_NUM_WORKERS", 1, lo=1)),
            heartbeat_ms=envknobs.env_float(
                "PIO_WORKER_HEARTBEAT_MS", 1000.0, lo=20.0),
            stall_ms=envknobs.env_float(
                "PIO_WORKER_STALL_MS", 120_000.0, lo=100.0),
            init_grace_ms=envknobs.env_float(
                "PIO_WORKER_INIT_GRACE_MS", 600_000.0, lo=1000.0),
            max_restarts=envknobs.env_int(
                "PIO_TRAIN_MAX_RESTARTS", 3, lo=0),
            drain_ms=envknobs.env_float(
                "PIO_TRAIN_DRAIN_MS", 30_000.0, lo=0.0),
            poll_ms=envknobs.env_float(
                "PIO_SUPERVISOR_POLL_MS", 200.0, lo=10.0),
        )

    def to_json(self) -> dict:
        return {s: getattr(self, s) for s in self.__slots__}


# ---------------------------------------------------------------------------
# telemetry (process-wide; created lazily so importing this module costs
# nothing in processes that never supervise)
# ---------------------------------------------------------------------------

def _metrics():
    reg = telemetry.registry()
    return (
        reg.counter("pio_train_restarts_total",
                    "Gang restarts by failure reason", ("reason",)),
        reg.gauge("pio_train_worker_alive",
                  "1 while the worker process is running", ("worker",)),
        reg.gauge("pio_train_worker_heartbeat_age_seconds",
                  "Seconds since the worker last touched its heartbeat file",
                  ("worker",)),
        reg.gauge("pio_train_gang_state",
                  "0 idle, 1 running, 2 draining, 3 failed").labels(),
    )


# ---------------------------------------------------------------------------
# the supervisor
# ---------------------------------------------------------------------------

class _Worker:
    __slots__ = ("idx", "proc", "hb_path", "log_path", "spawned_at",
                 "hb_token", "hb_seen_at")

    def __init__(self, idx, proc, hb_path, log_path, spawned_at):
        self.idx = idx
        self.proc = proc
        self.hb_path = hb_path
        self.log_path = log_path
        self.spawned_at = spawned_at
        # mtime is only an opaque CHANGE token; ages are measured on the
        # monotonic clock from when the change was observed, so an NTP
        # step can neither spuriously stall a healthy gang nor hide a
        # genuinely wedged worker.
        self.hb_token = None
        self.hb_seen_at = None

    def heartbeat_age_ms(self) -> Optional[float]:
        """Monotonic ms since the last observed beat, or None before the
        first one (the init grace window covers distributed init +
        compile)."""
        try:
            token = os.stat(self.hb_path).st_mtime_ns
        except OSError:
            return None
        now = time.monotonic()
        if token != self.hb_token:
            self.hb_token = token
            self.hb_seen_at = now
        return max(0.0, (now - self.hb_seen_at) * 1000.0)


class Supervisor:
    """Launch and babysit one training gang until it completes, drains,
    or exhausts its restart budget.

    ``worker_argv`` is the full command line of ONE worker; the
    supervisor adds only environment (coordinator wiring, heartbeat
    file, gang marker) and — on restart attempts — ``resume_argv`` so
    the relaunched gang continues from the latest checkpoint.

    ``per_worker_env`` (worker idx → env overrides) applies to the
    FIRST launch only: it exists to arm per-worker chaos
    (``PIO_FAULT_SPEC`` crash/latency rules) and a restarted gang must
    come up clean or the same injected fault would kill every relaunch.
    Pass a callable ``(attempt, worker_idx) -> dict`` to control every
    attempt explicitly.

    This class is the ONLY sanctioned spawner of training worker
    processes (guard-tested, like the ingest buffer's single dispatch
    path): liveness, restart accounting, and drain semantics all assume
    every gang member is on the supervisor's books.
    """

    def __init__(self, worker_argv: Sequence[str],
                 num_workers: Optional[int] = None, *,
                 env: Optional[dict] = None,
                 per_worker_env=None,
                 config: Optional[GangConfig] = None,
                 run_dir: Optional[str] = None,
                 gang_instance_id: Optional[str] = None,
                 resume_argv: Sequence[str] = ("--resume",),
                 coordinator_host: str = "127.0.0.1",
                 wire_coordinator: bool = True,
                 restart_scope: str = "gang"):
        """``wire_coordinator=False`` skips the jax.distributed env
        (``PIO_COORDINATOR_ADDRESS`` + the per-attempt port): the
        workers are independent servers, not an SPMD gang.

        ``restart_scope`` selects the recovery model. ``"gang"`` (the
        training default): every process participates in every
        collective, so ONE failure kills and relaunches ALL of them
        from the checkpoint. ``"worker"`` (services — the partitioned
        event server): workers share nothing at runtime, so a dead or
        wedged worker is killed and relaunched INDIVIDUALLY (its
        startup replays its own WAL partition) while the rest keep
        serving; ``max_restarts`` is a per-worker budget, and ANY exit
        — including rc 0 — is a failure, because a service worker has
        no legitimate reason to stop while supervised."""
        if restart_scope not in ("gang", "worker"):
            raise ValueError(f"restart_scope {restart_scope!r}")
        self.worker_argv = list(worker_argv)
        self.config = config or GangConfig.from_env(num_workers)
        if num_workers is not None:
            self.config.num_workers = max(1, int(num_workers))
        self.wire_coordinator = wire_coordinator
        self.restart_scope = restart_scope
        self.base_env = dict(os.environ if env is None else env)
        if callable(per_worker_env):
            self._env_for = per_worker_env
        else:
            first = {int(k): dict(v) for k, v in (per_worker_env or {}).items()}
            self._env_for = lambda attempt, idx: (
                first.get(idx, {}) if attempt == 0 else {})
        self.run_dir = run_dir or self._default_run_dir(gang_instance_id)
        self.gang_instance_id = gang_instance_id
        self.resume_argv = list(resume_argv)
        self.coordinator_host = coordinator_host

        self.restarts = 0
        # per-worker relaunch counts (restart_scope="worker"): read by
        # the fleet front's /healthz aggregation and mirrored into
        # supervisor.json; gang-scope restarts stay in self.restarts
        self.worker_restarts = [0] * self.config.num_workers
        self.state = "idle"
        self.events: list[dict] = []
        self._workers: list[_Worker] = []
        self._stop = threading.Event()
        self._attempt = 0
        # dynamic membership (restart_scope="worker" only): add/retire
        # requests land here from any thread and are applied by the
        # supervision loop itself, so every spawn happens on the
        # SUPERVISOR thread — pdeathsig binds to the spawning thread,
        # and a late-added worker must share the initial workers'
        # parent-death contract, not a shorter-lived caller's
        self._membership_lock = threading.Lock()
        self._membership_cmds: list[tuple[str, int]] = []
        # slots accepted but not yet spawned (launch-time workers before
        # the supervision thread starts them, added ones before the next
        # sweep): such a slot is starting, not dead
        self._unspawned: set[int] = set(range(self.config.num_workers))
        # idx -> monotonic SIGKILL deadline; a retiring worker is
        # EXPECTED to exit, so the any-exit-is-failure service rule and
        # the stall detector both skip it
        self._retiring: dict[int, float] = {}
        os.makedirs(self.run_dir, exist_ok=True)

    # -- plumbing ----------------------------------------------------------

    @staticmethod
    def _default_run_dir(gang_id: Optional[str]) -> str:
        from ..data.storage.registry import base_dir

        return os.path.join(base_dir(), "gang", gang_id or f"pid{os.getpid()}")

    @staticmethod
    def _free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def request_stop(self, signum=None, frame=None) -> None:
        """SIGTERM entry: drain the gang and stop (no restart)."""
        self._stop.set()

    def install_signal_handlers(self) -> None:
        """Main-thread only (CLI path; tests call request_stop())."""
        signal.signal(signal.SIGTERM, self.request_stop)
        signal.signal(signal.SIGINT, self.request_stop)

    def _event(self, type_: str, **kw) -> None:
        self.events.append({"type": type_, "t": time.time(), **kw})

    def worker_pids(self) -> list[Optional[int]]:
        return [w.proc.pid if w.proc.poll() is None else None
                for w in self._workers]

    def _worker_by_idx(self, idx: int) -> Optional[_Worker]:
        for w in self._workers:
            if w.idx == idx:
                return w
        return None

    def worker_pid(self, idx: int) -> Optional[int]:
        """Keyed pid lookup — positional ``worker_pids()`` stops being
        meaningful once dynamic membership leaves index gaps."""
        w = self._worker_by_idx(idx)
        if w is None or w.proc.poll() is not None:
            return None
        return w.proc.pid

    def worker_starting(self, idx: int) -> bool:
        """True while slot ``idx`` is accepted but not yet spawned, so
        ``worker_pid`` reads None for a worker that is coming up."""
        with self._membership_lock:
            return idx in self._unspawned

    def live_worker_indices(self) -> list[int]:
        """Indices on the books and not mid-retirement."""
        return sorted(w.idx for w in self._workers
                      if w.idx not in self._retiring)

    def is_retiring(self, idx: int) -> bool:
        return idx in self._retiring

    # -- dynamic membership (service scope) --------------------------------

    def add_worker(self, idx: Optional[int] = None) -> int:
        """Enqueue a NEW service worker at slot ``idx`` (lowest free
        slot when None); returns the slot. The spawn itself happens on
        the supervision thread at its next sweep — same heartbeat
        registration, restart budget, and parent-death arming as a
        launch-time worker. Thread-safe; ``restart_scope='worker'``
        only (a gang's size is its collective's world size)."""
        if self.restart_scope != "worker":
            raise RuntimeError("dynamic membership requires "
                               "restart_scope='worker'")
        with self._membership_lock:
            taken = {w.idx for w in self._workers}
            taken.update(i for op, i in self._membership_cmds
                         if op == "add")
            if idx is None:
                idx = 0
                while idx in taken:
                    idx += 1
            elif idx in taken:
                raise ValueError(f"worker {idx} is already on the books")
            self._membership_cmds.append(("add", int(idx)))
            self._unspawned.add(int(idx))
        return int(idx)

    def retire_worker(self, idx: int) -> None:
        """Enqueue a graceful retirement of worker ``idx``: the
        supervision thread SIGTERMs it (the worker's normal drain
        path), exempts it from failure detection, and books it out
        when it exits — SIGKILL only past the drain budget. Thread-
        safe; ``restart_scope='worker'`` only."""
        if self.restart_scope != "worker":
            raise RuntimeError("dynamic membership requires "
                               "restart_scope='worker'")
        with self._membership_lock:
            self._membership_cmds.append(("retire", int(idx)))

    def _apply_membership(self) -> None:
        """Drain queued add/retire commands (supervision thread)."""
        with self._membership_lock:
            cmds, self._membership_cmds = self._membership_cmds, []
        for op, idx in cmds:
            if op == "add":
                if self._worker_by_idx(idx) is not None:
                    continue  # raced a concurrent add of the same slot
                while len(self.worker_restarts) <= idx:
                    self.worker_restarts.append(0)
                self._workers.append(
                    self._spawn_worker(idx, None, resume=False, attempt=0))
                with self._membership_lock:
                    self._unspawned.discard(idx)
                self._event("workerAdded", worker=idx)
                log.info("service worker %d added (now %d on the books)",
                         idx, len(self._workers))
            else:
                w = self._worker_by_idx(idx)
                if w is None or idx in self._retiring:
                    continue
                self._retiring[idx] = (time.monotonic()
                                       + self.config.drain_ms / 1000.0)
                if w.proc.poll() is None:
                    try:
                        w.proc.send_signal(signal.SIGTERM)
                    except OSError:
                        pass
                self._event("workerRetireStart", worker=idx)
                log.info("service worker %d retiring (drain budget "
                         "%.1fs)", idx, self.config.drain_ms / 1000.0)

    def _reap_retiring(self) -> None:
        """Book out retiring workers that exited; SIGKILL past the
        drain deadline (supervision thread)."""
        if not self._retiring:
            return
        now = time.monotonic()
        for idx in list(self._retiring):
            w = self._worker_by_idx(idx)
            if w is None:
                del self._retiring[idx]
                continue
            rc = w.proc.poll()
            if rc is None and now >= self._retiring[idx]:
                try:
                    w.proc.kill()
                except OSError:
                    pass
                w.proc.wait()
                rc = w.proc.poll()
            if rc is not None:
                self._workers.remove(w)
                del self._retiring[idx]
                self._event("workerRetired", worker=idx, rc=rc)
                log.info("service worker %d retired (rc %s, %d still "
                         "on the books)", idx, rc, len(self._workers))

    # -- gang lifecycle ----------------------------------------------------

    def _spawn_worker(self, i: int, port: Optional[int],
                      resume: bool, attempt: int) -> _Worker:
        cfg = self.config
        argv = list(self.worker_argv)
        if resume:
            for tok in self.resume_argv:
                if tok not in argv:
                    argv.append(tok)
        hb = os.path.join(self.run_dir, f"worker_{i}.hb")
        try:  # stall ages are measured against THIS attempt only
            os.unlink(hb)
        except OSError:
            pass
        env = {
            **self.base_env,
            "PIO_NUM_PROCESSES": str(cfg.num_workers),
            "PIO_PROCESS_ID": str(i),
            ENV_GANG_WORKER: "1",
            ENV_HEARTBEAT_FILE: hb,
            "PIO_WORKER_HEARTBEAT_MS": str(cfg.heartbeat_ms),
            **self._env_for(attempt, i),
        }
        if self.wire_coordinator and port is not None:
            env["PIO_COORDINATOR_ADDRESS"] = \
                f"{self.coordinator_host}:{port}"
        if self.gang_instance_id:
            env[ENV_GANG_INSTANCE_ID] = self.gang_instance_id
        log_path = os.path.join(self.run_dir, f"worker_{i}.log")
        logf = open(log_path, "ab")
        try:
            proc = subprocess.Popen(
                argv, env=env, stdout=logf, stderr=subprocess.STDOUT)
        finally:
            logf.close()  # the child holds its own fd now
        return _Worker(i, proc, hb, log_path, time.monotonic())

    def _spawn_gang(self, resume: bool) -> None:
        cfg = self.config
        port = self._free_port() if self.wire_coordinator else None
        self._workers = [
            self._spawn_worker(i, port, resume, self._attempt)
            for i in range(cfg.num_workers)
        ]
        with self._membership_lock:
            self._unspawned.difference_update(range(cfg.num_workers))
        self._event("gangStart", attempt=self._attempt, resume=resume,
                    port=port,
                    pids=[w.proc.pid for w in self._workers])
        log.info("gang attempt %d: %d worker(s) up (resume=%s, "
                 "coordinator port %s)", self._attempt, cfg.num_workers,
                 resume, port)

    def _kill_gang(self, sig: int = signal.SIGKILL) -> None:
        for w in self._workers:
            if w.proc.poll() is None:
                try:
                    w.proc.send_signal(sig)
                except OSError:
                    pass
        for w in self._workers:
            try:
                w.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:  # pragma: no cover
                w.proc.kill()
                w.proc.wait()

    def _check_failure(self) -> Optional[dict]:
        """One monitor sweep → failure descriptor or None."""
        cfg = self.config
        now = time.monotonic()
        for w in self._workers:
            rc = w.proc.poll()
            if rc is not None:
                if rc not in (0, DRAIN_EXIT_CODE):
                    return {"reason": "exit", "worker": w.idx, "rc": rc}
                continue
            age = w.heartbeat_age_ms()
            if age is None:
                if (now - w.spawned_at) * 1000.0 > cfg.init_grace_ms:
                    return {"reason": "no_heartbeat", "worker": w.idx}
            elif age > cfg.stall_ms:
                return {"reason": "stall", "worker": w.idx,
                        "age_ms": round(age, 1)}
        # Workers exiting 0 before their peers is normal (they don't all
        # reach exit in the same poll window); a survivor blocked in a
        # dead collective is caught by the stall detector above.
        return None

    def _publish(self, state_code: float) -> None:
        _, alive_g, age_g, state_g = _metrics()
        workers = []
        for w in self._workers:
            alive = w.proc.poll() is None
            age = w.heartbeat_age_ms()
            alive_g.labels(str(w.idx)).set(1.0 if alive else 0.0)
            age_g.labels(str(w.idx)).set(-1.0 if age is None else age / 1000.0)
            workers.append({
                "worker": w.idx,
                "pid": w.proc.pid,
                "alive": alive,
                "returncode": w.proc.poll(),
                "heartbeatAgeMs": age,
                "retiring": w.idx in self._retiring,
                "restarts": (self.worker_restarts[w.idx]
                             if w.idx < len(self.worker_restarts) else 0),
                "log": w.log_path,
            })
        state_g.set(state_code)
        doc = {
            "gangInstanceId": self.gang_instance_id,
            "state": self.state,
            "attempt": self._attempt,
            "restarts": self.restarts,
            "config": self.config.to_json(),
            "workers": workers,
            "events": self.events,
        }
        tmp = os.path.join(self.run_dir, ".supervisor.json.tmp")
        try:
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1)
            os.replace(tmp, os.path.join(self.run_dir, "supervisor.json"))
        except OSError:  # pragma: no cover - run_dir ripped out under us
            log.debug("could not publish supervisor status", exc_info=True)

    def _drain(self) -> None:
        """SIGTERM every worker, give them the drain budget to
        checkpoint and exit, SIGKILL stragglers."""
        self.state = "draining"
        self._event("drainStart")
        self._publish(2.0)
        for w in self._workers:
            if w.proc.poll() is None:
                try:
                    w.proc.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        deadline = time.monotonic() + self.config.drain_ms / 1000.0
        while time.monotonic() < deadline:
            if all(w.proc.poll() is not None for w in self._workers):
                break
            time.sleep(self.config.poll_ms / 1000.0)
        stragglers = [w.idx for w in self._workers if w.proc.poll() is None]
        self._kill_gang()
        self._event("drainDone", stragglers=stragglers)
        if stragglers:
            log.warning("drain deadline hit; SIGKILLed worker(s) %s — the "
                        "run resumes from the last completed checkpoint",
                        stragglers)

    def _tail(self, w: _Worker, n: int = 2000) -> str:
        try:
            with open(w.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode(errors="replace")
        except OSError:
            return "<no log>"

    def _check_service_failure(self) -> Optional[dict]:
        """Per-worker failure sweep for ``restart_scope='worker'``: ANY
        exit is a failure (a supervised service worker has no reason to
        stop), plus the same no-first-beat / heartbeat-stall detection
        the gang path uses."""
        cfg = self.config
        now = time.monotonic()
        for w in self._workers:
            if w.idx in self._retiring:
                continue  # an exit is the POINT of retirement
            rc = w.proc.poll()
            if rc is not None:
                return {"reason": "exit", "worker": w.idx, "rc": rc}
            age = w.heartbeat_age_ms()
            if age is None:
                if (now - w.spawned_at) * 1000.0 > cfg.init_grace_ms:
                    return {"reason": "no_heartbeat", "worker": w.idx}
            elif age > cfg.stall_ms:
                return {"reason": "stall", "worker": w.idx,
                        "age_ms": round(age, 1)}
        return None

    def _run_service(self) -> str:
        """Per-worker supervision: a failed worker is killed and
        relaunched alone (no checkpoint, no resume argv — a fresh
        server whose startup replays its own WAL partition) while its
        peers keep serving. Terminal states: ``drained`` (stop
        requested) or ``failed`` (one worker exhausted its per-worker
        restart budget)."""
        from ..common.resilience import RetryPolicy

        cfg = self.config
        backoff = RetryPolicy(max_attempts=cfg.max_restarts + 1,
                              base_delay=0.5, max_delay=15.0)
        per_worker_restarts = self.worker_restarts
        self._attempt = 0
        self.state = "running"
        self._spawn_gang(resume=False)
        self._publish(1.0)
        last_publish = 0.0
        while True:
            if self._stop.is_set():
                self._drain()
                self.state = DRAINED
                self._publish(0.0)
                log.info("service drained cleanly (%d worker(s))",
                         len(self._workers))
                return DRAINED
            self._apply_membership()
            self._reap_retiring()
            failure = self._check_service_failure()
            if failure is not None:
                idx = failure["worker"]
                bad = self._worker_by_idx(idx)
                log.warning("service worker %d failed (%s); relaunching "
                            "it. log tail:\n%s", idx, failure,
                            self._tail(bad))
                self._event("workerFailure", **failure)
                if bad.proc.poll() is None:
                    try:
                        bad.proc.send_signal(signal.SIGKILL)
                    except OSError:
                        pass
                    bad.proc.wait()
                restarts_c, *_ = _metrics()
                restarts_c.labels(failure["reason"]).inc()
                while len(per_worker_restarts) <= idx:
                    per_worker_restarts.append(0)
                per_worker_restarts[idx] += 1
                self.restarts += 1
                if per_worker_restarts[idx] > cfg.max_restarts:
                    self.state = FAILED
                    self._event("gaveUp", worker=idx,
                                restarts=per_worker_restarts[idx])
                    self._publish(3.0)
                    self._kill_gang()
                    log.error("worker %d exhausted its restart budget "
                              "(%d); stopping the service", idx,
                              cfg.max_restarts)
                    return FAILED
                delay = backoff.backoff(per_worker_restarts[idx] - 1)
                self._event("workerRestart", worker=idx,
                            n=per_worker_restarts[idx],
                            backoff_s=round(delay, 3))
                # bounded wait that still honours a stop request — a
                # drain must not be stuck behind a restart backoff,
                # and a stop that lands DURING the backoff must not
                # spawn (and immediately kill) a fresh worker
                if self._stop.wait(delay):
                    continue
                self._attempt = per_worker_restarts[idx]
                self._workers[self._workers.index(bad)] = \
                    self._spawn_worker(idx, None, resume=False,
                                       attempt=per_worker_restarts[idx])
                self._publish(1.0)
            now = time.monotonic()
            if now - last_publish >= 1.0:
                self._publish(1.0)
                last_publish = now
            time.sleep(cfg.poll_ms / 1000.0)

    def run(self) -> str:
        """Supervise to a terminal state: ``completed`` (every worker
        exited 0), ``drained`` (stop requested; checkpoint preserved),
        or ``failed`` (restart budget exhausted)."""
        if self.restart_scope == "worker":
            return self._run_service()
        cfg = self.config
        restart_backoff = None
        resume = False
        while True:
            if self._stop.is_set():  # SIGTERM landed during backoff
                self.state = DRAINED
                self._publish(0.0)
                return DRAINED
            self._attempt = self.restarts
            self.state = "running"
            self._spawn_gang(resume=resume)
            self._publish(1.0)
            last_publish = 0.0
            failure = None
            while True:
                if self._stop.is_set():
                    self._drain()
                    self.state = DRAINED
                    self._publish(0.0)
                    log.info("gang drained cleanly; resume with "
                             "`pio train --resume` (checkpoints kept)")
                    return DRAINED
                rcs = [w.proc.poll() for w in self._workers]
                if all(rc in (0, DRAIN_EXIT_CODE) for rc in rcs):
                    if any(rc == DRAIN_EXIT_CODE for rc in rcs):
                        # Workers drained without our stop flag: someone
                        # SIGTERMed them directly. Honor it — don't
                        # relaunch a run the operator just stopped.
                        self.state = DRAINED
                        self._event("drainedByWorkers", rcs=rcs)
                        self._publish(0.0)
                        log.info("workers drained on their own SIGTERM; "
                                 "checkpoints kept, resume with --resume")
                        return DRAINED
                    self.state = COMPLETED
                    self._event("completed")
                    self._publish(0.0)
                    return COMPLETED
                failure = self._check_failure()
                if failure is not None:
                    break
                now = time.monotonic()
                if now - last_publish >= 1.0:
                    self._publish(1.0)
                    last_publish = now
                time.sleep(cfg.poll_ms / 1000.0)

            self._event("failure", **failure)
            bad = self._workers[failure["worker"]]
            log.warning(
                "worker %d failed (%s); killing the gang. log tail:\n%s",
                failure["worker"], failure, self._tail(bad))
            self._kill_gang()
            self._event("gangKilled")
            restarts_c, *_ = _metrics()
            restarts_c.labels(failure["reason"]).inc()
            if self.restarts >= cfg.max_restarts:
                self.state = FAILED
                self._event("gaveUp", restarts=self.restarts)
                self._publish(3.0)
                log.error("restart budget exhausted (%d); giving up — the "
                          "last checkpoint remains resumable",
                          self.restarts)
                return FAILED
            self.restarts += 1
            resume = True
            if restart_backoff is None:
                from ..common.resilience import RetryPolicy

                restart_backoff = RetryPolicy(
                    max_attempts=cfg.max_restarts + 1, base_delay=0.5,
                    max_delay=15.0)
            delay = restart_backoff.backoff(self.restarts - 1)
            self._event("restart", n=self.restarts,
                        backoff_s=round(delay, 3))
            log.info("gang restart %d/%d in %.2fs (resume from latest "
                     "checkpoint)", self.restarts, cfg.max_restarts, delay)
            time.sleep(delay)
