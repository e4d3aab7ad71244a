"""Elastic topology (ISSUE 20): the fleet sizes itself under load.

- pure decision units (workflow/elastic.py): floor beats everything,
  shed/utilization pressure grows the fleet, quiet shrinks it by
  draining the least-loaded READY replica (ties break AWAY from the
  canary's slot 0), at-max and no-ready-candidate hold;
- the damped controller: hysteresis (floor skips it — a fleet below
  its floor is failing NOW), cooldown, gates reported on held
  decisions, the 16-entry acted-decision log;
- FrontProxy draining marks: a draining backend is excluded from BOTH
  connect passes and from ready/active counts; freeing a slot clears
  its marks;
- supervisor dynamic membership against REAL subprocesses: deferred
  spawn on the supervision thread (the PDEATHSIG contract), heartbeat
  registration for late-added workers, per-worker restart budgets,
  graceful retirement (workerRetired rc == DRAIN_EXIT_CODE);
- seeded `scale-directive-confinement` lint violation + the
  chokepoint-presence guard;
- soak ramp SLO rows (scale-up-within-bound, drain-on-quiet) red and
  green paths from fabricated fleet-size timelines;
- e2e: a REAL elastic fleet (tests/fleet_front.py ... elastic) grows
  under a query flood and drains back to the floor on quiet with zero
  non-{200,503,504} responses; `pio eventserver scale` rebalances
  partition ownership with every acked event exactly once across the
  drain/claim handoff.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest
import requests

from incubator_predictionio_tpu.common.splice import FrontProxy
from incubator_predictionio_tpu.parallel.supervisor import (
    DRAIN_EXIT_CODE, ENV_HEARTBEAT_FILE, GangConfig, Supervisor)
from incubator_predictionio_tpu.workflow import elastic

from server_utils import free_port

HERE = os.path.dirname(os.path.abspath(__file__))


def _samples(*specs):
    """specs: (slot, ready, pending, limit[, shed_delta[, draining]])"""
    out = []
    for spec in specs:
        slot, ready, pending, limit = spec[:4]
        shed = spec[4] if len(spec) > 4 else 0
        draining = spec[5] if len(spec) > 5 else False
        out.append(elastic.ReplicaSample(
            slot=slot, alive=True, ready=ready, draining=draining,
            pending=pending, pending_limit=limit, shed_delta=shed))
    return out


def _cfg(**kw):
    base = dict(min_replicas=1, max_replicas=3, up_threshold=0.8,
                down_threshold=0.2, hysteresis_ticks=2,
                cooldown_ms=1000.0, tick_ms=100.0)
    base.update(kw)
    return elastic.ElasticConfig(**base)


# ---------------------------------------------------------------------------
# the pure decision function (what `pio fleet plan` replays)
# ---------------------------------------------------------------------------

class TestDecision:
    def test_below_floor_scales_up(self):
        d = elastic.plan(_samples((0, True, 0, 8)), _cfg(min_replicas=2))
        assert (d.direction, d.reason, d.target) == ("up", "floor", 2)

    def test_shed_pressure_scales_up(self):
        d = elastic.plan(_samples((0, True, 1, 8, 5)), _cfg())
        assert (d.direction, d.reason, d.target) == ("up", "shed", 2)
        assert d.shed_delta == 5

    def test_utilization_pressure_scales_up(self):
        d = elastic.plan(_samples((0, True, 7, 8)), _cfg())
        assert (d.direction, d.reason) == ("up", "utilization")

    def test_at_max_holds_under_pressure(self):
        d = elastic.plan(_samples((0, True, 8, 8)), _cfg(max_replicas=1))
        assert (d.direction, d.reason, d.target) == ("hold", "at-max", 1)

    def test_quiet_drains_least_loaded_highest_slot(self):
        # equal load: the tie breaks toward the HIGHEST slot so the
        # canary seat (slot 0) stays populated
        d = elastic.plan(_samples((0, True, 0, 8), (1, True, 0, 8)),
                         _cfg())
        assert (d.direction, d.reason, d.slot, d.target) == \
            ("down", "quiet", 1, 1)
        # unequal load: the least-loaded replica goes, even at slot 0
        d = elastic.plan(_samples((0, True, 0, 8), (1, True, 3, 8)),
                         _cfg(down_threshold=0.5))
        assert (d.direction, d.slot) == ("down", 0)

    def test_quiet_while_settling_holds(self):
        # slot 1 is active-but-not-ready (a scale-up mid-settle):
        # draining now would pick slot 0 — the only READY replica —
        # and cancel the scale-up; the loop must hold instead
        d = elastic.plan(_samples((0, True, 0, 8), (1, False, 0, 8)),
                         _cfg())
        assert (d.direction, d.reason, d.actual) == \
            ("hold", "settling", 2)

    def test_no_ready_replicas_holds(self):
        d = elastic.plan(
            _samples((0, False, 0, 8), (1, False, 0, 8)), _cfg())
        assert (d.direction, d.reason) == ("hold", "settling")

    def test_sheds_veto_scale_down(self):
        d = elastic.plan(_samples((0, True, 0, 8), (1, True, 0, 8, 1)),
                         _cfg())
        assert d.direction != "down"

    def test_at_floor_quiet_is_steady(self):
        d = elastic.plan(_samples((0, True, 0, 8)), _cfg())
        assert (d.direction, d.reason, d.target) == ("hold", "steady", 1)

    def test_draining_replicas_do_not_count_as_actual(self):
        d = elastic.plan(
            _samples((0, True, 0, 8), (1, False, 0, 8, 0, True)),
            _cfg(min_replicas=2))
        assert (d.direction, d.reason, d.actual) == ("up", "floor", 1)


# ---------------------------------------------------------------------------
# the damped controller (hysteresis + cooldown + decision log)
# ---------------------------------------------------------------------------

class TestController:
    def test_hysteresis_gates_until_ticks_agree(self):
        c = elastic.ElasticController(_cfg(hysteresis_ticks=3))
        hot = _samples((0, True, 8, 8))
        d1 = c.observe(hot, now=0.0)
        assert (d1.direction, d1.gates) == ("hold", ("hysteresis",))
        d2 = c.observe(hot, now=0.1)
        assert d2.direction == "hold"
        d3 = c.observe(hot, now=0.2)
        assert (d3.direction, d3.gates) == ("up", ())

    def test_disagreeing_tick_resets_the_counter(self):
        c = elastic.ElasticController(_cfg(hysteresis_ticks=2))
        hot, calm = _samples((0, True, 8, 8)), _samples((0, True, 4, 8))
        c.observe(hot, now=0.0)
        c.observe(calm, now=0.1)             # steady: counters reset
        d = c.observe(hot, now=0.2)
        assert (d.direction, d.gates) == ("hold", ("hysteresis",))

    def test_floor_skips_hysteresis(self):
        c = elastic.ElasticController(
            _cfg(min_replicas=2, hysteresis_ticks=5))
        d = c.observe(_samples((0, True, 0, 8)), now=0.0)
        assert (d.direction, d.reason) == ("up", "floor")

    def test_cooldown_gates_after_an_acted_decision(self):
        c = elastic.ElasticController(
            _cfg(hysteresis_ticks=1, cooldown_ms=1000.0))
        hot = _samples((0, True, 8, 8))
        d = c.observe(hot, now=0.0)
        assert d.direction == "up"
        c.record_action(d, now=0.0)
        d2 = c.observe(hot, now=0.5)
        assert (d2.direction, "cooldown" in d2.gates) == ("hold", True)
        d3 = c.observe(hot, now=1.5)          # cooldown over, counter
        assert d3.direction == "up"           # re-accumulated already

    def test_record_action_caps_decision_log_at_16(self):
        c = elastic.ElasticController(_cfg(hysteresis_ticks=1))
        hot = _samples((0, True, 8, 8))
        for i in range(20):
            d = c.observe(hot, now=float(i) * 10.0)
            if d.direction == "up":
                c.record_action(d, now=float(i) * 10.0)
        assert len(c.decisions) == 16
        assert all("at" in e and e["direction"] == "up"
                   for e in c.decisions)

    def test_from_env_clamps(self, monkeypatch):
        monkeypatch.setenv("PIO_FLEET_MIN_REPLICAS", "4")
        monkeypatch.setenv("PIO_FLEET_MAX_REPLICAS", "2")  # < min
        monkeypatch.setenv("PIO_SCALE_UP_THRESHOLD", "7.5")  # > 1
        monkeypatch.setenv("PIO_SCALE_DOWN_THRESHOLD", "9.0")  # > up
        cfg = elastic.ElasticConfig.from_env()
        assert cfg.min_replicas == 4
        assert cfg.max_replicas == 4          # clamped up to min
        assert cfg.up_threshold == 1.0
        assert cfg.down_threshold <= cfg.up_threshold
        for k in ("PIO_FLEET_MIN_REPLICAS", "PIO_FLEET_MAX_REPLICAS",
                  "PIO_SCALE_UP_THRESHOLD", "PIO_SCALE_DOWN_THRESHOLD"):
            monkeypatch.delenv(k)
        cfg = elastic.ElasticConfig.from_env(default_min=2,
                                             default_max=5)
        assert (cfg.min_replicas, cfg.max_replicas) == (2, 5)


# ---------------------------------------------------------------------------
# FrontProxy draining marks (satellite: draining is not dead)
# ---------------------------------------------------------------------------

class TestFrontDraining:
    def test_draining_excluded_from_counts(self):
        front = FrontProxy([1001, 1002])
        front.set_ready(0, True)
        front.set_ready(1, True)
        assert (front.active_count(), front.ready_count()) == (2, 2)
        front.set_draining(1, True)
        assert front.is_draining(1)
        assert (front.active_count(), front.ready_count()) == (1, 1)
        assert not front._routable(1)

    def test_set_backend_pads_and_clears_marks(self):
        front = FrontProxy([1001])
        front.set_backend(3, 1004)            # pads slots 1..2 as None
        assert front.worker_ports == [1001, None, None, 1004]
        assert front.active_count() == 2      # None slots not routable
        front.set_ready(3, True)
        front.set_draining(3, True)
        front.set_backend(3, None)            # freeing clears the marks
        assert not front.is_draining(3)
        assert front.is_ready(3)              # back to unprobed default
        front.set_backend(3, 1005)
        assert front._routable(3)


# ---------------------------------------------------------------------------
# supervisor dynamic membership (REAL subprocesses)
# ---------------------------------------------------------------------------

# a service worker: beats its heartbeat file and exits DRAIN_EXIT_CODE
# on SIGTERM (the graceful-drain contract retirement relies on)
_WORKER_SRC = """
import os, signal, sys, time
hb = os.environ["PIO_WORKER_HEARTBEAT_FILE"]
signal.signal(signal.SIGTERM, lambda s, f: sys.exit(3))
while True:
    with open(hb, "a"):
        os.utime(hb, None)
    time.sleep(0.05)
"""


def _service_sup(tmp_path, workers=1, max_restarts=3):
    return Supervisor(
        [sys.executable, "-c", _WORKER_SRC], workers,
        config=GangConfig(num_workers=workers, heartbeat_ms=50,
                          stall_ms=30_000, init_grace_ms=30_000,
                          max_restarts=max_restarts, drain_ms=10_000,
                          poll_ms=25),
        run_dir=str(tmp_path / "run"), wire_coordinator=False,
        restart_scope="worker", resume_argv=())


def _sup_poll(fn, deadline_s=30, msg="condition"):
    deadline = time.monotonic() + deadline_s
    last = None
    while time.monotonic() < deadline:
        last = fn()
        if last:
            return last
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}; last={last!r}")


class TestDynamicMembership:
    def test_gang_scope_rejects_membership(self, tmp_path):
        sup = Supervisor(["true"], 1, run_dir=str(tmp_path / "g"))
        with pytest.raises(RuntimeError):
            sup.add_worker()
        with pytest.raises(RuntimeError):
            sup.retire_worker(0)

    def test_duplicate_slot_rejected(self, tmp_path):
        sup = _service_sup(tmp_path)
        assert sup.add_worker(1) == 1
        with pytest.raises(ValueError):
            sup.add_worker(1)                 # queued add holds the slot
        # lowest-free allocation honours the queued claim on slot 1
        # (the launch worker at 0 is not on the books until run())
        assert sup.add_worker() == 0
        assert sup.add_worker() == 2

    def test_add_retire_lifecycle(self, tmp_path):
        sup = _service_sup(tmp_path)
        # enqueue BEFORE the supervision thread exists: the spawn must
        # be deferred to that thread (pdeathsig binds to the spawning
        # thread — a late-added worker has to share the launch workers'
        # parent-death contract), so nothing spawns here
        slot = sup.add_worker(1)
        assert slot == 1 and sup.worker_pid(1) is None
        t = threading.Thread(target=sup.run, daemon=True)
        t.start()
        try:
            _sup_poll(lambda: sup.worker_pid(0) and sup.worker_pid(1),
                      msg="both workers spawned")
            assert sorted(e["worker"] for e in sup.events
                          if e["type"] == "workerAdded") == [1]
            # the late-added worker got the SAME heartbeat machinery:
            # its file registers beats (no workerFailure sweep fires)
            hb = os.path.join(sup.run_dir, "worker_1.hb")
            _sup_poll(lambda: os.path.exists(hb),
                      msg="late worker heartbeat file")
            assert sup.live_worker_indices() == [0, 1]

            # graceful retirement: SIGTERM -> worker exits rc 3 ->
            # booked out, no failure/restart accounting
            sup.retire_worker(1)
            _sup_poll(lambda: 1 not in sup.live_worker_indices()
                      and not sup.is_retiring(1), msg="retirement")
            retired = [e for e in sup.events
                       if e["type"] == "workerRetired"]
            assert [(e["worker"], e["rc"]) for e in retired] == \
                [(1, DRAIN_EXIT_CODE)]
            assert not any(e["type"] == "workerFailure"
                           for e in sup.events)
            assert sup.worker_restarts[1] == 0
        finally:
            sup.request_stop()
            t.join(timeout=30)
        assert sup.state == "drained"

    def test_unspawned_slots_read_as_starting(self, tmp_path):
        sup = _service_sup(tmp_path)
        sup.add_worker(1)
        # before run(): the launch slot and the queued add are coming
        # up (no pid yet), an unclaimed slot is not
        assert sup.worker_starting(0) and sup.worker_pid(0) is None
        assert sup.worker_starting(1) and not sup.worker_starting(2)
        t = threading.Thread(target=sup.run, daemon=True)
        t.start()
        try:
            _sup_poll(lambda: sup.worker_pid(0) and sup.worker_pid(1),
                      msg="both workers spawned")
            assert not sup.worker_starting(0)
            assert not sup.worker_starting(1)
            # a spawned worker that dies reads dead, not starting
            pid = sup.worker_pid(1)
            os.kill(pid, signal.SIGKILL)
            _sup_poll(lambda: sup.worker_pid(1) != pid,
                      msg="worker 1 seen dead or relaunched")
            assert not sup.worker_starting(1)
        finally:
            sup.request_stop()
            t.join(timeout=30)

    def test_added_worker_has_restart_budget(self, tmp_path):
        sup = _service_sup(tmp_path, max_restarts=1)
        t = threading.Thread(target=sup.run, daemon=True)
        t.start()
        try:
            _sup_poll(lambda: sup.worker_pid(0), msg="launch worker")
            slot = sup.add_worker()
            pid = _sup_poll(lambda: sup.worker_pid(slot),
                            msg="added worker spawned")
            os.kill(pid, signal.SIGKILL)
            _sup_poll(lambda: (sup.worker_pid(slot) or 0) not in (0, pid),
                      msg="added worker relaunched")
            assert sup.worker_restarts[slot] == 1
            assert any(e["type"] == "workerFailure"
                       and e["worker"] == slot for e in sup.events)
            assert any(e["type"] == "workerRestart"
                       and e["worker"] == slot for e in sup.events)
        finally:
            sup.request_stop()
            t.join(timeout=30)

    def test_restart_budget_exhaustion_fails_service(self, tmp_path):
        sup = _service_sup(tmp_path, max_restarts=0)
        outcome = {}
        t = threading.Thread(
            target=lambda: outcome.update(state=sup.run()), daemon=True)
        t.start()
        try:
            pid = _sup_poll(lambda: sup.worker_pid(0), msg="worker up")
            os.kill(pid, signal.SIGKILL)
            t.join(timeout=30)
            assert outcome.get("state") == "failed"
            assert any(e["type"] == "gaveUp" for e in sup.events)
        finally:
            sup.request_stop()
            t.join(timeout=30)


# ---------------------------------------------------------------------------
# seeded scale-directive-confinement violation (satellite: lint)
# ---------------------------------------------------------------------------

@pytest.mark.lint
class TestScaleConfinementRule:
    def _findings(self, tmp_path, files):
        from test_lint import findings_for

        return findings_for(tmp_path, files,
                            ["scale-directive-confinement"])

    def test_seeded_violation(self, tmp_path):
        fs = self._findings(tmp_path, {
            "workflow/fleet.py": """
                def elastic_loop(coordinator, sup):
                    coordinator.apply_scale({})  # the chokepoint
                """,
            "workflow/rogue.py": """
                def sneak(sup, coordinator):
                    sup.add_worker(3)
                    sup.retire_worker(0)
                    coordinator.set_replicas(9)
                """,
        })
        assert [(f.line, f.rule) for f in fs] == [
            (3, "scale-directive-confinement"),
            (4, "scale-directive-confinement"),
            (5, "scale-directive-confinement")]
        assert all(f.path.endswith("workflow/rogue.py") for f in fs)
        assert "outside the elastic control loop" in fs[0].message

    def test_allowed_homes_stay_clean(self, tmp_path):
        fs = self._findings(tmp_path, {
            "workflow/fleet.py": """
                def elastic_loop(coordinator, sup):
                    coordinator.apply_scale({})
                    sup.add_worker(1)
                """,
            "data/api/event_log.py": """
                def apply_target(sup):
                    sup.retire_worker(2)
                """,
        })
        assert fs == []

    def test_missing_chokepoint_is_a_finding(self, tmp_path):
        """Renaming apply_scale out of workflow/fleet.py must not turn
        the rule vacuously green."""
        fs = self._findings(tmp_path, {
            "workflow/fleet.py": "def elastic_loop():\n    pass\n",
        })
        assert len(fs) == 1
        assert "chokepoint" in fs[0].message


# ---------------------------------------------------------------------------
# soak ramp SLO rows: red and green paths from fabricated timelines
# ---------------------------------------------------------------------------

def _elastic_soak_fixture(tmp_path, fleet_size):
    from incubator_predictionio_tpu.workflow import soak

    cfg = soak.SoakConfig(
        engine_dir=str(tmp_path), workdir=str(tmp_path),
        duration_s=60.0, elastic=True, faults=(), quality_sample=0.0,
        query_cache_size=0)
    plan = soak.plan_scenario(cfg)
    assert plan.ramp == {"upAtS": 18.0, "downAtS": 39.0, "factor": 10.0,
                         "min": 1, "max": 3}
    ledger = soak._Ledger()
    samples = soak._Samples()
    samples.fleet_size.extend(fleet_size)
    recon = {"lostAckedCount": 0, "duplicatedCount": 0,
             "ackedEvents": 0}
    slos, _fault_rows = soak.evaluate_slos(
        plan, ledger, samples, recon, {"finalLagS": 0.0},
        {"engine": 0, "eventserver": 0}, None, [])
    return {s["name"]: s for s in slos}


class TestRampSlos:
    def test_green_timeline(self, tmp_path):
        rows = _elastic_soak_fixture(tmp_path, [
            (10.0, 1, 1, 1),
            (20.5, 2, 1, 2),      # spawned, not ready yet
            (24.0, 2, 2, 2),      # ready 6s after the 18s step
            (40.0, 2, 2, 2),
            (47.5, 1, 1, 1),      # back at floor 8.5s after 39s step
        ])
        up, down = rows["scale-up-within-bound"], rows["drain-on-quiet"]
        assert up["ok"] and up["value"] == 6.0
        assert down["ok"] and down["value"] == 8.5

    def test_red_never_grew(self, tmp_path):
        rows = _elastic_soak_fixture(tmp_path, [
            (10.0, 1, 1, 1),      # pinned at the floor the whole run
            (25.0, 1, 1, 1),
            (50.0, 1, 1, 1),
        ])
        up = rows["scale-up-within-bound"]
        assert not up["ok"] and up["value"] is None
        assert "never seen" in up["detail"]

    def test_red_never_shrank(self, tmp_path):
        rows = _elastic_soak_fixture(tmp_path, [
            (10.0, 1, 1, 1),
            (20.0, 2, 2, 2),      # grew on cue...
            (50.0, 2, 2, 2),      # ...but never drained on quiet
        ])
        down = rows["drain-on-quiet"]
        assert rows["scale-up-within-bound"]["ok"]
        assert not down["ok"] and down["value"] is None
        assert "never seen" in down["detail"]

    def test_red_outside_bounds(self, tmp_path):
        rows = _elastic_soak_fixture(tmp_path, [
            (10.0, 1, 1, 1),
            (55.0, 2, 2, 2),      # grew 37s after the step (> 30s)
        ])
        assert not rows["scale-up-within-bound"]["ok"]
        assert rows["scale-up-within-bound"]["value"] == 37.0

    def test_scale_events_metric_registered(self):
        from incubator_predictionio_tpu.workflow.soak import SLO_METRICS

        assert "pio_fleet_scale_events_total" in SLO_METRICS


# ---------------------------------------------------------------------------
# e2e: a REAL elastic fleet grows under flood, drains on quiet
# ---------------------------------------------------------------------------

@pytest.mark.fleet
@pytest.mark.chaos
def test_elastic_fleet_scales_up_under_flood_and_drains_on_quiet(
        tmp_path):
    """The tentpole acceptance loop on one host: launch at the floor
    (1 replica), flood queries until admission sheds, watch the
    autoscaler spawn replica 1 through the supervisor and report it via
    /healthz; stop the flood and watch it drain the least-loaded
    replica back to the floor — with every client response in
    {200, 503, 504} throughout."""
    from test_fleet import (_Fleet, _sqlite_env, _storage_for, _train,
                            _poll)

    env = _sqlite_env(
        tmp_path,
        PIO_FLEET_MIN_REPLICAS="1",
        PIO_FLEET_MAX_REPLICAS="2",
        # tiny admission queue: the flood reads as shed/utilization
        # within a tick or two even on a fast host
        PIO_QUERY_MAX_PENDING="2",
        PIO_SCALE_TICK_MS="100",
        PIO_SCALE_COOLDOWN_MS="1000",
        # 2 agreeing ticks: one noisy between-burst snapshot (pending
        # momentarily low under a live flood) must not drain the fleet
        PIO_SCALE_HYSTERESIS_TICKS="2",
        # pending stays well above this while the flood runs (sleepS
        # keeps the admission queue occupied) and drops to 0 the tick
        # it stops — the down-vote must not fire on split-load noise
        PIO_SCALE_DOWN_THRESHOLD="0.1",
    )
    storage = _storage_for(env)
    _train(storage, "one")

    class _ElasticFleet(_Fleet):
        def __init__(self, env):
            import tempfile

            self.replicas = 1
            self.port = free_port()
            self.base = f"http://127.0.0.1:{self.port}"
            self._log = tempfile.NamedTemporaryFile(
                prefix=f"pio_elastic_front_{self.port}_",
                suffix=".log", delete=False)
            self.proc = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "fleet_front.py"),
                 str(self.port), "1", "elastic"],
                env=env, stdout=self._log, stderr=subprocess.STDOUT)

    fleet = _ElasticFleet(env)
    codes: list = []
    stop_flood = threading.Event()

    def flood(idx):
        # sleepS keeps each accepted query resident in the replica for
        # a beat: the admission queue stays OCCUPIED between snapshots
        # (a microsecond-answer engine would read as quiet on most
        # ticks no matter how hard the open loop hammers it)
        n = 0
        while not stop_flood.is_set():
            n += 1
            try:
                r = requests.post(fleet.base + "/queries.json",
                                  json={"user": f"f{idx}-{n}",
                                        "sleepS": 0.25},
                                  timeout=20)
                codes.append(r.status_code)
            except requests.RequestException:
                pass  # connection-level noise, judged by http codes
    try:
        doc = fleet.wait_ready()
        assert doc["targetReplicas"] == 1
        assert doc["elastic"]["enabled"] is True
        threads = [threading.Thread(target=flood, args=(i,))
                   for i in range(20)]
        for t in threads:
            t.start()
        try:
            # the autoscaler must detect pressure, spawn slot 1 through
            # the supervisor, and the readiness poller must mark it
            grown = _poll(
                lambda: (lambda h: h if h.get("readyReplicas", 0) >= 2
                         else None)(fleet.healthz()),
                60, msg="scale-up to 2 ready replicas")
            assert grown["targetReplicas"] == 2
            assert grown["elastic"]["decisions"], \
                "acted decision log is empty"
            up = grown["elastic"]["decisions"][0]
            assert up["direction"] == "up"
            assert up["reason"] in ("shed", "utilization")
        finally:
            stop_flood.set()
            for t in threads:
                t.join(30)
        # quiet: drain back to the floor; the drained slot is released
        # (freed, not dead) once the replica finishes and exits
        shrunk = _poll(
            lambda: (lambda h: h
                     if (h.get("activeReplicas") == 1
                         and not h.get("drainingReplicas"))
                     else None)(fleet.healthz()),
            90, msg="drain back to the floor")
        assert shrunk["targetReplicas"] == 1
        downs = [d for d in shrunk["elastic"]["decisions"]
                 if d["direction"] == "down"]
        assert downs and downs[-1]["reason"] == "quiet"
        # lossless-drain contract: the flood never saw a non-contract
        # status (draining replicas finish in-flight work; the front
        # only sheds 503/504)
        bad = [c for c in codes if c not in (200, 503, 504)]
        assert not bad, f"non-contract responses: {sorted(set(bad))}"
        assert 200 in codes, "flood never got an accepted answer"
        fleet.stop()
    finally:
        fleet.kill()


# ---------------------------------------------------------------------------
# e2e: `pio eventserver scale` lease/fence handoff, exactly-once
# ---------------------------------------------------------------------------

@pytest.mark.partition
@pytest.mark.chaos
def test_eventserver_scale_rebalances_leases_exactly_once(tmp_path):
    """Runtime rescale of the partitioned event tier: scale 2 -> 1
    drains the highest worker, whose partition lease is claimed (epoch
    bump) and PARKED by the front with its WAL subdir replayed; scale
    1 -> 2 releases the parked lease to the newcomer. Every acked
    event is present exactly once through every transition, and the
    orphaned shard stays readable while parked."""
    from test_event_log import (_ev, _make_mw_env, _prepare_metadata,
                                _wait_partitions, _wait_ready)

    env = _make_mw_env(tmp_path,
                       PIO_FS_BASEDIR=str(tmp_path / "pio_store"))
    key = _prepare_metadata(env)
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    info_path = os.path.join(str(tmp_path), "pio_store",
                             "eventserver_front.json")

    def info():
        try:
            with open(info_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def rescale(target):
        doc = info()
        tmp = doc["scaleFile"] + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(target))
        os.replace(tmp, doc["scaleFile"])
        os.kill(doc["pid"], signal.SIGHUP)

    def wait_info(cond, what, timeout=60):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            doc = info()
            if doc and cond(doc):
                return doc
            time.sleep(0.1)
        raise AssertionError(f"timed out waiting for {what}: {info()}")

    def ack(session, start, n):
        ids = []
        for i in range(start, start + n):
            r = session.post(f"{base}/events.json?accessKey={key}",
                             json=_ev(i), timeout=15)
            assert r.status_code == 201, r.text
            ids.append(r.json()["eventId"])
        return ids

    proc = subprocess.Popen(
        [sys.executable, "-m",
         "incubator_predictionio_tpu.tools.console", "eventserver",
         "--workers", "2", "--ip", "127.0.0.1", "--port", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        _wait_ready(proc, base)
        wait_info(lambda d: d["workers"] == [0, 1], "front info")
        _wait_partitions(proc, base, (0, 1))
        acked = []
        # two pinned sessions land on both workers: both shards take
        # writes before the first rebalance
        for s in (requests.Session(), requests.Session()):
            acked += ack(s, len(acked), 8)

        # -- scale down: worker 1 drains, its lease parks on the front
        rescale(1)
        doc = wait_info(
            lambda d: d["workers"] == [0] and d["parkedPartitions"] == [1]
            and not d["retiring"], "scale-down to 1 worker")
        # ingest continues through the survivor; the parked shard stays
        # readable via the merged view
        acked += ack(requests.Session(), len(acked), 6)
        r = requests.get(f"{base}/events.json?accessKey={key}&limit=-1",
                         timeout=30)
        got = [e["eventId"] for e in r.json()]
        assert sorted(got) == sorted(acked), \
            "merged read during parked phase lost or duplicated events"

        # the front CLAIMED the orphan: the lease file records a holder
        from incubator_predictionio_tpu.data.api import event_log
        ev_dir = os.path.join(str(tmp_path), "events", "pio_eventdata")
        li = event_log.lease_info(ev_dir, 1)
        assert li is not None and li["held"], li

        # -- scale back up: the parked lease is handed to the newcomer
        rescale(2)
        wait_info(lambda d: d["workers"] == [0, 1]
                  and d["parkedPartitions"] == [], "scale-up to 2")
        # the relaunched partition serves writes again under its OWN
        # re-claimed (epoch-bumped) lease
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            acked += ack(requests.Session(), len(acked), 2)
            sizes = {p: os.path.getsize(os.path.join(
                ev_dir, f"events_1.p{p}.jsonl")) for p in (0, 1)}
            if sizes[1] > 0:
                break
            time.sleep(0.1)

        # -- exactly-once across every transition ----------------------
        def merged_ok():
            r = requests.get(
                f"{base}/events.json?accessKey={key}&limit=-1",
                timeout=30)
            if r.status_code != 200:
                return None
            got = [e["eventId"] for e in r.json()]
            return got if sorted(got) == sorted(acked) else None
        deadline = time.monotonic() + 30
        final = None
        while time.monotonic() < deadline and final is None:
            final = merged_ok()
            if final is None:
                time.sleep(0.5)
        assert final is not None, "acked events lost or duplicated"
        assert len(final) == len(set(final)), "duplicate event ids"

        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
        assert proc.returncode == 0, out.decode(errors="replace")[-3000:]
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
