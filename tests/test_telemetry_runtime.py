"""The runtime beneath the spans (``common/telemetry.py``): the collector's
pauses as counters and ``py.gc`` spans, the event loop's lag as
``loop.stall`` / ``loop.beat`` spans and the ``pio_event_loop_*`` families,
a train's share of the pauses on its ``train.run`` root. All of them are
roots of the one trace ``PROCESS_TRACE``, in the one ring.

A test that waits on a clock has a time limit of its own (``wait_for``, a
join with a timeout) and no tight upper bound: six xdist workers share a
busy box.
"""

import asyncio
import gc
import os
import subprocess
import sys
import threading
import time

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

import incubator_predictionio_tpu
from incubator_predictionio_tpu.common import telemetry

pytestmark = pytest.mark.telemetry

ROOT = os.path.dirname(os.path.dirname(
    os.path.abspath(incubator_predictionio_tpu.__file__)))


def process_spans(name, since_ns=0, **tags):
    return [s for s in telemetry.spans_snapshot()
            if s.name == name and s.t0_ns >= since_ns
            and all((s.tags or {}).get(k) == v for k, v in tags.items())]


def gc_families():
    """{family: {generation: value}} of the two pio_gc_* families."""
    return {fam.name: {labels[0]: child.value()
                       for labels, child in fam.samples()}
            for fam in telemetry.registry().collect()
            if fam.name.startswith("pio_gc_")}


# ---------------------------------------------------------------------------
# the collector's pauses
# ---------------------------------------------------------------------------

def test_a_forced_collection_is_a_root_of_the_process_trace():
    before, t0 = gc_families(), time.perf_counter_ns()
    with telemetry.span("t.some_request") as open_request:
        gc.collect()
    t1 = time.perf_counter_ns()
    (rec,) = process_spans("py.gc", t0, generation=2)
    assert rec.trace_id == telemetry.PROCESS_TRACE and rec.parent_id is None
    assert rec.trace_id != open_request.trace_id   # not the request's work
    assert t0 <= rec.t0_ns <= rec.t1_ns <= t1
    assert set(rec.tags) == {"generation", "collected", "uncollectable",
                             "thread"}
    assert rec.tags["thread"] == threading.get_ident()
    after = gc_families()
    assert set(after) == {"pio_gc_collections_total",
                          "pio_gc_pause_seconds_total"}
    assert after["pio_gc_collections_total"]["2"] == \
        before["pio_gc_collections_total"]["2"] + 1
    grown = (after["pio_gc_pause_seconds_total"]["2"]
             - before["pio_gc_pause_seconds_total"]["2"])
    assert grown == pytest.approx((rec.t1_ns - rec.t0_ns) * 1e-9, abs=1e-6)
    body = telemetry.render_all()
    assert "# TYPE pio_gc_collections_total counter" in body
    assert 'pio_gc_pause_seconds_total{generation="2"}' in body


@pytest.mark.parametrize("threshold_ns, spans", [(10**10, 0), (0, 1)])
def test_a_young_collection_counts_and_leaves_a_span_only_over_the_threshold(
        monkeypatch, threshold_ns, spans):
    monkeypatch.setattr(telemetry, "GC_SPAN_NS", threshold_ns)
    n0, p0 = telemetry.gc_totals()
    before, t0 = gc_families(), time.perf_counter_ns()
    gc.collect(0)
    assert len(process_spans("py.gc", t0)) == spans
    n1, p1 = telemetry.gc_totals()
    assert n1 == n0 + 1 and p1 > p0
    assert gc_families()["pio_gc_collections_total"]["0"] == \
        before["pio_gc_collections_total"]["0"] + 1


def test_ten_thousand_short_collections_leave_the_ring_alone():
    with telemetry.span("t.runtime.marker"):
        pass
    ring_before = len(telemetry.spans_snapshot())
    n0, _ = telemetry.gc_totals()
    t0 = time.perf_counter_ns()
    for _ in range(10_000):
        gc.collect(0)
    assert telemetry.gc_totals()[0] >= n0 + 10_000
    # one preempted on a busy box may pass the millisecond; the ring is
    # 65,536 and the window's spans stay
    assert len(process_spans("py.gc", t0)) < 100
    assert len(telemetry.spans_snapshot()) < ring_before + 100
    assert any(s.name == "t.runtime.marker"
               for s in telemetry.spans_snapshot())


def test_the_hook_runs_under_a_counter_shard_lock():
    """A collection can start inside ``Counter.inc``, whose shard lock is
    not reentrant: the hook takes none."""
    counter = telemetry.CounterFamily("t_runtime_lock_total", "x").labels()
    done = []

    def collect_under_the_lock():
        lock, _box = counter._shards[telemetry._shard_index()]
        with lock:
            gc.collect()
        counter.inc()
        done.append(True)

    t0 = time.perf_counter_ns()
    worker = threading.Thread(target=collect_under_the_lock, daemon=True)
    worker.start()
    worker.join(30)
    assert not worker.is_alive() and done == [True]
    assert counter.value() == 1
    assert any(s.tags["thread"] == worker.ident
               for s in process_spans("py.gc", t0, generation=2))


def test_metrics_off_takes_the_hook_out_and_back():
    assert telemetry._on_gc in gc.callbacks
    telemetry.set_metrics_enabled(False)
    try:
        assert telemetry._on_gc not in gc.callbacks
        assert gc_families() == {}
        t0 = time.perf_counter_ns()
        gc.collect()
        assert process_spans("py.gc", t0) == []
    finally:
        telemetry.set_metrics_enabled(True)
    assert gc.callbacks.count(telemetry._on_gc) == 1


def test_pio_metrics_0_installs_nothing():
    """A fresh interpreter with PIO_METRICS=0: no hook of ours in
    ``gc.callbacks``, and an application with the loop monitor in its
    ``cleanup_ctx`` starts no task."""
    code = (
        "import asyncio, gc\n"
        "from aiohttp import web\n"
        "from incubator_predictionio_tpu.common import telemetry\n"
        "assert not gc.callbacks, gc.callbacks\n"
        "async def main():\n"
        "    app = web.Application()\n"
        "    app.cleanup_ctx.append(telemetry.loop_monitor('engine'))\n"
        "    runner = web.AppRunner(app)\n"
        "    await runner.setup()\n"
        "    others = asyncio.all_tasks() - {asyncio.current_task()}\n"
        "    await runner.cleanup()\n"
        "    return len(others)\n"
        "print('TASKS', asyncio.run(main()))\n"
        "gc.collect()\n"
        "print('SPANS', len(telemetry.spans_snapshot()))\n"
        "print('GC' if 'pio_gc_' in telemetry.render_all() else 'NOGC')\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=dict(os.environ, PIO_METRICS="0"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["TASKS", "0", "SPANS", "0", "NOGC"]


# ---------------------------------------------------------------------------
# the loop's lag
# ---------------------------------------------------------------------------

def drive(loop_name, *visits, settle=0.25):
    """Run an application with the loop monitor on a loop of this test's
    own; GET each of ``visits`` in turn (a path, or seconds to wait);
    returns the monitor's task once the application was cleaned up."""
    state = {}

    async def sleeps(request):
        time.sleep(0.1)                      # blocks the loop, off the CPU
        return web.Response(text="slept")

    async def spins(request):
        until = time.thread_time() + 0.1     # 100 ms of this thread's CPU
        while time.thread_time() < until:
            pass
        return web.Response(text="spun")

    async def collects(request):
        """A slow collection on ANOTHER thread; the loop only waits."""
        worker = threading.Thread(target=gc.collect, daemon=True)
        state["gc_t0"] = time.perf_counter_ns()
        worker.start()
        while worker.is_alive():
            await asyncio.sleep(0.005)
        state["gc_thread"] = worker.ident
        return web.Response(text="collected")

    async def main():
        app = web.Application()
        app.cleanup_ctx.append(telemetry.loop_monitor(loop_name))
        app.add_routes([web.get("/sleeps", sleeps), web.get("/spins", spins),
                        web.get("/collects", collects)])
        async with TestClient(TestServer(app)) as client:
            state["task"] = next(
                t for t in asyncio.all_tasks()
                if t.get_name() == f"pio-loop-monitor-{loop_name}")
            await asyncio.sleep(settle)      # a first tick, before any visit
            for visit in visits:
                if isinstance(visit, str):
                    resp = await client.get(visit)
                    assert resp.status == 200
                    await asyncio.sleep(settle)
                else:
                    await asyncio.sleep(visit)
        state["left"] = [t for t in asyncio.all_tasks()
                         if t is not asyncio.current_task()]

    async def bounded():
        await asyncio.wait_for(main(), 120)

    junk = None
    if "/collects" in visits:
        # a heap that takes the collector a fifth of a second to walk,
        # built with the collector off so that building it is not the stall
        gc.disable()
        try:
            junk = [[] for _ in range(3_000_000)]
        finally:
            gc.enable()
    asyncio.run(bounded())
    del junk
    return state


def the_long_stall(loop_name, since_ns):
    """The one stall of 40 ms or more (a busy box may add short ones)."""
    stalls = process_spans("loop.stall", since_ns, loop=loop_name)
    (long_one,) = [s for s in stalls if s.tags["lag_ms"] >= 40]
    assert long_one.trace_id == telemetry.PROCESS_TRACE
    assert long_one.parent_id is None
    assert set(long_one.tags) == {"loop", "lag_ms", "loop_cpu_ms", "cpu_ms",
                                  "gc_ms", "nivcsw", "majflt"}
    assert (long_one.t1_ns - long_one.t0_ns) * 1e-6 == pytest.approx(
        long_one.tags["lag_ms"])
    return long_one


def test_a_handler_that_sleeps_in_the_loop_is_a_stall_off_the_cpu():
    t0 = time.perf_counter_ns()
    drive("t-sleeps", "/sleeps")
    stall = the_long_stall("t-sleeps", t0)
    assert stall.tags["loop_cpu_ms"] < 25       # asleep, not running
    assert stall.tags["gc_ms"] < 25
    stalled = next(
        child.value() for fam in telemetry.registry().collect()
        if fam.name == "pio_event_loop_stall_seconds_total"
        for labels, child in fam.samples() if labels == ("t-sleeps",))
    assert stalled >= 0.04
    body = telemetry.render_all()
    assert 'pio_event_loop_lag_seconds_count{loop="t-sleeps"}' in body
    assert "# TYPE pio_event_loop_lag_seconds histogram" in body


def test_a_handler_that_spins_is_a_stall_on_the_loops_own_thread():
    t0 = time.perf_counter_ns()
    drive("t-spins", "/spins")
    stall = the_long_stall("t-spins", t0)
    # the readings run from the tick before, so they hold the spin whole
    assert stall.tags["loop_cpu_ms"] >= 60
    assert stall.tags["cpu_ms"] >= 60
    assert stall.tags["loop_cpu_ms"] >= 0.4 * stall.tags["lag_ms"]


def test_a_collection_on_another_thread_is_a_stall_of_the_collectors():
    t0 = time.perf_counter_ns()
    state = drive("t-collects", "/collects")
    pause = max(process_spans("py.gc", state["gc_t0"], generation=2,
                              thread=state["gc_thread"]),
                key=lambda s: s.t1_ns - s.t0_ns)
    stall = max(process_spans("loop.stall", t0, loop="t-collects"),
                key=lambda s: s.tags["lag_ms"])
    assert stall.tags["lag_ms"] >= 20, (pause, stall)
    assert stall.t0_ns < pause.t1_ns and pause.t0_ns < stall.t1_ns
    assert stall.tags["gc_ms"] >= 0.5 * stall.tags["lag_ms"]
    assert stall.tags["loop_cpu_ms"] < 0.5 * stall.tags["lag_ms"]


def test_a_quiet_second_is_one_beat_and_the_task_ends_with_the_application(
        recwarn):
    t0 = time.perf_counter_ns()
    state = drive("t-quiet", 1.4, settle=0.0)
    beats = process_spans("loop.beat", t0, loop="t-quiet")
    assert len(beats) == 1
    beat = beats[0]
    assert beat.trace_id == telemetry.PROCESS_TRACE and beat.parent_id is None
    assert set(beat.tags) == {"loop", "ticks", "lag_med_ms", "lag_max_ms",
                              "cpu_ms", "loop_cpu_ms", "gc_ms"}
    assert 12 <= beat.tags["ticks"] <= 21
    assert 0 <= beat.tags["lag_med_ms"] <= beat.tags["lag_max_ms"]
    assert beat.t1_ns - beat.t0_ns >= telemetry.LOOP_BEAT_NS
    assert state["task"].done() and state["left"] == []
    gc.collect()                                 # a pending task would warn
    assert not [w for w in recwarn.list
                if "pending" in str(w.message) or "never awaited" in
                str(w.message)]


# ---------------------------------------------------------------------------
# a train's share
# ---------------------------------------------------------------------------

def test_run_train_says_how_long_the_collector_held_it_up(memory_storage):
    from incubator_predictionio_tpu.models.recommendation import (
        RecommendationEngine)
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import run_train

    from test_dase_train_e2e import ENGINE_PARAMS, _seed_ratings

    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    n0, p0 = telemetry.gc_totals()
    instance_id = run_train(engine, ENGINE_PARAMS, ctx,
                            engine_factory_name="rec")
    n1, p1 = telemetry.gc_totals()
    (root,) = [s for s in telemetry.spans_snapshot()
               if s.name == "train.run" and s.trace_id == instance_id]
    assert set(root.tags) == {"instance", "factory", "gc_ms",
                              "gc_collections"}
    assert 0 <= root.tags["gc_collections"] <= n1 - n0
    assert 0.0 <= root.tags["gc_ms"] <= (p1 - p0) * 1e-6
    # the collections of the train are roots of the process's trace, not
    # spans of the train's tree
    assert not [s for s in telemetry.spans_snapshot()
                if s.trace_id == instance_id
                and s.name in ("py.gc", "loop.stall", "loop.beat")]
