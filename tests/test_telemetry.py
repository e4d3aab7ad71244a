"""Unified telemetry subsystem: registry semantics, Prometheus
exposition, server /metrics endpoints, sampled tracing, and the
disabled-path cost guarantee.

- exposition golden test (exact text format output)
- histogram log2 bucket-boundary math (bit_length indexing, exact
  powers, +Inf overflow)
- multi-threaded lock-sharded counter correctness
- GET /metrics e2e on the event server AND the engine server (valid
  Prometheus text covering ingest / query / storage families)
- X-Pio-Trace-Id propagation through a live query (stage spans in the
  sink, header echoed)
- guard: the disabled path (PIO_METRICS=0) adds no per-request
  allocations on the hot ingest instrumentation
- guard: no new ad-hoc module-level counter dicts under data/api/ and
  workflow/ — metrics go through the registry
"""

import gc
import json
import os
import re
import sys
import threading

import pytest
import requests

import incubator_predictionio_tpu
from incubator_predictionio_tpu.common import telemetry
from incubator_predictionio_tpu.data.api.event_server import EventServer
from incubator_predictionio_tpu.data.api.stats import Stats
from incubator_predictionio_tpu.data.storage import Storage
from incubator_predictionio_tpu.data.storage.base import AccessKey, App

from server_utils import ServerThread

pytestmark = pytest.mark.telemetry


# ---------------------------------------------------------------------------
# registry + exposition
# ---------------------------------------------------------------------------

def test_exposition_golden():
    """Byte-exact Prometheus text format: HELP/TYPE comments, label
    escaping, histogram cumulative buckets + _sum/_count."""
    r = telemetry.Registry()
    c = r.counter("t_requests_total", "Requests served", ("method",))
    c.labels("GET").inc()
    c.labels("GET").inc(2)
    c.labels('we"ird\\path').inc()
    g = r.gauge("t_temperature", "A gauge")
    g.labels().set(2.5)
    h = r.histogram("t_sizes", "Sizes", lo_exp=0, n_buckets=2, scale=1)
    h.labels().observe_raw(1)
    h.labels().observe_raw(2)
    h.labels().observe_raw(9)  # past the top bucket -> +Inf
    assert r.render() == (
        "# HELP t_requests_total Requests served\n"
        "# TYPE t_requests_total counter\n"
        't_requests_total{method="GET"} 3\n'
        't_requests_total{method="we\\"ird\\\\path"} 1\n'
        "# HELP t_sizes Sizes\n"
        "# TYPE t_sizes histogram\n"
        't_sizes_bucket{le="1"} 1\n'
        't_sizes_bucket{le="2"} 2\n'
        't_sizes_bucket{le="+Inf"} 3\n'
        "t_sizes_sum 12\n"
        "t_sizes_count 3\n"
        "# HELP t_temperature A gauge\n"
        "# TYPE t_temperature gauge\n"
        "t_temperature 2.5\n"
    )


def test_histogram_bucket_boundary_math():
    """Bucket index = smallest power-of-two bound >= value, computed
    with bit_length — exact at the powers themselves."""
    h = telemetry.Histogram(lo_exp=0, n_buckets=16, scale=1)
    # bound of bucket j is 2**j: value 2**j must land IN bucket j,
    # value 2**j + 1 in bucket j+1
    for j in range(1, 15):
        assert h.bucket_index(2 ** j) == j
        assert h.bucket_index(2 ** j + 1) == j + 1
    assert h.bucket_index(1) == 0
    assert h.bucket_index(0) == 0
    assert h.bucket_index(2 ** 16) == 16      # == top bound -> last bucket
    assert h.bucket_index(2 ** 16 + 1) == 16  # past it -> +Inf slot
    assert h.upper_bound(3) == 8.0

    # ns->seconds latency shape: 1024 ns lands in the first bucket
    # (le=2**10 ns), 1025 ns in the second
    lat = telemetry.Histogram(
        lo_exp=10, n_buckets=26, scale=1e-9)
    assert lat.bucket_index(1024) == 0
    assert lat.bucket_index(1025) == 1
    assert lat.upper_bound(0) == pytest.approx(1.024e-6)

    lat.observe_raw(1024)
    lat.observe_raw(10 ** 9)  # 1 s
    counts, total, sum_raw = lat.snapshot()
    assert total == 2 and sum_raw == 1024 + 10 ** 9
    assert counts[0] == 1


def test_counter_multithreaded_exact():
    """Lock-sharded counters lose no increments under contention."""
    fam = telemetry.CounterFamily("t_mt_total", "mt", ("who",))
    child = fam.labels("x")
    n_threads, per_thread = 8, 20_000

    def work():
        for _ in range(per_thread):
            child.inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert child.value() == n_threads * per_thread


def test_registry_get_or_create_and_conflicts():
    r = telemetry.Registry()
    a = r.counter("t_x_total", "x", ("k",))
    assert r.counter("t_x_total", "x", ("k",)) is a
    with pytest.raises(ValueError):
        r.gauge("t_x_total", "x", ("k",))
    with pytest.raises(ValueError):
        r.counter("t_x_total", "x", ("other",))
    with pytest.raises(ValueError):
        a.labels("a", "b")  # label arity enforced
    # histograms: the bucket shape is part of the identity — a second
    # registrant with a different lo_exp/n_buckets/scale must error,
    # not silently adopt the first shape (its observations would render
    # with the wrong scale)
    h = r.histogram("t_h_seconds", "h", lo_exp=0, n_buckets=4, scale=1)
    assert r.histogram("t_h_seconds", "h",
                       lo_exp=0, n_buckets=4, scale=1) is h
    with pytest.raises(ValueError):
        r.histogram("t_h_seconds", "h")  # default latency shape differs


def test_stats_json_view_is_registry_backed():
    """Stats keeps its /stats.json shape, served from a telemetry
    CounterFamily rather than an ad-hoc dict."""
    s = Stats()
    s.record(7, "rate", "user", 201)
    s.record_many({(7, "rate", "user", 201): 2, (8, "buy", "user", 400): 1})
    out = s.to_json()
    assert {(c["appId"], c["event"], c["status"]): c["count"]
            for c in out["counts"]} == {(7, "rate", 201): 3,
                                        (8, "buy", 400): 1}
    assert s.to_json(8)["counts"] == [
        {"appId": 8, "event": "buy", "entityType": "user", "status": 400,
         "count": 1}]
    assert isinstance(s.family, telemetry.CounterFamily)


# ---------------------------------------------------------------------------
# /metrics e2e
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})?'
    r" -?[0-9.e+\-]+$")


def _assert_valid_exposition(text: str) -> dict:
    """Every line is a HELP/TYPE comment or a sample; returns
    {metric_name: value} for non-comment lines."""
    samples = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _SAMPLE_RE.match(line), f"invalid exposition line: {line!r}"
        name = re.split(r"[{ ]", line, 1)[0]
        samples[name] = line.rsplit(" ", 1)[1]
    return samples


def _setup_event_storage():
    env = {
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "MEMORY",
    }
    storage = Storage(env)
    app_id = storage.get_meta_data_apps().insert(App(0, "telemapp"))
    key = storage.get_meta_data_access_keys().insert(
        AccessKey("", app_id, ()))
    return storage, app_id, key


def test_event_server_metrics_e2e():
    """GET /metrics on the event server: valid text format covering the
    ingest histogram families and (with --stats) per-app counters."""
    storage, _app_id, key = _setup_event_storage()
    server = EventServer(storage, enable_stats=True)
    with ServerThread(server.app) as st:
        for i in range(3):
            r = requests.post(
                f"{st.base}/events.json?accessKey={key}",
                json={"event": "view", "entityType": "user",
                      "entityId": f"u{i}"})
            assert r.status_code == 201
        r = requests.get(f"{st.base}/metrics")
        assert r.status_code == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        body = r.text
    samples = _assert_valid_exposition(body)
    # ingest family: three committed events through the group buffer
    assert "pio_ingest_group_size_count" in samples
    assert "pio_ingest_commit_seconds_count" in samples
    assert "pio_ingest_queue_wait_seconds_bucket" in samples
    # per-app stats counters from the live server's collector
    assert 'pio_ingest_events_total{app_id=' in body
    assert 'event="view"' in body
    # storage breaker gauge family is registered (resilience collector)
    assert "# TYPE pio_storage_breaker_state gauge" in body
    # histograms expose cumulative buckets ending in +Inf
    assert 'pio_ingest_group_size_bucket{le="+Inf"}' in body
    _assert_runtime_families(body, "event")


def _assert_runtime_families(body, loop):
    """The four families of the runtime beneath a running server."""
    assert "# TYPE pio_gc_collections_total counter" in body
    assert 'pio_gc_pause_seconds_total{generation="2"}' in body
    assert "# TYPE pio_event_loop_lag_seconds histogram" in body
    assert 'pio_event_loop_lag_seconds_bucket{loop="%s",le="+Inf"}' % loop \
        in body
    assert 'pio_event_loop_stall_seconds_total{loop="%s"}' % loop in body


def _trained_engine_server(memory_storage):
    from incubator_predictionio_tpu.models.recommendation import (
        RecommendationEngine)
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import run_train
    from incubator_predictionio_tpu.workflow.create_server import EngineServer

    from test_dase_train_e2e import ENGINE_PARAMS, _seed_ratings

    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    run_train(engine, ENGINE_PARAMS, ctx, engine_factory_name="rec")
    return EngineServer(engine, engine_factory_name="rec",
                        storage=memory_storage)


def test_engine_server_metrics_e2e(memory_storage):
    """GET /metrics on the engine server: query stage histograms
    accumulate per query; compile gauges cover the warmed algorithms."""
    server = _trained_engine_server(memory_storage)
    with ServerThread(server.app) as st:
        for u in ("1", "2"):
            r = requests.post(st.base + "/queries.json",
                              json={"user": u, "num": 2})
            assert r.status_code == 200, r.text
        body = requests.get(st.base + "/metrics").text
    samples = _assert_valid_exposition(body)
    assert "# TYPE pio_query_stage_seconds histogram" in body
    for stage in ("featurize", "predict", "serve"):
        m = re.search(
            r'pio_query_stage_seconds_count\{stage="%s",batched="0"\} (\d+)'
            % stage, body)
        assert m and int(m.group(1)) >= 2, f"missing stage {stage}"
    assert "# TYPE pio_engine_compile_seconds gauge" in body
    assert 'pio_engine_compile_count{algorithm=' in body
    assert "pio_engine_query_count" in samples
    _assert_runtime_families(body, "engine")


def test_dashboard_metrics_pages():
    """The dashboard serves the registry raw at /metrics and as a
    readable table at /metrics/html, linked from the index."""
    from incubator_predictionio_tpu.tools.dashboard import Dashboard

    storage, _app_id, _key = _setup_event_storage()
    d = Dashboard(storage)
    with ServerThread(d.app) as st:
        raw = requests.get(st.base + "/metrics")
        assert raw.status_code == 200
        _assert_valid_exposition(raw.text)
        page = requests.get(st.base + "/metrics/html")
        assert page.status_code == 200 and "Telemetry" in page.text
        assert "/metrics/html" in requests.get(st.base + "/").text


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_trace_id_propagation_through_query(memory_storage, tmp_path):
    """A query carrying X-Pio-Trace-Id is traced end to end: the id is
    echoed on the response, and the sink receives the http root span
    plus the featurize/predict/serve stage spans — proving the trace
    context crossed asyncio.to_thread into Deployment.query."""
    sink = tmp_path / "spans.jsonl"
    telemetry.configure_tracer(rate=1.0, sink=str(sink))
    try:
        server = _trained_engine_server(memory_storage)
        with ServerThread(server.app) as st:
            r = requests.post(st.base + "/queries.json",
                              json={"user": "1", "num": 2},
                              headers={"X-Pio-Trace-Id": "deadbeef01"})
            assert r.status_code == 200
            assert r.headers["X-Pio-Trace-Id"] == "deadbeef01"
            # untraced request: no header, no extra spans
            r2 = requests.post(st.base + "/queries.json",
                               json={"user": "2", "num": 2})
            assert r2.status_code == 200
    finally:
        telemetry.configure_tracer(rate=0.0)
    spans = [json.loads(line) for line in
             sink.read_text().splitlines()]
    mine = [s for s in spans if s["traceId"] == "deadbeef01"]
    names = {s["span"] for s in mine}
    assert {"query.featurize", "query.predict", "query.serve"} <= names
    root = [s for s in mine if s["span"].startswith("http POST")]
    assert root and root[0]["tags"]["status"] == 200
    assert all(s["durUs"] >= 0 for s in mine)
    # rate=0 after the finally: nothing is sampled
    assert telemetry.sample_trace(None) is None


def test_trace_sampling_rules(tmp_path):
    rec = telemetry.TraceRecorder(rate=0.0, sink=str(tmp_path / "t"))
    assert rec.sample(None) is None
    assert rec.sample("upstream-id") is None  # off means off
    rec = telemetry.TraceRecorder(rate=1.0, sink=str(tmp_path / "t"))
    assert rec.sample(None) is not None
    assert rec.sample("upstream-id").trace_id == "upstream-id"


def test_event_server_trace_header_echo(tmp_path):
    """Ingest POSTs propagate the trace id too (one id follows a
    request across tiers)."""
    sink = tmp_path / "ingest_spans.jsonl"
    telemetry.configure_tracer(rate=1.0, sink=str(sink))
    try:
        storage, _app_id, key = _setup_event_storage()
        server = EventServer(storage)
        with ServerThread(server.app) as st:
            r = requests.post(
                f"{st.base}/events.json?accessKey={key}",
                json={"event": "view", "entityType": "user",
                      "entityId": "u1"},
                headers={"X-Pio-Trace-Id": "ingest-trace-7"})
            assert r.status_code == 201
            assert r.headers["X-Pio-Trace-Id"] == "ingest-trace-7"
    finally:
        telemetry.configure_tracer(rate=0.0)
    spans = [json.loads(line) for line in sink.read_text().splitlines()]
    assert any(s["traceId"] == "ingest-trace-7"
               and s["span"].startswith("http POST /events.json")
               for s in spans)


# ---------------------------------------------------------------------------
# spans: the one primitive of training and serving
# ---------------------------------------------------------------------------

def _since(t0_ns):
    return [s for s in telemetry.spans_snapshot() if s.t0_ns >= t0_ns]


def _covered_share(root, spans):
    """Share of ``root`` covered by the union of the spans beneath it that
    name a piece of work (dase.algo_train only groups the als.* spans)."""
    ivs = sorted((s.t0_ns, s.t1_ns) for s in spans
                 if s.trace_id == root.trace_id and s is not root
                 and s.name != "dase.algo_train")
    covered, at = 0, root.t0_ns
    for a, b in ivs:
        a, b = max(a, at), min(b, root.t1_ns)
        if b > a:
            covered, at = covered + b - a, b
    return covered / (root.t1_ns - root.t0_ns)


def test_span_tree_crosses_copy_context_into_worker_thread():
    """Trace id and parent ride the contextvars slot: a span opened in a
    worker thread under a copied context is a child of the span that was
    open when the context was copied — the query executor's mechanism."""
    import contextvars
    import time
    from concurrent.futures import ThreadPoolExecutor

    def work():
        with telemetry.span("t.tree.child", where="worker"):
            telemetry.add_span("t.tree.after", time.perf_counter_ns() - 10,
                               time.perf_counter_ns())
        return threading.get_ident()

    t0 = time.perf_counter_ns()
    with ThreadPoolExecutor(1) as pool:
        with telemetry.span("t.tree.root", trace_id="inst-1") as root:
            ctx = contextvars.copy_context()
            tid = pool.submit(ctx.run, work).result()
            bare = pool.submit(work).result()  # no copied context
    assert tid == bare != threading.get_ident()
    spans = {(s.name, s.trace_id): s for s in _since(t0)}
    r = spans[("t.tree.root", "inst-1")]
    child = spans[("t.tree.child", "inst-1")]
    after = spans[("t.tree.after", "inst-1")]
    assert r.parent_id is None and r.span_id == root.span_id
    assert child.parent_id == r.span_id and after.parent_id == child.span_id
    assert child.tags == {"where": "worker"}
    assert r.t0_ns <= child.t0_ns <= child.t1_ns <= r.t1_ns
    # without the copied context the worker's span is a root of its own,
    # under a small integer id
    orphan = [s for s in _since(t0) if s.name == "t.tree.child"
              and s.trace_id != "inst-1"]
    assert len(orphan) == 1 and orphan[0].parent_id is None
    assert isinstance(orphan[0].trace_id, int)


def test_span_ring_is_bounded():
    assert telemetry.RING_SIZE == 65536
    for _ in range(telemetry.RING_SIZE + 10):
        with telemetry.span("t.ring"):
            pass
    snap = telemetry.spans_snapshot()
    assert len(snap) == telemetry.RING_SIZE
    assert all(s.name == "t.ring" for s in snap[:10])      # older ones left
    assert snap[0].span_id < snap[-1].span_id               # oldest first
    snap.clear()                                            # a copy
    assert len(telemetry.spans_snapshot()) == telemetry.RING_SIZE


def test_telemetry_import_does_not_import_jax():
    """The event server records here and never loads jax: a fresh
    interpreter that imports telemetry and opens a span has no jax."""
    import subprocess

    code = ("import sys\n"
            "from incubator_predictionio_tpu.common import telemetry\n"
            "with telemetry.span('x'):\n    pass\n"
            "assert telemetry.spans_snapshot()[-1].name == 'x'\n"
            "print('JAX' if 'jax' in sys.modules else 'CLEAN')\n")
    root = os.path.dirname(os.path.dirname(
        os.path.abspath(incubator_predictionio_tpu.__file__)))
    r = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "CLEAN"


def test_compiles_are_spans_and_counters():
    """One jax.monitoring listener: every backend compile is an
    xla.compile span under whatever was open, and counts on /metrics."""
    import time

    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.workflow.context import WorkflowContext

    WorkflowContext()  # hands jax over, once per process
    reg = telemetry.registry()
    n = reg.counter("pio_xla_compiles_total", "").labels()
    secs = reg.counter("pio_xla_compile_seconds_total", "").labels()
    before, secs_before = n.value(), secs.value()
    t0 = time.perf_counter_ns()
    with telemetry.span("t.compile.parent") as parent:
        jax.jit(lambda x: jnp.tanh(x) * 1.2345 + 6.789)(
            jnp.ones((3, 7))).block_until_ready()
    assert n.value() > before and secs.value() > secs_before
    mine = [s for s in _since(t0) if s.name == "xla.compile"]
    assert mine and all(s.parent_id == parent.span_id for s in mine)
    assert all(s.tags["seconds"] > 0 and s.t0_ns < s.t1_ns for s in mine)
    body = telemetry.render_all()
    assert "# TYPE pio_xla_compiles_total counter" in body
    assert "# TYPE pio_xla_cache_events_total counter" in body


def test_run_train_leaves_its_spans_under_one_root(memory_storage):
    """A tiny run_train on the CPU: the training table's spans all hang
    under one train.run root whose trace id is the engine-instance id,
    and they cover it (nothing large happens between them)."""
    import time

    from incubator_predictionio_tpu.models.recommendation import (
        RecommendationEngine)
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import run_train

    from incubator_predictionio_tpu.controller.engine import EngineParams

    from test_dase_train_e2e import _seed_ratings

    _seed_ratings(memory_storage)
    engine = RecommendationEngine()()
    ctx = WorkflowContext(app_name="testapp", storage=memory_storage)
    # what earlier tests of this process left in the bounded cache of jitted
    # loops is dropped here, not inside the train (freeing nine executables
    # is 50 ms between two spans of a 400 ms train)
    from incubator_predictionio_tpu.ops import als

    als._train_fn_cache.clear()
    # a rank no other test trains: the step compiles here, as in a first
    # `pio train`, so the train is a second long and not 10 ms of glue
    params = EngineParams.from_json({
        "datasource": {"params": {"app_name": "testapp"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 7, "numIterations": 3, "lambda": 0.05}}]})
    t0 = time.perf_counter_ns()
    instance_id = run_train(engine, params, ctx, engine_factory_name="rec")
    spans = _since(t0)
    roots = [s for s in spans if s.name == "train.run"]
    assert len(roots) == 1
    root = roots[0]
    assert root.trace_id == instance_id and root.parent_id is None
    assert root.tags["instance"] == instance_id
    assert set(root.tags) == {"instance", "factory", "gc_ms",
                              "gc_collections"}
    mine = [s for s in spans if s.trace_id == instance_id]
    by_name = {s.name: s for s in mine}
    assert {"dase.read", "dase.prepare", "dase.algo_train", "als.layout",
            "als.init", "als.pack", "als.upload", "als.loop",
            "als.readback", "dase.serialize", "dase.persist"} <= set(by_name)
    ids = {s.span_id: s for s in mine}
    for s in mine:
        if s is not root:
            assert s.parent_id in ids, s.name
    algo = by_name["dase.algo_train"]
    assert algo.tags["algorithm"] and algo.parent_id == root.span_id
    assert by_name["als.loop"].parent_id == algo.span_id
    assert by_name["xla.compile"].parent_id == by_name["als.loop"].span_id
    assert by_name["dase.persist"].tags["bytes"] > 0
    order = ["dase.read", "dase.prepare", "als.layout", "als.pack",
             "als.init_wait", "als.upload", "als.loop", "als.readback",
             "dase.serialize", "dase.persist"]
    starts = [by_name[n].t0_ns for n in order]
    assert starts == sorted(starts)
    # the init runs on a worker thread beside the layout and the pack: in
    # the train's tree all the same, begun before the layout has ended, and
    # what the calling thread waits for it ends no earlier than it does
    init, wait = by_name["als.init"], by_name["als.init_wait"]
    assert init.parent_id == wait.parent_id == algo.span_id
    assert init.tags == {"users": "dropped", "overlap": "layout"}
    assert algo.t0_ns <= init.t0_ns <= by_name["als.layout"].t1_ns
    assert init.t1_ns <= wait.t1_ns <= by_name["als.upload"].t0_ns
    assert _covered_share(root, mine) >= 0.95


def test_train_als_in_turn_leaves_no_init_wait(monkeypatch):
    """PIO_PIPELINE=off: the parent's order of stages, the init after the
    layout on the calling thread (tag overlap=none), and no als.init_wait;
    with the overlap the wait is there even where it waits 0 ms."""
    import time

    import numpy as np

    from incubator_predictionio_tpu.ops.als import ALSParams, train_als

    rng = np.random.default_rng(3)
    u = rng.integers(0, 40, 500).astype(np.int32)
    i = rng.integers(0, 25, 500).astype(np.int32)
    r = rng.random(500).astype(np.float32)
    params = ALSParams(rank=6, num_iterations=2)

    def stages():
        t0 = time.perf_counter_ns()
        train_als(u, i, r, 40, 25, params)
        spans = sorted((s for s in _since(t0) if s.name.startswith("als.")),
                       key=lambda s: s.t0_ns)
        return [s.name for s in spans], {s.name: s for s in spans}

    monkeypatch.setenv("PIO_PIPELINE", "off")
    names, by_name = stages()
    assert names == ["als.layout", "als.init", "als.pack", "als.upload",
                     "als.loop", "als.readback"]
    assert by_name["als.init"].tags == {"users": "dropped",
                                        "overlap": "none"}
    assert by_name["als.layout"].t1_ns <= by_name["als.init"].t0_ns
    monkeypatch.delenv("PIO_PIPELINE")
    names, by_name = stages()
    assert sorted(names) == sorted(
        ["als.layout", "als.init", "als.pack", "als.init_wait",
         "als.upload", "als.loop", "als.readback"])
    assert by_name["als.init"].tags["overlap"] == "layout"
    assert by_name["als.init"].t1_ns <= by_name["als.init_wait"].t1_ns


def test_served_query_leaves_wait_and_topk_spans_under_its_root(
        memory_storage, tmp_path):
    """One POST /queries.json: the root from the middleware, the wait for
    an executor thread, the three stages and the top-k's two halves, one
    tree. query.featurize starts before query.predict (the old Trace
    stamped every start at the time of the call, after the last stage);
    the sink's line keeps its four old fields and gains the two ids."""
    import time

    sink = tmp_path / "spans.jsonl"
    telemetry.configure_tracer(rate=1.0, sink=str(sink))
    try:
        server = _trained_engine_server(memory_storage)
        with ServerThread(server.app) as st:
            t0 = time.perf_counter_ns()
            r = requests.post(st.base + "/queries.json",
                              json={"user": "1", "num": 2},
                              headers={"X-Pio-Trace-Id": "feedface02"})
            assert r.status_code == 200
            missing = requests.post(st.base + "/nothing-here", json={})
            assert missing.status_code == 404
    finally:
        telemetry.configure_tracer(rate=0.0)
    mine = [s for s in _since(t0) if s.trace_id == "feedface02"]
    by_name = {s.name: s for s in mine}
    root = by_name["http POST /queries.json"]
    assert root.parent_id is None and root.tags == {"status": 200}
    wait = by_name["query.admit_wait"]
    assert wait.parent_id == root.span_id and wait.tags["pending"] >= 1
    for stage in ("query.featurize", "query.predict", "query.serve"):
        assert by_name[stage].parent_id == root.span_id
    assert (wait.t1_ns <= by_name["query.featurize"].t0_ns
            <= by_name["query.featurize"].t1_ns
            <= by_name["query.predict"].t0_ns
            <= by_name["query.predict"].t1_ns
            <= by_name["query.serve"].t0_ns)
    ids = {s.span_id: s for s in mine}
    for name in ("topk.dispatch", "topk.wait"):
        up = by_name[name]
        while up.parent_id is not None:     # somewhere beneath predict
            up = ids[up.parent_id]
            if up.name == "query.predict":
                break
        assert up.name == "query.predict", name
    assert by_name["topk.dispatch"].t1_ns <= by_name["topk.wait"].t0_ns
    assert _covered_share(root, mine) > 0
    # the 404 is a root too, with its status
    other = [s for s in _since(t0) if s.name == "http POST /nothing-here"]
    assert other and other[0].tags == {"status": 404}

    lines = [json.loads(x) for x in sink.read_text().splitlines()]
    sunk = {x["span"]: x for x in lines if x["traceId"] == "feedface02"}
    assert set(by_name) <= set(sunk)
    for x in sunk.values():
        assert {"traceId", "span", "startUs", "durUs", "spanId",
                "parentId"} <= set(x)
    assert (sunk["query.featurize"]["startUs"]
            <= sunk["query.predict"]["startUs"]
            <= sunk["query.serve"]["startUs"])
    assert sunk["query.predict"]["parentId"] == root.span_id
    assert sunk["http POST /queries.json"]["parentId"] is None
    now_us = time.time_ns() // 1000
    assert 0 < now_us - sunk["query.featurize"]["startUs"] < 600e6  # epoch


# ---------------------------------------------------------------------------
# disabled-path guarantees
# ---------------------------------------------------------------------------

def test_disabled_path_no_allocations():
    """With PIO_METRICS off, the exact telemetry calls on the hot
    ingest path — timer_start, Counter.inc, Histogram.observe_since —
    must allocate nothing per request (timer_start returns the cached
    small int 0, the others return before touching state)."""
    fam_c = telemetry.CounterFamily("t_noalloc_total", "x")
    fam_h = telemetry.HistogramFamily("t_noalloc_seconds", "x")
    c = fam_c.labels()
    h = fam_h.labels()

    def hot_request():
        t0 = telemetry.timer_start()
        c.inc()
        h.observe_since(t0)
        with telemetry.span("t.noalloc") as sp:
            telemetry.add_span("t.noalloc.child", t0, t0)
        h.observe_raw(sp.dur_ns)

    ring_before = len(telemetry.spans_snapshot())
    telemetry.set_metrics_enabled(False)
    try:
        assert telemetry.span("a") is telemetry.span("b")  # one shared no-op
        for _ in range(100):   # warm frames, caches, freelists
            hot_request()
        gc.collect()
        before = sys.getallocatedblocks()
        for _ in range(10_000):
            hot_request()
        gc.collect()
        grown = sys.getallocatedblocks() - before
    finally:
        telemetry.set_metrics_enabled(True)
    # zero in practice; tiny slack for unrelated interpreter churn
    assert grown <= 10, f"disabled telemetry path allocated ({grown} blocks)"
    assert c.value() == 0
    _counts, total, _sum = h.snapshot()
    assert total == 0
    assert len(telemetry.spans_snapshot()) == ring_before

    # and the enabled path actually records
    hot_request()
    assert c.value() == 1
    mine = [x.name for x in telemetry.spans_snapshot()
            if x.trace_id != telemetry.PROCESS_TRACE]
    assert mine[-2:] == ["t.noalloc.child", "t.noalloc"]


def test_disabled_metrics_skip_recording():
    telemetry.set_metrics_enabled(False)
    try:
        assert telemetry.timer_start() == 0
        h = telemetry.Histogram(0, 4, 1)
        h.observe_raw(3)
        h.observe_since(0)
        assert h.snapshot()[1] == 0
    finally:
        telemetry.set_metrics_enabled(True)


# ---------------------------------------------------------------------------
# AST guard: metrics go through the registry
# ---------------------------------------------------------------------------

def test_no_adhoc_module_counter_dicts():
    """No NEW module-level counter dicts under data/api/ and workflow/:
    a counter-ish name assigned a dict/Counter literal at module scope
    is ad-hoc state the registry should own (this is exactly what
    stats.py and the ingest counters migrated away from). Enforced by
    the shared `pio lint` engine."""
    from incubator_predictionio_tpu.tools.lint import assert_rule_clean

    assert_rule_clean("no-adhoc-counters")
