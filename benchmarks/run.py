#!/usr/bin/env python3
"""The benchmark's one command.

  python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip: it loads, warms up (set-up), measures for
``--seconds``, checks what the timed path produced against the plain
reference, prints ONE JSON object as the last line of standard output and
exits. ``--workload <cell>`` opens ``benchmarks/cells/<cell>.json`` and
nothing else decides what runs: the cell names its configuration
(``configs/<config>.json``) and its traffic mix (``traffic/<mix>.json``);
the per-layer metrics are the files ``metrics/<name>.py`` that
``BENCHMARK.json`` lists for the cell. Two traffic kinds are code here:
``retrain`` and ``queries``.

No accelerator, fewer chips than the cell asks for, or a checkout without
the program: a non-zero exit and no result line, never a CPU number.
``--rehearse`` runs the plumbing at the configuration's tiny ``rehearse``
size on the CPU; its line says it is not a chip run.

The compile cache is wherever JAX_COMPILATION_CACHE_DIR says, else the
program's own ``<checkout>/.jax_cache``: the harness sets nothing of its own.
"""

from __future__ import annotations

T_PROCESS_START = __import__("time").monotonic()

import argparse
import collections
from concurrent.futures import ThreadPoolExecutor
import gc
import importlib.util
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LIB = os.path.join(HERE, "lib")

EXIT_NO_PROGRAM = 2
EXIT_NO_CHIP = 3


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_PROCESS_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearse: bool = False) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic) of ``cells/<name>.json``; ``rehearse``
    lays each file's tiny ``rehearse`` values over it and holds JAX to the
    CPU. Also puts the program and the benchmark's modules on the path."""
    cell = load_json("cells", name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config.update(config["rehearse"])
        traffic.update(traffic.get("rehearse", {}))
    for p in (ROOT, LIB, os.path.join(HERE, "engines")):
        if p not in sys.path:
            sys.path.insert(0, p)
    return cell, config, traffic


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the run's record ---------------------------------------------------------


class Record:
    """Everything a per-layer metric may read: host spans, the reduced
    trace, the configuration, the traffic and what the window did."""

    def __init__(self, cell: dict, config: dict, traffic: dict, args):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seconds = float(args.seconds)
        self.seed = int(args.seed)
        self.traced = bool(args.trace)
        self.spans: list[tuple[str, float, float]] = []
        self.window_spans: list[tuple[str, float, float]] = []
        self.trace: dict | None = None
        self.window: dict = {}
        self.device: dict = {}
        self.peaks: dict = {}
        self._lock = threading.Lock()

    def add_span(self, name: str, t0: float, t1: float) -> None:
        with self._lock:
            self.spans.append((name, t0, t1))

    def close_window(self) -> None:
        """What follows (a traced extra train) is not the window's."""
        with self._lock:
            self.window_spans = list(self.spans)

    def window_span_seconds(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1 in self.window_spans if n == name]


def wrap_span(record: Record, owner, attr: str, name: str):
    """Record a span around ``owner.attr`` from outside, for this run only.
    The span is also written into the profiler's trace (``bench:<name>``).
    Returns the undo."""
    import jax

    inner = getattr(owner, attr)

    def wrapped(*a, **kw):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:" + name):
            try:
                return inner(*a, **kw)
            finally:
                record.add_span(name, t0, time.perf_counter())

    setattr(owner, attr, wrapped)
    return lambda: setattr(owner, attr, inner)


class CompileCounts:
    """jax.monitoring counts, by phase: persistent-cache hits and misses and
    backend compiles, so that a compile inside the window shows."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.counts: collections.Counter = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/"):
            self.counts[(self.phase, event.rsplit("/", 1)[1])] += 1

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts[(self.phase, "backend_compiles")] += 1

    def line(self) -> str:
        parts = []
        for phase in ("setup", "window", "after"):
            got = {k[1]: v for k, v in self.counts.items() if k[0] == phase}
            parts.append(f"{phase}: " + (", ".join(
                f"{k}={v}" for k, v in sorted(got.items())) or "none"))
        return "compile cache and compiles by phase — " + "; ".join(parts)


def memory_peak_bytes() -> int:
    import jax

    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks)


def make_storage():
    """The program's MEMORY sources for metadata and models: a multi-GB
    artifact goes through the checksummed writer and reader, and nothing is
    written to disk."""
    from incubator_predictionio_tpu.data.storage.registry import Storage

    env = {"PIO_STORAGE_SOURCES_MEM_TYPE": "MEMORY"}
    for repo in ("METADATA", "MODELDATA", "EVENTDATA"):
        env[f"PIO_STORAGE_REPOSITORIES_{repo}_SOURCE"] = "MEM"
        env[f"PIO_STORAGE_REPOSITORIES_{repo}_NAME"] = f"bench_{repo.lower()}"
    return Storage(env)


def engine_params(config: dict, key: str, num_iterations: int | None = None):
    from incubator_predictionio_tpu.controller import EngineParams

    algo = {"rank": config["rank"], "lambda": config["lambda"],
            "seed": config["seed"],
            # what "auto" resolves to on a TPU, said outright so that the
            # rehearsal on the CPU gathers in the same type
            "computeDtype": config.get("gather_dtype", "auto"),
            "numIterations": (config["numIterations"]
                              if num_iterations is None else num_iterations)}
    return EngineParams.from_json({
        "datasource": {"params": {"key": key}},
        "algorithms": [{"name": "als", "params": algo}],
    })


class Tracer:
    """The profiler around one piece of the run; the reduced trace goes into
    the record and the files are removed."""

    WINDOW = "traced"

    def __init__(self, record: Record, workdir: str):
        self.record, self.dir = record, os.path.join(workdir, "trace")

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench:" + self.WINDOW)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        import trace_reduce

        self._ann.__exit__(*exc)
        jax.profiler.stop_trace()
        if exc[0] is None:
            path = trace_reduce.find_xplane(self.dir)
            say(f"trace: {os.path.getsize(path) / 1e6:.1f} MB")
            raw = trace_reduce.read_trace(path)
            if self.record.device["platform"] == "cpu" and not raw["devices"]:
                say("rehearsal: the CPU trace has no device plane; the "
                    "trace-fed metrics are left out")
                return
            self.record.trace = t = trace_reduce.reduce_trace(raw, self.WINDOW)
            top = sorted(t["module_seconds"].items(), key=lambda kv: -kv[1])
            say("trace modules: " + json.dumps(top[:8]))


# -- traffic kind: retrain ---------------------------------------------------


def kind_retrain(record: Record, workdir: str) -> dict:
    """Set-up: one run_train at the mix's warm-up iterations on the data of
    seed + offset (same plan, same executable). Window: whole run_train calls
    on the data of --seed at the configuration's numIterations, another
    started while less than --seconds have passed."""
    import jax

    import bench_engine
    import datagen
    import reference
    from incubator_predictionio_tpu.models import recommendation
    from incubator_predictionio_tpu.ops import als
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import (
        load_deployment, run_train,
    )

    cfg, traffic = record.config, record.traffic
    storage = make_storage()
    engine = bench_engine.retrain_engine()
    factory = "bench_engine.retrain_engine"

    degs = datagen.degrees(cfg)

    def data_of(seed: int) -> str:
        u, i, r = datagen.ratings(cfg, seed, degs)
        key = f"ratings-{seed}"
        bench_engine.INPUTS[key] = {
            "user": u, "item": i, "rating": r,
            "n_users": cfg["n_users"], "n_items": cfg["n_items"]}
        return key

    def train(key: str, iters: int | None = None) -> str:
        return run_train(engine, engine_params(cfg, key, iters),
                         WorkflowContext(storage=storage),
                         engine_factory_name=factory)

    with ThreadPoolExecutor(2) as pool:
        warm = pool.submit(
            data_of, record.seed + int(traffic["warmup_seed_offset"]))
        key = data_of(record.seed)
        warm_key = warm.result()
    say(f"data: {len(bench_engine.INPUTS[key]['user'])} ratings twice")
    undo = []
    if record.traced:
        undo.append(wrap_span(record, recommendation, "train_als",
                              "train_als"))
        undo.append(wrap_span(record, als, "plan_and_fill_both",
                              "plan_and_fill_both"))
    models = storage.get_model_data_models()
    warm_id = train(warm_key, int(traffic["warmup_iterations"]))
    models.delete(warm_id)
    del bench_engine.INPUTS[warm_key]
    record.spans.clear()
    gc.collect()

    def window() -> dict:
        ids = []
        t0 = time.perf_counter()
        while True:
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:run_train"):
                ids.append(train(key))
            t2 = time.perf_counter()
            record.add_span("run_train", t1, t2)
            if t2 - t0 >= record.seconds:
                return {"instance_ids": ids, "wall_s": t2 - t0,
                        "trains": len(ids)}

    def after_window(win: dict) -> None:
        for old in win["instance_ids"][:-1]:
            models.delete(old)
        if record.traced:
            # the profiler covers ONE extra run_train at the warm-up's
            # iterations: a whole train's trace does not come back under
            # the cap
            with Tracer(record, workdir):
                with jax.profiler.TraceAnnotation("bench:run_train"):
                    extra = train(key, int(traffic["warmup_iterations"]))
            models.delete(extra)
            record.window["traced_iterations"] = int(
                traffic["warmup_iterations"])
        for u in undo:
            u()

    def check(win: dict) -> dict:
        d = bench_engine.INPUTS[key]
        t0 = time.perf_counter()
        # the reference starts on its host passes while the artifact is read
        # back through the verifying loader
        with ThreadPoolExecutor(1) as pool:
            ref = pool.submit(
                reference.als_reference, d["user"], d["item"], d["rating"],
                cfg["n_users"], cfg["n_items"], cfg["rank"], cfg["lambda"],
                cfg["seed"], cfg["numIterations"], cfg["gather_dtype"],
                log=say)
            dep, _inst, _ = load_deployment(
                engine, win["instance_ids"][-1],
                WorkflowContext(storage=storage), engine_factory_name=factory)
            got = dep.models[0].factors
            wx, wy = ref.result()
        say(f"reference and read-back: {time.perf_counter() - t0:.1f}s")
        out = reference.als_compare(got.user_factors, got.item_factors,
                                    wx, wy, cfg["limits"], degs)
        say("gaps seen: " + json.dumps(out.pop("_seen")))
        return out

    return {"window": window, "after_window": after_window, "check": check,
            "end_to_end": lambda win: {
                "retrain_s": (win["wall_s"] / win["trains"], "s")},
            "attempted": lambda win: (win["trains"], 0)}


# -- traffic kind: queries ---------------------------------------------------


class ServerThread:
    """The EngineServer's aiohttp application on a loop of its own."""

    def __init__(self, server):
        import asyncio
        import socket

        self.server = server
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{self.port}"
        self._loop = asyncio.new_event_loop()
        self._started = threading.Event()
        self._stop = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        import asyncio

        from aiohttp import web

        asyncio.set_event_loop(self._loop)

        async def main():
            self._stop = asyncio.Event()
            self.server.app["stopper"] = self._stop.set
            runner = web.AppRunner(self.server.app, shutdown_timeout=5.0)
            await runner.setup()
            await web.TCPSite(runner, "127.0.0.1", self.port).start()
            self._started.set()
            await self._stop.wait()
            await runner.cleanup()

        self._loop.run_until_complete(main())

    def start(self):
        self._thread.start()
        if not self._started.wait(30):
            raise RuntimeError("the engine server did not start")

    def stop(self):
        import asyncio

        fut = asyncio.run_coroutine_threadsafe(
            self.server.drain_then_stop(self._stop.set), self._loop)
        fut.result(timeout=60)
        self._thread.join(30)
        self.server.finalize_shutdown()
        self._loop.close()


def post_json(url: str, body: dict, timeout: float = 660.0):
    """One warm-up query. The first query of each ``num`` compiles (half a
    minute at 9.4M items in a checkout with an empty cache), which the
    server's default budget of 30 s per query answers with 504: the warm-up
    asks for the longest budget a client may have. The window's requests
    carry no such header."""
    import urllib.request

    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 "X-Pio-Deadline-Ms": "600000"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


class Offer:
    """One window's load: the schedule from the traffic file and the seed,
    and the load generator as a child that is READY before the window and
    starts on the parent's word."""

    def __init__(self, base_url: str, traffic: dict, n_users: int, seed: int,
                 seconds: float, workdir: str):
        import numpy as np

        import loadgen

        self.seconds = seconds
        self.sched = loadgen.schedule(traffic, n_users, seed, seconds)
        n = len(self.sched["due"])
        rng = np.random.default_rng(seed)
        self.keep = sorted(rng.permutation(n)[:int(
            traffic["compared_requests"])].tolist())
        self.out_path = os.path.join(workdir, "loadgen_out.json")
        self.job = dict(self.sched, base_url=base_url, out=self.out_path,
                        keep_bodies=self.keep,
                        answer_timeout_s=seconds + 60.0,
                        connections=int(traffic.get("connections", 64)))
        job_path = os.path.join(workdir, "loadgen_job.json")
        with open(job_path, "w") as f:
            json.dump(self.job, f)
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(LIB, "loadgen.py"), job_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        try:
            ready = self.child.stdout.readline().strip()
            if ready != "READY":
                raise RuntimeError(f"the load generator said {ready!r}")
        except BaseException:
            self.kill()
            raise

    def kill(self) -> None:
        self.child.kill()
        self.child.wait()

    def go(self) -> float:
        """Start the window a quarter of a second from now; returns the
        start on the wall clock."""
        start = time.time() + 0.25
        self.child.stdin.write(f"{start!r}\n")
        self.child.stdin.flush()
        return start

    def result(self) -> dict:
        """Wait for every answer (the child waits a minute past the close
        for each) and read what the child wrote."""
        try:
            rc = self.child.wait(timeout=self.seconds + 90.0)
        except BaseException:
            self.kill()
            raise
        if rc != 0:
            raise RuntimeError(f"the load generator exited {rc}")
        with open(self.out_path) as f:
            res = json.load(f)
        return {"job": self.job, "result": res, "summary": res["summary"],
                "wall_s": res["summary"]["wall_s"]}


def serve_setup(record: Record):
    """Factors from the seed, persisted and loaded by the normal path into
    a real EngineServer behind a ServerThread, every ``num`` of the mix
    warmed. Returns (server thread, server, draw)."""
    import bench_engine
    import datagen
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import run_train
    from incubator_predictionio_tpu.workflow.create_server import EngineServer

    cfg, traffic = record.config, record.traffic
    storage = make_storage()
    engine = bench_engine.serve_engine()
    factory = "bench_engine.serve_engine"
    n_users, n_items, rank = cfg["n_users"], cfg["n_items"], cfg["rank"]

    def draw(n_rows: int, stream: int):
        return datagen.factors(n_rows, rank, record.seed, stream)

    key = f"factors-{record.seed}"
    bench_engine.INPUTS[key] = {
        "user_factors": draw(n_users, datagen.USER_STREAM),
        "item_factors": draw(n_items, datagen.ITEM_STREAM)}
    say("factors drawn")
    run_train(engine, engine_params(cfg, key),
              WorkflowContext(storage=storage), engine_factory_name=factory)
    del bench_engine.INPUTS[key]
    gc.collect()
    say("model persisted")
    server = EngineServer(engine, engine_factory_name=factory,
                          storage=storage)
    say("server loaded")
    st = ServerThread(server)
    st.start()
    nums = sorted({int(n) for n, _ in traffic["num_shares"]})
    for num in nums:
        for user in ("0", "1"):
            status, body = post_json(st.base + "/queries.json",
                                     {"user": user, "num": num})
            if status != 200 or len(body["itemScores"]) != num:
                raise RuntimeError(f"warm-up query num={num}: {status} {body}")
    return st, server, draw


def kind_queries(record: Record, workdir: str) -> dict:
    """Set-up: ``serve_setup``. Window: the load generator (a child that
    never imports jax) offers the mix's fixed rate, open loop, for
    --seconds."""
    import jax

    import datagen
    import loadgen
    import reference
    from incubator_predictionio_tpu.models import _sharded_serving

    cfg, traffic = record.config, record.traffic
    n_users, n_items = cfg["n_users"], cfg["n_items"]
    st, server, draw = serve_setup(record)
    undo = []
    if record.traced:
        undo.append(wrap_span(record, _sharded_serving.ShardedCatalog,
                              "top_k", "top_k"))

    offer = Offer(st.base, traffic, n_users, record.seed, record.seconds,
                  workdir)
    sched, keep = offer.sched, offer.keep

    def window() -> dict:
        start = offer.go()
        if record.traced:
            lead = float(traffic.get("trace_after_s", 2.0))
            time.sleep(max(0.0, start + lead - time.time()))
            try:
                with Tracer(record, workdir):
                    time.sleep(float(traffic.get("trace_seconds", 4.0)))
            except BaseException:
                offer.kill()
                raise
        return offer.result()

    def after_window(win: dict) -> None:
        for u in undo:
            u()
        st.stop()
        server.deployment = None

    def end_to_end(win: dict) -> dict:
        lat = win["summary"]["latency_ms"]
        return {"query_p50_ms": (loadgen.percentile(lat, 50), "ms"),
                "query_p95_ms": (loadgen.percentile(lat, 95), "ms")}

    def check(win: dict) -> dict:
        gc.collect()
        items = jax.device_put(draw(n_items, datagen.ITEM_STREAM))
        users = draw(n_users, datagen.USER_STREAM)
        res = win["result"]
        served, malformed = [], 0
        for k in keep:
            if res["status"][k] != 200:
                continue  # counted in ``failed``; never answered: below
            body = res["bodies"].get(str(k))
            user = sched["user"][k]
            try:
                scores = body["itemScores"]
                served.append({
                    "row": int(user) if user.isdigit() else None,
                    "num": sched["num"][k],
                    "items": [int(s["item"]) for s in scores],
                    "scores": [float(s["score"]) for s in scores]})
            except (KeyError, TypeError, ValueError):
                malformed += 1
        never = sum(1 for s in res["status"] if s <= 0)
        gaps = reference.topk_gaps(items, users, served)
        say(f"compared {gaps['compared']} of {len(keep)} sampled answers")
        lim = cfg["limits"]
        return {
            "rank_gap": (gaps["rank_gap"], lim["rank_gap"]),
            "score_gap": (gaps["score_gap"], lim["score_gap"]),
            "malformed": (gaps["malformed"] + malformed, 0),
            "unanswered": (never, 0),
        }

    return {"window": window, "after_window": after_window, "check": check,
            "end_to_end": end_to_end,
            "attempted": lambda win: (win["summary"]["attempted"],
                                      win["summary"]["failed"])}


KINDS = {"retrain": kind_retrain, "queries": kind_queries}


# -- main --------------------------------------------------------------------


def per_layer_metrics(record: Record, manifest: dict, cell_name: str) -> dict:
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        mod = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                          "metric_" + m["name"].replace(".", "_"))
        value = mod.read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU: checks the plumbing, NOT a "
                         "chip run")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "incubator_predictionio_tpu")):
        print("benchmarks/run.py: this directory does not hold the program "
              "(incubator_predictionio_tpu/); nothing to measure",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell, config, traffic = load_cell(args.workload, args.rehearse)
    if args.rehearse:
        say("REHEARSAL at a tiny size on the CPU — NOT a chip run; nothing "
            "below is a device measurement")

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and (platform != "tpu"
                              or len(devices) < int(cell["chips"])):
        print(f"benchmarks/run.py: JAX found platform={platform!r} with "
              f"{len(devices)} device(s); cell {args.workload!r} needs "
              f"{cell['chips']} TPU chip(s). No fallback.", file=sys.stderr)
        return EXIT_NO_CHIP
    import work

    record = Record(cell, config, traffic, args)
    record.device = {"platform": platform, "kind": devices[0].device_kind,
                     "count": len(devices)}
    if not args.rehearse:
        record.peaks = work.peaks_for(devices[0].device_kind)
    compiles = CompileCounts()

    with tempfile.TemporaryDirectory(prefix="bench_") as workdir:
        phases = KINDS[traffic["kind"]](record, workdir)
        setup_s = time.monotonic() - T_PROCESS_START
        say(f"set-up done in {setup_s:.1f}s")
        compiles.phase = "window"
        win = phases["window"]()
        compiles.phase = "after"
        record.close_window()
        record.window.update(win)
        say(f"window closed after {win['wall_s']:.2f}s")
        peak = memory_peak_bytes()
        phases["after_window"](win)
        compared = phases["check"](win)
    attempted, failed = phases["attempted"](win)

    e2e = dict(phases["end_to_end"](win), setup_s=(setup_s, "s"))
    if args.trace:
        metrics = per_layer_metrics(record, manifest, args.workload)
    else:
        metrics = {k: {"value": float(v), "unit": u}
                   for k, (v, u) in e2e.items()}
    correct = all(v <= lim for v, lim in compared.values())
    device = dict(record.device, memory_peak_bytes=peak)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if args.trace and record.trace:
        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
        line["breakdown"] = {"device_ops": record.trace["device_ops"],
                             "idle_gaps": record.trace["idle_gaps"]}
    if args.rehearse:
        line["rehearsal"] = "CPU rehearsal at a tiny size, not a chip run"
    line["end_to_end_seen"] = {k: v for k, (v, _u) in e2e.items()}
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in compared.items()}
    say(compiles.line())
    for k, (v, lim) in compared.items():
        print(f"compared {k}: {v!r} (limit {lim!r}) "
              f"{'ok' if v <= lim else 'OVER'}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
