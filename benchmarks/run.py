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
``BENCHMARK.json`` lists for the cell. A deployment and a traffic kind are
files too: the configuration's ``deployment`` names ``deployments/<name>.py``
(engines, inputs from the seed, request bodies, reference and comparison,
the calls a traced run wraps), the mix's ``kind`` names ``kinds/<kind>.py``
(``phases(record, workdir, deployment)``: window, after_window, check,
end_to_end, attempted). This file knows no template, no algorithm, no request
field and no reference.

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
import importlib.util
import json
import os
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LIB = os.path.join(HERE, "lib")

EXIT_NO_PROGRAM = 2
EXIT_NO_CHIP = 3

# the kinds and lib/serving.py reach this file as ``run``, also where it is
# the script
sys.modules.setdefault("run", sys.modules[__name__])


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


def load_module(*parts: str):
    """``benchmarks/<parts>.py`` as a module: a metric, a traffic kind or a
    deployment, found by the name that a data file gives."""
    name = "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, *parts) + ".py")
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


# -- main --------------------------------------------------------------------


def per_layer_metrics(record: Record, manifest: dict, cell_name: str) -> dict:
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        value = load_module("metrics", m["name"]).read(record)
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
        phases = load_module("kinds", traffic["kind"]).phases(
            record, workdir, load_module("deployments", config["deployment"]))
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
