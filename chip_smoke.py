#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the flagship path once, through the CLI a user would type, on ONE
TPU chip: events → `pio train` → persisted model → `pio deploy` →
`POST /queries.json`, at the full width the repo claims.

  phase K  the Pallas solves (rank 10→16, 32, 128) against a float64
           reference on a small batch, compiled by Mosaic (no interpret);
           the shadow grader's metric kernel against hand-computed values
  phase A  quickstart through the event store at ML-100k shape:
           `pio app new` → `pio import` → `pio train` on
           templates/recommendation → `pio deploy` → queries
  phase B  ML-20M shape (138,493 × 26,744 × 20,000,263), rank 32:
           `pio train --engine-dir tools/chip_smoke_engine` twice in fresh
           processes (first, then warm compile cache), deploy, queries
  phase C  the same engine at rank 128 (the wide kernel), train only

After every train a CPU child reloads the persisted model the way `pio
deploy` does and checks it against a float64 NumPy reference (the last ALS
half-step must satisfy its own normal equations) and computes the answers
the server must give.

This process never imports jax: a parent that has touched JAX holds the
chip, and a child that needs it then fails or hangs. Every child owns the
chip alone and has exited (or been killed) before the next one starts.

Exit code 0 and a last stdout line
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
only when every phase passed on a TPU. No accelerator, a missing repo, a
failed or timed-out phase → non-zero exit and no such line.

  python3 chip_smoke.py                  # the chip check (needs a TPU)
  python3 chip_smoke.py --four-chip      # builder's run on a four-chip host
  python3 chip_smoke.py --cpu-rehearsal  # plumbing at a tiny size on the CPU;
                                         # NOT a chip run and it says so
                                         # (with --four-chip: 4 virtual devices)
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
PIO = os.path.join(REPO, "bin", "pio")
SMOKE_ENGINE = os.path.join(REPO, "tools", "chip_smoke_engine")
QUICKSTART_ENGINE = os.path.join(REPO, "templates", "recommendation")

#: the driver allows 1200 s; stop with room to kill children and report
DEADLINE_SECONDS = 1100.0
FACTS_TAG = "CHIP_SMOKE_FACTS "

_t_start = time.monotonic()
_live: list[subprocess.Popen] = []


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _t_start:6.1f}s] {msg}", flush=True)


def remaining() -> float:
    return DEADLINE_SECONDS - (time.monotonic() - _t_start)


# -- child processes ---------------------------------------------------------


def _kill(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    if proc in _live:
        _live.remove(proc)


def kill_all() -> None:
    for proc in list(_live):
        _kill(proc)


def spawn(argv: list[str], env: dict, log_path: str) -> subprocess.Popen:
    """Start a child in its own session (so a timeout can kill its whole
    group), stdout+stderr to ``log_path``."""
    with open(log_path, "wb") as logf:
        proc = subprocess.Popen(argv, env=env, cwd=REPO, stdout=logf,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    _live.append(proc)
    return proc


def tail(path: str, n: int = 40) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def run_child(label: str, argv: list[str], env: dict, work: str,
              timeout: float) -> tuple[str, float]:
    """Run one child to completion; returns (its output, wall seconds).
    Non-zero exit or timeout fails the smoke."""
    log_path = os.path.join(work, f"{label}.log")
    budget = min(timeout, remaining())
    if budget <= 0:
        raise SmokeFailure(f"{label}: no time left before the deadline")
    t0 = time.monotonic()
    proc = spawn(argv, env, log_path)
    try:
        rc = proc.wait(timeout=budget)
    except subprocess.TimeoutExpired:
        _kill(proc)
        raise SmokeFailure(
            f"{label}: timed out after {budget:.0f}s\n{tail(log_path)}")
    _live.remove(proc)
    wall = time.monotonic() - t0
    if rc != 0:
        raise SmokeFailure(f"{label}: exit code {rc}\n{tail(log_path)}")
    with open(log_path, errors="replace") as f:
        return f.read(), wall


def facts_of(label: str, output: str) -> dict:
    for line in reversed(output.splitlines()):
        if line.startswith(FACTS_TAG):
            return json.loads(line[len(FACTS_TAG):])
    raise SmokeFailure(f"{label}: child printed no {FACTS_TAG.strip()} line")


def self_argv(*args: str) -> list[str]:
    return [sys.executable, os.path.abspath(__file__), *args]


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def check_device(label: str, dev: dict, want_platform: str,
                 want_count: int | None = None) -> None:
    check(dev["platform"] == want_platform,
          f"{label}: ran on platform={dev['platform']!r}, "
          f"expected {want_platform!r}")
    if want_count is not None:
        check(dev["deviceCount"] == want_count,
              f"{label}: {dev['deviceCount']} devices, expected {want_count}")


# -- the server as a child ---------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body: dict | None = None, timeout: float = 30.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


class Server:
    """`bin/pio deploy` as a child the parent talks to over HTTP and
    SIGTERMs; a clean drain exits 0."""

    def __init__(self, label: str, deploy_args: list[str], env: dict,
                 work: str):
        self.label = label
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(work, f"{label}.log")
        self.proc = spawn(
            [PIO, "deploy", *deploy_args, "--ip", "127.0.0.1",
             "--port", str(self.port)], env, self.log_path)

    def wait_ready(self, timeout: float) -> tuple[dict, float]:
        t0 = time.monotonic()
        budget = min(timeout, remaining())
        while time.monotonic() - t0 < budget:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.label}: server exited {self.proc.returncode} "
                    f"before answering\n{tail(self.log_path)}")
            try:
                status, body = http_json(self.url + "/", timeout=5.0)
                if status == 200 and body.get("status") == "alive":
                    return body, time.monotonic() - t0
            except (urllib.error.URLError, OSError, ValueError):
                pass
            time.sleep(0.5)
        raise SmokeFailure(
            f"{self.label}: not ready after {budget:.0f}s\n"
            f"{tail(self.log_path)}")

    def query(self, q: dict) -> dict:
        try:
            status, body = http_json(self.url + "/queries.json", q)
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{self.label}: query {q} → HTTP {e.code} "
                f"{e.read()[:300]!r}\n{tail(self.log_path)}")
        check(status == 200, f"{self.label}: query {q} → HTTP {status}")
        return body

    def stop(self) -> None:
        """SIGTERM → graceful drain → exit code 0."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=min(60.0, max(remaining(), 5.0)))
        except subprocess.TimeoutExpired:
            _kill(self.proc)
            raise SmokeFailure(
                f"{self.label}: did not exit within 60s of SIGTERM\n"
                f"{tail(self.log_path)}")
        _live.remove(self.proc)
        check(rc == 0, f"{self.label}: exit code {rc} after SIGTERM "
              f"(expected 0 after the drain)\n{tail(self.log_path)}")


def check_answer(label: str, q: dict, got: dict, want: dict) -> None:
    """Served itemScores against the reference the verify child computed
    from the persisted factors: same length, finite, scores equal rank by
    rank, items equal except where neighbouring scores tie."""
    items = got.get("itemScores")
    check(isinstance(items, list), f"{label}: {q} → no itemScores: {got}")
    check(len(items) == len(want["items"]),
          f"{label}: {q} → {len(items)} items, expected "
          f"{len(want['items'])}")
    for rank, (g, w_item, w_score) in enumerate(
            zip(items, want["items"], want["scores"])):
        s = g["score"]
        check(isinstance(s, float) and math.isfinite(s),
              f"{label}: {q} rank {rank}: score {s!r} not finite")
        tol = 1e-4 * max(1.0, abs(w_score))
        check(abs(s - w_score) <= tol,
              f"{label}: {q} rank {rank}: score {s} vs reference {w_score}")
        if g["item"] != w_item:
            near = [ws for wi, ws in zip(want["items"], want["scores"])
                    if wi == g["item"]]
            check(bool(near) and abs(near[0] - w_score) <= tol,
                  f"{label}: {q} rank {rank}: item {g['item']!r} vs "
                  f"reference {w_item!r} (not a tie)")


# -- phases ------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What every phase needs: where its children run and what they must
    land on."""

    env: dict
    work: str
    platform: str          # every child must report this platform ...
    devices: int | None    # ... and this many devices (None: any number)
    engine_dir: str        # tools/chip_smoke_engine, or its rehearsal copy
    n_users: int
    n_items: int

    @property
    def compute_dtype(self) -> str:
        """What computeDtype="auto" resolves to (ops/als._resolve_params):
        the dtype the device gathers factor rows in."""
        return "bfloat16" if self.platform == "tpu" else "float32"

    def with_store(self, name: str, jsonl_events: bool = False) -> "Run":
        """This run against a fresh store under the work directory."""
        store = os.path.join(self.work, name)
        os.makedirs(store, exist_ok=True)
        env = dict(self.env, PIO_FS_BASEDIR=store)
        if jsonl_events:
            # events in the JSONL log (the native codec's scan path);
            # metadata and models in sqlite
            env.update({
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "JL",
                "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
                "PIO_STORAGE_SOURCES_DB_PATH":
                    os.path.join(store, "pio.sqlite"),
                "PIO_STORAGE_SOURCES_JL_TYPE": "JSONL",
                "PIO_STORAGE_SOURCES_JL_PATH": os.path.join(store, "events"),
            })
        return dataclasses.replace(self, env=env)


def relay(label: str, output: str, tag: str) -> None:
    """Repeat a child's ``tag``-prefixed result lines under ``label``."""
    for line in output.splitlines():
        if line.startswith(tag):
            say(f"{label}: {line[len(tag):].strip()}")


def cache_entries(cache_dir: str | None) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def pio_train(run: Run, label: str, train_args: list[str], timeout: float,
              any_device_count: bool = False) -> dict:
    """`pio train` in a fresh process (tools/console.py:main under the
    fact-reporting wrapper below); returns the child's facts."""
    out, wall = run_child(label, self_argv("--child-pio", "train",
                                           *train_args), run.env, run.work,
                          timeout)
    facts = facts_of(label, out)
    check("Training completed" in out, f"{label}: no completion line")
    check(f"platform={run.platform} " in out,
          f"{label}: completion line does not say platform={run.platform}")
    dev = facts["device"]
    check_device(label, dev, run.platform,
                 None if any_device_count else run.devices)
    say(f"{label}: platform={dev['platform']} "
        f"device_kind={dev['deviceKind']!r} devices={dev['deviceCount']} "
        f"process wall {wall:.1f}s, pio train {facts['verb_seconds']:.1f}s; "
        f"compile cache {facts['cache_dir']}: "
        f"{facts['cache_hits']} hit(s), {facts['cache_misses']} miss(es) "
        f"written, {cache_entries(facts['cache_dir'])} file(s) in it; "
        f"native codec loaded={facts['native_loaded']}")
    if facts["peak_bytes_per_device"]:
        say(f"{label}: peak device bytes in use "
            f"{facts['peak_bytes_per_device']}")
    return facts


def verify_model(run: Run, label: str, engine_dir: str, variant: str | None,
                 triple_npz: str, queries: list[dict], rank: int,
                 compare_previous: bool = False) -> list[dict]:
    """CPU child: reload the persisted model as `pio deploy` would, check
    it against the float64 reference, return the answers the server must
    give to ``queries``."""
    spec = os.path.join(run.work, f"{label}.spec.json")
    out_path = os.path.join(run.work, f"{label}.expected.json")
    with open(spec, "w") as f:
        json.dump({"engine_dir": engine_dir, "variant": variant,
                   "triple_npz": triple_npz, "queries": queries,
                   "compute_dtype": run.compute_dtype,
                   "shape": [run.n_users, run.n_items, rank],
                   "compare_previous": compare_previous,
                   "out": out_path}, f)
    out, _ = run_child(label, self_argv("--child-verify", spec),
                       dict(run.env, JAX_PLATFORMS="cpu"), run.work,
                       timeout=300.0)
    relay(label, out, "[verify]")
    with open(out_path) as f:
        return json.load(f)["answers"]


def serve_and_check(run: Run, label: str, engine_dir: str,
                    queries: list[dict], answers: list[dict]) -> None:
    server = Server(label, ["--engine-dir", engine_dir], run.env, run.work)
    status, ready_s = server.wait_ready(timeout=300.0)
    for key in ("platform", "deviceKind", "deviceCount"):
        check(key in status, f"{label}: GET / carries no {key!r}")
    check_device(label, status, run.platform, run.devices)
    t0 = time.monotonic()
    for q, want in zip(queries, answers):
        check_answer(label, q, server.query(q), want)
    q_s = time.monotonic() - t0
    server.stop()
    say(f"{label}: platform={status['platform']} "
        f"device_kind={status['deviceKind']!r} "
        f"devices={status['deviceCount']}; ready in {ready_s:.1f}s, "
        f"{len(queries)} queries answered and checked in {q_s:.2f}s "
        f"(first of each shape compiles), SIGTERM → exit 0")


def phase_kernels(run: Run) -> dict:
    """Device probe + Pallas solves vs reference, in ONE child."""
    out, wall = run_child("K-kernels", self_argv("--child-kernels"), run.env,
                          run.work, timeout=300.0)
    dev = facts_of("K-kernels", out)["device"]
    if dev["platform"] != run.platform:
        raise SmokeFailure(
            f"JAX found no {run.platform} device: jax.devices()[0] is "
            f"platform={dev['platform']!r} kind={dev['deviceKind']!r} "
            f"(JAX_PLATFORMS={run.env.get('JAX_PLATFORMS')!r}). This check "
            "needs the accelerator; it does not fall back.")
    check_device("K-kernels", dev, run.platform, run.devices)
    relay("K", out, "[kernels]")
    say(f"K: platform={dev['platform']} device_kind={dev['deviceKind']!r} "
        f"devices={dev['deviceCount']} wall {wall:.1f}s")
    return dev


def write_quickstart_events(path: str, npz_path: str, n_users: int,
                            n_items: int, nnz: int) -> int:
    """Seeded ML-100k-shaped `rate` events as importable JSONL, plus the
    same triple as arrays for the verify child. Returns the event count."""
    import numpy as np

    rng = np.random.default_rng(11)
    u = rng.integers(0, n_users, nnz)
    i = np.minimum((n_items * rng.random(nnz) ** 2).astype(np.int64),
                   n_items - 1)
    r = rng.integers(1, 6, nnz)
    # one event per (user, item): a re-rating would be two equations for
    # one cell and the quickstart has none
    _, first = np.unique(u * n_items + i, return_index=True)
    first.sort()
    u, i, r = u[first], i[first], r[first]
    with open(path, "w") as f:
        for k in range(len(u)):
            sec = k // 1000
            f.write(json.dumps({
                "event": "rate", "entityType": "user",
                "entityId": f"u{u[k]}", "targetEntityType": "item",
                "targetEntityId": f"i{i[k]}",
                "properties": {"rating": int(r[k])},
                "eventTime": f"2024-01-01T{sec // 3600:02d}:"
                             f"{sec // 60 % 60:02d}:{sec % 60:02d}."
                             f"{k % 1000:03d}Z",
            }) + "\n")
    np.savez(npz_path, u=u, i=i, r=r.astype(np.float32),
             user_prefix="u", item_prefix="i")
    return len(u)


def phase_a(run: Run) -> None:
    say("phase A: quickstart through the event store (ML-100k shape)")
    run = dataclasses.replace(run.with_store("a_store", jsonl_events=True),
                              n_users=943, n_items=1682)
    events = os.path.join(run.work, "a_events.jsonl")
    triple = os.path.join(run.work, "a_triple.npz")
    n_events = write_quickstart_events(events, triple, 943, 1682, 100_000)
    out, _ = run_child("A-app-new", [PIO, "app", "new", "MyApp1"], run.env,
                       run.work, 120.0)
    check("Access Key" in out, "A-app-new: no access key printed")
    out, wall = run_child(
        "A-import", [PIO, "import", "--app-name", "MyApp1", "--input",
                     events], run.env, run.work, 300.0)
    check(f"Imported {n_events} events" in out,
          f"A-import: did not import {n_events} events\n{out[-500:]}")
    say(f"A-import: {n_events} events in {wall:.1f}s")
    facts = pio_train(run, "A-train", ["--engine-dir", QUICKSTART_ENGINE],
                      timeout=400.0)
    check(facts["native_loaded"] is True,
          "A-train: the native event codec was not loaded by the train — "
          "find_ratings ran the pure-Python scan fallback")
    queries = [{"user": "u1", "num": 4}, {"user": "u7", "num": 10},
               {"user": "u-nobody", "num": 4}]
    answers = verify_model(run, "A-verify", QUICKSTART_ENGINE, None, triple,
                           queries, rank=10)
    serve_and_check(run, "A-deploy", QUICKSTART_ENGINE, queries, answers)


def phase_b(run: Run, train_extra: tuple[str, ...] = ()) -> Run:
    """Returns the run bound to phase B's store (the four-chip tail
    trains into it once more)."""
    say(f"phase B: {run.n_users} users × {run.n_items} items, rank 32, "
        "train twice in fresh processes, deploy, query")
    run = run.with_store("b_store")
    args = ["--engine-dir", run.engine_dir, *train_extra]
    first = pio_train(run, "B-train-1", args, 500.0)
    check(first["cache_requests"] > 0,
          "B-train-1: no compile went through the persistent cache — it is "
          f"not in force (dir {first['cache_dir']})")
    if first["cache_hits"]:
        say(f"B-train-1: NOT cold — found {first['cache_hits']} cached "
            "executable(s) from an earlier run in the cache directory")
    else:
        check(first["cache_misses"] > 0,
              "B-train-1: cold train wrote nothing to the compile cache")
    warm = pio_train(run, "B-train-2", args, 500.0)
    check(warm["cache_hits"] > 0 and warm["cache_misses"] == 0,
          f"B-train-2: warm train had {warm['cache_hits']} cache hit(s) and "
          f"{warm['cache_misses']} miss(es); expected hits > 0, misses 0")
    say(f"B: first train {first['verb_seconds']:.1f}s "
        f"({first['cache_hits']} hits) vs warm {warm['verb_seconds']:.1f}s "
        f"({warm['cache_hits']} hits)")
    queries = [{"user": "17", "num": 4},            # k bucket 8
               {"user": "4242", "num": 10},         # k bucket 16
               {"user": str(run.n_users - 1), "num": 40},  # k bucket 64
               {"user": "no-such-user", "num": 10}]
    answers = verify_model(run, "B-verify", run.engine_dir, None, "synth",
                           queries, rank=32)
    serve_and_check(run, "B-deploy", run.engine_dir, queries, answers)
    return run


def phase_c(run: Run) -> None:
    say(f"phase C: {run.n_users} users × {run.n_items} items, rank 128 "
        "(the wide kernel), train only")
    run = run.with_store("c_store")
    pio_train(run, "C-train",
              ["--engine-dir", run.engine_dir, "--variant", "wide"], 600.0)
    verify_model(run, "C-verify", run.engine_dir, "wide", "synth", [],
                 rank=128)


def phase_four_chip(run: Run) -> None:
    """The builder's run on a four-chip host (not the driver's check):
    every shard_map program on four real devices, phase B over
    `--mesh=4`, then the same train on one of the four chips and the two
    models compared."""
    _, wall = run_child("M-dryrun", self_argv("--child-dryrun", "4"),
                        run.env, run.work, 600.0)
    say(f"M: __graft_entry__.dryrun_multichip(4) on four {run.platform} "
        f"devices passed in {wall:.1f}s")
    run = phase_b(run, ("--", "--mesh=4"))
    pio_train(run, "M-train-one-chip",
              ["--engine-dir", run.engine_dir, "--", "--mesh=1"], 500.0,
              any_device_count=True)  # a 1-device mesh on a 4-device host
    verify_model(run, "M-compare", run.engine_dir, None, "synth", [],
                 rank=32, compare_previous=True)


def rehearsal_engine_dir(work: str) -> str:
    """The smoke engine at ML-100k shape, for the CPU rehearsal only."""
    dst = os.path.join(work, "rehearsal_engine")
    shutil.copytree(SMOKE_ENGINE, dst)
    for name in ("engine.json", "engine.json.wide"):
        path = os.path.join(dst, name)
        with open(path) as f:
            doc = json.load(f)
        doc["datasource"]["params"]["scale"] = "ml100k"
        with open(path, "w") as f:
            json.dump(doc, f)
    return dst


# -- children (these DO import jax) ------------------------------------------


def _device_facts() -> dict:
    from incubator_predictionio_tpu.parallel.mesh import device_report

    return device_report()


def child_kernels() -> int:
    """Device report, then batched_spd_solve — the selector the trainer
    uses — against a float64 reference at the three kernel regimes."""
    import jax
    import numpy as np

    from incubator_predictionio_tpu.ops.pallas_kernels import (
        batched_spd_solve,
    )

    dev = _device_facts()
    print(FACTS_TAG + json.dumps({"device": dev}), flush=True)
    if dev["platform"] != "tpu":
        return 0  # the parent decides what a non-TPU platform means
    rng = np.random.default_rng(0)
    for k, n in ((10, 1024), (32, 1024), (128, 256)):
        m = rng.standard_normal((n, k, k)).astype(np.float32)
        a = np.einsum("nij,nkj->nik", m, m) + 0.5 * np.eye(k, dtype=np.float32)
        b = rng.standard_normal((n, k)).astype(np.float32)
        solve = jax.jit(lambda a, b: batched_spd_solve(a, b, platform="tpu"))
        hlo = solve.lower(a, b).as_text()
        if "tpu_custom_call" not in hlo:
            print(f"[kernels] rank {k}: lowered WITHOUT a Mosaic kernel")
            return 1
        x = np.asarray(solve(a, b))
        ref = np.linalg.solve(a.astype(np.float64),
                              b.astype(np.float64)[..., None])[..., 0]
        err = float(np.abs(x - ref).max() / np.abs(ref).max())
        print(f"[kernels] rank {k}: Mosaic kernel, {n} systems, max error "
              f"vs float64 reference {err:.1e}", flush=True)
        if not (np.isfinite(x).all() and err < 1e-3):
            return 1
    # ops/eval.py (the shadow grader's jitted metrics) has never run on a
    # chip either; one sample whose answer is computable by hand:
    # ranked 1,2,3,4 with 1 and 3 relevant
    from incubator_predictionio_tpu.ops.eval import ranking_metrics

    got = ranking_metrics([[1, 2, 3, 4]], [[1, 3]], k=4)
    want = {"map": (1 + 2 / 3) / 2,
            "ndcg": (1 + 1 / np.log2(4)) / (1 + 1 / np.log2(3)),
            "auc": 3 / 4}
    worst = max(abs(got[m] - want[m]) for m in want)
    print(f"[kernels] ops/eval ranking_metrics on device: MAP {got['map']:.4f} "
          f"NDCG {got['ndcg']:.4f} AUC {got['auc']:.4f}, worst difference "
          f"from the hand-computed values {worst:.1e}", flush=True)
    return 0 if worst < 1e-4 else 1


def child_pio(argv: list[str]) -> int:
    """tools/console.py:main — what bin/pio execs — plus one line of
    facts the parent cannot see from outside: the devices, the compile
    cache's directory and its hit/miss counters, whether the train loaded
    the native codec."""
    import collections

    import jax

    counts: collections.Counter = collections.Counter()

    def on_event(event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/"):
            counts[event.rsplit("/", 1)[1]] += 1

    jax.monitoring.register_event_listener(on_event)
    from incubator_predictionio_tpu.tools import console

    t0 = time.perf_counter()
    rc = console.main(argv)
    verb_seconds = time.perf_counter() - t0
    from incubator_predictionio_tpu import native

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    print(FACTS_TAG + json.dumps({
        "device": _device_facts(),
        "verb_seconds": verb_seconds,
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "cache_requests": counts["compile_requests_use_cache"],
        "cache_hits": counts["cache_hits"],
        "cache_misses": counts["cache_misses"],
        "native_loaded": native.loaded() is not None,
        "peak_bytes_per_device": peaks,
    }), flush=True)
    return rc


def child_dryrun(n: int) -> int:
    import __graft_entry__ as ge

    ge.dryrun_multichip(n)
    return 0


def child_verify(spec_path: str) -> int:
    """Reload the newest COMPLETED instance the way `pio deploy` does and
    hold it against float64 NumPy. Runs on the CPU (the chip may be busy
    and is not needed)."""
    import ml_dtypes
    import numpy as np

    from incubator_predictionio_tpu.data.storage.registry import Storage
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import (
        load_deployment,
    )
    from incubator_predictionio_tpu.workflow.json_extractor import (
        engine_and_params_from_json, load_engine_json,
    )

    with open(spec_path) as f:
        spec = json.load(f)
    engine_json = load_engine_json(
        os.path.join(spec["engine_dir"], "engine.json"), spec["variant"])
    engine, params, factory = engine_and_params_from_json(
        engine_json, spec["engine_dir"])
    deployment, instance, _ = load_deployment(
        engine, None, WorkflowContext(storage=Storage.instance()),
        engine_factory_name=factory,
        engine_variant=engine_json.get("id", "default"))
    model = deployment.models[0]
    x = np.asarray(model.factors.user_factors)
    y = np.asarray(model.factors.item_factors)
    n_users, n_items, rank = spec["shape"]
    ok = True

    def report(cond: bool, msg: str) -> None:
        nonlocal ok
        ok = ok and bool(cond)
        print(f"[verify] {'ok  ' if cond else 'FAIL'} {msg}", flush=True)

    report(x.shape == (n_users, rank) and y.shape == (n_items, rank),
           f"instance {instance.id}: user factors {x.shape}, item factors "
           f"{y.shape} (expected ({n_users}, {rank}) / ({n_items}, {rank}))")
    report(np.isfinite(x).all() and np.isfinite(y).all(),
           "all factors finite")

    if spec["triple_npz"] == "synth":
        # the smoke engine's own seeded triple (tools/chip_smoke_engine)
        from bench import SCALES, synth_ratings

        ds = dict(params.data_source_params)
        u, i, r = synth_ratings(*SCALES[ds["scale"]], ds["seed"])
        up = ip = ""
    else:
        z = np.load(spec["triple_npz"])
        u, i, r = z["u"], z["i"], z["r"]
        up, ip = str(z["user_prefix"]), str(z["item_prefix"])
    # external id number → model row, through the model's own id maps
    u_row = np.array([model.users.get(f"{up}{a}", -1)
                      for a in range(int(u.max()) + 1)])
    i_row = np.array([model.items.get(f"{ip}{a}", -1)
                      for a in range(int(i.max()) + 1)])
    um, im = u_row[u], i_row[i]
    report((um >= 0).all() and (im >= 0).all(),
           f"every one of the {len(u)} training events maps to a model row")

    # The loop's last half-step solved the ITEM side from the final user
    # factors, so every item row must satisfy its own ridge normal
    # equations (Σ x xᵀ + λI) y = Σ r x, with x rounded the way the
    # device gathered it. Backward error, so conditioning does not enter.
    algo = dict(params.algorithm_params_list[0][1])
    lam = float(algo.get("lambda", 0.01))
    xg = x.astype(ml_dtypes.bfloat16) if spec["compute_dtype"] == "bfloat16" \
        else x
    xg = xg.astype(np.float64)
    counts = np.bincount(im, minlength=n_items)
    rated = np.nonzero(counts)[0]
    order = rated[np.argsort(counts[rated])]
    sample = order[np.linspace(0, len(order) - 1, 48).astype(int)]
    by_item = np.argsort(im, kind="stable")
    starts = np.searchsorted(im[by_item], np.arange(n_items + 1))
    worst = 0.0
    for it in sample:
        sel = by_item[starts[it]:starts[it + 1]]
        p = xg[um[sel]]
        a = p.T @ p + lam * np.eye(rank)
        b = p.T @ r[sel].astype(np.float64)
        yi = y[it].astype(np.float64)
        resid = np.linalg.norm(a @ yi - b) / (
            np.linalg.norm(a, 2) * np.linalg.norm(yi) + np.linalg.norm(b))
        worst = max(worst, float(resid))
    report(worst < 1e-3,
           f"last half-step vs float64 reference on {len(sample)} items "
           f"(1 to {counts[sample].max()} ratings each): worst backward "
           f"error {worst:.1e} (gather dtype {spec['compute_dtype']})")
    pred = np.einsum("nk,nk->n", x[um[:1_000_000]], y[im[:1_000_000]])
    rmse = float(np.sqrt(np.mean((pred - r[:1_000_000]) ** 2)))
    rms = float(np.sqrt(np.mean(r[:1_000_000] ** 2)))
    report(np.isfinite(rmse) and rmse < rms,
           f"train RMSE {rmse:.3f} on the first {len(pred)} events "
           f"(an all-zero model scores {rms:.3f})")

    if spec.get("compare_previous"):
        # the same data trained on another mesh shape one train earlier
        done = Storage.instance().get_meta_data_engine_instances() \
            .get_completed(factory or "engine", "1",
                           engine_json.get("id", "default"))
        prev_dep, prev, _ = load_deployment(
            engine, done[1].id, WorkflowContext(storage=Storage.instance()),
            engine_factory_name=factory,
            engine_variant=engine_json.get("id", "default"))
        for name, new, old in (
                ("user", x, prev_dep.models[0].factors.user_factors),
                ("item", y, prev_dep.models[0].factors.item_factors)):
            diff = np.abs(new - np.asarray(old))
            tight = float(np.mean(diff <= 5e-5 + 5e-4 * np.abs(old)))
            loose = float(np.mean(diff <= 2e-4 + 2e-3 * np.abs(old)))
            rel = float(np.linalg.norm(new - old) / np.linalg.norm(old))
            report(rel < 1e-2,
                   f"{name} factors vs instance {prev.id}: relative "
                   f"difference {rel:.1e}, max abs {float(diff.max()):.1e}; "
                   f"{tight:.4%} of entries within rtol 5e-4/atol 5e-5, "
                   f"{loose:.4%} within rtol 2e-3/atol 2e-4 "
                   "(tests/test_als_model_axis.py tolerances)")

    answers = []
    for q in spec["queries"]:
        row = model.users.get(q["user"])
        if row is None:
            answers.append({"items": [], "scores": []})
            continue
        scores = y.astype(np.float64) @ x[row].astype(np.float64)
        top = np.argsort(-scores, kind="stable")[:q["num"]]
        answers.append({"items": [model.items.inverse(int(t)) for t in top],
                        "scores": [float(scores[t]) for t in top]})
    with open(spec["out"], "w") as f:
        json.dump({"answers": answers}, f)
    return 0 if ok else 1


# -- main --------------------------------------------------------------------


def run(ns: argparse.Namespace) -> dict:
    """Every phase; returns the device for the result line."""
    for need in (PIO, SMOKE_ENGINE, QUICKSTART_ENGINE,
                 os.path.join(REPO, "incubator_predictionio_tpu")):
        if not os.path.exists(need):
            raise SmokeFailure(
                f"{os.path.relpath(need, REPO)} is missing: chip_smoke.py "
                "runs the repository it sits in, and this directory does "
                "not hold it")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if ns.cpu_rehearsal:
            say("CPU REHEARSAL of the plumbing at a tiny size — NOT a chip "
                "run; nothing below is a device measurement")
            env["JAX_PLATFORMS"] = "cpu"
            if ns.four_chip:  # four virtual CPU devices stand in
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "")
                    + " --xla_force_host_platform_device_count=4").strip()
            run_ = Run(env, work, "cpu", 4 if ns.four_chip else None,
                       rehearsal_engine_dir(work),
                       n_users=943, n_items=1682)          # ML-100k
        else:
            run_ = Run(env, work, "tpu", 4 if ns.four_chip else 1,
                       SMOKE_ENGINE, n_users=138_493, n_items=26_744)
        dev = phase_kernels(run_)
        if ns.four_chip:
            phase_four_chip(run_)
        else:
            phase_a(run_)
            phase_b(run_)
            phase_c(run_)
        return {"platform": dev["platform"], "kind": dev["deviceKind"],
                "count": dev["deviceCount"]}
    finally:
        kill_all()
        if ns.keep:
            say(f"child logs kept in {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0].startswith("--child-"):
        sys.path.insert(0, REPO)
        mode, rest = argv[0], argv[1:]
        if mode == "--child-kernels":
            return child_kernels()
        if mode == "--child-pio":
            return child_pio(rest)
        if mode == "--child-verify":
            return child_verify(rest[0])
        if mode == "--child-dryrun":
            return child_dryrun(int(rest[0]))
        raise SystemExit(f"unknown child mode {mode}")
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="tiny sizes on the CPU: checks the script's "
                        "plumbing, NOT the chip")
    p.add_argument("--four-chip", action="store_true",
                   help="builder's run on a four-chip host: "
                        "dryrun_multichip(4) + phase B with --mesh=4")
    p.add_argument("--keep", action="store_true",
                   help="keep the child logs and stores")
    ns = p.parse_args(argv)
    try:
        dev = run(ns)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    if "jax" in sys.modules:
        print("[smoke] FAILED: the parent process imported jax",
              file=sys.stderr, flush=True)
        return 1
    wall = time.monotonic() - _t_start
    if ns.cpu_rehearsal:
        say(f"rehearsal plumbing passed in {wall:.0f}s")
        print(json.dumps({"ok": False, "rehearsal": True,
                          "note": "CPU rehearsal, not a chip run",
                          "device": dev}))
        return 0
    say(f"every phase passed in {wall:.0f}s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
