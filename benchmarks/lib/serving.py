"""Serving plumbing of the query kinds, and of ``knee.py``: the EngineServer
on a loop of its own, one warm-up POST, the window's load as a child process,
and the set-up that ends in a warmed server. It knows no template, no request
field and no reference: the engine, the inputs, the bodies and the warm-up
come from the deployment file that the configuration names."""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import threading
import time

import run as bench


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
    """One warm-up query. The first query of a shape compiles (half a minute
    at 9.4M items in a checkout with an empty cache), which the server's
    default budget of 30 s per query answers with 504: the warm-up asks for
    the longest budget a client may have. The window's requests carry no
    such header."""
    import urllib.request

    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 "X-Pio-Deadline-Ms": "600000"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


class Offer:
    """One window's load: the schedule from the traffic file and the seed,
    each row turned into its JSON body by the deployment (``bodies``), and
    the load generator as a child that is READY before the window and starts
    on the parent's word."""

    def __init__(self, base_url: str, traffic: dict, n_users: int, seed: int,
                 seconds: float, workdir: str, bodies):
        import numpy as np

        import loadgen

        self.seconds = seconds
        self.sched = loadgen.schedule(traffic, n_users, seed, seconds)
        n = len(self.sched["due"])
        rng = np.random.default_rng(seed)
        self.keep = sorted(rng.permutation(n)[:int(
            traffic["compared_requests"])].tolist())
        self.out_path = os.path.join(workdir, "loadgen_out.json")
        self.job = dict(self.sched, body=bodies(self.sched),
                        base_url=base_url, out=self.out_path,
                        keep_bodies=self.keep,
                        answer_timeout_s=seconds + 60.0,
                        connections=int(traffic.get("connections", 64)))
        job_path = os.path.join(workdir, "loadgen_job.json")
        with open(job_path, "w") as f:
            json.dump(self.job, f)
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(bench.LIB, "loadgen.py"), job_path],
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


def serve_setup(record, deployment):
    """The deployment's inputs from the seed, persisted and loaded by the
    normal path into a real EngineServer behind a ServerThread, every
    warm-up body of the deployment answered. Returns (server thread,
    server)."""
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import run_train
    from incubator_predictionio_tpu.workflow.create_server import EngineServer

    cfg = record.config
    storage = bench.make_storage()
    engine, factory = deployment.engine(record.traffic["kind"])
    key = deployment.serve_inputs(cfg, record.seed)
    bench.say("inputs drawn")
    run_train(engine, deployment.engine_params(cfg, key),
              WorkflowContext(storage=storage), engine_factory_name=factory)
    deployment.release(key)
    gc.collect()
    bench.say("model persisted")
    server = EngineServer(engine, engine_factory_name=factory,
                          storage=storage)
    bench.say("server loaded")
    st = ServerThread(server)
    st.start()
    for body, answered in deployment.warmup(record.traffic):
        status, answer = post_json(st.base + "/queries.json", body)
        if status != 200 or not answered(answer):
            raise RuntimeError(f"warm-up query {body}: {status} {answer}")
    return st, server
