import asyncio
import json
import subprocess
import sys
import os
import threading
import time

import numpy as np
from aiohttp import web

import loadgen

TRAFFIC = {"rate_qps": 40.0, "arrival_seed": 5, "num_shares": [[10, 0.8], [4, 0.2]],
           "user_zipf_s": 1.0, "unknown_user_share": 0.05}


def test_every_seed_has_the_same_work_in_another_order():
    a = loadgen.schedule(TRAFFIC, 1000, 1, 5.0)
    b = loadgen.schedule(TRAFFIC, 1000, 2**31 + 9, 5.0)
    assert len(a["due"]) == len(b["due"]) == 200
    ga, gb = np.diff(a["due"]), np.diff(b["due"])
    assert not np.allclose(ga, gb)
    assert sorted(a["num"]) == sorted(b["num"])
    assert a["num"].count(4) == 40
    assert sum(u.startswith("x") for u in a["user"]) == 10 == sum(
        u.startswith("x") for u in b["user"])
    assert a["due"][0] == 0.0 and 4.0 < a["due"][-1] < 5.5


def test_percentile_counts_unanswered_as_over_any_limit():
    assert loadgen.percentile([1.0] * 94 + [float("inf")] * 6, 95) == float("inf")
    assert loadgen.percentile(list(range(1, 101)), 95) == 95
    assert loadgen.percentile(list(range(1, 101)), 50) == 50


def test_the_load_generator_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import loadgen; "
            "loadgen.schedule(%r, 100, 1, 1.0); "
            "assert 'jax' not in sys.modules" % (
                os.path.dirname(loadgen.__file__), TRAFFIC))
    assert subprocess.run([sys.executable, "-c", code],
                          timeout=120).returncode == 0


class _Server:
    """Answers after 50 ms; sheds every fifth request with 503."""

    def __init__(self):
        self.n = 0
        self.received = []
        self.loop = asyncio.new_event_loop()
        self.ready = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)

    async def _query(self, request):
        self.received.append(await request.read())
        self.n += 1
        if self.n % 5 == 0:
            return web.json_response({"message": "shed"}, status=503)
        await asyncio.sleep(0.05)
        return web.json_response({"itemScores": []})

    async def _health(self, request):
        return web.Response()

    def _run(self):
        asyncio.set_event_loop(self.loop)

        async def main():
            app = web.Application()
            app.add_routes([web.post("/queries.json", self._query),
                            web.get("/healthz", self._health)])
            self.runner = web.AppRunner(app)
            await self.runner.setup()
            site = web.TCPSite(self.runner, "127.0.0.1", 0)
            await site.start()
            self.port = site._server.sockets[0].getsockname()[1]
            self.stop = asyncio.Event()
            self.ready.set()
            await self.stop.wait()
            await self.runner.cleanup()

        self.loop.run_until_complete(main())


def test_child_times_from_due_reports_lateness_counts_refusals(tmp_path):
    srv = _Server()
    srv.thread.start()
    assert srv.ready.wait(10)
    try:
        # one connection: requests queue behind each other, so a later
        # request's latency from its DUE time includes the wait
        # the child builds no body: it posts what the job holds, whatever
        # the fields are, in the key order given
        body = [{"num": 4, "blackList": [f"i{k}"], "user": "1"}
                for k in range(5)]
        job = dict(due=[0.0] * 5, body=body, warmup_requests=0,
                   base_url=f"http://127.0.0.1:{srv.port}",
                   out=str(tmp_path / "out.json"), keep_bodies=[0, 1, 2, 3, 4],
                   answer_timeout_s=10.0, connections=1)
        (tmp_path / "job.json").write_text(json.dumps(job))
        child = subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(loadgen.__file__),
                                          "loadgen.py"),
             str(tmp_path / "job.json")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        assert child.stdout.readline().strip() == "READY"
        child.stdin.write(repr(time.time() + 0.2) + "\n")
        child.stdin.flush()
        assert child.wait(timeout=30) == 0
        res = json.loads((tmp_path / "out.json").read_text())
        s = res["summary"]
        assert s["attempted"] == 5 and s["failed"] == 1
        lat = sorted(s["latency_ms"])
        assert lat[-1] == float("inf")            # the 503 has no latency
        assert lat[0] >= 50.0 and lat[3] >= 150.0  # queued behind the others
        assert all(v >= 0.0 for v in s["late_ms"])
        assert list(res["bodies"].values()) == [{"itemScores": []}] * 4
        assert sorted(srv.received) == sorted(
            json.dumps(b).encode() for b in body)
    finally:
        srv.loop.call_soon_threadsafe(srv.stop.set)
        srv.thread.join(10)
