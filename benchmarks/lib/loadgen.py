"""Open-loop load generator for the ``queries`` traffic kind, and the general
schedule generator that reads a traffic file. This file never imports jax:
the harness runs it as a child process while it holds the chip itself.

Every seed gives the same cycle of inter-arrival gaps (the quantiles of an
exponential at the mix's rate, in the mix's own order), of ``num`` and of
unknown users; the seed only turns the cycle and draws which known users ask. Latency is timed from when a request was DUE, not from when it
was sent, and how late each send ran is reported beside it.

The child posts ``job["body"][k]`` when ``job["due"][k]`` comes and builds no
body itself: the deployment file made them from the schedule's rows.

As a child:  python3 loadgen.py <job.json>   (writes job["out"])
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def schedule(traffic: dict, n_users: int, seed: int, seconds: float) -> dict:
    """The requests of one window: ``due`` seconds from the window's start,
    ``user`` ids (strings; unknown ids are ``"x<n>"``) and ``num``.

    The arrival pattern belongs to the MIX, not to the seed: the Poisson
    gaps, the ``num`` of each request and which requests carry an unknown
    user are drawn from the mix's own ``arrival_seed``, so every seed offers
    the same bursts. The seed turns that cycle to another starting point and
    draws which known users ask. (Where the seed drew the order of the gaps,
    the bursts and with them the queue's tail differed from seed to seed by
    far more than two runs of one seed did.)"""
    rate = float(traffic["rate_qps"])
    n = max(int(round(rate * seconds)), 1)
    mix = np.random.default_rng(int(traffic["arrival_seed"]))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = gaps[mix.permutation(n)]
    nums = np.concatenate([
        np.full(int(round(share * n)), int(num))
        for num, share in traffic["num_shares"]])
    nums = np.resize(nums, n)[mix.permutation(n)]
    unknown = np.zeros(n, bool)
    unknown[mix.permutation(n)[:int(round(
        float(traffic["unknown_user_share"]) * n))]] = True

    rng = np.random.default_rng(int(seed))
    turn = int(rng.integers(n))
    gaps, nums, unknown = (np.roll(a, turn) for a in (gaps, nums, unknown))
    due = np.cumsum(gaps)
    due -= due[0]
    # users: zipfian rank over the held users, rank -> id by a fixed
    # scramble so that the hot users are not rows 0, 1, 2...
    s = float(traffic["user_zipf_s"])
    cdf = np.cumsum(1.0 / np.arange(1, n_users + 1) ** s)
    ranks = np.searchsorted(cdf, rng.random(n) * cdf[-1])
    ids = (ranks.astype(np.int64) * 2654435761) % n_users
    users = [f"x{j}" if unknown[j] else str(int(ids[j])) for j in range(n)]
    return {"due": due.tolist(), "user": users, "num": nums.tolist()}


async def _drive(job: dict) -> dict:
    import asyncio

    import aiohttp

    url = job["base_url"] + "/queries.json"
    due, body = job["due"], job["body"]
    keep = set(job.get("keep_bodies", ()))
    n = len(due)
    sent = [None] * n
    done = [None] * n
    status = [0] * n
    bodies: dict[int, object] = {}
    timeout = aiohttp.ClientTimeout(total=float(job["answer_timeout_s"]))
    conn = aiohttp.TCPConnector(limit=int(job.get("connections", 64)))
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as sess:
        # open the keep-alive connections before the window
        async def touch():
            async with sess.get(job["base_url"] + "/healthz") as r:
                await r.read()
        await asyncio.gather(*[touch() for _ in range(8)])

        async def warm(k: int):
            async with sess.post(url, json=body[k]) as r:
                await r.read()
        # a few of the window's own requests over those connections, not
        # counted: the server's request path is then warm end to end
        await asyncio.gather(*[warm(k) for k in range(
            min(n, int(job.get("warmup_requests", 8))))])
        # ready; the parent then names the start on the wall clock, and time
        # is kept from the monotonic clock after that
        print("READY", flush=True)
        wait = float(sys.stdin.readline()) - time.time()
        t0 = time.monotonic() + max(wait, 0.0)

        async def one(k: int):
            delay = t0 + due[k] - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            sent[k] = time.monotonic() - t0
            try:
                async with sess.post(url, json=body[k]) as r:
                    raw = await r.read()
                    status[k] = r.status
                    if k in keep and r.status == 200:
                        bodies[k] = json.loads(raw)
            except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
                    ValueError) as e:
                status[k] = -1
                bodies[k] = repr(e)[:200]
            done[k] = time.monotonic() - t0

        tasks = [asyncio.create_task(one(k)) for k in range(n)]
        await asyncio.gather(*tasks)
    return {"sent": sent, "done": done, "status": status,
            "bodies": {str(k): v for k, v in bodies.items()}}


def summarize(job: dict, res: dict) -> dict:
    """Latencies from the due times over ALL requests; one that failed, was
    shed or never answered has no latency and counts as over any limit."""
    due = np.asarray(job["due"])
    ok = np.asarray([s == 200 for s in res["status"]])
    done = np.asarray([d if d is not None else np.inf for d in res["done"]])
    lat_ms = np.where(ok, (done - due) * 1e3, np.inf)
    late_ms = (np.asarray(res["sent"], float) - due) * 1e3
    return {
        "attempted": int(len(due)),
        "failed": int((~ok).sum()),
        "latency_ms": lat_ms.tolist(),
        "late_ms": late_ms.tolist(),
        "wall_s": float(max(done[ok].max() if ok.any() else 0.0, due[-1])),
    }


def percentile(values, q: float) -> float:
    """The q-th percentile by the nearest-rank rule over ALL values, ``inf``
    standing for a request without an answer."""
    v = np.sort(np.asarray(values, float))
    k = min(len(v) - 1, max(0, int(np.ceil(q / 100.0 * len(v))) - 1))
    return float(v[k])


def main(argv: list[str]) -> int:
    import asyncio

    with open(argv[1]) as f:
        job = json.load(f)
    res = asyncio.run(_drive(job))
    res["summary"] = summarize(job, res)
    with open(job["out"], "w") as f:
        json.dump(res, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
