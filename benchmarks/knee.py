#!/usr/bin/env python3
"""Find a serve cell's knee once, on the chip: one process, one set-up, a
rising ladder of offered rates, the same open loop as a run. The knee is the
highest rate at which every request is answered 200 and the backlog does not
grow (the last quarter's median latency stays under twice the first
quarter's). The builder writes 0.8 of it into the traffic file as
``rate_qps``; a benchmark run never searches.

  python3 benchmarks/knee.py --workload <cell> --seed 1 --rates 20,30,40 [--seconds 10]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell, config, traffic = bench.load_cell(args.workload, args.rehearse)
    import jax

    import loadgen

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("knee.py: no TPU", file=sys.stderr)
        return 3
    import serving

    deployment = bench.load_module("deployments", config["deployment"])
    record = bench.Record(cell, config, traffic, args)
    st, _server = serving.serve_setup(record, deployment)
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            with tempfile.TemporaryDirectory(prefix="knee_") as work:
                offer = serving.Offer(st.base, dict(traffic, rate_qps=rate),
                                      config["n_users"], args.seed + k,
                                      args.seconds, work, deployment.bodies)
                offer.go()
                s = offer.result()["summary"]
            lat = s["latency_ms"]
            q = max(len(lat) // 4, 1)
            print(json.dumps({
                "rate_qps": rate, "attempted": s["attempted"],
                "failed": s["failed"], "wall_s": s["wall_s"],
                "p50_ms": loadgen.percentile(lat, 50),
                "p95_ms": loadgen.percentile(lat, 95),
                "first_quarter_median_ms": statistics.median(lat[:q]),
                "last_quarter_median_ms": statistics.median(lat[-q:]),
                "late_p95_ms": loadgen.percentile(s["late_ms"], 95)}),
                flush=True)
    finally:
        st.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
