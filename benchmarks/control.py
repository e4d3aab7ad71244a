#!/usr/bin/env python3
"""The controls and planted faults of "how ``correct`` is decided", read at a
cell's own size. Not part of a benchmark run: the builder runs it on the chip
to set each limit between the program's readings and these, and
``benchmarks/tests/test_correct.py`` reads the same at the rehearsal size.

  python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 [--rehearse]

What is read is the deployment file's: ``control(kind, cfg, traffic, seed,
faults)`` of ``deployments/<the configuration's deployment>.py`` gives the
plain reference put in the program's place in the nearest precision below
the one the configuration states, and the faults planted in it; its
docstrings say which. This file names no template.

Prints one JSON object per seed, then the smallest reading of each number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench  # noqa: E402

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--faults", type=int, choices=(0, 1), default=1,
                    help="0: the control alone (retrain cells)")
    args = ap.parse_args(argv)
    _cell, cfg, traffic = bench.load_cell(args.workload, args.rehearse)
    deployment = bench.load_module("deployments", cfg["deployment"])
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("control.py: no TPU; --rehearse runs the tiny size on the CPU",
              file=sys.stderr)
        return 3
    least: dict[str, float] = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        got = deployment.control(traffic["kind"], cfg, traffic, seed,
                                 bool(args.faults))
        print(json.dumps({"seed": seed, "readings": got}), flush=True)
        for what, nums in got.items():
            for k, v in nums.items():
                key = f"{what}.{k}"
                least[key] = min(least.get(key, float("inf")), v)
    print(json.dumps({"least": least}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
