#!/usr/bin/env python3
"""The controls and planted faults of "how ``correct`` is decided", read at a
cell's own size. Not part of a benchmark run: the builder runs it on the chip
to set each limit between the program's readings and these, and
``benchmarks/tests/test_correct.py`` runs it at the rehearsal size.

  python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 [--rehearse]

The control is the plain reference put in the program's place and computed in
the nearest precision below the one the configuration states:

- retrain cells: gathered factor rows rounded to float8_e4m3fn where the
  configuration states bfloat16 (bfloat16 where it states float32);
- serve cells: the catalog and the query vector in bfloat16 where the
  configuration states float32; the items the lower precision puts first are
  read against the float32 reference (no decode, no server).

The faults are planted in the reference put in the program's place: half of
the ratings left out; one iteration fewer; every factor row scaled by 1.01
where it is produced. (A train that returns its initial state reads about 1
by the gap's own measure and needs no run.)

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

LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def als_readings(cfg: dict, seed: int, degs, faults: bool = True) -> dict:
    import datagen
    import reference

    u, i, r = datagen.ratings(cfg, seed, degs)
    args = (cfg["n_users"], cfg["n_items"], cfg["rank"], cfg["lambda"],
            cfg["seed"])
    n = cfg["numIterations"]

    def gaps(x, y):
        return reference.als_compare(x, y, want_x, want_y, {}, degs)["_seen"]

    want_x, want_y = reference.als_reference(u, i, r, *args, n,
                                             cfg["gather_dtype"])
    out = {}
    x, y = reference.als_reference(u, i, r, *args, n,
                                   LOWER[cfg["gather_dtype"]])
    out["control_lower_precision"] = gaps(x, y)
    if not faults:
        return out
    half = slice(0, len(u), 2)
    x, y = reference.als_reference(u[half], i[half], r[half], *args, n,
                                   cfg["gather_dtype"])
    out["fault_half_ratings"] = gaps(x, y)
    if n > 1:
        x, y = reference.als_reference(u, i, r, *args, n - 1,
                                       cfg["gather_dtype"])
        out["fault_one_sweep_short"] = gaps(x, y)
    out["fault_rows_scaled_1.01"] = gaps(want_x * 1.01, want_y * 1.01)
    return out


def topk_readings(cfg: dict, traffic: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import datagen
    import loadgen
    import reference

    rank = cfg["rank"]
    items = jax.device_put(datagen.factors(cfg["n_items"], rank, seed,
                                           datagen.ITEM_STREAM))
    users = datagen.factors(cfg["n_users"], rank, seed, datagen.USER_STREAM)
    sched = loadgen.schedule(traffic, cfg["n_users"], seed, 30.0)
    rng = np.random.default_rng(seed)
    keep = rng.permutation(len(sched["due"]))[:int(
        traffic["compared_requests"])]
    lower = jnp.dtype(LOWER[cfg["catalog_dtype"]])
    cat_lo = items.astype(lower)

    @jax.jit
    def answer(vec):
        s = jnp.matmul(cat_lo, vec.astype(lower),
                       preferred_element_type=jnp.float32)
        return jax.lax.top_k(s, reference.MAX_NUM)

    def served_by(alter):
        served = []
        for k in keep:
            user = sched["user"][k]
            if not user.isdigit():
                served.append({"row": None, "num": sched["num"][k],
                               "items": [], "scores": []})
                continue
            scores, idx = jax.device_get(answer(users[int(user)]))
            num = sched["num"][k]
            served.append(alter({
                "row": int(user), "num": num,
                "items": idx[:num].tolist(),
                "scores": scores[:num].tolist()}))
        return served

    out = {"control_lower_precision":
           reference.topk_gaps(items, users, served_by(lambda q: q))}

    def last_item_swapped(q):
        q["items"][-1] = (q["items"][-1] + 1) % cfg["n_items"]
        return q

    out["fault_one_item_altered"] = reference.topk_gaps(
        items, users, served_by(last_item_swapped))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--faults", type=int, choices=(0, 1), default=1,
                    help="0: the control alone (retrain cells)")
    args = ap.parse_args(argv)
    _cell, cfg, traffic = bench.load_cell(args.workload, args.rehearse)
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("control.py: no TPU; --rehearse runs the tiny size on the CPU",
              file=sys.stderr)
        return 3
    degs = None
    if traffic["kind"] == "retrain":
        import datagen

        degs = datagen.degrees(cfg)
    least: dict[str, float] = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        got = (als_readings(cfg, seed, degs, bool(args.faults))
               if traffic["kind"] == "retrain"
               else topk_readings(cfg, traffic, seed))
        print(json.dumps({"seed": seed, "readings": got}), flush=True)
        for what, nums in got.items():
            for k, v in nums.items():
                key = f"{what}.{k}"
                least[key] = min(least.get(key, float("inf")), v)
    print(json.dumps({"least": least}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
