"""Deployment ``recommendation-als``: the recommendation template's explicit
ALS, trained from a COO triple past the event store and served through
``POST /queries.json {"user", "num"}``. Everything the harness knows of the
template is here: the engines, the inputs from the seed, the request bodies,
the plain reference with its comparison and its controls, and the calls a
traced run wraps. A configuration names this file by its ``deployment`` key.
"""

from __future__ import annotations

import gc
import json
import time
from concurrent.futures import ThreadPoolExecutor

import bench_engine
import datagen
import reference

#: the nearest precision below the one a configuration states
LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def engine(kind: str):
    """(engine, its factory's name) for a traffic kind."""
    make = {"retrain": bench_engine.retrain_engine,
            "queries": bench_engine.serve_engine}[kind]
    return make(), "bench_engine." + make.__name__


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


def spans(kind: str) -> list[tuple]:
    """(owner, attribute, span name) of the calls a traced run wraps."""
    from incubator_predictionio_tpu.models import (
        _sharded_serving, recommendation,
    )
    from incubator_predictionio_tpu.ops import als

    return {"retrain": [(recommendation, "train_als", "train_als"),
                        (als, "plan_and_fill_both", "plan_and_fill_both")],
            "queries": [(_sharded_serving.ShardedCatalog, "top_k", "top_k")],
            }[kind]


def release(key: str) -> None:
    del bench_engine.INPUTS[key]


# -- inputs from the seed ----------------------------------------------------


def train_inputs(cfg: dict, seeds: list[int], log) -> list[str]:
    """The ratings of each seed (same degrees, another pairing), handed to
    the engine's DataSource under the key returned for it; the degrees ride
    along for ``check_retrain``."""
    degs = datagen.degrees(cfg)

    def data_of(seed: int) -> str:
        u, i, r = datagen.ratings(cfg, seed, degs)
        key = f"ratings-{seed}"
        bench_engine.INPUTS[key] = {
            "user": u, "item": i, "rating": r, "degrees": degs,
            "n_users": cfg["n_users"], "n_items": cfg["n_items"]}
        return key

    with ThreadPoolExecutor(len(seeds)) as pool:
        keys = list(pool.map(data_of, seeds))
    log(f"data: {len(bench_engine.INPUTS[keys[0]]['user'])} ratings "
        f"{len(keys)} times")
    return keys


def _factors(cfg: dict, seed: int, stream: int):
    n_rows = cfg["n_users" if stream == datagen.USER_STREAM else "n_items"]
    return datagen.factors(n_rows, cfg["rank"], seed, stream)


def serve_inputs(cfg: dict, seed: int) -> str:
    key = f"factors-{seed}"
    bench_engine.INPUTS[key] = {
        "user_factors": _factors(cfg, seed, datagen.USER_STREAM),
        "item_factors": _factors(cfg, seed, datagen.ITEM_STREAM)}
    return key


# -- request bodies ----------------------------------------------------------


def bodies(sched: dict) -> list[dict]:
    """One JSON body per row of the schedule, in order."""
    return [{"user": user, "num": num}
            for user, num in zip(sched["user"], sched["num"])]


def warmup(traffic: dict) -> list[tuple]:
    """(body, what its answer has to satisfy): every ``num`` of the mix."""
    nums = sorted({int(n) for n, _ in traffic["num_shares"]})
    return [({"user": user, "num": num},
             lambda answer, num=num: len(answer["itemScores"]) == num)
            for num in nums for user in ("0", "1")]


# -- the comparison that decides ``correct`` ---------------------------------


def check_retrain(cfg: dict, key: str, persisted_models, log) -> dict:
    d = bench_engine.INPUTS[key]
    t0 = time.perf_counter()
    # the reference starts on its host passes while the artifact is read
    # back through the verifying loader
    with ThreadPoolExecutor(1) as pool:
        ref = pool.submit(
            reference.als_reference, d["user"], d["item"], d["rating"],
            cfg["n_users"], cfg["n_items"], cfg["rank"], cfg["lambda"],
            cfg["seed"], cfg["numIterations"], cfg["gather_dtype"], log=log)
        got = persisted_models()[0].factors
        wx, wy = ref.result()
    log(f"reference and read-back: {time.perf_counter() - t0:.1f}s")
    out = reference.als_compare(got.user_factors, got.item_factors,
                                wx, wy, cfg["limits"], d["degrees"])
    log("gaps seen: " + json.dumps(out.pop("_seen")))
    return out


def check_queries(cfg: dict, seed: int, sched: dict, keep: list[int],
                  res: dict, log) -> dict:
    import jax

    gc.collect()
    items = jax.device_put(_factors(cfg, seed, datagen.ITEM_STREAM))
    users = _factors(cfg, seed, datagen.USER_STREAM)
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
    log(f"compared {gaps['compared']} of {len(keep)} sampled answers")
    lim = cfg["limits"]
    return {
        "rank_gap": (gaps["rank_gap"], lim["rank_gap"]),
        "score_gap": (gaps["score_gap"], lim["score_gap"]),
        "malformed": (gaps["malformed"] + malformed, 0),
        "unanswered": (never, 0),
    }


# -- controls and planted faults (control.py, tests) -------------------------


def control_retrain(cfg: dict, seed: int, faults: bool = True) -> dict:
    """Gaps of the reference with the gathered rows rounded to the precision
    below ``gather_dtype`` and, with ``faults``, of the reference with half
    of the ratings left out, one sweep fewer, and every factor row scaled by
    1.01 where it is produced. (A train that returns its initial state reads
    about 1 by the gap's own measure and needs no run.)"""
    degs = datagen.degrees(cfg)
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


def control_queries(cfg: dict, traffic: dict, seed: int) -> dict:
    """Gaps of the items that the catalog and the query vector in the
    precision below ``catalog_dtype`` put first (no decode, no server), and
    of those answers with one item altered."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import loadgen

    items = jax.device_put(_factors(cfg, seed, datagen.ITEM_STREAM))
    users = _factors(cfg, seed, datagen.USER_STREAM)
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


def control(kind: str, cfg: dict, traffic: dict, seed: int,
            faults: bool = True) -> dict:
    return (control_retrain(cfg, seed, faults) if kind == "retrain"
            else control_queries(cfg, traffic, seed))
