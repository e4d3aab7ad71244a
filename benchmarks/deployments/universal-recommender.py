"""Deployment ``universal-recommender``: the universal-recommender template's
stock ``URAlgorithm`` (cross-occurrence counts, Dunning's G², the k best
correlators per item), retrained from two (user, item) event sets past the
event store. Everything the harness knows of the template is here: the
engine, the events from the seed, the plain reference with its comparison
and its controls, and the calls a traced run wraps. A configuration names
this file by its ``deployment`` key. It has no serving kind: a query against
a catalog of this size is a gather over k slots and a look-up in the event
store, which would measure the host.
"""

from __future__ import annotations

import json
import time
from concurrent.futures import ThreadPoolExecutor

import bench_ur_engine
import datagen_ur
import reference_cco
import work_cco

GAPS = ("score_gap", "rank_gap", "fill_gap", "malformed")


def engine(kind: str):
    """(engine, its factory's name) for a traffic kind."""
    make = {"retrain": bench_ur_engine.retrain_engine}[kind]
    return make(), "bench_ur_engine." + make.__name__


def engine_params(config: dict, key: str, num_iterations: int | None = None):
    """``num_iterations`` (the mix's ``warmup_iterations``) means nothing to
    a count: every train is the whole train."""
    from incubator_predictionio_tpu.controller import EngineParams

    algo = {"maxCorrelatorsPerItem": config["maxCorrelatorsPerItem"],
            "minLLR": config["minLLR"], "user_chunk": config["user_chunk"]}
    return EngineParams.from_json({
        "datasource": {"params": {"key": key}},
        "algorithms": [{"name": "ur", "params": algo}],
    })


def spans(kind: str) -> list[tuple]:
    """(owner, attribute, span name) of the calls a traced run wraps: they
    put names on the device's idle gaps."""
    from incubator_predictionio_tpu.models import universal_recommender
    from incubator_predictionio_tpu.ops import llr

    return {"retrain": [
        (universal_recommender, "cco_indicators_multi", "cco_indicators"),
        (llr, "_dedupe_pair", "dedupe_pair"),
        (llr, "_gather_indicators", "gather_indicators")]}[kind]


def release(key: str) -> None:
    del bench_ur_engine.INPUTS[key]


# -- inputs from the seed ----------------------------------------------------


def train_inputs(cfg: dict, seeds: list[int], log) -> list[str]:
    """The events of each seed (same degrees, another pairing and other
    stars), handed to the engine's DataSource under the key returned."""
    degs = datagen_ur.degrees(cfg)

    def data_of(seed: int) -> str:
        key = f"events-{seed}"
        bench_ur_engine.INPUTS[key] = {
            "events": datagen_ur.events(cfg, seed, degs), "seed": seed,
            "n_users": cfg["n_users"], "n_items": cfg["n_items"]}
        return key

    with ThreadPoolExecutor(len(seeds)) as pool:
        keys = list(pool.map(data_of, seeds))
    log("data: " + ", ".join(
        f"{len(u)} {name}" for name, (u, _i) in
        bench_ur_engine.INPUTS[keys[0]]["events"].items())
        + f" events, {len(keys)} times")
    return keys


# -- the comparison that decides ``correct`` ---------------------------------


def _sides(cfg: dict, events: dict) -> dict:
    """Each event's distinct pairs, indexed both ways (the sorts run side by
    side)."""
    with ThreadPoolExecutor(len(events)) as pool:
        made = pool.map(lambda ui: reference_cco.Side(
            *ui, cfg["n_users"], cfg["n_items"]), events.values())
        return dict(zip(events, made))


def _compared_rows(cfg: dict, sides: dict, seed: int, n_rows: int):
    primary = sides[cfg["event_names"][0]]
    return reference_cco.sample_rows(primary.per_item, seed, n_rows,
                                     cfg["popular_rows"])


def _references(cfg: dict, sides: dict, rows, log=None) -> dict:
    """The reference's scores of ``rows`` for every indicator."""
    primary = sides[cfg["event_names"][0]]
    refs = {}
    for name in cfg["event_names"]:
        t0 = time.perf_counter()
        refs[name] = reference_cco.reference_scores(
            primary, sides[name], rows, cfg["n_users"], cfg["n_items"])
        if log:
            log(f"reference {name}: {len(rows)} rows in "
                f"{time.perf_counter() - t0:.1f}s")
    return refs


def _gaps(cfg: dict, refs: dict, rows, indicator_of) -> dict:
    """``{gap.event: value}`` of the rows ``indicator_of(name) -> (idx,
    score)`` against the reference, for every indicator."""
    out = {}
    for name, ref in refs.items():
        got = reference_cco.compare(*indicator_of(name), ref, rows,
                                    cfg["maxCorrelatorsPerItem"],
                                    cfg["n_users"])
        out.update({f"{gap}.{name}": got[gap] for gap in GAPS})
    return out


def check_retrain(cfg: dict, key: str, persisted_models, log) -> dict:
    """What the LAST window train persisted, read back through the
    checksum, against the plain reference on ``compared_rows`` primary items
    of each indicator."""
    d = bench_ur_engine.INPUTS[key]
    t0 = time.perf_counter()
    # the reference sorts its pairs while the artifact is read back through
    # the verifying loader
    with ThreadPoolExecutor(1) as pool:
        sides = pool.submit(_sides, cfg, d["events"])
        model = persisted_models()[0]
        sides = sides.result()
    log(f"read-back and the reference's pairs: "
        f"{time.perf_counter() - t0:.1f}s")
    # what the roofline shares divide by (metrics/cco.*.py read it)
    d["work"] = work_cco.least_work(
        {name: s.per_user for name, s in sides.items()},
        cfg["event_names"][0], cfg["n_items"])
    rows = _compared_rows(cfg, sides, d["seed"], cfg["compared_rows"])
    missing = [n for n in cfg["event_names"] if n not in model.indicators]
    if missing:
        return {f"malformed.{n}": (float("inf"), 0.0) for n in missing}

    def persisted(name):
        ind = model.indicators[name]
        return ind.idx[rows], ind.score[rows]

    seen = _gaps(cfg, _references(cfg, sides, rows, log), rows, persisted)
    log("gaps seen: " + json.dumps(seen))
    return {k: (v, limit_of(cfg, k)) for k, v in seen.items()}


def limit_of(cfg: dict, gap: str):
    """The limit of ``<gap>.<event>``: the gap's, whatever the indicator."""
    return cfg["limits"][gap.split(".")[0]]


# -- controls and planted faults (control.py, tests) -------------------------


def control(kind: str, cfg: dict, traffic: dict, seed: int,
            faults: bool = True) -> dict:
    """Gaps of the reference put in the program's place with the counts
    accumulated range by range in bfloat16 and with G² computed in bfloat16
    (the precisions below the exact integers and the float32 that the
    configuration states) and, with ``faults``, with the heavy users left
    out of the counts, with one user range left out, and with primary and
    secondary swapped (which the self pair cannot show: its counts are
    symmetric). ``control_rows`` primary items of each indicator."""
    sides = _sides(cfg, datagen_ur.events(cfg, seed))
    rows = _compared_rows(cfg, sides, seed, cfg["control_rows"])
    heavy = reference_cco.heavy_users(sides.values(), cfg["n_users"])
    primary = sides[cfg["event_names"][0]]
    planted = {"control_counts_bf16": "counts_bf16",
               "control_llr_bf16": "llr_bf16"}
    if faults:
        planted.update(fault_heavy_dropped="heavy_dropped",
                       fault_range_dropped="range_dropped",
                       fault_swapped="swapped")
    refs = _references(cfg, sides, rows)
    return {what: _gaps(
        cfg, refs, rows,
        lambda name, fault=fault: reference_cco.faulty_indicator(
            primary, sides[name], rows, cfg["n_users"], cfg["n_items"],
            cfg["maxCorrelatorsPerItem"], fault, heavy=heavy,
            u_chunk=cfg["user_chunk"]))
        for what, fault in planted.items()}
