"""Deployment ``similarproduct-views``: the Similar-Product template trained
from its shop's log. A day of ``view`` events and of the items' ``$set`` /
``$unset`` / ``$delete`` events lies in the event server's JSONL log, and
``pio train`` runs the template as it ships: the STOCK
``SimilarProductDataSource`` reads the views back through
``PEventStore.find_ratings``, sums them a (user, item) pair as upstream's
``reduceByKey`` does, replays the items' categories through
``PEventStore.aggregate_properties``, then ``SimilarProductAlgorithm``
(implicit ALS) and the artifact with its 476k-entry ``item_categories``.
Everything the harness knows of it is here: the engine, the logs written
from the seed, the comparison that decides ``correct`` with its controls,
and the calls a traced run wraps. It has no serve kind.

The store, the apps and the batch path into the log are the event-log
sibling's (``deployments/recommendation-eventlog.py``: ``open_store``,
``ingest``), loaded as a module of this file's own.
"""

from __future__ import annotations

import json
import resource
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import bench_simprod_engine
import datagen
import datagen_eventlog
import datagen_views
import program_spans
import reference
import reference_eventlog
import reference_implicit
import run as bench
import store_spans
import trace_reduce

eventlog = bench.load_module("deployments", "recommendation-eventlog")

#: the nearest precision below the one a configuration states
LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}
#: the spans beneath ``dase.read`` that name a piece of its work
WORK = store_spans.WORK + ("prep.pair_counts", "store.aggregate")
#: key (= the app's name) -> {"events", "app_id", "bytes"}
INPUTS: dict[str, dict] = {}
#: the id tables, the degrees and the store, made once a process
STATE: dict = {}


def engine(kind: str):
    """(engine, its factory's name) for a traffic kind."""
    make = {"retrain": bench_simprod_engine.retrain_engine}[kind]
    return make(), "bench_simprod_engine." + make.__name__


def _datasource_params(cfg: dict, key: str) -> dict:
    return {"appName": key, "eventNames": list(cfg["eventNames"])}


def _algo_params(config: dict, num_iterations: int | None = None) -> dict:
    return {"rank": config["rank"], "lambda": config["lambda"],
            "alpha": config["alpha"], "seed": config["seed"],
            # what "auto" resolves to on a TPU, said outright so that the
            # rehearsal on the CPU gathers in the same type
            "computeDtype": config.get("gather_dtype", "auto"),
            "numIterations": (config["numIterations"]
                              if num_iterations is None else num_iterations)}


def engine_params(config: dict, key: str, num_iterations: int | None = None):
    from incubator_predictionio_tpu.controller import EngineParams

    return EngineParams.from_json({
        "datasource": {"params": _datasource_params(config, key)},
        "algorithms": [{"name": "als",
                        "params": _algo_params(config, num_iterations)}],
    })


def spans(kind: str) -> list[tuple]:
    """(owner, attribute, span name): the siblings' calls, so that their
    harness-fed metrics read here too."""
    from incubator_predictionio_tpu.models import similar_product
    from incubator_predictionio_tpu.ops import als

    return {"retrain": [(similar_product, "train_als", "train_als"),
                        (als, "plan_and_fill_both", "plan_and_fill_both")],
            }[kind]


def release(key: str) -> None:
    """The app's log goes through the store's own ``remove`` (which drops
    its cached scan too); the bytes counter is noted for
    ``store.scan_mb_per_s``."""
    STATE["storage"].get_l_events().remove(INPUTS.pop(key)["app_id"])
    bench_simprod_engine.STORE["bytes_before_window"] = \
        store_spans.counter_value(store_spans.SCAN_BYTES)


# -- the logs of the seeds -----------------------------------------------------


def _state(cfg: dict) -> dict:
    if not STATE:
        uid = datagen_eventlog.user_ids(cfg["n_users"])
        iid = datagen_eventlog.item_ids(cfg["n_items"])
        STATE.update(
            degrees=datagen.degrees(cfg), uid=uid, iid=iid,
            users=datagen_eventlog.as_strings(uid),
            items=datagen_eventlog.as_strings(iid),
            storage=eventlog.open_store())
        bench_simprod_engine.STORE["storage"] = STATE["storage"]
    return STATE


def train_inputs(cfg: dict, seeds: list[int], log) -> list[str]:
    """An app and its log for each seed; the key is the app's name. The
    generator's own arrays ride along for ``check_retrain``."""
    from incubator_predictionio_tpu.data.storage import base

    st = _state(cfg)
    apps = st["storage"].get_meta_data_apps()
    l_events = st["storage"].get_l_events()
    keys = [f"views-{seed}" for seed in seeds]
    ids = [apps.insert(base.App(0, key, None)) for key in keys]

    def log_of(seed: int, key: str, app_id: int) -> None:
        ev = datagen_views.events(cfg, seed, st["degrees"])
        l_events.init(app_id)
        n_bytes = eventlog.ingest(l_events, app_id, datagen_views.bodies(
            ev, st["uid"], st["iid"], eventlog.BODY_EVENTS))
        INPUTS[key] = {"events": ev, "app_id": app_id, "bytes": n_bytes}

    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(seeds)) as pool:
        list(pool.map(log_of, seeds, keys, ids))
    first = INPUTS[keys[0]]["events"]
    log(f"data: {len(first['kind'])} events "
        f"({int((first['kind'] == datagen_views.VIEW).sum())} views of "
        f"{len(first['pairs'][0])} pairs) in a log of "
        f"{INPUTS[keys[0]]['bytes']} bytes, {len(keys)} times, "
        f"{time.perf_counter() - t0:.1f}s")
    return keys


# -- the comparison that decides ``correct`` -----------------------------------


def _first_seen(ev: dict) -> tuple[np.ndarray, np.ndarray]:
    """(row of each generator user, row of each generator item) as a store
    that numbers ids in the order its ``view`` events arrive lays them out
    (-1: no view)."""
    vu, vi = datagen_views.views_of(ev)
    n_users, n_items = len(STATE["users"]), len(STATE["items"])
    row_of_user = np.full(n_users, -1, np.int64)
    rows = reference_eventlog.first_seen_rows(vu)
    row_of_user[:len(rows)] = rows
    row_of_item = np.full(n_items, -1, np.int64)
    rows = reference_eventlog.first_seen_rows(vi)
    row_of_item[:len(rows)] = rows
    return row_of_user, row_of_item


def _rows_wrong(ids: list[str], bimap, row_of_gen: np.ndarray):
    """(for each row of the ``BiMap`` the generator's row of its id, how
    many ids are wrong): ``reference_eventlog.rows_of``'s count plus the
    rows that are not where a first-seen numbering puts their id."""
    gen_of_row, wrong = reference_eventlog.rows_of(
        ids, [bimap.inverse_get(k) for k in range(len(bimap))])
    known = gen_of_row >= 0
    moved = int((row_of_gen[gen_of_row[known]]
                 != np.nonzero(known)[0]).sum())
    return gen_of_row, wrong + moved


def _read_again(cfg: dict, key: str):
    """The stock ``read_training`` once more on the app's log."""
    from incubator_predictionio_tpu.controller.base import doer
    from incubator_predictionio_tpu.workflow.context import WorkflowContext

    source = doer(bench_simprod_engine.ViewLogDataSource,
                  _datasource_params(cfg, key))
    return source.read_training(WorkflowContext())


def read_coverage(tree):
    """(seconds of the train's ``dase.read``, share of it that the union
    of the work spans covers, seconds by span name); nothing for a train
    without a ``dase.read``."""
    reads = program_spans.named(tree, store_spans.READ)
    if not reads:
        return None
    read = reads[0]
    inside = [s for s in program_spans.named(tree, "store.parse", *WORK)
              if read.t0_ns <= s.t0_ns and s.t1_ns <= read.t1_ns]
    work = trace_reduce.merge_intervals(
        (s.t0_ns, s.t1_ns) for s in inside if s.name in WORK)
    parts: dict[str, float] = {}
    for s in inside:
        tags = s.tags or {}
        name = s.name + "".join(
            "." + str(tags[t]) for t in ("step", "source") if t in tags)
        parts[name] = parts.get(name, 0.0) + program_spans.seconds(s)
    return (program_spans.seconds(read),
            sum(b - a for a, b in work) / (read.t1_ns - read.t0_ns), parts)


def _log_read_coverage(log) -> None:
    snap = program_spans.snapshot()
    roots = [s for s in snap if s.name == program_spans.TRAIN_ROOT
             and s.parent_id is None]
    for tree in program_spans.trees(snap, roots):
        got = read_coverage(tree)
        if got is not None:
            log("dase.read %.2fs, work spans cover %.2f%%: %s" % (
                got[0], 100.0 * got[1], json.dumps(
                    {k: round(v, 3) for k, v in got[2].items()})))
        loops = program_spans.named(tree, "als.loop")
        if loops:
            log("als.loop tags: " + json.dumps(loops[0].tags))


def _names_of(members: np.ndarray) -> list[frozenset]:
    names = np.asarray(datagen_views.CATEGORIES, object)
    return [frozenset(names[row]) for row in members]


def categories_wrong(item_categories: dict, members: np.ndarray) -> int:
    """Items whose persisted categories are not the generator's replay's:
    another set, a set where the item has none, none where it has some,
    and every id the generator does not know."""
    want = dict(zip(STATE["items"], _names_of(members)))
    wrong = sum(1 for item_id in item_categories if item_id not in want)
    for item_id, names in want.items():
        wrong += frozenset(item_categories.get(item_id, ())) != names
    return wrong


def _serve(cfg: dict, model, queries: list[dict]) -> list[dict]:
    """Each query through the stock ``predict`` of the model read back;
    the answers' ids as generator rows (-1 for an id it does not know)."""
    from incubator_predictionio_tpu.controller.base import doer

    algo = doer(bench_simprod_engine.SimilarProductAlgorithm,
                _algo_params(cfg))
    ids = STATE["items"]
    number = {s: k for k, s in enumerate(ids)}
    names = datagen_views.CATEGORIES
    served = []
    for q in queries:
        body = {"items": [ids[j] for j in q["items"]], "num": q["num"]}
        if q["categories"] is not None:
            body["categories"] = [names[c] for c in q["categories"]]
        if q["white"] is not None:
            body["whiteList"] = [ids[j] for j in q["white"]]
        if q["black"] is not None:
            body["blackList"] = [ids[j] for j in q["black"]]
        scored = algo.predict(model, body)["itemScores"]
        served.append({"items": [number.get(s["item"], -1) for s in scored],
                       "scores": [s["score"] for s in scored]})
    return served


def sampled_queries(cfg: dict, seed: int, members: np.ndarray,
                    unit: np.ndarray) -> list[dict]:
    """The generator's queries; a blackList gets up to four of the query's
    own unfiltered best, so that it bites."""
    rng = np.random.default_rng([int(seed), datagen_views.QUERY_STREAM, 1])
    queries = datagen_views.queries(cfg, seed, members, int(cfg["queries"]))
    for q in queries:
        if q["black"] is not None:
            best = reference_implicit.top_similar(
                unit, members, q, ignore_black=True)["items"][:q["num"]]
            own = rng.permutation(best)[:int(rng.integers(1, 5))]
            q["black"] = np.unique(np.concatenate([q["black"], own]))
    return queries


def check_queries(cfg: dict, seed: int, model, gen_of_irow: np.ndarray,
                  members: np.ndarray, log) -> dict:
    """256 sampled queries through the stock ``predict`` against the
    float32 summed cosine of the SAME persisted factors under the
    generator's rules."""
    t0 = time.perf_counter()
    row_of_item = np.empty(len(gen_of_irow), np.int64)
    row_of_item[gen_of_irow] = np.arange(len(gen_of_irow))
    # the persisted factors in the generator's item order
    unit = reference_implicit.unit_rows(
        np.asarray(model.factors.item_factors)[row_of_item])
    queries = sampled_queries(cfg, seed, members, unit)
    gaps = reference_implicit.similar_gaps(unit, members, queries,
                                           _serve(cfg, model, queries))
    log(f"queries: {gaps['compared']} served and compared in "
        f"{time.perf_counter() - t0:.1f}s")
    return gaps


def check_retrain(cfg: dict, key: str, persisted_models, log) -> dict:
    _state(cfg)
    ev, lim = INPUTS[key]["events"], cfg["limits"]
    n_items = cfg["n_items"]
    pu, pi, pc = ev["pairs"]
    _log_read_coverage(log)
    log("peak host RSS so far: %.2f GB" % (
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6))
    t0 = time.perf_counter()
    model = persisted_models()[0]
    log(f"read-back: {time.perf_counter() - t0:.1f}s")
    row_of_user, row_of_item = _first_seen(ev)
    members = datagen_views.final_members(ev)
    # (b) the persisted items' ids against the ids that occur, each at the
    # row a first-seen numbering gives it
    gen_of_irow, ids_wrong = _rows_wrong(STATE["items"], model.items,
                                         row_of_item)
    out = {"categories_wrong": (categories_wrong(model.item_categories,
                                                 members),
                                lim["categories_wrong"])}
    fro = {k: v for k, v in lim.items() if k.endswith("_fro")}
    with ThreadPoolExecutor(1) as pool:
        # (c) the plain implicit ALS on the generator's counted pairs in
        # the first-seen row order, beside the host's comparisons
        u, i = row_of_user[pu].astype(np.int32), row_of_item[pi].astype(
            np.int32)
        ref = pool.submit(
            reference_implicit.implicit_als_reference, u, i, pc,
            cfg["n_users"], n_items, cfg["rank"], cfg["lambda"],
            cfg["alpha"], cfg["seed"], cfg["numIterations"],
            cfg["gather_dtype"], log=log)
        # (a) what the stock DataSource reads against what was posted; the
        # model carries no user ids, so the users' are the read's
        t0 = time.perf_counter()
        td = _read_again(cfg, key)
        read_u, wrong_u = _rows_wrong(STATE["users"], td.users, row_of_user)
        read_i, _ = reference_eventlog.rows_of(
            STATE["items"],
            [td.items.inverse_get(k) for k in range(len(td.items))])
        out["ids_wrong"] = (ids_wrong + wrong_u, lim["ids_wrong"])
        out["pairs_diff"] = (reference_implicit.pairs_diff(
            (read_u[td.user_idx], read_i[td.item_idx], td.rating),
            (pu, pi, pc), n_items), lim["pairs_diff"])
        log(f"read again and compared: {time.perf_counter() - t0:.1f}s")
        wx, wy = ref.result()
    got = model.factors
    gaps = reference.als_compare(
        got.user_factors, got.item_factors, wx, wy, fro,
        (np.bincount(u, minlength=len(wx)), np.bincount(i, minlength=len(wy))))
    log("gaps seen: " + json.dumps(gaps.pop("_seen")))
    out.update(gaps)
    if out["ids_wrong"][0] == 0:
        served = check_queries(cfg, int(key.rsplit("-", 1)[1]), model,
                               gen_of_irow, members, log)
    else:
        served = {"leak": float("inf"), "rank_gap": float("inf")}
    out.update({k: (served[k], lim[k]) for k in ("leak", "rank_gap")})
    return out


# -- controls and planted faults (control.py, tests) ---------------------------


def control_retrain(cfg: dict, seed: int, faults: bool = True) -> dict:
    """What the comparison reads when the plain reference stands in the
    program's place with a fault planted: the gathered rows rounded to the
    precision below ``gather_dtype`` and, with ``faults``: one entry an
    EVENT (the program before its pairs were counted), explicit ALS on the
    counts, the shared YtY left out, alpha halved, one ``view`` event
    dropped from the read, and a replay that ignores an item's later
    ``$set``. Each gives ``pairs_diff``, ``ids_wrong``,
    ``categories_wrong``, ``user_fro`` and ``item_fro``."""
    st = _state(cfg)
    ev = datagen_views.events(cfg, seed, st["degrees"])
    n_users, n_items = cfg["n_users"], cfg["n_items"]
    pu, pi, pc = ev["pairs"]
    row_of_user, row_of_item = _first_seen(ev)
    u, i = row_of_user[pu].astype(np.int32), row_of_item[pi].astype(np.int32)
    members = datagen_views.final_members(ev)

    def ref(u=u, i=i, c=pc, **kw):
        kw = {"alpha": cfg["alpha"], "gather_dtype": cfg["gather_dtype"],
              **kw}
        return reference_implicit.implicit_als_reference(
            u, i, c, n_users, n_items, cfg["rank"], cfg["lambda"],
            seed=cfg["seed"], n_iters=cfg["numIterations"], **kw)

    want_x, want_y = ref()
    weights = (np.bincount(u, minlength=n_users),
               np.bincount(i, minlength=n_items))

    def seen(x, y, read=(pu, pi, pc), cats_wrong: int = 0) -> dict:
        gaps = reference.als_compare(x, y, want_x, want_y, {},
                                     weights)["_seen"]
        return {"pairs_diff": reference_implicit.pairs_diff(
                    read, (pu, pi, pc), n_items),
                "ids_wrong": 0, "categories_wrong": cats_wrong,
                "user_fro": gaps["user_fro"], "item_fro": gaps["item_fro"]}

    out = {"control_lower_precision": seen(
        *ref(gather_dtype=LOWER[cfg["gather_dtype"]]))}
    if not faults:
        return out
    vu, vi = datagen_views.views_of(ev)
    ones = np.ones(len(vu), np.float32)
    out["fault_one_entry_an_event"] = seen(
        *ref(row_of_user[vu].astype(np.int32),
             row_of_item[vi].astype(np.int32), ones), read=(vu, vi, ones))
    out["fault_explicit"] = seen(*reference.als_reference(
        u, i, pc.astype(np.float32), n_users, n_items, cfg["rank"],
        cfg["lambda"], cfg["seed"], cfg["numIterations"],
        cfg["gather_dtype"]))
    out["fault_no_yty"] = seen(*ref(yty=False))
    out["fault_alpha_half"] = seen(*ref(alpha=0.5 * cfg["alpha"]))
    # one view of a pair that is viewed twice or more: the ids stay
    less = pc.copy()
    less[int(np.argmax(pc >= 2))] -= 1
    out["fault_one_event_dropped"] = seen(*ref(c=less), read=(pu, pi, less))
    kept_first = datagen_views.final_members(ev, ignore_reset=True)
    out["fault_reset_ignored"] = seen(
        want_x, want_y,
        cats_wrong=int((kept_first != members).any(axis=1).sum()))
    return out


def control(kind: str, cfg: dict, traffic: dict, seed: int,
            faults: bool = True) -> dict:
    return {"retrain": control_retrain}[kind](cfg, seed, faults)
