"""Deployment ``ecommerce-als``: the ecommerce template's stock
``ECommerceAlgorithm.predict`` and ``ECommerceModel.recommend`` behind
``POST /queries.json {"user", "num", "categories", "whiteList", "blackList"}``.
Every query reads the event store twice (what the user has seen, what the
merchandiser has withdrawn), builds a mask over the whole catalog from those
answers and the request's own rules, ships it to the device and selects under
it. Everything the harness knows of the template is here: the engine, the
inputs from the seed (factors, each item's category, the event store's
contents), the request bodies, the plain reference with its comparison and
its controls, and the call a traced run wraps. A configuration names this
file by its ``deployment`` key. It has no retrain kind.

The event store is the program's default kind of source, SQLITE on disk, in
a directory of the run's own under the temporary directory; ``run.py``'s
MEMORY sources keep the metadata and the artifact, as in the sibling.
"""

from __future__ import annotations

import atexit
import datetime
import gc
import shutil
import tempfile
import time

import numpy as np

import bench_ecomm_engine
import datagen
import datagen_ecomm
import reference_ecomm
import run as bench

APP = "BenchShop"
#: the nearest precision below the one a configuration states
LOWER = {"float32": "bfloat16"}
#: what ``serve_inputs`` made from the seed, kept for ``warmup``, ``bodies``
#: and ``check_queries`` (the harness hands those the schedule alone)
STATE: dict = {}


def engine(kind: str):
    """(engine, its factory's name) for a traffic kind."""
    make = {"queries": bench_ecomm_engine.serve_engine}[kind]
    return make(), "bench_ecomm_engine." + make.__name__


def engine_params(config: dict, key: str, num_iterations: int | None = None):
    from incubator_predictionio_tpu.controller import EngineParams

    algo = {"appName": APP, "rank": config["rank"], "lambda": config["lambda"],
            "seed": config["seed"], "seenEvents": config["seenEvents"],
            "numIterations": config["numIterations"]}
    return EngineParams.from_json({
        "datasource": {"params": {"key": key}},
        "algorithms": [{"name": "ecomm", "params": algo}],
    })


def spans(kind: str) -> list[tuple]:
    """(owner, attribute, span name): the sibling's call, so that
    ``serve.topk_call_ms`` reads here too."""
    from incubator_predictionio_tpu.models import _sharded_serving

    return {"queries": [(_sharded_serving.ShardedCatalog, "top_k", "top_k")]
            }[kind]


def release(key: str) -> None:
    del bench_ecomm_engine.INPUTS[key]


# -- inputs from the seed ----------------------------------------------------


def generate(cfg: dict, seed: int) -> dict:
    """Everything drawn from the seed: both factor matrices, each item's
    category, the hot users' own best items and the event store's contents."""
    g = {"seed": int(seed),
         "user_factors": datagen.factors(cfg["n_users"], cfg["rank"], seed,
                                         datagen.USER_STREAM),
         "item_factors": datagen.factors(cfg["n_items"], cfg["rank"], seed,
                                         datagen.ITEM_STREAM),
         "cats": datagen_ecomm.categories(cfg, seed)}
    g["hot_top"] = datagen_ecomm.top_items(
        g["item_factors"], g["user_factors"][datagen_ecomm.hot_users(cfg)])
    g["events"] = datagen_ecomm.events(cfg, seed, g["hot_top"])
    #: user row -> item rows written to the store after it was loaded
    g["written"] = {}
    return g


def open_store(g: dict):
    """A ``Storage`` whose METADATA and EVENTDATA are one SQLITE file: the
    app, every ``view`` and ``buy`` event of ``g`` and ONE ``$set`` of the
    constraint entity ``unavailableItems``. The event rows go in by one bulk
    insert on a connection of the loader's own (3.9M Event objects through
    ``insert_batch`` would take minutes), with the table's two indexes
    dropped and built again at the end by the DAO's own ``init`` (a third of
    the time of keeping them row by row); the table and its indexes are the
    DAO's, and so is every later read and write."""
    import sqlite3

    from incubator_predictionio_tpu.data.storage import base
    from incubator_predictionio_tpu.data.storage.datamap import DataMap
    from incubator_predictionio_tpu.data.storage.event import Event
    from incubator_predictionio_tpu.data.storage.registry import Storage

    workdir = tempfile.mkdtemp(prefix="bench_ecomm_")
    atexit.register(shutil.rmtree, workdir, ignore_errors=True)
    path = f"{workdir}/pio.sqlite"
    env = {"PIO_STORAGE_SOURCES_SHOP_TYPE": "SQLITE",
           "PIO_STORAGE_SOURCES_SHOP_PATH": path}
    for repo in ("METADATA", "EVENTDATA"):
        env[f"PIO_STORAGE_REPOSITORIES_{repo}_SOURCE"] = "SHOP"
        env[f"PIO_STORAGE_REPOSITORIES_{repo}_NAME"] = f"bench_{repo.lower()}"
    storage = Storage(env)
    app_id = storage.get_meta_data_apps().insert(base.App(0, APP, None))
    l_events = storage.get_l_events()
    l_events.init(app_id)
    table = f"{storage.repo_namespace('EVENTDATA')}_{app_id}"
    now = datetime.datetime.now(datetime.timezone.utc)
    loader = sqlite3.connect(path)
    with loader:
        for (index,) in loader.execute(
                "SELECT name FROM sqlite_master WHERE type = 'index' AND "
                "tbl_name = ? AND sql IS NOT NULL", (table,)).fetchall():
            loader.execute(f"DROP INDEX {index}")
        loader.executemany(
            f"INSERT INTO {table} VALUES (?,?,?,?,?,?,?,?,?,?,?)",
            datagen_ecomm.store_rows(g["events"], g["seed"],
                                     int(now.timestamp() * 1e6)))
    loader.execute("PRAGMA wal_checkpoint(TRUNCATE)")
    loader.close()
    l_events.init(app_id)
    l_events.insert(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties=DataMap({"items": [
            str(i) for i in g["events"]["withdrawn"].tolist()]}),
        event_time=now), app_id)
    return storage, app_id


def serve_inputs(cfg: dict, seed: int) -> str:
    g = generate(cfg, seed)
    bench.say(f"factors, categories, {len(g['hot_top'])} hot users' best "
              f"items, {len(g['events']['item'])} events drawn")
    storage, app_id = open_store(g)
    bench.say("event store loaded")
    bench_ecomm_engine.STORE["storage"] = storage
    names = [{name} for name in datagen_ecomm.CATEGORIES]
    key = f"shop-{seed}"
    bench_ecomm_engine.INPUTS[key] = {
        "user_factors": g.pop("user_factors"),
        "item_factors": g.pop("item_factors"),
        "item_categories": dict(zip(
            map(str, range(cfg["n_items"])),
            map(names.__getitem__, g["cats"].tolist()))),
        "app_name": APP}
    STATE.clear()
    STATE.update(g, cfg=cfg, storage=storage, app_id=app_id)
    return key


# -- request bodies ----------------------------------------------------------


def bodies(sched: dict) -> list[dict]:
    """One JSON body per row of the schedule, in order. Which row carries
    which rule is the mix's (``datagen_ecomm.rules_of``); the ids in the
    lists are drawn from the seed."""
    lists = datagen_ecomm.request_lists(
        STATE["cfg"], STATE["traffic"], STATE["seed"], sched, STATE["cats"],
        STATE["hot_top"])
    out = []
    for user, num, rule in zip(sched["user"], sched["num"], lists):
        body = {"user": user, "num": num}
        if "categories" in rule:
            body["categories"] = [datagen_ecomm.CATEGORIES[c]
                                  for c in rule["categories"]]
        for field in ("whiteList", "blackList"):
            if field in rule:
                body[field] = [str(i) for i in rule[field].tolist()]
        out.append(body)
    return out


def warmup(traffic: dict) -> list[tuple]:
    """(body, what its answer has to satisfy): every ``num`` of the mix,
    every category (``CategoryIndex.mask`` is built on first use by a loop
    over the catalog), a whiteList, a blackList; and the read-your-write
    case: the hottest user's best item is written to the store as a ``view``
    after the first answer, and the next answer must leave it out."""
    STATE["traffic"] = traffic
    hottest = int(datagen_ecomm.hot_users(STATE["cfg"])[0])
    nums = sorted({int(n) for n, _ in traffic["num_shares"]})
    full = lambda num: lambda answer: len(answer["itemScores"]) == num
    out = [({"user": str(hottest), "num": num}, full(num)) for num in nums]
    out += [({"user": str(hottest), "num": nums[-1], "categories": [name]},
             full(nums[-1])) for name in datagen_ecomm.CATEGORIES]
    shelf = [str(i) for i in np.flatnonzero(STATE["cats"] == 0)[:50].tolist()]
    out.append(({"user": "1", "num": nums[0], "whiteList": shelf},
                lambda answer: {s["item"] for s in answer["itemScores"]}
                <= set(shelf)))
    out.append(({"user": "1", "num": nums[0], "blackList": shelf},
                full(nums[0])))
    written = []

    def write_best(answer) -> bool:
        from incubator_predictionio_tpu.data.storage.event import Event

        best = answer["itemScores"][0]["item"]
        STATE["storage"].get_l_events().insert(Event(
            event="view", entity_type="user", entity_id=str(hottest),
            target_entity_type="item", target_entity_id=best),
            STATE["app_id"])
        STATE["written"].setdefault(hottest, []).append(int(best))
        written.append(best)
        return True

    out.append(({"user": str(hottest), "num": nums[-1]}, write_best))
    out.append(({"user": str(hottest), "num": nums[-1]},
                lambda answer: len(answer["itemScores"]) == nums[-1]
                and written[0] not in {s["item"]
                                       for s in answer["itemScores"]}))
    return out


# -- the comparison that decides ``correct`` ---------------------------------


def requests_of(g: dict, sched: dict, lists: list[dict], rows,
                ignore: str | None = None) -> list[dict]:
    """The reference's request (``reference_ecomm``) of each of ``rows`` of
    the schedule, from the generator's own lists. ``ignore`` plants a fault:
    ``seen``, ``withdrawn``, ``categories``, ``whiteList`` or ``blackList``
    is left out of every request."""
    ev = g["events"]
    out = []
    for k in rows:
        user, rule = sched["user"][k], lists[k]
        row = int(user) if user.isdigit() else None
        parts = {"withdrawn": ev["withdrawn"],
                 "blackList": rule.get("blackList", ()),
                 "seen": () if row is None else np.concatenate([
                     datagen_ecomm.seen_of(ev, row),
                     np.asarray(g["written"].get(row, ()), np.int64)])}
        out.append({
            "row": row, "num": int(sched["num"][k]),
            "categories": None if ignore == "categories"
            else rule.get("categories"),
            "white": None if ignore == "whiteList" else rule.get("whiteList"),
            "forbidden": np.concatenate([
                np.asarray(ids, np.int64) for what, ids in parts.items()
                if what != ignore])})
    return out


def check_queries(cfg: dict, seed: int, sched: dict, keep: list[int],
                  res: dict, log) -> dict:
    STATE.pop("storage").close()
    gc.collect()
    t0 = time.perf_counter()
    items = datagen.factors(cfg["n_items"], cfg["rank"], seed,
                            datagen.ITEM_STREAM)
    users = datagen.factors(cfg["n_users"], cfg["rank"], seed,
                            datagen.USER_STREAM)
    lists = datagen_ecomm.request_lists(cfg, STATE["traffic"], seed, sched,
                                        STATE["cats"], STATE["hot_top"])
    served, malformed = [], 0
    for k in keep:
        served.append(None)
        if res["status"][k] != 200:
            continue  # counted in ``failed``; never answered: below
        try:
            scores = res["bodies"].get(str(k))["itemScores"]
            served[-1] = {"items": [int(s["item"]) for s in scores],
                          "scores": [float(s["score"]) for s in scores]}
        except (KeyError, TypeError, ValueError):
            malformed += 1
    got = reference_ecomm.gaps(
        items, users, STATE["cats"],
        requests_of(STATE, sched, lists, keep), served)
    log(f"compared {got['compared']} of {len(keep)} sampled answers in "
        f"{time.perf_counter() - t0:.1f}s")
    lim = cfg["limits"]
    never = sum(1 for s in res["status"] if s <= 0)
    return {
        "rank_gap": (got["rank_gap"], lim["rank_gap"]),
        "score_gap": (got["score_gap"], lim["score_gap"]),
        "leak": (got["leak"], lim["leak"]),
        "fill_gap": (got["fill_gap"], lim["fill_gap"]),
        "malformed": (got["malformed"] + malformed, 0),
        "unanswered": (never, 0),
    }


# -- controls and planted faults (control.py, tests) -------------------------

FAULTS = ("seen", "withdrawn", "categories", "whiteList", "blackList")


def control(kind: str, cfg: dict, traffic: dict, seed: int,
            faults: bool = True) -> dict:
    """Gaps of the reference put in the server's place with the catalog and
    the query vector rounded to the precision below ``catalog_dtype`` and,
    with ``faults``, with one rule left out of every request: the seen
    filter off, the withdrawn list, the categories, the whiteList or the
    blackList ignored. The requests are those of a 30 s window of the seed,
    sampled as a run samples them. No server, no store."""
    import ml_dtypes

    import loadgen

    g = generate(cfg, seed)
    sched = loadgen.schedule(traffic, cfg["n_users"], seed, 30.0)
    keep = np.random.default_rng(seed).permutation(len(sched["due"]))[:int(
        traffic["compared_requests"])]
    lists = datagen_ecomm.request_lists(cfg, traffic, seed, sched, g["cats"],
                                        g["hot_top"])
    args = (g["item_factors"], g["user_factors"], g["cats"])
    requests = requests_of(g, sched, lists, keep)
    want = reference_ecomm.top_allowed(*args, requests)

    def read(answers) -> dict:
        got = reference_ecomm.gaps(*args, requests, [
            reference_ecomm.answer_of(a, q["num"])
            for a, q in zip(answers, requests)], want)
        del got["compared"]
        return got

    lower = getattr(ml_dtypes, LOWER[cfg["catalog_dtype"]])
    out = {"control_lower_precision": read(reference_ecomm.top_allowed(
        *args, requests, lower=lower))}
    for what in FAULTS if faults else ():
        out[f"fault_{what}_ignored"] = read(reference_ecomm.top_allowed(
            *args, requests_of(g, sched, lists, keep, ignore=what)))
    return out
