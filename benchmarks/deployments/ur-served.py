"""Deployment ``ur-served``: the universal-recommender template's stock
``URAlgorithm.predict`` and ``URModel.recommend`` behind ``POST
/queries.json`` of ActionML's UR query spec (``user``, ``item``, ``fields``
with a bias, ``blacklistItems``, ``num``; popularity backfill). Every query
of a user reads that user's history from the event store, ships it to the
device as catalog rows, and the device sums the postings of the resident
indicators that name them, applies the rules and selects. Everything the
harness knows of the template is here: the engine, the inputs from the seed
(indicators, categories, popularity, the event store's contents), the
request bodies, the plain reference with its comparison and its controls. A
configuration names this file by its ``deployment`` key. It has no retrain
kind (``universal-recommender`` retrains the template).

The import of ``ResidentIndicators`` below is what the cell needs of the
program: a checkout that uploads the model a query has no such name and
fails here, before anything is generated.

The event store is the program's default kind of source, SQLITE on disk, in
a directory of the run's own under the temporary directory; ``run.py``'s
MEMORY sources keep the metadata and the artifact, as in the siblings.
"""

from __future__ import annotations

import atexit
import datetime
import gc
import shutil
import tempfile
import time

import numpy as np

import bench_ur_serve_engine
import datagen_ecomm
import datagen_ur_serve
import reference_ur_serve
import run as bench
import store_spans
import work_ur

from incubator_predictionio_tpu.ops.llr import ResidentIndicators  # noqa: F401

APP = "BenchURShop"
#: the nearest precision below the one a configuration states
LOWER = {"float32": "bfloat16"}
#: what ``serve_inputs`` made from the seed, kept for ``warmup``, ``bodies``
#: and ``check_queries`` (the harness hands those the schedule alone)
STATE: dict = {}


def engine(kind: str):
    """(engine, its factory's name) for a traffic kind."""
    make = {"queries": bench_ur_serve_engine.serve_engine}[kind]
    return make(), "bench_ur_serve_engine." + make.__name__


def engine_params(config: dict, key: str, num_iterations: int | None = None):
    from incubator_predictionio_tpu.controller import EngineParams

    algo = {"appName": APP,
            "maxCorrelatorsPerItem": config["maxCorrelatorsPerItem"]}
    return EngineParams.from_json({
        "datasource": {"params": {"key": key}},
        "algorithms": [{"name": "ur", "params": algo}],
    })


def spans(kind: str) -> list[tuple]:
    """Nothing is wrapped from outside: the scoring call carries the
    program's own spans (``ur.score``, ``ur.backfill``)."""
    return {"queries": []}[kind]


def release(key: str) -> None:
    del bench_ur_serve_engine.INPUTS[key]


# -- inputs from the seed ----------------------------------------------------


def events_of(cfg: dict, seed: int) -> dict:
    ev = datagen_ur_serve.user_events(cfg)
    ev["item"] = datagen_ur_serve.items_of(ev["rank"], cfg, seed)
    return ev


def hottest_user(cfg: dict) -> int:
    """The user row the mix's zipf asks for most."""
    return int(datagen_ecomm.hot_users(dict(cfg, hot_users=1))[0])


def request_of(g: dict, q: dict, num: int) -> dict:
    """The reference's request of one row's fields
    (``datagen_ur_serve.request_fields``): the user's history from the
    generator's events and what set-up wrote after them, never from the
    store."""
    history = {}
    if q["user"] is not None:
        history = {e: np.concatenate([rows, np.asarray(
            g["written"].get((q["user"], e), ()), np.int64)])
            for e, rows in datagen_ur_serve.history_of(
                g["events"], q["user"]).items()}
    return {"history": history, "item": q.get("item"),
            "category": q.get("category"), "bias": q.get("bias", -1.0),
            "blacklist": q.get("blacklist", ()), "num": int(num)}


def drawn(cfg: dict, seed: int) -> dict:
    """What is drawn from the seed on the host: the events, the categories
    and the popularity ranking."""
    g = {"seed": int(seed), "cfg": cfg, "events": events_of(cfg, seed),
         "cats": datagen_ecomm.categories(cfg, seed),
         #: (user row, event name) -> item rows written after the load
         "written": {}}
    g["popularity"] = datagen_ur_serve.popularity(cfg, g["events"])
    return g


def generate(cfg: dict, seed: int) -> dict:
    """Everything drawn from the seed: `drawn`, and the indicators with
    each item's posting length."""
    g = drawn(cfg, seed)
    g["indicators"], g["named"] = datagen_ur_serve.indicators(cfg, seed)
    return g


def own_top(g: dict, slots, user: int, size: int) -> np.ndarray:
    """The rows of the user's own unfiltered best ``size`` items, best
    first: what ``{user, num: size}`` has to answer by the reference, with
    the history as it stands now (what set-up wrote included). ``slots``
    hold the user's history (`reference_ur_serve.gather`)."""
    return reference_ur_serve.top(
        g["cfg"], slots, g["popularity"], g["cats"],
        request_of(g, {"user": int(user)}, size))["items"][:size]


def fields_of(g: dict, traffic: dict, sched: dict, each):
    """(`datagen_ur_serve.request_fields` of a schedule, the reference's
    `Slots` of everything its requests need) from ONE pass over the
    indicators (``each``: `reference_ur_serve.gather`): the histories and
    query items are known before the blacklists are, the blacklists are of
    the own best items of exactly the users the schedule gives that shape,
    and the comparison after the window sums the same slots."""
    args = (g["cfg"], traffic, g["seed"], sched)
    size = int(traffic["black_list_top"])
    slots = reference_ur_serve.gather(g["cfg"], each, [
        request_of(g, q, size)
        for q in datagen_ur_serve.request_fields(*args)])
    tops = {user: own_top(g, slots, user, size)
            for user in datagen_ur_serve.users_asking(traffic, sched,
                                                      "blacklist")}
    return datagen_ur_serve.request_fields(*args, tops), slots


def held(cfg: dict, indicators: dict, rows: int = 1 << 18):
    """`reference_ur_serve.gather`'s ``each`` over forward arrays that are
    held, block by block as ``datagen_ur_serve.indicator_blocks`` gives
    them."""
    def blocks(number: int):
        idx, score = indicators[cfg["eventNames"][number]]
        for lo in range(0, len(idx), rows):
            yield lo, idx[lo:lo + rows], score[lo:lo + rows]
    return reference_ur_serve.in_turn(blocks, cfg["eventNames"])


def open_store(g: dict):
    """A ``Storage`` whose METADATA and EVENTDATA are one SQLITE file: the
    app and every ``view`` and ``buy`` event of ``g``. The event rows go in
    by one bulk insert on a connection of the loader's own, with the table's
    indexes dropped and built again at the end by the DAO's own ``init``
    (the e-commerce sibling's loader); the table and its indexes are the
    DAO's, and so is every later read and write."""
    import sqlite3

    from incubator_predictionio_tpu.data.storage import base
    from incubator_predictionio_tpu.data.storage.registry import Storage

    workdir = tempfile.mkdtemp(prefix="bench_ur_")
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
    return storage, app_id


def serve_inputs(cfg: dict, seed: int) -> str:
    """The store is loaded (one thread of Python and SQLite, 35 s at full
    size) beside the indicators' draw (the device, the fetch and numpy)."""
    import concurrent.futures

    g = drawn(cfg, seed)
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        store = pool.submit(open_store, g)
        g["indicators"], g["named"] = datagen_ur_serve.indicators(cfg, seed)
        bench.say(f"indicators of {len(g['indicators'])} event types drawn "
                  f"beside {len(g['events']['item'])} events")
        storage, app_id = store.result()
    bench.say("event store loaded")
    bench_ur_serve_engine.STORE["storage"] = storage
    names = [{name} for name in datagen_ecomm.CATEGORIES]
    key = f"ur-shop-{seed}"
    bench_ur_serve_engine.INPUTS[key] = {
        "indicators": g.pop("indicators"), "n_users": cfg["n_users"],
        "item_categories": dict(zip(
            map(str, range(cfg["n_items"])),
            map(names.__getitem__, g["cats"].tolist()))),
        "popularity": g["popularity"], "app_name": APP}
    STATE.clear()
    STATE.update(g, storage=storage, app_id=app_id)
    return key


# -- request bodies ----------------------------------------------------------


def body_of(q: dict, user: str, num: int) -> dict:
    """The JSON body of one row's fields, in the UR query spec."""
    body: dict = {"num": int(num)}
    if q["shape"] == "item":
        body["item"] = str(q["item"])
    else:
        body["user"] = user
    if "category" in q:
        body["fields"] = [{"name": "categories", "bias": q["bias"],
                           "values": [datagen_ecomm.CATEGORIES[
                               q["category"]]]}]
    if "blacklist" in q:
        body["blacklistItems"] = [str(i) for i in q["blacklist"].tolist()]
    return body


def bodies(sched: dict) -> list[dict]:
    """One JSON body per row of the schedule, in order. Which row has which
    shape is the mix's (``datagen_ur_serve.shapes_of``); the categories,
    items and ids are drawn from the seed, a blacklist from its user's own
    best items (the indicators are drawn again block by block for that:
    set-up, not the window). Leaves the fields and the reference's slots
    of every request in ``STATE`` for `check_queries` (the one pass serves
    both: nothing of the comparison but its sums waits for the answers),
    and in ``work_ur.WINDOW`` what each request needs: the slots that name
    a row of its history (from the generator's posting lengths), and its
    path."""
    cfg = STATE["cfg"]
    fields, STATE["slots"] = fields_of(
        STATE, STATE["traffic"], sched,
        datagen_ur_serve.each_block(cfg, STATE["seed"]))
    STATE["fields"] = fields
    postings, path = [], []
    for q, num in zip(fields, sched["num"]):
        rows = reference_ur_serve.rows_of(request_of(STATE, q, num),
                                          cfg["eventNames"])
        postings.append(int(sum(STATE["named"][e][r].sum()
                                for e, r in rows.items())))
        path.append("history" if any(len(r) for r in rows.values())
                    else "backfill")
    work_ur.WINDOW.update(
        postings=postings, path=path,
        program_postings_before=store_spans.counter_value(
            work_ur.POSTINGS_READ))
    return [body_of(q, user, num)
            for q, user, num in zip(fields, sched["user"], sched["num"])]


def warmup(traffic: dict):
    """(body, what its answer has to satisfy), one after the other: every
    ``num`` of the mix on the history and the backfill path, every category
    as a filter, a boost, an item, a blacklist of the hottest user's own
    best three as the server just gave them; and the read-your-write case:
    the hottest user's best item is written to the store as a ``buy`` after
    the first answer, and the next answer must leave it out (its postings
    then count, too)."""
    STATE["traffic"] = traffic
    hottest = hottest_user(STATE["cfg"])
    nums = sorted({int(n) for n, _ in traffic["num_shares"]})
    some = lambda answer: len(answer["itemScores"]) > 0
    full = lambda num: lambda answer: len(answer["itemScores"]) == num
    given = lambda answer: {s["item"] for s in answer["itemScores"]}
    for num in nums:
        yield {"user": str(hottest), "num": num}, some
    for num in nums:
        yield {"user": "nobody-yet", "num": num}, full(num)
    field = lambda name, bias: [{"name": "categories", "values": [name],
                                 "bias": bias}]
    for name in datagen_ecomm.CATEGORIES:
        yield ({"user": str(hottest), "num": nums[-1],
                "fields": field(name, -1)}, lambda answer: True)
    yield ({"user": str(hottest), "num": nums[0],
            "fields": field(datagen_ecomm.CATEGORIES[0],
                            float(traffic["boost_bias"]))}, some)
    # a product page of the item the most rows name (an item that no row
    # names has no similar items, and the answer is rightly empty)
    named = sum(STATE["named"].values())
    yield {"item": str(int(named.argmax())), "num": nums[-1]}, some
    best = []
    yield ({"user": str(hottest), "num": nums[-1]},
           lambda answer: best.extend(
               s["item"] for s in answer["itemScores"][:3]) or len(best) > 0)
    yield ({"user": str(hottest), "num": nums[-1], "blacklistItems": best},
           lambda answer: some(answer) and not given(answer) & set(best))

    def write_best(answer) -> bool:
        from incubator_predictionio_tpu.data.storage.event import Event

        STATE["storage"].get_l_events().insert(Event(
            event="buy", entity_type="user", entity_id=str(hottest),
            target_entity_type="item", target_entity_id=best[0]),
            STATE["app_id"])
        STATE["written"].setdefault((hottest, "buy"), []).append(int(best[0]))
        return answer["itemScores"][0]["item"] == best[0]

    yield {"user": str(hottest), "num": nums[-1]}, write_best
    yield ({"user": str(hottest), "num": nums[-1]},
           lambda answer: some(answer) and best[0] not in given(answer))


# -- the comparison that decides ``correct`` ---------------------------------


def requests_of(g: dict, fields: list[dict], sched: dict, rows
                ) -> list[dict]:
    return [request_of(g, fields[k], sched["num"][k]) for k in rows]


def check_queries(cfg: dict, seed: int, sched: dict, keep: list[int],
                  res: dict, log) -> dict:
    STATE.pop("storage").close()
    gc.collect()
    t0 = time.perf_counter()
    fields = STATE.pop("fields")
    assert len(fields) == len(sched["due"]), "not the window's schedule"
    requests = requests_of(STATE, fields, sched, keep)
    slots = STATE.pop("slots")
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
    got = reference_ur_serve.gaps(cfg, slots, STATE["popularity"],
                                  STATE["cats"], requests, served)
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

#: the query item is forbidden too, but no fault plants it: an item is a
#: correlator of its own row by chance only, so leaving the rule out
#: returns nothing forbidden and a control of it would read under the limit
FAULTS = ("blacklist", "history", "filter")


def control(kind: str, cfg: dict, traffic: dict, seed: int,
            faults: bool = True) -> dict:
    """Gaps of the reference put in the server's place with the scores
    rounded to the precision below ``score_dtype``; with one event type
    dropped (the second: ``view``); with a stale history (an event written
    to the store after the load is not read: the hottest user's best item
    bought, as set-up does); and, with ``faults``, with one rule left out
    of every request: the blacklist or the user's own buys returned, the
    filter ignored. The requests are those of a 30 s
    window of the seed, sampled as a run samples them. No server, no
    store."""
    import ml_dtypes

    import loadgen

    g = generate(cfg, seed)
    hottest = hottest_user(cfg)
    sched = loadgen.schedule(traffic, cfg["n_users"], seed, 30.0)
    keep = np.random.default_rng(seed).permutation(len(sched["due"]))[:int(
        traffic["compared_requests"])]
    each = held(cfg, g["indicators"])
    # as a run: the buy is written in warm-up, the bodies are made after it
    g["written"][hottest, "buy"] = [int(own_top(g, reference_ur_serve.gather(
        cfg, each, [request_of(g, {"user": hottest}, 1)]), hottest, 1)[0])]
    fields, slots = fields_of(g, traffic, sched, each)
    requests = requests_of(g, fields, sched, keep)
    stale = requests_of(dict(g, written={}), fields, sched, keep)
    args = (g["popularity"], g["cats"])
    want = [reference_ur_serve.top(cfg, slots, *args, q) for q in requests]

    def read(answers) -> dict:
        got = reference_ur_serve.gaps(cfg, slots, *args, requests, [
            reference_ur_serve.answer_of(a, q["num"])
            for a, q in zip(answers, requests)], want)
        del got["compared"]
        return got

    lower = reference_ur_serve.gather(
        cfg, each, requests,
        lower=getattr(ml_dtypes, LOWER[cfg["score_dtype"]]))
    out = {
        "control_lower_precision": read([
            reference_ur_serve.top(cfg, lower, *args, q) for q in requests]),
        "control_event_type_dropped": read([
            reference_ur_serve.top(cfg, slots, *args, q,
                                   only=cfg["eventNames"][:1])
            for q in requests]),
        "control_stale_history": read([
            reference_ur_serve.top(cfg, slots, *args, q) for q in stale])}
    for what in FAULTS if faults else ():
        out[f"fault_{what}_ignored"] = read([
            reference_ur_serve.top(cfg, slots, *args, q, ignore=what)
            for q in requests])
    return out
