"""Deployment ``recommendation-eventlog``: the quickstart. An application's
``rate`` and ``buy`` events lie in the event server's JSONL log, and ``pio
train`` runs the recommendation template as it ships: the STOCK
``RecommendationDataSource`` reads them back through
``PEventStore.find_ratings`` (the log's scan by the native codec, the
selection, string ids to rows, two string ``BiMap``s), then the sibling's
``ALSAlgorithm`` and artifact. Everything the harness knows of it is here:
the engine, the logs written from the seed, the comparison that decides
``correct`` with its controls, and the calls a traced run wraps. A
configuration names this file by its ``deployment`` key. It has no serve
kind.

The event store of the run is the program's ``JSONL`` source in a directory
of the run's own, every option at its default; ``run.py``'s MEMORY sources
keep the engine instance and the artifact, as in the sibling. Each seed's
events go into an app of their own through the event server's own batch
path, ``native.ingest_batch`` -> ``JSONLEvents.insert_canonical_lines``
(no HTTP), so a log is byte for byte what the event server writes.
"""

from __future__ import annotations

import atexit
import datetime
import json
import resource
import shutil
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import bench_eventlog_engine
import datagen
import datagen_eventlog
import program_spans
import reference
import reference_eventlog
import store_spans

#: the nearest precision below the one a configuration states
LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}
#: events a request body; the event server's group commit hands the codec
#: runs of any length (``data/api/ingest_buffer.py``)
BODY_EVENTS = 65536
#: key (= the app's name) -> {"events", "app_id", "bytes"}
INPUTS: dict[str, dict] = {}
#: the id tables, the degrees and the store, made once a process
STATE: dict = {}


def engine(kind: str):
    """(engine, its factory's name) for a traffic kind."""
    make = {"retrain": bench_eventlog_engine.retrain_engine}[kind]
    return make(), "bench_eventlog_engine." + make.__name__


def _datasource_params(cfg: dict, key: str) -> dict:
    return {"appName": key, "eventNames": list(cfg["eventNames"])}


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
        "datasource": {"params": _datasource_params(config, key)},
        "algorithms": [{"name": "als", "params": algo}],
    })


def spans(kind: str) -> list[tuple]:
    """(owner, attribute, span name): the sibling's calls, so that its
    harness-fed metrics read here too."""
    from incubator_predictionio_tpu.models import recommendation
    from incubator_predictionio_tpu.ops import als

    return {"retrain": [(recommendation, "train_als", "train_als"),
                        (als, "plan_and_fill_both", "plan_and_fill_both")],
            }[kind]


def release(key: str) -> None:
    """The app's log goes through the store's own ``remove`` (which drops
    its cached scan too), so the window's host memory is one log's; the
    bytes counter is noted for ``store.scan_mb_per_s``."""
    STATE["storage"].get_l_events().remove(INPUTS.pop(key)["app_id"])
    bench_eventlog_engine.STORE["bytes_before_window"] = \
        store_spans.counter_value(store_spans.SCAN_BYTES)


# -- the logs of the seeds -----------------------------------------------------


def open_store():
    """A ``Storage`` whose EVENTDATA is the JSONL source in a fresh
    directory (removed at exit) and whose METADATA, the apps, is MEMORY."""
    from incubator_predictionio_tpu.data.storage.registry import Storage

    workdir = tempfile.mkdtemp(prefix="bench_eventlog_")
    atexit.register(shutil.rmtree, workdir, ignore_errors=True)
    return Storage({
        "PIO_STORAGE_SOURCES_LOG_TYPE": "JSONL",
        "PIO_STORAGE_SOURCES_LOG_PATH": workdir,
        "PIO_STORAGE_SOURCES_APPS_TYPE": "MEMORY",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "LOG",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "bench_eventdata",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "APPS",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "bench_metadata"})


def ingest(l_events, app_id: int, request_bodies) -> int:
    """Each body through the event server's batch path; the log's bytes."""
    from incubator_predictionio_tpu import native
    from incubator_predictionio_tpu.data.storage.event import (
        format_event_time,
    )

    if not native.available():
        raise RuntimeError("the native codec is not there: no batch path")
    total = 0
    for body, n in request_bodies:
        now = datetime.datetime.now(datetime.timezone.utc)
        got = native.ingest_batch(body, n, format_event_time(now))
        if got is None or len(got[0]) != n:
            raise RuntimeError("the batch path refused a generated body")
        l_events.insert_canonical_lines(got[1], app_id)
        total += len(got[1])
    return total


def _state(cfg: dict) -> dict:
    if not STATE:
        uid = datagen_eventlog.user_ids(cfg["n_users"])
        iid = datagen_eventlog.item_ids(cfg["n_items"])
        STATE.update(
            degrees=datagen.degrees(cfg), uid=uid, iid=iid,
            users=datagen_eventlog.as_strings(uid),
            items=datagen_eventlog.as_strings(iid), storage=open_store())
        bench_eventlog_engine.STORE["storage"] = STATE["storage"]
    return STATE


def train_inputs(cfg: dict, seeds: list[int], log) -> list[str]:
    """An app and its log for each seed; the key is the app's name. The
    generator's own arrays ride along for ``check_retrain``."""
    from incubator_predictionio_tpu.data.storage import base

    st = _state(cfg)
    apps = st["storage"].get_meta_data_apps()
    l_events = st["storage"].get_l_events()
    keys = [f"eventlog-{seed}" for seed in seeds]
    ids = [apps.insert(base.App(0, key, None)) for key in keys]

    def log_of(seed: int, key: str, app_id: int) -> None:
        ev = datagen_eventlog.events(cfg, seed, st["degrees"])
        l_events.init(app_id)
        n_bytes = ingest(l_events, app_id, datagen_eventlog.bodies(
            ev, st["uid"], st["iid"], BODY_EVENTS))
        INPUTS[key] = {"events": ev, "app_id": app_id, "bytes": n_bytes}

    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(seeds)) as pool:
        list(pool.map(log_of, seeds, keys, ids))
    first = INPUTS[keys[0]]
    log(f"data: {len(first['events']['user'])} events in a log of "
        f"{first['bytes']} bytes, {len(keys)} times, "
        f"{time.perf_counter() - t0:.1f}s")
    return keys


# -- the comparison that decides ``correct`` -----------------------------------


def _generator_rows(users, items) -> tuple[np.ndarray, np.ndarray, int]:
    """For each row of the two ``BiMap``s the generator's row of its id
    (``reference_eventlog.rows_of``), and how many ids are wrong."""
    out, wrong = [], 0
    for ids, bimap in ((STATE["users"], users), (STATE["items"], items)):
        rows, n = reference_eventlog.rows_of(
            ids, [bimap.inverse_get(k) for k in range(len(bimap))])
        out.append(rows)
        wrong += n
    return out[0], out[1], wrong


def _read_again(cfg: dict, key: str):
    """The stock ``read_training`` once more on the app's log."""
    from incubator_predictionio_tpu.controller.base import doer
    from incubator_predictionio_tpu.workflow.context import WorkflowContext

    source = doer(bench_eventlog_engine.EventLogDataSource,
                  _datasource_params(cfg, key))
    return source.read_training(WorkflowContext())


def _log_read_coverage(log) -> None:
    """How much of each train's ``dase.read`` its work spans cover."""
    snap = program_spans.snapshot()
    roots = [s for s in snap if s.name == program_spans.TRAIN_ROOT
             and s.parent_id is None]
    for tree in program_spans.trees(snap, roots):
        got = store_spans.read_coverage(tree)
        if got is not None:
            log("dase.read %.2fs, store.* spans cover %.2f%% (source=%s): %s"
                % (got[0], 100.0 * got[1], got[2], json.dumps(
                    {k: round(v, 3) for k, v in got[3].items()})))


def check_retrain(cfg: dict, key: str, persisted_models, log) -> dict:
    _state(cfg)
    ev, lim = INPUTS[key]["events"], cfg["limits"]
    n_items = cfg["n_items"]
    want = (ev["user"], ev["item"], ev["rating"])
    _log_read_coverage(log)
    log("peak host RSS so far: %.2f GB" % (
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6))
    t0 = time.perf_counter()
    model = persisted_models()[0]
    log(f"read-back: {time.perf_counter() - t0:.1f}s")
    # (b) the persisted model's ids against the ids that occur
    gen_of_urow, gen_of_irow, ids_wrong = _generator_rows(
        model.users, model.items)
    got = model.factors
    out = {"ids_wrong": (ids_wrong, lim["ids_wrong"])}
    fro = {k: v for k, v in lim.items() if k.endswith("_fro")}
    with ThreadPoolExecutor(1) as pool:
        ref = None
        if ids_wrong == 0:
            # (c) the sibling's plain ALS on the generator's triple in the
            # persisted model's row order
            row_of_user = np.empty(len(gen_of_urow), np.int32)
            row_of_user[gen_of_urow] = np.arange(len(gen_of_urow))
            row_of_item = np.empty(len(gen_of_irow), np.int32)
            row_of_item[gen_of_irow] = np.arange(len(gen_of_irow))
            u, i = row_of_user[ev["user"]], row_of_item[ev["item"]]
            ref = pool.submit(
                reference.als_reference, u, i, ev["rating"], len(row_of_user),
                len(row_of_item), cfg["rank"], cfg["lambda"], cfg["seed"],
                cfg["numIterations"], cfg["gather_dtype"], log=log)
        # (a) what the stock DataSource reads against what was posted
        t0 = time.perf_counter()
        td = _read_again(cfg, key)
        read_u, read_i, _ = _generator_rows(td.users, td.items)
        diff = reference_eventlog.triple_diff(
            (read_u[td.user_idx], read_i[td.item_idx], td.rating), want,
            n_items)
        out["triple_diff"] = (diff, lim["triple_diff"])
        log(f"read again and compared: {time.perf_counter() - t0:.1f}s")
        if ref is None:
            out.update({k: (float("inf"), v) for k, v in fro.items()})
            return out
        wx, wy = ref.result()
    gaps = reference.als_compare(
        got.user_factors, got.item_factors, wx, wy, fro,
        (np.bincount(u, minlength=len(wx)), np.bincount(i, minlength=len(wy))))
    log("gaps seen: " + json.dumps(gaps.pop("_seen")))
    out.update(gaps)
    return out


# -- controls and planted faults (control.py, tests) ---------------------------


def control_retrain(cfg: dict, seed: int, faults: bool = True) -> dict:
    """What the comparison reads when the plain reference stands in the
    program's place with a fault planted: the gathered rows rounded to the
    precision below ``gather_dtype`` and, with ``faults``, one event dropped
    from the read, every ``buy`` rated 1.0, and one user's events from the
    second on laid under a new last row that carries the same id. Each gives
    ``triple_diff``, ``ids_wrong``, ``user_fro`` and ``item_fro``."""
    st = _state(cfg)
    ev = datagen_eventlog.events(cfg, seed, st["degrees"])
    n_users, n_items = cfg["n_users"], cfg["n_items"]
    # rows as a store that numbers ids as they arrive lays them out
    row_of_user = reference_eventlog.first_seen_rows(ev["user"])
    row_of_item = reference_eventlog.first_seen_rows(ev["item"])
    gen_of_urow = np.argsort(row_of_user)
    u = row_of_user[ev["user"]].astype(np.int32)
    i = row_of_item[ev["item"]].astype(np.int32)
    r = ev["rating"]
    want = (ev["user"], ev["item"], r)

    def ref(u, i, r, n_rows=n_users, dtype=cfg["gather_dtype"]):
        return reference.als_reference(
            u, i, r, n_rows, n_items, cfg["rank"], cfg["lambda"],
            cfg["seed"], cfg["numIterations"], dtype)

    want_x, want_y = ref(u, i, r)
    weights = (np.bincount(u, minlength=n_users),
               np.bincount(i, minlength=n_items))

    def seen(read, ids_wrong: int, x, y) -> dict:
        gaps = reference.als_compare(x[:n_users], y, want_x, want_y, {},
                                     weights)["_seen"]
        return {"triple_diff": reference_eventlog.triple_diff(
                    read, want, n_items),
                "ids_wrong": ids_wrong, "user_fro": gaps["user_fro"],
                "item_fro": gaps["item_fro"]}

    out = {"control_lower_precision": seen(
        want, 0, *ref(u, i, r, dtype=LOWER[cfg["gather_dtype"]]))}
    if not faults:
        return out
    # an event of a user and an item that both have another: the ids stay
    twice_u = np.bincount(ev["user"], minlength=n_users) >= 2
    twice_i = np.bincount(ev["item"], minlength=n_items) >= 2
    drop = int(np.nonzero(twice_u[ev["user"]] & twice_i[ev["item"]])[0][0])
    keep = np.arange(len(u)) != drop
    out["fault_one_event_dropped"] = seen(
        tuple(a[keep] for a in want), 0, *ref(u[keep], i[keep], r[keep]))
    r1 = np.where(ev["buy"], np.float32(1.0), r)
    out["fault_buy_rated_1"] = seen((ev["user"], ev["item"], r1), 0,
                                    *ref(u, i, r1))
    split = int(np.argmax(twice_u))
    u2 = u.copy()
    u2[np.nonzero(ev["user"] == split)[0][1:]] = n_users
    row_ids = [st["users"][g] for g in gen_of_urow] + [st["users"][split]]
    out["fault_user_under_two_rows"] = seen(
        want, reference_eventlog.rows_of(st["users"], row_ids)[1],
        *ref(u2, i, r, n_rows=n_users + 1))
    return out


def control(kind: str, cfg: dict, traffic: dict, seed: int,
            faults: bool = True) -> dict:
    return {"retrain": control_retrain}[kind](cfg, seed, faults)
