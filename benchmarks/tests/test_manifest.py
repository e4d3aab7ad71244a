"""BENCHMARK.json against the contract's letter, and the data-driven layout:
a cell, a configuration, a traffic mix and a per-layer metric can each be
added as new files plus new entries, editing no file; and so can a
deployment (``deployments/<name>.py`` with its engine under ``engines/``:
another template, other request bodies, another reference) and a traffic
kind (``kinds/<kind>.py``)."""

import json
import os
import re
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_lengths():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    names = []
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
        assert 1 <= len(c["why"]) <= 200 and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("benchmarks/")
        names.append(c["name"])
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(p["layer"]) and p["source"] in SOURCES
    every = m["end_to_end"] + m["per_layer"]
    for x in every:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
    assert len({x["name"] for x in every}) == len(every)
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}
    assert len(m["end_to_end"]) <= 5


def test_cells_files_and_manifest_agree():
    m = manifest()
    listed = {w["name"]: w for w in m["workloads"]}
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "cells"))
               if f.endswith(".json")}
    assert on_disk == set(listed)
    for name, w in listed.items():
        with open(os.path.join(BENCH, "cells", name + ".json")) as f:
            cell = json.load(f)
        assert cell == {k: w[k] for k in ("config", "traffic", "chips", "why")}
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    for c in m["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in m["workloads"])


def test_each_metric_has_cells_that_report_what_it_moves():
    m = manifest()
    cells = {w["name"] for w in m["workloads"]}

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    e2e = {e["name"]: cells_of(e) for e in m["end_to_end"]}
    for cell in cells:
        assert any(cell in c for n, c in e2e.items() if n != "setup_s")
        assert any(cell in cells_of(p) for p in m["per_layer"])
    for p in m["per_layer"]:
        assert p["moves"] in e2e and p["moves"] != "setup_s"
        assert cells_of(p) <= e2e[p["moves"]], p["name"]
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           p["name"] + ".py"))


def checkout_copy(tmp_path):
    """A temp copy of the benchmark beside the program: (the checkout, its
    benchmarks/, every file there with its bytes)."""
    work = tmp_path / "checkout"
    work.mkdir()
    shutil.copytree(BENCH, work / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "incubator_predictionio_tpu"),
               work / "incubator_predictionio_tpu")
    before = {p: p.read_bytes() for p in (work / "benchmarks").rglob("*")
              if p.is_file()}
    return work, work / "benchmarks", before


def rehearse_traced(work, cell):
    """The result line of ``--rehearse --trace 1`` of a cell in ``work``."""
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell,
         "--seed", "3", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=work, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def assert_nothing_edited(b, before):
    after = {p: p.read_bytes() for p in b.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[p] == data for p, data in before.items())


def test_a_later_pr_adds_a_cell_by_files_alone(tmp_path):
    """A dummy cell, configuration, traffic mix and per-layer metric are
    ADDED (files and manifest entries); no file that was there is edited;
    the dummy cell runs (at the rehearsal size) and reports the dummy
    metric."""
    work, b, before = checkout_copy(tmp_path)
    cfg = json.loads((b / "configs/amazon-catalog9m-als128.json").read_text())
    cfg["name"] = "dummy-catalog"
    cfg["rehearse"] = {"n_users": 500, "n_items": 3000}
    (b / "configs/dummy-catalog.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic/queries-steady-p8.json").read_text())
    mix["num_shares"] = [[3, 1.0]]
    (b / "traffic/dummy-mix.json").write_text(json.dumps(mix))
    cell = {"config": "dummy-catalog", "traffic": "dummy-mix", "chips": 1,
            "why": "dummy"}
    (b / "cells/dummy-cell.json").write_text(json.dumps(cell))
    (b / "metrics/dummy.requests.py").write_text(
        "def read(record):\n"
        "    return float(record.window['summary']['attempted'])\n")
    m = manifest()
    m["configs"].append({"name": "dummy-catalog", "source": "x",
                         "file": "benchmarks/configs/dummy-catalog.json",
                         "reduced": [], "why": "dummy"})
    m["workloads"].append(dict(cell, name="dummy-cell"))
    for e in m["end_to_end"]:
        if e["name"].startswith("query_"):
            e["workloads"].append("dummy-cell")
    m["per_layer"].append({
        "name": "dummy.requests", "unit": "req", "better": "higher",
        "source": "program_counter", "layer": "loadgen",
        "moves": "query_p95_ms", "workloads": ["dummy-cell"]})
    (work / "BENCHMARK.json").write_text(json.dumps(m))
    line = rehearse_traced(work, "dummy-cell")
    assert line["correct"] is True
    assert line["metrics"]["dummy.requests"]["value"] == line["attempted"]
    assert "serve.topk_call_ms" not in line["metrics"]   # not its cell
    assert_nothing_edited(b, before)


TOY_ENGINE = '''
"""A popularity engine: train counts the ratings of each item, predict
answers the num most rated items that are not in the query's blackList."""
import numpy as np

from incubator_predictionio_tpu.controller import (
    Algorithm, DataSource, Engine,
)

INPUTS = {}


class RatedItems(DataSource):
    def read_training(self, ctx):
        return INPUTS["rated"]


class Popularity(Algorithm):
    def train(self, ctx, rated):
        return np.bincount(rated)

    def predict(self, counts, query):
        black = set(query["blackList"])
        best = [i for i in np.argsort(-counts, kind="stable").tolist()
                if str(i) not in black][:query["num"]]
        return {"itemScores": [{"item": str(i), "score": int(counts[i])}
                               for i in best]}


def engine():
    return Engine(data_source_class=RatedItems,
                  algorithm_class_map={"popularity": Popularity})
'''

TOY_DEPLOYMENT = '''
"""Bodies {"num", "blackList"}; the answers are compared with NumPy."""
import numpy as np

import toy_popularity


def engine(kind):
    return toy_popularity.engine(), "toy_popularity.engine"


def engine_params(config, key, num_iterations=None):
    from incubator_predictionio_tpu.controller import EngineParams

    return EngineParams.from_json(
        {"algorithms": [{"name": "popularity", "params": {}}]})


def rated(config, seed):
    return np.random.default_rng(seed).integers(
        0, config["n_items"], config["n_ratings"])


def serve_inputs(config, seed):
    toy_popularity.INPUTS["rated"] = rated(config, seed)
    return "rated"


def release(key):
    del toy_popularity.INPUTS[key]


def warmup(traffic):
    return [({"num": 1, "blackList": []},
             lambda answer: len(answer["itemScores"]) == 1)]


def bodies(config, traffic, seed):
    rng = np.random.default_rng(seed + 1)
    return [{"num": int(traffic["num"]), "blackList": [
                str(i) for i in rng.integers(0, config["n_items"], 3)]}
            for _ in range(int(traffic["queries"]))]


def check(config, seed, sent, answers):
    counts = np.bincount(rated(config, seed))
    order = np.argsort(-counts, kind="stable").tolist()
    wrong = 0
    for body, (status, answer) in zip(sent, answers):
        want = [str(i) for i in order
                if str(i) not in body["blackList"]][:body["num"]]
        wrong += [s["item"] for s in answer["itemScores"]] != want
    return {"toy_wrong_answers": (wrong, 0),
            "toy_unanswered": (len(sent) - len(answers), 0)}
'''

TOY_KIND = '''
"""Closed loop: the mix's queries one after another, as fast as answered."""
import time

import run as bench


def phases(record, workdir, deployment):
    import serving

    st, server = serving.serve_setup(record, deployment)
    sent = deployment.bodies(record.config, record.traffic, record.seed)

    def window():
        t0 = time.perf_counter()
        answers = [serving.post_json(st.base + "/queries.json", body)
                   for body in sent]
        return {"answers": answers, "wall_s": time.perf_counter() - t0}

    def after_window(win):
        st.stop()
        server.deployment = None

    return {"window": window, "after_window": after_window,
            "check": lambda win: deployment.check(
                record.config, record.seed, sent, win["answers"]),
            "end_to_end": lambda win: {"toy_answers_per_s": (
                len(win["answers"]) / win["wall_s"], "1/s")},
            "attempted": lambda win: (len(sent), sum(
                status != 200 for status, _ in win["answers"]))}
'''


def test_a_later_pr_adds_a_deployment_and_a_kind_by_files_alone(tmp_path):
    """A template the benchmark has never run goes in as files and entries:
    an engine, a deployment file with other request bodies and a reference
    of its own, a traffic kind, a configuration, a mix, a cell and a
    metric. No file that was there is edited; the cell runs (rehearsal) and
    its line carries the toy's own compared names."""
    work, b, before = checkout_copy(tmp_path)
    (b / "engines/toy_popularity.py").write_text(TOY_ENGINE)
    (b / "deployments/toy-popularity.py").write_text(TOY_DEPLOYMENT)
    (b / "kinds/toy-closed-loop.py").write_text(TOY_KIND)
    cfg = {"name": "toy-shop", "source": "x", "deployment": "toy-popularity",
           "n_items": 5000, "n_ratings": 400000, "reduced": [],
           "rehearse": {"n_items": 50, "n_ratings": 4000}}
    (b / "configs/toy-shop.json").write_text(json.dumps(cfg))
    (b / "traffic/toy-blacklisted.json").write_text(json.dumps(
        {"kind": "toy-closed-loop", "num": 4, "queries": 30}))
    cell = {"config": "toy-shop", "traffic": "toy-blacklisted", "chips": 1,
            "why": "toy"}
    (b / "cells/toy-cell.json").write_text(json.dumps(cell))
    (b / "metrics/toy.answers.py").write_text(
        "def read(record):\n"
        "    return float(len(record.window['answers']))\n")
    m = manifest()
    m["configs"].append({"name": "toy-shop", "source": "x", "reduced": [],
                         "file": "benchmarks/configs/toy-shop.json",
                         "why": "toy"})
    m["workloads"].append(dict(cell, name="toy-cell"))
    m["end_to_end"].append({
        "name": "toy_answers_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.1, "source": "host_clock", "workloads": ["toy-cell"]})
    m["per_layer"].append({
        "name": "toy.answers", "unit": "req", "better": "higher",
        "source": "program_counter", "layer": "serving",
        "moves": "toy_answers_per_s", "workloads": ["toy-cell"]})
    (work / "BENCHMARK.json").write_text(json.dumps(m))
    line = rehearse_traced(work, "toy-cell")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 30
    assert line["compared"] == {
        "toy_wrong_answers": {"value": 0, "limit": 0},
        "toy_unanswered": {"value": 0, "limit": 0}}
    assert line["metrics"] == {"toy.answers": {"value": 30.0, "unit": "req"}}
    assert set(line["end_to_end_seen"]) == {"toy_answers_per_s", "setup_s"}
    assert_nothing_edited(b, before)


def test_without_the_program_no_result(tmp_path):
    work = tmp_path / "bare"
    work.mkdir()
    shutil.copytree(BENCH, work / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), work)
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "retrain-electronics-r128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=work, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""
