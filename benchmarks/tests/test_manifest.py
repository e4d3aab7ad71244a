"""BENCHMARK.json against the contract's letter, and the data-driven layout:
a cell, a configuration, a traffic mix of an existing kind and a per-layer
metric can each be added as new files plus new entries, editing no file."""

import json
import os
import re
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

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


def test_a_later_pr_adds_a_cell_by_files_alone(tmp_path):
    """A temp copy of the benchmark beside the program; a dummy cell,
    configuration, traffic mix and per-layer metric are ADDED (files and
    manifest entries); no file that was there is edited; the dummy cell
    runs (at the rehearsal size) and reports the dummy metric."""
    work = tmp_path / "checkout"
    work.mkdir()
    shutil.copytree(BENCH, work / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "incubator_predictionio_tpu"),
               work / "incubator_predictionio_tpu")
    before = {p: p.read_bytes() for p in (work / "benchmarks").rglob("*")
              if p.is_file()}
    b = work / "benchmarks"
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
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "dummy-cell",
         "--seed", "3", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=work, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["dummy.requests"]["value"] == line["attempted"]
    assert "serve.topk_call_ms" not in line["metrics"]   # not its cell
    after = {p: p.read_bytes() for p in (work / "benchmarks").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[p] == data for p, data in before.items())


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
