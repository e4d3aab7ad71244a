"""PR 28 moved what is particular to a deployment out of ``run.py`` into
``deployments/``, ``kinds/`` and ``lib/serving.py``. The move changes nothing:
the constants below were recorded from the PARENT's harness (commit 13f62be)
at the rehearsal size on the CPU, two seeds a cell, and the moved harness has
to reproduce them: the inputs it hands the engine (sha256 over the arrays of
each ``bench_engine.INPUTS`` entry), the load generator's job (sha256 over
``due`` and the bodies in the order sent), the keys of the result line and
the ``compared`` values.

The hashes and the keys depend on the harness alone and are exact.
``compared`` is deterministic for a seed on one machine (two recordings gave
the same digits), but it also carries the PROGRAM's arithmetic at 13f62be: it
is held to four digits, and a later PR that changes the program's numerics on
purpose re-records it. ``attempted`` of the retrain cell (whole trains in one
second of the sandbox's CPU) is not deterministic and is not pinned."""

import hashlib
import json

import numpy as np
import pytest

import run as bench

RETRAIN, SERVE = "retrain-electronics-r128", "serve-catalog9m-steady"

PARENT = {
    (RETRAIN, 123): {
        "inputs": {
            "ratings-1000126":
                "0acea9c70b51d38879b520c3a3ce075967620159811d65c0fa1d541052e1583a",
            "ratings-123":
                "461ee7abcd40ffa92021c6de4f86e0cdc9a1a22ac2452c2473261e6cb2e91dce",
        },
        "compared": {"user_fro": 0.017850727880877312,
                     "item_fro": 0.0211614289321194},
    },
    (RETRAIN, 2147483725): {
        "inputs": {
            "ratings-2147483725":
                "aadb1d41ed6aa2beb12009b2771a7f1d2f6ec6b9732a95145b0e8bd17d5065f7",
            "ratings-2148483728":
                "602e85a86442b43dc904c93a26bfe3008b4fe4704aa2436fdd8be8a755b76e3e",
        },
        "compared": {"user_fro": 0.015866334798045077,
                     "item_fro": 0.020068858459750104},
    },
    (SERVE, 123): {
        "inputs": {
            "factors-123":
                "dceb45f7d5eb4d24e57c1b5e6b4a7308f184c886b3412a4ac25da818defaf4ab",
        },
        "job":
            "362698f55f83dba7a0826b0a9447f6567fee034391dcf29712d5689b0e58f684",
        "compared": {"rank_gap": 0.0, "score_gap": 8.751288171386978e-07,
                     "malformed": 0, "unanswered": 0},
    },
    (SERVE, 2147483725): {
        "inputs": {
            "factors-2147483725":
                "ba1c3bf3e0aa89c5870422e02a470980b2065e09e8162801e0842adcb2bbfa7d",
        },
        "job":
            "2695226461cd96000d9610a855e162ac052cfbdd97d1af57bd621f675985b26e",
        "compared": {"rank_gap": 0.0, "score_gap": 7.321489725478912e-07,
                     "malformed": 0, "unanswered": 0},
    },
}

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device",
             "rehearsal", "end_to_end_seen", "compared"]
DEVICE_KEYS = ["count", "kind", "memory_peak_bytes", "platform"]
#: a rehearsal on the CPU has no device plane: the trace-fed metrics are
#: left out on both sides of the move
METRICS = {
    (RETRAIN, 0): ["retrain_s", "setup_s"],
    (RETRAIN, 1): ["als.init_s", "als.loop_s", "als.readback_s",
                   "als.upload_s", "dase.outside_als_s", "dase.persist_s",
                   "layout.fill_s", "train.window_compiles"],
    (SERVE, 0): ["query_p50_ms", "query_p95_ms", "setup_s"],
    (SERVE, 1): ["loadgen.late_ms", "serve.admit_wait_ms",
                 "serve.busy_host_share", "serve.device_wait_ms",
                 "serve.host_ms", "serve.topk_call_ms",
                 "serve.window_compiles"],
}


class HashingInputs(dict):
    """``bench_engine.INPUTS`` that notes the sha256 of each entry's arrays."""

    def __init__(self):
        super().__init__()
        self.seen = {}

    def __setitem__(self, key, entry):
        h = hashlib.sha256()
        for name in sorted(entry):
            if isinstance(entry[name], np.ndarray):
                h.update(name.encode()
                         + np.ascontiguousarray(entry[name]).tobytes())
        self.seen[key] = h.hexdigest()
        super().__setitem__(key, entry)


@pytest.mark.parametrize("cell,seed,trace", [
    (cell, seed, trace) for (cell, seed) in PARENT
    for trace in ((0, 1) if seed == 123 else (0,))])
def test_the_moved_harness_reproduces_the_parent(capsys, monkeypatch, cell,
                                                 seed, trace):
    bench.load_cell(cell, rehearse=True)     # the benchmark's import path
    import bench_engine
    import serving

    inputs = HashingInputs()
    monkeypatch.setattr(bench_engine, "INPUTS", inputs)
    jobs = []
    offer_init = serving.Offer.__init__

    def noting(self, *a, **kw):
        offer_init(self, *a, **kw)
        jobs.append(self.job)

    monkeypatch.setattr(serving.Offer, "__init__", noting)
    rc = bench.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     "1", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    want = PARENT[cell, seed]

    assert inputs.seen == want["inputs"]
    assert len(jobs) == ("job" in want)
    for job in jobs:
        sent = json.dumps({"due": job["due"], "body": job["body"]})
        assert hashlib.sha256(sent.encode()).hexdigest() == want["job"]
    assert list(line) == LINE_KEYS
    assert sorted(line["device"]) == DEVICE_KEYS
    assert sorted(line["metrics"]) == METRICS[cell, trace]
    assert line["correct"] is True and line["failed"] == 0
    assert list(line["compared"]) == list(want["compared"])
    for name, value in want["compared"].items():
        assert line["compared"][name]["value"] == pytest.approx(
            value, rel=1e-4, abs=0), name
