"""Fresh-process `pio train` cost — what a user who types `pio train` pays.

The in-process "warm" protocol (bench_templates.py) re-trains inside one
long-lived process. A real `pio train` is a FRESH process: it pays the
interpreter, the jax import, the backend start-up, and — unless the
persistent XLA compilation cache holds them — every compile. This
harness measures that:

- writes a minimal engine dir (synthetic DataSource at the
  bench_templates config-3 scale: 100k users x 20k items, 5M views,
  implicit ALS rank 32 x 10),
- runs `bin/pio train` in a subprocess TWICE (the first populates the
  compile cache), timing the second process's TRAIN PHASE (the
  engine-reported train seconds, excluding interpreter/jax import),
- prints one JSON line.

The compile cache is wherever the program keeps it
(JAX_COMPILATION_CACHE_DIR if set, else the checkout's .jax_cache —
workflow/context.py); this script invents no directory of its own, so a
second invocation starts warm. From PR 10 (bf63b1b) through PR 20 the
cache was switched off by a bug, so in that window this script's "warm"
run was a second cold run; the one figure it ever recorded (2026-07,
9.2 s) predates the break and was taken on a machine that is gone.

Run on a QUIET host: `python tools/bench_fresh_process.py`.
"""

import json
import os
import re
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENGINE_PY = '''
import numpy as np

from incubator_predictionio_tpu.controller.datasource import DataSource
from incubator_predictionio_tpu.controller.engine import Engine
from incubator_predictionio_tpu.data.storage.bimap import BiMap
from incubator_predictionio_tpu.models.similar_product import (
    SimilarProductAlgorithm, TrainingData,
)

N_USERS, N_ITEMS, NNZ = 100_000, 20_000, 5_000_000


class SynthDS(DataSource):
    def read_training(self, ctx):
        rng = np.random.default_rng(2)
        u = rng.integers(0, N_USERS, NNZ).astype(np.int32)
        i = np.minimum((N_ITEMS * rng.random(NNZ) ** 2).astype(np.int32),
                       N_ITEMS - 1)
        r = np.ones(NNZ, np.float32)
        return TrainingData(
            u, i, r,
            BiMap({str(j): j for j in range(N_USERS)}),
            BiMap({str(j): j for j in range(N_ITEMS)}),
            {},
        )


def engine():
    return Engine(data_source_class=SynthDS,
                  algorithm_class_map={"als": SimilarProductAlgorithm})
'''

ENGINE_JSON = {
    "id": "default",
    "description": "fresh-process bench engine",
    "engineFactory": "bench_engine.engine",
    "algorithms": [{"name": "als", "params": {
        "rank": 32, "numIterations": 10, "lambda": 0.01, "alpha": 1.0}}],
}


def run_train(engine_dir: str, env: dict) -> tuple[float, float]:
    """Returns (process wall seconds, engine-reported train seconds)."""
    t0 = time.perf_counter()
    r = subprocess.run(
        ["bash", os.path.join(REPO, "bin", "pio"), "train",
         "--engine-dir", engine_dir],
        capture_output=True, text=True, env=env, timeout=900)
    wall = time.perf_counter() - t0
    if r.returncode != 0:
        raise RuntimeError(f"pio train failed:\n{r.stdout}\n{r.stderr}")
    train_s = None
    for line in (r.stdout + r.stderr).splitlines():
        # the train verb prints "Training completed in X.XXs on ..."
        m = re.search(r"Training completed in ([0-9.]+)s", line)
        if m:
            train_s = float(m.group(1))
    return wall, train_s if train_s is not None else wall


def main():
    d = tempfile.mkdtemp(prefix="pio_fresh_")
    engine_dir = os.path.join(d, "engine")
    os.makedirs(engine_dir)
    with open(os.path.join(engine_dir, "bench_engine.py"), "w") as f:
        f.write(ENGINE_PY)
    with open(os.path.join(engine_dir, "engine.json"), "w") as f:
        json.dump(ENGINE_JSON, f)
    env = dict(os.environ)
    env.update({
        "PIO_FS_BASEDIR": os.path.join(d, "store"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
        "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
        "PIO_STORAGE_SOURCES_DB_PATH": os.path.join(d, "pio.sqlite"),
    })
    wall1, train1 = run_train(engine_dir, env)
    wall2, train2 = run_train(engine_dir, env)
    nnz = 5_000_000
    print(f"[fresh] run1 wall {wall1:.1f}s train {train1:.1f}s "
          f"(compile-cache populate); run2 wall {wall2:.1f}s "
          f"train {train2:.1f}s", file=sys.stderr, flush=True)
    print(json.dumps({
        "metric": "pio train similar_product fresh-process, warm compile "
                  "cache (tpu)",
        "value": round(nnz / train2, 1),
        "unit": "events/sec/chip",
        "detail": {"train_seconds": round(train2, 2),
                   "process_wall_seconds": round(wall2, 2)},
    }), flush=True)


if __name__ == "__main__":
    main()
