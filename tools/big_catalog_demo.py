"""Sharded-serving capability demo: a catalog BIGGER than one v5e HBM
answers /queries.json (VERDICT r4 next #1 "done" criterion).

Builds a synthetic ALS model with an item-factor matrix that cannot fit
one v5e chip's 16 GiB HBM (default: 36M items x rank 128 f32 = 18.4 GiB),
deploys it through the REAL EngineServer with shardedServing=always over
an 8-device mesh (virtual CPU here; the same program shards over ICI on
a pod slice), and serves live HTTP queries — per-shard top-k +
k-candidate all_gather, never a full score row.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      JAX_PLATFORMS=cpu python tools/big_catalog_demo.py
Needs ~45 GB host RAM at the default size; PIO_DEMO_ITEMS scales it.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax

    from incubator_predictionio_tpu.controller import Engine, EngineParams
    from incubator_predictionio_tpu.data.storage.bimap import BiMap, IdentityBiMap
    from incubator_predictionio_tpu.models.recommendation import (
        ALSModel, RecommendationDataSource, ALSAlgorithm,
    )
    from incubator_predictionio_tpu.ops.als import ALSFactors

    n_items = int(os.environ.get("PIO_DEMO_ITEMS", 36_000_000))
    rank = int(os.environ.get("PIO_DEMO_RANK", 128))
    n_users = 1000
    gib = n_items * rank * 4 / 2**30
    print(f"[demo] catalog: {n_items:,} items x rank {rank} = {gib:.1f} GiB "
          f"(one v5e HBM = 16 GiB) over {len(jax.devices())} devices")

    t0 = time.time()
    rng = np.random.default_rng(0)
    # generate in slices to keep peak RAM = catalog + one slice
    item_factors = np.empty((n_items, rank), np.float32)
    step = 4_000_000
    for lo in range(0, n_items, step):
        hi = min(lo + step, n_items)
        item_factors[lo:hi] = rng.standard_normal(
            (hi - lo, rank), dtype=np.float32)
    user_factors = rng.standard_normal((n_users, rank), dtype=np.float32)
    print(f"[demo] host factors built in {time.time()-t0:.1f}s")

    model = ALSModel(
        factors=ALSFactors(user_factors, item_factors, n_users, n_items),
        users=BiMap({str(j): j for j in range(n_users)}),
        items=IdentityBiMap(n_items),
    )
    engine = Engine(data_source_class=RecommendationDataSource,
                    algorithm_class_map={"als": ALSAlgorithm})
    ep = EngineParams.from_json({
        "datasource": {"params": {"appName": "demo"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": rank,
                                   "shardedServing": "always"}}],
    })

    class Ctx:
        workflow_params = type("WP", (), {"resume": False,
                                          "nan_guard": False})()

        def get_mesh(self):
            from incubator_predictionio_tpu.parallel.mesh import default_mesh

            return default_mesh()

        def get_storage(self):
            return None

    t0 = time.time()
    dep = engine.prepare_deployment(Ctx(), ep, [model])
    m = dep.models[0]
    assert m.serving_mesh is not None, "expected a sharded deployment"
    cat = m.sharded_catalog()
    per_shard = cat.dev.shape[0] // cat.n_shards * cat.rank * 4 / 2**30
    print(f"[demo] sharded catalog resident in {time.time()-t0:.1f}s: "
          f"{cat.n_shards} shards x {per_shard:.1f} GiB "
          f"(spec {cat.dev.sharding.spec})")

    # serve over real HTTP
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tests"))
    import requests
    from server_utils import ServerThread
    from incubator_predictionio_tpu.workflow.create_server import EngineServer

    server = EngineServer.__new__(EngineServer)  # bypass storage-backed load
    import threading

    from aiohttp import web

    server.deployment = dep
    server.instance = None
    server.plugins = __import__(
        "incubator_predictionio_tpu.workflow.plugins",
        fromlist=["EngineServerPluginContext"]).EngineServerPluginContext()
    server._lock = threading.Lock()
    server._query_count = 0
    server.feedback = False
    server._batch_queue = None
    # arm the admission gate (handle_query routes through it); huge
    # sharded queries run seconds each, so no deadline budget here
    server._init_overload_state(query_deadline_ms=0)
    server.app = web.Application()
    server.app.add_routes([web.post("/queries.json", server.handle_query)])

    with ServerThread(server.app) as st:
        for user in ("1", "7", "999"):
            t0 = time.time()
            r = requests.post(st.base + "/queries.json",
                              json={"user": user, "num": 5}, timeout=600)
            dt = time.time() - t0
            assert r.status_code == 200, r.text
            scores = r.json()["itemScores"]
            assert len(scores) == 5
            assert scores[0]["score"] >= scores[-1]["score"]
            print(f"[demo] /queries.json user={user}: top item "
                  f"{scores[0]['item']} score {scores[0]['score']:.3f} "
                  f"({dt:.2f}s over {gib:.0f} GiB sharded catalog)")
    print(json.dumps({"demo": "sharded-serving-beyond-one-hbm",
                      "items": n_items, "rank": rank, "gib": round(gib, 1),
                      "shards": cat.n_shards, "ok": True}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
