"""Traffic kind ``retrain``. Set-up: one run_train at the mix's warm-up
iterations on the data of seed + offset (same plan, same executable). Window:
whole run_train calls on the data of --seed at the configuration's
numIterations, another started while less than --seconds have passed.

Of the deployment it calls ``engine``, ``engine_params``, ``train_inputs``,
``release``, ``spans`` and ``check_retrain``."""

from __future__ import annotations

import gc
import time

import run as bench


def phases(record, workdir: str, deployment) -> dict:
    import jax

    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.core_workflow import (
        load_deployment, run_train,
    )

    cfg, traffic = record.config, record.traffic
    storage = bench.make_storage()
    engine, factory = deployment.engine("retrain")

    def train(key: str, iters: int | None = None) -> str:
        return run_train(engine, deployment.engine_params(cfg, key, iters),
                         WorkflowContext(storage=storage),
                         engine_factory_name=factory)

    key, warm_key = deployment.train_inputs(
        cfg, [record.seed, record.seed + int(traffic["warmup_seed_offset"])],
        bench.say)
    undo = [bench.wrap_span(record, *target) for target in
            (deployment.spans("retrain") if record.traced else ())]
    models = storage.get_model_data_models()
    warm_id = train(warm_key, int(traffic["warmup_iterations"]))
    models.delete(warm_id)
    deployment.release(warm_key)
    record.spans.clear()
    gc.collect()

    def window() -> dict:
        ids = []
        t0 = time.perf_counter()
        while True:
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:run_train"):
                ids.append(train(key))
            t2 = time.perf_counter()
            record.add_span("run_train", t1, t2)
            if t2 - t0 >= record.seconds:
                return {"instance_ids": ids, "wall_s": t2 - t0,
                        "trains": len(ids)}

    def after_window(win: dict) -> None:
        for old in win["instance_ids"][:-1]:
            models.delete(old)
        if record.traced:
            # the profiler covers ONE extra run_train at the warm-up's
            # iterations: a whole train's trace does not come back under
            # the cap
            with bench.Tracer(record, workdir):
                with jax.profiler.TraceAnnotation("bench:run_train"):
                    extra = train(key, int(traffic["warmup_iterations"]))
            models.delete(extra)
            record.window["traced_iterations"] = int(
                traffic["warmup_iterations"])
        for u in undo:
            u()

    def check(win: dict) -> dict:
        def persisted_models():
            dep, _inst, _ = load_deployment(
                engine, win["instance_ids"][-1],
                WorkflowContext(storage=storage), engine_factory_name=factory)
            return dep.models

        return deployment.check_retrain(cfg, key, persisted_models, bench.say)

    return {"window": window, "after_window": after_window, "check": check,
            "end_to_end": lambda win: {
                "retrain_s": (win["wall_s"] / win["trains"], "s")},
            "attempted": lambda win: (win["trains"], 0)}
