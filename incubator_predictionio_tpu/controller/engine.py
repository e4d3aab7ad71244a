"""Engine — binds DASE component classes with their parameters.

Reference: core/.../controller/Engine.scala (class maps + train/eval
composition), EngineParams, SimpleEngine, EngineFactory.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Mapping, Optional, Sequence, Type

from ..common import deadline, faultinject, telemetry
from .algorithm import Algorithm
from .base import SanityCheck, doer
from .datasource import DataSource
from .preparator import IdentityPreparator, Preparator
from .serving import FirstServing, Serving

log = logging.getLogger("pio.engine")

# Per-query serving-stage latency (featurize = Serving.supplement query
# massage, predict = every algorithm's device dispatch, serve = result
# blend). Children pre-bound at import so the hot path pays one dict-get
# nothing, just an observe. The batched path records the same stages
# once per coalesced batch under batched="1".
_STAGE_SECONDS = telemetry.registry().histogram(
    "pio_query_stage_seconds",
    "Per-query serving stage latency by stage "
    "(featurize/predict/serve); batched=1 rows are one observation "
    "per micro-batch dispatch",
    ("stage", "batched"))
_ST_FEATURIZE = _STAGE_SECONDS.labels("featurize", "0")
_ST_PREDICT = _STAGE_SECONDS.labels("predict", "0")
_ST_SERVE = _STAGE_SECONDS.labels("serve", "0")
_ST_FEATURIZE_B = _STAGE_SECONDS.labels("featurize", "1")
_ST_PREDICT_B = _STAGE_SECONDS.labels("predict", "1")
_ST_SERVE_B = _STAGE_SECONDS.labels("serve", "1")


def _as_class_map(spec) -> dict[str, Type]:
    """Accept a single class or a {name: class} map (reference: Engine
    constructors take either; single class registers under "")."""
    if spec is None:
        return {}
    if isinstance(spec, Mapping):
        return dict(spec)
    return {"": spec}


@dataclasses.dataclass
class EngineParams:
    """Per-component parameter selection (reference: EngineParams).

    ``algorithm_params_list`` is a list of (name, params_dict) pairs —
    multiple algorithms blend through Serving.
    """

    data_source_params: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    preparator_params: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    algorithm_params_list: Sequence[tuple[str, Mapping[str, Any]]] = dataclasses.field(
        default_factory=list
    )
    serving_params: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    data_source_name: str = ""
    preparator_name: str = ""
    serving_name: str = ""

    @staticmethod
    def from_json(obj: Mapping[str, Any]) -> "EngineParams":
        """Parse the engine.json "params-style" dict:
        {"datasource": {"params": {...}}, "algorithms": [{"name": ...,
        "params": {...}}], ...} (reference: WorkflowUtils.getParamsFromJsonByFieldAndClass)."""

        def unwrap(block):
            if block is None:
                return "", {}
            if "params" in block or "name" in block:
                return block.get("name", ""), block.get("params", {}) or {}
            return "", block

        ds_name, ds_params = unwrap(obj.get("datasource"))
        p_name, p_params = unwrap(obj.get("preparator"))
        s_name, s_params = unwrap(obj.get("serving"))
        algos = []
        for a in obj.get("algorithms", []) or []:
            algos.append((a.get("name", ""), a.get("params", {}) or {}))
        return EngineParams(
            data_source_params=ds_params,
            preparator_params=p_params,
            algorithm_params_list=algos,
            serving_params=s_params,
            data_source_name=ds_name,
            preparator_name=p_name,
            serving_name=s_name,
        )

    def to_json(self) -> dict[str, Any]:
        return {
            "datasource": {"name": self.data_source_name, "params": dict(self.data_source_params)},
            "preparator": {"name": self.preparator_name, "params": dict(self.preparator_params)},
            "algorithms": [
                {"name": n, "params": dict(p)} for n, p in self.algorithm_params_list
            ],
            "serving": {"name": self.serving_name, "params": dict(self.serving_params)},
        }


class Engine:
    """Reference: controller/Engine.scala. Composes DASE for train/eval."""

    def __init__(
        self,
        data_source_class,
        preparator_class=None,
        algorithm_class_map=None,
        serving_class=None,
    ):
        self.data_source_class_map = _as_class_map(data_source_class)
        self.preparator_class_map = _as_class_map(preparator_class or IdentityPreparator)
        self.algorithm_class_map = _as_class_map(algorithm_class_map)
        self.serving_class_map = _as_class_map(serving_class or FirstServing)

    # -- component instantiation -----------------------------------------
    def _pick(self, class_map: dict[str, Type], name: str, what: str) -> Type:
        if name in class_map:
            return class_map[name]
        if not name and len(class_map) == 1:
            return next(iter(class_map.values()))
        raise KeyError(
            f"{what} {name!r} not registered; available: {sorted(class_map)}"
        )

    def make_components(self, engine_params: EngineParams):
        ds = doer(
            self._pick(self.data_source_class_map, engine_params.data_source_name, "datasource"),
            engine_params.data_source_params,
        )
        prep = doer(
            self._pick(self.preparator_class_map, engine_params.preparator_name, "preparator"),
            engine_params.preparator_params,
        )
        algo_list = [
            (
                name,
                doer(self._pick(self.algorithm_class_map, name, "algorithm"), params),
            )
            for name, params in (engine_params.algorithm_params_list or [("", {})])
        ]
        serving = doer(
            self._pick(self.serving_class_map, engine_params.serving_name, "serving"),
            engine_params.serving_params,
        )
        return ds, prep, algo_list, serving

    @staticmethod
    def _maybe_sanity_check(obj, label: str, enabled: bool,
                            nan_guard: bool = False) -> None:
        if enabled and isinstance(obj, SanityCheck):
            log.info("sanity check: %s", label)
            obj.sanity_check()
        if nan_guard:
            from ..common.nan_guard import check_finite

            check_finite(obj, label)

    # -- training (reference: Engine.train) -------------------------------
    def train(self, ctx, engine_params: EngineParams, workflow_params=None) -> list[Any]:
        from ..workflow.workflow_params import WorkflowParams

        wp = workflow_params or WorkflowParams()
        # Single source of truth: algorithms read flags (nan_guard,
        # resume) from ctx.workflow_params — sync it even when callers
        # bypass run_train and invoke Engine.train directly.
        ctx.workflow_params = wp
        ds, prep, algo_list, _ = self.make_components(engine_params)

        with telemetry.span("dase.read"):
            td = ds.read_training(ctx)
            self._maybe_sanity_check(td, "datasource",
                                     not wp.skip_sanity_check, wp.nan_guard)
        if wp.stop_after_read:
            log.info("--stop-after-read: halting before prepare")
            return []
        with telemetry.span("dase.prepare"):
            pd = prep.prepare(ctx, td)
            self._maybe_sanity_check(pd, "preparator",
                                     not wp.skip_sanity_check, wp.nan_guard)
        if wp.stop_after_prepare:
            log.info("--stop-after-prepare: halting before train")
            return []
        models = []
        root_hook = getattr(ctx, "checkpoint_hook", None)
        if root_hook is not None:
            import os

            from ..workflow.checkpoint import CheckpointHook
        for idx, (name, algo) in enumerate(algo_list):
            log.info("training algorithm %s (%s)", name or "<default>", type(algo).__name__)
            # Stage label for error attribution inside iterative trainers
            # (e.g. train_als' per-iteration NaN guard).
            ctx.stage_label = f"algorithm[{name or 'default'}]"
            if root_hook is not None:
                # Per-algorithm subdirectory: without it, multiple
                # algorithms in one engine would collide on orbax step
                # numbers and restore each other's snapshots.
                ctx.checkpoint_hook = CheckpointHook(
                    os.path.join(root_hook.directory, f"algo_{idx}_{name or 'default'}"),
                    every_n=root_hook.every_n,
                    max_to_keep=root_hook.max_to_keep,
                )
            try:
                # cost-based placement (--device=auto): _train_placed
                # swaps the mesh for this stage and restores it on every
                # exit path (workflow/placement.py)
                with telemetry.span("dase.algo_train",
                                    algorithm=name or "default"):
                    model = self._train_placed(
                        ctx, algo, name, pd, getattr(wp, "device", "auto"))
                    self._maybe_sanity_check(
                        model, f"algorithm[{name or 'default'}]",
                        not wp.skip_sanity_check, wp.nan_guard)
            finally:
                if root_hook is not None:
                    ctx.checkpoint_hook.close()
                    ctx.checkpoint_hook = root_hook
            models.append(model)
        return models

    # -- evaluation (reference: Engine.eval) ------------------------------
    def _train_placed(self, ctx, algo, name: str, pd, device_mode: str):
        """One algorithm train under cost-based placement (the same
        mesh swap Engine.train applies — eval sweeps train many
        candidates, so a mis-placed transfer-bound stage costs per
        candidate, not once)."""
        from ..workflow.placement import mesh_for_stage

        try:
            sm = algo.stage_model(pd)
        except Exception:  # noqa: BLE001 - sizing must never kill training
            log.exception("stage_model failed; using configured mesh")
            sm = None
        prev_mesh = ctx.mesh
        try:
            ctx.mesh = mesh_for_stage(
                ctx, sm, device_mode, f"algorithm[{name or 'default'}]")
            return algo.train(ctx, pd)
        finally:
            ctx.mesh = prev_mesh

    def eval(self, ctx, engine_params: EngineParams, workflow_params=None):
        """Per-fold: train on fold TD, batch-predict fold queries.
        Yields (eval_info, [(query, predicted, actual), ...]) per fold."""
        device_mode = getattr(workflow_params, "device", None) or getattr(
            getattr(ctx, "workflow_params", None), "device", "auto")
        ds, prep, algo_list, serving = self.make_components(engine_params)
        results = []
        for fold_i, (td, eval_info, qa) in enumerate(ds.read_eval(ctx)):
            pd = prep.prepare(ctx, td)
            models = [self._train_placed(ctx, algo, name, pd, device_mode)
                      for name, algo in algo_list]
            qa = list(qa)
            queries = [serving.supplement(q) for q, _ in qa]
            per_algo = [
                algo.batch_predict(models[i], queries)
                for i, (_, algo) in enumerate(algo_list)
            ]
            qpa = [
                (q, serving.serve(q, [pred[j] for pred in per_algo]), a)
                for j, (q, a) in enumerate(qa)
            ]
            results.append((eval_info, qpa))
            log.info("eval fold %d: %d query/actual pairs", fold_i, len(qpa))
        return results

    # -- deployment (reference: Engine.prepareDeployment path) ------------
    def prepare_deployment(self, ctx, engine_params: EngineParams, models: list[Any]):
        """Re-bind stored models to live algorithm instances for serving."""
        _, _, algo_list, serving = self.make_components(engine_params)
        if len(models) != len(algo_list):
            raise ValueError(
                f"{len(models)} stored models but {len(algo_list)} algorithms"
            )
        restored = [
            algo.restore_model(m, ctx) for (_, algo), m in zip(algo_list, models)
        ]
        return Deployment(self, algo_list, restored, serving)


class Deployment:
    """Live serving bundle: algorithms + restored models + serving."""

    def __init__(self, engine: Engine, algo_list, models, serving: Serving):
        self.engine = engine
        self.algo_list = algo_list
        self.models = models
        self.serving = serving

    def query(self, q) -> Any:
        # Stage telemetry: one span per stage (a child of the request's
        # root: the open span propagates through the copied context into
        # the executor thread) and the same duration into the stage
        # histogram. Each stage opens with a chaos fault point
        # (latency/hang/fail injection on the serving path, the overload
        # harness's slow-model lever) and a deadline spend-point: a
        # worker thread past its request's budget frees itself at the
        # next stage boundary instead of finishing work for a client
        # that already got 504.
        dl = deadline.current()
        with telemetry.span("query.featurize") as sp:
            faultinject.fault_point("query.featurize")
            q = self.serving.supplement(q)
        _ST_FEATURIZE.observe_raw(sp.dur_ns)
        with telemetry.span("query.predict",
                            algorithms=len(self.algo_list)) as sp:
            if dl is not None:
                dl.check("query.predict")
            faultinject.fault_point("query.predict")
            predictions = [
                algo.predict(model, q)
                for (_, algo), model in zip(self.algo_list, self.models)
            ]
        _ST_PREDICT.observe_raw(sp.dur_ns)
        with telemetry.span("query.serve") as sp:
            if dl is not None:
                dl.check("query.serve")
            faultinject.fault_point("query.serve")
            result = self.serving.serve(q, predictions)
        _ST_SERVE.observe_raw(sp.dur_ns)
        return result

    def batch_query(self, queries) -> list[Any]:
        """Vectorized multi-query path (one device dispatch per
        algorithm instead of one per query) — used by the engine
        server's micro-batching window and `pio batchpredict`."""
        # One fault point per coalesced dispatch (not per query): a
        # latency injection here models ONE slow vectorized forward,
        # exactly what a wedged device queue looks like to the batcher.
        # No deadline spend-points — a batch mixes requests with
        # different budgets; expiry is enforced per-request at the
        # future level by the admission gate.
        t0 = telemetry.timer_start()
        faultinject.fault_point("query.batch_predict")
        qs = [self.serving.supplement(q) for q in queries]
        t1 = time.perf_counter_ns() if t0 else 0
        _ST_FEATURIZE_B.observe_since(t0)
        per_algo = [
            algo.batch_predict(model, qs)
            for (_, algo), model in zip(self.algo_list, self.models)
        ]
        t2 = time.perf_counter_ns() if t0 else 0
        _ST_PREDICT_B.observe_since(t1)
        out = [
            self.serving.serve(q, [pred[j] for pred in per_algo])
            for j, q in enumerate(qs)
        ]
        _ST_SERVE_B.observe_since(t2)
        return out


class SimpleEngine(Engine):
    """Reference: SimpleEngine — one DataSource + one Algorithm, identity
    preparator, first serving."""

    def __init__(self, data_source_class, algorithm_class):
        super().__init__(
            data_source_class,
            IdentityPreparator,
            {"": algorithm_class},
            FirstServing,
        )


class EngineFactory:
    """Reference: EngineFactory trait — ``apply()`` returns an Engine.
    Subclass and override apply(), or pass a plain function returning an
    Engine wherever a factory is accepted."""

    def apply(self) -> Engine:
        raise NotImplementedError

    def __call__(self) -> Engine:
        return self.apply()
