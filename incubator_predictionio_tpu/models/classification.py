"""Classification template (attribute-based classifier).

Reference: examples/scala-parallel-classification + upstream
predictionio-template-attribute-based-classifier (SURVEY.md §2.8 row 2):
$set events carry numeric attributes + a "plan" label on "user" entities;
MLlib NaiveBayes (variant: LogisticRegressionWithLBFGS) trains on
LabeledPoints; query = attribute vector → predicted label.

TPU-native: aggregateProperties → dense [N,D] feature matrix;
ops/linear kernels (mesh-sharded stats / L-BFGS).

Wire format (template parity):
  query  {"attr0": 2, "attr1": 0, "attr2": 0}
  result {"label": 1.0}
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from ..controller import (
    Algorithm,
    DataSource,
    Engine,
    EngineFactory,
    Params,
    SanityCheck,
)
from ..data.store.p_event_store import PEventStore
from ..ops.linear import (
    LogisticRegressionModel,
    NaiveBayesModel,
    lr_sgd_steps,
    nb_fold_in,
    train_logistic_regression,
    train_logistic_regression_process_local,
    train_naive_bayes,
    train_naive_bayes_process_local,
)
from ..workflow.input_pipeline import pipeline_of as _pipeline_of


@dataclasses.dataclass
class TrainingData(SanityCheck):
    features: np.ndarray  # [N, D] f32
    labels: np.ndarray  # [N] int32
    attribute_names: Sequence[str]
    label_values: np.ndarray  # class index → original label value
    #: True when features/labels hold only THIS gang worker's strided
    #: entity slice (workflow/train_feed.py) while label_values is the
    #: allgathered GLOBAL class vocabulary — trainers must all-reduce.
    partition_local: bool = False
    #: gang-wide labeled-entity count (== len(features) when not
    #: partition-local).
    n_global: int = -1

    def sanity_check(self):
        n = (self.n_global if self.partition_local
             else len(self.features))
        assert n > 0, "no labeled entities found"
        assert len(self.features) == len(self.labels)


PreparedData = TrainingData


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    entity_type: str = "user"
    attributes: Sequence[str] = ("attr0", "attr1", "attr2")
    label: str = "plan"


class ClassificationDataSource(DataSource):
    params_cls = DataSourceParams
    params_aliases = {"appName": "app_name", "entityType": "entity_type"}

    def read_training(self, ctx) -> TrainingData:
        p: DataSourceParams = self.params
        app_name = p.app_name or ctx.app_name
        storage = ctx.get_storage()
        from ..workflow import train_feed

        if train_feed.partition_feed_active(storage):
            # gang data plane: per-partition $set replays allgathered
            # as derived aggregates; this worker keeps its strided
            # entity slice for the data-parallel trainers
            feats, y, label_values, n_global = \
                train_feed.partition_examples(
                    app_name, p.entity_type, list(p.attributes),
                    p.label, storage=storage,
                    channel_name=ctx.channel_name)
            return TrainingData(
                features=feats, labels=y,
                attribute_names=tuple(p.attributes),
                label_values=label_values,
                partition_local=True, n_global=n_global)
        props = PEventStore.aggregate_properties(
            app_name,
            p.entity_type,
            channel_name=ctx.channel_name,
            required=list(p.attributes) + [p.label],
            storage=ctx.get_storage(),
        )
        feats, labels = [], []
        for _eid, pm in props.items():
            feats.append([float(pm.require(a)) for a in p.attributes])
            labels.append(pm.require(p.label))
        label_values, y = np.unique(np.asarray(labels), return_inverse=True)
        return TrainingData(
            features=np.asarray(feats, np.float32),
            labels=y.astype(np.int32),
            attribute_names=tuple(p.attributes),
            label_values=label_values,
        )

    def read_eval(self, ctx):
        from ..e2.cross_validation import k_fold_indices

        td = self.read_training(ctx)
        folds = []
        for train_sel, test_sel in k_fold_indices(len(td.labels), k=3, seed=1):
            train = TrainingData(
                td.features[train_sel], td.labels[train_sel],
                td.attribute_names, td.label_values,
            )
            queries = [
                (
                    dict(zip(td.attribute_names, td.features[j].tolist())),
                    {"label": float(td.label_values[td.labels[j]])},
                )
                for j in np.nonzero(test_sel)[0]
            ]
            folds.append((train, None, queries))
        return folds


@dataclasses.dataclass
class ClassifierModel:
    inner: object  # NaiveBayesModel | LogisticRegressionModel
    attribute_names: Sequence[str]
    label_values: np.ndarray
    # Per-entity memory of the example a streamed fold-in increment
    # last contributed (entityId -> (features tuple, class index)): a
    # re-$set REPLACES that example in the NB sufficient statistics
    # instead of stacking a duplicate. None on trained/legacy models
    # (populated by the first increment). Entities that existed at
    # TRAIN time are not individually recoverable from the aggregated
    # training read, so their first streamed update adds one extra
    # example — bounded, unlike the unbounded drift of re-counting
    # every update.
    foldin_seen: Optional[dict] = None

    def predict_label(self, features: np.ndarray) -> float:
        x = np.asarray(features, np.float32)[None, :]
        if isinstance(self.inner, NaiveBayesModel):
            scores = self.inner.predict_log_joint(x)[0]
        else:
            scores = self.inner.predict_logits(x)[0]
        return float(self.label_values[int(np.argmax(scores))])


@dataclasses.dataclass(frozen=True)
class NaiveBayesParams(Params):
    # MLlib NaiveBayes additive smoothing; template engine.json: {"lambda": 1.0}
    smoothing: float = 1.0


def _wire_bytes(features: "np.ndarray") -> int:
    """Bytes this feature matrix actually crosses the link as: the NB/LR
    trainers upload the narrowest LOSSLESS dtype (uint8 for small
    nonneg integer counts, bf16 when exactly representable — the SAME
    gates ops/linear.py applies). The placement stage model must price
    THOSE bytes on the device side (pricing f32 overstated the link 4x
    and mis-routed LR off the chip: measured 879k CPU vs 2.7M
    on-device) while the CPU side streams the full f32 width
    (host_bytes) — the narrowing is a TPU-upload feature."""
    x8 = features.astype(np.uint8)
    if np.array_equal(x8.astype(np.float32), features):
        return x8.nbytes
    import jax.numpy as jnp

    xb = features.astype(jnp.bfloat16)  # real bf16 gate, not an f16 proxy
    if np.array_equal(np.asarray(xb, np.float32), features):
        return features.size * 2
    return features.nbytes


#: Cap on ClassifierModel.foldin_seen — see the field comment.
FOLDIN_SEEN_MAX = 100_000


def _foldin_examples(events, data_source_params, model: ClassifierModel):
    """New labeled examples from tailed $set events, mapped with the
    SAME entity-type/attributes/label config training read. Only
    COMPLETE events (every attribute + the label in one $set — the
    template's import shape) fold in O(new events); partial property
    updates would need a full aggregate replay and are skipped with a
    debug note. Labels outside the trained class set are skipped too —
    a new class needs a retrain (the model's output width is fixed)."""
    dsp = dict(data_source_params or {})
    entity_type = dsp.get("entity_type", dsp.get("entityType", "user"))
    attrs = list(dsp.get("attributes") or model.attribute_names)
    label = dsp.get("label", "plan")
    label_of = {float(v): j for j, v in
                enumerate(np.asarray(model.label_values, np.float64))}
    latest: dict = {}
    for e in events:
        if not isinstance(e, dict) or e.get("event") != "$set":
            continue
        if e.get("entityType") != entity_type or not e.get("entityId"):
            continue
        props = e.get("properties") or {}
        try:
            x = [float(props[a]) for a in attrs]
            y = label_of[float(props[label])]
        except (KeyError, TypeError, ValueError):
            continue    # partial $set or unseen label: skip (docstring)
        latest[e["entityId"]] = (x, y)   # last $set per entity wins
    if not latest:
        return None, None, None
    ids = list(latest)
    xs = [latest[i][0] for i in ids]
    ys = [latest[i][1] for i in ids]
    return (ids, np.asarray(xs, np.float32), np.asarray(ys, np.int64))


class NaiveBayesAlgorithm(Algorithm):
    params_cls = NaiveBayesParams
    params_aliases = {"lambda": "smoothing"}

    def stage_model(self, pd: PreparedData):
        """One pass of sufficient stats over [N, D] — transfer-bound
        where the host→device link is slow; --device=auto prices it."""
        from ..workflow.placement import StageModel

        return StageModel(bytes_to_device=_wire_bytes(pd.features),
                          device_passes=1.0,
                          host_bytes=pd.features.nbytes, cpu_passes=1.0)

    def train(self, ctx, pd: PreparedData) -> ClassifierModel:
        if getattr(pd, "partition_local", False):
            # partition-local gang feed: stats psum across the gang
            model = train_naive_bayes_process_local(
                pd.features, pd.labels,
                n_classes=len(pd.label_values),
                smoothing=self.params.smoothing,
                mesh=ctx.get_mesh() if ctx else None,
            )
        else:
            model = train_naive_bayes(
                pd.features, pd.labels, n_classes=len(pd.label_values),
                smoothing=self.params.smoothing,
                mesh=ctx.get_mesh() if ctx else None,
                pipeline=_pipeline_of(ctx),
            )
        return ClassifierModel(model, pd.attribute_names, pd.label_values)

    def predict(self, model: ClassifierModel, query: dict) -> dict:
        x = np.asarray(
            [float(query[a]) for a in model.attribute_names], np.float32
        )
        return {"label": model.predict_label(x)}

    def fold_in(self, model: ClassifierModel, events, ctx,
                data_source_params=None):
        """EXACT incremental NB (ops.linear.nb_fold_in): the stored
        sufficient statistics plus the new examples' counts rebuild
        the log params exactly as a retrain on the updated example set
        would — an entity a PRIOR increment added is REPLACED (its old
        example's counts subtracted), not double-counted; see the
        ``foldin_seen`` field note for train-time entities."""
        ids, x, y = _foldin_examples(events, data_source_params, model)
        if x is None:
            return None
        seen = dict(getattr(model, "foldin_seen", None) or {})
        x_rm, y_rm = [], []
        for eid in ids:
            prev = seen.get(eid)
            if prev is not None:
                x_rm.append(prev[0])
                y_rm.append(prev[1])
        inner = nb_fold_in(model.inner, x, y,
                           x_remove=np.asarray(x_rm, np.float32)
                           if x_rm else None,
                           y_remove=np.asarray(y_rm, np.int64)
                           if y_rm else None)
        if inner is None:
            import logging

            logging.getLogger("pio.foldin").warning(
                "NB fold-in declined: model carries no sufficient "
                "statistics (pre-upgrade blob) — retrain once to "
                "enable online updates")
            return None
        for eid, xi, yi in zip(ids, x, y):
            seen.pop(eid, None)   # re-insert = move to freshest
            seen[eid] = (tuple(float(v) for v in xi), int(yi))
        # bounded: the map rides inside every published artifact, so
        # unbounded growth would inflate each increment's serialize/
        # checksum/validate cost with the distinct-entity count.
        # Evicted (oldest-updated) entities degrade to the train-time
        # rule — their NEXT update adds one extra example once.
        while len(seen) > FOLDIN_SEEN_MAX:
            seen.pop(next(iter(seen)))
        return ClassifierModel(inner, model.attribute_names,
                               model.label_values, foldin_seen=seen)


@dataclasses.dataclass(frozen=True)
class LogisticRegressionParams(Params):
    reg: float = 0.0
    max_iters: int = 100


class LogisticRegressionAlgorithm(Algorithm):
    params_cls = LogisticRegressionParams
    params_aliases = {"regParam": "reg", "maxIterations": "max_iters"}

    def stage_model(self, pd: PreparedData):
        """L-BFGS passes over resident [N, D]: upload once, iterate on
        device vs iterate on host (same jitted program either way).

        cpu_passes carries a measured 10x compute-intensity factor: the
        host probe prices STREAMING bytes, but each L-BFGS iteration's
        softmax/grad work runs ~1.4 GB/s on this class of core (measured
        847k ev/s actual vs a ~10M prediction without the factor —
        under-pricing CPU routed LR off the chip and LOST 3x)."""
        from ..workflow.placement import StageModel

        iters = float(self.params.max_iters)
        return StageModel(bytes_to_device=_wire_bytes(pd.features),
                          device_passes=iters,
                          host_bytes=pd.features.nbytes,
                          cpu_passes=iters * 10.0)

    def train(self, ctx, pd: PreparedData) -> ClassifierModel:
        if getattr(pd, "partition_local", False):
            # partition-local gang feed: per-step gradient psum across
            # the gang (synchronous data parallelism)
            model = train_logistic_regression_process_local(
                pd.features, pd.labels,
                n_classes=len(pd.label_values),
                reg=self.params.reg, max_iters=self.params.max_iters,
                mesh=ctx.get_mesh() if ctx else None,
            )
        else:
            model = train_logistic_regression(
                pd.features, pd.labels, n_classes=len(pd.label_values),
                reg=self.params.reg, max_iters=self.params.max_iters,
                mesh=ctx.get_mesh() if ctx else None,
                pipeline=_pipeline_of(ctx),
            )
        return ClassifierModel(model, pd.attribute_names, pd.label_values)

    predict = NaiveBayesAlgorithm.predict

    def fold_in(self, model: ClassifierModel, events, ctx,
                data_source_params=None):
        """Online SGD (ops.linear.lr_sgd_steps): a few gradient steps
        over the new examples nudge the warm weights — the streaming
        approximation of the L-BFGS re-solve a retrain would run
        (gradient steps are inherently additive; no per-entity
        replacement bookkeeping applies)."""
        _ids, x, y = _foldin_examples(events, data_source_params, model)
        if x is None:
            return None
        inner = lr_sgd_steps(model.inner, x, y, reg=self.params.reg)
        if inner is None:
            return None
        return ClassifierModel(inner, model.attribute_names,
                               model.label_values)


class ClassificationEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            data_source_class=ClassificationDataSource,
            algorithm_class_map={
                "naive": NaiveBayesAlgorithm,
                "lr": LogisticRegressionAlgorithm,
                "": NaiveBayesAlgorithm,
            },
        )
