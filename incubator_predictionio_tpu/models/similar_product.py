"""Similar-Product template.

Reference: predictionio-template-similar-product (SURVEY.md §2.8 row 3):
"view" events → MLlib ALS.trainImplicit; serving returns top-k items
cosine-similar to the query items' factor vectors, with
whitelist/blacklist/category business-rule filters.

TPU-native: implicit ALS via ops.als; item-item cosine top-k on device
(ops.topk.similar_items); category metadata from aggregated $set events.

Wire format (template parity):
  query  {"items": ["i1"], "num": 4, "categories": ["c"],
          "whiteList": [...], "blackList": [...]}
  result {"itemScores": [{"item": ..., "score": ...}]}
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from ..common import telemetry
from ..controller import Algorithm, DataSource, Engine, EngineFactory, Params, SanityCheck
from ..data.storage.bimap import BiMap
from ..data.store.p_event_store import PEventStore
from ..ops.als import (
    ALSFactors, ALSParams, train_als, train_als_partition_local,
)
from ..workflow.input_pipeline import pipeline_of
from ..ops.topk import normalize_rows
from ._sharded_serving import (
    ShardedCatalogServing,
    serving_mesh_for,
    validate_serving_mode,
)
from ._filters import CategoryIndex, build_exclude_mask


@dataclasses.dataclass
class TrainingData(SanityCheck):
    user_idx: np.ndarray
    item_idx: np.ndarray
    rating: np.ndarray  # implicit strength (view counts)
    users: BiMap
    items: BiMap
    item_categories: dict[str, set[str]]  # item id → categories
    #: True when the triple holds only THIS gang worker's event-log
    #: partitions (workflow/train_feed.py); users/items are the global
    #: allgathered maps and the trainer must all-reduce.
    partition_local: bool = False

    def sanity_check(self):
        if self.partition_local:
            assert len(self.users) > 0, "no view events found"
        else:
            assert len(self.user_idx) > 0, "no view events found"


PreparedData = TrainingData


def count_pairs(user_idx: np.ndarray, item_idx: np.ndarray,
                rating: np.ndarray, n_items: int):
    """The events of each (user, item) pair summed into ONE entry: its
    value is the sum of the events' ratings (1.0 an event: the number of
    views). Reference: the template's ``ALSAlgorithm`` maps a view to
    ``((user, item), 1)`` and ``reduceByKey(_ + _)`` before
    ``ALS.trainImplicit``, so a pair viewed r times has confidence
    ``1 + alpha r`` and right-hand side ``(1 + alpha r) y``; r entries of
    1.0 would give the same gram and ``r (1 + alpha) y``.

    One stable sort of int64 keys; the pairs stay in the order in which
    each was FIRST seen, and rows are not renumbered, so the ``BiMap``s
    stand. Events without a repeated pair come back as they are (all-ones
    data still trains through ``binary_ratings``). Span
    ``prep.pair_counts`` (tags ``events``, ``pairs``)."""
    with telemetry.span("prep.pair_counts", events=len(user_idx)) as sp:
        key = user_idx.astype(np.int64) * np.int64(n_items) + item_idx
        order = np.argsort(key, kind="stable")
        key = key[order]
        starts = np.ones(len(key), bool)
        starts[1:] = key[1:] != key[:-1]
        at = np.nonzero(starts)[0]
        sp.tag(pairs=len(at))
        if len(at) == len(key):
            return user_idx, item_idx, rating
        sums = np.add.reduceat(rating[order], at).astype(np.float32)
        # the stable sort puts a pair's first event first in its group
        first = order[at]
        by_first = np.argsort(first)
        first = first[by_first]
        return user_idx[first], item_idx[first], sums[by_first]


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    event_names: Sequence[str] = ("view",)
    item_entity_type: str = "item"


class SimilarProductDataSource(DataSource):
    params_cls = DataSourceParams
    params_aliases = {"appName": "app_name", "eventNames": "event_names"}

    def read_training(self, ctx) -> TrainingData:
        p: DataSourceParams = self.params
        app_name = p.app_name or ctx.app_name
        storage = ctx.get_storage()
        from ..workflow import train_feed

        if train_feed.partition_feed_active(storage):
            # gang data plane (workflow/train_feed.py): view events
            # stream partition-local; the category metadata is the
            # same allgathered property merge the classifiers use —
            # one shared shard scan feeds BOTH extractions
            feed_ctx = train_feed.open_feed(app_name, storage,
                                            ctx.channel_name)
            u, i, r, users, items = train_feed.partition_ratings(
                app_name, event_names=list(p.event_names),
                rating_from_props=False, storage=storage,
                channel_name=ctx.channel_name, feed_ctx=feed_ctx)
            # this worker's partitions only: a pair whose events lie with
            # two workers stays two entries (ROADMAP R-M14)
            u, i, r = count_pairs(u, i, r, len(items))
            cats = {
                item_id: set(c)
                for item_id, props in train_feed.partition_properties(
                    app_name, p.item_entity_type, storage=storage,
                    channel_name=ctx.channel_name,
                    feed_ctx=feed_ctx).items()
                if (c := props.get("categories"))}
            return TrainingData(u, i, r, users, items, cats,
                                partition_local=True)
        u, i, r, users, items = PEventStore.find_ratings(
            app_name,
            event_names=list(p.event_names),
            rating_from_props=False,
            storage=storage,
            channel_name=ctx.channel_name,
        )
        u, i, r = count_pairs(u, i, r, len(items))
        cats: dict[str, set[str]] = {}
        for item_id, pm in PEventStore.aggregate_properties(
            app_name, p.item_entity_type, storage=storage
        ).items():
            c = pm.get_opt("categories")
            if c:
                cats[item_id] = set(c)
        return TrainingData(u, i, r, users, items, cats)


@dataclasses.dataclass
class SimilarProductModel(ShardedCatalogServing):
    factors: ALSFactors
    items: BiMap
    item_categories: dict[str, set[str]]
    _cat_index: object = dataclasses.field(default=None, repr=False, compare=False)
    # PAlgorithm serving analog: when set, the catalog is sharded over
    # every mesh device at serve time (ops.sharded_topk).
    serving_mesh: object = dataclasses.field(default=None, repr=False, compare=False)
    _sharded_cat: object = dataclasses.field(default=None, repr=False, compare=False)

    def category_index(self) -> CategoryIndex:
        if self._cat_index is None:
            self._cat_index = CategoryIndex(self.items, self.item_categories)
        return self._cat_index

    def _host_catalog(self):
        """Cosine serving needs unit rows: normalize ONCE at deploy
        time, not per query (ops.topk.normalize_rows)."""
        return normalize_rows(self.factors.item_factors)

    def warm_up(self, num: int = 10):
        self.warm_catalog()
        if len(self.items):
            self.similar([next(iter(self.items.keys()))], num)

    def similar(
        self,
        query_items: Sequence[str],
        num: int,
        categories: Optional[Sequence[str]] = None,
        white_list: Optional[Sequence[str]] = None,
        black_list: Optional[Sequence[str]] = None,
    ):
        idxs = [self.items.get(q) for q in query_items]
        idxs = [j for j in idxs if j is not None]
        if not idxs:
            return []
        exclude = build_exclude_mask(
            self.items, self.category_index(), categories,
            white_list, black_list,
        )
        exclude[idxs] = True  # never return the query items themselves
        qvecs = self.factors.item_factors[idxs]
        scores, idx = self.catalog().similar(qvecs, num, exclude=exclude)
        return [
            (self.items.inverse(int(j)), float(s))
            for s, j in zip(scores, idx)
            if np.isfinite(s)
        ]


@dataclasses.dataclass(frozen=True)
class SimilarProductAlgoParams(Params):
    rank: int = 10
    num_iterations: int = 20
    reg: float = 0.01
    alpha: float = 1.0
    seed: Optional[int] = None
    # "auto" → bfloat16 on TPU meshes; set "float32" in engine.json to
    # reproduce pre-auto runs exactly. -1 → auto HBM-budget chunking.
    compute_dtype: str = "auto"
    chunk_tiles: int = -1
    # engine.json "shardedServing": auto|always|never (ops.sharded_topk).
    sharded_serving: str = "auto"


class SimilarProductAlgorithm(Algorithm):
    params_cls = SimilarProductAlgoParams
    params_aliases = {
        "lambda": "reg", "numIterations": "num_iterations",
        "computeDtype": "compute_dtype", "chunkTiles": "chunk_tiles",
        "shardedServing": "sharded_serving",
    }

    def train(self, ctx, pd: PreparedData) -> SimilarProductModel:
        p = self.params
        validate_serving_mode(p.sharded_serving)  # before the expensive run
        als_params = ALSParams(
            rank=p.rank, num_iterations=p.num_iterations, reg=p.reg,
            implicit_prefs=True, alpha=p.alpha,
            seed=p.seed if p.seed is not None else 3,
            compute_dtype=p.compute_dtype, chunk_tiles=p.chunk_tiles,
        )
        common = dict(
            mesh=ctx.get_mesh() if ctx else None,
            checkpoint_hook=getattr(ctx, "checkpoint_hook", None),
            resume=bool(ctx and ctx.workflow_params.resume),
            nan_guard=bool(ctx and ctx.workflow_params.nan_guard),
            nan_guard_stage=getattr(ctx, "stage_label",
                                    "algorithm[als]"),
        )
        if getattr(pd, "partition_local", False):
            # partition-local gang feed: gram all-reduce trainer
            factors = train_als_partition_local(
                pd.user_idx, pd.item_idx, pd.rating,
                n_users=len(pd.users), n_items=len(pd.items),
                params=als_params, **common)
        else:
            factors = train_als(
                pd.user_idx, pd.item_idx, pd.rating,
                n_users=len(pd.users), n_items=len(pd.items),
                params=als_params, pipeline=pipeline_of(ctx), **common)
        model = SimilarProductModel(factors, pd.items, pd.item_categories)
        model.serving_mesh = serving_mesh_for(
            ctx, len(pd.items), p.rank, p.sharded_serving)
        return model

    def predict(self, model: SimilarProductModel, query: dict) -> dict:
        pairs = model.similar(
            [str(x) for x in query.get("items", [])],
            int(query.get("num", 10)),
            categories=query.get("categories"),
            white_list=query.get("whiteList"),
            black_list=query.get("blackList"),
        )
        return {"itemScores": [{"item": i, "score": s} for i, s in pairs]}

    def prepare_model_for_persistence(self, model: SimilarProductModel):
        return {
            "user_factors": np.asarray(model.factors.user_factors),
            "item_factors": np.asarray(model.factors.item_factors),
            "items": model.items.to_persisted(),
            "item_categories": {k: sorted(v) for k, v in model.item_categories.items()},
        }

    def restore_model(self, stored, ctx) -> SimilarProductModel:
        if isinstance(stored, SimilarProductModel):
            if stored.serving_mesh is None:
                stored.serving_mesh = serving_mesh_for(
                    ctx, stored.factors.item_factors.shape[0],
                    stored.factors.item_factors.shape[1],
                    self.params.sharded_serving)
            return stored
        uf, itf = stored["user_factors"], stored["item_factors"]
        model = SimilarProductModel(
            factors=ALSFactors(uf, itf, uf.shape[0], itf.shape[0]),
            items=BiMap.from_persisted(stored["items"]),
            item_categories={k: set(v) for k, v in stored["item_categories"].items()},
        )
        model.serving_mesh = serving_mesh_for(
            ctx, itf.shape[0], itf.shape[1], self.params.sharded_serving)
        return model


class SimilarProductEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            data_source_class=SimilarProductDataSource,
            algorithm_class_map={"als": SimilarProductAlgorithm, "": SimilarProductAlgorithm},
        )
