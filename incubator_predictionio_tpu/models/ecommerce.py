"""E-Commerce Recommendation template.

Reference: examples/scala-parallel-ecommercerecommendation (SURVEY.md
§2.8 note): implicit ALS over view/buy events; at SERVE time the
prediction filters out items the user has already seen (LEventStore read
inside predict — the canonical serve-time-context template) and items
$set as unavailable via a "constraint" entity.

Wire format (template parity):
  query  {"user": "u1", "num": 4, "categories": [...],
          "whiteList": [...], "blackList": [...], "unseenOnly": true}
  result {"itemScores": [{"item": ..., "score": ...}]}
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from ..common import telemetry
from ..controller import Algorithm, Engine, EngineFactory, Params
from ..data.store.l_event_store import LEventStore
from ..data.store.p_event_store import PEventStore
from ..data.storage.bimap import BiMap
from ..ops.als import ALSFactors, ALSParams, train_als
from ..workflow.input_pipeline import pipeline_of
from ._sharded_serving import (
    ShardedCatalogServing,
    serving_mesh_for,
    validate_serving_mode,
)
from ._filters import CategoryIndex, build_exclude
from .similar_product import (
    SimilarProductDataSource,
    DataSourceParams as SPDataSourceParams,
)


@dataclasses.dataclass(frozen=True)
class ECommerceDataSourceParams(SPDataSourceParams):
    event_names: Sequence[str] = ("view", "buy")


class ECommerceDataSource(SimilarProductDataSource):
    params_cls = ECommerceDataSourceParams

    def read_eval(self, ctx):
        """K-fold split for `pio eval` (models/template_evals.py):
        each held-out (user, item) interaction becomes a fold query.
        ``unseenOnly`` is off for eval queries — the seen-item filter
        would exclude exactly the interaction being graded."""
        from ..e2.cross_validation import k_fold_indices
        from .similar_product import TrainingData as SPTrainingData

        td = self.read_training(ctx)
        folds = []
        for train_sel, test_sel in k_fold_indices(
                len(td.user_idx), k=3, seed=0):
            train = SPTrainingData(
                td.user_idx[train_sel], td.item_idx[train_sel],
                td.rating[train_sel], td.users, td.items,
                td.item_categories,
            )
            queries = [
                (
                    {"user": td.users.inverse(int(td.user_idx[j])),
                     "num": 10, "unseenOnly": False},
                    {"item": td.items.inverse(int(td.item_idx[j]))},
                )
                for j in np.nonzero(test_sel)[0]
            ]
            folds.append((train, None, queries))
        return folds


@dataclasses.dataclass
class ECommerceModel(ShardedCatalogServing):
    factors: ALSFactors
    users: BiMap
    items: BiMap
    item_categories: dict[str, set[str]]
    app_name: str
    seen_event_names: Sequence[str]
    _storage: object = dataclasses.field(default=None, repr=False, compare=False)
    _cat_index: object = dataclasses.field(default=None, repr=False, compare=False)
    # PAlgorithm serving analog: when set, the catalog is sharded over
    # every mesh device at serve time (ops.sharded_topk).
    serving_mesh: object = dataclasses.field(default=None, repr=False, compare=False)
    _sharded_cat: object = dataclasses.field(default=None, repr=False, compare=False)

    def category_index(self) -> CategoryIndex:
        if self._cat_index is None:
            self._cat_index = CategoryIndex(self.items, self.item_categories)
        return self._cat_index

    def warm_up(self, num: int = 10):
        self.warm_catalog()
        if len(self.users):
            self.recommend(next(iter(self.users.keys())), num)

    def _seen_items(self, user: str) -> set[str]:
        """Serve-time LEventStore read (reference: ECommAlgorithm.predict
        querying recent view events)."""
        with telemetry.span("query.store_read", what="seen") as sp:
            try:
                events = LEventStore.find_by_entity(
                    self.app_name, "user", user,
                    event_names=list(self.seen_event_names),
                    limit=200, storage=self._storage,
                )
            except Exception:
                return set()
            sp.tag(events=len(events))
        return {e.target_entity_id for e in events if e.target_entity_id}

    def _unavailable_items(self) -> set[str]:
        """$set constraint entity (reference: ECommAlgorithm
        unavailableItems constraint)."""
        with telemetry.span("query.store_read", what="unavailable") as sp:
            try:
                events = LEventStore.find_by_entity(
                    self.app_name, "constraint", "unavailableItems",
                    event_names=["$set"], limit=1, storage=self._storage,
                )
            except Exception:
                return set()
            sp.tag(events=len(events))
        for e in events:
            return set(e.properties.get_or_else("items", []))
        return set()

    def recommend(
        self,
        user: str,
        num: int,
        categories: Optional[Sequence[str]] = None,
        white_list: Optional[Sequence[str]] = None,
        black_list: Optional[Sequence[str]] = None,
        unseen_only: bool = True,
    ):
        uidx = self.users.get(user)
        if uidx is None:
            return []
        extra = list(self._unavailable_items())
        if unseen_only:
            extra += list(self._seen_items(user))
        # flat layout: the rules go to the kernel as rows and a resident
        # category mask, and the mask is composed on the device; the mesh
        # layout's kernel takes a dense mask per shard. Either way the
        # rules apply BEFORE the (partial) top-k (ShardedCatalog
        # contract) — filtered items never inflate the candidate merge
        catalog = self.catalog()
        exclude = build_exclude(
            self.items, self.category_index(), categories,
            white_list, black_list, extra_excluded_items=extra,
            rows=catalog.layout == "flat",
        )
        scores, idx = catalog.top_k(
            self.factors.user_factors[uidx], num, exclude=exclude)
        return [
            (self.items.inverse(int(j)), float(s))
            for s, j in zip(scores, idx)
            if np.isfinite(s)
        ]


@dataclasses.dataclass(frozen=True)
class ECommerceAlgoParams(Params):
    app_name: str = ""
    rank: int = 10
    num_iterations: int = 20
    reg: float = 0.01
    alpha: float = 1.0
    seen_events: Sequence[str] = ("view", "buy")
    seed: Optional[int] = None
    # "auto" → bfloat16 on TPU meshes; set "float32" in engine.json to
    # reproduce pre-auto runs exactly. -1 → auto HBM-budget chunking.
    compute_dtype: str = "auto"
    chunk_tiles: int = -1
    # engine.json "shardedServing": auto|always|never (ops.sharded_topk).
    sharded_serving: str = "auto"


class ECommerceAlgorithm(Algorithm):
    params_cls = ECommerceAlgoParams
    params_aliases = {
        "appName": "app_name", "lambda": "reg",
        "numIterations": "num_iterations", "seenEvents": "seen_events",
        "computeDtype": "compute_dtype", "chunkTiles": "chunk_tiles",
        "shardedServing": "sharded_serving",
    }

    def train(self, ctx, pd) -> ECommerceModel:
        p = self.params
        validate_serving_mode(p.sharded_serving)  # before the expensive run
        factors = train_als(
            pd.user_idx, pd.item_idx, pd.rating,
            n_users=len(pd.users), n_items=len(pd.items),
            params=ALSParams(
                rank=p.rank, num_iterations=p.num_iterations, reg=p.reg,
                implicit_prefs=True, alpha=p.alpha,
                seed=p.seed if p.seed is not None else 3,
                compute_dtype=p.compute_dtype, chunk_tiles=p.chunk_tiles,
            ),
            mesh=ctx.get_mesh() if ctx else None,
            checkpoint_hook=getattr(ctx, "checkpoint_hook", None),
            resume=bool(ctx and ctx.workflow_params.resume),
            nan_guard=bool(ctx and ctx.workflow_params.nan_guard),
            nan_guard_stage=getattr(ctx, "stage_label", "algorithm[als]"),
            pipeline=pipeline_of(ctx),
        )
        model = ECommerceModel(
            factors=factors, users=pd.users, items=pd.items,
            item_categories=pd.item_categories,
            app_name=p.app_name or ctx.app_name,
            seen_event_names=tuple(p.seen_events),
        )
        model._storage = ctx.get_storage()
        model.serving_mesh = serving_mesh_for(
            ctx, len(pd.items), p.rank, p.sharded_serving)
        return model

    def predict(self, model: ECommerceModel, query: dict) -> dict:
        pairs = model.recommend(
            str(query["user"]),
            int(query.get("num", 10)),
            categories=query.get("categories"),
            white_list=query.get("whiteList"),
            black_list=query.get("blackList"),
            unseen_only=bool(query.get("unseenOnly", True)),
        )
        return {"itemScores": [{"item": i, "score": s} for i, s in pairs]}

    def prepare_model_for_persistence(self, model: ECommerceModel):
        return {
            "user_factors": np.asarray(model.factors.user_factors),
            "item_factors": np.asarray(model.factors.item_factors),
            "users": model.users.to_persisted(),
            "items": model.items.to_persisted(),
            "item_categories": {k: sorted(v) for k, v in model.item_categories.items()},
            "app_name": model.app_name,
            "seen_event_names": list(model.seen_event_names),
        }

    def restore_model(self, stored, ctx) -> ECommerceModel:
        if isinstance(stored, ECommerceModel):
            stored._storage = ctx.get_storage()
            if stored.serving_mesh is None:
                stored.serving_mesh = serving_mesh_for(
                    ctx, stored.factors.item_factors.shape[0],
                    stored.factors.item_factors.shape[1],
                    self.params.sharded_serving)
            return stored
        uf, itf = stored["user_factors"], stored["item_factors"]
        model = ECommerceModel(
            factors=ALSFactors(uf, itf, uf.shape[0], itf.shape[0]),
            users=BiMap.from_persisted(stored["users"]),
            items=BiMap.from_persisted(stored["items"]),
            item_categories={k: set(v) for k, v in stored["item_categories"].items()},
            app_name=stored["app_name"],
            seen_event_names=tuple(stored["seen_event_names"]),
        )
        model._storage = ctx.get_storage()
        model.serving_mesh = serving_mesh_for(
            ctx, itf.shape[0], itf.shape[1], self.params.sharded_serving)
        return model


class ECommerceEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            data_source_class=ECommerceDataSource,
            algorithm_class_map={"ecomm": ECommerceAlgorithm, "": ECommerceAlgorithm},
        )
