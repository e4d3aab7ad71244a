"""Recommendation template — the quickstart engine (flagship).

Reference: examples/scala-parallel-recommendation + upstream
predictionio-template-recommender (SURVEY.md §2.8 row 1): PDataSource reads
rate/buy events → RDD[Rating]; P2LAlgorithm wraps MLlib ALS.train; serving
returns model.recommendProducts(user, num).

TPU-native redesign: DataSource → columnar COO triple via PEventStore;
ALSAlgorithm → ops.als (shard_map'd alternating solves over the mesh);
predict → ops.topk AOT-compiled matvec+top_k.

Wire format (byte-compatible with the quickstart):
  query  {"user": "1", "num": 4}
  result {"itemScores": [{"item": "32", "score": 6.17}, ...]}
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
    Preparator,
    SanityCheck,
    Serving,
)
from ..data.storage.bimap import BiMap, extend_bimap
from ..data.store.p_event_store import PEventStore
from ..ops.als import (
    ALSFactors, ALSParams, fold_in_factors, train_als,
    train_als_partition_local,
)
from ..workflow.input_pipeline import pipeline_of
from ._sharded_serving import (
    ShardedCatalogServing,
    serving_mesh_for,
    validate_serving_mode,
)


# -- data types ------------------------------------------------------------


@dataclasses.dataclass
class TrainingData(SanityCheck):
    user_idx: np.ndarray
    item_idx: np.ndarray
    rating: np.ndarray
    users: BiMap
    items: BiMap
    #: True when the triple holds only THIS gang worker's event-log
    #: partitions (workflow/train_feed.py) while users/items are the
    #: allgathered GLOBAL maps — the trainer must then all-reduce
    #: instead of assuming the local data is complete.
    partition_local: bool = False

    def sanity_check(self):
        if self.partition_local:
            # a worker's own partitions can legitimately be empty; the
            # GLOBAL vocabulary says whether the app has data at all
            assert len(self.users) > 0, "no rating events found"
        else:
            assert len(self.user_idx) > 0, "no rating events found"
        assert len(self.user_idx) == len(self.item_idx) == len(self.rating)


PreparedData = TrainingData  # identity preparation (quickstart parity)


@dataclasses.dataclass
class ALSModel(ShardedCatalogServing):
    factors: ALSFactors
    users: BiMap
    items: BiMap
    # Catalog caching + layout selection: ShardedCatalogServing (its two
    # fields are declared here: dataclass machinery needs them per class).
    # When set (a Mesh), the catalog is served SHARDED over every mesh
    # device instead of replicated on one chip — the PAlgorithm serving
    # analog for factor matrices beyond one chip's HBM (reference:
    # core/.../controller/PAlgorithm.scala — batchPredict). Populated by
    # train/restore_model via ops.sharded_topk.serving_mesh_for.
    serving_mesh: object = dataclasses.field(default=None, repr=False, compare=False)
    _sharded_cat: object = dataclasses.field(default=None, repr=False, compare=False)

    def warm_up(self, num: int = 10):
        """Compile + cache the serving executable (called at deploy time)."""
        self.warm_catalog()
        if len(self.users):
            self.recommend_products(next(iter(self.users.keys())), num)

    def example_query(self):
        """A valid query for serving warm-ups (micro-batch shape
        pre-compilation in the engine server)."""
        if not len(self.users):
            return None
        return {"user": next(iter(self.users.keys())), "num": 10}

    def recommend_products(self, user: str, num: int):
        uidx = self.users.get(user)
        if uidx is None:
            return []
        # one call whatever the layout (mesh / flat) —
        # the ShardedCatalog facade owns the dispatch
        scores, idx = self.catalog().top_k(
            self.factors.user_factors[uidx], num)
        return [
            (self.items.inverse(int(i)), float(s))
            for s, i in zip(scores, idx)
            if np.isfinite(s)
        ]


# -- DASE components -------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    event_names: Sequence[str] = ("rate", "buy")
    buy_rating: float = 4.0  # implicit "buy" events get this rating (template parity)


class RecommendationDataSource(DataSource):
    params_cls = DataSourceParams
    params_aliases = {"appName": "app_name", "eventNames": "event_names"}

    def read_training(self, ctx) -> TrainingData:
        p: DataSourceParams = self.params
        app_name = p.app_name or ctx.app_name
        storage = ctx.get_storage()
        from ..workflow import train_feed

        if train_feed.partition_feed_active(storage):
            # gang data plane: this worker scans ONLY its event-log
            # partitions (colseg snapshots + tail parse); the id maps
            # are allgathered once — no merged-view fan-in
            u, i, r, users, items = train_feed.partition_ratings(
                app_name,
                event_names=list(p.event_names),
                event_default_ratings={"buy": p.buy_rating},
                storage=storage,
                channel_name=ctx.channel_name,
            )
            return TrainingData(u, i, r, users, items,
                                partition_local=True)
        # "buy" events carry no rating property → template assigns one.
        u, i, r, users, items = PEventStore.find_ratings(
            app_name,
            event_names=list(p.event_names),
            event_default_ratings={"buy": p.buy_rating},
            storage=storage,
            channel_name=ctx.channel_name,
        )
        return TrainingData(u, i, r, users, items)

    def read_eval(self, ctx):
        """K-fold split for `pio eval` (reference: template's readEval)."""
        from ..e2.cross_validation import k_fold_indices

        td = self.read_training(ctx)
        folds = []
        for train_sel, test_sel in k_fold_indices(len(td.user_idx), k=3, seed=0):
            train = TrainingData(
                td.user_idx[train_sel], td.item_idx[train_sel],
                td.rating[train_sel], td.users, td.items,
            )
            queries = [
                (
                    {"user": td.users.inverse(int(td.user_idx[j])), "num": 10},
                    {"rating": float(td.rating[j]),
                     "item": td.items.inverse(int(td.item_idx[j]))},
                )
                for j in np.nonzero(test_sel)[0]
            ]
            folds.append((train, None, queries))
        return folds


@dataclasses.dataclass(frozen=True)
class AlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 10
    # engine.json uses "lambda"; JsonExtractor maps it onto reg (see
    # params_from_dict call in ALSAlgorithm.__init__).
    reg: float = 0.01
    seed: Optional[int] = None
    implicit_prefs: bool = False
    alpha: float = 1.0
    lambda_scaling: str = "plain"
    block_len: int = 32
    # "auto" → bfloat16 on TPU meshes, float32 elsewhere; -1 → chunk the
    # half-step scan automatically when the gram batch would exceed the
    # HBM budget (ml20m trains at bench-identical settings out of the
    # box — see ops.als._resolve_params).
    compute_dtype: str = "auto"
    chunk_tiles: int = -1
    # None → auto-detect all-ones ratings and elide value-slab upload
    # (ops.als.ALSParams.binary_ratings); engine.json "binaryRatings".
    binary_ratings: Optional[bool] = None
    # "auto" → shard the serving catalog over the mesh when the item
    # factors exceed one chip's HBM budget (ops.sharded_topk);
    # engine.json "shardedServing": auto|always|never.
    sharded_serving: str = "auto"


class ALSAlgorithm(Algorithm):
    """P2LAlgorithm analog (reference: template ALSAlgorithm.scala)."""

    params_cls = AlgorithmParams
    # Reference engine.json spellings → Params fields.
    params_aliases = {
        "lambda": "reg",
        "numIterations": "num_iterations",
        "implicitPrefs": "implicit_prefs",
        "appName": "app_name",
        "lambdaScaling": "lambda_scaling",
        "blockLen": "block_len",
        "computeDtype": "compute_dtype",
        "chunkTiles": "chunk_tiles",
        "binaryRatings": "binary_ratings",
        "shardedServing": "sharded_serving",
    }

    @staticmethod
    def als_params(p: "AlgorithmParams") -> ALSParams:
        return ALSParams(
            rank=p.rank,
            num_iterations=p.num_iterations,
            reg=p.reg,
            lambda_scaling=p.lambda_scaling,
            implicit_prefs=p.implicit_prefs,
            alpha=p.alpha,
            seed=p.seed if p.seed is not None else 3,
            block_len=p.block_len,
            compute_dtype=p.compute_dtype,
            chunk_tiles=p.chunk_tiles,
            binary_ratings=p.binary_ratings,
        )

    def train(self, ctx, pd: PreparedData) -> ALSModel:
        validate_serving_mode(self.params.sharded_serving)  # before the expensive run
        if getattr(pd, "partition_local", False):
            # partition-local gang feed: the triple is this worker's
            # events only — all-reduce the per-row normal equations
            # (falls back to the slab trainer when single-process)
            factors = train_als_partition_local(
                pd.user_idx, pd.item_idx, pd.rating,
                n_users=len(pd.users), n_items=len(pd.items),
                params=self.als_params(self.params),
                mesh=ctx.get_mesh() if ctx else None,
                checkpoint_hook=getattr(ctx, "checkpoint_hook", None),
                resume=bool(ctx and ctx.workflow_params.resume),
                nan_guard=bool(ctx and ctx.workflow_params.nan_guard),
                nan_guard_stage=getattr(ctx, "stage_label",
                                        "algorithm[als]"),
            )
        else:
            factors = train_als(
                pd.user_idx, pd.item_idx, pd.rating,
                n_users=len(pd.users), n_items=len(pd.items),
                params=self.als_params(self.params),
                mesh=ctx.get_mesh() if ctx else None,
                checkpoint_hook=getattr(ctx, "checkpoint_hook", None),
                resume=bool(ctx and ctx.workflow_params.resume),
                nan_guard=bool(ctx and ctx.workflow_params.nan_guard),
                nan_guard_stage=getattr(ctx, "stage_label",
                                        "algorithm[als]"),
                pipeline=pipeline_of(ctx),
            )
        model = ALSModel(factors=factors, users=pd.users, items=pd.items)
        model.serving_mesh = serving_mesh_for(
            ctx, len(pd.items), self.params.rank, self.params.sharded_serving)
        return model

    @staticmethod
    def _is_ranking_query(query: dict) -> bool:
        # "items" present (even empty) selects ranking mode; absent or
        # null means catalog recommendation
        return query.get("items") is not None

    @staticmethod
    def _rank_candidates(model: ALSModel, query: dict) -> dict:
        """Product-ranking mode (ecosystem parity:
        predictionio-template-product-ranking): rank the GIVEN candidate
        list for the user instead of searching the whole catalog —
        storefronts reorder a page of products by affinity. Unknown
        user → items back in sent order with score 0 ("isOriginal": the
        template's fallback signal); unknown items rank last in sent
        order."""
        items = [str(x) for x in query["items"]]
        uid = model.users.get(str(query["user"]))
        if uid is None:
            return {"itemScores": [{"item": it, "score": 0.0}
                                   for it in items],
                    "isOriginal": True}
        uvec = model.factors.user_factors[uid]
        known = [(pos, model.items.get(it))
                 for pos, it in enumerate(items)]
        rows = [iid for _, iid in known if iid is not None]
        # one gathered matvec for the whole candidate page — no
        # per-item dispatch on the serving hot path
        gathered = (model.factors.item_factors[rows] @ uvec
                    if rows else np.zeros(0, np.float32))
        scores = np.full(len(items), -np.inf, np.float64)
        scores[[pos for pos, iid in known if iid is not None]] = gathered
        order = sorted(range(len(items)), key=lambda p: (-scores[p], p))
        return {"itemScores": [
            {"item": items[p],
             "score": float(scores[p]) if np.isfinite(scores[p]) else 0.0}
            for p in order], "isOriginal": False}

    def predict(self, model: ALSModel, query: dict) -> dict:
        if self._is_ranking_query(query):
            return self._rank_candidates(model, query)
        num = int(query.get("num", 10))
        item_scores = model.recommend_products(str(query["user"]), num)
        return {
            "itemScores": [
                {"item": item, "score": score} for item, score in item_scores
            ]
        }

    def batch_predict(self, model: ALSModel, queries: Sequence[dict]) -> list[dict]:
        if not queries:
            return []
        # ranking-mode queries ("items" present) answer per query — the
        # serving micro-batch and `pio batchpredict` paths must match
        # predict() exactly; only catalog queries ride the batched top-k
        ranking = [j for j, q in enumerate(queries)
                   if self._is_ranking_query(q)]
        if ranking:
            out: list[Optional[dict]] = [None] * len(queries)
            for j in ranking:
                out[j] = self._rank_candidates(model, queries[j])
            rest_idx = [j for j in range(len(queries)) if out[j] is None]
            rest = self.batch_predict(
                model, [queries[j] for j in rest_idx])
            for j, r in zip(rest_idx, rest):
                out[j] = r
            return out  # type: ignore[return-value]
        known = [model.users.get(str(q["user"])) is not None for q in queries]
        uvecs = np.stack(
            [
                model.factors.user_factors[model.users(str(q["user"]))]
                if ok
                else np.zeros(model.factors.user_factors.shape[1], np.float32)
                for q, ok in zip(queries, known)
            ]
        )
        num = max(int(q.get("num", 10)) for q in queries)
        # device-resident factors (cached) — passing the host array would
        # re-upload the full catalog matrix on every serving micro-batch
        scores, idx = model.catalog().batch_top_k(uvecs, num)
        out = []
        for j, (q, ok) in enumerate(zip(queries, known)):
            if not ok:
                out.append({"itemScores": []})
                continue
            n = min(int(q.get("num", 10)), idx.shape[1])  # catalog may be smaller
            out.append(
                {
                    "itemScores": [
                        {"item": model.items.inverse(int(idx[j, t])),
                         "score": float(scores[j, t])}
                        for t in range(n)
                    ]
                }
            )
        return out

    #: Proximal weight μ of the fold-in's ‖x − x_old‖² term: an
    #: existing entity's current factor enters its re-solve as a
    #: pseudo-observation of this strength, so one new event nudges a
    #: long-history user instead of replacing them. New entities have a
    #: zero anchor row — for them the solve degrades to the exact
    #: cold-start ridge.
    FOLD_IN_ANCHOR_WEIGHT = 1.0

    def fold_in(self, model: ALSModel, events, ctx,
                data_source_params=None) -> Optional[ALSModel]:
        """Closed-form streaming fold-in (ops.als.fold_in_factors):
        map new rate/buy events onto (user, item, rating) triples with
        the SAME event-name/default-rating rules the data source
        trains with, extend the id maps for unseen users/items, then
        ridge-solve the touched item rows against fixed user factors
        and the touched user rows against the updated item factors.
        O(new events); the served model is never mutated."""
        dsp = dict(data_source_params or {})
        names = list(dsp.get("event_names") or dsp.get("eventNames")
                     or DataSourceParams.event_names)
        buy_rating = float(dsp.get("buy_rating",
                                   dsp.get("buyRating",
                                           DataSourceParams.buy_rating)))
        triples: dict[tuple[str, str], float] = {}
        for e in events:
            if not isinstance(e, dict) or e.get("event") not in names:
                continue
            u, it = e.get("entityId"), e.get("targetEntityId")
            if not u or not it:
                continue
            props = e.get("properties") or {}
            try:
                r = float(props["rating"])
            except (KeyError, TypeError, ValueError):
                r = buy_rating if e.get("event") == "buy" else 1.0
            triples[(str(u), str(it))] = r  # last write wins, like upsert
        if not triples:
            return None
        users, _new_u = extend_bimap(
            model.users, (u for u, _ in triples))
        items, _new_i = extend_bimap(
            model.items, (i for _, i in triples))
        # ids an IdentityBiMap could not extend (non-consecutive) drop
        # out here via .get() returning None
        coo = [(users.get(u), items.get(i), r)
               for (u, i), r in triples.items()]
        coo = [(ui, ii, r) for ui, ii, r in coo
               if ui is not None and ii is not None]
        if len(coo) < len(triples):
            import logging

            logging.getLogger("pio.foldin").warning(
                "fold-in: skipped %d event(s) whose ids cannot extend "
                "the identity catalog map", len(triples) - len(coo))
        if not coo:
            return None
        k = model.factors.user_factors.shape[1]
        uf = np.asarray(model.factors.user_factors, np.float32)
        itf = np.asarray(model.factors.item_factors, np.float32)
        if len(users) > uf.shape[0]:
            uf = np.vstack([uf, np.zeros((len(users) - uf.shape[0], k),
                                         np.float32)])
        else:
            uf = uf.copy()
        if len(items) > itf.shape[0]:
            itf = np.vstack([itf, np.zeros((len(items) - itf.shape[0], k),
                                           np.float32)])
        else:
            itf = itf.copy()
        p = self.params
        kw = dict(reg=p.reg, lambda_scaling=p.lambda_scaling,
                  implicit_prefs=p.implicit_prefs, alpha=p.alpha)

        def touched(axis: int):
            by: dict[int, tuple[list, list]] = {}
            for ui, ii, r in coo:
                row = ui if axis == 0 else ii
                cp = ii if axis == 0 else ui
                by.setdefault(row, ([], []))
                by[row][0].append(cp)
                by[row][1].append(r)
            rows = sorted(by)
            return (rows, [np.asarray(by[r][0], np.int64) for r in rows],
                    [np.asarray(by[r][1], np.float32) for r in rows])

        def mu_for(rows, n_trained: int) -> np.ndarray:
            # the proximal anchor only means something for rows that
            # HAD a factor: brand-new rows (appended past the old
            # matrix) must solve the exact cold-start ridge, not a
            # ridge stiffened by mu toward a meaningless zero anchor
            return np.where(np.asarray(rows) < n_trained,
                            np.float32(self.FOLD_IN_ANCHOR_WEIGHT),
                            np.float32(0.0))

        # items first against the (frozen) user side — a new item rated
        # by existing users lands a real factor; then users against the
        # UPDATED item side, so a new user's first event on a brand-new
        # item still resolves both rows in one increment
        n_u0 = model.factors.user_factors.shape[0]
        n_i0 = model.factors.item_factors.shape[0]
        i_rows, i_idx, i_val = touched(1)
        itf[i_rows] = fold_in_factors(uf, i_idx, i_val,
                                      anchor=itf[i_rows],
                                      anchor_weight=mu_for(i_rows, n_i0),
                                      **kw)
        u_rows, u_idx, u_val = touched(0)
        uf[u_rows] = fold_in_factors(itf, u_idx, u_val,
                                     anchor=uf[u_rows],
                                     anchor_weight=mu_for(u_rows, n_u0),
                                     **kw)
        out = ALSModel(
            factors=ALSFactors(uf, itf, len(users), len(items)),
            users=users, items=items)
        # same serving layout as the live model; the device catalog cache
        # (_sharded_cat) stays None and re-warms at the gate
        out.serving_mesh = model.serving_mesh
        return out

    def prepare_model_for_persistence(self, model: ALSModel):
        return {
            "user_factors": np.asarray(model.factors.user_factors),
            "item_factors": np.asarray(model.factors.item_factors),
            "users": model.users.to_persisted(),
            "items": model.items.to_persisted(),
        }

    def restore_model(self, stored, ctx) -> ALSModel:
        if isinstance(stored, ALSModel):
            if stored.serving_mesh is None:
                stored.serving_mesh = serving_mesh_for(
                    ctx, stored.factors.item_factors.shape[0],
                    stored.factors.item_factors.shape[1],
                    self.params.sharded_serving)
            return stored
        uf = stored["user_factors"]
        itf = stored["item_factors"]
        model = ALSModel(
            factors=ALSFactors(uf, itf, uf.shape[0], itf.shape[0]),
            users=BiMap.from_persisted(stored["users"]),
            items=BiMap.from_persisted(stored["items"]),
        )
        model.serving_mesh = serving_mesh_for(
            ctx, itf.shape[0], itf.shape[1], self.params.sharded_serving)
        return model


class RecommendationEngine(EngineFactory):
    """engine.json: "engineFactory":
    "incubator_predictionio_tpu.models.recommendation.RecommendationEngine"
    """

    def apply(self) -> Engine:
        return Engine(
            data_source_class=RecommendationDataSource,
            algorithm_class_map={"als": ALSAlgorithm, "": ALSAlgorithm},
        )
