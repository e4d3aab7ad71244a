"""Universal Recommender (CCO) template.

Reference: ActionML universal-recommender (SURVEY.md §2.8 row 5):
multi-event DataSource (primary "buy" + secondary "view",
"category-pref", ...); Mahout SimilarityAnalysis builds LLR-thresholded
cross-occurrence indicator matrices; indicators are indexed into
Elasticsearch and queries run as ES boolean similarity queries with
business rules (category filters/boosts, blacklists, date rules).

TPU-native redesign: ops/llr.py computes the indicators as dense chunked
MXU matmuls + vectorized G²; the served "index" is what Elasticsearch
held for upstream, an index BY CORRELATOR built at deploy time from the
persisted [I, K] arrays and resident on the device
(`ops/llr.place_indicators`). A query ships its history as catalog rows
and one dispatch sums the postings that name them, applies the business
rules (categories, blacklist, exclude-purchased: rows and resident
category masks, `models/_filters.py`) and selects the top k.

Wire format (UR parity, core subset):
  query  {"user": "u1", "num": 4, "fields": [{"name": "categories",
          "values": ["c"], "bias": -1}], "blacklistItems": [...]}
  result {"itemScores": [{"item": ..., "score": ...}]}
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from ..common import telemetry
from ..controller import Algorithm, DataSource, Engine, EngineFactory, Params, SanityCheck
from ..data.storage.bimap import BiMap
from ..data.store.l_event_store import LEventStore
from ..data.store.p_event_store import PEventStore
from ..ops.llr import (
    Indicators, ResidentIndicators, cco_indicators_multi, compile_ladders,
    place_indicators, popular_rows, score_rows,
)
from ._filters import CategoryIndex, _rows, build_exclude, split_fields


@dataclasses.dataclass
class TrainingData(SanityCheck):
    # per event name: (user_idx, item_idx) COO
    events: dict[str, tuple[np.ndarray, np.ndarray]]
    users: BiMap
    items: BiMap
    item_categories: dict[str, set[str]]
    # item id → {"availableDate"/"expireDate"/"date": ISO string} for the
    # UR date rules (reference UR: available/expire serving filters and
    # the query dateRange rule).
    item_dates: dict[str, dict] = dataclasses.field(default_factory=dict)

    def sanity_check(self):
        assert self.events, "no indicator events found"
        primary = next(iter(self.events.values()))
        assert len(primary[0]) > 0, "primary event has no data"


PreparedData = TrainingData


@dataclasses.dataclass(frozen=True)
class URDataSourceParams(Params):
    app_name: str = ""
    # First name = the primary (conversion) event, like UR's eventNames.
    event_names: Sequence[str] = ("buy", "view")
    item_entity_type: str = "item"


class URDataSource(DataSource):
    params_cls = URDataSourceParams
    params_aliases = {"appName": "app_name", "eventNames": "event_names"}

    def read_training(self, ctx) -> TrainingData:
        p: URDataSourceParams = self.params
        app_name = p.app_name or ctx.app_name
        batch = PEventStore.find_batch(
            app_name,
            event_names=list(p.event_names),
            storage=ctx.get_storage(),
            channel_name=ctx.channel_name,
        )
        users = BiMap.string_int(batch.entity_id)
        items = BiMap.string_int(
            t for t in batch.target_entity_id if t is not None
        )
        per_event: dict[str, tuple[list, list]] = {n: ([], []) for n in p.event_names}
        for name, u, t in zip(batch.event, batch.entity_id, batch.target_entity_id):
            if t is None:
                continue
            lu, li = per_event[name]
            lu.append(users(u))
            li.append(items(t))
        events = {
            n: (np.asarray(lu, np.int32), np.asarray(li, np.int32))
            for n, (lu, li) in per_event.items()
        }
        cats: dict[str, set[str]] = {}
        dates: dict[str, dict] = {}
        for item_id, pm in PEventStore.aggregate_properties(
            app_name, p.item_entity_type, storage=ctx.get_storage()
        ).items():
            c = pm.get_opt("categories")
            if c:
                cats[item_id] = set(c)
            d = {k: pm.get_opt(k)
                 for k in ("availableDate", "expireDate", "date")}
            d = {k: v for k, v in d.items() if v}
            if d:
                dates[item_id] = d
        return TrainingData(events, users, items, cats, dates)


@dataclasses.dataclass
class URModel:
    # event name → Indicators ([I,K] idx/LLR vs the primary item space)
    indicators: dict[str, Indicators]
    users: BiMap
    items: BiMap
    item_categories: dict[str, set[str]]
    app_name: str
    event_names: Sequence[str]
    # primary-event count per item — the UR "popular" backfill ranking
    # used for cold/unknown users (reference UR: RankingFieldName /
    # popModel backfill).
    popularity: np.ndarray = None
    # item id → {"availableDate"/"expireDate"/"date": ISO} (date rules)
    item_dates: dict[str, dict] = dataclasses.field(default_factory=dict)
    _storage: object = dataclasses.field(default=None, repr=False, compare=False)
    _cat_index: object = dataclasses.field(default=None, repr=False, compare=False)
    _date_arrays: object = dataclasses.field(default=None, repr=False, compare=False)
    _resident: object = dataclasses.field(default=None, repr=False, compare=False)

    def category_index(self) -> CategoryIndex:
        if self._cat_index is None:
            self._cat_index = CategoryIndex(self.items, self.item_categories)
        return self._cat_index

    def date_arrays(self):
        """(avail_ts, expire_ts, date_ts) [I] epoch-second arrays.
        Missing availableDate → -inf (always available); missing
        expireDate → +inf (never expires); missing date → NaN (fails any
        dateRange comparison, matching UR's must-clause semantics)."""
        if self._date_arrays is None:
            from ..data.storage.event import parse_event_time

            n = len(self.items)
            avail = np.full(n, -np.inf)
            expire = np.full(n, np.inf)
            date = np.full(n, np.nan)
            for item_id, d in self.item_dates.items():
                j = self.items.get(item_id)
                if j is None:
                    continue
                # str() coercion + AttributeError: the property value is
                # arbitrary JSON (int/list/...), and parse_event_time
                # raises AttributeError on non-strings.
                try:
                    if "availableDate" in d:
                        avail[j] = parse_event_time(
                            str(d["availableDate"])).timestamp()
                    if "expireDate" in d:
                        expire[j] = parse_event_time(
                            str(d["expireDate"])).timestamp()
                    if "date" in d:
                        date[j] = parse_event_time(str(d["date"])).timestamp()
                except (ValueError, TypeError, AttributeError):
                    pass  # unparseable property: treat as absent
            self._date_arrays = (avail, expire, date)
        return self._date_arrays

    def resident(self) -> ResidentIndicators:
        """The served state on the device: the event types' indicators as
        one index by correlator and the popularity vector, placed at
        first use (deploy: `warm_up`) and kept, as
        `ShardedCatalogServing.catalog` keeps the ALS catalog. Without it
        every query would upload the model."""
        if self._resident is None:
            # a ranking of zeros ranks nothing: no backfill then
            popular = (self.popularity is not None
                       and bool(np.any(self.popularity)))
            self._resident = place_indicators(
                {name: self.indicators[name] for name in self.event_names
                 if name in self.indicators},
                self.popularity if popular else None)
        return self._resident

    def warm_up(self, num: int = 10):
        """Deploy time: the state resident (indicators, popularity, every
        category's mask), the ids' forward maps built, and every step of
        the shape ladders compiled (`ops/llr.compile_ladders`; here the
        small programs that compose masks and boosts): no query after this
        builds, ships or compiles anything whose size grows with the
        catalog."""
        resident = self.resident()
        if not resident.n_items:
            return
        cats = self.category_index()
        some = cats.resident_all()[:2]
        if some:  # the small programs that compose masks and boosts
            two = [{"values": some, "bias": -1}, {"values": some[:1]}]
            build_exclude(self.items, cats, fields=two, rows=True)
            cats.device_boost([(some, 2.0), (some[:1], 2.0)])
        compile_ladders(resident, num)
        if len(self.users):
            self.recommend(next(iter(self.users.keys())), num)

    def _history(self, user: str) -> dict[str, list[int]]:
        """The user's history an event type, as catalog rows, read from
        the event store NOW (reference: UR queries the event store at
        serve time so new events influence results immediately; no cache
        of the store's answers). One combined store query under span
        ``query.store_read`` (``what=history``), bucketed by event name;
        targets the catalog does not know are skipped, repeats kept
        (`ops/llr.score_rows` counts a row once)."""
        out: dict[str, list[int]] = {name: [] for name in self.event_names}
        with telemetry.span("query.store_read", what="history") as sp:
            try:
                events = LEventStore.find_by_entity(
                    self.app_name, "user", user,
                    event_names=list(self.event_names),
                    limit=500 * max(len(self.event_names), 1),
                    storage=self._storage,
                )
            except Exception:
                events = []
            sp.tag(events=len(events))
        for e in events:
            rows = out.get(e.event)
            if rows is None or not e.target_entity_id:
                continue
            j = self.items.get(e.target_entity_id)
            if j is not None:
                rows.append(j)
        return out

    def _date_exclude(self, current_date: Optional[str],
                      date_range: Optional[dict]) -> np.ndarray:
        """UR date rules as an exclude mask: items not yet available or
        already expired at the query's currentDate (default: now), plus
        the optional dateRange clause on the item's "date" property."""
        from ..data.storage.event import parse_event_time

        n = len(self.items)
        exclude = np.zeros(n, dtype=bool)
        avail, expire, date = self.date_arrays()
        if current_date:
            now = parse_event_time(str(current_date)).timestamp()
        else:
            import time as _time

            now = _time.time()
        exclude |= (now < avail) | (now > expire)
        if date_range:
            after = date_range.get("after")
            before = date_range.get("before")
            ok = ~np.isnan(date)
            if after:
                ok &= date >= parse_event_time(str(after)).timestamp()
            if before:
                ok &= date <= parse_event_time(str(before)).timestamp()
            exclude |= ~ok
        return exclude

    def recommend(
        self,
        user: Optional[str],
        num: int,
        fields: Optional[Sequence[dict]] = None,
        blacklist_items: Optional[Sequence[str]] = None,
        exclude_primary_history: bool = True,
        items: Optional[Sequence[str]] = None,
        current_date: Optional[str] = None,
        date_range: Optional[dict] = None,
    ):
        """UR query core: user-based, item-based ("similar to these
        items"), or both (the query items join the history's rows of every
        event type); cold/unknown users fall
        back to the popularity ranking through the SAME filter pipeline
        (reference UR: popModel backfill; item-based and dateRange
        queries per the UR query spec)."""
        history = (self._history(user) if user is not None
                   else {n: [] for n in self.event_names})
        # Item-based query: the query items act as history for every
        # indicator type: each candidate's postings then name them with
        # their correlator weight (the item-similarity column).
        query_rows = _rows(self.items, items)
        for rows in history.values():
            rows.extend(query_rows)

        # The rules as rows and resident category masks: never return
        # the query items, the blacklist or (by default) what the user
        # already has under the primary event; a field with a bias under
        # 0 filters by its categories, one with a bias of 0 or more
        # multiplies (composed on the device from the resident masks).
        # Nothing of the catalog's length is built on the host, except
        # under item dates, whose rule is still a dense host mask.
        dated = bool(current_date or date_range or self.item_dates)
        exclude = build_exclude(
            self.items, self.category_index(), black_list=blacklist_items,
            extra_rows=query_rows + (
                history[self.event_names[0]] if exclude_primary_history
                else []),
            fields=fields, rows=not dated)
        if dated:
            exclude |= self._date_exclude(current_date, date_range)
        boost = self.category_index().device_boost(split_fields(fields)[1])

        resident = self.resident()
        if not any(history.values()):
            # Cold/unknown user with no query items: popularity-ranked
            # backfill through the same rules, on the device.
            if resident.popularity is None:
                return []
            scores, idx = popular_rows(resident, num, exclude, boost)
        else:
            scores, idx, _postings = score_rows(
                resident, history, num, exclude, boost)
        return [
            (self.items.inverse(int(j)), float(s))
            for s, j in zip(scores, idx)
            if np.isfinite(s) and s > 0
        ]


@dataclasses.dataclass(frozen=True)
class URAlgorithmParams(Params):
    app_name: str = ""
    max_correlators_per_item: int = 50
    llr_threshold: float = 0.0
    # 2048 measured best at the bench shapes once host prep went
    # native (product path 3.57M ev/s vs 3.09M at 1024; direct-call
    # sweep best 3.77M): deeper MXU contractions and half the [I, I]
    # accumulator read-write passes outweigh the wider slabs. Results
    # are layout-invariant (exact counts either way).
    user_chunk: int = 2048


class URAlgorithm(Algorithm):
    params_cls = URAlgorithmParams
    params_aliases = {
        "appName": "app_name",
        "maxCorrelatorsPerItem": "max_correlators_per_item",
        "minLLR": "llr_threshold",
    }

    def train(self, ctx, pd: PreparedData) -> URModel:
        p = self.params
        names = list(pd.events.keys())
        primary_name = names[0]
        pu, pi = pd.events[primary_name]
        # One fused scan over the user ranges for every event-type pair: the
        # primary's dedupe/partition/upload/membership slabs are shared
        # across pairs and the self-pair rides the primary slabs
        # outright (ops.llr.cco_indicators_multi; multi-chip meshes run
        # the same fusion sharded over DATA_AXIS with psum'd counts;
        # per-pair fallback only when the fused accumulators exceed the
        # HBM budget — bit-identical either way).
        secondaries = {
            name: pd.events[name]
            for name in names if len(pd.events[name][0])
        }
        indicators = cco_indicators_multi(
            pu, pi, secondaries,
            n_users=len(pd.users), n_items=len(pd.items),
            max_correlators=p.max_correlators_per_item,
            llr_threshold=p.llr_threshold,
            u_chunk=p.user_chunk,
            mesh=ctx.get_mesh() if ctx else None,
        )
        # Popularity backfill ranking: raw primary-event count per item
        # (reference UR's default "popular" popModel).
        with telemetry.span("ur.popularity"):
            popularity = np.bincount(
                np.asarray(pi, np.int64), minlength=len(pd.items)
            ).astype(np.float32)
        model = URModel(
            indicators=indicators, users=pd.users, items=pd.items,
            item_categories=pd.item_categories,
            app_name=p.app_name or ctx.app_name,
            event_names=tuple(names),
            popularity=popularity,
            item_dates=dict(pd.item_dates),
        )
        model._storage = ctx.get_storage()
        return model

    def predict(self, model: URModel, query: dict) -> dict:
        # UR query spec: "user" and/or "item"/"itemSet" (item-based),
        # "fields" biz rules, "blacklistItems", "currentDate" (for the
        # available/expire rules), "dateRange" {"after","before"}.
        items = query.get("itemSet") or query.get("items")
        if not items and query.get("item") is not None:
            items = [query["item"]]
        user = query.get("user")
        pairs = model.recommend(
            str(user) if user is not None else None,
            int(query.get("num", 10)),
            fields=query.get("fields"),
            blacklist_items=query.get("blacklistItems"),
            items=[str(i) for i in items] if items else None,
            current_date=query.get("currentDate"),
            date_range=query.get("dateRange"),
        )
        return {"itemScores": [{"item": i, "score": s} for i, s in pairs]}

    def prepare_model_for_persistence(self, model: URModel):
        return {
            "indicators": {
                n: {"idx": ind.idx, "score": ind.score}
                for n, ind in model.indicators.items()
            },
            "users": model.users.to_persisted(),
            "items": model.items.to_persisted(),
            "item_categories": {k: sorted(v) for k, v in model.item_categories.items()},
            "app_name": model.app_name,
            "event_names": list(model.event_names),
            "popularity": np.asarray(model.popularity)
            if model.popularity is not None else None,
            "item_dates": dict(model.item_dates),
        }

    def restore_model(self, stored, ctx) -> URModel:
        if isinstance(stored, URModel):
            stored._storage = ctx.get_storage()
            return stored
        model = URModel(
            indicators={
                n: Indicators(idx=v["idx"], score=v["score"])
                for n, v in stored["indicators"].items()
            },
            users=BiMap.from_persisted(stored["users"]),
            items=BiMap.from_persisted(stored["items"]),
            item_categories={k: set(v) for k, v in stored["item_categories"].items()},
            app_name=stored["app_name"],
            event_names=tuple(stored["event_names"]),
            popularity=stored.get("popularity"),
            item_dates=dict(stored.get("item_dates") or {}),
        )
        model._storage = ctx.get_storage()
        return model


class UniversalRecommenderEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            data_source_class=URDataSource,
            algorithm_class_map={"ur": URAlgorithm, "": URAlgorithm},
        )
