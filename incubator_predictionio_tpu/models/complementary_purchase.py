"""Complementary Purchase engine — basket-level co-purchase suggestions.

Reference ecosystem parity: the `predictionio-template-complementary-
purchase` template (PredictionIO template gallery; SURVEY.md §2.8 notes
the examples/ ecosystem beyond the five headline configs) suggested
items frequently bought IN THE SAME SHOPPING BASKET as the query items
— association rules mined from per-user time-windowed "buy" sessions.

TPU-native redesign: baskets (user × time-window sessions) take the
"user" axis of the striped LLR co-occurrence kernel (ops/llr.py — the
same MXU path the Universal Recommender uses), so mining runs as dense
[basket-chunk, items]ᵀ×[basket-chunk, items] einsum stripes with
LLR-thresholded top-k indicators per item, and serving scores a query
basket on device (the indicators resident as an index by correlator,
the basket shipped as rows: ops/llr.score_rows, the universal
recommender's kernel).

DASE shape:
- DataSource: "buy" events (entity=user, target=item).
- Algorithm params: ``basketWindowSecs`` (gap that closes a session,
  default 3600), ``maxCorrelatorsPerItem``, ``minLLR``.
- Query: ``{"items": ["i1", ...], "num": 4}`` →
  ``{"itemScores": [{"item": ..., "score": ...}]}`` with the queried
  items excluded.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from ..controller import Algorithm, Engine, EngineFactory, Params, SanityCheck
from ..controller.datasource import DataSource
from ..data.storage.bimap import BiMap
from ..ops.llr import Indicators, cco_indicators, place_indicators, score_rows
from ..ops.topk import RowExclude


@dataclasses.dataclass
class TrainingData(SanityCheck):
    user_idx: np.ndarray   # [n] int32
    item_idx: np.ndarray   # [n] int32
    time_us: np.ndarray    # [n] int64 event time (µs)
    users: BiMap
    items: BiMap

    def sanity_check(self) -> None:
        assert len(self.user_idx) > 0, "no buy events found"
        assert len(self.user_idx) == len(self.item_idx) == len(self.time_us)


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    event_name: str = "buy"


class ComplementaryDataSource(DataSource):
    params_cls = DataSourceParams
    params_aliases = {"appName": "app_name", "eventName": "event_name"}

    def read_training(self, ctx) -> TrainingData:
        from ..data.store.p_event_store import PEventStore

        p = self.params
        batch = PEventStore.find_batch(
            p.app_name or (ctx.app_name if ctx else ""),
            event_names=[p.event_name],
            storage=ctx.get_storage() if ctx else None,
            channel_name=ctx.channel_name if ctx else None)
        keep = [j for j, tid in enumerate(batch.target_entity_id)
                if tid is not None]
        users = BiMap.string_int(batch.entity_id[j] for j in keep)
        items = BiMap.string_int(batch.target_entity_id[j] for j in keep)
        return TrainingData(
            users.map_array([batch.entity_id[j] for j in keep]
                            ).astype(np.int32),
            items.map_array([batch.target_entity_id[j] for j in keep]
                            ).astype(np.int32),
            batch.event_time_us[keep], users, items)

    def read_eval(self, ctx):
        """K-fold basket-completion split for `pio eval`
        (models/template_evals.py): each held-out buy becomes a fold
        query made of the shopper's OTHER training-fold items — the
        held-out item must surface as their complement."""
        from ..e2.cross_validation import k_fold_indices

        td = self.read_training(ctx)
        folds = []
        for train_sel, test_sel in k_fold_indices(
                len(td.user_idx), k=3, seed=0):
            train = TrainingData(
                td.user_idx[train_sel], td.item_idx[train_sel],
                td.time_us[train_sel], td.users, td.items)
            basket_items: dict[int, list[str]] = {}
            for j in np.nonzero(train_sel)[0]:
                basket_items.setdefault(int(td.user_idx[j]), []).append(
                    td.items.inverse(int(td.item_idx[j])))
            queries = []
            for j in np.nonzero(test_sel)[0]:
                rest = basket_items.get(int(td.user_idx[j]))
                if not rest:
                    continue   # nothing to query from: cold shopper
                queries.append((
                    {"items": sorted(set(rest))[:8], "num": 10},
                    {"item": td.items.inverse(int(td.item_idx[j]))},
                ))
            folds.append((train, None, queries))
        return folds


def form_baskets(user_idx: np.ndarray, time_us: np.ndarray,
                 window_us: int) -> np.ndarray:
    """Basket id per event: one basket per (user, purchase session),
    where a gap > window_us between a user's consecutive buys closes
    the session — the template's time-window basket semantics,
    vectorized (sort by (user, time), session breaks where the user
    changes or the gap exceeds the window, cumsum for dense ids)."""
    n = len(user_idx)
    if n == 0:
        return np.zeros(0, np.int64)
    order = np.lexsort((time_us, user_idx))
    su, st = user_idx[order], time_us[order]
    new_basket = np.ones(n, bool)
    new_basket[1:] = (su[1:] != su[:-1]) | (st[1:] - st[:-1] > window_us)
    basket_sorted = np.cumsum(new_basket) - 1
    baskets = np.empty(n, np.int64)
    baskets[order] = basket_sorted
    return baskets


@dataclasses.dataclass(frozen=True)
class AlgoParams(Params):
    basket_window_secs: int = 3600
    max_correlators: int = 20
    llr_threshold: float = 0.0


@dataclasses.dataclass
class ComplementaryModel:
    indicators: Indicators
    items: BiMap
    _resident: object = dataclasses.field(default=None, repr=False,
                                          compare=False)

    def resident(self):
        """The indicators on the device, placed at first use and kept."""
        if self._resident is None:
            self._resident = place_indicators({"basket": self.indicators})
        return self._resident

    def warm_up(self, num: int = 4):
        if len(self.items):
            self.suggest([self.items.inverse(0)], num)

    def suggest(self, basket_items: Sequence[str], num: int
                ) -> list[tuple[str, float]]:
        known = [j for j in map(self.items.get, basket_items)
                 if j is not None]
        if not known or self.indicators.idx.shape[0] == 0:
            return []
        rows = np.asarray(known, np.int32)
        scores, idx, _postings = score_rows(
            self.resident(), {"basket": rows}, num,
            exclude=RowExclude(base=None, deny=rows, allow=None))
        return [(self.items.inverse(int(j)), float(s))
                for s, j in zip(scores, idx) if np.isfinite(s) and s > 0]


class ComplementaryAlgorithm(Algorithm):
    params_cls = AlgoParams
    params_aliases = {
        "basketWindowSecs": "basket_window_secs",
        "maxCorrelatorsPerItem": "max_correlators",
        "minLLR": "llr_threshold",
    }

    def train(self, ctx, td: TrainingData) -> ComplementaryModel:
        p = self.params
        baskets = form_baskets(
            td.user_idx, td.time_us, int(p.basket_window_secs) * 1_000_000)
        n_baskets = int(baskets.max()) + 1 if len(baskets) else 0
        ind = cco_indicators(
            baskets, td.item_idx, baskets, td.item_idx,
            n_users=max(n_baskets, 1), n_items=len(td.items),
            max_correlators=p.max_correlators,
            llr_threshold=p.llr_threshold,
            mesh=ctx.get_mesh() if ctx else None,
        )
        return ComplementaryModel(ind, td.items)

    def predict(self, model: ComplementaryModel, query: dict) -> dict:
        pairs = model.suggest(
            [str(x) for x in query.get("items", [])],
            int(query.get("num", 4)))
        return {"itemScores": [{"item": i, "score": s} for i, s in pairs]}

    def prepare_model_for_persistence(self, model: ComplementaryModel):
        return {
            "idx": model.indicators.idx,
            "score": model.indicators.score,
            "items": model.items.to_persisted(),
        }

    def restore_model(self, stored, ctx) -> ComplementaryModel:
        if isinstance(stored, ComplementaryModel):
            return stored
        return ComplementaryModel(
            Indicators(idx=np.asarray(stored["idx"]),
                       score=np.asarray(stored["score"])),
            BiMap(dict(stored["items"])),
        )


class ComplementaryPurchaseEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            data_source_class=ComplementaryDataSource,
            algorithm_class_map={"cooccurrence": ComplementaryAlgorithm,
                                 "": ComplementaryAlgorithm},
        )
