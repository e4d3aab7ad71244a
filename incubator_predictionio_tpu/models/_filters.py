"""Shared serving-time business-rule filters for the recommender
templates (similar-product, e-commerce, universal recommender).

One implementation of the category / whiteList / blackList exclude-mask
(reference: each template's predict applies the same rules). Category
membership is precomputed into per-category boolean masks at model
build/restore time so the per-query cost is a few numpy vector ops, not a
Python loop over the catalog.
"""

from __future__ import annotations

from typing import Mapping, Optional, Sequence

import numpy as np

from ..common import telemetry
from ..data.storage.bimap import BiMap

_M_RULES = telemetry.registry().counter(
    "pio_query_rules_total",
    "Serve-time business rules applied by build_exclude_mask, one count "
    "a rule a query: categories, whiteList, blackList, extra (seen, "
    "unavailable or query items handed in by the template), or none.",
    ("rule",))


class CategoryIndex:
    """category name → bool mask [n_items] (lazily built, cached)."""

    def __init__(self, items: BiMap, item_categories: Mapping[str, set]):
        self._items = items
        self._cats = item_categories
        self._masks: dict[str, np.ndarray] = {}

    def mask(self, category: str) -> np.ndarray:
        m = self._masks.get(category)
        if m is None:
            n = len(self._items)
            m = np.zeros(n, dtype=bool)
            for item_id, cats in self._cats.items():
                if category in cats:
                    j = self._items.get(item_id)
                    if j is not None:
                        m[j] = True
            self._masks[category] = m
        return m

    def any_of(self, categories: Sequence[str]) -> np.ndarray:
        out = np.zeros(len(self._items), dtype=bool)
        for c in categories:
            out |= self.mask(c)
        return out


def _suppress(exclude: np.ndarray, items: BiMap,
              ids: Optional[Sequence[str]]) -> int:
    """Mark the catalog rows of ``ids`` in ``exclude``; ids the catalog
    does not know are skipped. Returns how many rows were marked."""
    rows = [j for j in map(items.get, ids or ()) if j is not None]
    exclude[rows] = True
    return len(rows)


def build_exclude_mask(
    items: BiMap,
    category_index: Optional[CategoryIndex] = None,
    categories: Optional[Sequence[str]] = None,
    white_list: Optional[Sequence[str]] = None,
    black_list: Optional[Sequence[str]] = None,
    extra_excluded_items: Optional[Sequence[str]] = None,
) -> np.ndarray:
    """True = suppressed. Combines the reference templates' rules:
    category membership (must match one), whitelist (only these),
    blacklist, plus arbitrary extra item ids (seen/unavailable/query
    items).

    Span ``query.mask_build`` (tags ``rules``: the rules this query
    carried, joined by ``+``, or ``none``; ``excluded``: the catalog
    rows that blackList and the extra ids resolved to, the sparse part
    of the mask) and counter ``pio_query_rules_total{rule}``, one count
    a rule a query."""
    rules = [name for name, given in (
        ("categories", categories and category_index is not None),
        ("whiteList", white_list), ("blackList", black_list),
        ("extra", extra_excluded_items)) if given] or ["none"]
    for name in rules:
        _M_RULES.labels(name).inc()
    with telemetry.span("query.mask_build", rules="+".join(rules)) as sp:
        n = len(items)
        exclude = np.zeros(n, dtype=bool)
        if categories and category_index is not None:
            exclude |= ~category_index.any_of(categories)
        if white_list:
            allowed = {items.get(w) for w in white_list} - {None}
            mask = np.ones(n, dtype=bool)
            if allowed:
                mask[list(allowed)] = False
            exclude |= mask
        sp.tag(excluded=_suppress(exclude, items, black_list)
               + _suppress(exclude, items, extra_excluded_items))
    return exclude
