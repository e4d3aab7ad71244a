"""Shared serving-time business-rule filters for the recommender
templates (similar-product, e-commerce, universal recommender).

One implementation of the category / whiteList / blackList rules
(reference: each template's predict applies the same rules):
``_resolve`` turns a query's lists into a sparse description (`Rules`),
and the two forms a kernel takes are made from that one description. The
DENSE form is a fresh ``bool[n_items]`` on the host (`build_exclude_mask`:
similar-product, the ``mesh`` serving layout). The ROW form
(`build_exclude` with ``rows=True``: the e-commerce template on the
``flat`` layout, the universal recommender) hands the kernel the rows
themselves and a category mask that already lives on the device, and
nothing of catalog length is allocated or shipped for the query. The
universal recommender's ``fields`` are the same rules under another
spelling (`split_fields`): a bias under 0 is a category group an item must
match, a bias of 0 or more a multiplier (`CategoryIndex.device_boost`).
Category membership is precomputed into per-category boolean masks at
first use (`CategoryIndex.resident_all` builds every category's in one
pass, at deploy time), so the per-query cost is a few vector ops or none,
not a Python loop over the catalog.
"""

from __future__ import annotations

import functools
from typing import Mapping, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..common import telemetry
from ..data.storage.bimap import BiMap
from ..ops.topk import RowExclude, row_capacity

_M_RULES = telemetry.registry().counter(
    "pio_query_rules_total",
    "Serve-time business rules applied by build_exclude_mask, one count "
    "a rule a query: categories, fields (the universal recommender's), "
    "whiteList, blackList, extra (seen, unavailable or query items "
    "handed in by the template), or none.",
    ("rule",))

_M_MASK_PATH = telemetry.registry().counter(
    "pio_query_mask_path_total",
    "Queries by where their exclude mask was composed: device = the rows "
    "and a resident category mask handed to the top-k kernel; dense = a "
    "bool[n_items] built on the host (lists over the row ladder's top, "
    "the mesh layout, similar-product, a query under item dates).",
    ("path",))


@functools.lru_cache(maxsize=None)
def _all_excluded(n_items: int):
    """Device-resident all-True mask, one per catalog size: what a
    category that holds no item excludes. Shared, so that category names
    the catalog does not know (they come from queries) cannot fill the
    device with masks."""
    return jax.device_put(np.ones((n_items,), dtype=bool))


class CategoryIndex:
    """category name → bool mask [n_items] (lazily built, cached).

    Two forms a category, both built at first use and kept: ``mask`` /
    ``any_of``, HOST arrays "in this category" for the dense form of the
    rules; ``device_exclude``, a ``jax.Array`` "NOT in this category"
    resident on the device (``n_items`` bytes a category: 24 x 9.4 MB =
    226 MB for the Amazon catalog), which the row form passes to the
    kernel as its base mask with no transfer."""

    def __init__(self, items: BiMap, item_categories: Mapping[str, set]):
        self._items = items
        self._cats = item_categories
        self._masks: dict[str, np.ndarray] = {}
        self._device_not_in: dict[str, jax.Array] = {}

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

    def device_exclude(self, categories: Sequence[str]) -> jax.Array:
        """``~any_of(categories)`` as a device array: for ONE category
        the resident mask itself; for several, their conjunction composed
        on the device (one small dispatch a further category, nothing
        crosses)."""
        return functools.reduce(
            jnp.logical_and, map(self._resident_not_in, categories))

    def device_boost(self, boosts: Sequence[tuple]) -> Optional[jax.Array]:
        """The ``float32[n_items]`` multiplier of ``boosts`` ((category
        names, bias) pairs: an item in one of the names is multiplied by
        the bias, once a pair) composed on the device from the resident
        masks, or None for no pair. One small dispatch a pair; nothing
        crosses but the bias."""
        out = None
        for values, bias in boosts:
            b = _boost_of(self.device_exclude(values), np.float32(bias))
            out = b if out is None else out * b
        return out

    def resident_all(self) -> list[str]:
        """Every category's two forms built NOW, in ONE pass over the
        catalog's categories (`mask` makes a pass a category), and the
        device form put: a deploy-time call (``warm_up``), after which no
        query of a known category builds or ships a mask. Returns the
        categories' names, sorted."""
        rows: dict[str, list[int]] = {}
        for item_id, cats in self._cats.items():
            j = self._items.get(item_id)
            if j is not None:
                for c in cats:
                    rows.setdefault(c, []).append(j)
        for category, members in rows.items():
            if category not in self._masks:
                m = np.zeros(len(self._items), dtype=bool)
                m[members] = True
                self._masks[category] = m
            self._resident_not_in(category)
        return sorted(rows)

    def _resident_not_in(self, category: str) -> jax.Array:
        m = self._device_not_in.get(category)
        if m is None:
            host = self.mask(category)
            m = (jax.device_put(~host) if host.any()
                 else _all_excluded(len(host)))
            self._device_not_in[category] = m
        return m


@jax.jit
def _boost_of(not_in, bias):
    return jnp.where(not_in, jnp.float32(1.0), bias)


class Rules(NamedTuple):
    """What a query's rules come to, sparsely: ``deny``, the int32
    catalog rows of blackList, the handed-in ids (ids the catalog does
    not know skipped, duplicates kept) and the handed-in rows; ``allow``,
    the rows of a whiteList (None where none was given; EMPTY where the
    catalog knows none of its ids, which suppresses everything);
    ``groups``, lists of category names of EACH of which an item must
    match one (``categories`` is one group, every filtering field of
    ``fields`` one more; empty where none was given, or no
    `CategoryIndex` to look them up in); ``names``, the rules the query
    carried."""
    deny: np.ndarray
    allow: Optional[np.ndarray]
    groups: list[Sequence[str]]
    names: list[str]


def _rows(items: BiMap, ids: Optional[Sequence[str]]) -> list[int]:
    """The catalog rows of ``ids``; ids the catalog does not know are
    skipped."""
    return [j for j in map(items.get, ids or ()) if j is not None]


def split_fields(fields: Optional[Sequence[dict]]) -> tuple[list, list]:
    """The universal recommender's ``fields`` rules by the sign of their
    bias: (groups to filter by, (values, bias) pairs to boost by). A bias
    under 0 (the default, -1) keeps only the items that match one of the
    field's values; a bias of 0 or more multiplies their score."""
    groups, boosts = [], []
    for f in fields or ():
        values, bias = f.get("values", []), float(f.get("bias", -1))
        if bias < 0:
            groups.append(values)
        else:
            boosts.append((values, bias))
    return groups, boosts


def _resolve(items, category_index, categories, white_list, black_list,
             extra_excluded_items, fields=None, extra_rows=None) -> Rules:
    groups = ([categories] if categories else []) + split_fields(fields)[0]
    if category_index is None:
        groups = []
    names = [name for name, given in (
        ("categories", categories and groups), ("fields", fields),
        ("whiteList", white_list), ("blackList", black_list),
        ("extra", extra_excluded_items or (
            extra_rows is not None and len(extra_rows))))
        if given] or ["none"]
    return Rules(
        deny=np.asarray(_rows(items, black_list)
                        + _rows(items, extra_excluded_items)
                        + list(() if extra_rows is None else extra_rows),
                        np.int32),
        allow=(np.asarray(_rows(items, white_list), np.int32)
               if white_list else None),
        groups=groups, names=names)


def _dense(rules: Rules, n_items: int,
           category_index: Optional[CategoryIndex]) -> np.ndarray:
    """The description as a fresh ``bool[n_items]``, True = suppressed."""
    exclude = np.zeros(n_items, dtype=bool)
    for group in rules.groups:
        exclude |= ~category_index.any_of(group)
    if rules.allow is not None:
        outside = np.ones(n_items, dtype=bool)
        outside[rules.allow] = False
        exclude |= outside
    exclude[rules.deny] = True
    return exclude


def build_exclude(
    items: BiMap,
    category_index: Optional[CategoryIndex] = None,
    categories: Optional[Sequence[str]] = None,
    white_list: Optional[Sequence[str]] = None,
    black_list: Optional[Sequence[str]] = None,
    extra_excluded_items: Optional[Sequence[str]] = None,
    *,
    rows: bool = False,
    fields: Optional[Sequence[dict]] = None,
    extra_rows: Optional[Sequence[int]] = None,
) -> Union[np.ndarray, RowExclude]:
    """What `ops/topk.top_k_items` and `ops/llr.score_rows` take as
    ``exclude`` for these rules: category membership (must match one),
    whitelist (only these), blacklist, plus arbitrary extra items
    (seen/unavailable/query items) as ids or, where the caller has
    resolved them already, as catalog rows (``extra_rows``). ``fields``
    are the universal recommender's rules: those with a bias under 0
    filter, each a category group of its own (`split_fields`; the
    boosting ones are the caller's, `CategoryIndex.device_boost`).

    ``rows`` says whether the caller's kernel takes rows (the ``flat``
    serving layout, the resident indicators). Where it does and both
    lists fit `ops/topk.row_capacity`, the answer is a `RowExclude`: the
    rows, and the category groups as `CategoryIndex.device_exclude`'s
    resident masks, or-ed on the device where there are several (path
    ``device``). Otherwise (``rows`` False, or a list over the ladder's
    top: observed from its length) it is the dense ``bool[n_items]`` host
    mask of the same description (path ``dense``).

    Span ``query.mask_build`` (tags ``rules``: the rules this query
    carried, joined by ``+``, or ``none``; ``excluded``: the catalog
    rows that blackList and the extra items resolved to, the sparse part
    of the mask; ``path``: ``device`` or ``dense``), counter
    ``pio_query_rules_total{rule}``, one count a rule a query, and
    counter ``pio_query_mask_path_total{path}``, one count a query."""
    with telemetry.span("query.mask_build") as sp:
        rules = _resolve(items, category_index, categories, white_list,
                         black_list, extra_excluded_items, fields, extra_rows)
        for name in rules.names:
            _M_RULES.labels(name).inc()
        on_device = rows and row_capacity(rules.deny,
                                          rules.allow) is not None
        path = "device" if on_device else "dense"
        _M_MASK_PATH.labels(path).inc()
        sp.tag(rules="+".join(rules.names), excluded=len(rules.deny),
               path=path)
        if on_device:
            return RowExclude(
                base=(functools.reduce(jnp.logical_or, map(
                    category_index.device_exclude, rules.groups))
                    if rules.groups else None),
                deny=rules.deny, allow=rules.allow)
        return _dense(rules, len(items), category_index)


def build_exclude_mask(
    items: BiMap,
    category_index: Optional[CategoryIndex] = None,
    categories: Optional[Sequence[str]] = None,
    white_list: Optional[Sequence[str]] = None,
    black_list: Optional[Sequence[str]] = None,
    extra_excluded_items: Optional[Sequence[str]] = None,
) -> np.ndarray:
    """The DENSE form of the rules: a fresh ``bool[n_items]`` on the host,
    True = suppressed (`build_exclude` with ``rows=False``, which see for
    the rules, the span and the counters). For kernels that take a mask
    per shard (the ``mesh`` layout) and the template that has not moved
    to rows (similar-product); shipped by `top_k_items` under
    ``topk.mask_put``."""
    return build_exclude(items, category_index, categories, white_list,
                         black_list, extra_excluded_items)
