"""The exclude mask composed on the device from rows (ops/topk.RowExclude)
against the dense host mask of the same rules.

``models/_filters.build_exclude`` turns a query's rules into ONE sparse
description and hands the kernel either its dense form (a fresh
``bool[n_items]``, shipped whole) or its row form (int32 rows padded to a
step of ``topk.ROW_LADDER`` and a category mask resident on the device).
Both must give ``top_k_items`` the same answer, scores and indices bit for
bit: at k = 10 on a catalog large enough for the block selection, and at
k = n_items, where the finite scores ARE the complement of the mask, so
the composed mask is compared elementwise. The reference mask is written
here from the lists alone (``np.isin``).
"""

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from incubator_predictionio_tpu.common import telemetry  # noqa: E402
from incubator_predictionio_tpu.data.storage.bimap import BiMap  # noqa: E402
from incubator_predictionio_tpu.models import _filters  # noqa: E402
from incubator_predictionio_tpu.ops import topk  # noqa: E402
from incubator_predictionio_tpu.workflow.context import (  # noqa: E402
    enable_compilation_cache,
)

N_ITEMS, RANK = 40_000, 8
CATS = ("c0", "c1", "c2", "c3")
FLOOR, TOP = topk.ROW_LADDER[0], topk.ROW_LADDER[-1]


def ids(rows) -> list[str]:
    return [f"i{int(j)}" for j in rows]


class Catalog:
    def __init__(self):
        rng = np.random.default_rng(35)
        self.factors = rng.normal(size=(N_ITEMS, RANK)).astype(np.float32)
        self.users = rng.normal(size=(4, RANK)).astype(np.float32)
        self.items = BiMap.string_int(ids(range(N_ITEMS)))
        # every item in one category, a tenth of them in a second one
        self.cat = rng.integers(0, len(CATS), N_ITEMS)
        self.also = np.where(rng.random(N_ITEMS) < 0.1,
                             rng.integers(0, len(CATS), N_ITEMS), -1)
        self.index = _filters.CategoryIndex(self.items, {
            f"i{j}": {CATS[c]} | ({CATS[a]} if a >= 0 else set())
            for j, (c, a) in enumerate(zip(self.cat, self.also))})

    def want_mask(self, categories=None, white=None, black=(), extra=()):
        """bool[N_ITEMS], True = suppressed, from the lists alone."""
        def known(id_list):
            return [int(i[1:]) for i in id_list
                    if i[1:].isdigit() and int(i[1:]) < N_ITEMS]

        gone = np.zeros(N_ITEMS, bool)
        if categories:
            codes = [CATS.index(c) for c in categories if c in CATS]
            gone |= ~(np.isin(self.cat, codes) | np.isin(self.also, codes))
        if white:
            gone |= ~np.isin(np.arange(N_ITEMS), known(white))
        gone[known(black) + known(extra)] = True
        return gone


@pytest.fixture(scope="module")
def catalog():
    return Catalog()


def _draw(rng, size, replace=False):
    return ids(rng.choice(N_ITEMS, size, replace=replace))


def _fixed_cases() -> dict:
    rng = np.random.default_rng(1)
    shelf = _draw(rng, 300)
    return {
        "no-rule": {},
        "one-category": {"categories": ["c1"]},
        "two-categories": {"categories": ["c0", "c3"]},
        "a-category-twice": {"categories": ["c2", "c2"]},
        "unknown-category": {"categories": ["nope"]},
        "unknown-and-known-category": {"categories": ["nope", "c1"]},
        "blackList-with-unknown-ids": {
            "black": _draw(rng, 20) + ["nope", "i999999", ""]},
        "duplicates": {"black": _draw(rng, 50) * 3, "extra": shelf[:10] * 2},
        "extra-only": {"extra": _draw(rng, 2200)},
        "whiteList": {"white": shelf},
        "whiteList-of-unknown-ids-only": {"white": ["nope", "i999999"]},
        "whiteList-with-duplicates-and-unknown": {
            "white": shelf[:40] * 2 + ["nope"]},
        "lists-overlap": {"white": shelf, "black": shelf[:150],
                          "extra": shelf[100:200]},
        "everything": {"categories": ["c0", "c1"], "white": shelf,
                       "black": shelf[::3], "extra": _draw(rng, 2000)},
        # padding at every ladder step: exactly full, and one over
        "deny-fills-the-floor": {"extra": _draw(rng, FLOOR)},
        "deny-one-over-the-floor": {"extra": _draw(rng, FLOOR + 1)},
        "allow-fills-the-floor": {"white": _draw(rng, FLOOR)},
        "allow-one-over-the-floor": {"white": _draw(rng, FLOOR + 1),
                                     "black": _draw(rng, 10)},
        "deny-fills-the-top": {"extra": _draw(rng, TOP, replace=True),
                               "categories": ["c3"]},
        "allow-fills-the-top": {"white": _draw(rng, TOP),
                                "extra": _draw(rng, 3000)},
    }


def _random_case(seed: int) -> dict:
    """A random combination of the four rules, each present or not."""
    rng = np.random.default_rng(1000 + seed)
    rules = {}
    if rng.random() < 0.5:
        rules["categories"] = list(rng.choice(
            CATS + ("nope",), int(rng.integers(1, 4)), replace=False))
    if rng.random() < 0.4:
        rules["white"] = _draw(rng, int(rng.integers(1, 3000)))
        if rng.random() < 0.3:
            rules["white"] += ["nope"] * 3
    if rng.random() < 0.6:
        rules["black"] = _draw(rng, int(rng.integers(1, 200)), replace=True)
    if rng.random() < 0.8:
        rules["extra"] = _draw(rng, int(rng.integers(1, 6000)), replace=True)
    return rules


CASES = {**_fixed_cases(),
         **{f"random-{seed}": _random_case(seed) for seed in range(12)}}


def _build(catalog, rules, rows):
    return _filters.build_exclude(
        catalog.items, catalog.index, rules.get("categories"),
        rules.get("white"), rules.get("black"),
        extra_excluded_items=rules.get("extra"), rows=rows)


def _assert_same(got, want):
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[0], want[0])


@pytest.mark.parametrize("case", CASES)
def test_rows_answer_as_the_dense_mask_bit_for_bit(catalog, case):
    rules = CASES[case]
    gone = catalog.want_mask(**rules)
    dense = _filters.build_exclude_mask(
        catalog.items, catalog.index, rules.get("categories"),
        rules.get("white"), rules.get("black"),
        extra_excluded_items=rules.get("extra"))
    np.testing.assert_array_equal(dense, gone)
    rows = _build(catalog, rules, rows=True)
    assert isinstance(rows, topk.RowExclude)               # all fit the ladder
    assert topk.select_block_len(N_ITEMS, 10)             # the block path
    user = catalog.users[len(case) % len(catalog.users)]
    _assert_same(topk.top_k_items(user, catalog.factors, 10, exclude=rows),
                 topk.top_k_items(user, catalog.factors, 10, exclude=dense))
    # k = n_items: every item comes back, the suppressed ones at -inf, so
    # the mask composed on the device is compared row by row
    scores, idx = topk.top_k_items(user, catalog.factors, N_ITEMS,
                                   exclude=rows)
    _assert_same((scores, idx), topk.top_k_items(
        user, catalog.factors, N_ITEMS, exclude=dense))
    composed = np.ones(N_ITEMS, bool)
    composed[idx[np.isfinite(scores)]] = False
    np.testing.assert_array_equal(composed, gone)


@pytest.mark.parametrize("rules", [
    {"extra": _draw(np.random.default_rng(2), TOP + 1, replace=True)},
    {"white": _draw(np.random.default_rng(3), TOP + 1),
     "categories": ["c1"], "black": ["i5"]},
], ids=["deny-over-the-top", "allow-over-the-top"])
def test_a_list_over_the_ladder_takes_the_dense_path(catalog, rules,
                                                     monkeypatch):
    monkeypatch.setattr(telemetry._STATE, "metrics_on", True)
    paths = {p: _filters._M_MASK_PATH.labels(p) for p in ("device", "dense")}
    before = {p: c.value() for p, c in paths.items()}
    got = _build(catalog, rules, rows=True)
    assert isinstance(got, np.ndarray)
    np.testing.assert_array_equal(got, catalog.want_mask(**rules))
    assert {p: c.value() - before[p] for p, c in paths.items()} == {
        "device": 0, "dense": 1}
    built = [s for s in telemetry.spans_snapshot()
             if s.name == "query.mask_build"][-1]
    assert built.tags["path"] == "dense"
    # and handing such lists to the kernel as rows is refused, not cut
    with pytest.raises(ValueError, match="over the ladder"):
        topk.top_k_items(
            catalog.users[0], catalog.factors, 10, exclude=topk.RowExclude(
                None, np.zeros(TOP + 1, np.int32), None))


def test_the_counter_and_the_tags_say_which_path_ran(catalog, monkeypatch):
    monkeypatch.setattr(telemetry._STATE, "metrics_on", True)
    paths = {p: _filters._M_MASK_PATH.labels(p) for p in ("device", "dense")}
    rules = {"categories": ["c1"], "black": ["i3", "nope"],
             "white": ids(range(0, 600, 2))}
    seen = []
    for rows in (True, False):
        before = {p: c.value() for p, c in paths.items()}
        with telemetry.span("test.query") as root:
            exclude = _build(catalog, rules, rows=rows)
            topk.top_k_items(catalog.users[1], catalog.factors, 10,
                             exclude=exclude)
        mine = [s for s in telemetry.spans_snapshot()
                if s.trace_id == root.trace_id
                and s.parent_id == root.span_id]
        seen.append((
            [(s.name, s.tags) for s in mine
             if s.name in ("query.mask_build", "topk.mask_put")],
            {p: c.value() - before[p] for p, c in paths.items()}))
    tags = {"rules": "categories+whiteList+blackList", "excluded": 1}
    assert seen[0] == (
        [("query.mask_build", {**tags, "path": "device"}),
         ("topk.mask_put", {"bytes": 4 * (2 * FLOOR + 1)})],
        {"device": 1, "dense": 0})
    assert seen[1] == (
        [("query.mask_build", {**tags, "path": "dense"}),
         ("topk.mask_put", {"bytes": N_ITEMS})],
        {"device": 0, "dense": 1})


def test_one_category_passes_the_resident_mask_itself(catalog):
    """No rows to speak of and ONE category: the base is the very array
    that lives on the device (no copy, no dispatch); a category without
    items shares one all-True mask whatever its name."""
    first = _build(catalog, {"categories": ["c2"]}, rows=True)
    again = _build(catalog, {"categories": ["c2"], "black": ["i7"]},
                   rows=True)
    assert isinstance(first.base, jax.Array) and first.base is again.base
    np.testing.assert_array_equal(np.asarray(first.base),
                                  ~catalog.index.mask("c2"))
    assert first.allow is None and len(first.deny) == 0
    assert _build(catalog, {}, rows=True).base is None
    unknown = [_build(catalog, {"categories": [name]}, rows=True).base
               for name in ("nope", "neither")]
    assert unknown[0] is unknown[1] and bool(np.asarray(unknown[0]).all())


def _compiles_since(t0_ns: int) -> int:
    return sum(1 for s in telemetry.spans_snapshot()
               if s.name == "xla.compile" and s.t0_ns >= t0_ns)


def test_lists_of_one_ladder_step_share_one_executable(catalog, monkeypatch):
    """The rows' shapes are the ladder's, never the lists' lengths: a k no
    other test uses compiles once for the floor, whatever the lengths and
    with or without a whiteList, and once more for the step above."""
    monkeypatch.setattr(telemetry._STATE, "metrics_on", True)
    enable_compilation_cache()  # hands jax to telemetry: xla.compile spans
    rng = np.random.default_rng(4)
    user, k = catalog.users[2], 7

    def call(n_deny, n_allow=None):
        t0 = time.perf_counter_ns()
        topk.top_k_items(user, catalog.factors, k, exclude=topk.RowExclude(
            None, rng.choice(N_ITEMS, n_deny).astype(np.int32),
            None if n_allow is None
            else rng.choice(N_ITEMS, n_allow).astype(np.int32)))
        return _compiles_since(t0)

    assert call(5) >= 1
    assert [call(3000), call(FLOOR), call(0, 1), call(17, FLOOR)] \
        == [0, 0, 0, 0]
    assert call(FLOOR + 1) >= 1
    assert [call(TOP, TOP), call(1, FLOOR + 1)] == [0, 0]


def test_no_rows_traces_to_the_call_of_before(catalog):
    """A caller that passes no rows (the recommendation template, the
    sibling cell) lowers to the same program with and without the new
    argument: ``rows`` adds nothing to the jaxpr unless given."""
    user, mask = catalog.users[0], topk.no_exclude_mask(N_ITEMS)
    without = topk._topk_scores.lower(user, catalog.factors, mask, 10)
    given = topk._topk_scores.lower(user, catalog.factors, mask, 10, None)
    assert without.as_text() == given.as_text()
    assert "scatter" not in without.as_text()
    rows = topk.pack_rows(np.zeros(0, np.int32), None, FLOOR, N_ITEMS)
    assert "scatter" in topk._topk_scores.lower(
        user, catalog.factors, mask, 10, rows).as_text()
