"""The plain reference of the served Universal Recommender deployment, in
NumPy, computed in blocks. It imports nothing of the program: no index on
the device, no event store, no ``models/_filters.py``.

The query's definition: ``score_i = boost_i * sum over event types e of sum
over slots s of score_e[i, s] * [idx_e[i, s] in history_e]``, padding slots
(``idx < 0``) count nothing, a query item joins the history of every type,
a ``fields`` rule with a bias under 0 keeps only its category's items and
one with a bias of 0 or more multiplies their scores, the blacklist, the
query items and the user's history of the primary event never appear, only
scores over 0 are answers, and a request with no history at all gets the
popularity ranking under the same rules. Sums are float64.

At 9.4M items a request's sum cannot be taken row by row over the forward
arrays for every request (256 requests x 940M slots), so the forward arrays
are passed ONCE, block by block (`Slots.add`: which slots name a row that
some request's history holds), and each request then sums its own slots.
``tests/test_ur_serving.py`` holds the same definition written out densely,
and so does `dense_top` below, which
``benchmarks/tests/test_ur_served_deployment.py`` holds this file against.

A request is ``{"history": {event name: item rows}, "item": row or None,
"category": index or None, "bias": float, "blacklist": rows, "num"}``.
"""

from __future__ import annotations

import numpy as np

#: the largest ``num`` a traffic mix may ask for
MAX_NUM = 32


def rows_of(q: dict, events) -> dict:
    """{event name: sorted distinct rows}: the history, the query item in
    every type's."""
    extra = [] if q.get("item") is None else [q["item"]]
    return {e: np.unique(np.concatenate([
        np.asarray(q["history"].get(e, ()), np.int64),
        np.asarray(extra, np.int64)])) for e in events}


class Slots:
    """The slots of the forward arrays that name one of ``wanted[e]`` (the
    rows some request's history of event type ``e`` holds), gathered block
    by block and grouped by the row they name."""

    def __init__(self, n_items: int, wanted: dict, lower=None):
        self.lower = lower
        self.wanted = {e: np.unique(np.asarray(w, np.int64))
                       for e, w in wanted.items()}
        self.place = {}
        for e, w in self.wanted.items():
            # place[row + 1]: where the row stands in wanted[e], or -1; the
            # padding slot -1 looks up place[0]
            table = np.full(n_items + 1, -1, np.int32)
            table[w + 1] = np.arange(len(w), dtype=np.int32)
            self.place[e] = table
        #: per event type {first row of a block: what `add` found in it}
        self.parts = {e: {} for e in wanted}
        self.runs = None

    def add(self, e, row0: int, idx: np.ndarray, score: np.ndarray) -> None:
        """One block of event type ``e``'s forward arrays, rows ``row0``
        on. Blocks may come in any order and from several threads: `close`
        puts them in row order."""
        place = self.place[e][idx + 1]
        rows, slots = np.nonzero(place >= 0)
        found = score[rows, slots]
        if self.lower is not None:
            found = found.astype(self.lower)
        self.parts[e][row0] = (place[rows, slots], rows + row0,
                               found.astype(np.float64))

    def close(self) -> None:
        self.runs = {}
        for e, by_row in self.parts.items():
            parts = [by_row[row0] for row0 in sorted(by_row)]
            place, item, score = (np.concatenate([p[j] for p in parts])
                                  if parts else np.empty(0, t)
                                  for j, t in enumerate(
                                      (np.int32, np.int64, np.float64)))
            order = np.argsort(place, kind="stable")
            starts = np.searchsorted(place[order],
                                     np.arange(len(self.wanted[e]) + 1))
            self.runs[e] = (starts, item[order], score[order])
        self.parts = None

    def sums(self, rows: dict) -> tuple[np.ndarray, np.ndarray]:
        """(items ascending, float64 sums) over every slot that names a
        row of ``rows`` ({event name: rows}, each among ``wanted``)."""
        items, scores = [np.empty(0, np.int64)], [np.empty(0, np.float64)]
        for e, history in rows.items():
            starts, item, score = self.runs[e]
            at = np.searchsorted(self.wanted[e], history)
            assert (self.wanted[e][at] == history).all(), "row not gathered"
            for a in at:
                items.append(item[starts[a]:starts[a + 1]])
                scores.append(score[starts[a]:starts[a + 1]])
        items, scores = np.concatenate(items), np.concatenate(scores)
        found, inverse = np.unique(items, return_inverse=True)
        return found, np.bincount(inverse, weights=scores,
                                  minlength=len(found))


def in_turn(blocks, events):
    """`gather`'s ``each`` of ``blocks(event number)`` -> (first row, idx,
    score) of each block of that type: one block after the other."""
    def each(work) -> None:
        for number in range(len(events)):
            for row0, idx, score in blocks(number):
                work(number, row0, idx, score)
    return each


def gather(cfg: dict, each, requests: list[dict], lower=None) -> Slots:
    """`Slots` of everything ``requests`` need. ``each(work)`` calls
    ``work(event number, first row, idx, score)`` once for every block of
    every event type's forward arrays, in any order, on threads of its own
    choosing (`in_turn`; ``datagen_ur_serve.each_block``)."""
    events = cfg["eventNames"]
    wanted = {e: [np.empty(0, np.int64)] for e in events}
    for q in requests:
        for e, rows in rows_of(q, events).items():
            wanted[e].append(rows)
    slots = Slots(cfg["n_items"], {e: np.concatenate(w)
                                   for e, w in wanted.items()}, lower)
    each(lambda number, row0, idx, score: slots.add(
        events[number], row0, idx, score))
    slots.close()
    return slots


def forbidden_of(q: dict, events, ignore: str | None = None) -> np.ndarray:
    """The rows a request may not return: its blacklist, its query item,
    its history of the primary event. ``ignore`` plants a fault: one of
    the three is left out."""
    parts = {"blacklist": q.get("blacklist", ()),
             "item": [] if q.get("item") is None else [q["item"]],
             "history": q["history"].get(events[0], ())}
    return np.unique(np.concatenate([np.asarray(rows, np.int64)
                                     for what, rows in parts.items()
                                     if what != ignore] +
                                    [np.empty(0, np.int64)]))


def top(cfg: dict, slots: Slots, popularity: np.ndarray, cats: np.ndarray,
        q: dict, ignore: str | None = None, only=None) -> dict:
    """``{"items", "scores"}``: the request's best allowed items, at most
    MAX_NUM, best first (score descending, then row ascending), scores
    float64; ``"scale"``, its best score before any rule; and ``"all"``,
    (rows ascending, boosted scores) of every item that scores at all,
    before any exclusion. ``ignore`` plants a fault: ``blacklist``,
    ``item``, ``history`` (a forbidden row may be returned) or ``filter``
    (the category is ignored). ``only``: the event types that count (a
    control drops one)."""
    events = cfg["eventNames"]
    rows = rows_of(q, events)
    if any(len(r) for r in rows.values()):
        items, total = slots.sums({e: r for e, r in rows.items()
                                   if only is None or e in only})
    else:
        items = np.flatnonzero(popularity > 0)
        total = popularity[items].astype(np.float64)
    scale = float(total.max()) if len(total) else 1.0
    keep = total > 0
    if q.get("category") is not None:
        match = cats[items] == q["category"]
        if q["bias"] >= 0:
            total = np.where(match, total * q["bias"], total)
            keep = total > 0
        elif ignore != "filter":
            keep &= match
    everything = (items, total)
    keep &= ~np.isin(items, forbidden_of(q, events, ignore))
    items, total = items[keep], total[keep]
    order = np.lexsort((items, -total))[:MAX_NUM]
    return {"items": items[order], "scores": total[order], "scale": scale,
            "all": everything}


def exact_scores(ref: dict, ids: np.ndarray) -> np.ndarray:
    """The reference score of each of ``ids`` (boosted, before the
    exclusions; 0 where nothing names the item), from ``ref["all"]``."""
    items, total = ref["all"]
    if not len(items):
        return np.zeros(len(ids))
    at = np.minimum(np.searchsorted(items, ids), len(items) - 1)
    return np.where(items[at] == ids, total[at], 0.0)


def allowed(cfg: dict, cats: np.ndarray, q: dict, ids: np.ndarray
            ) -> np.ndarray:
    ok = ~np.isin(ids, forbidden_of(q, cfg["eventNames"]))
    if q.get("category") is not None and q["bias"] < 0:
        ok &= cats[ids] == q["category"]
    return ok


def gaps(cfg: dict, slots: Slots, popularity: np.ndarray, cats: np.ndarray,
         requests: list[dict], served: list, want=None) -> dict:
    """``served``: per request ``{"items": [rows], "scores": [floats]}`` as
    answered (None where no answer was kept). Against the reference:

    - ``rank_gap``: how far the reference score of the j-th served item
      lies below the reference's own j-th best ALLOWED score (0 where the
      served list is a top-num of the allowed, ties in any order);
    - ``score_gap``: how far a served score is from the reference score of
      that item; both over ``scale``, the request's best score;
    - ``leak``: served items that a rule forbids, or that nothing scores;
    - ``fill_gap``: answers shorter than min(num, the allowed items that
      score over 0);
    - ``malformed``: an id outside the catalog, a repeated id, more than
      ``num`` items, items without a score each."""
    n_items = cfg["n_items"]
    out = {"rank_gap": 0.0, "score_gap": 0.0, "leak": 0, "fill_gap": 0,
           "malformed": 0, "compared": 0}
    for k, (q, got) in enumerate(zip(requests, served)):
        if got is None:
            continue
        out["compared"] += 1
        ref = want[k] if want is not None else top(
            cfg, slots, popularity, cats, q)
        ids = np.asarray(got["items"], np.int64)
        if (len(ids) > q["num"] or len(got["scores"]) != len(ids)
                or len(np.unique(ids)) != len(ids) or (ids < 0).any()
                or (ids >= n_items).any() or q["num"] > MAX_NUM):
            out["malformed"] += 1
            continue
        out["fill_gap"] += len(ids) < min(q["num"], len(ref["items"]))
        if not len(ids):
            continue
        exact = exact_scores(ref, ids)
        out["leak"] += int((~allowed(cfg, cats, q, ids)
                            | (exact <= 0)).sum())
        n = min(len(ids), len(ref["scores"]))
        if n:
            out["rank_gap"] = max(out["rank_gap"], float(
                (ref["scores"][:n] - exact[:n]).max() / ref["scale"]))
        out["score_gap"] = max(out["score_gap"], float(np.abs(
            np.asarray(got["scores"], np.float64) - exact).max()
            / ref["scale"]))
    return out


def answer_of(ref: dict, num: int) -> dict:
    """A reference answer put in a server's place (the controls)."""
    return {"items": ref["items"][:num].tolist(),
            "scores": ref["scores"][:num].tolist()}


def dense_top(cfg: dict, forward: dict, popularity: np.ndarray,
              cats: np.ndarray, q: dict) -> tuple[np.ndarray, np.ndarray]:
    """The definition written out densely, for a small catalog (the test
    of this file): (items, float64 scores) best first, at most MAX_NUM."""
    n = cfg["n_items"]
    rows = rows_of(q, cfg["eventNames"])
    if any(len(r) for r in rows.values()):
        total = np.zeros(n, np.float64)
        for e, (idx, score) in forward.items():
            member = np.zeros(n + 1, np.float64)
            member[rows[e]] = 1.0
            hit = np.where(idx >= 0, member[idx], 0.0)
            total += (score.astype(np.float64) * hit).sum(axis=1)
    else:
        total = popularity.astype(np.float64)
    if q.get("category") is not None:
        match = cats == q["category"]
        total = (np.where(match, total, -np.inf) if q["bias"] < 0
                 else np.where(match, total * q["bias"], total))
    total[forbidden_of(q, cfg["eventNames"])] = -np.inf
    order = np.lexsort((np.arange(n), -total))[:MAX_NUM]
    order = order[total[order] > 0]
    return order, total[order]
