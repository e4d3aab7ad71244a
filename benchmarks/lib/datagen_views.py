"""Seeded events for the ``similarproduct-views`` deployment: a shop's day of
click-stream as the event server's request bodies, ``view`` events of users
on items and the ``$set`` / ``$unset`` / ``$delete`` events that keep each
item's ``categories``. Nothing here imports the program.

The distinct (user, item) pairs are the event-log sibling's
(``datagen_eventlog``: the configuration's two degree sequences, the same
for every ``--seed``, paired by the seed with no pair twice), so the layout
plan and the train step's executable are one for every seed. A pair is
viewed ``c`` times, P(c) = 0.8 x 0.2^(c-1) capped at ``view_count_cap``,
drawn by the pair's rank from ``shape_seed``: every seed's log holds the
same number of events. ``--seed`` draws the pairing, the order of arrival,
each item's categories and which items are touched again.

Every item is ``$set`` once with 1-3 categories just before its first view;
of the items, ``reset_share`` are ``$set`` again later with other categories
(the last wins), ``unset_share`` have their categories ``$unset``,
``delete_share`` are ``$delete``d and ``$set`` anew. ``final_members`` is
the generator's own replay of that: what a train must find.

A request body is a JSON array of events, every row the same width (JSON
allows blanks between tokens), so a chunk of bodies is one uint8 matrix
filled by columns; ``native.ingest_batch`` then writes the canonical lines.
"""

from __future__ import annotations

import numpy as np

import datagen
import datagen_ecomm
import datagen_eventlog
from datagen_eventlog import DAY_START_MS

CATEGORIES = datagen_ecomm.CATEGORIES
VIEW, SET, UNSET, DELETE = 0, 1, 2, 3
COUNT_STREAM, CATEGORY_STREAM, TOUCH_STREAM, QUERY_STREAM = 21, 22, 23, 24
#: the share of items with one, two and three categories
CATEGORY_COUNTS = (0.5, 0.3, 0.2)

_VIEW = (b'{"event":"view","entityType":"user","entityId":"%s",'
         b'"targetEntityType":"item","targetEntityId":"%s",'
         b'"eventTime":"2014-07-01T%s"')
_SET = (b'{"event":"$set","entityType":"item","entityId":"%s",'
        b'"eventTime":"2014-07-01T%s","properties":{"categories":[%s]}')
_UNSET = (b'{"event":"$unset","entityType":"item","entityId":"%s",'
          b'"eventTime":"2014-07-01T%s","properties":{"categories":null}')
_DELETE = (b'{"event":"$delete","entityType":"item","entityId":"%s",'
           b'"eventTime":"2014-07-01T%s"')
_USER_LEN, _ITEM_LEN, _CLOCK_LEN = 14, 10, len(b"00:00:00.000Z")
#: three names in quotes with two commas, the longest three
_NAMES_LEN = sum(sorted(len(c) + 2 for c in CATEGORIES)[-3:]) + 2


def view_counts(cfg: dict) -> np.ndarray:
    """int32[n_ratings]: how often the pair of each rank is viewed; a
    function of the configuration alone."""
    rng = np.random.default_rng([int(cfg["shape_seed"]), COUNT_STREAM])
    c = rng.geometric(1.0 - float(cfg["view_repeat_p"]), cfg["n_ratings"])
    return np.minimum(c, int(cfg["view_count_cap"])).astype(np.int32)


def _combos(cfg: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """int8[n, 3]: 1-3 different categories a row by the e-commerce
    sibling's zipfian shares, -1 where a row has fewer."""
    shares = datagen_ecomm.category_shares(cfg)
    cdf = np.cumsum(shares)
    out = np.searchsorted(cdf, rng.random((n, 3)) * cdf[-1]).astype(np.int8)
    # a repeated category moves on to the next one
    out[:, 1] = np.where(out[:, 1] == out[:, 0],
                         (out[:, 1] + 1) % len(shares), out[:, 1])
    for _ in range(2):
        clash = (out[:, 2] == out[:, 0]) | (out[:, 2] == out[:, 1])
        out[:, 2] = np.where(clash, (out[:, 2] + 1) % len(shares), out[:, 2])
    many = np.searchsorted(np.cumsum(CATEGORY_COUNTS), rng.random(n)) + 1
    out[many < 2, 1] = -1
    out[many < 3, 2] = -1
    return out


def events(cfg: dict, seed: int, degs=None) -> dict:
    """One seed's events in the order they arrive: ``kind`` (VIEW, SET,
    UNSET, DELETE), ``user`` (int32 row, -1 for an item's own event),
    ``item``, ``combo`` (int8[., 3]: the categories a SET carries) and
    ``time_ms`` (never falling). Besides: ``pairs`` = (user, item, count)
    of the distinct pairs, ``first`` / ``again`` (each item's first and
    later categories), ``reset`` / ``unset`` / ``deleted`` (the item rows
    touched again)."""
    du, di = degs if degs is not None else datagen.degrees(cfg)
    n_items = cfg["n_items"]
    rng = np.random.default_rng(int(seed))
    pu = np.repeat(np.arange(cfg["n_users"], dtype=np.int32), du)
    pi = np.repeat(np.arange(n_items, dtype=np.int32), di)
    pi = datagen_eventlog._no_pair_twice(pu, pi[rng.permutation(len(pi))],
                                         rng)
    pc = view_counts(cfg)
    arrival = rng.permutation(int(pc.sum()))
    vu, vi = np.repeat(pu, pc)[arrival], np.repeat(pi, pc)[arrival]
    n_views = len(vu)
    seen, first_view = np.unique(vi, return_index=True)
    if len(seen) != n_items:
        raise ValueError("an item without a view: the degrees give each one")

    crng = np.random.default_rng([int(seed), CATEGORY_STREAM])
    first = _combos(cfg, crng, n_items)
    again = _combos(cfg, crng, n_items)
    same = (again == first).all(axis=1)
    again[same, 0] = (again[same, 0] + 1) % len(CATEGORIES)
    # ... which may now repeat a later one of the row: drop those
    again[same, 1:] = -1
    trng = np.random.default_rng([int(seed), TOUCH_STREAM])
    touched = trng.permutation(n_items)
    n_reset = int(round(cfg["reset_share"] * n_items))
    n_unset = int(round(cfg["unset_share"] * n_items))
    n_delete = int(round(cfg["delete_share"] * n_items))
    reset = touched[:n_reset]
    unset = touched[n_reset:n_reset + n_unset]
    deleted = touched[n_reset + n_unset:n_reset + n_unset + n_delete]

    def later(items):
        """A place in the arrival order after the item's first view."""
        return first_view[items] + (trng.random(len(items))
                                    * (n_views - first_view[items])
                                    ).astype(np.int64)

    gone, anew = later(deleted), later(deleted)
    gone, anew = np.minimum(gone, anew), np.maximum(gone, anew)
    none = np.full((1, 3), -1, np.int8)
    # sort key: 4 x place, +0 before the view there, +2 the view, +3 after
    parts = [
        (VIEW, vu, vi, np.arange(n_views) * 4 + 2, none),
        (SET, None, np.arange(n_items), first_view * 4, first),
        (SET, None, reset, later(reset) * 4 + 3, again[reset]),
        (UNSET, None, unset, later(unset) * 4 + 3, none),
        (DELETE, None, deleted, gone * 4 + 3, none),
        (SET, None, deleted, anew * 4 + 3, again[deleted]),
    ]
    kind = np.concatenate([np.full(len(p[2]), p[0], np.int8) for p in parts])
    user = np.concatenate([p[1] if p[1] is not None
                           else np.full(len(p[2]), -1, np.int32)
                           for p in parts]).astype(np.int32)
    item = np.concatenate([p[2] for p in parts]).astype(np.int32)
    combo = np.concatenate([np.broadcast_to(p[4], (len(p[2]), 3))
                            for p in parts])
    # stable: a DELETE and its SET at one place keep that order
    order = np.argsort(np.concatenate([p[3] for p in parts]), kind="stable")
    time_ms = DAY_START_MS + np.cumsum(rng.integers(0, 2, len(order)))
    return {"kind": kind[order], "user": user[order], "item": item[order],
            "combo": combo[order], "time_ms": time_ms,
            "pairs": (pu, pi, pc), "first": first, "again": again,
            "reset": reset, "unset": unset, "deleted": deleted}


def views_of(ev: dict) -> tuple[np.ndarray, np.ndarray]:
    """(user, item) of the ``view`` events in arrival order."""
    is_view = ev["kind"] == VIEW
    return ev["user"][is_view], ev["item"][is_view]


def members_of(combo: np.ndarray) -> np.ndarray:
    """bool[n, 24]: the categories of each row of int8[n, 3] combos."""
    out = np.zeros((len(combo), len(CATEGORIES)), bool)
    for col in combo.T:
        has = col >= 0
        out[np.nonzero(has)[0], col[has]] = True
    return out


def final_members(ev: dict, ignore_reset: bool = False) -> np.ndarray:
    """bool[n_items, 24]: each item's categories once the day is over (no
    True in a row: the item has none and a train must not list it).
    ``ignore_reset``: the fault of a replay that keeps an item's FIRST
    ``$set`` (the control)."""
    out = members_of(ev["first"])
    touched = ev["deleted"] if ignore_reset else np.concatenate(
        [ev["reset"], ev["deleted"]])
    out[touched] = members_of(ev["again"][touched])
    out[ev["unset"]] = False
    return out


# -- request bodies --------------------------------------------------------------


def _names(combo: np.ndarray) -> np.ndarray:
    """[n, _NAMES_LEN] ASCII: ``"A","B"`` and blanks, for each combo row."""
    code = ((combo[:, 0].astype(np.int64) + 1) * 625
            + (combo[:, 1].astype(np.int64) + 1) * 25
            + (combo[:, 2].astype(np.int64) + 1))
    uniq, inverse = np.unique(code, return_inverse=True)
    table = np.full((len(uniq), _NAMES_LEN), ord(" "), np.uint8)
    for row, c in enumerate(uniq.tolist()):
        idx = [d - 1 for d in (c // 625, c // 25 % 25, c % 25) if d > 0]
        text = ",".join('"%s"' % CATEGORIES[j] for j in idx).encode()
        table[row, :len(text)] = np.frombuffer(text, np.uint8)
    return table[inverse]


def _template(form: bytes, fields: tuple, width: int):
    """(the row padded with blanks to ``width`` and closed, where each
    field starts); ``fields``: (mark byte, length) in the form's order."""
    marks = tuple(bytes([m]) * n for m, n in fields)
    row = form % marks
    row += b" " * (width - len(row) - 2) + b"},"
    return (np.frombuffer(row, np.uint8),) + tuple(row.index(m)
                                                   for m in marks)


def _forms():
    id_, clock = (1, _ITEM_LEN), (3, _CLOCK_LEN)
    width = len(_SET % (b"i" * _ITEM_LEN, b"c" * _CLOCK_LEN,
                        b"n" * _NAMES_LEN)) + 2
    return {
        VIEW: _template(_VIEW, ((2, _USER_LEN), id_, clock), width),
        SET: _template(_SET, (id_, clock, (4, _NAMES_LEN)), width),
        UNSET: _template(_UNSET, (id_, clock), width),
        DELETE: _template(_DELETE, (id_, clock), width),
    }


def bodies(ev: dict, uid: np.ndarray, iid: np.ndarray, chunk: int):
    """Request bodies of at most ``chunk`` events each, in arrival order:
    (JSON array as bytes, number of events)."""
    forms = _forms()
    table = np.stack([forms[k][0] for k in (VIEW, SET, UNSET, DELETE)])
    if int(ev["time_ms"][-1]) - DAY_START_MS >= 86400000:
        raise ValueError("the events do not fit the day")
    for lo in range(0, len(ev["kind"]), chunk):
        sl = slice(lo, lo + chunk)
        kind = ev["kind"][sl]
        rows = table[kind]
        clock = datagen_eventlog._clock(ev["time_ms"][sl] - DAY_START_MS)
        items = iid[ev["item"][sl]]
        for k in (VIEW, SET, UNSET, DELETE):
            at = np.nonzero(kind == k)[0]
            if not len(at):
                continue
            starts = forms[k][1:]
            if k == VIEW:
                rows[at, starts[0]:starts[0] + _USER_LEN] = \
                    uid[ev["user"][sl][at]]
                starts = starts[1:]
            rows[at, starts[0]:starts[0] + _ITEM_LEN] = items[at]
            rows[at, starts[1]:starts[1] + _CLOCK_LEN] = clock[at]
            if k == SET:
                rows[at, starts[2]:starts[2] + _NAMES_LEN] = _names(
                    ev["combo"][sl][at])
        yield b"[" + rows.tobytes()[:-1] + b"]", len(rows)


# -- the sampled queries ----------------------------------------------------------


def queries(cfg: dict, seed: int, members: np.ndarray, n: int) -> list[dict]:
    """``n`` similar-product queries by item ROWS of the generator:
    ``{"items": 1-3 rows, "num", "categories": indices or None, "white":
    rows or None, "black": rows or None}``; the shares of the rules are the
    configuration's ``query_rule_shares``. A blackList that should bite is
    the deployment's to add (it needs the trained factors)."""
    rng = np.random.default_rng([int(seed), QUERY_STREAM])
    shares = datagen_ecomm.category_shares(cfg)
    rules = ("none", "categories", "whiteList", "blackList")
    p = [float(cfg["query_rule_shares"][r]) for r in rules]
    out = []
    for _ in range(n):
        q = {"items": rng.choice(cfg["n_items"], int(rng.integers(1, 4)),
                                 replace=False),
             "num": 10 if rng.random() < 0.8 else 4,
             "categories": None, "white": None, "black": None}
        rule = rules[int(rng.choice(len(rules), p=p))]
        if rule == "categories":
            q["categories"] = [int(rng.choice(len(shares), p=shares))]
        elif rule == "whiteList":
            inside = np.nonzero(
                members[:, int(rng.choice(len(shares), p=shares))])[0]
            size = min(int(rng.integers(50, 501)), len(inside))
            q["white"] = rng.choice(inside, size, replace=False)
        elif rule == "blackList":
            q["black"] = rng.choice(cfg["n_items"],
                                    int(rng.integers(1, 21)), replace=False)
        out.append(q)
    return out
