"""Seeded events for the ``recommendation-eventlog`` deployment: what an
application posts to the event server over a day, as request bodies. Nothing
here imports the program.

The two degree sequences are the sibling's (``datagen.degrees``: the
configuration's ``shape_seed``, the same for every ``--seed``), so the layout
plan and the train step's executable are too; ``--seed`` pairs user stubs
with item stubs, says which events are a ``buy``, draws the stars and the
order of arrival. No (user, item) pair occurs twice: a pair the pairing drew
twice swaps its item with another event's, which keeps both degree sequences.

Ids are shaped as the data set's: a ``reviewerID`` of 14 characters ("A" and
13 of 0-9A-Z), an ``asin`` of 10 ("B0" and 8). They are a function of the
row's number alone (a multiplication that is a bijection, written in base
36), so a seed is another day of the same shop.

A request body is a JSON array of events, every row the same width (JSON
allows blanks between tokens), so a chunk of bodies is one uint8 matrix
filled by columns; ``native.ingest_batch`` then writes the canonical lines.
"""

from __future__ import annotations

import numpy as np

import datagen

_ALNUM = np.frombuffer(b"0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZ", np.uint8)
_DIGIT = np.frombuffer(b"0123456789", np.uint8)

#: 2014-07-01T00:00:00Z, the data set's last month, in epoch milliseconds
DAY_START_MS = 1404172800000

_RATE = (b'{"event":"rate","entityType":"user","entityId":"%s",'
         b'"targetEntityType":"item","targetEntityId":"%s",'
         b'"eventTime":"2014-07-01T%s","properties":{"rating":R}},')
_BUY = (b'{"event":"buy" ,"entityType":"user","entityId":"%s",'
        b'"targetEntityType":"item","targetEntityId":"%s",'
        b'"eventTime":"2014-07-01T%s"')
_USER_LEN, _ITEM_LEN, _CLOCK_LEN = 14, 10, len(b"00:00:00.000Z")


def _base36(x: np.ndarray, width: int) -> np.ndarray:
    """[n, width] ASCII digits of ``x`` in base 36, most significant first."""
    out = np.empty((len(x), width), np.uint8)
    x = x.astype(np.uint64)
    for k in range(width - 1, -1, -1):
        x, d = np.divmod(x, np.uint64(36))
        out[:, k] = _ALNUM[d]
    return out


def user_ids(n_users: int) -> np.ndarray:
    """[n_users, 14] uint8: row k is user k's id. The product wraps modulo
    2**64 (an odd factor: a bijection), and 2**64 < 36**13."""
    x = np.arange(1, n_users + 1, dtype=np.uint64) * np.uint64(
        0x9E3779B97F4A7C15)
    out = np.empty((n_users, _USER_LEN), np.uint8)
    out[:, 0] = ord("A")
    out[:, 1:] = _base36(x, _USER_LEN - 1)
    return out


def item_ids(n_items: int) -> np.ndarray:
    """[n_items, 10] uint8: row k is item k's id (the factor has no divisor
    in common with 36**8: a bijection on the residues)."""
    x = (np.arange(1, n_items + 1, dtype=np.uint64) * np.uint64(2654435761)
         ) % np.uint64(36 ** 8)
    out = np.empty((n_items, _ITEM_LEN), np.uint8)
    out[:, 0], out[:, 1] = ord("B"), ord("0")
    out[:, 2:] = _base36(x, _ITEM_LEN - 2)
    return out


def as_strings(ids: np.ndarray) -> list[str]:
    """The id matrix as Python strings, row by row."""
    width = ids.shape[1]
    return np.ascontiguousarray(ids).view(f"S{width}").ravel().astype(
        f"U{width}").tolist()


def _no_pair_twice(u: np.ndarray, i: np.ndarray,
                   rng: np.random.Generator) -> np.ndarray:
    """``i`` with the items of repeated (user, item) pairs swapped against
    other events' items until no pair is left twice."""
    i = i.copy()
    n_items = int(i.max()) + 1
    for _ in range(256):
        key = u.astype(np.int64) * n_items + i
        order = np.argsort(key, kind="stable")
        again = order[1:][key[order][1:] == key[order][:-1]]
        if not len(again):
            return i
        # disjoint transpositions: a partner is drawn once and is no
        # repeat itself, so the items stay the multiset they were
        other = rng.integers(0, len(i), len(again))
        once = np.zeros(len(again), bool)
        once[np.unique(other, return_index=True)[1]] = True
        once &= ~np.isin(other, again)
        again, other = again[once], other[once]
        i[again], i[other] = i[other], i[again]
    raise ValueError("these degrees leave pairs twice: too few rows for "
                     "the heaviest user or item")


def events(cfg: dict, seed: int, degs=None) -> dict:
    """One seed's events in the order they arrive: ``user`` and ``item``
    (int32 rows of the id tables), ``buy`` (bool), ``stars`` (1..5, what a
    ``rate`` event carries), ``time_ms`` (epoch milliseconds, never falling,
    several events a millisecond) and ``rating``: what the template makes of
    the event, the stars of a ``rate`` and ``buy_rating`` for a ``buy``."""
    du, di = degs if degs is not None else datagen.degrees(cfg)
    rng = np.random.default_rng(int(seed))
    u = np.repeat(np.arange(cfg["n_users"], dtype=np.int32), du)
    i = np.repeat(np.arange(cfg["n_items"], dtype=np.int32), di)
    i = _no_pair_twice(u, i[rng.permutation(len(i))], rng)
    arrival = rng.permutation(len(u))
    u, i = u[arrival], i[arrival]
    buy = rng.random(len(u)) < cfg["buy_share"]
    stars = rng.integers(1, 6, len(u), dtype=np.uint8)
    time_ms = DAY_START_MS + np.cumsum(rng.integers(0, 2, len(u)))
    rating = np.where(buy, np.float32(cfg["buy_rating"]),
                      stars.astype(np.float32))
    return {"user": u, "item": i, "buy": buy, "stars": stars,
            "time_ms": time_ms, "rating": rating}


def _clock(ms_of_day: np.ndarray) -> np.ndarray:
    """[n, 13] ASCII ``HH:MM:SS.mmmZ``."""
    out = np.empty((len(ms_of_day), _CLOCK_LEN), np.uint8)
    out[:, [2, 5]], out[:, 8], out[:, 12] = ord(":"), ord("."), ord("Z")
    ms = ms_of_day.astype(np.int64)
    fields = ((ms // 3600000, (0, 1)), (ms // 60000 % 60, (3, 4)),
              (ms // 1000 % 60, (6, 7)))
    for value, (tens, ones) in fields:
        out[:, tens], out[:, ones] = _DIGIT[value // 10], _DIGIT[value % 10]
    sub = ms % 1000
    out[:, 9], out[:, 10], out[:, 11] = (
        _DIGIT[sub // 100], _DIGIT[sub // 10 % 10], _DIGIT[sub % 10])
    return out


def _template(form: bytes, width: int = 0):
    """(the row, where the user id, the item id and the clock start);
    a form with no closing brace gets blanks up to ``width`` before it."""
    marks = (b"\x01" * _USER_LEN, b"\x02" * _ITEM_LEN, b"\x03" * _CLOCK_LEN)
    row = form % marks
    if width:
        row += b" " * (width - len(row) - 2) + b"},"
    return (np.frombuffer(row, np.uint8), *(row.index(m) for m in marks))


def bodies(ev: dict, uid: np.ndarray, iid: np.ndarray, chunk: int):
    """Request bodies of at most ``chunk`` events each, in arrival order:
    (JSON array as bytes, number of events)."""
    rate, at_user, at_item, at_clock = _template(_RATE)
    # a buy carries no properties: blanks up to the rate row's width
    buy_row = _template(_BUY, len(rate))[0]
    at_stars = int(np.nonzero(rate == ord("R"))[0][-1])
    if int(ev["time_ms"][-1]) - DAY_START_MS >= 86400000:
        raise ValueError("the events do not fit the day")
    for lo in range(0, len(ev["user"]), chunk):
        sl = slice(lo, lo + chunk)
        is_buy = ev["buy"][sl]
        rows = np.where(is_buy[:, None], buy_row, rate)
        rows[:, at_user:at_user + _USER_LEN] = uid[ev["user"][sl]]
        rows[:, at_item:at_item + _ITEM_LEN] = iid[ev["item"][sl]]
        rows[:, at_clock:at_clock + _CLOCK_LEN] = _clock(
            ev["time_ms"][sl] - DAY_START_MS)
        rows[~is_buy, at_stars] = _DIGIT[ev["stars"][sl][~is_buy]]
        yield b"[" + rows.tobytes()[:-1] + b"]", len(rows)
