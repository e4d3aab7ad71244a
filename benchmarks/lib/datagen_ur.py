"""Seeded events for the Universal Recommender deployment: one rating per
(user, item) at a rating data set's shape, read as two indicators. Nothing
here imports the program.

As in ``datagen.py`` everything that shapes the program's layout comes from
the configuration's own ``shape_seed`` and is the same for every ``--seed``:
the ratings per user, the ratings per item, and the stars each user gives
(so how many of a user's ratings are a ``buy``). ``--seed`` decides which
items a user rates: a random pairing of the user stubs with the item stubs,
repaired by swaps until no (user, item) pair occurs twice. A rating is a
``view`` event; one of ``buy_min_stars`` or more is a ``buy`` event too. So
every seed has ``n_ratings`` distinct pairs, the data set's star histogram to
the unit, the same users and items at the same activity, and other pairs:
one layout and one executable, other slab contents, as the ALS cells have.
"""

from __future__ import annotations

import numpy as np

#: rounds of swaps before ``pair_stubs`` gives up: the degree sequences then
#: leave (almost) no simple graph, which is the configuration's fault
MAX_ROUNDS = 400


def degree_sequence(n_rows: int, total: int, sigma: float, floor: int,
                    cap: int, rng: np.random.Generator) -> np.ndarray:
    """``n_rows`` degrees >= ``floor`` that sum to ``total``: ``floor``
    each, the rest multinomial over log-normal weights that are clipped so
    that no row expects more than ``cap`` (a plain log-normal that fits the
    data set's median and mean puts a dozen items above the number of
    users)."""
    spare = total - floor * n_rows
    if spare < 0:
        raise ValueError(f"{total} ratings cannot give {n_rows} rows "
                         f"{floor} each")
    w = rng.lognormal(0.0, sigma, n_rows)
    for _ in range(16):
        w = np.minimum(w, (cap - floor) * w.sum() / spare)
    return floor + rng.multinomial(spare, w / w.sum())


def star_counts(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """(star values ascending, ratings at each) that sum to ``n_ratings``:
    the configuration's histogram itself where it does, else scaled (a
    rehearsal), the rounding's remainder on the commonest value."""
    stars = sorted((float(s), int(c))
                   for s, c in cfg["star_histogram"].items())
    values = np.array([s for s, _ in stars])
    counts = np.array([c for _, c in stars], np.int64)
    if counts.sum() != cfg["n_ratings"]:
        counts = counts * cfg["n_ratings"] // counts.sum()
        counts[counts.argmax()] += cfg["n_ratings"] - counts.sum()
    return values, counts


def degrees(cfg: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(ratings per user, ratings per item, bought [n_ratings] bool: whether
    each rating slot, user by user, is a ``buy``): a function of the counts,
    the floors and caps, the histogram and ``shape_seed`` alone."""
    rng = np.random.default_rng(int(cfg["shape_seed"]))
    du = degree_sequence(cfg["n_users"], cfg["n_ratings"],
                         cfg["user_degree_sigma"], cfg["min_user_ratings"],
                         cfg["max_user_ratings"], rng)
    di = degree_sequence(cfg["n_items"], cfg["n_ratings"],
                         cfg["item_degree_sigma"], 1,
                         cfg["max_item_ratings"], rng)
    values, counts = star_counts(cfg)
    slot_stars = rng.permutation(np.repeat(values, counts))
    return du, di, slot_stars >= cfg["buy_min_stars"]


def _draw(weight: np.ndarray, n: int, rng: np.random.Generator):
    """``n`` distinct indices, one after the other in proportion to
    ``weight`` (exponential keys; a weight of 0 is never drawn)."""
    if np.count_nonzero(weight) < n:
        raise ValueError(f"{n} distinct partners wanted, "
                         f"{np.count_nonzero(weight)} have a stub left")
    with np.errstate(divide="ignore"):
        keys = rng.exponential(size=len(weight)) / weight
    return np.argpartition(keys, n - 1)[:n]


def pair_stubs(du: np.ndarray, di: np.ndarray,
               rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """(user, item) int32 arrays, user by user, of a simple bipartite graph
    with exactly these degrees, drawn from ``rng``. Random pairing of the
    stubs repeats a pair where a busy user meets a popular item (a fifth of
    the pairs at the MovieLens-20M shape), so the dense corner is drawn
    without repeats:
    each busy user takes distinct items in proportion to the stubs the items
    have left, then each popular item takes distinct users among the others
    likewise. What is left is paired at random, and each repeat (u, i) swaps
    items with a random edge (u', i') where neither (u, i') nor (u', i)
    exists yet, round after round over those still repeated."""
    total = int(du.sum())
    dense = int(np.sqrt(total)) // 4   # a busy user; a popular item has 2x
    left_u, left_i = du.astype(np.int64), di.astype(np.int64)
    us, is_ = [], []
    for user in sorted(np.flatnonzero(du > dense), key=lambda r: -du[r]):
        items = _draw(left_i, left_u[user], rng)
        left_i[items] -= 1
        us.append(np.full(len(items), user))
        is_.append(items)
        left_u[user] = 0
    for item in sorted(np.flatnonzero(left_i > 2 * dense),
                       key=lambda r: -left_i[r]):
        users = _draw(left_u, left_i[item], rng)
        left_u[users] -= 1
        us.append(users)
        is_.append(np.full(len(users), item))
        left_i[item] = 0
    u, i = _pair_sparse(left_u, left_i, rng)
    key = np.sort(np.concatenate(us + [u]) * len(di)
                  + np.concatenate(is_ + [i]))
    return (key // len(di)).astype(np.int32), (key % len(di)).astype(np.int32)


def _pair_sparse(du, di, rng):
    """``pair_stubs``'s last step. Which pairs exist is a bit a pair
    (n_users x n_items / 8 bytes)."""
    n_users, n_items = len(du), len(di)
    u = np.repeat(np.arange(n_users, dtype=np.int64), du)
    i = np.repeat(np.arange(n_items, dtype=np.int64), di)
    key = np.sort(u * n_items + i[rng.permutation(len(i))])
    i = key % n_items
    repeated = np.zeros(len(key), bool)
    repeated[1:] = key[1:] == key[:-1]

    bits = np.zeros((n_users * n_items + 7) // 8, np.uint8)
    starts = np.flatnonzero(np.diff(key >> 3, prepend=-1))
    bits[key[starts] >> 3] = np.bitwise_or.reduceat(
        np.uint8(1) << (key & 7).astype(np.uint8), starts)
    del key

    def has(k):
        return (bits[k >> 3] >> (k & 7).astype(np.uint8)) & 1 == 1

    def put(k, on: bool):
        mask = np.uint8(1) << (k & 7).astype(np.uint8)
        if on:
            np.bitwise_or.at(bits, k >> 3, mask)
        else:
            np.bitwise_and.at(bits, k >> 3, ~mask)

    for _ in range(MAX_ROUNDS):
        bad = np.flatnonzero(repeated)
        if not len(bad):
            return u, i
        other = rng.integers(0, len(u), len(bad))
        mine, theirs = u[bad] * n_items + i[other], u[other] * n_items + i[bad]
        ok = ~(repeated[other] | has(mine) | has(theirs))
        # of the proposals that would make one pair twice, or take one edge
        # twice, none goes through in this round
        for taken in (np.concatenate([mine, theirs]), other):
            _, where, times = np.unique(taken, return_inverse=True,
                                        return_counts=True)
            clash = (times > 1)[where]
            ok &= ~(clash[:len(bad)] | clash[-len(bad):])
        bad, other = bad[ok], other[ok]
        put(u[other] * n_items + i[other], False)
        put(mine[ok], True)
        put(theirs[ok], True)
        i[bad], i[other] = i[other], i[bad]
        repeated[bad] = False
    raise ValueError(f"{int(repeated.sum())} pairs still repeated after "
                     f"{MAX_ROUNDS} rounds of swaps")


def events(cfg: dict, seed: int, degs=None) -> dict:
    """``{"buy": (user, item), "view": (user, item)}`` of one seed, in the
    order of the configuration's ``event_names`` (the first is the primary
    indicator); int32 arrays. ``view`` is every rating, ``buy`` those of
    ``buy_min_stars`` stars or more; each user by user."""
    du, di, bought = degs if degs is not None else degrees(cfg)
    rng = np.random.default_rng(int(seed))
    u, i = pair_stubs(du, di, rng)

    def as_stored(slots):
        # user by user, a user's items in no order: how an event store
        # keyed by entity hands a scan back
        slots = rng.permutation(slots)
        slots = slots[np.argsort(u[slots], kind="stable")]
        return u[slots], i[slots]

    by_name = {"view": as_stored(len(u)),
               "buy": as_stored(np.flatnonzero(bought))}
    return {name: by_name[name] for name in cfg["event_names"]}
