"""The plain reference of a cross-occurrence (CCO) indicator: counts by the
sparse definition, Dunning's G² by Mahout's formula in float64, the k best
per primary item. NumPy alone; nothing here imports the program.

For a primary item i and a secondary event: the users who did the primary
event on i, each one's DISTINCT secondary items, one count per (user, item):
``bincount``. n_i and n_j are distinct users per item of the primary and of
the secondary event, N the number of users.
``LogLikelihood.logLikelihoodRatio(k11, k12, k21, k22)`` of Mahout:
2 (H(row sums) + H(column sums) - H(cells)) with H(x...) = xlogx(sum) -
sum(xlogx), 0 where rounding makes it negative. An item does not correlate
with itself, and a pair nobody shares has no score.

``fault`` plants what the comparison has to catch, in the reference put in
the program's place (``faulty_indicator``): the heavy users left out of the
counts, one user range left out, primary and secondary swapped, the counts
accumulated range by range in bfloat16, G² computed in bfloat16.
"""

from __future__ import annotations

import math

import numpy as np

FAULTS = ("heavy_dropped", "range_dropped", "swapped", "counts_bf16",
          "llr_bf16")


class Side:
    """One event's distinct (user, item) pairs, indexed both ways."""

    def __init__(self, u, i, n_users: int, n_items: int):
        u, i = np.asarray(u, np.int64), np.asarray(i, np.int64)
        ok = (u >= 0) & (u < n_users) & (i >= 0) & (i < n_items)
        key = np.unique(u[ok] * n_items + i[ok])
        self.user, self.item = key // n_items, key % n_items
        self.per_user = np.bincount(self.user, minlength=n_users)
        self.per_item = np.bincount(self.item, minlength=n_items)
        self.user_ptr = np.concatenate([[0], np.cumsum(self.per_user)])
        by_item = np.argsort(self.item, kind="stable")
        self.users_by_item = self.user[by_item]
        self.item_ptr = np.concatenate([[0], np.cumsum(self.per_item)])

    def users_of(self, item: int) -> np.ndarray:
        return self.users_by_item[self.item_ptr[item]:self.item_ptr[item + 1]]

    def items_of(self, users: np.ndarray):
        """(the users repeated, their items): every pair of ``users``."""
        lo, n = self.user_ptr[users], self.per_user[users]
        ends = np.cumsum(n)
        at = np.arange(int(ends[-1]) if len(ends) else 0) \
            - np.repeat(ends - n, n) + np.repeat(lo, n)
        return np.repeat(users, n), self.item[at]


def xlogx(x):
    x = np.asarray(x, np.float64)
    return np.where(x > 0, x * np.log(np.maximum(x, 1e-300)), 0.0)


def llr(k11, k12, k21, k22):
    """Mahout's logLikelihoodRatio over arrays, float64."""
    row = xlogx(k11 + k12 + k21 + k22) - xlogx(k11 + k12) - xlogx(k21 + k22)
    col = xlogx(k11 + k12 + k21 + k22) - xlogx(k11 + k21) - xlogx(k12 + k22)
    mat = (xlogx(k11 + k12 + k21 + k22)
           - xlogx(k11) - xlogx(k12) - xlogx(k21) - xlogx(k22))
    return np.where(row + col < mat, 0.0, 2.0 * (row + col - mat))


def llr_bf16(k11, k12, k21, k22):
    """The same formula with every value and every intermediate rounded to
    bfloat16 (the precision below the float32 the configuration states)."""
    import ml_dtypes

    bf = ml_dtypes.bfloat16
    k11, k12, k21, k22 = (np.asarray(k, np.float32).astype(bf)
                          for k in (k11, k12, k21, k22))

    def xl(x):
        safe = np.maximum(x, bf(1e-30))
        return np.where(x > 0, x * np.log(safe), bf(0.0))

    def h2(a, b):
        return xl(a + b) - xl(a) - xl(b)

    mat = xl(k11 + k12 + k21 + k22) - xl(k11) - xl(k12) - xl(k21) - xl(k22)
    g2 = bf(2.0) * (h2(k11 + k12, k21 + k22) + h2(k11 + k21, k12 + k22) - mat)
    return np.maximum(g2, bf(0.0)).astype(np.float64)


def heavy_users(sides, n_users: int) -> np.ndarray:
    """The users whom the program lays out apart: more distinct pairs over
    all events than 16 times the mean, and than 256."""
    per_user = sum(s.per_user for s in sides)
    cap = max(int(16 * max(per_user.sum() / max(n_users, 1), 1.0)), 256)
    return np.nonzero(per_user > cap)[0]


def count_rows(primary: Side, secondary: Side, rows, n_items: int,
               drop_users=None, bf16_chunk: int | None = None) -> np.ndarray:
    """[len(rows), n_items] counts, float64 (whole numbers unless
    ``bf16_chunk``): for each primary item of ``rows`` the users who did the
    primary event on it (without ``drop_users``), their distinct secondary
    items, one count a pair. ``bf16_chunk``: the counts of each range of so
    many users are added to a running sum that is rounded to bfloat16 after
    every range."""
    if bf16_chunk:
        import ml_dtypes

    dropped = None
    if drop_users is not None:
        dropped = np.zeros(len(primary.per_user), bool)
        dropped[drop_users] = True
    out = np.zeros((len(rows), n_items), np.float64)
    for r, item in enumerate(rows):
        users = primary.users_of(int(item))
        if dropped is not None:
            users = users[~dropped[users]]
        who, items = secondary.items_of(users)
        if not bf16_chunk:
            out[r] = np.bincount(items, minlength=n_items)
            continue
        n_ranges = -(-len(primary.per_user) // bf16_chunk)
        parts = np.bincount((who // bf16_chunk) * n_items + items,
                            minlength=n_ranges * n_items
                            ).reshape(n_ranges, n_items)
        acc = np.zeros(n_items, np.float32)
        for part in parts[parts.any(axis=1)]:
            acc = (acc + part).astype(ml_dtypes.bfloat16).astype(np.float32)
        out[r] = acc
    return out


def score_rows(counts, n_i_rows, n_j, n_users: int, rows,
               in_bf16: bool = False) -> np.ndarray:
    """[len(rows), n_items] G² of the counts; 0 on the diagonal and where
    nobody is shared."""
    k11 = counts
    k12 = np.maximum(n_i_rows[:, None] - counts, 0.0)
    k21 = np.maximum(n_j[None, :] - counts, 0.0)
    k22 = np.maximum(n_users - k11 - k12 - k21, 0.0)
    s = (llr_bf16 if in_bf16 else llr)(k11, k12, k21, k22)
    s = np.where(counts > 0, s, 0.0)
    s[np.arange(len(rows)), rows] = 0.0
    return s


def reference_scores(primary: Side, secondary: Side, rows, n_users: int,
                     n_items: int, fault: str | None = None,
                     heavy=None, u_chunk: int = 2048,
                     block: int = 128) -> np.ndarray:
    """[len(rows), n_items] float64 scores of the primary items ``rows``
    against every secondary item, ``block`` rows at a time. ``heavy``: the
    users that ``heavy_dropped`` leaves out (``heavy_users`` over every
    event of the train)."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    rows = np.asarray(rows, np.int64)
    count_p, count_s = primary, secondary
    if fault == "swapped":
        count_p, count_s = secondary, primary
    drop = None
    if fault == "heavy_dropped":
        drop = np.asarray(heavy, np.int64)
    elif fault == "range_dropped":
        # the range that holds the first row's first user (never an empty
        # fault where that item has a user at all)
        seen = primary.users_of(int(rows[0]))
        lo = (int(seen[0]) // u_chunk if len(seen) else 0) * u_chunk
        drop = np.arange(lo, min(lo + u_chunk, n_users))
    n_j = secondary.per_item.astype(np.float64)
    out = np.empty((len(rows), n_items), np.float64)
    for lo in range(0, len(rows), block):
        part = rows[lo:lo + block]
        counts = count_rows(
            count_p, count_s, part, n_items, drop_users=drop,
            bf16_chunk=u_chunk if fault == "counts_bf16" else None)
        out[lo:lo + block] = score_rows(
            counts, primary.per_item[part].astype(np.float64), n_j, n_users,
            part, in_bf16=fault == "llr_bf16")
    return out


def top_k(scores: np.ndarray, k: int):
    """(idx [R, k] int32 with -1 for an empty slot, score [R, k] float32):
    the k best of each row by score, the best first, as the program's
    ``Indicators`` hold them."""
    k = min(k, scores.shape[1])
    part = np.argpartition(-scores, k - 1, axis=1)[:, :k]
    s = np.take_along_axis(scores, part, axis=1)
    order = np.argsort(-s, axis=1, kind="stable")
    idx = np.take_along_axis(part, order, axis=1).astype(np.int32)
    s = np.take_along_axis(s, order, axis=1)
    idx[s <= 0] = -1
    return idx, s.astype(np.float32)


def sample_rows(per_item: np.ndarray, seed: int, n_rows: int,
                n_popular: int) -> np.ndarray:
    """The primary items that are compared: the ``n_popular`` with the most
    users always, the rest drawn from the seed, ``n_rows`` in all (every
    item where the catalog has no more)."""
    n_items = len(per_item)
    if n_rows >= n_items:
        return np.arange(n_items)
    popular = np.argsort(-per_item, kind="stable")[:n_popular]
    rest = np.setdiff1d(np.arange(n_items), popular)
    rng = np.random.default_rng(int(seed))
    drawn = rng.choice(rest, n_rows - len(popular), replace=False)
    return np.concatenate([popular, np.sort(drawn)])


def float32_ulp_of_terms(n_users: int) -> float:
    """The spacing of float32 at N ln N, the largest term of G²: what a
    score computed in float32 cannot resolve, whatever its own size."""
    return n_users * math.log(max(n_users, 2)) * 2.0 ** -23


def compare(idx, score, ref, rows, k: int, n_users: int) -> dict:
    """The gaps of an indicator's rows ``idx``, ``score`` ([R, k], -1 = an
    empty slot) from the reference's scores ``ref`` [R, n_items] of the
    same primary items ``rows``.

    - ``score_gap``: the largest |score - reference's score of the SAME
      item| / (reference's score + floor) over the filled slots;
    - ``rank_gap``: the largest (reference's k-th score - reference's score
      of the weakest pick) / (reference's k-th + floor), an empty slot being
      a pick of score 0: 0 unless a better item was passed over;
    - ``fill_gap``: slots. A row has to fill at least as many as the
      reference has scores over 16 float32 spacings of N ln N (or k), and
      at most as many as it has positive scores at all;
    - ``malformed``: slots that name no item of the catalog, the row's own
      item, an item twice, or carry a score that is not a positive number.

    floor = N ln N / 1024: G² in float32 is good to a few spacings of its
    largest term N ln N (0.2 at N = 138,493) whatever the score's own size,
    so small scores are held to that and large ones to a relative gap."""
    idx = np.asarray(idx, np.int64)
    score = np.asarray(score, np.float64)
    rows = np.asarray(rows, np.int64)
    n_rows, n_items = ref.shape
    floor = n_users * math.log(max(n_users, 2)) / 1024.0
    filled = idx >= 0
    bad = filled & ((idx >= n_items) | (idx == rows[:, None])
                    | ~np.isfinite(score) | (score <= 0))
    safe = np.where(filled & (idx < n_items), idx, 0)
    ordered = np.sort(np.where(filled, safe, -1), axis=1)
    twice = (ordered[:, 1:] == ordered[:, :-1]) & (ordered[:, 1:] >= 0)
    ref_at = np.where(filled, np.take_along_axis(ref, safe, axis=1), 0.0)
    score_gap = np.where(filled & ~bad,
                         np.abs(score - ref_at) / (ref_at + floor), 0.0)
    kk = min(k, n_items)
    ref_kth = -np.partition(-ref, kk - 1, axis=1)[:, kk - 1]
    weakest = ref_at.min(axis=1) if idx.shape[1] else np.zeros(n_rows)
    rank_gap = np.maximum(ref_kth - weakest, 0.0) / (ref_kth + floor)
    clear = np.minimum((ref > 16 * float32_ulp_of_terms(n_users)).sum(1), kk)
    possible = np.minimum((ref > 0).sum(1), kk)
    n_filled = filled.sum(1)
    fill_gap = np.maximum(np.maximum(clear - n_filled, n_filled - possible),
                          0)
    return {"score_gap": float(score_gap.max(initial=0.0)),
            "rank_gap": float(rank_gap.max(initial=0.0)),
            "fill_gap": float(fill_gap.max(initial=0)),
            "malformed": float(bad.sum() + twice.sum())}


def faulty_indicator(primary: Side, secondary: Side, rows, n_users: int,
                     n_items: int, k: int, fault: str, heavy=None,
                     u_chunk: int = 2048):
    """(idx, score) of ``rows`` as a program with ``fault`` would persist
    them."""
    return top_k(reference_scores(primary, secondary, rows, n_users, n_items,
                                  fault=fault, heavy=heavy, u_chunk=u_chunk),
                 k)
