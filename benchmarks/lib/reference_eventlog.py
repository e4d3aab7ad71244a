"""What the event-log deployment's ``correct`` compares besides the factors:
the triples a train read against the events that were posted, and the ids of
a model against the ids that occur. Nothing here imports the program, reads
the log or takes anything the store made: both sides arrive as arrays and
lists, and the wanted side is the generator's own (``datagen_eventlog``).

The factors themselves are the sibling's comparison (``reference.py``: a
whole plain ALS on the chip) on the generator's triple RELABELLED into the
model's row order: the initial factors are drawn row by row, so which id
has which row is part of the arithmetic.
"""

from __future__ import annotations

import numpy as np


def rows_of(ids: list[str], row_ids: list) -> tuple[np.ndarray, int]:
    """``ids``: the generator's id of each of its rows; ``row_ids``: the id
    a model or a read gives each of ITS rows (None: a row with no id).
    Returns (for each row of the model the generator's row or -1; how many
    ids are wrong): generator ids that no row carries, plus rows whose id
    is unknown, absent or carried by an earlier row already."""
    number = {s: k for k, s in enumerate(ids)}
    gen_of_row = np.fromiter((number.get(s, -1) for s in row_ids),
                             np.int64, len(row_ids))
    known = gen_of_row[gen_of_row >= 0]
    first = np.zeros(len(ids), bool)
    first[known] = True
    repeats = len(known) - int(first.sum())
    wrong = (len(ids) - int(first.sum())) + (len(row_ids) - len(known)) \
        + repeats
    return gen_of_row, int(wrong)


def triple_diff(got: tuple, want: tuple, n_items: int) -> int:
    """Size of the symmetric difference of two multisets of (user, item,
    rating), users and items as generator rows (a triple of ``got`` with
    an unknown id, -1, counts on its own)."""
    gu, gi, gr = (np.asarray(a) for a in got)
    ok = (gu >= 0) & (gi >= 0)
    unknown = int((~ok).sum())
    gu, gi, gr = gu[ok], gi[ok], gr[ok]
    wu, wi, wr = (np.asarray(a) for a in want)
    pair = np.concatenate([gu.astype(np.int64) * n_items + gi,
                           wu.astype(np.int64) * n_items + wi])
    bits = np.concatenate([gr.astype(np.float32).view(np.uint32),
                           wr.astype(np.float32).view(np.uint32)])
    side = np.concatenate([np.ones(len(gu), np.int64),
                           -np.ones(len(wu), np.int64)])
    if not len(pair):
        return unknown
    order = np.lexsort((bits, pair))
    pair, bits, side = pair[order], bits[order], side[order]
    starts = np.concatenate([[True], (pair[1:] != pair[:-1])
                             | (bits[1:] != bits[:-1])])
    return unknown + int(np.abs(
        np.add.reduceat(side, np.nonzero(starts)[0])).sum())


def first_seen_rows(codes: np.ndarray) -> np.ndarray:
    """For each code the row a first-seen numbering of ``codes`` gives it
    (-1 for a code that does not occur): the order in which a store that
    numbers ids as they arrive would lay the rows out."""
    uniq, first = np.unique(codes, return_index=True)
    out = np.full(int(codes.max()) + 1 if len(codes) else 0, -1, np.int64)
    out[uniq[np.argsort(first, kind="stable")]] = np.arange(len(uniq))
    return out
