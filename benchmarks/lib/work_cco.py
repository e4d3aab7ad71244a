"""Operations and bytes that a cross-occurrence (CCO) retrain NEEDS, from the
data alone: what ``cco.count_roofline`` and ``cco.step_mfu`` divide by. As in
``work.py`` these count the least work, not what a kernel happens to do: the
dense kernel multiplies every user's row by every pair of items (2 U I^2 a
pair), but all a count matrix needs is one addition for each (primary item,
secondary item) pair that a user really has. A later triangle-only or sparse
kernel is measured against the same numbers and cannot pass 100%.

One multiply-add counts as 2 operations.
"""

from __future__ import annotations

import numpy as np

#: a distinct (user, item) pair as the least a layout can hold it in
PAIR_BYTES = 4


def pair_ops(per_user_primary, per_user_secondary) -> float:
    """One pair of indicators: 2 x sum over users of (distinct primary
    items) x (distinct secondary items) = twice the sum of the count
    matrix's entries."""
    return 2.0 * float(np.dot(np.asarray(per_user_primary, np.float64),
                              np.asarray(per_user_secondary, np.float64)))


def least_work(per_user: dict, primary: str, n_items: int) -> dict:
    """``per_user``: event name -> distinct items per user, for every
    indicator of the train (the primary's own among them: the self pair).
    Operations: ``pair_ops`` of the primary with each. Bytes: every distinct
    pair of every event read once, and each pair's [I, I] int32 count matrix
    written once and read once (by the scoring)."""
    ops = sum(pair_ops(per_user[primary], d) for d in per_user.values())
    pairs = sum(float(np.sum(d)) for d in per_user.values())
    matrices = len(per_user) * float(n_items) * n_items * 4 * 2
    return {"ops": ops, "bytes": pairs * PAIR_BYTES + matrices}


def roofline_seconds(work: dict, peaks: dict) -> dict:
    """The least time the chip could take: the larger of operations over the
    int8 peak (membership is binary, so int8 is exact) and bytes over the
    HBM peak, and which of the two binds."""
    t_ops = work["ops"] / peaks["int8_ops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "binds": "operations" if t_ops >= t_bytes else "bytes"}
