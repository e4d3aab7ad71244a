"""Operations and bytes that the algorithms NEED, from shapes alone: what the
roofline shares and the MFUs divide by. Counts of the least work, not of what
a kernel happens to do: recomputed or padded work does not count, so a share
computed from them cannot pass 100% by over-counting.

One multiply-add counts as 2 operations.
"""

from __future__ import annotations

import json
import os


def peaks_for(device_kind: str) -> dict:
    """The published peaks of a device kind. An unknown kind is an error,
    never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def spd_solve_flops(k: int) -> float:
    """One k x k SPD solve by Cholesky: k^3/3 for the factor, 2 k^2 for the
    two triangular solves."""
    return k ** 3 / 3.0 + 2.0 * k ** 2


def als_sweep_flops(n_ratings: int, n_users: int, n_items: int,
                    rank: int) -> dict:
    """One ALS sweep (both half-steps): per side a rank x rank gram and a
    right-hand side accumulated over every rating, then one solve per row."""
    gram = 2 * (2.0 * n_ratings * rank * rank)
    rhs = 2 * (2.0 * n_ratings * rank)
    solve = (n_users + n_items) * spd_solve_flops(rank)
    return {"gram": gram, "rhs": rhs, "solve": solve,
            "total": gram + rhs + solve}


def als_solve_bytes(n_users: int, n_items: int, rank: int) -> float:
    """Least HBM traffic of one sweep's solves where the normal equations
    are materialised in HBM between the gram and the solve, as float32: read
    k x k + k, write k, per row."""
    return (n_users + n_items) * (rank * rank + 2 * rank) * 4.0


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> dict:
    """The least time the chip could take, and which of the two binds."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "binds": "operations" if t_flops >= t_bytes else "bytes"}


def topk_scan_flops(n_items: int, rank: int) -> float:
    return 2.0 * n_items * rank


def topk_scan_bytes(n_items: int, rank: int, itemsize: int = 4) -> float:
    """One query reads the whole catalog once."""
    return float(n_items) * rank * itemsize
