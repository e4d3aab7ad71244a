"""The plain references, and the comparisons that decide ``correct``.

Nothing here imports the program or takes anything the program made: the
references start from the seeded inputs (benchmarks/lib/datagen.py) and the
configuration's numbers.

ALS (retrain cells). Explicit-feedback ALS with a plain ridge, as the
configuration states it: factors initialised N(0,1)/sqrt(rank) from the
configuration's ``seed`` (NumPy ``default_rng``, users drawn first), each
half-step solves per row  (sum_j p_j p_j^T + lambda I) x = sum_j r_j p_j
with the counterpart rows p_j rounded to the configuration's gather type and
everything else in float32 at ``highest`` precision. Rows are grouped by their
number of ratings d (padded to a power of two c). For c <= rank the same
solution is computed through the c x c dual system
x = P^T (P P^T + lambda I)^-1 r, which is algebraically identical and keeps
4.2 million users with one or two ratings each from costing a rank^3 solve
apiece; for c > rank the rank x rank normal equations are solved directly.
Both go through one batched Gauss-Jordan elimination (SPD: no pivoting).

Top-k (serve cells). Scores of the sampled queries over the whole catalog in
float32 at ``highest``; the comparison reads how far each served item's
reference score lies below the reference's own item of the same rank.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_BYTES = 384 * 1024 * 1024


# -- ALS ---------------------------------------------------------------------


def initial_item_factors(n_users: int, n_items: int, rank: int,
                         seed: int) -> np.ndarray:
    """Y0 as the configuration states it. The user block is drawn first and
    thrown away: the first half-step solves the users from Y0 alone."""
    rng = np.random.default_rng(int(seed))
    scratch = np.empty((1 << 13, rank))   # small enough to stay in cache
    left = n_users
    while left:
        n = min(left, len(scratch))
        rng.standard_normal(out=scratch[:n])
        left -= n
    y0 = rng.standard_normal((n_items, rank)) / np.sqrt(rank)
    return y0.astype(np.float32)


def _pow2_at_least(d: np.ndarray) -> np.ndarray:
    return 1 << np.ceil(np.log2(np.maximum(d, 1))).astype(np.int64)


def group_rows(row: np.ndarray, col: np.ndarray, val: np.ndarray,
               n_rows: int, n_cols: int) -> list[dict]:
    """One side's ratings as dense groups: for each power-of-two capacity c
    the rows with (c/2, c] ratings, ``cols`` [n, c] padded with ``n_cols``
    (a zero row of the counterpart) and ``vals`` [n, c] padded with 0."""
    order = np.argsort(row, kind="stable")
    rs, cs, vs = row[order], col[order], val[order]
    deg = np.bincount(rs, minlength=n_rows)
    starts = np.zeros(n_rows + 1, np.int64)
    np.cumsum(deg, out=starts[1:])
    pos = np.arange(len(rs), dtype=np.int64) - starts[rs]
    cap = _pow2_at_least(deg)
    groups = []
    for c in np.unique(cap[deg > 0]):
        rows = np.nonzero((cap == c) & (deg > 0))[0]
        index_in_group = np.full(n_rows, -1, np.int64)
        index_in_group[rows] = np.arange(len(rows))
        sel = cap[rs] == c
        cols = np.full((len(rows), int(c)), n_cols, np.int32)
        vals = np.zeros((len(rows), int(c)), np.float32)
        cols[index_in_group[rs[sel]], pos[sel]] = cs[sel]
        vals[index_in_group[rs[sel]], pos[sel]] = vs[sel]
        groups.append({"rows": rows, "cols": cols, "vals": vals})
    return groups


def _spd_solve(a, b):
    """Batched solve of SPD systems a [n, c, c] x = b [n, c] by Gauss-Jordan
    elimination on the augmented matrix, batch last."""
    import jax
    import jax.numpy as jnp

    c = a.shape[1]
    m = jnp.concatenate([a, b[:, :, None]], axis=2).transpose(1, 2, 0)

    def step(j, m):
        pivot_row = jax.lax.dynamic_index_in_dim(m, j, 0, keepdims=False)
        pivot = jax.lax.dynamic_index_in_dim(pivot_row, j, 0, keepdims=False)
        pivot_row = pivot_row / pivot[None, :]
        col = jax.lax.dynamic_index_in_dim(m, j, 1, keepdims=False)
        m = m - col[:, None, :] * pivot_row[None, :, :]
        return jax.lax.dynamic_update_index_in_dim(m, pivot_row, j, 0)

    m = jax.lax.fori_loop(0, c, step, m)
    return m[:, c, :].T


@functools.lru_cache(maxsize=None)
def _solve_group_fn(rank: int):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def solve(yg, out, cols, vals, rows, lam):
        p = yg[cols]                                   # [n, c, k]
        c = cols.shape[1]
        if c <= rank:
            g = jnp.einsum("nck,ndk->ncd", p, p, precision=hi)
            g = g + lam * jnp.eye(c, dtype=jnp.float32)
            a = _spd_solve(g, vals)
            x = jnp.einsum("nc,nck->nk", a, p, precision=hi)
        else:
            a = jnp.einsum("nck,ncm->nkm", p, p, precision=hi)
            a = a + lam * jnp.eye(rank, dtype=jnp.float32)
            b = jnp.einsum("nck,nc->nk", p, vals, precision=hi)
            x = _spd_solve(a, b)
        return out.at[rows].set(x)

    return jax.jit(solve, donate_argnums=(1,))


def _chunks(groups: list[dict], n_rows: int, n_cols: int, rank: int):
    """The groups cut into equal chunks on the device. A short last chunk is
    padded with rows that hold no rating: their solution is exactly 0 and is
    written to row ``n_rows``, the zero row that every side keeps at its end."""
    import jax

    out = []
    for g in groups:
        n, c = g["cols"].shape
        small = min(c, rank)
        per_row = 4 * max(c * rank, 3 * small * (small + 1))
        chunk = int(max(1, min(n, CHUNK_BYTES // per_row)))
        for s in range(0, n, chunk):
            cols, vals = g["cols"][s:s + chunk], g["vals"][s:s + chunk]
            rows = g["rows"][s:s + chunk].astype(np.int32)
            pad = chunk - len(rows)
            if pad:
                cols = np.concatenate(
                    [cols, np.full((pad, c), n_cols, np.int32)])
                vals = np.concatenate([vals, np.zeros((pad, c), np.float32)])
                rows = np.concatenate([rows, np.full(pad, n_rows, np.int32)])
            out.append(jax.device_put((cols, vals, rows)))
    return out


def als_reference(user, item, rating, n_users: int, n_items: int, rank: int,
                  lam: float, seed: int, n_iters: int,
                  gather_dtype="bfloat16", log=lambda msg: None):
    """(user factors, item factors) as host float32 arrays after
    ``n_iters`` sweeps (users from items, then items from users)."""
    import time

    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()

    gd = jnp.dtype(gather_dtype)
    if gd.itemsize >= 2:
        rounded = jax.jit(lambda a: a.astype(gd).astype(jnp.float32))
    else:
        # an 8-bit float is rounded on the host (ml_dtypes): the TPU compiler
        # normalises float32 -> float8 -> float32 away (my chip run, PR 25:
        # the fp8 control read a gap of exactly 0 when rounded on the device)
        def rounded(a):
            return jnp.asarray(
                np.asarray(a).astype(gd).astype(np.float32))
    solve = _solve_group_fn(rank)
    lam = np.float32(lam)
    # three host passes that release the interpreter lock, side by side
    with ThreadPoolExecutor(3) as pool:
        init = pool.submit(initial_item_factors, n_users, n_items, rank, seed)
        g_user = pool.submit(group_rows, user, item, rating, n_users, n_items)
        g_item = pool.submit(group_rows, item, user, rating, n_items, n_users)
        by_user = _chunks(g_user.result(), n_users, n_items, rank)
        by_item = _chunks(g_item.result(), n_items, n_users, rank)
        y0 = init.result()
    y = jnp.asarray(np.concatenate([y0, np.zeros((1, rank), np.float32)]))
    log(f"reference: init, grouping and upload {time.perf_counter() - t0:.1f}s"
        f" ({len(by_user)} + {len(by_item)} chunks)")
    x = None
    for it in range(n_iters):
        t0 = time.perf_counter()
        yg = rounded(y)
        x = jnp.zeros((n_users + 1, rank), jnp.float32)
        for cols, vals, rows in by_user:
            x = solve(yg, x, cols, vals, rows, lam)
        xg = rounded(x)
        y = jnp.zeros((n_items + 1, rank), jnp.float32)
        for cols, vals, rows in by_item:
            y = solve(xg, y, cols, vals, rows, lam)
        y.block_until_ready()
        log(f"reference: sweep {it + 1} {time.perf_counter() - t0:.1f}s")
    x, y = jax.device_get((x, y))
    return x[:n_users], y[:n_items]


def factor_gaps(got: np.ndarray, want: np.ndarray, weights=None) -> dict:
    """How far the program's factor rows lie from the reference's: the
    Frobenius gap of the whole side, and per-row gaps measured against the
    reference row's norm or the median row's, whichever is larger. The row
    norms are taken on the default device (float32 sums of at most a few
    hundred squares), the statistics on the host in float64."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms_of(g, w):
        d = g - w
        return jnp.sqrt((d * d).sum(axis=1)), jnp.sqrt((w * w).sum(axis=1))

    diff, norms = (np.asarray(a, np.float64)
                   for a in jax.device_get(norms_of(got, want)))
    row = diff / np.maximum(norms, np.median(norms))
    w = np.ones_like(diff) if weights is None else np.asarray(
        weights, np.float64)
    return {
        "fro": float(np.sqrt((diff ** 2).sum() / (norms ** 2).sum())),
        # each row weighted by its number of ratings: a fault confined to
        # the few heavy rows (the most popular items) moves this one
        "fro_by_ratings": float(np.sqrt((w * diff ** 2).sum()
                                        / (w * norms ** 2).sum())),
        "row_p50": float(np.quantile(row, 0.5)),
        "row_p99": float(np.quantile(row, 0.99)),
        "row_p999": float(np.quantile(row, 0.999)),
        "row_max": float(row.max()),
    }


def als_compare(got_x, got_y, want_x, want_y, limits: dict,
                ratings_per_row=(None, None)) -> dict:
    """name -> (value, limit) for every gap that the configuration gives a
    limit; the other gaps are returned under ``"_seen"`` for the log."""
    seen = {}
    for side, got, want, w in (("user", got_x, want_x, ratings_per_row[0]),
                               ("item", got_y, want_y, ratings_per_row[1])):
        if got.shape != want.shape or not np.isfinite(got).all():
            seen.update({f"{side}_{k}": float("inf") for k in
                         ("fro", "fro_by_ratings", "row_p50", "row_p99",
                          "row_p999", "row_max")})
            continue
        for k, v in factor_gaps(got, want, w).items():
            seen[f"{side}_{k}"] = v
    out = {k: (seen[k], float(lim)) for k, lim in limits.items()}
    out["_seen"] = seen
    return out


# -- top-k -------------------------------------------------------------------

#: the largest ``num`` a traffic mix may ask for
MAX_NUM = 16


def topk_gaps(item_factors, user_vecs: np.ndarray,
              served: list[dict]) -> dict:
    """``served``: per sampled query ``{"row": index into user_vecs or None
    for an unknown user, "num": n, "items": [ids], "scores": [floats]}``.
    ``item_factors`` is the catalog on the device.

    For each served item at rank j: how far its reference score lies below
    the reference's own j-th best (0 where the served list IS the top-num,
    ties included), and how far the served score is from the reference
    score of that item; both over the spread of the query's scores.
    Shape faults (wrong length, an id outside the catalog, a repeated id, an
    answer for an unknown user) count in ``malformed``."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    n_items = item_factors.shape[0]

    @jax.jit
    def score_block(cat, vecs):
        s = jnp.matmul(cat, vecs.T, precision=hi)            # [n_items, b]
        top, _ = jax.lax.top_k(s.T, MAX_NUM)
        return s, top, jnp.std(s, axis=0)

    rank_gap = score_gap = 0.0
    malformed = 0
    known = [q for q in served if q["row"] is not None]
    for q in served:
        if q["row"] is None and q["items"]:
            malformed += 1
    block = 16
    for b0 in range(0, len(known), block):
        qs = known[b0:b0 + block]
        vecs = np.zeros((block, user_vecs.shape[1]), np.float32)
        vecs[:len(qs)] = user_vecs[[q["row"] for q in qs]]
        s, top, spread = score_block(item_factors, vecs)
        ids = np.zeros((block, MAX_NUM), np.int32)
        for j, q in enumerate(qs):
            it = np.asarray(q["items"], np.int64)
            if (len(it) != q["num"] or len(set(it.tolist())) != len(it)
                    or (it < 0).any() or (it >= n_items).any()
                    or q["num"] > MAX_NUM):
                malformed += 1
                q["_bad"] = True
                continue
            ids[j, :len(it)] = it
        ref_of_served = jax.device_get(
            jnp.take_along_axis(s.T, jnp.asarray(ids), axis=1))
        top, spread = jax.device_get((top, spread))
        for j, q in enumerate(qs):
            if q.get("_bad"):
                continue
            n = q["num"]
            rank_gap = max(rank_gap, float(
                ((top[j, :n] - ref_of_served[j, :n]) / spread[j]).max()))
            score_gap = max(score_gap, float(
                (np.abs(np.asarray(q["scores"]) - ref_of_served[j, :n])
                 / spread[j]).max()))
    return {"rank_gap": rank_gap, "score_gap": score_gap,
            "malformed": malformed, "compared": len(served)}
