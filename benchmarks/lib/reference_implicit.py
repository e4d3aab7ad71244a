"""The plain reference of the ``similarproduct-views`` deployment: implicit
ALS after Hu, Koren and Volinsky (ICDM 2008) on the generator's own events,
and the float32 summed-cosine top-``num`` of a query under its rules.

Nothing here imports ``incubator_predictionio_tpu``, reads the log or takes
anything the store made: every input is an array of the generator's
(``datagen_views``) or the factors under comparison.

Implicit ALS, as upstream's ``ALSAlgorithm`` of the Similar Product template
asks MLlib for it (``view`` -> ``((user, item), 1)``, ``reduceByKey(_ + _)``,
``ALS.trainImplicit``): a pair viewed r times is ONE observation of
preference p = 1 and confidence c = 1 + alpha r. A half-step solves, per row,

    (YtY + Yt (C - I) Y + lambda I) x = Yt C p

where Y are the counterpart's factors, YtY is over ALL of its rows and
(C - I) and C p are non-zero on the row's own observations only: with P the
row's observed counterpart rows and w = alpha r,  (M + Pt diag(w) P) x =
Pt (1 + w),  M = YtY + lambda I.

Departures from ``ALS.trainImplicit``, each the configuration's own:
- the counterpart rows are rounded to ``gather_dtype`` once a half-step,
  those under YtY too, as ``lib/reference.als_reference`` does (the program
  gathers and contracts bfloat16 rows on a TPU); everything else is float32
  at ``highest`` precision;
- lambda is plain (``lambda_scaling: plain``): MLlib since 1.4 scales it by
  the row's number of observations;
- the factors start from NumPy ``default_rng(seed)`` N(0, 1) / sqrt(rank),
  users drawn first (``reference.initial_item_factors``), not MLlib's draw;
- no non-negativity, no intermediate checkpoints: neither is asked for.

How a row is solved. 4.2 million users hold one or two pairs each, and a
rank x rank elimination apiece does not fit a check (17 MB of passes a row).
Rows are grouped by their number of pairs d, padded to a power of two c
(``reference.group_rows``). For c <= rank the SAME solution comes from the
c x c system that Woodbury's identity gives,

    x = t - Qt D (I + D P Qt D)^-1 D P t,   Q = P M^-1,  t = Qt (1 + w),
    D = diag(sqrt(w)),

with M^-1 taken once a half-step in float64 on the host (rank x rank); for
c > rank the rank x rank system is eliminated directly. Both go through
``reference._spd_solve``. ``implicit_als_dense`` is the definition above
written out in float64 NumPy with one Cholesky a row, for sizes at which a
test can compare the two.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference
from reference import MAX_NUM


def pair_counts(user: np.ndarray, item: np.ndarray, n_items: int):
    """The ``view`` events (user, item) reduced to (user, item, count) of
    the distinct pairs, pairs in the order in which each is first seen."""
    key = user.astype(np.int64) * n_items + item
    _, first, count = np.unique(key, return_index=True, return_counts=True)
    order = np.argsort(first, kind="stable")
    first = first[order]
    return user[first], item[first], count[order].astype(np.float32)


def pairs_diff(got: tuple, want: tuple, n_items: int) -> int:
    """How many (user, item) pairs the two sides count differently: a pair
    on one side only, or on both with another count. ``got`` may hold a
    pair several times (a read that did not reduce), which then differs
    from its count unless the count is that entry's."""
    gu, gi, gc = (np.asarray(a) for a in got)
    wu, wi, wc = (np.asarray(a) for a in want)
    unknown = (gu < 0) | (gi < 0)
    key = np.concatenate([gu[~unknown].astype(np.int64) * n_items
                          + gi[~unknown],
                          wu.astype(np.int64) * n_items + wi])
    val = np.concatenate([gc[~unknown].astype(np.float64),
                          -wc.astype(np.float64)])
    side = np.concatenate([np.ones(int((~unknown).sum()), np.int64),
                           np.zeros(len(wu), np.int64)])
    if not len(key):
        return int(unknown.sum())
    order = np.argsort(key, kind="stable")
    key, val, side = key[order], val[order], side[order]
    at = np.nonzero(np.concatenate([[True], key[1:] != key[:-1]]))[0]
    net = np.add.reduceat(val, at)
    entries = np.add.reduceat(side, at)
    # equal sums from several entries are still not ONE entry of the count
    return int(unknown.sum()) + int(((net != 0) | (entries > 1)).sum())


# -- implicit ALS -------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _solve_group_fn(rank: int, yty: bool):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    spd = reference._spd_solve

    def solve(yg, out, cols, counts, rows, m, m_inv, lam, alpha):
        p = yg[cols]                                    # [n, c, k]
        c = cols.shape[1]
        w = alpha * counts                              # 0 on padding
        r = jnp.where(counts > 0, 1.0 + w, 0.0)
        d = jnp.sqrt(w)
        if c > rank:
            a = m + jnp.einsum("nck,nc,ncm->nkm", p, w, p, precision=hi)
            x = spd(a, jnp.einsum("nck,nc->nk", p, r, precision=hi))
        elif yty:
            q = jnp.einsum("nck,km->ncm", p, m_inv, precision=hi)
            t = jnp.einsum("nck,nc->nk", q, r, precision=hi)
            g = jnp.einsum("nck,ndk->ncd", p, q, precision=hi)
            s = (jnp.eye(c, dtype=jnp.float32)
                 + d[:, :, None] * g * d[:, None, :])
            v = d * jnp.einsum("nck,nk->nc", p, t, precision=hi)
            x = t - jnp.einsum("nck,nc->nk", q, d * spd(s, v), precision=hi)
        else:
            # the control without YtY: M = lambda I, whose inverse would
            # swamp float32; the dual of the weighted ridge instead,
            # x = Pt D (D P Pt D + lambda I)^-1 D^-1 (1 + w)
            pd = p * d[:, :, None]
            g = jnp.einsum("nck,ndk->ncd", pd, pd, precision=hi)
            g = g + lam * jnp.eye(c, dtype=jnp.float32)
            v = jnp.where(counts > 0, r / jnp.where(d > 0, d, 1.0), 0.0)
            x = jnp.einsum("nc,nck->nk", spd(g, v), pd, precision=hi)
        return out.at[rows].set(x)

    return jax.jit(solve, donate_argnums=(1,))


def _chunks(groups: list[dict], n_rows: int, n_cols: int, rank: int,
            chunk_bytes: int):
    """``reference._chunks`` with this solve's footprint a row: the
    gathered rows twice (P and Q) and the c x c system's passes."""
    import jax

    out = []
    for g in groups:
        n, c = g["cols"].shape
        small = min(c, rank)
        per_row = 4 * (2 * c * rank + 3 * small * (small + 1))
        chunk = int(max(1, min(n, chunk_bytes // per_row)))
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


def _rounder(gather_dtype):
    import jax
    import jax.numpy as jnp

    gd = jnp.dtype(gather_dtype)
    if gd.itemsize >= 2:
        return jax.jit(lambda a: a.astype(gd).astype(jnp.float32))
    # an 8-bit float is rounded on the host, as reference.als_reference has it
    return lambda a: jnp.asarray(np.asarray(a).astype(gd).astype(np.float32))


def implicit_als_reference(user, item, count, n_users: int, n_items: int,
                           rank: int, lam: float, alpha: float, seed: int,
                           n_iters: int, gather_dtype="bfloat16",
                           yty: bool = True, log=lambda msg: None,
                           chunk_bytes: int = reference.CHUNK_BYTES):
    """(user factors, item factors) as host float32 arrays after ``n_iters``
    sweeps (users from items, then items from users) over the entries
    (user, item, count); an entry a distinct pair is what the configuration
    states, an entry an EVENT with count 1 is the fault this reference
    catches. ``yty=False`` leaves the shared YtY out (a control)."""
    import time

    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    rounded = _rounder(gather_dtype)
    solve = _solve_group_fn(rank, bool(yty))
    gram = jax.jit(lambda a: jnp.einsum(
        "nk,nm->km", a, a, precision=jax.lax.Precision.HIGHEST))
    lam32, alpha32 = np.float32(lam), np.float32(alpha)
    eye = np.eye(rank)
    count = np.asarray(count, np.float32)
    with ThreadPoolExecutor(3) as pool:
        init = pool.submit(reference.initial_item_factors, n_users, n_items,
                           rank, seed)
        g_user = pool.submit(reference.group_rows, user, item, count,
                             n_users, n_items)
        g_item = pool.submit(reference.group_rows, item, user, count,
                             n_items, n_users)
        by_user = _chunks(g_user.result(), n_users, n_items, rank,
                          chunk_bytes)
        by_item = _chunks(g_item.result(), n_items, n_users, rank,
                          chunk_bytes)
        y0 = init.result()
    y = jnp.asarray(np.concatenate([y0, np.zeros((1, rank), np.float32)]))
    log(f"reference: init, grouping and upload {time.perf_counter() - t0:.1f}s"
        f" ({len(by_user)} + {len(by_item)} chunks)")

    def half_step(counterpart, n_rows, chunks):
        cg = rounded(counterpart)
        m = lam * eye
        if yty:
            m = m + np.asarray(gram(cg), np.float64)
        m_inv = np.linalg.inv(m).astype(np.float32)
        m = jnp.asarray(m.astype(np.float32))
        out = jnp.zeros((n_rows + 1, rank), jnp.float32)
        for cols, counts, rows in chunks:
            out = solve(cg, out, cols, counts, rows, m, m_inv, lam32,
                        alpha32)
        # the padded rows of a short chunk wrote their 0 here
        return out.at[n_rows].set(0.0)

    x = None
    for it in range(n_iters):
        t0 = time.perf_counter()
        x = half_step(y, n_users, by_user)
        y = half_step(x, n_items, by_item)
        y.block_until_ready()
        log(f"reference: sweep {it + 1} {time.perf_counter() - t0:.1f}s")
    x, y = jax.device_get((x, y))
    return x[:n_users], y[:n_items]


def implicit_als_dense(user, item, count, n_users: int, n_items: int,
                       rank: int, lam: float, alpha: float, seed: int,
                       n_iters: int, gather_dtype="bfloat16"):
    """The definition written out, float64 NumPy, one Cholesky a row: for
    the sizes of a test only (a dense [n_users, n_items] matrix)."""
    import ml_dtypes  # noqa: F401  (registers the narrow float types)

    gd = np.dtype(gather_dtype)
    cm1 = np.zeros((n_users, n_items))
    np.add.at(cm1, (user, item), alpha * np.asarray(count, np.float64))
    y = reference.initial_item_factors(n_users, n_items, rank, seed
                                       ).astype(np.float64)
    x = np.zeros((n_users, rank))

    def side(counterpart, weights):
        cg = counterpart.astype(np.float32).astype(gd).astype(np.float64)
        yty = cg.T @ cg
        out = np.zeros((weights.shape[0], rank))
        for row in range(weights.shape[0]):
            w = weights[row]
            seen = w > 0
            if not seen.any():
                continue
            p = cg[seen]
            a = yty + (p.T * w[seen]) @ p + lam * np.eye(rank)
            b = p.T @ (1.0 + w[seen])
            low = np.linalg.cholesky(a)
            out[row] = np.linalg.solve(low.T, np.linalg.solve(low, b))
        return out

    for _ in range(n_iters):
        x = side(y, cm1)
        y = side(x, cm1.T)
    return x.astype(np.float32), y.astype(np.float32)


# -- the served side: summed cosine under the rules ----------------------------------


def unit_rows(factors: np.ndarray) -> np.ndarray:
    """float32 rows over their norm (a zero row stays zero)."""
    f = np.asarray(factors, np.float32)
    norm = np.sqrt((f.astype(np.float64) ** 2).sum(axis=1))
    return (f / np.where(norm > 0, norm, 1.0)[:, None]).astype(np.float32)


def allowed(q: dict, members: np.ndarray) -> np.ndarray:
    """bool[n_items]: the items a query may be answered with. Never a
    query item; of the ``categories`` given, at least one; on the
    ``white`` list if one is given; not on the ``black`` list."""
    ok = np.ones(len(members), bool)
    ok[np.asarray(q["items"], np.int64)] = False
    if q["categories"] is not None:
        ok &= members[:, list(q["categories"])].any(axis=1)
    if q["white"] is not None:
        white = np.zeros(len(members), bool)
        white[np.asarray(q["white"], np.int64)] = True
        ok &= white
    if q["black"] is not None:
        ok[np.asarray(q["black"], np.int64)] = False
    return ok


def similar_scores(unit: np.ndarray, q: dict) -> np.ndarray:
    """float32[n_items]: each item's cosine to every query item, summed."""
    return unit @ unit[np.asarray(q["items"], np.int64)].sum(
        axis=0, dtype=np.float32)


def top_similar(unit: np.ndarray, members: np.ndarray, q: dict,
                ignore_black: bool = False) -> dict:
    """``{"items", "scores", "spread"}``: the MAX_NUM best allowed items,
    best first (ties to the lower row), and the spread of the scores."""
    s = similar_scores(unit, q)
    ok = allowed(dict(q, black=None) if ignore_black else q, members)
    ids = np.nonzero(ok)[0]
    if len(ids) > MAX_NUM:
        ids = ids[np.argpartition(-s[ids], MAX_NUM - 1)[:MAX_NUM]]
    ids = ids[np.lexsort((ids, -s[ids]))]
    return {"items": ids, "scores": s[ids], "spread": float(s.std())}


def similar_gaps(unit: np.ndarray, members: np.ndarray, queries: list[dict],
                 served: list[dict]) -> dict:
    """``served``: per query ``{"items": [rows], "scores": [floats]}`` (-1
    for an id the catalog does not hold).

    - ``leak``: served items that a rule forbids (a query item, outside the
      categories, off the whiteList, on the blackList), and ids unknown;
    - ``rank_gap``: how far the reference score of the j-th served item
      lies below the reference's own j-th best ALLOWED score, over the
      spread of the query's scores; an answer that is malformed (a
      repeated id, more than ``num``) or shorter than min(num, allowed)
      reads infinite."""
    out = {"leak": 0, "rank_gap": 0.0, "compared": 0}
    for q, got in zip(queries, served):
        out["compared"] += 1
        ids = np.asarray(got["items"], np.int64)
        known = ids[(ids >= 0) & (ids < len(members))]
        out["leak"] += len(ids) - len(known)
        ref = top_similar(unit, members, q)
        out["leak"] += int((~allowed(q, members)[known]).sum())
        if (len(ids) > q["num"] or len(np.unique(ids)) != len(ids)
                or len(ids) < min(q["num"], len(ref["items"]))
                or len(known) != len(ids)):
            out["rank_gap"] = float("inf")
            continue
        if len(ids):
            s = similar_scores(unit, q)
            out["rank_gap"] = max(out["rank_gap"], float(
                (ref["scores"][:len(ids)] - s[ids]).max() / ref["spread"]))
    return out
