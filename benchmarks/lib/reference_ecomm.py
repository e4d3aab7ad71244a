"""The plain reference of the e-commerce deployment, in NumPy: the exact
top-``num`` of a user's float32 scores among the items the rules allow,
with the allowed set built by the sparse definition from the generator's own
lists. It imports nothing of the program: not ``models/_filters.py``, not the
event store.

A request is ``{"row": the user's row or None for an unknown user, "num",
"categories": category indices or None, "white": item rows or None,
"forbidden": item rows (seen, withdrawn, blackList)}``. An item is allowed
where its category is one of ``categories`` (if given), it is in ``white``
(if given) and it is not in ``forbidden``.
"""

from __future__ import annotations

import numpy as np

from reference import MAX_NUM  # the largest ``num`` a traffic mix may ask for


def _among(ids: np.ndarray, sorted_ids: np.ndarray) -> np.ndarray:
    """bool[len(ids)]: which of ``ids`` are in the sorted ``sorted_ids``."""
    if not len(sorted_ids):
        return np.zeros(len(ids), bool)
    at = np.minimum(np.searchsorted(sorted_ids, ids), len(sorted_ids) - 1)
    return sorted_ids[at] == ids


def _sorted_lists(q: dict) -> dict:
    return dict(q, forbidden=np.unique(q["forbidden"]),
                white=None if q["white"] is None else np.unique(q["white"]))


def allowed(q: dict, cats: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """bool[len(ids)]: which of the item rows ``ids`` the request allows
    (``forbidden`` and ``white`` sorted: ``_sorted_lists``)."""
    ok = ~_among(ids, q["forbidden"])
    if q["categories"] is not None:
        ok &= np.isin(cats[ids], q["categories"])
    if q["white"] is not None:
        ok &= _among(ids, q["white"])
    return ok


def row_scores(rows: np.ndarray, vec: np.ndarray, lower=None) -> np.ndarray:
    """float32 score of each of a few item rows: products and a sum along
    the rank, row by row, so an item's score does not depend on which rows
    stand beside it (a matrix product's rounding does)."""
    if lower is not None:
        rows, vec = (a.astype(lower).astype(np.float32) for a in (rows, vec))
    return (rows * vec).sum(axis=1, dtype=np.float32)


def _merge(best, scores: np.ndarray, ids: np.ndarray):
    """The MAX_NUM best of ``best`` and the new candidates, by score
    descending, then row ascending."""
    s = np.concatenate([best[0], scores])
    i = np.concatenate([best[1], ids])
    if len(s) > 4 * MAX_NUM:
        keep = np.argpartition(s, -MAX_NUM)[-MAX_NUM:]
        s, i = s[keep], i[keep]
    order = np.lexsort((i, -s))[:MAX_NUM]
    return s[order], i[order]


def top_allowed(item_factors: np.ndarray, user_vecs: np.ndarray,
                cats: np.ndarray, requests: list[dict], lower=None,
                block: int = 1 << 18) -> list[dict]:
    """For each request of a known user ``{"scores", "items"}``: its best
    allowed items (at most MAX_NUM, fewer where fewer are allowed), best
    first, with their float32 scores; and ``"spread"``, the standard
    deviation of the user's scores over the whole catalog. None for an
    unknown user. The catalog is scored block by block by a matrix product,
    which finds the best; their scores and order are then ``row_scores``'.
    ``lower``: a dtype to round the catalog and the query vector to before
    the float32 products (the control in the precision below)."""
    requests = [_sorted_lists(q) for q in requests]
    known = [k for k, q in enumerate(requests) if q["row"] is not None]
    vecs = np.asarray(user_vecs[[requests[k]["row"] for k in known]],
                      np.float32)
    if lower is not None:
        vecs = vecs.astype(lower).astype(np.float32)
    n_items = item_factors.shape[0]
    empty = (np.empty(0, np.float32), np.empty(0, np.int64))
    best = [empty for _ in known]
    total = np.zeros(len(known))
    total_sq = np.zeros(len(known))
    for lo in range(0, n_items, block):
        rows = item_factors[lo:lo + block]
        if lower is not None:
            rows = rows.astype(lower).astype(np.float32)
        s = vecs @ rows.T                                     # [known, block]
        total += s.sum(axis=1, dtype=np.float64)
        total_sq += np.square(s, dtype=np.float64).sum(axis=1)
        ids = np.arange(lo, lo + len(rows))
        for j, k in enumerate(known):
            q = requests[k]
            if q["white"] is not None:
                cand = q["white"][(q["white"] >= lo)
                                  & (q["white"] < lo + len(rows))]
            elif len(best[j][0]) == MAX_NUM:
                # a later row only displaces with a strictly greater score
                cand = ids[s[j] > best[j][0][-1]]
            else:
                cand = ids
            cand = cand[allowed(q, cats, cand)]
            if len(cand):
                best[j] = _merge(best[j], s[j, cand - lo], cand)
    mean = total / n_items
    spread = np.sqrt(np.maximum(total_sq / n_items - mean * mean, 0.0))
    out: list = [None] * len(requests)
    for j, k in enumerate(known):
        ids = best[j][1]
        s = row_scores(item_factors[ids], user_vecs[requests[k]["row"]], lower)
        order = np.lexsort((ids, -s))
        out[k] = {"scores": s[order], "items": ids[order],
                  "spread": float(spread[j])}
    return out


def gaps(item_factors: np.ndarray, user_vecs: np.ndarray, cats: np.ndarray,
         requests: list[dict], served: list[dict], want=None) -> dict:
    """``served``: per request ``{"items": [rows], "scores": [floats]}`` as
    answered (None where no answer was kept). Against the reference:

    - ``rank_gap``: how far the reference score of the j-th served item lies
      below the reference's own j-th best ALLOWED score (0 where the served
      list is the top-num of the allowed, ties included);
    - ``score_gap``: how far a served score is from the reference score of
      that item; both over the spread of the user's scores;
    - ``leak``: served items that a rule forbids;
    - ``fill_gap``: answers shorter than min(num, the allowed items);
    - ``malformed``: an id outside the catalog, a repeated id, more than
      ``num`` items, items without a score each, an answer for an unknown
      user.

    ``want``: what ``top_allowed`` gave for these requests, if at hand."""
    if want is None:
        want = top_allowed(item_factors, user_vecs, cats, requests)
    requests = [_sorted_lists(q) for q in requests]
    n_items = item_factors.shape[0]
    out = {"rank_gap": 0.0, "score_gap": 0.0, "leak": 0, "fill_gap": 0,
           "malformed": 0, "compared": 0}
    for q, ref, got in zip(requests, want, served):
        if got is None:
            continue
        out["compared"] += 1
        ids = np.asarray(got["items"], np.int64)
        if ref is None:
            out["malformed"] += len(ids) > 0
            continue
        if (len(ids) > q["num"] or len(got["scores"]) != len(ids)
                or len(np.unique(ids)) != len(ids)
                or (ids < 0).any() or (ids >= n_items).any()
                or q["num"] > MAX_NUM):
            out["malformed"] += 1
            continue
        out["leak"] += int((~allowed(q, cats, ids)).sum())
        out["fill_gap"] += len(ids) < min(q["num"], len(ref["items"]))
        if not len(ids):
            continue
        n = min(len(ids), len(ref["scores"]))
        ref_of_served = row_scores(item_factors[ids], user_vecs[q["row"]])
        out["rank_gap"] = max(out["rank_gap"], float(
            (ref["scores"][:n] - ref_of_served[:n]).max() / ref["spread"]))
        out["score_gap"] = max(out["score_gap"], float(np.abs(
            np.asarray(got["scores"], np.float32) - ref_of_served).max()
            / ref["spread"]))
    return out


def answer_of(ref, num: int):
    """A reference answer put in a server's place (the controls)."""
    if ref is None:
        return {"items": [], "scores": []}
    return {"items": ref["items"][:num].tolist(),
            "scores": ref["scores"][:num].tolist()}
