"""Ranking-quality metric kernels for the shadow scorer (MAP@k /
NDCG@k / AUC) plus the windowed canary-vs-last-good verdict.

Reference behaviour: MLlib's RankingMetrics / BinaryClassificationMetrics
evaluator suite (arxiv 1505.06807) — the offline evaluator catalog —
re-cut for ONLINE use inside the serving loop, where per-sample overhead
must stay cheap at ALX-style serving scale points (arxiv 2112.02194):
one jitted kernel over a padded [batch, k] relevance matrix, one host
transfer, shapes bucketed so a steady sample stream reuses a single
executable.

Conventions (shared by every caller — the deltas only mean something if
both windows are scored identically):

- A *sample* is one ranked item list (best first, truncated to k) plus
  the set of held-out relevant items (the user's next events).
- Samples with an empty label set are invalid (nothing to grade).
- AP@k divides by min(|labels|, k): a perfect top-k scores 1.0 even
  when more than k items are relevant.
- NDCG@k uses binary gains with 1/log2(pos+1) discounts; IDCG places
  the min(|labels|, k) relevant items first.
- AUC is in-list: the probability a relevant item outranks an
  irrelevant one *within the returned list*; samples whose list is all
  relevant or all irrelevant carry no pairs and are excluded from the
  AUC mean (tracked separately as ``n_auc``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .topk import pad_batch_pow2

__all__ = ["MetricWindow", "bucket_k_eval", "quality_verdict",
           "ranking_metrics"]


@jax.jit
def _ranking_metrics(rel, pmask, n_rel, valid):
    # rel:   [b, k] 0/1 relevance at each ranked position
    # pmask: [b, k] 1 where a real ranked item exists (lists may be
    #        shorter than k; real items are a prefix)
    # n_rel: [b] held-out relevant-item count per sample
    # valid: [b] 1 for real samples (batch rows are pow2-padded)
    k = rel.shape[1]
    pos = jnp.arange(1, k + 1, dtype=jnp.float32)
    rel = rel * pmask
    cum = jnp.cumsum(rel, axis=1)
    # AP@k: precision is only read at relevant positions, all inside the
    # real prefix, so the padded tail never contributes
    ap = (rel * (cum / pos[None, :])).sum(axis=1)
    ap = ap / jnp.maximum(jnp.minimum(n_rel, float(k)), 1.0)
    disc = 1.0 / jnp.log2(pos + 1.0)
    dcg = (rel * disc[None, :]).sum(axis=1)
    ideal = (pos[None, :] <= jnp.minimum(n_rel, float(k))[:, None])
    idcg = (ideal.astype(jnp.float32) * disc[None, :]).sum(axis=1)
    ndcg = dcg / jnp.maximum(idcg, 1e-9)
    # in-list AUC via one cumsum: for each relevant position, the
    # concordant pairs are the negatives ranked BELOW it
    neg = pmask * (1.0 - rel)
    neg_above = jnp.cumsum(neg, axis=1) - neg
    n_pos = rel.sum(axis=1)
    n_neg = neg.sum(axis=1)
    concordant = (rel * (n_neg[:, None] - neg_above)).sum(axis=1)
    pairs = n_pos * n_neg
    auc = concordant / jnp.maximum(pairs, 1.0)
    has_pairs = valid * (pairs > 0).astype(jnp.float32)
    n = valid.sum()
    n_auc = has_pairs.sum()
    return (
        (ap * valid).sum() / jnp.maximum(n, 1.0),
        (ndcg * valid).sum() / jnp.maximum(n, 1.0),
        (auc * has_pairs).sum() / jnp.maximum(n_auc, 1.0),
        n,
        n_auc,
    )


def bucket_k_eval(k: int) -> int:
    """Pow2 (≥8) k bucket so callers varying k share executables —
    ops/topk.py's bucket_k without the catalog cap (labels are not
    bounded by a catalog here)."""
    return max(8, 1 << max(int(k) - 1, 0).bit_length())


def ranking_metrics(ranked, labels, k: int) -> dict:
    """Score a batch of samples: ``ranked`` is a sequence of ranked
    item-id lists (best first), ``labels`` the parallel sequence of
    held-out relevant-item collections. Returns mean ``map``/``ndcg``/
    ``auc`` plus the sample counts they were averaged over (``n``
    graded samples, ``n_auc`` of them carrying AUC pairs)."""
    b = len(ranked)
    zero = {"map": 0.0, "ndcg": 0.0, "auc": 0.0, "n": 0, "n_auc": 0}
    if b == 0:
        return zero
    k = max(1, int(k))
    kp = bucket_k_eval(k)
    rel = np.zeros((b, kp), np.float32)
    pmask = np.zeros((b, kp), np.float32)
    n_rel = np.zeros((b,), np.float32)
    valid = np.zeros((b,), np.float32)
    for i, (items, labs) in enumerate(zip(ranked, labels)):
        labs = set(labs)
        if not labs:
            continue
        valid[i] = 1.0
        n_rel[i] = float(len(labs))
        for j, item in enumerate(items[:k]):
            pmask[i, j] = 1.0
            if item in labs:
                rel[i, j] = 1.0
    if not valid.any():
        return zero
    out = _ranking_metrics(
        jnp.asarray(pad_batch_pow2(rel)),
        jnp.asarray(pad_batch_pow2(pmask)),
        jnp.asarray(pad_batch_pow2(n_rel)),
        jnp.asarray(pad_batch_pow2(valid)),
    )
    # single host transfer (ops/topk.py idiom): one device_get, one
    # round trip
    m, nd, auc, n, n_auc = jax.device_get(out)
    return {"map": float(m), "ndcg": float(nd), "auc": float(auc),
            "n": int(round(float(n))), "n_auc": int(round(float(n_auc)))}


class MetricWindow:
    """Host-side accumulator for one watch window: fold per-tick
    ``ranking_metrics`` batches into running sums so the verdict reads
    a whole-window mean, not the last tick's."""

    __slots__ = ("map_sum", "ndcg_sum", "auc_sum", "n", "n_auc")

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.map_sum = 0.0
        self.ndcg_sum = 0.0
        self.auc_sum = 0.0
        self.n = 0
        self.n_auc = 0

    def add(self, metrics: dict) -> None:
        n = int(metrics.get("n", 0))
        if n <= 0:
            return
        self.map_sum += metrics["map"] * n
        self.ndcg_sum += metrics["ndcg"] * n
        self.n += n
        n_auc = int(metrics.get("n_auc", 0))
        self.auc_sum += metrics.get("auc", 0.0) * n_auc
        self.n_auc += n_auc

    def means(self) -> dict:
        n = max(self.n, 1)
        return {"map": self.map_sum / n, "ndcg": self.ndcg_sum / n,
                "auc": self.auc_sum / max(self.n_auc, 1),
                "n": self.n, "n_auc": self.n_auc}


def quality_verdict(canary: dict, last_good: dict, *,
                    min_samples: int, max_drop: float):
    """Windowed canary-vs-last-good comparison with a minimum-sample
    gate. Both inputs are ``MetricWindow.means()``-shaped dicts scored
    over the SAME queries and labels. Returns ``(breach, deltas)``:
    ``deltas[metric] = last_good − canary`` (positive = the canary is
    worse), and ``breach`` is True only when BOTH windows carry at
    least ``min_samples`` graded samples AND the NDCG drop exceeds
    ``max_drop`` — NDCG@k is the trigger metric (rank-sensitive and
    bounded); MAP/AUC ride along for telemetry. The sample gate is why
    thin traffic can't false-trigger: an unlucky 3-query window is not
    evidence."""
    deltas = {m: round(float(last_good.get(m, 0.0))
                       - float(canary.get(m, 0.0)), 6)
              for m in ("map", "ndcg", "auc")}
    floor = max(1, int(min_samples))
    n = min(int(canary.get("n", 0)), int(last_good.get("n", 0)))
    breach = n >= floor and deltas["ndcg"] > float(max_drop)
    return breach, deltas
