"""Top-k scoring kernels for serving (the `recommendProducts` hot path).

Reference behaviour: MLlib MatrixFactorizationModel.recommendProducts —
driver-side BLAS dot products + sort (SURVEY.md §3.2 hot path). TPU-native:
one fused matvec + lax.top_k per query, jitted once per (model-shape, k);
the engine server calls the cached executable so per-query Python work is
JSON parsing only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..common import telemetry


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_scores(user_vec, item_factors, exclude_mask, k: int):
    # mul+reduce instead of a gemv: the reduction tree over rank is then
    # independent of the row count, so a MODEL_AXIS-sharded catalog
    # (ops/sharded_topk.py) produces bitwise-identical scores. A gemv's
    # row-block tail handling varies with n_items — measured 1-ULP
    # differences on row slices. Cost: none; the serving matvec is
    # HBM-bandwidth-bound on reading the catalog either way.
    scores = (item_factors * user_vec[None, :]).sum(axis=1)  # [n_items]
    scores = jnp.where(exclude_mask, -jnp.inf, scores)
    return jax.lax.top_k(scores, k)


@functools.lru_cache(maxsize=None)
def _no_exclude_mask(n_items: int):
    """Device-resident all-False mask, one per catalog size. Building
    `jnp.zeros((n_items,), bool)` per query cost ~0.2 ms of eager
    dispatch + transfer on the CPU-local hot path (ISSUE 17 profile) —
    for a mask that never changes. Same jit cache key, same executable,
    so answers stay bitwise identical."""
    return jax.device_put(np.zeros((n_items,), dtype=bool))


def top_k_items(user_vec, item_factors, k: int, exclude=None):
    """Returns (scores[k], indices[k]) as host numpy arrays.

    ``exclude``: optional bool mask [n_items] of items to suppress
    (seen-item filtering for the e-commerce template).
    """
    n_items = item_factors.shape[0]
    if exclude is None:
        exclude = _no_exclude_mask(n_items)
    k = min(int(k), n_items)
    # arguments go to the jitted kernel RAW: jit's C++ dispatch commits
    # them to device far cheaper than eager jnp.asarray per query
    # (measured ~0.4 ms/query of lax_numpy/bind machinery saved)
    # topk.dispatch: the enqueue (a first k's compile shows here, with
    # an xla.compile child); topk.wait: the device's queue, the scan and
    # the readback.
    with telemetry.span("topk.dispatch"):
        out = _topk_scores(user_vec, item_factors, exclude, k)
    # Single host transfer: each device_get is a round trip, so (scores,
    # idx) come back together.
    with telemetry.span("topk.wait"):
        return jax.device_get(out)


@functools.partial(jax.jit, static_argnames=("k",))
def _batch_topk(user_vecs, item_factors, k: int):
    scores = user_vecs @ item_factors.T  # [b, n_items]
    return jax.lax.top_k(scores, k)


def bucket_k(k: int, n_total: int) -> int:
    """Pow2 (≥8) k buckets so clients varying "num" share executables.
    Shared by the single-device and sharded (ops/sharded_topk.py) paths —
    the sharded bit-identity guarantee depends on both bucketing alike."""
    return min(max(8, 1 << max(k - 1, 0).bit_length()), n_total)


def pad_batch_pow2(user_vecs: np.ndarray) -> np.ndarray:
    """Pad the batch dim to the next power of two (serving batches vary
    per micro-batch window; unpadded shapes would compile one executable
    per distinct size). Batches >256 pass through: eval / `pio
    batchpredict` call once with thousands of fixed-size queries — one
    compile either way, and pow2 padding there would waste up to 2x the
    matmul. (EngineServer caps its micro-batch max_batch at 256 to match.)"""
    b = user_vecs.shape[0]
    bp = (1 << max(b - 1, 0).bit_length()) if b <= 256 else b
    if bp == b:
        return user_vecs
    return np.concatenate(
        [user_vecs,
         np.zeros((bp - b,) + user_vecs.shape[1:], user_vecs.dtype)],
        axis=0)


def batch_top_k(user_vecs, item_factors, k: int):
    """Vectorized top-k for batch_predict/eval sweeps and the serving
    micro-batch path. The batch dim is padded to the next power of two:
    serving batches vary in size per window, and an unpadded shape would
    compile a fresh executable per distinct size."""
    user_vecs = np.asarray(user_vecs)
    k = min(int(k), item_factors.shape[0])
    b = user_vecs.shape[0]
    # k is a static jit arg too: bucketed so clients varying "num" share
    # executables per bucket instead of compiling one per distinct value.
    kp = bucket_k(k, item_factors.shape[0])
    user_vecs = pad_batch_pow2(user_vecs)
    scores, idx = jax.device_get(_batch_topk(user_vecs, item_factors, kp))
    return scores[:b, :k], idx[:b, :k]


def normalize_rows(x) -> np.ndarray:
    """Row-normalize a factor matrix on the host (float32). Done ONCE at
    deploy/warm-up time: per-query catalog normalization was O(N·rank)
    wasted work, and device-side norm reductions vary bitwise with the
    row count at small shapes, which would break the sharded-catalog
    bit-identity guarantee (ops/sharded_topk.py)."""
    x = np.asarray(x, np.float32)
    return x / (np.linalg.norm(x, axis=1, keepdims=True) + 1e-9)


def similar_items(query_vecs, item_factors_normed, k: int, exclude=None):
    """Summed cosine similarity of query items against the catalog
    (similar-product semantics). ``item_factors_normed`` must be
    row-normalized (normalize_rows) — model caches do this once.

    sum_q dot(f, qn_q) == dot(f, sum_q qn_q): the query vectors fold
    into one, so this is exactly the top_k_items matvec — one kernel,
    shared executables, and bitwise parity with the sharded path."""
    qn = normalize_rows(np.atleast_2d(np.asarray(query_vecs, np.float32)))
    return top_k_items(qn.sum(axis=0), item_factors_normed, k, exclude=exclude)
