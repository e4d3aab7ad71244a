"""Top-k scoring kernels for serving (the `recommendProducts` hot path).

Reference behaviour: MLlib MatrixFactorizationModel.recommendProducts —
driver-side BLAS dot products + sort (SURVEY.md §3.2 hot path). TPU-native:
one fused matvec + lax.top_k per query, jitted once per (model-shape, k);
the engine server calls the cached executable so per-query Python work is
JSON parsing only.

"lax.top_k" names the RESULT, not always the operator: on the TPU
`lax.top_k` of a whole score row is a full stable sort of the row (21 of
28 ms at 9.4M items, PERF.md §5), so on a large catalog `_topk_scores`
reaches the same values and indices, ties included, through
`select_topk`: block maxima, the k best blocks, a sort of their k·L
candidates. Small catalogs call `lax.top_k` itself (`select_block_len`).

What a query suppresses reaches `_topk_scores` as a resident ``bool[n_items]``
mask, or as that plus ROWS (`RowExclude`): the mask the e-commerce rules
describe is then composed on the device, inside the same executable, and
the query ships a few KB of row indices, not a mask of catalog length.

The rules and the selection are shared with `ops/llr` (the Universal
Recommender's resident index scores into the same row ladder and the same
selection): `ROW_LADDER`, `row_capacity`, `RowExclude`, `pack_rows`,
`put_rows`, `suppress_rows`, `no_exclude_mask`, `select_block_len` and
`select_topk` are this module's public pieces; a change to the packed rows'
layout or to the tie order is a change to both kernels.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..common import telemetry


_M_SELECT = telemetry.registry().counter(
    "pio_topk_select_total",
    "Single-query top-k calls by how the k best of the score row are "
    "selected: blocks = block maxima, the k best blocks, a sort of their "
    "candidates; direct = lax.top_k of the whole row (small catalogs).",
    ("path",))

#: shortest block: one row of the TPU's (8, 128) float32 tile
_MIN_BLOCK_LEN = 128


def select_block_len(n_items: int, k: int) -> int:
    """Block length L of the two-stage selection for a row of ``n_items``
    scores, or 0 where plain ``lax.top_k`` is to run. The two stages sort
    B = ceil(n/L) block maxima and k·L candidates; B + k·L is least at
    L = sqrt(n/k), and the power of two nearest to that on the log scale
    is taken (1024 for 9.4M items and k = 10: 9,180 maxima and 10,240
    candidates). Where B + k·L is half the row or more (small catalogs,
    large k) the stages would not sort markedly less than the row itself
    and the answer is 0. Both arguments are static in the jit, so the
    choice is made once per executable, from the shape alone."""
    if not 1 <= k <= n_items:
        return 0
    length = max(_MIN_BLOCK_LEN, 2 ** round(math.log2(n_items / k) / 2))
    n_blocks = -(-n_items // length)
    return length if 2 * (n_blocks + k * length) < n_items else 0


def select_topk(scores, k: int, block_len: int):
    """``lax.top_k(scores, k)`` of one score row ``[n]``, values and
    indices bit for bit, without sorting the row: pad it with -inf to
    ``[B, block_len]``, take each block's maximum, pick the kb = min(k, B)
    blocks with the largest maxima (``lax.top_k``: ties toward the lowest
    block index), and order only their kb·L candidates by (score
    descending, global index ascending), the two-key sort that
    ops/sharded_topk.py merges with.

    Why this is exact, tie order included. ``lax.top_k`` orders elements
    by (score descending, index ascending) and returns the first k. Let e
    be an element of a block that was NOT chosen. Each of the kb = k
    chosen blocks (kb < k only when every block is chosen) has a maximum
    that is greater than e's block maximum, or equal to it in a block of
    lower index — the order ``lax.top_k`` chose the blocks in — and e's
    block maximum is at least e. So each chosen block holds an element that is greater than
    e, or equal to e at a lower global index (a lower block lies wholly
    below e's): k elements precede e in ``lax.top_k``'s own order and e
    is not in the answer. The answer therefore lies among the candidates,
    and the two-key sort orders them as ``lax.top_k`` would. Padded lanes
    are -inf at indices above every real one, so they lose every tie,
    and only the last block has any (at most L - 1 of them): the chosen
    blocks hold at least k real elements, and no padded lane is
    returned. With fewer than k finite scores the -inf rows come out in
    index order, as ``lax.top_k`` gives them. One caveat, shared with the
    sharded merges: ``lax.sort`` takes +0.0 and -0.0 for equal, so a row
    that holds both orders them by index, where the CPU's ``lax.top_k``
    puts +0.0 first."""
    n = scores.shape[0]
    n_blocks = -(-n // block_len)
    blocks = jnp.pad(scores, (0, n_blocks * block_len - n),
                     constant_values=-jnp.inf).reshape(n_blocks, block_len)
    kb = min(k, n_blocks)
    _, chosen = jax.lax.top_k(blocks.max(axis=1), kb)
    cand = blocks[chosen].ravel()
    idx = (chosen[:, None] * block_len
           + jnp.arange(block_len, dtype=chosen.dtype)[None, :]).ravel()
    neg, idx = jax.lax.sort((-cand, idx), num_keys=2)
    return -neg[:k], idx[:k]


#: row capacities a `RowExclude`'s lists are padded to, smallest first:
#: the rows' SHAPE is a step of this ladder and never the lists' lengths,
#: so one executable per (k, step) serves every query. The floor holds a
#: shop's usual query with room (serve-ecomm9m-rules-p4: at most 2,223
#: suppressed rows, a whiteList of at most 500); the one step above it is
#: 8 times that, where resolving the ids on the host (a dict lookup each)
#: already costs more than a dense mask does. Longer lists take the dense
#: host mask.
ROW_LADDER = (4096, 32768)


def row_capacity(deny, allow) -> Optional[int]:
    """The first step of `ROW_LADDER` that holds each of the two row
    lists (``allow`` may be None), or None where one is over the ladder's
    top."""
    longest = max(len(deny), 0 if allow is None else len(allow))
    return next((step for step in ROW_LADDER if longest <= step), None)


class RowExclude(NamedTuple):
    """What a query suppresses, as `_topk_scores` composes it on the
    device: ``base``, a ``bool[n_items]`` RESIDENT on the device (True =
    suppressed) or None for none; ``deny``, int32 catalog rows to
    suppress (duplicates allowed); ``allow``, int32 rows outside which
    EVERYTHING is suppressed, or None for no such list (an empty array
    suppresses the whole catalog). Both lists must fit `row_capacity`."""
    base: Optional[jax.Array]
    deny: np.ndarray
    allow: Optional[np.ndarray]


def pack_rows(deny, allow, capacity: int, n_items: int) -> np.ndarray:
    """The two lists as ONE int32 array (a put costs by the array, not by
    the byte, at this size): ``[deny | allow | whether allow was given]``,
    each list SORTED (the scatter is told so and skips its own sort) and
    filled up to ``capacity`` with the row ``n_items``: out of range, so
    the scatter drops it, and the largest, so the order holds."""
    out = np.full(2 * capacity + 1, n_items, np.int32)
    out[:len(deny)] = np.sort(deny)
    if allow is not None:
        out[capacity:capacity + len(allow)] = np.sort(allow)
    out[-1] = allow is not None
    return out


def put_rows(exclude: RowExclude, n_items: int):
    """`pack_rows`'s array of the two lists, on the device. Span
    ``topk.mask_put`` (tag ``bytes``), as for a dense host mask."""
    _base, deny, allow = exclude
    capacity = row_capacity(deny, allow)
    if capacity is None:
        raise ValueError(
            f"{len(deny)} deny / {0 if allow is None else len(allow)} allow "
            f"rows are over the ladder {ROW_LADDER}")
    with telemetry.span("topk.mask_put") as sp:
        rows = pack_rows(deny, allow, capacity, n_items)
        sp.tag(bytes=rows.nbytes)
        return jax.device_put(rows)


def suppress_rows(scores, rows):
    """-inf at the rows of ``deny`` and, where an ``allow`` list was
    given, at every row outside it: elementwise what the dense mask of
    the same lists gives (models/_filters.py). Whether it was given is a
    traced value, so a query with a whiteList and one without share the
    executable. ``rows`` is `pack_rows`'s array."""
    capacity = rows.shape[0] // 2
    deny, allow, has_allow = rows[:capacity], rows[capacity:-1], rows[-1] != 0
    listed = jnp.zeros(scores.shape, bool).at[allow].set(
        True, mode="drop", indices_are_sorted=True)
    scores = jnp.where(has_allow & ~listed, -jnp.inf, scores)
    return scores.at[deny].set(-jnp.inf, mode="drop", indices_are_sorted=True)


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_scores(user_vec, item_factors, exclude_mask, k: int, rows=None):
    # mul+reduce instead of a gemv: the reduction tree over rank is then
    # independent of the row count, so a MODEL_AXIS-sharded catalog
    # (ops/sharded_topk.py) produces bitwise-identical scores. A gemv's
    # row-block tail handling varies with n_items — measured 1-ULP
    # differences on row slices. Cost: none; the serving matvec is
    # HBM-bandwidth-bound on reading the catalog either way.
    scores = (item_factors * user_vec[None, :]).sum(axis=1)  # [n_items]
    scores = jnp.where(exclude_mask, -jnp.inf, scores)
    if rows is not None:
        scores = suppress_rows(scores, rows)
    block_len = select_block_len(scores.shape[0], k)
    if block_len:
        return select_topk(scores, k, block_len)
    return jax.lax.top_k(scores, k)


@functools.lru_cache(maxsize=None)
def no_exclude_mask(n_items: int):
    """Device-resident all-False mask, one per catalog size. Building
    `jnp.zeros((n_items,), bool)` per query cost ~0.2 ms of eager
    dispatch + transfer on the CPU-local hot path (ISSUE 17 profile) —
    for a mask that never changes. Same jit cache key, same executable,
    so answers stay bitwise identical."""
    return jax.device_put(np.zeros((n_items,), dtype=bool))


def top_k_items(user_vec, item_factors, k: int, exclude=None):
    """Returns (scores[k], indices[k]) as host numpy arrays.

    ``exclude`` says which items to suppress (True = suppressed), and its
    TYPE which path the call takes; the answer is the same on each:

    - None, or a ``bool[n_items]`` array resident on the device (a
      ``jax.Array``: `no_exclude_mask`, a `CategoryIndex` device mask):
      nothing crosses to the device but the query vector;
    - a host ``np.ndarray`` ``bool[n_items]`` (the dense mask of
      `models/_filters.build_exclude_mask`): its ``n_items`` bytes are put
      under span ``topk.mask_put``;
    - a `RowExclude`: its row lists, padded to a step of `ROW_LADDER`, are
      put under the same span (tag ``bytes``: some KB) and `_topk_scores`
      composes the mask from them and the resident base. Lists over the
      ladder's top are the caller's to turn into a dense mask
      (``row_capacity`` says so beforehand): ValueError here.
    """
    n_items = item_factors.shape[0]
    rows = None
    if isinstance(exclude, RowExclude):
        exclude, rows = exclude.base, put_rows(exclude, n_items)
    if exclude is None:
        exclude = no_exclude_mask(n_items)
    k = min(int(k), n_items)
    # arguments go to the jitted kernel RAW: jit's C++ dispatch commits
    # them to device far cheaper than eager jnp.asarray per query
    # (measured ~0.4 ms/query of lax_numpy/bind machinery saved)
    # topk.dispatch: the enqueue (a first k's compile shows here, with
    # an xla.compile child); topk.wait: the device's queue, the scan and
    # the readback. ``select`` says which selection this (n_items, k)
    # compiled to: the predicate _topk_scores itself traces with.
    select = "blocks" if select_block_len(n_items, k) else "direct"
    _M_SELECT.labels(select).inc()
    if isinstance(exclude, np.ndarray):
        # a dense mask built on the host this query (the business rules of
        # models/_filters.py): its bytes cross here, under a span of their
        # own, so that topk.dispatch stays the enqueue alone. A resident
        # mask (no_exclude_mask) takes no put.
        with telemetry.span("topk.mask_put", bytes=exclude.nbytes):
            exclude = jax.device_put(exclude)
    with telemetry.span("topk.dispatch", select=select):
        out = _topk_scores(user_vec, item_factors, exclude, k, rows)
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
    bit-identity guarantee (ops/sharded_topk.py).

    Each row over its own norm, taken in float64; a zero row stays zero
    (the reference's cosine answers 0 for a zero vector). No additive
    epsilon: implicit ALS shrinks the rows of an item that only
    one-item users view by orders of magnitude a sweep (norms of 1e-9
    after five), and ``x / (norm + 1e-9)`` scored such an item at a
    fraction of its cosine."""
    x = np.asarray(x, np.float32)
    norm = np.sqrt(np.einsum("ij,ij->i", x, x, dtype=np.float64))
    # a reciprocal that float32 cannot hold is a zero row's
    with np.errstate(divide="ignore"):
        inv = np.where(norm > np.finfo(np.float32).tiny, 1.0 / norm, 0.0)
    return x * inv.astype(np.float32)[:, None]


def similar_items(query_vecs, item_factors_normed, k: int, exclude=None):
    """Summed cosine similarity of query items against the catalog
    (similar-product semantics). ``item_factors_normed`` must be
    row-normalized (normalize_rows) — model caches do this once.

    sum_q dot(f, qn_q) == dot(f, sum_q qn_q): the query vectors fold
    into one, so this is exactly the top_k_items matvec — one kernel,
    shared executables, and bitwise parity with the sharded path."""
    qn = normalize_rows(np.atleast_2d(np.asarray(query_vecs, np.float32)))
    return top_k_items(qn.sum(axis=0), item_factors_normed, k, exclude=exclude)
