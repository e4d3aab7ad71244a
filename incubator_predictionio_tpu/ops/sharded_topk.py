"""Sharded serving: top-k over catalogs bigger than one chip's HBM.

Reference: core/.../controller/PAlgorithm.scala — batchPredict (models that
stay distributed at serve time and are queried without collecting to one
node; the MLlib ALX scenario SURVEY.md §7 "hard parts" names explicitly).

TPU-native redesign: the item-factor matrix lives sharded over EVERY device
of the serving mesh (dim 0 split across all mesh axes). A query computes
per-shard local scores and a per-shard local top-k, then all_gathers only
the k-candidate (score, global-index) pairs — never a full score row — and
merges them with a two-key lexicographic sort that reproduces single-device
``lax.top_k`` semantics bit-for-bit (ties break toward the lowest global
index, exactly as ``lax.top_k`` does). That stays true now that the flat
single-device path no longer calls ``lax.top_k`` on a large row:
``ops/topk._select_topk`` returns ``lax.top_k``'s values and indices by
construction (its docstring has the argument) and ends in this same
two-key sort, so "what ``lax.top_k`` gives on the unsharded row" is still
the one contract all three layouts meet. Per-query collective traffic is
O(shards * k * 8 bytes), independent of catalog size, so it rides ICI
comfortably at serving rates.

Bit-identity with the single-device kernels in ops/topk.py is a tested
invariant (tests/test_sharded_serving.py): sharding splits rows, never the
rank-reduction axis, and the merge preserves top_k's selection + tie order.
The single-query (matvec) and similarity paths are bitwise identical to
their unsharded counterparts. The batched path returns identical indices
in identical order with scores equal to ≤2 ULP: gemm libraries block the
reduction by OUTPUT shape, so even the unsharded kernel produces slightly
different bits for a [b, N] vs [b, N/8] product — measured, not assumed
(same holds for MXU tilings on TPU). Matvec lowers per-row and is
shape-independent, which is why the serving hot path stays exact.

Two shard layouts share that contract:

- MESH sharding (``ShardedCatalog``): catalogs beyond one chip's HBM,
  dim 0 split over every device of the serving mesh, candidates merged
  through an all_gather. One shard per device.
- HOST sharding (``HostShardedCatalog``): million-item catalogs on a
  SINGLE device. The catalog lives as one stacked [S, rows, rank] device
  array and a ``lax.scan`` walks the shard axis, so peak per-step memory
  is one shard's score row instead of the full [b, N] score matrix, and
  business-rule filters mask each shard BEFORE its partial top-k so
  filtered items never reach the merge. Armed by ``PIO_SERVE_SHARD_ITEMS``
  (rows per shard; 0 = off). The merge is the same two-key sort, so the
  bit-identity contract above carries over verbatim.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import device_memory_bytes, pad_rows
from .topk import bucket_k, pad_batch_pow2


def _mesh_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


@dataclasses.dataclass
class ShardedCatalog:
    """Item-factor matrix resident sharded over all devices of a mesh.

    ``dev`` is [Np, rank] with dim 0 split over every mesh axis; rows
    ``n_items..Np-1`` are zero padding (masked to -inf inside the kernels
    so they can never displace a real item).
    """

    dev: object
    n_items: int
    mesh: Mesh

    @property
    def rank(self) -> int:
        return self.dev.shape[1]

    @property
    def padded_rows(self) -> int:
        return self.dev.shape[0]

    @property
    def n_shards(self) -> int:
        return int(self.mesh.size)


def put_sharded_catalog(item_factors, mesh: Mesh) -> ShardedCatalog:
    """Host factors → device catalog sharded over all mesh axes on dim 0."""
    x = np.asarray(item_factors, np.float32)
    shards = int(mesh.size)
    padded = pad_rows(x, shards)
    sharding = NamedSharding(mesh, P(_mesh_axes(mesh), None))
    return ShardedCatalog(jax.device_put(padded, sharding), x.shape[0], mesh)


# -- sharding decision -----------------------------------------------------


def _serving_shard_threshold_bytes() -> int:
    """Catalog size beyond which "auto" shards serving: an explicit
    PIO_SHARDED_SERVING_BYTES wins (malformed → warn + device default);
    otherwise 1/4 of the device's reported memory
    (parallel.mesh.device_memory_bytes) — factors compete with the
    training slabs and per-query intermediates for HBM."""
    from ..common import envknobs

    raw = envknobs.env_str("PIO_SHARDED_SERVING_BYTES", "")
    if raw:
        explicit = envknobs.env_int("PIO_SHARDED_SERVING_BYTES", 0,
                                    float_ok=True)
        if explicit > 0:
            return explicit
        import warnings

        warnings.warn(
            f"PIO_SHARDED_SERVING_BYTES={raw!r} is not a positive "
            "number; using the device-derived default", stacklevel=2)
    return device_memory_bytes() // 4


def validate_serving_mode(mode: str) -> str:
    """Fail fast on a bad "shardedServing" value — called at the TOP of
    train so a typo dies before the expensive ALS run, not after it."""
    if mode not in ("auto", "always", "never"):
        raise ValueError(
            f"shardedServing={mode!r}: expected auto|always|never")
    return mode


def should_shard_serving(
    n_items: int, rank: int, mesh: Optional[Mesh], mode: str = "auto"
) -> bool:
    """Deploy-time policy: shard item factors over the mesh?

    mode: "never" | "always" | "auto" (auto → shard when the f32 factor
    matrix exceeds the per-chip budget). Engine.json spelling:
    "shardedServing". A 1-device mesh never shards (nothing to split)."""
    validate_serving_mode(mode)
    if mesh is None or mode == "never" or int(mesh.size) <= 1:
        return False
    if mode == "always":
        return True
    return n_items * rank * 4 > _serving_shard_threshold_bytes()


def serving_mesh_for(ctx, n_items: int, rank: int, mode: str):
    """The deploy-time sharding decision every ALS-family algorithm
    shares (train + restore_model): the ctx mesh when policy says shard,
    else None (single-chip serving)."""
    mesh = ctx.get_mesh() if ctx is not None else None
    return mesh if should_shard_serving(n_items, rank, mesh, mode) else None


# -- kernels ---------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _sharded_topk_fn(mesh: Mesh, k: int, has_exclude: bool):
    """Compile-cached sharded scorer (user·item affinity over a batch of
    query rows; similarity queries fold into a single row upstream).
    Cached per (mesh, bucketed-k, exclude?) so serving reuses
    executables across queries; jit handles shape specialisation below."""
    axes = _mesh_axes(mesh)
    shards = int(mesh.size)
    axis_sizes = [mesh.shape[a] for a in axes]
    item_spec = P(axes, None)
    row_spec = P(axes)

    def shard_fn(qv, local_items, local_excl, n_items):
        nl = local_items.shape[0]
        sid = jnp.int32(0)
        for a, _sz in zip(axes, axis_sizes):
            sid = sid * _sz + jax.lax.axis_index(a)
        rows = sid * nl + jnp.arange(nl, dtype=jnp.int32)
        if qv.shape[0] == 1:
            # single query: the same row-invariant mul+reduce the
            # single-device _topk_scores uses → bitwise-identical scores
            scores = (local_items * qv[0][None, :]).sum(axis=1)[None, :]
        else:
            scores = qv @ local_items.T  # [b, nl]
        dead = rows >= n_items
        if has_exclude:
            dead = dead | local_excl
        scores = jnp.where(dead[None, :], -jnp.inf, scores)
        kl = min(k, nl)
        s, li = jax.lax.top_k(scores, kl)  # [b, kl] local candidates
        gi = jnp.take(rows, li)
        gs = jax.lax.all_gather(s, axes)
        gg = jax.lax.all_gather(gi, axes)
        gs = gs.reshape((shards,) + s.shape)
        gg = gg.reshape((shards,) + gi.shape)
        b = s.shape[0]
        cand_s = jnp.moveaxis(gs, 0, 1).reshape(b, shards * kl)
        cand_i = jnp.moveaxis(gg, 0, 1).reshape(b, shards * kl)
        # two-key sort: score descending, global index ascending — the
        # exact tie order lax.top_k produces on an unsharded score row
        neg, idx = jax.lax.sort((-cand_s, cand_i), dimension=1, num_keys=2)
        kk = min(k, shards * kl)
        return -neg[:, :kk], idx[:, :kk]

    excl_spec = row_spec if has_exclude else P()

    @jax.jit
    def run(qv, items, excl, n_items):
        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(), item_spec, excl_spec, P()),
            out_specs=(P(), P()),
            check_vma=False,
        )(qv, items, excl, n_items)

    return run


def _put_exclude(exclude, cat: ShardedCatalog):
    mask = pad_rows(np.asarray(exclude, bool), cat.n_shards, fill=True)
    return jax.device_put(
        mask, NamedSharding(cat.mesh, P(_mesh_axes(cat.mesh))))


def sharded_top_k_items(user_vec, cat: ShardedCatalog, k: int, exclude=None):
    """Sharded analog of ops.topk.top_k_items — (scores[k], idx[k]) host."""
    k = min(int(k), cat.n_items)
    kp = bucket_k(k, cat.n_items)
    qv = np.asarray(user_vec, np.float32)[None, :]
    fn = _sharded_topk_fn(cat.mesh, kp, exclude is not None)
    excl = _put_exclude(exclude, cat) if exclude is not None else np.zeros(0, bool)
    s, i = jax.device_get(
        fn(qv, cat.dev, excl, np.int32(cat.n_items)))
    return s[0, :k], i[0, :k]


def sharded_batch_top_k(user_vecs, cat: ShardedCatalog, k: int):
    """Sharded analog of ops.topk.batch_top_k (same batch pow2 padding)."""
    user_vecs = np.asarray(user_vecs, np.float32)
    k = min(int(k), cat.n_items)
    b = user_vecs.shape[0]
    user_vecs = pad_batch_pow2(user_vecs)
    kp = bucket_k(k, cat.n_items)
    fn = _sharded_topk_fn(cat.mesh, kp, False)
    s, i = jax.device_get(
        fn(user_vecs, cat.dev, np.zeros(0, bool), np.int32(cat.n_items)))
    return s[:b, :k], i[:b, :k]


def sharded_similar_items(query_vecs, cat: ShardedCatalog, k: int, exclude=None):
    """Sharded analog of ops.topk.similar_items — ``cat`` must hold
    ROW-NORMALIZED factors (ops.topk.normalize_rows), mirroring the
    single-device contract. The query fold makes this the single-query
    matvec path, so scores are bitwise identical to the unsharded kernel."""
    from .topk import normalize_rows

    qn = normalize_rows(np.atleast_2d(np.asarray(query_vecs, np.float32)))
    return sharded_top_k_items(qn.sum(axis=0), cat, k, exclude=exclude)


# -- host sharding: million-item catalogs on ONE device --------------------


def env_serve_shard_items() -> int:
    """Rows per host shard (PIO_SERVE_SHARD_ITEMS). 0 (the default)
    disables host sharding entirely — serving is then bit-identical to,
    and literally the same code path as, the pre-sharding engine."""
    from ..common import envknobs

    return envknobs.env_int("PIO_SERVE_SHARD_ITEMS", 0, lo=0,
                            float_ok=True, warn=True)


@dataclasses.dataclass
class HostShardedCatalog:
    """Item factors stacked [S, rows_per_shard, rank] on ONE device.

    Rows ``n_items..S*rows_per_shard-1`` (the last shard's tail) are zero
    padding; the kernels mask them to -inf so they can never displace a
    real item. Unlike the mesh ``ShardedCatalog`` the shard count is a
    capacity choice (``PIO_SERVE_SHARD_ITEMS``), not the device count:
    a ``lax.scan`` over the shard axis bounds peak score-row memory at
    one shard regardless of catalog size."""

    dev: object
    n_items: int

    @property
    def rank(self) -> int:
        return self.dev.shape[2]

    @property
    def rows_per_shard(self) -> int:
        return self.dev.shape[1]

    @property
    def n_shards(self) -> int:
        return self.dev.shape[0]


def _stack_shards(x: np.ndarray, rows_per_shard: int, fill=0):
    """[N, ...] → [S, rows_per_shard, ...] with the tail padded by
    ``fill``."""
    n = x.shape[0]
    shards = max(1, -(-n // rows_per_shard))
    pad = shards * rows_per_shard - n
    if pad:
        x = np.concatenate(
            [x, np.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0)
    return x.reshape((shards, rows_per_shard) + x.shape[1:])


def put_host_sharded_catalog(item_factors,
                             rows_per_shard: int) -> HostShardedCatalog:
    """Host factors → single-device catalog stacked on a shard axis."""
    x = np.asarray(item_factors, np.float32)
    rows_per_shard = min(max(1, int(rows_per_shard)), max(1, x.shape[0]))
    stacked = _stack_shards(x, rows_per_shard)
    return HostShardedCatalog(jax.device_put(stacked), x.shape[0])


@functools.lru_cache(maxsize=None)
def _host_topk_fn(k: int, has_exclude: bool):
    """Compile-cached host-sharded scorer: scan the shard axis, per-shard
    mask (padding + business-rule filter) → partial top-k → exact global
    merge. Single-query rows use the row-invariant mul+reduce, so scores
    are bitwise identical to ops.topk._topk_scores; the batched rows use
    the same gemm contract as the mesh path (identical indices, scores
    within gemm-blocking ULPs)."""

    @jax.jit
    def run(qv, items, excl, n_items):
        shards, nl, _rank = items.shape
        kl = min(k, nl)

        def body(carry, xs):
            local_items, local_excl, b0 = xs
            rows = b0 + jnp.arange(nl, dtype=jnp.int32)
            if qv.shape[0] == 1:
                scores = (local_items * qv[0][None, :]).sum(axis=1)[None, :]
            else:
                scores = qv @ local_items.T  # [b, nl]
            dead = rows >= n_items
            if has_exclude:
                dead = dead | local_excl
            scores = jnp.where(dead[None, :], -jnp.inf, scores)
            s, li = jax.lax.top_k(scores, kl)  # [b, kl]
            return carry, (s, b0 + li)

        b0s = jnp.arange(shards, dtype=jnp.int32) * nl
        _, (ss, gg) = jax.lax.scan(body, 0, (items, excl, b0s))
        b = qv.shape[0]
        cand_s = jnp.moveaxis(ss, 0, 1).reshape(b, shards * kl)
        cand_i = jnp.moveaxis(gg, 0, 1).reshape(b, shards * kl)
        # same two-key merge as the mesh kernel: score descending, global
        # index ascending — lax.top_k's exact selection + tie order
        neg, idx = jax.lax.sort((-cand_s, cand_i), dimension=1, num_keys=2)
        kk = min(k, shards * kl)
        return -neg[:, :kk], idx[:, :kk]

    return run


def _stack_exclude(exclude, cat: HostShardedCatalog):
    mask = np.asarray(exclude, bool)
    return _stack_shards(mask, cat.rows_per_shard, fill=True)


def host_sharded_top_k_items(user_vec, cat: HostShardedCatalog, k: int,
                             exclude=None):
    """Host-sharded analog of ops.topk.top_k_items — (scores[k], idx[k])
    host numpy, bitwise identical to the unsharded kernel."""
    k = min(int(k), cat.n_items)
    kp = bucket_k(k, cat.n_items)
    qv = np.asarray(user_vec, np.float32)[None, :]
    fn = _host_topk_fn(kp, exclude is not None)
    excl = (_stack_exclude(exclude, cat) if exclude is not None
            else np.zeros((cat.n_shards, 1), bool))
    s, i = jax.device_get(fn(qv, cat.dev, excl, np.int32(cat.n_items)))
    return s[0, :k], i[0, :k]


def host_sharded_batch_top_k(user_vecs, cat: HostShardedCatalog, k: int):
    """Host-sharded analog of ops.topk.batch_top_k (same batch pow2
    padding), for the micro-batch window: one scanned dispatch scores the
    WHOLE coalesced batch against every shard."""
    user_vecs = np.asarray(user_vecs, np.float32)
    k = min(int(k), cat.n_items)
    b = user_vecs.shape[0]
    user_vecs = pad_batch_pow2(user_vecs)
    kp = bucket_k(k, cat.n_items)
    fn = _host_topk_fn(kp, False)
    s, i = jax.device_get(
        fn(user_vecs, cat.dev, np.zeros((cat.n_shards, 1), bool),
           np.int32(cat.n_items)))
    return s[:b, :k], i[:b, :k]


def host_sharded_similar_items(query_vecs, cat: HostShardedCatalog, k: int,
                               exclude=None):
    """Host-sharded analog of ops.topk.similar_items — ``cat`` must hold
    ROW-NORMALIZED factors; the query fold keeps this on the bitwise-
    exact single-query path."""
    from .topk import normalize_rows

    qn = normalize_rows(np.atleast_2d(np.asarray(query_vecs, np.float32)))
    return host_sharded_top_k_items(qn.sum(axis=0), cat, k, exclude=exclude)


# -- host sharding for the universal recommender's indicator scorer -------


@dataclasses.dataclass
class HostShardedIndicators:
    """One event type's correlator table stacked [S, rows_per_shard, K]
    on one device. Padding rows hold idx=-1 (the "no correlator" value),
    so their gathered membership — and score contribution — is zero; the
    kernel additionally masks them to -inf before the partial top-k."""

    idx: object    # int32 [S, nl, K]
    score: object  # float32 [S, nl, K]

    @property
    def rows_per_shard(self) -> int:
        return self.idx.shape[1]

    @property
    def n_shards(self) -> int:
        return self.idx.shape[0]


def put_host_sharded_indicators(indicators,
                                rows_per_shard: int) -> HostShardedIndicators:
    """ops.llr.Indicators → stacked single-device shard layout."""
    idx = np.asarray(indicators.idx, np.int32)
    score = np.asarray(indicators.score, np.float32)
    rows_per_shard = min(max(1, int(rows_per_shard)), max(1, idx.shape[0]))
    return HostShardedIndicators(
        jax.device_put(_stack_shards(idx, rows_per_shard, fill=-1)),
        jax.device_put(_stack_shards(score, rows_per_shard)))


@functools.lru_cache(maxsize=None)
def _host_ur_topk_fn(k: int, n_types: int):
    """Host-sharded twin of ops.llr.score_user: the einsum reduction runs
    over the correlator axis PER ROW, so sharding the item rows leaves
    every row's arithmetic — gather, einsum, boost, exclude — bitwise
    intact; only the top-k selection is split and exactly re-merged."""

    @jax.jit
    def run(idxs, scores, membs, boosts, item_boost, exclude, n_items):
        shards, nl = idxs[0].shape[0], idxs[0].shape[1]
        kl = min(k, nl)

        def body(carry, xs):
            loc_idx, loc_score, ib, ex, b0 = xs
            rows = b0 + jnp.arange(nl, dtype=jnp.int32)
            total = jnp.zeros((nl,), jnp.float32)
            for t in range(n_types):
                m = jnp.where(loc_idx[t] >= 0,
                              membs[t][jnp.maximum(loc_idx[t], 0)], 0.0)
                total = total + jnp.einsum(
                    "ik,ik->i", loc_score[t], m) * boosts[t]
            total = total * ib
            total = jnp.where((rows >= n_items) | ex, -jnp.inf, total)
            s, li = jax.lax.top_k(total[None, :], kl)
            return carry, (s[0], b0 + li[0])

        b0s = jnp.arange(shards, dtype=jnp.int32) * nl
        _, (ss, gg) = jax.lax.scan(
            body, 0, (idxs, scores, item_boost, exclude, b0s))
        cand_s = ss.reshape(1, shards * kl)
        cand_i = gg.reshape(1, shards * kl)
        neg, idx = jax.lax.sort((-cand_s, cand_i), dimension=1, num_keys=2)
        kk = min(k, shards * kl)
        return -neg[0, :kk], idx[0, :kk]

    return run


def host_sharded_score_user(indicator_list, k: int, n_items: int,
                            exclude, item_boost):
    """Host-sharded analog of ops.llr.score_user. ``indicator_list`` is
    [(HostShardedIndicators, membership[N] f32, boost)], ``exclude`` a
    bool [N] mask (True = suppressed), ``item_boost`` a float [N] vector;
    returns (scores[k'], idx[k']) with k' = min(k, n_items), bitwise
    identical to the unsharded scorer."""
    if not indicator_list:
        raise ValueError("host_sharded_score_user needs >=1 indicator type")
    shards0 = indicator_list[0][0]
    nl = shards0.rows_per_shard
    k_eff = min(int(k), int(n_items))
    fn = _host_ur_topk_fn(k_eff, len(indicator_list))
    idxs = tuple(h.idx for h, _m, _b in indicator_list)
    scores = tuple(h.score for h, _m, _b in indicator_list)
    membs = tuple(jnp.asarray(m, jnp.float32)
                  for _h, m, _b in indicator_list)
    boosts = tuple(jnp.float32(b) for _h, _m, b in indicator_list)
    # None ⇒ identity mask/boost: *1.0f and where(False, ...) are exact,
    # so the no-filter call stays bitwise identical to ops.llr.score_user.
    ib_host = (np.ones(int(n_items), np.float32) if item_boost is None
               else np.asarray(item_boost, np.float32))
    ex_host = (np.zeros(int(n_items), bool) if exclude is None
               else np.asarray(exclude, bool))
    ib = _stack_shards(ib_host, nl)
    ex = _stack_shards(ex_host, nl, fill=True)
    s, i = jax.device_get(
        fn(idxs, scores, membs, boosts, ib, ex, np.int32(n_items)))
    return s[:k_eff], i[:k_eff]
