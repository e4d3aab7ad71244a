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
``ops/topk.select_topk`` returns ``lax.top_k``'s values and indices by
construction (its docstring has the argument) and ends in this same
two-key sort, so "what ``lax.top_k`` gives on the unsharded row" is still
the one contract both layouts (flat on one device, this mesh one) meet.
Per-query collective traffic is O(shards * k * 8 bytes), independent of
catalog size, so it rides ICI comfortably at serving rates.

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
"""

from __future__ import annotations

import dataclasses
import functools
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
    matrix exceeds 1/4 of the device's reported memory,
    parallel.mesh.device_memory_bytes — factors compete with the
    training slabs and per-query intermediates for HBM). Engine.json
    spelling: "shardedServing". A 1-device mesh never shards (nothing
    to split)."""
    validate_serving_mode(mode)
    if mesh is None or mode == "never" or int(mesh.size) <= 1:
        return False
    if mode == "always":
        return True
    return n_items * rank * 4 > device_memory_bytes() // 4


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
