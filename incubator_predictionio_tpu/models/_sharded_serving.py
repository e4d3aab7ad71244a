"""Template-facing serving-catalog facade for the ALS-family models.

Reference: core/.../controller/PAlgorithm.scala — batchPredict (serve a
model that stays distributed). ``ShardedCatalog`` here is the ONE object
templates score through; it picks the device layout at construction and
the templates never see which kernel answered (lint rule
``sharded-topk-confinement``: only this module may touch
``ops.sharded_topk`` internals):

- ``mesh`` — a serving mesh was assigned (``serving_mesh_for``: the
  catalog's bytes against ``parallel.mesh.device_memory_bytes``, or the
  engine.json key ``shardedServing``): dim 0 split over every mesh
  device, candidates merged through an all_gather.
- ``flat`` — the whole matrix on one device (``ops/topk.py``).

Both layouts answer bit-identically on the single-query and
similarity paths, and with identical indices on the batched path (see
ops/sharded_topk.py module docstring for the measured gemm-ULP caveat).

Each template model keeps two dataclass fields (``serving_mesh``,
``_sharded_cat`` — dataclass machinery needs them declared per class)
and mixes in ``ShardedCatalogServing`` for the caching + layout
selection, so the sharding policy lives in exactly one place.
"""

from __future__ import annotations

import numpy as np

from ..ops.sharded_topk import (  # noqa: F401  (serving_mesh_for and
    # validate_serving_mode are re-exported: templates import the whole
    # sharding surface from HERE, never from ops.sharded_topk)
    put_sharded_catalog,
    serving_mesh_for,
    sharded_batch_top_k,
    sharded_similar_items,
    sharded_top_k_items,
    validate_serving_mode,
)
from ..ops.topk import batch_top_k, similar_items, top_k_items

__all__ = [
    "ShardedCatalog", "ShardedCatalogServing",
    "serving_mesh_for", "validate_serving_mode",
]


class ShardedCatalog:
    """Layout-selecting serving catalog: factor rows resident on one
    device (``flat``) or sharded over the serving mesh (``mesh``),
    scored through one API."""

    def __init__(self, host_factors, serving_mesh=None):
        import jax

        x = np.asarray(host_factors, np.float32)
        self.n_items = int(x.shape[0])
        if serving_mesh is not None:
            self.layout = "mesh"
            self._cat = put_sharded_catalog(x, serving_mesh)
        else:
            self.layout = "flat"
            self._cat = jax.device_put(x)

    @property
    def n_shards(self) -> int:
        return self._cat.n_shards if self.layout == "mesh" else 1

    def top_k(self, user_vec, k: int, exclude=None):
        """(scores[k'], idx[k']) host numpy. ``exclude`` is the optional
        business-rule filter (True = suppressed), applied BEFORE the
        (per-shard, partial) top-k, in one of the forms
        `models/_filters.build_exclude` makes: on either layout a dense
        ``bool[n_items]`` host mask, shipped whole every query; on the
        ``flat`` layout also a ``bool[n_items]`` resident on the device
        (nothing shipped) or an `ops/topk.RowExclude` (rows and a
        resident base mask; the mask is composed on the device, see
        `ops/topk.top_k_items`). The ``mesh`` layout's kernel takes the
        dense mask only."""
        if self.layout == "mesh":
            return sharded_top_k_items(user_vec, self._cat, k,
                                       exclude=exclude)
        return top_k_items(user_vec, self._cat, k, exclude=exclude)

    def batch_top_k(self, user_vecs, k: int):
        """Micro-batch window path: ONE dispatch for the whole
        coalesced batch, whatever the layout."""
        if self.layout == "mesh":
            return sharded_batch_top_k(user_vecs, self._cat, k)
        return batch_top_k(user_vecs, self._cat, k)

    def similar(self, query_vecs, k: int, exclude=None):
        """Summed-cosine similarity — the catalog must hold
        ROW-NORMALIZED factors (similar-product's ``_host_catalog``)."""
        if self.layout == "mesh":
            return sharded_similar_items(query_vecs, self._cat, k,
                                         exclude=exclude)
        return similar_items(query_vecs, self._cat, k, exclude=exclude)


class ShardedCatalogServing:
    """Caches the device-resident ``ShardedCatalog`` picked by the
    deploy-time ``serving_mesh`` decision. Without the cache every
    query would re-upload the whole matrix and p50 blows past the 10 ms
    budget — the serving hot path uploads only the rank-float query
    vector.

    Subclasses override ``_host_catalog()`` when the served factors are
    not the raw item factors (similar-product serves row-normalized
    vectors).
    """

    def _host_catalog(self):
        return self.factors.item_factors

    def catalog(self) -> ShardedCatalog:
        if self._sharded_cat is None:
            self._sharded_cat = ShardedCatalog(
                self._host_catalog(), self.serving_mesh)
        return self._sharded_cat

    def sharded_catalog(self):
        """Back-compat mesh-layout handle (tools/big_catalog_demo)."""
        cat = self.catalog()
        if cat.layout != "mesh":
            raise ValueError("model has no serving mesh assigned")
        return cat._cat

    def warm_catalog(self) -> None:
        """Make the catalog resident (called from model warm_up)."""
        self.catalog()
