"""Alternating Least Squares on a TPU mesh.

The reference's recommendation templates call MLlib's Spark ALS
(reference: examples/scala-parallel-recommendation — mllib ALS.train /
ALS.trainImplicit; the distributed in/out-block shuffle lives inside Spark,
SURVEY.md §2.9). This is a ground-up TPU design instead, following the ALX
recipe (PAPERS.md: arxiv 2112.02194):

- Ratings are laid out as length-bucketed dense row slabs
  (ops/rowblocks.py): each row's entries occupy one [C_b]-wide slab row,
  so the per-row normal equations fall straight out of a batched
  [R, C_b, k] einsum on the MXU — there is no tile→row segment reduction
  at all. The layout minimizes padded entries because the half-step is
  GATHER-BOUND: the TPU gather unit sustains a fixed ~420M rows/s
  (measured 2026-07, tools/profile_als.py), so every padded entry wastes
  a fixed gather slot. See docs/tpu.md "Findings carried from 2026-07".
- Factor matrices are dense f32 arrays in layout ("π") order. The side
  being *solved* is slot-sharded over the mesh data axis; on a 1-D mesh
  the counterpart factor matrix is replicated for the gather. On a 2-D
  (d, m) mesh the counterpart is instead row-sharded over MODEL_AXIS
  (the ALX sharded layout): each device gathers only slots it owns
  (zeros elsewhere) and the per-row normal equations — linear in
  per-entry outer products — are psummed over 'm'. HBM budget: factor
  storage per device is n_rows·k·4/m bytes, so catalog capacity scales
  linearly with the model axis. Ownership windows are windows of SLOTS,
  so the ALX layout composes with any data-axis layout (including
  a mesh that spans processes) with no extra machinery.
- One half-step solves the regularized normal equations
  (YᵀY + λ·c·I) x = Yᵀr per row with a batched Pallas elimination
  solve (ops/pallas_kernels.py).
- The whole iteration loop runs inside one jit under shard_map; the only
  cross-device traffic is the counterpart replication (1-D) or, on 2-D
  meshes, per-chunk normal-equation psums (one [512, k, k] all-reduce
  per fused solve chunk — same total bytes as a single big psum, more
  latency points; the price of never materializing the normal
  equations) + the factor re-shard.

Regularization conventions (must match template behaviour — SURVEY.md §7
"hard parts"): ``lambda_scaling='nratings'`` multiplies λ by the row's
rating count (ALS-WR, classic MLlib); ``'plain'`` uses λ directly
(Spark ≥1.4 default). Both supported; explicit and implicit feedback.
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
from ..common import telemetry
from ..common.faultinject import fault_point
from ..parallel import supervisor as gang

from .pallas_kernels import batched_spd_solve, solve_path
from .rowblocks import (
    BucketArrays, LayoutPlan,
    plan_and_fill_both,
)
from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, default_mesh, fast_put


@dataclasses.dataclass(frozen=True)
class ALSParams:
    rank: int = 10
    num_iterations: int = 10
    reg: float = 0.01  # "lambda" in engine.json (reserved word in Python)
    lambda_scaling: str = "plain"  # 'plain' | 'nratings'
    implicit_prefs: bool = False
    alpha: float = 1.0  # implicit-feedback confidence weight
    seed: int = 3
    # Retained for engine.json compatibility (blockLen): the bucketed
    # layout has no tiles, so this only scales the chunk_tiles budget
    # below (chunk_tiles × block_len = gathered entries per device step).
    block_len: int = 32
    # "auto" → bfloat16 on a TPU mesh, float32 elsewhere. Explicit
    # "float32"/"bfloat16" override.
    compute_dtype: str = "auto"
    # Device-step granularity: each bucket's gather+gram(+solve) slab is
    # chunked to ≈ chunk_tiles × block_len gathered entries per step,
    # bounding the live [chunk, C_b, k] intermediate. -1 OR 0 = auto
    # (the fused pipeline targets 512-row chunks — the Pallas solve's
    # native slab width — capped at ~0.5 GB of gathered slab; chunking
    # never changes the math in this layout, so there is no "unchunked"
    # mode to ask for); engine.json's chunkTiles maps here and an
    # explicit value bounds the fused slab too.
    chunk_tiles: int = -1
    # All-ones ratings (implicit view/buy streams): the value slabs are
    # fully derivable on device, so train_als skips building/uploading
    # them — about half the host→device slab bytes. None = auto-detect
    # from the data; False forces the explicit-value path (tests).
    binary_ratings: "bool | None" = None


@dataclasses.dataclass
class ALSFactors:
    user_factors: np.ndarray  # [n_users, k] f32 (host side after train)
    item_factors: np.ndarray  # [n_items, k]
    n_users: int
    n_items: int


_AUTO_ENTRIES_PER_STEP = 1 << 17

# Checkpoint-fingerprint seed identifying the factor-storage layout
# ("π"/slot order, ops/rowblocks.py). Bump when the layout changes so
# snapshots from an older layout are rejected deterministically instead
# of resuming permuted factors when shapes happen to coincide.
_LAYOUT_TAG = 0x70_10_00_02


def _resolve_params(mesh: Mesh, params: ALSParams) -> tuple[ALSParams, int]:
    """Materialize 'auto' knobs; returns (params, entries_per_step)."""
    cd = params.compute_dtype
    if cd == "auto":
        platform = mesh.devices.flat[0].platform
        cd = "bfloat16" if platform == "tpu" else "float32"
        params = dataclasses.replace(params, compute_dtype=cd)
    if params.chunk_tiles > 0:
        entries = max(params.chunk_tiles * max(params.block_len, 1), 8)
    else:
        entries = _AUTO_ENTRIES_PER_STEP
    return params, entries


def _grams_rows(p, val, *, implicit, alpha, compute_dtype):
    """Per-row normal-equation contributions from gathered counterpart
    rows p [R, C, k]: grams [R, k, k] f32, rhs [R, k] f32.

    Padding / non-owned slots must already be zero rows in p. Both sums
    are linear in per-entry outer products, so zero rows contribute
    nothing — and shard-partial p's (each model shard zeroing slots it
    doesn't own) psum to exactly the full-gather result.

    ``val=None``: binary-ratings mode — every real entry is 1.0, so the
    per-entry weights collapse to scalars and no value slab ever exists
    (not even as a device-side ones array: a materialized ones slab
    would re-spend in HBM reads exactly the bytes the upload elision
    saved).
    """
    cd = compute_dtype
    if implicit:
        # Hu-Koren-Volinsky: A = YᵀY + Yᵀ(C-I)Y + λ·c·I, b = YᵀCp where
        # p=1 for observed. C-I = alpha·r on observed entries only.
        if val is None:
            grams = jnp.einsum("rck,rcm->rkm", p * jnp.asarray(alpha, cd), p,
                               preferred_element_type=jnp.float32)
            rhs = (1.0 + alpha) * jnp.sum(p, axis=1,
                                          dtype=jnp.float32)
        else:
            cw = (alpha * val)[..., None].astype(cd)  # confidence-1 weights
            w = 1.0 + alpha * val
            grams = jnp.einsum("rck,rcm->rkm", p * cw, p,
                               preferred_element_type=jnp.float32)
            rhs = jnp.einsum("rck,rc->rk", p, w.astype(cd),
                             preferred_element_type=jnp.float32)
    else:
        grams = jnp.einsum("rck,rcm->rkm", p, p,
                           preferred_element_type=jnp.float32)
        if val is None:
            rhs = jnp.sum(p, axis=1, dtype=jnp.float32)
        else:
            rhs = jnp.einsum("rck,rc->rk", p, val.astype(cd),
                             preferred_element_type=jnp.float32)
    return grams, rhs


def _gather_model_partial(y_local, col, compute_dtype):
    """ALX sharded gather: slots this shard owns, zero rows elsewhere.

    ``y_local`` is this device's slot shard of the counterpart factor
    matrix ([total_slots / m, k], MODEL_AXIS-sharded, contiguous blocks in
    axis order). Slot indices outside this shard's window — including the
    sentinel padding index — gather exact zeros, so psumming any
    per-entry-linear reduction of the result over MODEL_AXIS equals the
    full-gather reduction without ever materializing the full matrix on
    one device (PAPERS.md ALX, arxiv 2112.02194 §3).
    """
    cd = compute_dtype
    rows_local = y_local.shape[0]
    off = jax.lax.axis_index(MODEL_AXIS) * rows_local
    lc = col - off
    valid = (lc >= 0) & (lc < rows_local)
    p = jnp.take(y_local, jnp.clip(lc, 0, rows_local - 1), axis=0)
    return p.astype(cd) * valid[..., None].astype(cd)


def _slab_normal_eq(gather, colb, valb, *, sentinel, entries_per_step,
                    implicit, alpha, compute_dtype):
    """grams/rhs for one bucket slab [R, C], chunked over rows so the
    gathered [chunk, C, k] intermediate stays bounded."""
    R, C = colb.shape
    chunk_r = max(1, min(R, entries_per_step // max(C, 1)))
    n_sub = -(-R // chunk_r)
    kw = dict(implicit=implicit, alpha=alpha, compute_dtype=compute_dtype)
    if n_sub <= 1:
        return _grams_rows(gather(colb), valb, **kw)
    padR = n_sub * chunk_r - R
    cc = jnp.pad(colb, ((0, padR), (0, 0)), constant_values=sentinel)
    cc = cc.reshape(n_sub, chunk_r, C)

    if valb is None:  # binary-ratings: no value slab exists
        grams, rhs = jax.lax.map(
            lambda ccol: _grams_rows(gather(ccol), None, **kw), cc)
    else:
        vv = jnp.pad(valb, ((0, padR), (0, 0)))
        vv = vv.reshape(n_sub, chunk_r, C)

        def body(chunk):
            ccol, cval = chunk
            return _grams_rows(gather(ccol), cval, **kw)

        grams, rhs = jax.lax.map(body, (cc, vv))
    k = grams.shape[-1]
    return (grams.reshape(n_sub * chunk_r, k, k)[:R],
            rhs.reshape(n_sub * chunk_r, k)[:R])


def _ridge_solve(a, b, lam, yty, *, implicit, model_sharded, platform, k):
    """psum → +YᵀY → ridge → batched SPD solve (shared by the fused
    per-chunk path and the heavy-bucket path)."""
    if model_sharded:
        # Reconstruct the full per-row normal equations from the shard
        # partials — the one collective of the sharded gather.
        a = jax.lax.psum(a, MODEL_AXIS)
        b = jax.lax.psum(b, MODEL_AXIS)
    if implicit:
        a = a + yty[None, :, :]  # shared YᵀY term (all items)
    a = a + lam[:, None, None] * jnp.eye(k, dtype=jnp.float32)
    # Pallas VMEM elimination on TPU, XLA Cholesky elsewhere. platform
    # is the MESH's device platform, threaded from the caller —
    # jax.default_backend() is wrong here: the driver dry-runs a CPU mesh
    # while a TPU stays the process default backend (and vice versa in
    # tests), and pallas_call on CPU without interpret mode is an error.
    x = batched_spd_solve(a, b, vma=(DATA_AXIS,), platform=platform)
    return x.astype(jnp.float32)


#: rows per fused gather→gram→solve step: the Pallas solve's native slab
#: width, so per-chunk solves carry zero batch padding
_FUSED_CHUNK_ROWS = 512
#: cap on the gathered [chunk, C, k] slab bytes per fused step
_FUSED_SLAB_BYTES = 512 * 1024 * 1024


def _fused_bucket_solve(gather, colb, valb, lam_b, yty, *, sentinel,
                        entries_budget, implicit, alpha, compute_dtype,
                        model_sharded, platform, k):
    """One NON-overflow bucket: gather → per-row grams → ridge → solve,
    chunked over rows, never materializing the bucket's [R, k, k] normal
    equations (at rank 128 the full-side materialization would be ~11 GB
    at ML-20M — the r3 fused design keeps live memory per step at the
    [chunk, C, k] gather slab plus one [chunk, k, k] gram block).
    ``entries_budget``: user-configured cap on chunk_r × C (chunkTiles ×
    blockLen) — None = auto (512 rows, byte-capped)."""
    R, C = colb.shape
    cd_bytes = 2 if compute_dtype == jnp.bfloat16 else 4
    chunk_r = _FUSED_CHUNK_ROWS
    while chunk_r > 64 and chunk_r * C * k * cd_bytes > _FUSED_SLAB_BYTES:
        chunk_r //= 2
    if entries_budget is not None:
        chunk_r = max(1, min(chunk_r, entries_budget // max(C, 1) or 1))
    chunk_r = min(chunk_r, max(R, 1))
    n_sub = -(-R // chunk_r)
    kw = dict(implicit=implicit, alpha=alpha, compute_dtype=compute_dtype)

    def solve_chunk(ccol, cval, clam):
        grams, rhs = _grams_rows(gather(ccol), cval, **kw)
        return _ridge_solve(grams, rhs, clam, yty, implicit=implicit,
                            model_sharded=model_sharded, platform=platform,
                            k=k)

    if n_sub <= 1:
        return solve_chunk(colb, valb, lam_b)
    padR = n_sub * chunk_r - R
    cc = jnp.pad(colb, ((0, padR), (0, 0)), constant_values=sentinel)
    # padded lam rows: benign 1.0 ridge keeps the padded systems SPD
    ll = jnp.pad(lam_b, (0, padR), constant_values=1.0)
    if valb is None:  # binary-ratings: no value slab exists
        x = jax.lax.map(
            lambda chunk: solve_chunk(chunk[0], None, chunk[1]),
            (cc.reshape(n_sub, chunk_r, C), ll.reshape(n_sub, chunk_r)),
        )
    else:
        vv = jnp.pad(valb, ((0, padR), (0, 0)))
        x = jax.lax.map(
            lambda chunk: solve_chunk(*chunk),
            (cc.reshape(n_sub, chunk_r, C), vv.reshape(n_sub, chunk_r, C),
             ll.reshape(n_sub, chunk_r)),
        )
    return x.reshape(n_sub * chunk_r, k)[:R]


def _half_step_local(y, lam, yty, *bucket_args, plan: LayoutPlan,
                     sentinel, implicit, alpha, compute_dtype,
                     entries_per_step, entries_budget, platform,
                     model_sharded, binary=False):
    """Solve one side's factors for one shard's slots (runs inside
    shard_map; all arrays are the local shard).

    Replicated mode (``model_sharded=False``): ``y`` is the full
    counterpart matrix plus a trailing all-zero sentinel row that padding
    slot indices resolve to.

    Model-sharded mode: ``y`` is this device's MODEL_AXIS slot shard; the
    gather is partial (zeros for non-owned slots) and the per-row normal
    equations are psummed over MODEL_AXIS before the solve — the ALX
    sharded layout, so factor HBM scales with 1/m.

    Non-overflow buckets run the FUSED gather→gram→ridge→solve pipeline
    (no [R, k, k] materialization); the dedicated heavy bucket (overflow
    parents, plan.has_heavy_bucket) materializes its small gram block so
    the virtual slabs can scatter-add into it before its solve.
    """
    k = y.shape[1]
    n_buckets = len(plan.lengths)
    has_heavy = plan.has_heavy_bucket
    n_fused = n_buckets - (1 if has_heavy else 0)

    def gather(cols):
        # col slabs may arrive uint16 (narrow counterpart slot space —
        # half the upload bytes); widen per chunk, in-register.
        cols = cols.astype(jnp.int32)
        if model_sharded:
            return _gather_model_partial(y, cols, compute_dtype)
        return jnp.take(y, cols, axis=0).astype(compute_dtype)

    solve_kw = dict(implicit=implicit, model_sharded=model_sharded,
                    platform=platform, k=k)
    # binary mode: value slabs were never uploaded — every real entry is
    # 1.0, and padding/non-owned slots already gather zero factor ROWS,
    # so the per-entry weights collapse to scalars inside _grams_rows
    # (valb=None; no ones array is ever materialized).
    stride = 1 if binary else 2
    base = 0
    x_parts = []
    for bi in range(n_fused):
        colb = bucket_args[stride * bi]
        valb = None if binary else bucket_args[stride * bi + 1]
        R_b = colb.shape[0]
        x_parts.append(_fused_bucket_solve(
            gather, colb, valb, jax.lax.slice(lam, (base,), (base + R_b,)),
            yty, sentinel=sentinel, entries_budget=entries_budget,
            alpha=alpha, compute_dtype=compute_dtype, **solve_kw))
        base += R_b

    if has_heavy:
        colb = bucket_args[stride * n_fused]
        valb = None if binary else bucket_args[stride * n_fused + 1]
        if binary:
            v_cols, v_parent = bucket_args[n_buckets:n_buckets + 2]
            v_vals = None
        else:
            v_cols, v_vals, v_parent = (
                bucket_args[2 * n_buckets:2 * n_buckets + 3])
        R_h = colb.shape[0]
        kw = dict(sentinel=sentinel, entries_per_step=entries_per_step,
                  implicit=implicit, alpha=alpha,
                  compute_dtype=compute_dtype)
        a, b = _slab_normal_eq(gather, colb, valb, **kw)
        vg, vr = _slab_normal_eq(gather, v_cols, v_vals, **kw)
        # Merge overflow chunks into their parent rows; parents all live
        # in this (last) bucket, so re-base the shard-local slots.
        vp = v_parent - base
        a = a.at[vp].add(vg)
        b = b.at[vp].add(vr)
        x_parts.append(_ridge_solve(
            a, b, jax.lax.slice(lam, (base,), (base + R_h,)), yty,
            **solve_kw))

    return (jnp.concatenate(x_parts, axis=0) if len(x_parts) > 1
            else x_parts[0])


def _host_lam(plan: LayoutPlan, params: ALSParams) -> np.ndarray:
    """Per-slot ridge weights (static — computed once on the host)."""
    counts = plan.counts_slot.astype(np.float32)
    if params.lambda_scaling == "nratings":
        lam = params.reg * np.maximum(counts, 1.0)
    else:
        lam = np.full(counts.shape, params.reg, dtype=np.float32)
    # Slots with no ratings keep a well-conditioned system (solution 0).
    return (lam + np.where(counts == 0, 1e-6, 0.0)).astype(np.float32)


def _side_flat(arrs: BucketArrays, plan: LayoutPlan, lam: np.ndarray,
               binary: bool = False, col_sentinel: int | None = None):
    """Flatten one side's device args: per-bucket (col, val) pairs,
    optional (v_cols, v_vals, v_parent), then lam. ``binary``: value
    slabs are elided entirely (synthesized on device as ones).
    ``col_sentinel``: the counterpart sentinel index — when it fits
    uint16, col slabs upload at half width (the device widens per chunk
    inside the gather)."""
    narrow = col_sentinel is not None and col_sentinel <= np.iinfo(np.uint16).max

    def col(c):
        return c.astype(np.uint16) if narrow else c

    if binary:
        flat = [col(c) for c in arrs.cols]
        if plan.v_rows_per_shard > 0:
            flat += [col(arrs.v_cols), np.asarray(plan.v_parent, np.int32)]
    else:
        flat = []
        for c, v in zip(arrs.cols, arrs.vals):
            flat += [col(c), v]
        if plan.v_rows_per_shard > 0:
            flat += [col(arrs.v_cols), arrs.v_vals,
                     np.asarray(plan.v_parent, np.int32)]
    flat.append(lam)
    return flat


def _make_train_fn(mesh: Mesh, params: ALSParams, plan_u: LayoutPlan,
                   plan_i: LayoutPlan):
    """Build the jitted full training loop for fixed layouts. Returns
    (fitted_fn, in_shardings); call as fn(n_iters, x0, y0, *u_flat,
    *i_flat) with the _side_flat arg order."""
    params, entries_per_step = _resolve_params(mesh, params)
    # an EXPLICIT chunkTiles bounds the fused pipeline's slab too; auto
    # lets it target the solve's native 512-row chunks
    entries_budget = entries_per_step if params.chunk_tiles > 0 else None
    cd = jnp.bfloat16 if params.compute_dtype == "bfloat16" else jnp.float32
    implicit = params.implicit_prefs
    # Kernel selection must follow the MESH's platform, not the process
    # default backend (see _half_step_local docstring).
    mesh_platform = mesh.devices.flat[0].platform
    # 2-D (d, m) mesh → ALX factor sharding: the counterpart factor
    # matrix is row-sharded over MODEL_AXIS (HBM per device ∝ 1/m) and
    # the per-row normal equations are psummed from shard partials.
    model_sharded = MODEL_AXIS in mesh.axis_names

    row2 = P(DATA_AXIS, None)
    row1 = P(DATA_AXIS)
    rep = P()
    y_spec = P(MODEL_AXIS, None) if model_sharded else rep

    binary = bool(params.binary_ratings)

    def side_specs(plan: LayoutPlan):
        specs = []
        for _ in plan.lengths:
            specs += [row2] if binary else [row2, row2]
        if plan.v_rows_per_shard > 0:
            specs += ([row2, row1] if binary else [row2, row2, row1])
        specs.append(row1)  # lam
        return specs

    u_specs, i_specs = side_specs(plan_u), side_specs(plan_i)
    n_u_args = len(u_specs)

    def one_side(y, flat, plan, specs, sentinel):
        if model_sharded:
            # No sentinel row: the sharded gather masks by ownership
            # window (the sentinel index falls outside every window).
            y_cd = jax.lax.with_sharding_constraint(
                y.astype(cd), NamedSharding(mesh, y_spec))
        else:
            # Sentinel zero row appended so padding slot indices gather
            # 0s (mask-free hot loop); cast once so the hot loop gathers
            # half-width bf16 rows instead of f32.
            y_cd = jnp.concatenate(
                [y, jnp.zeros((1, y.shape[1]), y.dtype)], axis=0
            ).astype(cd)
        yty = (
            jnp.einsum("nk,nm->km", y_cd, y_cd,
                       preferred_element_type=jnp.float32)
            if implicit
            else jnp.zeros((params.rank, params.rank), jnp.float32)
        )
        lam = flat[-1]
        bucket_args = flat[:-1]
        fn = shard_map(
            functools.partial(
                _half_step_local,
                plan=plan,
                sentinel=sentinel,
                implicit=implicit,
                alpha=params.alpha,
                compute_dtype=cd,
                entries_per_step=entries_per_step,
                entries_budget=entries_budget,
                platform=mesh_platform,
                model_sharded=model_sharded,
                binary=binary,
            ),
            mesh=mesh,
            in_specs=(y_spec, row1, rep) + tuple(specs[:-1]),
            out_specs=row1,
        )
        x = fn(y_cd, lam, yty, *bucket_args)
        if model_sharded:
            # Solved slots leave the shard_map split over 'd'; re-shard to
            # the MODEL_AXIS storage layout (XLA all-to-all over ICI) so
            # the next half-step consumes it as a sharded counterpart.
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, y_spec))
        return x

    sent_u, sent_i = plan_u.total_slots, plan_i.total_slots

    # The big slab arrays enter as jit args (not baked-in constants), and
    # n_iters is traced so one compilation serves full runs, checkpoint
    # chunks, and resume remainders alike.
    def loop(n_iters, x0, y0, *flat):
        u_flat = flat[:n_u_args]
        i_flat = flat[n_u_args:]

        def body(_, carry):
            x, y = carry
            x = one_side(y, u_flat, plan_u, u_specs, sent_i)
            y = one_side(x, i_flat, plan_i, i_specs, sent_u)
            return (x, y)

        return jax.lax.fori_loop(0, n_iters, body, (x0, y0))

    factors_s = NamedSharding(mesh, y_spec)
    in_shardings = (
        NamedSharding(mesh, rep), factors_s, factors_s,
    ) + tuple(NamedSharding(mesh, s) for s in u_specs + i_specs)
    # Outputs stay MODEL_AXIS-sharded on a 2-D mesh — replicating here
    # would all-gather both full factor matrices onto every device and
    # defeat the 1/m HBM scaling (host device_get assembles from shards).
    # Multi-controller runs need replicated outputs so every process can
    # device_get its result.
    out_s = (factors_s if jax.process_count() == 1
             else NamedSharding(mesh, rep))
    fitted = jax.jit(
        loop,
        in_shardings=in_shardings,
        out_shardings=(out_s, out_s),
    )
    return fitted, in_shardings


def _mesh_dims(mesh: Mesh) -> tuple[int, int]:
    if DATA_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh must have a '{DATA_AXIS}' axis, "
                         f"got {mesh.axis_names}")
    return mesh.shape[DATA_AXIS], mesh.shape.get(MODEL_AXIS, 1)


def _plan_signature(plan: LayoutPlan) -> tuple:
    """Everything _make_train_fn bakes into the executable for one side."""
    return (
        tuple(int(x) for x in plan.lengths),
        tuple(int(x) for x in plan.bucket_rows),
        plan.rows_per_shard, plan.n_shards, plan.v_rows_per_shard,
        plan.overflow_len, plan.total_slots,
    )


_train_fn_cache: dict = {}


def _cached_train_fn(mesh: Mesh, params: ALSParams, plan_u: LayoutPlan,
                     plan_i: LayoutPlan):
    """Reuse the jitted loop across train calls with identical mesh /
    params / layout shapes: jax's jit cache keys on the CALLABLE, so a
    fresh _make_train_fn closure per `pio train` would recompile the
    whole program (~3-6s) even for back-to-back trains on the same data
    shapes (repeat trains, eval sweeps, serving reload-retrain loops)."""
    key = (
        tuple(id(d) for d in mesh.devices.flat), mesh.axis_names,
        _executable_params_key(params),
        _plan_signature(plan_u), _plan_signature(plan_i),
        jax.process_count(),
    )
    hit = _train_fn_cache.get(key)
    if hit is None:
        hit = _make_train_fn(mesh, params, plan_u, plan_i)
        if len(_train_fn_cache) > 8:  # bound: old layouts just recompile
            _train_fn_cache.clear()
        _train_fn_cache[key] = hit
    return hit


def _pack_flat(flat):
    """Concatenate the per-bucket slabs into ONE 1-D buffer per dtype.

    Packing trades the ~70 per-slab transfers for 2-3 large ones plus
    static slices inside the jitted loop. Single-device meshes only —
    packing would destroy the per-slab DATA_AXIS shardings a multi-chip
    mesh needs. Whether this machine's link needs it is unmeasured
    (ROADMAP D7)."""
    groups: dict[str, list] = {}
    offsets: dict[str, int] = {}
    spec = []
    for a in flat:
        a = np.ascontiguousarray(a)
        ds = a.dtype.str
        off = offsets.get(ds, 0)
        spec.append((ds, off, a.shape))
        groups.setdefault(ds, []).append(a.ravel())
        offsets[ds] = off + a.size
    order = tuple(sorted(groups))
    bufs = tuple(
        groups[ds][0] if len(groups[ds]) == 1 else np.concatenate(groups[ds])
        for ds in order)
    return bufs, (order, tuple(spec))


_packed_fn_cache: dict = {}


#: ALSParams fields that do NOT shape the compiled program:
#: num_iterations is a traced operand, reg/lambda_scaling flow in as
#: the lam data array, seed only shapes the host init. Everything NOT
#: listed here keys the executable cache — a DENYLIST, so a future
#: field added to ALSParams fails safe (spurious recompile) instead of
#: silently serving a stale program compiled for different params.
_NON_SHAPING_PARAMS = frozenset(
    {"num_iterations", "reg", "lambda_scaling", "seed"})


def _executable_params_key(params: ALSParams) -> tuple:
    """Cache key over the ALSParams fields BAKED into the compiled
    program. Lets an eval sweep over regularization / iterations /
    seeds (the `pio eval` candidate pattern) reuse ONE executable with
    zero recompiles; with the device slab cache, binary-ratings sweeps
    additionally re-upload only the small lam vector per candidate
    (explicit-value sweeps re-upload the f32 buffer that lam is packed
    with — value slabs and lam share a dtype group)."""
    return tuple(
        getattr(params, f.name) for f in dataclasses.fields(params)
        if f.name not in _NON_SHAPING_PARAMS)

#: Device-resident slab cache: repeat trains over IDENTICAL data skip
#: the host->device upload entirely — the `pio eval` pattern (N
#: parameter candidates x one prepared dataset) and long-lived
#: retrain-on-reload servers. Keyed by content hash, so any changed
#: byte misses; param-dependent slabs (lam) simply hash differently per
#: candidate and re-upload at their own (tiny) cost. Bounded LRU over
#: device bytes; PIO_ALS_DEVICE_CACHE=0 disables.
_dev_buf_cache: "dict[tuple, object]" = {}
_dev_buf_cache_order: list = []
_DEV_BUF_CACHE_BYTES = 256 * 1024 * 1024


def _cached_dev_put(buf: np.ndarray, dev) -> "jax.Array":
    from ..common import envknobs

    if not envknobs.env_flag("PIO_ALS_DEVICE_CACHE", True):
        return jax.device_put(buf, dev)
    import hashlib

    digest = hashlib.blake2b(buf, digest_size=16).digest()
    key = (digest, buf.dtype.str, buf.shape, getattr(dev, "id", id(dev)))
    hit = _dev_buf_cache.get(key)
    if hit is not None:
        # LRU, not FIFO: refresh recency so a hot model's slabs aren't
        # the first evicted just because they were uploaded first
        _dev_buf_cache_order.remove(key)
        _dev_buf_cache_order.append(key)
        return hit
    arr = jax.device_put(buf, dev)
    _dev_buf_cache[key] = arr
    _dev_buf_cache_order.append(key)
    total = sum(int(np.prod(k[2])) * np.dtype(k[1]).itemsize
                for k in _dev_buf_cache)
    while total > _DEV_BUF_CACHE_BYTES and len(_dev_buf_cache_order) > 1:
        old = _dev_buf_cache_order.pop(0)
        victim = _dev_buf_cache.pop(old, None)
        if victim is not None:
            total -= int(np.prod(old[2])) * np.dtype(old[1]).itemsize
    return arr


def _cached_packed_train_fn(mesh: Mesh, params: ALSParams,
                            plan_u: LayoutPlan, plan_i: LayoutPlan,
                            pack_key):
    """jit(unpack-then-loop), cached like _cached_train_fn (the inner
    fn inlines — one executable, no double compile)."""
    key = (
        tuple(id(d) for d in mesh.devices.flat), mesh.axis_names,
        _executable_params_key(params),
        _plan_signature(plan_u), _plan_signature(plan_i),
        pack_key,
    )
    hit = _packed_fn_cache.get(key)
    if hit is None:
        fn, _ = _cached_train_fn(mesh, params, plan_u, plan_i)
        order, spec = pack_key
        buf_idx = {ds: k for k, ds in enumerate(order)}

        def packed(n_iters, x0, y0, *bufs):
            flat = []
            for ds, off, shape in spec:
                size = 1
                for dim in shape:
                    size *= dim
                flat.append(bufs[buf_idx[ds]][off:off + size].reshape(shape))
            return fn(n_iters, x0, y0, *flat)

        hit = jax.jit(packed)
        if len(_packed_fn_cache) > 8:
            _packed_fn_cache.clear()
        _packed_fn_cache[key] = hit
    return hit


#: Rows of the float64 scratch the init is drawn through: at rank 128 it
#: is 8 MiB, so a chunk is drawn, scaled and cast without leaving the cache.
_INIT_SCRATCH_ROWS = 8192


def _init_stream(params: ALSParams, blocks, stop=None) -> bool:
    """The init's one stream: ``default_rng(seed)`` drawn block after block,
    each ``(n_rows, out, slots)`` of ``blocks`` as ``n_rows x k`` float64
    standard normals through one reused scratch, chunk by chunk (NumPy fills
    in order, so chunks give the stream of one whole draw, bit for bit).
    With ``out`` a chunk is divided by ``sqrt(k)`` in float64 and cast into
    ``out[slots[rows]]``, or ``out[rows]`` where ``slots`` is None; without
    ``out`` the samples are drawn and dropped: no divide, no cast, no array.

    ``stop`` (a ``threading.Event``) is read once a chunk: once it is set
    the draw ends and the answer is False, with ``out`` left half filled."""
    k = params.rank
    scale = np.sqrt(k)
    rng = np.random.default_rng(params.seed)
    scratch = np.empty((_INIT_SCRATCH_ROWS, k))
    for n_rows, out, slots in blocks:
        for lo in range(0, n_rows, _INIT_SCRATCH_ROWS):
            if stop is not None and stop.is_set():
                return False
            chunk = scratch[:n_rows - lo]
            rng.standard_normal(out=chunk)
            if out is not None:
                chunk /= scale
                hi = lo + len(chunk)
                out[slice(lo, hi) if slots is None else slots[lo:hi]] = chunk
    return True


def _fresh_init(params: ALSParams, plan_u: LayoutPlan, plan_i: LayoutPlan,
                n_users: int, n_items: int, keep_users: bool = True):
    """MLlib-style init (scaled standard normal), drawn in GLOBAL row
    order and placed into layout slots — identical factors regardless of
    mesh shape or layout, and filler slots start at exactly 0 (so the
    implicit-mode YᵀY term never sees garbage rows).

    The stream is fixed (``_init_stream``): ``default_rng(seed)`` gives the
    ``(n_users, k)`` normals first, then the ``(n_items, k)``, each
    ``/ sqrt(k)`` in float64 and cast to float32.

    A sweep starts with ``x = one_side(y, ...)``, so a train of one
    iteration or more never reads ``x0``: its caller passes
    ``keep_users=False`` and gets ``(None, y0)``. The user block is then
    still DRAWN, sample for sample, because ``y0`` has to come from where
    the stream stands after it, and dropped: no ``[n_users, k]`` array on
    the host. A train of no iteration, whose result IS the init, keeps
    it."""
    k = params.rank
    x0 = np.zeros((plan_u.total_slots, k), np.float32) if keep_users else None
    y0 = np.zeros((plan_i.total_slots, k), np.float32)
    _init_stream(params, ((n_users, x0, plan_u.slot_of_row),
                          (n_items, y0, plan_i.slot_of_row)))
    return x0, y0


class _InitAhead:
    """The init of a fresh train of one sweep or more, begun before the
    layout on ONE worker thread (``pio-init``) and joined where ``y0`` is
    needed. What it draws needs no plan: the user block, drawn and dropped,
    then the item block as float32 rows in GLOBAL order (``_init_stream``:
    the stream, the chunks and the arithmetic of ``_fresh_init``); the
    calling thread places the rows into ``plan_i``'s slots once it has
    them, so ``y0`` is ``_fresh_init``'s bit for bit. ``rng.standard_normal``
    releases the GIL, so the draw runs beside the layout's native fills,
    the pack and whatever else the calling thread does.

    Spans: the worker's ``als.init`` (tags ``users=dropped``,
    ``overlap=layout``) opens in a copy of the caller's context, so it stays
    in the train's tree; ``als.init_wait`` on the calling thread covers the
    join and the placing: what of the init is still on the critical path.

    A context manager: leaving it, by an exception of the calling thread
    too, tells the worker to stop (read once a chunk) and joins it, so no
    thread outlives ``train_als``. The worker's own exception is re-raised
    by ``y0``."""

    def __init__(self, params: ALSParams, n_users: int, n_items: int):
        import contextvars
        import threading
        from concurrent.futures import ThreadPoolExecutor

        self._stop = threading.Event()
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="pio-init")
        self._rows = self._pool.submit(
            contextvars.copy_context().run, self._draw, params, n_users,
            n_items)

    def _draw(self, params: ALSParams, n_users: int, n_items: int):
        with telemetry.span("als.init", users="dropped", overlap="layout"):
            rows = np.empty((n_items, params.rank), np.float32)
            _init_stream(params, ((n_users, None, None),
                                  (n_items, rows, None)), self._stop)
            return rows

    def y0(self, plan_i: LayoutPlan) -> np.ndarray:
        with telemetry.span("als.init_wait"):
            rows = self._rows.result()
            self._rows = None    # the rows die here, not with the train
            y0 = np.zeros((plan_i.total_slots, rows.shape[1]), np.float32)
            y0[plan_i.slot_of_row] = rows
        return y0

    def __enter__(self) -> "_InitAhead":
        return self

    def __exit__(self, et, ev, tb) -> bool:
        self._stop.set()
        self._pool.shutdown(wait=True)
        return False


def _usable_cpus() -> int:
    """CPUs this process may run on (its affinity mask where the platform
    has one): a worker thread beside the caller needs a second."""
    import os

    if hasattr(os, "sched_getaffinity"):
        return len(os.sched_getaffinity(0))
    return os.cpu_count() or 1


def _zeros_on_device(shape, sharding):
    """The ``x0`` of a train that never reads it: float32 zeros born where
    ``fast_put`` would place them, with no host array and no transfer."""
    devices = sharding.device_set
    return jnp.zeros(
        shape, jnp.float32,
        device=next(iter(devices)) if len(devices) == 1 else sharding)


def train_als(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    rating: np.ndarray,
    n_users: int,
    n_items: int,
    params: ALSParams,
    mesh: Optional[Mesh] = None,
    checkpoint_hook=None,
    resume: bool = False,
    nan_guard: bool = False,
    nan_guard_stage: str = "algorithm[als]",
    pipeline=None,
) -> ALSFactors:
    """Train explicit/implicit ALS from a COO rating triple.

    ``nan_guard``: dispatch one iteration at a time and fail with
    "stage: algorithm[als], iteration k" on the first non-finite factor
    (SURVEY.md §5.2 sanitizer tier) instead of returning a garbage
    model. Trades the fused n-iteration executable's speed for
    attribution, exactly like jax_debug_nans' op-by-op replay.

    ``checkpoint_hook`` (workflow.checkpoint.CheckpointHook): when enabled,
    the loop runs in hook.every_n-iteration chunks through the SAME jitted
    executable (n_iters is traced — zero recompiles) and snapshots the
    factor pytree at each chunk boundary; ``resume=True`` restores the
    latest snapshot and runs only the remaining iterations. Chunking is
    bitwise-identical math to the single fori_loop. The reference cannot do
    this at all — a failed Spark ALS job restarts from zero (SURVEY.md §5.4).

    Phases are spans (common/telemetry.py): ``als.layout``, ``als.init``
    (tag ``overlap``: on a worker thread beside the layout and the pack,
    or in turn after the layout), ``als.pack``, ``als.init_wait`` (what
    the calling thread still waits for the worker's init),
    ``als.upload`` (the host's part of the transfer; no
    barrier, so what the transfer still owes lies in the loop),
    ``als.loop`` (dispatch until the factors are ready; one per dispatch
    in the ``nan_guard`` and checkpoint-chunked branches, a compile its
    ``xla.compile`` child) and ``als.readback``.
    """
    mesh = mesh or default_mesh()
    d_size, m_size = _mesh_dims(mesh)

    if params.binary_ratings is None:
        params = dataclasses.replace(
            params,
            binary_ratings=bool(np.all(np.asarray(rating) == 1.0)))

    # Both sides' layout prep overlapped on input-pipeline worker
    # threads (rowblocks.plan_and_fill_both) — the host scatters are the
    # serial front of every ALS train and their GIL-releasing cores run
    # genuinely concurrent. ``pipeline`` (workflow ctx config, else env)
    # turns the overlap off with the rest of the streaming layer.
    if pipeline is None:
        from ..workflow.input_pipeline import PipelineConfig

        pipeline = PipelineConfig.from_env()

    # Fresh start or resume is known before the layout: it hangs on the
    # hook's latest snapshot, not on the plan (the shape and fingerprint
    # checks of a restored snapshot wait below, where the plan exists).
    resume_step = None
    if checkpoint_hook is not None and resume:
        from ..workflow.checkpoint import CheckpointIncompatibleError

        resume_step = checkpoint_hook.latest_step()
        if resume_step is not None and resume_step >= params.num_iterations:
            # Snapshots are never written at the final iteration, so a
            # checkpoint at step >= num_iterations means the params changed
            # (num_iterations lowered) since the interrupted run.
            raise CheckpointIncompatibleError(
                f"latest checkpoint is at iteration {resume_step} but only "
                f"{params.num_iterations} iterations were requested; the "
                "snapshot is from a run with more iterations — retrain from "
                "scratch or raise num_iterations"
            )
    # a fresh start (start_iter 0) of one sweep or more overwrites x0
    # before it reads it: the user block is drawn and dropped, so nothing
    # of the init needs plan_u and it runs beside the layout and the pack
    # (_InitAhead). A train of no iteration keeps x0, in turn; a resumed
    # train draws nothing.
    # The overlap follows what already decides that plan_and_fill_both
    # uses threads (PIO_PIPELINE=off runs every host stage in turn), and
    # needs a second CPU for the worker to run on.
    import contextlib

    keep_users = params.num_iterations < 1
    parallel = pipeline.mode != "off"
    ahead = (resume_step is None and not keep_users and parallel
             and _usable_cpus() >= 2)
    with (_InitAhead(params, n_users, n_items) if ahead
          else contextlib.nullcontext()) as init:
        with telemetry.span("als.layout"):
            plan_u, plan_i, arrs_u, arrs_i = plan_and_fill_both(
                user_idx, item_idx, rating, n_users, n_items, d_size,
                m_div=m_size, fill_vals=not params.binary_ratings,
                parallel=parallel)

        k = params.rank
        x_shape = (plan_u.total_slots, k)
        y_shape = (plan_i.total_slots, k)

        # Fingerprint of the exact COO triple: resume is only sound against
        # the identical rating data (shape equality alone misses in-place
        # rating updates that keep n_users/n_items fixed). Only computed
        # when a hook is active — it's O(nnz) hashing that plain trains
        # shouldn't pay.
        fingerprint = None
        if checkpoint_hook is not None:
            import zlib

            # Seeded with _LAYOUT_TAG (layout generation) and the slot
            # permutations (mesh-dependent): factors are stored in slot
            # order, so a snapshot is only resumable by a run with the
            # IDENTICAL plan — same data AND same (d, m) mesh shape.
            layout_fp = zlib.crc32(
                plan_i.slot_of_row.tobytes(),
                zlib.crc32(plan_u.slot_of_row.tobytes(), _LAYOUT_TAG))
            fingerprint = zlib.crc32(
                np.asarray(rating, np.float32).tobytes(),
                zlib.crc32(np.asarray(item_idx).tobytes(),
                           zlib.crc32(np.asarray(user_idx).tobytes(),
                                      layout_fp)))

        start_iter = 0
        x0 = y0 = None
        if resume_step is not None:
            start_iter, tree = checkpoint_hook.restore(resume_step)
            rx, ry = np.asarray(tree["user_factors"]), np.asarray(tree["item_factors"])
            if rx.shape != x_shape or ry.shape != y_shape:
                raise CheckpointIncompatibleError(
                    f"checkpoint shapes {rx.shape}/{ry.shape} do not match the "
                    f"current data layout {x_shape}/{y_shape}; the event data "
                    "changed since the interrupted run — retrain from scratch"
                )
            saved_fp = int(np.asarray(tree.get("fingerprint", -1)))
            if saved_fp != fingerprint:
                raise CheckpointIncompatibleError(
                    "checkpoint was written against different rating data "
                    "(fingerprint mismatch); the event store changed since "
                    "the interrupted run — retrain from scratch"
                )
            x0, y0 = rx, ry
        elif init is None:
            with telemetry.span("als.init",
                                users="kept" if keep_users else "dropped",
                                overlap="none"):
                x0, y0 = _fresh_init(params, plan_u, plan_i, n_users,
                                     n_items, keep_users=keep_users)
        fn, in_shardings = _cached_train_fn(mesh, params, plan_u, plan_i)
        binary = bool(params.binary_ratings)
        # Single-device runs pack the slabs: 2-3 large transfers instead of
        # ~70 small ones (see _pack_flat). run_fn/run_args abstract over
        # packed vs per-slab.
        packed = jax.process_count() == 1 and mesh.devices.size == 1
        with telemetry.span("als.pack"):
            flat = tuple(
                _side_flat(arrs_u, plan_u, _host_lam(plan_u, params), binary,
                           col_sentinel=plan_i.total_slots)
                + _side_flat(arrs_i, plan_i, _host_lam(plan_i, params),
                             binary, col_sentinel=plan_u.total_slots))
            if packed:
                bufs, pack_key = _pack_flat(flat)
        if init is not None:
            y0 = init.y0(plan_i)
    run_fn = (_cached_packed_train_fn(mesh, params, plan_u, plan_i, pack_key)
              if packed else fn)
    # No barrier after the puts: what the transfer still owes when they
    # return lies in als.loop.
    with telemetry.span("als.upload"):
        if jax.process_count() > 1:
            # Multi-controller: every process holds the SAME full numpy
            # arrays (the event store is shared), so build global
            # jax.Arrays explicitly — jit refuses sharded numpy inputs
            # across processes.
            def _globalize(host, sharding):
                return jax.make_array_from_callback(
                    host.shape, sharding, lambda idx: host[idx]
                )

            x0 = (_zeros_on_device(x_shape, in_shardings[1]) if x0 is None
                  else _globalize(np.asarray(x0), in_shardings[1]))
            y0 = _globalize(np.asarray(y0), in_shardings[2])
            run_args = tuple(
                _globalize(np.asarray(b), s)
                for b, s in zip(flat, in_shardings[3:])
            )
        else:
            # Explicit transfers (plain single-device puts on a
            # one-device mesh, see fast_put) instead of handing jit raw
            # numpy inputs.
            x0 = (_zeros_on_device(x_shape, in_shardings[1]) if x0 is None
                  else fast_put(np.asarray(x0), in_shardings[1]))
            y0 = fast_put(np.asarray(y0), in_shardings[2])
            if packed:
                dev = mesh.devices.flat[0]
                run_args = tuple(_cached_dev_put(b, dev) for b in bufs)
            else:
                run_args = tuple(
                    fast_put(np.asarray(b), sh)
                    for b, sh in zip(flat, in_shardings[3:]))
    chunk = checkpoint_hook.every_n if checkpoint_hook is not None and checkpoint_hook.enabled else 0
    # which device path the dispatches run: Hu-Koren-Volinsky or explicit,
    # with the value slabs or without them, through which solve
    loop_tags = {"implicit": bool(params.implicit_prefs), "binary": binary,
                 "solve": solve_path(k, mesh.devices.flat[0].platform)}
    if nan_guard:
        # Sanitizer tier: one dispatch per iteration + a device-side
        # finite reduction (ONE scalar fetched per iteration, not the
        # full factor matrices), so the failure names the iteration that
        # produced it. Checkpoint saves keep their chunk schedule.
        from ..common.nan_guard import NaNGuardError

        finite_probe = jax.jit(
            lambda a, b: jnp.isfinite(a).all() & jnp.isfinite(b).all())
        x, y = x0, y0
        for it in range(start_iter, params.num_iterations):
            fault_point("train.sweep")
            with telemetry.span("als.loop", **loop_tags):
                x, y = run_fn(np.int32(1), x, y, *run_args)
                # Beat AFTER the dispatch: the first sweep includes the
                # XLA compile, and the supervisor's stall detector only
                # arms at the first beat (init grace covers everything
                # before it).
                gang.beat()
                finite = bool(jax.device_get(finite_probe(x, y)))
            if not finite:
                raise NaNGuardError(
                    f"stage: {nan_guard_stage}, iteration {it + 1}: "
                    "non-finite factors (check input ratings for NaN/Inf "
                    "or raise the regularization)")
            done = it + 1
            saved = False
            if chunk and done % chunk == 0 and done < params.num_iterations:
                checkpoint_hook.save(
                    done, {"user_factors": x, "item_factors": y,
                           "fingerprint": np.int64(fingerprint)}
                )
                saved = True
                gang.beat()  # a save (manager init, fsync) can be slow too
            # Per-iteration dispatch ⇒ drain can honor EVERY sweep
            # boundary, not just the checkpoint cadence; an off-cadence
            # drain writes its own snapshot (all processes agree:
            # `saved` is deterministic and the flag is allgathered).
            if done < params.num_iterations and gang.drain_requested_global():
                if chunk and not saved:
                    checkpoint_hook.save(
                        done, {"user_factors": x, "item_factors": y,
                               "fingerprint": np.int64(fingerprint)}
                    )
                raise gang.GangDrainRequested(done)
    elif chunk and params.num_iterations - start_iter > chunk:
        x, y = x0, y0
        it = start_iter
        while it < params.num_iterations:
            fault_point("train.sweep")
            n = min(chunk, params.num_iterations - it)
            with telemetry.span("als.loop", **loop_tags):
                x, y = run_fn(n, x, y, *run_args)
                gang.beat()  # after the dispatch: sweep 1 includes compile
                jax.block_until_ready((x, y))  # the save would wait anyway
            it += n
            if it < params.num_iterations:
                checkpoint_hook.save(
                    it, {"user_factors": x, "item_factors": y,
                         "fingerprint": np.int64(fingerprint)}
                )
                gang.beat()  # a save (manager init, fsync) can be slow too
                if gang.drain_requested_global():
                    raise gang.GangDrainRequested(it)
    else:
        with telemetry.span("als.loop", **loop_tags):
            x, y = run_fn(params.num_iterations - start_iter, x0, y0,
                          *run_args)
            gang.beat()
            # the device_get below would block anyway; waiting here keeps
            # the device loop and the readback apart
            jax.block_until_ready((x, y))
    with telemetry.span("als.readback"):
        x, y = jax.device_get((x, y))
        user_factors = np.asarray(x)[plan_u.slot_of_row]
        item_factors = np.asarray(y)[plan_i.slot_of_row]
    return ALSFactors(
        user_factors=user_factors,
        item_factors=item_factors,
        n_users=n_users,
        n_items=n_items,
    )


def train_phase_seconds(since_ns: int) -> dict[str, float]:
    """Upload, compile and device-loop seconds of the train_als calls that
    started after ``since_ns`` (a ``time.perf_counter_ns`` reading), read
    from the span ring: what bench.py and tools/profile_similar.py print.
    ``compile`` is the ``xla.compile`` children of ``als.loop`` (a cache
    load counts) and ``device_train`` is the loop less them."""
    spans = [s for s in telemetry.spans_snapshot() if s.t0_ns >= since_ns]
    loops = {s.span_id for s in spans if s.name == "als.loop"}

    def seconds(pick) -> float:
        return sum(s.t1_ns - s.t0_ns for s in spans if pick(s)) * 1e-9

    compiles = seconds(
        lambda s: s.name == "xla.compile" and s.parent_id in loops)
    return {
        "upload_seconds": seconds(lambda s: s.name == "als.upload"),
        "compile_seconds": compiles,
        "device_train_seconds":
            seconds(lambda s: s.name == "als.loop") - compiles,
    }


#: Cap on one fused gather→gram chunk's [CH, k, k] f32 outer-product
#: slab in the partition-local trainer (the analog of _FUSED_SLAB_BYTES
#: for the event-COO layout).
_DP_CHUNK_BYTES = 64 * 1024 * 1024

#: Checkpoint-fingerprint seed of the partition-local (event-sharded)
#: layout — distinct from the slab layout's _LAYOUT_TAG so a snapshot
#: written by one trainer is rejected deterministically by the other
#: even when the factor shapes coincide.
_DP_LAYOUT_TAG = 0x70_10_10_01


def _dp_chunk(e_pad: int, k: int) -> int:
    """Events per fused gram chunk: bounded so the [CH, k, k] f32
    outer-product slab stays under _DP_CHUNK_BYTES."""
    ch = max(512, _DP_CHUNK_BYTES // max(k * k * 4, 1))
    return min(ch, max(e_pad, 1))


def _make_dp_train_fn(mesh: Mesh, params: ALSParams, n_u_pad: int,
                      n_i_pad: int, e_pad: int):
    """Build the jitted partition-local (data-parallel) ALS loop.

    Layout: the EVENT COO is sharded over the data axis (each gang
    worker supplies only its partitions' events — arbitrary rows, any
    order); factor matrices are replicated. One half-step computes
    per-row normal-equation partials from the local events
    (segment-sum of per-entry outer products — :func:`_grams_rows`
    linearity is exactly why partition-partial grams are sound), then
    **all-reduces the grams/rhs over the mesh** (the ALX replicated-
    grams recipe, arxiv 2112.02194), solves each device's own factor
    ROW BLOCK, and all-gathers the solved blocks back to a replicated
    factor matrix. The only collectives are the gram psum and the
    factor all-gather — no raw events ever cross the mesh. HBM bound:
    O(n_rows·k²) for the replicated normal equations per device; the
    slab trainer (:func:`train_als`) remains the path for models past
    that bound.
    """
    params, _ = _resolve_params(mesh, params)
    cd = jnp.bfloat16 if params.compute_dtype == "bfloat16" else jnp.float32
    implicit = params.implicit_prefs
    alpha = params.alpha
    nratings = params.lambda_scaling == "nratings"
    mesh_platform = mesh.devices.flat[0].platform
    if MODEL_AXIS in mesh.axis_names:
        raise ValueError(
            "the partition-local feed trainer shards factor blocks over "
            "the data axis only; 2-D (d, m) ALX meshes need the slab "
            "trainer (train_als)")
    d_size = mesh.shape[DATA_AXIS]
    k = params.rank
    rps_u = n_u_pad // d_size
    rps_i = n_i_pad // d_size
    ch = _dp_chunk(e_pad, k)
    assert e_pad % ch == 0, (e_pad, ch)
    n_ch = e_pad // ch
    eye = np.eye(k, dtype=np.float32)

    def lam_of(counts, reg):
        lam = (reg * jnp.maximum(counts, 1.0) if nratings
               else jnp.full(counts.shape, reg, jnp.float32))
        return lam + jnp.where(counts == 0, 1e-6, 0.0)

    def local_loop(n_iters, reg, x0, y0, u_loc, i_loc, r_loc, w_loc):
        # per-row GLOBAL observation counts (for nratings λ and the
        # zero-row conditioning), one psum each, computed once
        cnt_u = jax.lax.psum(
            jax.ops.segment_sum(w_loc, u_loc, num_segments=n_u_pad),
            DATA_AXIS)
        cnt_i = jax.lax.psum(
            jax.ops.segment_sum(w_loc, i_loc, num_segments=n_i_pad),
            DATA_AXIS)
        lam_u, lam_i = lam_of(cnt_u, reg), lam_of(cnt_i, reg)

        def half(y, rows, cols, lam, rps, n_pad):
            y_cd = y.astype(cd)
            yty = (jnp.einsum("nk,nm->km", y_cd, y_cd,
                              preferred_element_type=jnp.float32)
                   if implicit
                   else jnp.zeros((k, k), jnp.float32))
            if implicit:
                # Hu-Koren-Volinsky per-entry weights (same algebra as
                # _grams_rows' explicit-value implicit mode)
                gw = alpha * r_loc * w_loc
                bw = (1.0 + alpha * r_loc) * w_loc
            else:
                gw = w_loc
                bw = r_loc * w_loc

            def chunk(c, acc):
                g_acc, b_acc = acc
                sl = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                    a, c * ch, ch)
                cc, rr = sl(cols), sl(rows)
                p = jnp.take(y_cd, cc, axis=0)          # [CH, k]
                outer = jnp.einsum(
                    "ek,em->ekm", p * sl(gw)[:, None].astype(cd), p,
                    preferred_element_type=jnp.float32)
                rhs = jnp.einsum(
                    "ek,e->ek", p, sl(bw).astype(cd),
                    preferred_element_type=jnp.float32)
                return (g_acc + jax.ops.segment_sum(
                            outer, rr, num_segments=n_pad),
                        b_acc + jax.ops.segment_sum(
                            rhs, rr, num_segments=n_pad))

            # the accumulators start replicated but every chunk adds
            # this device's events: type the carry varying over 'd'
            g0, b0 = jax.lax.pcast(
                (jnp.zeros((n_pad, k, k), jnp.float32),
                 jnp.zeros((n_pad, k), jnp.float32)),
                (DATA_AXIS,), to="varying")
            grams, rhs = jax.lax.fori_loop(0, n_ch, chunk, (g0, b0))
            # replicated grams across the mesh (ALX): partition
            # partials sum to the full normal equations
            grams = jax.lax.psum(grams, DATA_AXIS)
            rhs = jax.lax.psum(rhs, DATA_AXIS)
            idx = jax.lax.axis_index(DATA_AXIS)
            a_blk = jax.lax.dynamic_slice_in_dim(grams, idx * rps, rps)
            b_blk = jax.lax.dynamic_slice_in_dim(rhs, idx * rps, rps)
            lam_blk = jax.lax.dynamic_slice_in_dim(lam, idx * rps, rps)
            if implicit:
                a_blk = a_blk + yty[None, :, :]
            a_blk = a_blk + lam_blk[:, None, None] * eye
            x_blk = batched_spd_solve(a_blk, b_blk, vma=(DATA_AXIS,),
                                      platform=mesh_platform)
            # factor blocks sharded over the data axis re-assemble to
            # the replicated matrix the next half-step gathers from
            return jax.lax.all_gather(
                x_blk.astype(jnp.float32), DATA_AXIS, axis=0,
                tiled=True)

        def body(_, carry):
            x, y = carry
            x = half(y, u_loc, i_loc, lam_u, rps_u, n_u_pad)
            y = half(x, i_loc, u_loc, lam_i, rps_i, n_i_pad)
            return (x, y)

        # all_gather's result is typed varying over 'd' (every device
        # holds the same values, but the type system cannot know), so
        # the factor carry is varying from the start ...
        x, y = jax.lax.fori_loop(
            0, n_iters, body,
            jax.lax.pcast((x0, y0), (DATA_AXIS,), to="varying"))
        # ... and each device returns its own row block: out_specs
        # re-assembles the blocks, jit's out_shardings replicates them
        idx = jax.lax.axis_index(DATA_AXIS)
        return (jax.lax.dynamic_slice_in_dim(x, idx * rps_u, rps_u),
                jax.lax.dynamic_slice_in_dim(y, idx * rps_i, rps_i))

    rep = P()
    row1 = P(DATA_AXIS)
    fn = shard_map(
        local_loop, mesh=mesh,
        in_specs=(rep, rep, rep, rep, row1, row1, row1, row1),
        out_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)))
    in_shardings = tuple(
        NamedSharding(mesh, s)
        for s in (rep, rep, rep, rep, row1, row1, row1, row1))
    fitted = jax.jit(fn, in_shardings=in_shardings,
                     out_shardings=(NamedSharding(mesh, rep),) * 2)
    return fitted, in_shardings


_dp_fn_cache: dict = {}


def _cached_dp_train_fn(mesh: Mesh, params: ALSParams, n_u_pad: int,
                        n_i_pad: int, e_pad: int):
    key = (
        tuple(id(d) for d in mesh.devices.flat), mesh.axis_names,
        # lambda_scaling is non-shaping for the SLAB trainer (λ arrives
        # as data) but the dp kernel computes λ in-graph from counts —
        # the branch is baked into the executable, so it must key it
        _executable_params_key(params), params.lambda_scaling,
        n_u_pad, n_i_pad, e_pad,
        jax.process_count(),
    )
    hit = _dp_fn_cache.get(key)
    if hit is None:
        hit = _make_dp_train_fn(mesh, params, n_u_pad, n_i_pad, e_pad)
        if len(_dp_fn_cache) > 8:
            _dp_fn_cache.clear()
        _dp_fn_cache[key] = hit
    return hit


def train_als_partition_local(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    rating: np.ndarray,
    n_users: int,
    n_items: int,
    params: ALSParams,
    mesh: Optional[Mesh] = None,
    checkpoint_hook=None,
    resume: bool = False,
    nan_guard: bool = False,
    nan_guard_stage: str = "algorithm[als]",
    force_dp: bool = False,
) -> ALSFactors:
    """ALS over PARTITION-LOCAL events: each gang process passes only
    the (user, item, rating) triple its event-log partitions hold —
    any rows, any order, already mapped to GLOBAL indices via the
    allgathered id vocabularies (workflow/train_feed.py). No process
    needs another's events: per-row normal equations are linear in
    per-event contributions, so partition partials all-reduce to the
    exact full-data equations (see :func:`_make_dp_train_fn`).

    Single-process calls fall back to :func:`train_als` (the data is
    complete locally, and the slab trainer is the faster single-host
    path); ``force_dp=True`` runs the data-parallel kernel anyway —
    the math-parity tests rely on it.

    ``checkpoint_hook``/``resume``/``nan_guard``: same contracts as
    the other trainers (chunked dispatch through one traced-n_iters
    executable, gang beats after every dispatch, allgathered drain at
    chunk boundaries, per-iteration finite probe under nan_guard).
    """
    mesh = mesh or default_mesh()
    if jax.process_count() == 1 and not force_dp:
        return train_als(user_idx, item_idx, rating, n_users, n_items,
                         params, mesh=mesh,
                         checkpoint_hook=checkpoint_hook, resume=resume,
                         nan_guard=nan_guard,
                         nan_guard_stage=nan_guard_stage)
    d_size, m_size = _mesh_dims(mesh)
    if m_size != 1:
        raise ValueError(
            "partition-local training needs a 1-D data mesh (factor "
            "blocks shard over 'd'); unset PIO_MESH_SHAPE's model axis")
    n_proc = jax.process_count()
    if d_size % n_proc:
        raise ValueError(
            f"data axis size {d_size} is not divisible by {n_proc} "
            "processes")
    n_local_devs = d_size // n_proc
    # The jit signature must agree across the gang: no per-process
    # auto-detection (a worker whose partitions happen to be all-ones
    # must not compile a different program than its peers).
    if params.binary_ratings is None:
        params = dataclasses.replace(params, binary_ratings=False)

    u = np.asarray(user_idx, np.int64)
    i = np.asarray(item_idx, np.int64)
    r = np.asarray(rating, np.float32)
    if u.size and (u.min() < 0 or u.max() >= n_users):
        raise ValueError("user_idx outside [0, n_users)")
    if i.size and (i.min() < 0 or i.max() >= n_items):
        raise ValueError("item_idx outside [0, n_items)")

    def roundup(n, m):
        return max(m, -(-n // m) * m)

    n_u_pad = roundup(n_users, d_size)
    n_i_pad = roundup(n_items, d_size)

    from jax.experimental import multihost_utils

    def agather(v):
        if n_proc == 1:
            return np.asarray([v])
        return np.asarray(
            multihost_utils.process_allgather(np.int32(v))).reshape(-1)

    # per-DEVICE event capacity: the max over the gang, so every shard
    # carries the same (padded) event count and the jit signature is
    # identical everywhere
    e_dev = int(agather(-(-max(u.size, 1) // n_local_devs)).max())
    ch = _dp_chunk(e_dev, params.rank)
    e_dev = roundup(e_dev, ch)
    e_local = e_dev * n_local_devs

    def pad_to(a, fill=0):
        out = np.full(e_local, fill, a.dtype)
        out[:a.size] = a
        return out

    u_loc = pad_to(u.astype(np.int32))
    i_loc = pad_to(i.astype(np.int32))
    r_loc = pad_to(r)
    w_loc = pad_to(np.ones(u.size, np.float32))

    fn, in_shardings = _cached_dp_train_fn(mesh, params, n_u_pad,
                                           n_i_pad, e_dev)

    k = params.rank
    rng = np.random.default_rng(params.seed)
    x0 = np.zeros((n_u_pad, k), np.float32)
    y0 = np.zeros((n_i_pad, k), np.float32)
    # same per-row init values as _fresh_init (global row order, same
    # seed) so the partition-fed gang tracks a merged-feed train_als
    # run row for row
    x0[:n_users] = (rng.standard_normal((n_users, k))
                    / np.sqrt(k)).astype(np.float32)
    y0[:n_items] = (rng.standard_normal((n_items, k))
                    / np.sqrt(k)).astype(np.float32)

    fingerprint = None
    if checkpoint_hook is not None:
        import zlib

        local_fp = zlib.crc32(
            r.tobytes(),
            zlib.crc32(i.tobytes(),
                       zlib.crc32(u.tobytes(), _DP_LAYOUT_TAG)))
        if n_proc > 1:
            all_fp = np.asarray(multihost_utils.process_allgather(
                np.int64(local_fp))).reshape(-1)
        else:
            all_fp = np.asarray([local_fp], np.int64)
        fingerprint = zlib.crc32(
            all_fp.tobytes(),
            zlib.crc32(np.int64(n_users).tobytes(),
                       zlib.crc32(np.int64(n_items).tobytes(),
                                  _DP_LAYOUT_TAG)))

    start_iter = 0
    rx0 = ry0 = None
    if checkpoint_hook is not None and resume:
        from ..workflow.checkpoint import CheckpointIncompatibleError

        step = checkpoint_hook.latest_step()
        if step is not None and step < params.num_iterations:
            start_iter, tree = checkpoint_hook.restore(step)
            rx = np.asarray(tree["user_factors"])
            ry = np.asarray(tree["item_factors"])
            if rx.shape != x0.shape or ry.shape != y0.shape or \
                    int(np.asarray(tree.get("fingerprint", -1))) \
                    != fingerprint:
                raise CheckpointIncompatibleError(
                    "checkpoint does not match the current partition-"
                    "local layout/data — retrain from scratch")
            rx0, ry0 = rx, ry
    if rx0 is not None:
        x0, y0 = rx0, ry0

    def _rep(host, sharding):
        if n_proc == 1:
            return np.asarray(host)
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])

    def _sharded(host, sharding):
        if n_proc == 1:
            return host
        return jax.make_array_from_process_local_data(
            sharding, host, (host.shape[0] * n_proc,))

    reg = np.float32(params.reg)
    gx = _rep(x0, in_shardings[2])
    gy = _rep(y0, in_shardings[3])
    ev_args = tuple(
        _sharded(a, s) for a, s in zip(
            (u_loc, i_loc, r_loc, w_loc), in_shardings[4:]))

    def dispatch(n, x, y):
        return fn(np.int32(n), reg, x, y, *ev_args)

    chunk = (checkpoint_hook.every_n
             if checkpoint_hook is not None and checkpoint_hook.enabled
             else 0)

    def save(it, x, y):
        checkpoint_hook.save(
            it, {"user_factors": np.asarray(jax.device_get(x)),
                 "item_factors": np.asarray(jax.device_get(y)),
                 "fingerprint": np.int64(fingerprint)})

    if nan_guard:
        from ..common.nan_guard import NaNGuardError

        finite_probe = jax.jit(
            lambda a, b: jnp.isfinite(a).all() & jnp.isfinite(b).all())
        x, y = gx, gy
        for it in range(start_iter, params.num_iterations):
            fault_point("train.sweep")
            x, y = dispatch(1, x, y)
            gang.beat()  # after the dispatch: sweep 1 includes compile
            if not bool(jax.device_get(finite_probe(x, y))):
                raise NaNGuardError(
                    f"stage: {nan_guard_stage}, iteration {it + 1}: "
                    "non-finite factors (check input ratings for "
                    "NaN/Inf or raise the regularization)")
            done = it + 1
            saved = False
            if chunk and done % chunk == 0 \
                    and done < params.num_iterations:
                save(done, x, y)
                saved = True
                gang.beat()
            if done < params.num_iterations \
                    and gang.drain_requested_global():
                if chunk and not saved:
                    save(done, x, y)
                raise gang.GangDrainRequested(done)
    elif chunk and params.num_iterations - start_iter > chunk:
        x, y = gx, gy
        it = start_iter
        while it < params.num_iterations:
            fault_point("train.sweep")
            n = min(chunk, params.num_iterations - it)
            x, y = dispatch(n, x, y)
            gang.beat()
            it += n
            if it < params.num_iterations:
                save(it, x, y)
                gang.beat()  # a save (manager init, barriers) is slow too
                if gang.drain_requested_global():
                    raise gang.GangDrainRequested(it)
    else:
        fault_point("train.sweep")
        x, y = dispatch(params.num_iterations - start_iter, gx, gy)
        gang.beat()
    x, y = jax.device_get((x, y))
    return ALSFactors(
        user_factors=np.asarray(x)[:n_users],
        item_factors=np.asarray(y)[:n_items],
        n_users=n_users,
        n_items=n_items,
    )


def fold_in_factors(y, obs_idx, obs_val, *, reg: float,
                    lambda_scaling: str = "plain",
                    implicit_prefs: bool = False, alpha: float = 1.0,
                    anchor=None, anchor_weight=1.0,
                    yty=None) -> np.ndarray:
    """Closed-form ridge fold-in: solve R rows against FIXED counterpart
    factors ``y`` [n, k] (the ALX fold-in recipe, arxiv 2112.02194 —
    one half-step of ALS for just the touched rows, with the opposite
    side frozen). This is the math of the streaming online-learning
    subsystem (workflow/online.py, docs/operations.md "Online
    learning"): a brand-new user's factor from their first events is
    EXACTLY what a full retrain would produce for them given the
    current counterpart matrix.

    ``obs_idx``: R arrays of counterpart row indices (one per solved
    row); ``obs_val``: R matching float arrays of ratings. Rows ride
    the same per-row normal equations as training (:func:`_grams_rows`
    — zero-padded gather slots contribute nothing), then a batched
    host solve: the systems are [k, k] and R is the handful of
    entities a fold-in increment touches, so a device dispatch would
    cost more than it saves.

    ``anchor`` [R, k] adds a proximal term μ‖x − x_old‖² (μ =
    ``anchor_weight``, scalar or per-row [R]): existing entities blend
    new evidence into their current factor instead of forgetting their
    history (the history itself is not re-read — O(new events), not
    O(log)); rows whose anchor is a brand-new entity's zero row should
    carry μ=0 so they solve the exact cold-start ridge.

    Regularization mirrors training: ``lambda_scaling='nratings'``
    scales λ by each row's (new-)rating count, ``'plain'`` uses λ as
    is; ``implicit_prefs`` adds the shared YᵀY term with
    confidence weights 1+α·r (Hu-Koren-Volinsky, matching
    ``train_als``'s implicit mode against the same ratings).

    Returns the solved rows, [R, k] float32.
    """
    y = np.asarray(y, np.float32)
    n, k = y.shape
    R = len(obs_idx)
    if R == 0:
        return np.zeros((0, k), np.float32)
    C = max((len(ix) for ix in obs_idx), default=0)
    if C == 0 or n == 0:
        return (np.asarray(anchor, np.float32).reshape(R, k)
                if anchor is not None else np.zeros((R, k), np.float32))
    p = np.zeros((R, C, k), np.float32)
    val = np.zeros((R, C), np.float32)
    counts = np.zeros(R, np.float32)
    for r, (ix, v) in enumerate(zip(obs_idx, obs_val)):
        ix = np.asarray(ix, np.int64)
        m = len(ix)
        if m:
            p[r, :m] = y[ix]
            val[r, :m] = np.asarray(v, np.float32)
            counts[r] = m
    grams, rhs = _grams_rows(
        jnp.asarray(p), jnp.asarray(val), implicit=implicit_prefs,
        alpha=alpha, compute_dtype=jnp.float32)
    grams = np.asarray(grams, np.float32)
    rhs = np.asarray(rhs, np.float32)
    if implicit_prefs:
        # the shared YtY term is O(n·k²) over the WHOLE counterpart
        # matrix — the one non-O(new events) piece of an implicit
        # fold-in. Callers folding repeatedly against the same side
        # can pass a precomputed/cached ``yty`` [k, k].
        if yty is None:
            yty = y.T @ y
        grams = grams + np.asarray(yty, np.float32)[None, :, :]
    lam = np.full(R, float(reg), np.float32)
    if lambda_scaling == "nratings":
        lam *= np.maximum(counts, 1.0)
    # no anchor = no proximal term AT ALL: adding mu to the normal
    # matrix without the matching rhs term would be phantom ridge
    # silently shrinking every solution toward zero. anchor_weight may
    # be per-row ([R]) — callers zero it for rows whose anchor is the
    # meaningless zero row of a brand-new entity, keeping those at the
    # exact cold-start ridge the contract promises.
    if anchor is None:
        mu = np.zeros(R, np.float32)
    else:
        mu = np.maximum(np.broadcast_to(
            np.asarray(anchor_weight, np.float32), (R,)), 0.0)
    a = grams + (lam + mu)[:, None, None] * np.eye(k, dtype=np.float32)
    if anchor is not None:
        rhs = rhs + mu[:, None] * np.asarray(anchor,
                                             np.float32).reshape(R, k)
    # batched [k, k] solves want an explicit trailing rhs column
    return np.linalg.solve(a, rhs[..., None])[..., 0].astype(np.float32)


def predict_rmse(factors: ALSFactors, user_idx, item_idx, rating) -> float:
    """Host-side RMSE over a COO triple (eval helper)."""
    x = factors.user_factors[np.asarray(user_idx)]
    y = factors.item_factors[np.asarray(item_idx)]
    pred = np.sum(x * y, axis=1)
    err = pred - np.asarray(rating, dtype=np.float32)
    return float(np.sqrt(np.mean(err**2)))
