"""Pallas TPU kernels for the ALS hot loop.

Profiling the ml20m half-step on a v5e chip (see bench.py) shows XLA's
batched ``cholesky`` + ``cho_solve`` of the [n_rows, k, k] normal equations
dominating the iteration (~575 ms for 138k rank-32 systems — the solver
lowering is latency-bound on small matrices). The VPU-friendly replacement
here solves all systems in VMEM by Gaussian elimination and a
back-substitution (``_gauss_solve``):

- The batch lives on the *lane* dimension: matrices are transposed to
  [k, k, N] so every elimination step is a [k, C]-shaped vector op across
  C systems at full lane width (C a multiple of 128).
- Each grid step copies a C-wide slab into VMEM scratch and runs the
  elimination entirely on-chip — HBM traffic is exactly one read of A/b
  and one write of x (the XLA formulation re-streams the whole [N, k, k]
  array every elimination step).
- The elimination touches only the trailing block, in static blocks of
  8 pivots: about 0.37 k³ element updates a system at k = 128, against
  the k³ of a Gauss-Jordan sweep that also clears the rows above each
  pivot. The back-substitution adds k².
- No pivoting: every system is SPD by construction (normal equations
  plus a λ·I ridge — ops/als.py adds 1e-6 even for empty rows).

The reference has no analog: its solves happen inside MLlib's
``CholeskyDecomposition.solve`` on the Spark executors (SURVEY.md §2.9).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


#: pivots per elimination block: the f32 sublane tile, so every block's
#: trailing slice starts on a tile boundary and is static
_BLOCK = 8


def _gauss_solve(a_s, b_s, *, k: int):
    """Solve on VMEM scratch [k, k, C] / [k, C] (k a multiple of 8); return x.

    Forward elimination of the trailing block, then a back-substitution.
    The pivots go in static blocks of 8: block [b0, b0 + 8) updates only
    rows ≥ b0 (the leading dim) and columns ≥ b0 (the sublane dim), so
    both slices of the scratch are static and tile-aligned. Within a
    block, a row's factor is masked to zero at rows ≤ the pivot, so the
    pivot row survives verbatim and A ends upper triangular in the rows
    and columns that matter. That is Σ_{m=1..k/8} 8·(8m)² element updates
    a system (0.37 k³ at k = 128, where a full Gauss-Jordan makes k³).
    The back-substitution reads row j of U as one [k, C] slab, k² work.
    """
    from jax.experimental import pallas as pl

    for b0 in range(0, k, _BLOCK):
        # Dynamic slicing happens on the refs (Mosaic lowers pl.ds ref
        # indexing; dynamic_slice on values is not implemented).
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (k - b0, 1), 0) + b0

        def step(j, _, b0=b0, row_ids=row_ids):
            rowj = a_s[pl.ds(j, 1), b0:, :][0]              # [k-b0, C]
            inv = 1.0 / a_s[pl.ds(j, 1), pl.ds(j, 1), :][0]  # [1, C] 1/a[j,j]
            bj = b_s[pl.ds(j, 1), :]                        # [1, C]
            f = a_s[b0:, pl.ds(j, 1), :][:, 0, :] * inv     # [k-b0, C] col j
            f = jnp.where(row_ids <= j, 0.0, f)
            a_s[b0:, b0:, :] = a_s[b0:, b0:, :] - f[:, None, :] * rowj[None]
            b_s[b0:, :] = b_s[b0:, :] - f * bj
            return 0

        jax.lax.fori_loop(b0, b0 + _BLOCK, step, 0)

    # U[j, c] for c > j meets x[c] already solved; x[c ≤ j] is still 0,
    # so the whole-row product needs no mask.
    all_ids = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)

    def back(t, x):
        j = k - 1 - t
        u = a_s[pl.ds(j, 1), :, :][0]                       # [k, C] row j
        s = jnp.sum(u * x, axis=0, keepdims=True)           # [1, C]
        xj = (b_s[pl.ds(j, 1), :] - s) / a_s[pl.ds(j, 1), pl.ds(j, 1), :][0]
        return jnp.where(all_ids == j, xj, x)

    return jax.lax.fori_loop(0, k, back, jnp.zeros(b_s.shape, jnp.float32))


def _solve_kernel(a_ref, b_ref, x_ref, a_s, b_s, *, k: int):
    """Solve C systems: a_ref [k, k, C], b_ref [k, C] → x_ref [k, C].

    a_s/b_s are VMEM scratch copies mutated in place by the elimination.
    """
    a_s[...] = a_ref[...]
    b_s[...] = b_ref[...]
    x_ref[...] = _gauss_solve(a_s, b_s, k=k)


def _solve_kernel_wide(a_hbm, b_hbm, x_hbm, a_s, b_s, sems, *, k: int):
    """Wide-rank slab (96 < k ≤ 128): a_hbm [G, k, k, C], C = 128.

    At k=128 the f32 [k, k, C] slab is 8 MB, so the pipelined kernel's
    double-buffered input block plus scratch copy (24 MB) exceeds VMEM
    (and Mosaic rejects lane blocks narrower than 128). Slabs therefore
    stay in HBM (ANY space) and each grid step DMAs ONE slab into a
    single VMEM scratch — no double buffering. The elimination is
    compute-bound (≈ 0.2 GFLOP/slab at k = 128 against 8 MB of traffic),
    so the lost DMA/compute overlap is noise.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    cp_a = pltpu.make_async_copy(a_hbm.at[i], a_s, sems.at[0])
    cp_b = pltpu.make_async_copy(b_hbm.at[i], b_s, sems.at[1])
    cp_a.start()
    cp_b.start()
    cp_a.wait()
    cp_b.wait()
    b_s[...] = _gauss_solve(a_s, b_s, k=k)
    cp_x = pltpu.make_async_copy(b_s, x_hbm.at[i], sems.at[2])
    cp_x.start()
    cp_x.wait()


@functools.partial(jax.jit, static_argnames=("interpret", "vma"))
def _solve_lanes(a_t, b_t, *, interpret: bool = False, vma=None):
    """a_t [k, k, Np], b_t [k, Np] (Np multiple of 128) → x_t [k, Np].

    ``vma``: when called inside ``shard_map`` (check_vma=True), the mesh
    axes the output varies over — forwarded to the out_shape aval.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, _, n = a_t.shape
    if vma is not None:
        out_shape = jax.ShapeDtypeStruct((k, n), jnp.float32, vma=vma)
    else:
        out_shape = jax.ShapeDtypeStruct((k, n), jnp.float32)
    # Slab width: full lane utilization, capped so the f32 [k, k, C]
    # input block (double-buffered by the pipeline) plus its scratch copy
    # stays under the ~16 MB VMEM budget. Ranks past 96 take the wide
    # path (_solve_slabs_wide) instead.
    c = 512 if k <= 32 else (256 if k <= 48 else 128)
    c = min(c, n)
    grid = (n // c,)

    kernel = functools.partial(_solve_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, k, c), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, c), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((k, c), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((k, k, c), jnp.float32),
            pltpu.VMEM((k, c), jnp.float32),
        ],
        interpret=interpret,
    )(a_t, b_t)


@functools.partial(jax.jit, static_argnames=("interpret", "vma"))
def _solve_slabs_wide(a_g, b_g, *, interpret: bool = False, vma=None):
    """a_g [G, k, k, 128], b_g [G, k, 128] → x_g [G, k, 128] (96 < k ≤ 128).

    Slab-major layout: the caller pre-transposes so each grid step's slab
    is one contiguous [k, k, 128] block — the kernel's manual DMA is a
    single contiguous transfer (see _solve_kernel_wide).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, k, _, c = a_g.shape
    if vma is not None:
        out_shape = jax.ShapeDtypeStruct((g, k, c), jnp.float32, vma=vma)
    else:
        out_shape = jax.ShapeDtypeStruct((g, k, c), jnp.float32)
    kernel = functools.partial(_solve_kernel_wide, k=k)
    return pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((k, k, c), jnp.float32),
            pltpu.VMEM((k, c), jnp.float32),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=interpret,
    )(a_g, b_g)


def _solve_reference(a, b):
    """XLA fallback: batched Cholesky solve (CPU and rank > 128)."""
    chol = jnp.linalg.cholesky(a)
    return jax.scipy.linalg.cho_solve((chol, True), b[..., None])[..., 0]


def solve_path(k: int, platform: str) -> str:
    """The solve ``batched_spd_solve`` auto-selects for rank ``k`` on
    ``platform``: ``pallas`` (the VMEM elimination, k ≤ 128 on a TPU) or
    ``cholesky`` (XLA's)."""
    return "pallas" if platform == "tpu" and k <= 128 else "cholesky"


def batched_spd_solve(a, b, *, use_pallas: bool | None = None,
                      platform: str | None = None,
                      interpret: bool = False, vma=None):
    """Solve N independent SPD systems a[i] @ x[i] = b[i].

    a: [N, k, k] float32, b: [N, k] float32 → x [N, k] float32.

    ``use_pallas=None`` auto-selects: the Pallas kernel when ``platform``
    is "tpu" and k ≤ 128 (the kernel's VMEM slab cap), the XLA Cholesky
    path otherwise. ``platform`` must be the platform of the devices that
    will EXECUTE this computation — pass the mesh's device platform when
    calling under shard_map/jit-with-shardings; it defaults to
    ``jax.default_backend()``, which is only correct outside any explicit
    mesh (the driver dry-runs CPU meshes while a TPU stays the process
    default backend). Traceable (jit/shard_map safe): all shape logic is
    static.
    """
    n, k = b.shape
    if use_pallas is None:
        if platform is None:
            platform = jax.default_backend()
        use_pallas = solve_path(k, platform) == "pallas"
    if not use_pallas:
        return _solve_reference(a, b)

    kp = _round_up(k, 8)
    # Lanes path: multiple of 512 so every slab width (512/256/128)
    # divides the batch. Wide path: its slab width is always 128, and a
    # padding slab is ~0.5 GFLOP of pure identity solves — don't round
    # further than needed.
    npad = _round_up(max(n, 1), 128 if kp > 96 else 512)
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if kp != k:
        # Pad with identity diagonal: padded coords solve to x=0 and do
        # not couple to the real ones.
        eye_pad = jnp.eye(kp, dtype=jnp.float32)[k:]  # [kp-k, kp]
        a = jnp.pad(a, ((0, 0), (0, kp - k), (0, kp - k)))
        a = a.at[:, k:, :].set(eye_pad[None])
        b = jnp.pad(b, ((0, 0), (0, kp - k)))
    if npad != n:
        pad = jnp.eye(kp, dtype=jnp.float32)[None].repeat(npad - n, axis=0)
        a = jnp.concatenate([a, pad], axis=0)
        b = jnp.concatenate([b, jnp.zeros((npad - n, kp), jnp.float32)], axis=0)

    vma_f = None if vma is None else frozenset(vma)
    if kp > 96:
        # Wide-rank path: slab-major [G, kp, kp, 128] so each slab is one
        # contiguous manual-DMA transfer inside the kernel.
        c = 128
        g = npad // c
        a_g = jnp.transpose(a.reshape(g, c, kp, kp), (0, 2, 3, 1))
        b_g = jnp.transpose(b.reshape(g, c, kp), (0, 2, 1))
        x_g = _solve_slabs_wide(a_g, b_g, interpret=interpret, vma=vma_f)
        return jnp.transpose(x_g, (0, 2, 1)).reshape(npad, kp)[:n, :k]

    a_t = jnp.transpose(a, (1, 2, 0))  # [kp, kp, Np] — batch on lanes
    b_t = jnp.transpose(b, (1, 0))     # [kp, Np]
    x_t = _solve_lanes(a_t, b_t, interpret=interpret, vma=vma_f)
    return jnp.transpose(x_t, (1, 0))[:n, :k]
