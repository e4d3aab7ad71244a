"""Length-bucketed row layout: ragged ratings → gather-minimal dense slabs.

The ALS half-step is gather-bound on TPU: the factor-row gather unit
sustains a fixed ~420M rows/s regardless of row width (≤128 lanes),
sortedness, or table size (measured 2026-07 with tools/profile_als.py;
see docs/tpu.md). Every padded entry is therefore a wasted gather
slot, and any segment-reduction after the gather is pure overhead. This
layout minimizes both:

- Each row's entries live in ONE dense slab row [C_b] whose capacity C_b
  comes from a geometric ladder of 8-multiples (~1.05 steps), so padding
  is ~5% instead of the ~11%+ of uniform tiling — and the per-row normal
  equations fall straight out of a [R_b, C_b, k] einsum with NO tile→row
  segment reduction at all (the reduction IS the einsum contraction).
- Rows longer than ``overflow_len`` split into full-width *virtual* rows
  plus a ladder remainder; virtual grams merge into their parent row with
  one tiny scatter-add (a few thousand rows at ML-20M scale).

Storage order ("π space"): solved-side factor rows live at *slots* laid
out shard-major over the mesh data axis, bucket-major within a shard,
ascending row id within a bucket (then filler slots). Contiguous
slot-blocks shard cleanly over both the data axis (solves) and the model
axis (ALX factor sharding) — MODEL_AXIS ownership windows are windows of
slots, so the two compose with no extra machinery. Column indices are
pre-mapped into the counterpart's π space on the host.

The layout is a pure function of the per-row nnz counts (``plan_layout``);
``fill_buckets`` then scatters the entries into the planned slabs.

The reference has no analog: its ALS data layout is MLlib's in/out-block
RDD partitioning inside Spark (SURVEY.md §2.9 model-parallel row).
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: Rows longer than this split into full-width virtual rows. 2048 keeps
#: the virtual-row scatter tiny (~2k rows at ML-20M) while bounding the
#: largest einsum slab.
OVERFLOW_LEN = 2048

#: Geometric growth of the capacity ladder past 64. Every padded slot is
#: a wasted gather (the ALS wall), so tighter is faster until the bucket
#: count (= separate einsum programs inside the one jit) hurts compile
#: time. Measured at ML-20M shape: 1.15 → mean padding 1.100 (5+15
#: buckets), 1.05 → 1.052 (12+37 buckets) — ~4.6% fewer gathered rows.
#: The r4 driver-verified A/B on the real chip ran 1.05 at 18.67M ev/s
#: vs 1.15 at 17.56M (+6.3% end-to-end, compile time within noise), so
#: 1.05 is the value. A constant: it shapes the global plan, which every
#: process of a multi-process train must agree on.
LADDER_GROWTH = 1.05


def length_ladder(max_len: int,
                  overflow_len: int = OVERFLOW_LEN) -> np.ndarray:
    """Row-capacity ladder: multiples of 8 up to 64, then ~×LADDER_GROWTH
    steps (rounded up to a multiple of 8), capped at ``overflow_len``.

    Geometric steps bound per-row padding waste while keeping the bucket
    count (= separate einsum programs) in the tens.
    """
    target = max(8, min(int(max_len), overflow_len))
    caps = []
    v = 0
    while v < target:
        if v < 64:
            v += 8
        else:
            v = min(max(-(-int(v * LADDER_GROWTH) // 8) * 8, v + 8),
                    overflow_len)
        caps.append(v)
    return np.asarray(caps, dtype=np.int64)


@dataclasses.dataclass(frozen=True)
class LayoutPlan:
    """Deterministic bucket layout derived from per-row counts alone."""

    lengths: np.ndarray        # [n_buckets] int64 — slab width per bucket
    bucket_rows: np.ndarray    # [n_buckets] int64 — rows per SHARD per bucket
    rows_per_shard: int        # Σ bucket_rows (incl. m-divisibility filler)
    n_shards: int
    n_rows: int                # logical rows
    overflow_len: int
    slot_of_row: np.ndarray    # [n_rows] int64 — global storage slot
    counts_slot: np.ndarray    # [n_shards*rows_per_shard] int64 (filler=0)
    bucket_of_row: np.ndarray  # [n_rows] int64
    # overflow bookkeeping (all-empty when no row exceeds overflow_len):
    v_rows_per_shard: int      # virtual rows per shard (max, padded)
    v_chunks_of_row: np.ndarray  # [n_rows] int64 — # full-width chunks
    v_base_of_row: np.ndarray  # [n_rows] int64 — row's first LOCAL v-slot
    v_parent: np.ndarray       # [n_shards*v_rows_per_shard] int64 LOCAL slot

    @property
    def has_heavy_bucket(self) -> bool:
        """True → the LAST bucket holds exactly the overflow parents
        (their grams are materialized + merged with the virtual slabs;
        all other buckets fuse ridge+solve per chunk). Derived, not
        stored: plan_layout routes heavy rows there iff any exist."""
        return self.v_rows_per_shard > 0

    @property
    def total_slots(self) -> int:
        return self.n_shards * self.rows_per_shard

    def shard_of_row(self, row: np.ndarray) -> np.ndarray:
        rpl = -(-self.n_rows // self.n_shards)
        return np.minimum(np.asarray(row) // rpl, self.n_shards - 1)


def plan_layout(counts: np.ndarray, n_shards: int, m_div: int = 1,
                overflow_len: int = OVERFLOW_LEN) -> LayoutPlan:
    """Plan the bucket layout for one side from its per-row nnz counts.

    Rows are owned by shards in contiguous logical ranges of
    ``ceil(n_rows / n_shards)``. ``m_div``: rows_per_shard is rounded up
    so the total padded row count divides the model axis.
    """
    counts = np.asarray(counts, dtype=np.int64)
    n_rows = counts.shape[0]
    S = int(n_shards)
    rpl = -(-n_rows // S)  # logical rows per shard (last shard may be short)
    row_ids = np.arange(n_rows, dtype=np.int64)
    shard_of_row = np.minimum(row_ids // rpl, S - 1)

    # overflow split: full-width virtual chunks + a non-empty remainder
    v_chunks = np.where(counts > overflow_len, counts // overflow_len, 0)
    rem = counts - v_chunks * overflow_len
    fix = (v_chunks > 0) & (rem == 0)
    v_chunks[fix] -= 1
    rem[fix] = overflow_len

    ladder = length_ladder(int(rem.max()) if n_rows else 8, overflow_len)
    bucket_of_row = np.searchsorted(ladder, np.maximum(rem, 1))
    n_buckets = len(ladder)
    # Rows with overflow (virtual) chunks go to a DEDICATED LAST bucket:
    # their normal equations need the virtual scatter-add before the
    # solve, so the device loop materializes grams only for this (small)
    # bucket and fuses ridge+solve per chunk everywhere else — the
    # full [rows, k, k] materialization would be ~11 GB at ML-20M
    # rank 128.
    heavy_mask = v_chunks > 0
    if heavy_mask.any():
        heavy_cap = ladder[np.searchsorted(
            ladder, max(int(rem[heavy_mask].max()), 1))]
        bucket_of_row = np.where(heavy_mask, n_buckets, bucket_of_row)
        ladder = np.append(ladder, heavy_cap)
        n_buckets += 1

    per_sb = np.bincount(
        shard_of_row * n_buckets + bucket_of_row, minlength=S * n_buckets
    ).reshape(S, n_buckets)
    bucket_rows = per_sb.max(axis=0)

    # drop empty buckets, keep bucket 0 (filler target) if ladder nonempty
    keep = np.nonzero(bucket_rows > 0)[0]
    if keep.size == 0:
        keep = np.array([0])
    new_idx = np.full(n_buckets, -1, dtype=np.int64)
    new_idx[keep] = np.arange(keep.size)
    lengths = ladder[keep]
    bucket_rows = bucket_rows[keep].astype(np.int64)
    bucket_of_row = new_idx[bucket_of_row]
    per_sb = per_sb[:, keep]
    n_buckets = keep.size

    rows_per_shard = int(bucket_rows.sum())
    pad_m = (-rows_per_shard) % int(m_div)
    if rows_per_shard + pad_m < 1:
        pad_m = 1
    bucket_rows[0] += pad_m  # filler rows take the cheapest slab width
    rows_per_shard += pad_m

    # slot of each row: shard-major, bucket blocks, rank within bucket
    bucket_base = np.zeros(n_buckets + 1, dtype=np.int64)
    np.cumsum(bucket_rows, out=bucket_base[1:])
    order = np.lexsort((row_ids, bucket_of_row, shard_of_row))
    sb_sorted = (shard_of_row * n_buckets + bucket_of_row)[order]
    group_start = np.zeros(len(order), dtype=np.int64)
    if len(order):
        new_group = np.empty(len(order), dtype=bool)
        new_group[0] = True
        new_group[1:] = sb_sorted[1:] != sb_sorted[:-1]
        starts = np.nonzero(new_group)[0]
        group_start = starts[np.cumsum(new_group) - 1]
    rank = np.arange(len(order), dtype=np.int64) - group_start
    slot_sorted = (
        shard_of_row[order] * rows_per_shard
        + bucket_base[bucket_of_row[order]]
        + rank
    )
    slot_of_row = np.empty(n_rows, dtype=np.int64)
    slot_of_row[order] = slot_sorted

    counts_slot = np.zeros(S * rows_per_shard, dtype=np.int64)
    counts_slot[slot_of_row] = counts

    # virtual rows: grouped per shard, ordered by (row, chunk)
    v_per_shard_real = np.bincount(
        shard_of_row, weights=v_chunks.astype(np.float64), minlength=S
    ).astype(np.int64)
    Rv = int(v_per_shard_real.max()) if n_rows else 0
    v_base_of_row = np.zeros(n_rows, dtype=np.int64)
    v_parent = np.zeros(S * Rv, dtype=np.int64)
    if Rv:
        # local v-slot base per row: running sum of v_chunks within shard
        order_r = row_ids  # rows already ascending == (shard, row) order
        cum = np.cumsum(v_chunks[order_r])
        shard_cum_start = np.zeros(n_rows, dtype=np.int64)
        # subtract the cumulative total of previous shards
        shard_first = np.searchsorted(shard_of_row, np.arange(S))
        prev_total = np.zeros(S, dtype=np.int64)
        for s in range(1, S):
            prev_total[s] = cum[shard_first[s] - 1] if shard_first[s] > 0 else 0
        shard_cum_start = prev_total[shard_of_row]
        v_base_of_row = cum - v_chunks - shard_cum_start
        heavy = np.nonzero(v_chunks > 0)[0]
        for r in heavy:  # heavy rows are few by construction
            s = shard_of_row[r]
            base = s * Rv + v_base_of_row[r]
            v_parent[base:base + v_chunks[r]] = (
                slot_of_row[r] - s * rows_per_shard
            )
    return LayoutPlan(
        lengths=lengths,
        bucket_rows=bucket_rows,
        rows_per_shard=rows_per_shard,
        n_shards=S,
        n_rows=n_rows,
        overflow_len=overflow_len,
        slot_of_row=slot_of_row,
        counts_slot=counts_slot,
        bucket_of_row=bucket_of_row,
        v_rows_per_shard=Rv,
        v_chunks_of_row=v_chunks,
        v_base_of_row=v_base_of_row,
        v_parent=v_parent,
    )


def plan_and_fill_both(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    rating: np.ndarray,
    n_users: int,
    n_items: int,
    n_shards: int,
    m_div: int = 1,
    fill_vals: bool = True,
    parallel: bool | None = None,
):
    """Plan and fill BOTH sides' slabs for an ALS train:
    ``(plan_u, plan_i, arrs_u, arrs_i)``.

    The two sides' plans (and then their fills) are independent host
    passes over the same COO triple, and both the native single-pass
    scatter (a ctypes call) and the numpy fallback's radix argsort
    release the GIL — so with ``parallel`` (default: on unless
    PIO_PIPELINE=off) each pair runs on input-pipeline worker threads,
    overlapping the dominant host cost of ALS layout prep. Results are
    identical to the serial path: nothing is shared but read-only
    inputs.
    """
    if parallel is None:
        from ..workflow.input_pipeline import PipelineConfig

        parallel = PipelineConfig.from_env().mode != "off"

    counts_u = np.bincount(np.asarray(user_idx, np.int64), minlength=n_users)
    counts_i = np.bincount(np.asarray(item_idx, np.int64), minlength=n_items)

    def _run(*thunks):
        if parallel:
            from ..workflow.input_pipeline import host_parallel

            return host_parallel(*thunks)
        return [t() for t in thunks]

    plan_u, plan_i = _run(
        lambda: plan_layout(counts_u, n_shards, m_div=m_div),
        lambda: plan_layout(counts_i, n_shards, m_div=m_div),
    )
    arrs_u, arrs_i = _run(
        lambda: fill_buckets(plan_u, user_idx, item_idx, rating,
                             col_slot_map=plan_i.slot_of_row,
                             sentinel=plan_i.total_slots,
                             fill_vals=fill_vals),
        lambda: fill_buckets(plan_i, item_idx, user_idx, rating,
                             col_slot_map=plan_u.slot_of_row,
                             sentinel=plan_u.total_slots,
                             fill_vals=fill_vals),
    )
    return plan_u, plan_i, arrs_u, arrs_i


@dataclasses.dataclass(frozen=True)
class BucketArrays:
    """Dense per-bucket entry slabs for a contiguous range of shards.

    cols hold COUNTERPART π-space slot indices; padding slots hold the
    sentinel (= counterpart total padded rows: a zero factor row in
    replicated mode, outside every ownership window in sharded mode).
    """

    cols: tuple[np.ndarray, ...]   # per bucket [S_loc*R_b, C_b] int32
    vals: tuple[np.ndarray, ...]   # per bucket [S_loc*R_b, C_b] f32
    v_cols: np.ndarray             # [S_loc*Rv, overflow_len] int32
    v_vals: np.ndarray             # [S_loc*Rv, overflow_len] f32
    shard0: int
    n_local_shards: int
    # fill_vals=False (binary-ratings mode): vals is an empty tuple and
    # v_vals a zero-size array — the device synthesizes exact ones.


def fill_buckets(plan: LayoutPlan, row: np.ndarray, col: np.ndarray,
                 val: np.ndarray, col_slot_map: np.ndarray, sentinel: int,
                 shard0: int = 0, n_local_shards: int | None = None,
                 use_native: bool | None = None,
                 fill_vals: bool = True) -> BucketArrays:
    """Scatter entries into the planned slabs for shards
    [shard0, shard0+n_local_shards). ``row`` must contain ONLY rows owned
    by those shards; ``col`` is global counterpart row ids, mapped through
    ``col_slot_map`` into the counterpart's π space.

    ``use_native``: None = auto (the C++ single-pass scatter when the
    toolchain is available — it replaces the numpy path's stable argsort,
    the dominant host cost of layout prep, and is bit-identical to it);
    False forces the numpy path (tests use both and assert equality).

    ``fill_vals=False`` (binary-ratings mode): the value slabs are
    neither allocated nor filled — every real entry is 1.0 and the
    device synthesizes exact ones (ops/als.py binary_ratings).
    """
    S_loc = plan.n_shards - shard0 if n_local_shards is None else int(n_local_shards)
    if fill_vals:
        val = np.asarray(val, dtype=np.float32)
    n_buckets = len(plan.lengths)
    Rv, OV = plan.v_rows_per_shard, plan.overflow_len

    # flat buffer: [bucket slabs ..., virtual slab]
    sizes = [S_loc * int(plan.bucket_rows[b]) * int(plan.lengths[b])
             for b in range(n_buckets)]
    v_size = S_loc * Rv * OV
    offsets = np.zeros(n_buckets + 2, dtype=np.int64)
    np.cumsum(np.asarray(sizes + [v_size], dtype=np.int64), out=offsets[1:])
    flat_cols = np.full(int(offsets[-1]), sentinel, dtype=np.int32)
    flat_vals = (np.zeros(int(offsets[-1]), dtype=np.float32)
                 if fill_vals else None)

    if len(row):
        if plan.n_rows > 2**31 - 1:
            raise NotImplementedError(
                "fill_buckets: row ids beyond int32 are not supported")
        n_rows = plan.n_rows
        shard_r = plan.shard_of_row(np.arange(n_rows, dtype=np.int64))
        # per-row flat bases (garbage for non-local rows — the range
        # check below guarantees none are referenced)
        bucket_base = np.zeros(n_buckets + 1, dtype=np.int64)
        np.cumsum(plan.bucket_rows, out=bucket_base[1:])
        b_r = plan.bucket_of_row
        rib = (plan.slot_of_row - shard_r * plan.rows_per_shard
               - bucket_base[b_r])
        prim_base = (offsets[b_r]
                     + ((shard_r - shard0) * plan.bucket_rows[b_r] + rib)
                     * plan.lengths[b_r])
        vc_r = plan.v_chunks_of_row
        # a row's virtual chunks are CONSECUTIVE v-slots, so its first
        # vc*OV entries land contiguously at v_base + pos
        v_base = (offsets[n_buckets]
                  + ((shard_r - shard0) * Rv + plan.v_base_of_row) * OV)

        # range checks before any gather of the per-row tables above
        # (also keeps the native and numpy paths raising identically)
        row64 = np.asarray(row, np.int64)
        s_lo, s_hi = (int(s) for s in plan.shard_of_row(
            np.array([row64.min(), row64.max()], np.int64)))
        if s_lo < shard0 or s_hi >= shard0 + S_loc:
            raise ValueError(
                "fill_buckets: entries reference rows outside shards "
                f"[{shard0}, {shard0 + S_loc})")
        col64 = np.asarray(col, np.int64)
        if len(col64) and (col64.min() < 0
                           or col64.max() >= len(col_slot_map)):
            raise ValueError(
                "fill_buckets: column ids outside the counterpart slot map")

        done = False
        if use_native is not False:
            # C++ single-pass scatter (native/src/event_codec.cc
            # pio_fill_entries): per-row write cursors replace the
            # argsort + position arithmetic below; same entry order.
            try:
                from ..native import NativeUnavailable, fill_entries
                fill_entries(row64, col64, val if fill_vals else None,
                             col_slot_map, prim_base, v_base, vc_r * OV,
                             flat_cols, flat_vals)
                done = True
            except NativeUnavailable:
                if use_native is True:
                    raise
        if not done:
            # numpy fallback: one int32 stable argsort (radix — 2x+
            # faster than int64 comparison sort; row ids bounded by the
            # int32 guard above), then only gathers of small per-ROW
            # tables + one scatter.
            order = np.argsort(np.asarray(row, np.int32), kind="stable")
            rs = row64[order]
            # remap columns into counterpart pi space at the SOURCE (all
            # real); sentinel prefill covers the padding slots.
            cs = np.asarray(col_slot_map, np.int64)[
                col64[order]].astype(np.int32)

            # position of each entry within its row (stable original order)
            rmin = int(rs[0])
            cnt = np.bincount((rs - rmin).astype(np.int64))
            starts = np.zeros(len(cnt), dtype=np.int64)
            np.cumsum(cnt[:-1], out=starts[1:])
            pos = np.arange(len(rs), dtype=np.int64) - starts[rs - rmin]

            vc_e = vc_r[rs] * OV
            dest = np.where(pos < vc_e,
                            v_base[rs] + pos,
                            prim_base[rs] + pos - vc_e)
            flat_cols[dest] = cs
            if fill_vals:
                flat_vals[dest] = val[order]

    cols, vals = [], []
    for b in range(n_buckets):
        R, C = S_loc * int(plan.bucket_rows[b]), int(plan.lengths[b])
        cols.append(flat_cols[offsets[b]:offsets[b + 1]].reshape(R, C))
        if fill_vals:
            vals.append(flat_vals[offsets[b]:offsets[b + 1]].reshape(R, C))
    v_cols = flat_cols[offsets[n_buckets]:offsets[n_buckets + 1]].reshape(
        S_loc * Rv, OV)
    v_vals = (flat_vals[offsets[n_buckets]:offsets[n_buckets + 1]].reshape(
        S_loc * Rv, OV) if fill_vals
        else np.zeros((0, OV), np.float32))
    return BucketArrays(
        cols=tuple(cols), vals=tuple(vals), v_cols=v_cols, v_vals=v_vals,
        shard0=shard0, n_local_shards=S_loc,
    )
