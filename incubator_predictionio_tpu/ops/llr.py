"""Correlated Cross-Occurrence (CCO) with log-likelihood-ratio scoring.

Reference behaviour: the Universal Recommender computes item-item
cross-occurrence matrices per event type with Apache Mahout's
SimilarityAnalysis.cooccurrencesIDSs (LLR-thresholded), then indexes the
indicators into Elasticsearch (SURVEY.md §2.8 row 5). TPU-native design
(SURVEY.md §7 step 10): co-occurrence counts are dense chunked matmuls on
the MXU — user-interaction matrices are scattered into dense [U_chunk, I]
slabs on device and C = Σ_chunks A_pᵀ A_s accumulates per primary/secondary
pair; Dunning's G² LLR is evaluated vectorized over the full count matrix;
top-k correlators per item are kept as static [I, K] index/score arrays
(what is persisted; served, they become an index by correlator on the
device, what Elasticsearch held: the serving section at the end).

Scale notes: events are pre-partitioned by user range on the host (sorted
slabs, like ops/blocked.py), so each scan step scatters only its own
events — the naive alternative of range-masking the whole event array per
step is quadratic and ~40x slower on TPU at 1M events. Slabs are int8
(binary, so exact) for the MXU's double-rate int8 mode with exact int32
accumulation (f32 only from the LLR stage on). Two
accumulation strategies, chosen by HBM budget: when the full [I, I]
f32 matrix fits a fraction of device memory, one scan over user ranges
builds each membership slab ONCE and accumulates the whole matrix
(then LLR + top-k per stripe slice, a second dispatch over the resident
matrix); bigger
catalogs stream [item_block, I] stripes through a bounded accumulator
(slabs rebuilt per stripe — the memory/compute trade). Both paths are
bit-identical (counts are exact integers; tested). Either
way only the [I, K] indicators materialize on the host.
"""

from __future__ import annotations

import dataclasses
import os
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..common import telemetry
from .topk import (
    ROW_LADDER, RowExclude, no_exclude_mask, pack_rows, put_rows,
    select_block_len, select_topk, suppress_rows,
)


_M_PATH = telemetry.registry().counter(
    "pio_cco_path_total",
    "Cross-occurrence device programs by accumulation strategy: fused = "
    "every pair of one primary in one scan, each with a resident "
    "[I, I] matrix; pair_full = one pair, one resident matrix; striped = "
    "one pair, [item_block, I] stripes through a bounded accumulator.",
    ("path",))


def _ladder(n: int) -> int:
    """The smallest m * 2**s >= n with m in 8..15 (n itself up to 16): at
    most an eighth over n. Slab widths are rounded up to it, so that a
    retrain on events whose widest range differs by less than a rung
    meets the executable already compiled. It holds only the widths
    still: n_items, the number of user ranges and of heavy ranges key
    the executable too, so a new item, the 2,049th new user or a user
    crossing the heavy threshold compiles anew. What the rounding adds
    is padding the sentinel already masks: at most an eighth more
    scatter work a slab (the striped path pays it once a stripe)."""
    n = max(int(n), 1)
    if n <= 16:
        return n
    s = n.bit_length() - 4
    return -(-n >> s) << s


#: Heavy-user rank-range width: drives BOTH the heavy-slab partition
#: (h_per) and the device scan's u_chunk — a mismatch would silently
#: treat in-range offsets as padding sentinels and drop events.
_HEAVY_RANGE = 16


def _xlogx(x):
    return jnp.where(x > 0, x * jnp.log(jnp.maximum(x, 1e-30)), 0.0)


def _entropy2(a, b):
    return _xlogx(a + b) - _xlogx(a) - _xlogx(b)


def llr_scores(k11, k12, k21, k22):
    """Dunning's G² over contingency counts (vectorized).

    Reference math: Mahout LogLikelihood.logLikelihoodRatio — G² =
    2·(H(row)+H(col)−H(matrix)) in the xlogx formulation.
    """
    row = _entropy2(k11 + k12, k21 + k22)
    col = _entropy2(k11 + k21, k12 + k22)
    mat = (
        _xlogx(k11 + k12 + k21 + k22)
        - _xlogx(k11) - _xlogx(k12) - _xlogx(k21) - _xlogx(k22)
    )
    g2 = 2.0 * (row + col - mat)
    # Guard tiny negatives from cancellation.
    return jnp.maximum(g2, 0.0)


def _partition_by_user(u: np.ndarray, i: np.ndarray, u_chunk: int,
                       n_ranges: int, n_items: int,
                       assume_sorted: bool = False):
    """Host prep: sort (user, item) pairs by user range and lay them out
    as [n_ranges, E] slabs, so the device scan step for slab row r
    touches only events of one user range. A range's primary and
    secondary slabs must be COMPLETE for the per-step product to count
    every cross pair, so ranges are never split here — skewed heavy
    users are extracted beforehand (see ``cco_indicators``) to keep E
    near the mean.

    Returns (eu [n_ranges, E], ei [n_ranges, E]): eu holds the user's
    LOCAL offset within its range (padding sentinel = u_chunk — no
    per-row base array needed on device), ei the item id (padding 0,
    masked by the sentinel); E is the widest range's count rounded up
    the ``_ladder``. Both upload uint16 when their value range
    fits (they nearly always do: u_chunk defaults to 2048, catalogs are
    rarely >65k items) — half the slab bytes of int32, which matters
    because the slab upload is a dominant warm-train cost on
    remote-attached chips."""
    # Events whose user id falls outside [0, n_ranges*u_chunk) are dropped
    # (contract: user ids < n_users; the pre-rewrite slab mask silently
    # ignored them too, and a bad id must not corrupt the layout).
    valid = (u >= 0) & (u < n_ranges * u_chunk)
    u, i = u[valid], i[valid]
    if assume_sorted:
        # dedupe already emits (user, item)-sorted pairs; re-argsorting
        # 8M rows cost ~0.3 s of pure host time per event set
        us, is_ = u, i
    else:
        order = np.argsort(u, kind="stable")
        us, is_ = u[order], i[order]
    chunk_of = (us // u_chunk).astype(np.int64)
    counts = np.bincount(chunk_of, minlength=n_ranges)
    e = _ladder(counts.max()) if counts.size else 1

    starts = np.zeros(n_ranges + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    pos = np.arange(len(us)) - starts[chunk_of]
    u_dtype = np.uint16 if u_chunk < 0xFFFF else np.int32
    i_dtype = np.uint16 if n_items <= 0xFFFF else np.int32
    eu = np.full((n_ranges, e), u_chunk, u_dtype)   # sentinel = u_chunk
    ei = np.zeros((n_ranges, e), i_dtype)
    eu[chunk_of, pos] = (us - chunk_of * u_chunk).astype(u_dtype)
    ei[chunk_of, pos] = is_.astype(i_dtype)
    return eu, ei


def _slab(uu, ii, u_chunk: int, n_items: int):
    """One range's binary int8 membership slab [u_chunk, n_items] from
    (local user offset, item) event pairs; the sentinel offset u_chunk
    lands padding on a scratch row that is sliced away.

    int8, not bf16: binary membership is exact in any dtype, and the
    v5e MXU runs int8 contractions at ~2x its bf16 rate (197 TOPS vs
    98 TFLOPs — measured 2.8x on the UR shapes). Counts accumulate in
    int32 (≤ n_users, exact) and widen to f32 only at the LLR stage.

    Built as a FLAT 1-D scatter-add then reshaped: the 2-D scatter-max
    lowered to TPU's serialized scatter path (~457 ns/element — measured
    3.6 s just building slabs for the UR bench), while the 1-D add runs
    ~28x faster. Events are deduped upstream, so each (u, i) lands
    exactly once and add ≡ max ≡ set (bit-identical counts)."""
    # int64 flat indices when the slab exceeds int32 addressing (the
    # striped path serves multi-million-item catalogs)
    idx_dtype = (jnp.int32 if (u_chunk + 1) * n_items < 2**31
                 else jnp.int64)
    flat = uu.astype(idx_dtype) * n_items + ii.astype(idx_dtype)
    a = jnp.zeros(((u_chunk + 1) * n_items,), jnp.int8)
    a = a.at[flat].add(jnp.int8(1))
    return a.reshape(u_chunk + 1, n_items)[:u_chunk]


@functools.partial(jax.jit, static_argnames=("n_items", "u_chunk", "block"))
def _cooccurrence_stripe(peu, pei, seu, sei, lo_item,
                         n_items: int, u_chunk: int, block: int):
    """One stripe C[lo_item:lo_item+block, :] of the co-occurrence
    matrix: Σ over slab rows of slab_p[:, stripe]ᵀ @ slab_s. Inputs are
    the host-partitioned [n_rows, E] event slabs (local user offsets,
    sentinel u_chunk = padding); each scan step scatters only its own
    row's events.

    Heavy users are not in the light slabs; ``cco_indicators`` routes
    them through this same kernel with rank-renumbered ids and small
    rank ranges."""

    def body(c, chunk):
        eu_p, ei_p, eu_s, ei_s = chunk
        ap = jax.lax.dynamic_slice(
            _slab(eu_p, ei_p, u_chunk, n_items), (0, lo_item),
            (u_chunk, block))
        asec = _slab(eu_s, ei_s, u_chunk, n_items)
        c = c + jnp.einsum("ui,uj->ij", ap, asec,
                           preferred_element_type=jnp.int32)
        return c, None

    c0 = jnp.zeros((block, n_items), jnp.int32)
    c, _ = jax.lax.scan(body, c0, (peu, pei, seu, sei))
    return c


def _pad_ranges(arrs, mult: int, u_chunk: int):
    """Pad the leading (range) axis to a device-count multiple with
    sentinel-only rows (local offset u_chunk = padding → zero slab →
    contributes nothing to the accumulate)."""
    n = arrs[0].shape[0]
    target = -(-n // mult) * mult
    if target == n:
        return arrs
    out = []
    for j, a in enumerate(arrs):
        fill = u_chunk if j % 2 == 0 else 0   # (eu, ei) alternating
        pad = np.full((target - n, a.shape[1]), fill, a.dtype)
        out.append(np.concatenate([np.asarray(a), pad], axis=0))
    return tuple(out)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "n_items", "u_chunk", "h_chunk", "self_flags"))
def _cco_count_multi(light_p, light_secs, heavy_p, heavy_secs, *, mesh=None,
                     n_items: int, u_chunk: int, h_chunk: int,
                     self_flags: tuple):
    """The [I, I] int32 count matrices of ALL of one primary's
    cross-occurrence pairs from ONE scan over the user ranges — each
    range's slabs are built ONCE (the striped kernel rebuilds them per
    stripe; at 20k items that redundant scatter was ~60% of UR's device
    time): each range's PRIMARY membership slab is built once and every
    secondary's matrix accumulates against it (self-pairs reuse the
    primary slab outright — no second scatter, no second upload). The
    per-pair path scatters the primary slab S times and uploads the
    primary events S times; for the UR bench (buy→buy + buy→view) the
    fusion removes a third of the event-slab upload bytes and half the
    primary scatters. Costs n_items^2 * 4 bytes of HBM a matrix, so the
    callers only route here when that fits (PIO_UR_FULL_MATRIX_ELEMS caps
    it; the striped path remains for big catalogs). Counts are exact
    integers → bit-identical to per-pair calls and to the striped path
    (tested).

    light_secs/heavy_secs: (eu, ei) pairs for NON-self secondaries, in
    output order; self_flags marks which outputs take the primary slab;
    heavy_p/heavy_secs use () for absent (static pytree shape).

    With a multi-device ``mesh`` (a static arg — Mesh is hashable — so
    repeat trains at the same shapes reuse one executable like every
    other kernel here) the user ranges shard over DATA_AXIS: each device
    scans only its local ranges and the per-device partial matrices psum
    over ICI (exact int32 → bit-identical to the single device; tested on
    the virtual mesh); every device then holds every matrix.

    The matrices are this dispatch's OUTPUTS and ``_cco_select``'s
    inputs, not temporaries of one executable that counts and selects:
    the runtime's ``memory_stats()`` count buffers and not an
    executable's temporaries (measured on a v5e: 8.6 GB of temporaries
    read 0.7 MB), so only so do the gigabytes this template keeps on the
    device show to whoever watches the device's memory, and a profile
    names the two halves; on one chip the two dispatches take 7% less
    than the one did (docs/tpu.md)."""
    if mesh is not None:
        from jax import shard_map
        from jax.lax import pcast
        from jax.sharding import PartitionSpec as _P
        from ..parallel.mesh import DATA_AXIS as _D

    def counts_fn(lp, lsecs, hp, hsecs):
        cs = tuple(jnp.zeros((n_items, n_items), jnp.int32)
                   for _ in self_flags)
        if mesh is not None:
            # shard_map's varying-manual-axes typing: the carry starts as
            # a replicated constant but the body output varies over the
            # data axis — mark it varying up front
            cs = tuple(pcast(c, (_D,), to="varying") for c in cs)
        xs = tuple(lp) + tuple(x for pair in lsecs for x in pair)
        cs, _ = jax.lax.scan(_mk_multi_body(self_flags, n_items, u_chunk),
                             cs, xs)
        if len(hp):
            xs_h = tuple(hp) + tuple(x for pair in hsecs for x in pair)
            cs, _ = jax.lax.scan(
                _mk_multi_body(self_flags, n_items, h_chunk), cs, xs_h)
        if mesh is not None:
            cs = tuple(jax.lax.psum(c, _D) for c in cs)
        return cs

    if mesh is None:
        return counts_fn(light_p, light_secs, heavy_p, heavy_secs)
    rows = _P(_D, None)

    def specs_like(tree):
        return jax.tree.map(lambda _: rows, tree)

    return shard_map(
        counts_fn, mesh=mesh,
        in_specs=(specs_like(light_p), specs_like(light_secs),
                  specs_like(heavy_p), specs_like(heavy_secs)),
        out_specs=tuple(_P() for _ in self_flags),
    )(light_p, light_secs, heavy_p, heavy_secs)


def _mk_multi_body(self_flags: tuple, n_items: int, chunk_rows: int):
    """``_cco_count_multi``'s scan body: build the primary slab once,
    accumulate every pair against it."""
    def body(cs, chunk):
        ap = _slab(chunk[0], chunk[1], chunk_rows, n_items)
        outs, r = [], 2
        for is_self in self_flags:
            if is_self:
                a2 = ap
            else:
                a2 = _slab(chunk[r], chunk[r + 1], chunk_rows, n_items)
                r += 2
            outs.append(cs[len(outs)] + jnp.einsum(
                "ui,uj->ij", ap, a2,
                preferred_element_type=jnp.int32))
        return tuple(outs), None
    return body


@functools.partial(jax.jit, static_argnames=(
    "n_items", "block", "k", "llr_threshold"))
def _cco_select(cs, n_js, n_i, lo_effs, n_total, *, n_items: int,
                block: int, k: int, llr_threshold: float):
    """G² and the k best of every row of the resident count matrices
    ``cs`` (one per secondary; n_js: [S, I] per-secondary distinct-user
    item counts), stripe by stripe, as ONE dispatch — one download
    instead of a dispatch + device_get round trip per stripe. After a
    mesh's count every device holds ``cs`` and this runs replicated."""
    outs = []
    for c, n_j in zip(cs, n_js):
        def body(carry, lo_eff, c=c, n_j=n_j):
            counts = jax.lax.dynamic_slice(c, (lo_eff, 0), (block, n_items))
            n_i_stripe = jax.lax.dynamic_slice(n_i, (lo_eff,), (block,))
            s, ix = _stripe_topk(counts, n_i_stripe, n_j, lo_eff, n_total,
                                 k=k, llr_threshold=llr_threshold)
            return carry, (s, ix)

        _, (ss, ixs) = jax.lax.scan(body, 0, lo_effs)
        outs.append((ss, ixs))
    return tuple(outs)


def _full_matrix_elem_cap() -> int:
    """Element budget for the [I, I] accumulator: an explicit
    PIO_UR_FULL_MATRIX_ELEMS wins (malformed values fall back with a
    warning rather than crashing training); otherwise the accumulator
    may use 1/4 of the device's reported memory — scan carries alias
    (no double buffer), and the remaining 3/4 leaves head-room for the
    bf16 slabs and LLR/top-k intermediates."""
    from ..common import envknobs

    raw = envknobs.env_str("PIO_UR_FULL_MATRIX_ELEMS", "")
    if raw:
        explicit = envknobs.env_int("PIO_UR_FULL_MATRIX_ELEMS", 0,
                                    float_ok=True)
        if explicit > 0:
            return explicit
        import warnings

        warnings.warn(
            f"PIO_UR_FULL_MATRIX_ELEMS={raw!r} is not a positive "
            "number; using the device-derived default", stacklevel=2)
    from ..parallel.mesh import device_memory_bytes

    # 1/4 of memory for the f32 accumulator: scan carries alias (no
    # double buffer), leaving head-room for slabs + LLR intermediates
    return device_memory_bytes() // 4 // 4


@dataclasses.dataclass
class Indicators:
    """Top-K LLR correlators per primary item (static shapes)."""

    idx: np.ndarray  # [I, K] int32, -1 = empty slot
    score: np.ndarray  # [I, K] f32 LLR

    @property
    def max_correlators(self) -> int:
        return self.idx.shape[1]


@functools.partial(jax.jit, static_argnames=("k", "llr_threshold"))
def _stripe_topk(counts, n_i_stripe, n_j, lo_item, n_total,
                 k: int, llr_threshold: float):
    """LLR + top-k for one [block, I] stripe of counts. Dunning
    contingency over DISTINCT USERS (Mahout semantics): n_i = users who
    did the primary event on item i, n_j likewise for the secondary
    event, N = total users."""
    block, n_items = counts.shape
    # counts arrive as exact int32 from the int8 MXU accumulate; LLR
    # math runs in f32 (counts <= n_users << 2^24, exact)
    counts = counts.astype(jnp.float32)
    k11 = counts
    k12 = jnp.maximum(n_i_stripe[:, None] - counts, 0.0)
    k21 = jnp.maximum(n_j[None, :] - counts, 0.0)
    k22 = jnp.maximum(n_total - k11 - k12 - k21, 0.0)
    llr = llr_scores(k11, k12, k21, k22)
    # No self-correlation on the diagonal and no score without counts.
    row_ids = lo_item + jnp.arange(block, dtype=jnp.int32)
    col_ids = jnp.arange(n_items, dtype=jnp.int32)
    llr = jnp.where(counts > 0, llr, 0.0)
    llr = jnp.where(row_ids[:, None] == col_ids[None, :], 0.0, llr)
    if llr_threshold > 0:
        llr = jnp.where(llr >= llr_threshold, llr, 0.0)
    return jax.lax.top_k(llr, k)


@functools.partial(jax.jit, static_argnames=(
    "n_items", "u_chunk", "block", "k", "llr_threshold", "h_chunk"))
def _all_stripes(lo_effs, light, heavy, n_i, n_j, n_total,
                 n_items: int, u_chunk: int, block: int, k: int,
                 llr_threshold: float, h_chunk: int):
    """Every item stripe in ONE dispatch: lax.scan over the stripe
    origins runs cooccurrence + LLR + top-k per stripe on device and
    returns the stacked [n_stripes, block, k] results — one download
    instead of a dispatch + device_get round trip per stripe."""
    def body(carry, lo_eff):
        counts = _cooccurrence_stripe(
            *light, lo_eff, n_items=n_items, u_chunk=u_chunk, block=block)
        if heavy is not None:
            counts = counts + _cooccurrence_stripe(
                *heavy, lo_eff, n_items=n_items, u_chunk=h_chunk,
                block=block)
        n_i_stripe = jax.lax.dynamic_slice(n_i, (lo_eff,), (block,))
        s, ix = _stripe_topk(counts, n_i_stripe, n_j, lo_eff, n_total,
                             k=k, llr_threshold=llr_threshold)
        return carry, (s, ix)

    _, (ss, ixs) = jax.lax.scan(body, 0, lo_effs)
    return ss, ixs


@functools.partial(jax.jit, static_argnames=(
    "mesh", "n_items", "u_chunk", "block", "k", "llr_threshold",
    "h_chunk"))
def _all_stripes_sharded(lo_effs, light, heavy, n_i, n_j, n_total, *,
                         mesh, n_items: int, u_chunk: int, block: int,
                         k: int, llr_threshold: float, h_chunk: int):
    """Multi-chip STRIPED path (catalogs whose [I, I] accumulator does
    not fit the budget): for each stripe, every device scans its local
    user ranges into a [block, I] partial and the partials psum over
    ICI; LLR + top-k stay replicated. Bit-identical to the
    single-device striped path (exact integer counts)."""
    from jax import shard_map
    from jax.lax import pcast
    from jax.sharding import PartitionSpec as _P
    from ..parallel.mesh import DATA_AXIS as _D

    def all_local(light_l, heavy_l):
        def one_stripe(lo_eff):
            def mk_body(chunk_rows: int):
                def body(c, chunk):
                    eu_p, ei_p, eu_s, ei_s = chunk
                    ap = jax.lax.dynamic_slice(
                        _slab(eu_p, ei_p, chunk_rows, n_items),
                        (0, lo_eff), (chunk_rows, block))
                    asec = _slab(eu_s, ei_s, chunk_rows, n_items)
                    return c + jnp.einsum(
                        "ui,uj->ij", ap, asec,
                        preferred_element_type=jnp.int32), None
                return body

            c0 = pcast(
                jnp.zeros((block, n_items), jnp.int32), (_D,),
                to="varying")
            c, _ = jax.lax.scan(mk_body(u_chunk), c0, light_l)
            if heavy_l is not None:
                c, _ = jax.lax.scan(mk_body(h_chunk), c, heavy_l)
            return jax.lax.psum(c, _D)

        def body(carry, lo_eff):
            counts = one_stripe(lo_eff)
            n_i_stripe = jax.lax.dynamic_slice(n_i, (lo_eff,), (block,))
            s, ix = _stripe_topk(counts, n_i_stripe, n_j, lo_eff,
                                 n_total, k=k,
                                 llr_threshold=llr_threshold)
            return carry, (s, ix)

        _, (ss, ixs) = jax.lax.scan(body, 0, lo_effs)
        return ss, ixs

    spec_rows = _P(_D, None)
    in_specs = (tuple(spec_rows for _ in light),
                None if heavy is None else tuple(spec_rows for _ in heavy))
    return shard_map(
        all_local, mesh=mesh, in_specs=in_specs, out_specs=_P(),
    )(light, heavy)


def _run_events(stage: str, events: dict, work, threaded: bool) -> dict:
    """One host stage of a train over its distinct events:
    ``{name: work(*args)}`` for ``events`` = name -> args, under ONE span
    ``stage`` opened here on the calling thread — its duration is the
    stage's wall whether the events ran side by side or in turn; tags
    ``events`` and ``threads`` (1 = in turn) — with each event's own pass
    a child span ``<stage>.event`` (tags ``event``, ``pairs`` = the length
    of the pass's input). ``threaded`` runs them through
    ``host_parallel``: the native passes are ctypes calls that release
    the GIL and share nothing but read-only inputs. A worker thread
    starts with an empty context, so each thunk runs in a copy of the
    caller's — the child spans hang under the stage's span, in the
    train's trace. The first exception reaches the caller, after every
    thread was joined."""
    import contextvars

    from ..workflow.input_pipeline import host_parallel

    def one(name, args):
        with telemetry.span(stage + ".event", event=name,
                            pairs=len(args[0])):
            return work(*args)

    with telemetry.span(stage, events=len(events),
                        threads=len(events) if threaded else 1):
        thunks = [functools.partial(contextvars.copy_context().run, one,
                                    name, args)
                  for name, args in events.items()]
        done = host_parallel(*thunks) if threaded else [t() for t in thunks]
    return dict(zip(events, done))


def _prepare_events(events: dict, n_users: int, n_items: int, u_chunk: int,
                    n_mesh_dev: int) -> dict:
    """The host half of one cross-occurrence train: ``events`` = name ->
    (u, i), the DISTINCT events (a self pair is its primary, and is not
    here twice), become name -> (light (eu, ei), heavy (eu, ei) or None,
    per-item distinct-user counts as float32).

    Two stages, each over all the events at once (``_run_events``), side
    by side as the ALS layout runs its two sides
    (``rowblocks.plan_and_fill_both``: unless PIO_PIPELINE=off) when
    there is more than one event:

    - ``cco.dedupe``: ``_dedupe_pair`` of each event — looked up in the
      module at call time, once an event: a traced benchmark run wraps
      that attribute. Output is (user, item)-sorted, which the partition
      relies on.
    - the barrier: ``_heavy_ranks`` needs the SUMMED per-user counts of
      every event (the threshold only shapes the layout, never the
      counts, so any consistent choice keeps results identical — and
      this one keeps the ranges, the widths and so the executables what
      they were when the events ran in turn).
    - ``cco.partition``: the [ranges, E] slabs of each event (one-pass
      native C when available, else the identical NumPy layout; with the
      start of its puts 0.26 s for the ML-20M retrain's 10.0M buy pairs
      and 0.53-0.63 s for its 20.0M view pairs, side by side, span
      ``cco.partition.event``; chip host, PR 32). On one
      device each event STARTS its own ``device_put``s as soon as its
      slabs exist, so one event's upload runs under another's layout; on
      a mesh the slabs stay HOST arrays padded to a device multiple —
      the jit uploads them sharded (an eager put would land everything
      on one device first)."""
    from ..workflow.input_pipeline import PipelineConfig

    threaded = (len(events) > 1
                and PipelineConfig.from_env().mode != "off")
    n_ranges = max((n_users + u_chunk - 1) // u_chunk, 1)

    deduped = _run_events(
        "cco.dedupe", events,
        lambda u, i: _dedupe_pair(u, i, n_users, n_items), threaded)
    rank, h_ranges = _heavy_ranks(
        sum(cnt for _u, _i, cnt in deduped.values()), n_users)

    def partition_put(u, i):
        try:
            from ..native import cco_partition

            light, heavy, counts = cco_partition(
                u, i, rank, n_users, u_chunk, n_ranges, n_items,
                _HEAVY_RANGE, h_ranges)
        except Exception:  # noqa: BLE001 - native optional; layout identical
            light, heavy = _layout_event(u, i, rank, h_ranges, u_chunk,
                                         n_ranges, n_items)
            heavy = heavy or None
            counts = np.bincount(i, minlength=n_items)
        if n_mesh_dev > 1:
            light = _pad_ranges(light, n_mesh_dev, u_chunk)
            if heavy is not None:
                heavy = _pad_ranges(heavy, n_mesh_dev, _HEAVY_RANGE)
        else:
            light = tuple(jax.device_put(x) for x in light)
            if heavy is not None:
                heavy = tuple(jax.device_put(x) for x in heavy)
        return light, heavy, counts.astype(np.float32)

    return _run_events(
        "cco.partition",
        {name: (u, i) for name, (u, i, _cnt) in deduped.items()},
        partition_put, threaded)


def cco_indicators(
    primary_u: np.ndarray,
    primary_i: np.ndarray,
    secondary_u: np.ndarray,
    secondary_i: np.ndarray,
    n_users: int,
    n_items: int,
    max_correlators: int = 50,
    llr_threshold: float = 0.0,
    u_chunk: int = 2048,
    item_block: int = 4096,
    mesh=None,
) -> Indicators:
    """Build the LLR-thresholded cross-occurrence indicator matrix between
    a primary event's items and a secondary event's items (same item-id
    space; self-co-occurrence when primary==secondary). Memory strategy
    per _full_matrix_elem_cap; with a multi-device ``mesh`` the
    full-matrix accumulate shards user ranges over DATA_AXIS (per-device
    scans + one exact psum over ICI) — bit-identical results, linear
    range-scan scaling."""

    n_mesh_dev = int(mesh.devices.size) if mesh is not None else 1
    full_fits = n_items * n_items <= _full_matrix_elem_cap()
    # both sides go through the dedupe, even the same arrays twice: the
    # per-pair program has no self pair
    (light_p, heavy_p, n_i), (light_s, heavy_s, n_j) = _prepare_events(
        {0: (primary_u, primary_i), 1: (secondary_u, secondary_i)},
        n_users, n_items, u_chunk, n_mesh_dev).values()
    light = light_p + light_s
    heavy = heavy_p + heavy_s if heavy_p is not None else None
    n_total = jnp.float32(n_users)

    k = min(max_correlators, n_items)
    block = min(item_block, n_items)

    # Last stripe may be ragged: compute a full block ending at the
    # catalog edge and slice the overlap off (same compiled shape).
    los = list(range(0, n_items, block))
    lo_effs_np = np.array([min(lo, n_items - block) for lo in los], np.int32)
    path = "pair_full" if full_fits else "striped"
    _M_PATH.labels(path).inc()
    with telemetry.span("cco.device", path=path, n_sec=1,
                        **_layout_tags(light, heavy)):
        n_i_dev, n_j_dev = jnp.asarray(n_i), jnp.asarray(n_j)
        lo_effs = jnp.asarray(lo_effs_np)
        on_mesh = dict(mesh=mesh) if n_mesh_dev > 1 else {}
        if full_fits:
            # full-matrix path: every slab built once, then the selection
            # over the resident matrix
            heavy_p, heavy_s = (heavy[:2], (heavy[2:],)) if heavy else ((), ())
            cs = _cco_count_multi(
                light[:2], (light[2:],), heavy_p, heavy_s, n_items=n_items,
                u_chunk=u_chunk, h_chunk=_HEAVY_RANGE, self_flags=(False,),
                **on_mesh)
            out, = _cco_select(cs, n_j_dev[None], n_i_dev, lo_effs, n_total,
                               n_items=n_items, block=block, k=k,
                               llr_threshold=llr_threshold)
            del cs
        else:
            stripes = _all_stripes_sharded if on_mesh else _all_stripes
            out = stripes(lo_effs, light, heavy, n_i_dev, n_j_dev, n_total,
                          n_items=n_items, u_chunk=u_chunk,
                          h_chunk=_HEAVY_RANGE, block=block, k=k,
                          llr_threshold=llr_threshold, **on_mesh)
        ss, ixs = jax.device_get(out)

    with telemetry.span("cco.gather"):
        return _gather_indicators(ss, ixs, los, lo_effs_np, block, n_items)


def _layout_tags(light, heavy) -> dict:
    """What ``cco.device`` says of the layout it was handed: the scan
    lengths and the slab widths (the shapes that key the executable)."""
    return {"ranges": int(light[0].shape[0]),
            "heavy_ranges": int(heavy[0].shape[0]) if heavy else 0,
            "E": int(max(a.shape[1] for a in light[::2])),
            "heavy_E": int(max(a.shape[1] for a in heavy[::2]))
            if heavy else 0}


def _heavy_ranks(per_user: np.ndarray, n_users: int):
    """(rank [n_users] or None, heavy range count). Heavy-user
    extraction: a user with far more interactions than the mean would
    inflate every slab row's width E (user ranges cannot be split — a
    scan step's product needs the range's COMPLETE primary+secondary
    events to count every cross pair). Heavy users are renumbered onto a
    dense RANK space and processed through the SAME kernels with
    _HEAVY_RANGE-sized rank ranges: FEW heavy users per range, so one
    range's slab width stays ≈ 16 heavy histories, and the slab height
    is the range size, so heavy slabs are [17, I] — tiny. The threshold
    only shapes the layout, never the counts."""
    mean_pu = max(float(per_user.sum()) / max(n_users, 1), 1.0)
    heavy_cap = max(int(16 * mean_pu), 256)
    heavy_users = np.nonzero(per_user > heavy_cap)[0]
    n_heavy = int(len(heavy_users))
    if not n_heavy:
        return None, 0
    rank = np.full(n_users, -1, np.int64)
    rank[heavy_users] = np.arange(n_heavy)
    return rank, -(-n_heavy // _HEAVY_RANGE)


def _layout_event(u, i, rank, h_ranges: int, u_chunk: int, n_ranges: int,
                  n_items: int):
    """NumPy layout of one event's deduped, user-sorted pairs: ((eu, ei)
    light slabs, (eu, ei) heavy slabs or ()), the heavy users of ``rank``
    apart."""
    if rank is None:
        return _partition_by_user(u, i, u_chunk, n_ranges, n_items,
                                  assume_sorted=True), ()
    hm = rank[u] >= 0
    light = _partition_by_user(u[~hm], i[~hm], u_chunk, n_ranges, n_items,
                               assume_sorted=True)
    return light, _partition_by_user(
        rank[u[hm]].astype(np.int32), i[hm].astype(np.int32), _HEAVY_RANGE,
        h_ranges, n_items, assume_sorted=True)


def _dedupe_pair(u, i, n_users: int, n_items: int):
    """Distinct (user, item) pairs sorted by (user, item), out-of-range
    ids dropped. Native path: counting-sort by user + small per-user
    sorts (two linear passes; a global 16-bit-radix sort was tried
    first and LOST to numpy's introsort). One call is one thread: 0.51 s
    for the 10.0M buy events and 1.07 s for the 20.0M view events of
    the ML-20M retrain, side by side, span ``cco.dedupe.event`` (chip
    host, PR 32) — the larger event is the stage's wall. The numpy
    packed-key np.unique fallback is order-identical (tested).

    Returns (users, items, per_user_distinct_counts)."""
    try:
        from ..native import pair_dedupe

        return pair_dedupe(np.asarray(u), np.asarray(i), n_users, n_items)
    except Exception:  # noqa: BLE001 - native optional; numpy identical
        pass
    u = np.asarray(u, np.int64)
    i = np.asarray(i, np.int64)
    valid = (i >= 0) & (i < n_items) & (u >= 0) & (u < n_users)
    u, i = u[valid], i[valid]
    key = np.unique(u * n_items + i)
    du = (key // n_items).astype(np.int32)
    return (du, (key % n_items).astype(np.int32),
            np.bincount(du, minlength=n_users).astype(np.int64))


def _gather_indicators(ss, ixs, los, lo_effs_np, block, n_items) -> Indicators:
    """Stacked per-stripe device results → host [I, K] Indicators
    (ragged last stripe sliced; zero-score slots → -1)."""
    idx_parts, score_parts = [], []
    for j, lo in enumerate(los):
        b = min(block, n_items - lo)
        skip = lo - int(lo_effs_np[j])
        score_parts.append(np.asarray(ss[j])[skip:skip + b])
        idx_parts.append(np.asarray(ixs[j])[skip:skip + b])
    score = np.concatenate(score_parts, axis=0)
    idx = np.concatenate(idx_parts, axis=0).astype(np.int32)
    idx[score <= 0] = -1
    return Indicators(idx=idx, score=score.astype(np.float32))


def cco_indicators_multi(
    primary_u: np.ndarray,
    primary_i: np.ndarray,
    secondaries: dict,
    n_users: int,
    n_items: int,
    max_correlators: int = 50,
    llr_threshold: float = 0.0,
    u_chunk: int = 2048,
    item_block: int = 4096,
    mesh=None,
) -> dict:
    """All cross-occurrence indicator matrices of ONE primary event from
    one fused scan over the user ranges (reference: the UR trains Mahout
    SimilarityAnalysis per event-type pair; here the pairs share the
    primary's dedupe, host partition, upload, and per-range membership
    slab — see _cco_count_multi). ``secondaries`` maps name →
    (u, i); passing the primary's OWN arrays (by identity) marks a
    self-pair, which reuses the primary slabs end to end.

    On a multi-device mesh the same fusion shards user ranges over
    DATA_AXIS with psum'd partial counts (the same two dispatches).
    Falls back to per-pair ``cco_indicators`` calls when the fused
    accumulators would not fit the HBM budget (each pair then gets the
    full-vs-striped choice independently). Results are bit-identical to
    per-pair calls either way (exact integer counts; tested)."""
    names = list(secondaries.keys())
    n_sec = len(names)
    n_mesh_dev = int(mesh.devices.size) if mesh is not None else 1
    # fused path budget: all S accumulators together may use HALF the
    # device memory (the single-pair cap allows one accumulator a
    # quarter — same headroom reasoning, S of them share it)
    fused_fits = n_sec * n_items * n_items <= 2 * _full_matrix_elem_cap()
    if n_sec == 0:
        return {}
    if not fused_fits or n_sec == 1:
        return {
            name: cco_indicators(
                primary_u, primary_i, su, si, n_users, n_items,
                max_correlators=max_correlators,
                llr_threshold=llr_threshold, u_chunk=u_chunk,
                item_block=item_block, mesh=mesh)
            for name, (su, si) in secondaries.items()
        }

    # a secondary that is the primary's OWN arrays is the self pair: it
    # reuses the primary's slabs and counts everywhere, and is no event
    # of its own (the primary goes under the index 0, which is no name)
    self_flags = tuple(su is primary_u and si is primary_i
                       for su, si in secondaries.values())
    others = [name for name, is_self in zip(names, self_flags)
              if not is_self]
    prepared = _prepare_events(
        {0: (primary_u, primary_i), **{n: secondaries[n] for n in others}},
        n_users, n_items, u_chunk, n_mesh_dev)
    p_light, p_heavy, n_i = prepared[0]
    n_heavy = p_heavy is not None
    sec_light = [prepared[n][0] for n in others]
    sec_heavy = [prepared[n][1] for n in others] if n_heavy else []
    n_js = [n_i if is_self else prepared[name][2]
            for name, is_self in zip(names, self_flags)]
    k = min(max_correlators, n_items)
    block = min(item_block, n_items)
    los = list(range(0, n_items, block))
    lo_effs_np = np.array([min(lo, n_items - block) for lo in los], np.int32)

    _M_PATH.labels("fused").inc()
    with telemetry.span(
            "cco.device", path="fused", n_sec=n_sec, **_layout_tags(
                p_light + tuple(x for sl in sec_light for x in sl),
                (p_heavy + tuple(x for sh in sec_heavy for x in sh))
                if n_heavy else None)):
        n_i_dev, n_js_dev = jnp.asarray(n_i), jnp.asarray(np.stack(n_js))
        lo_effs, n_total = jnp.asarray(lo_effs_np), jnp.float32(n_users)
        cs = _cco_count_multi(
            p_light, tuple(sec_light), p_heavy if n_heavy else (),
            tuple(sec_heavy), n_items=n_items, u_chunk=u_chunk,
            h_chunk=_HEAVY_RANGE, self_flags=self_flags,
            **(dict(mesh=mesh) if n_mesh_dev > 1 else {}))
        outs = _cco_select(cs, n_js_dev, n_i_dev, lo_effs, n_total,
                           n_items=n_items, block=block, k=k,
                           llr_threshold=llr_threshold)
        del cs
        outs = jax.device_get(outs)
    with telemetry.span("cco.gather"):
        return {
            name: _gather_indicators(ss, ixs, los, lo_effs_np, block,
                                     n_items)
            for name, (ss, ixs) in zip(names, outs)
        }


# -- serving: the indicators resident on the device ---------------------------
#
# A query's score is  score_i = boost_i * sum_e sum_s score_e[i, s] *
# [idx_e[i, s] in history_e].  The forward form of that sum gathers one
# membership bit for every slot of every item (I x K gathers an event type:
# 940M for 9.4M items, 50 correlators and two event types, 2.2 s of the
# gather unit); an index BY CORRELATOR reads, for the rows of the history,
# only the postings that name them. That index is built once, at deploy
# time, from the persisted ``idx`` / ``score`` and stays on the device;
# a query ships its history rows, its rule rows and a few scalars.

_M_UR_QUERIES = telemetry.registry().counter(
    "pio_ur_queries_total",
    "Indicator-scored queries by path: history = postings of the history's "
    "rows summed on the device; backfill = no history row and no query "
    "item, the popularity ranking under the same rules.",
    ("path",))
_M_UR_ROWS = telemetry.registry().counter(
    "pio_ur_history_rows_total",
    "History rows (distinct catalog rows an event type, query items "
    "counted in) that indicator-scored queries shipped to the device."
    ).labels()
_M_UR_POSTINGS = telemetry.registry().counter(
    "pio_ur_postings_read_total",
    "Postings (item, score) that indicator-scored queries read on the "
    "device: the entries of the index that name a row of the history."
    ).labels()

#: history rows an event type a query is padded to, smallest first (the
#: store's read hands back at most 500 events an event type; ``itemSet``
#: adds its own): one executable a step, whatever the history's length.
#: Longer histories take the next power of two.
_HISTORY_LADDER = (16, 128, 1024)
#: ``num`` is rounded up to a step of this ladder (then powers of two), so
#: that clients varying ``num`` share executables.
_NUM_LADDER = (8, 32)
#: how a run of postings is read (my chip run, PR 42: a scalar gather
#: from a 1.88 GB array costs 15-23 ns, a scatter-add 9-10 ns, a
#: contiguous slice nothing). The first `_RUN_HEAD` postings of every run
#: are gathered, all runs at once: most runs are shorter (a correlator is
#: named by 50 rows on average, by 6 in the median). What a run holds
#: beyond them is read as contiguous slices of `_RUN_CHUNK` postings, one a
#: loop step.
_RUN_HEAD = 128
_RUN_CHUNK = 16384


class ResidentIndicators(NamedTuple):
    """The served state of a set of indicators, on the device: per event
    type (``names``, in scoring order) ONE form, an index by correlator.
    ``post_item[e]`` int32 / ``post_score[e]`` float32 hold the event
    type's (item, score) postings sorted by correlator, then by item (the
    slots of a padded row, ``idx < 0``, lie behind the last run and no run
    reaches them); ``offsets[e]`` int32 ``[n_items + 2]`` holds where the
    run of each correlator starts: run c is ``[offsets[c], offsets[c +
    1])``, and the run of the row ``n_items``, which pads a history, is
    empty. ``popularity`` is the float32 backfill ranking, or None."""
    names: tuple
    n_items: int
    post_item: tuple
    post_score: tuple
    offsets: tuple
    popularity: Optional[jax.Array]

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in (
            *self.post_item, *self.post_score, *self.offsets,
            self.popularity) if a is not None)


@functools.partial(jax.jit, static_argnames=("n_items", "k"),
                   donate_argnums=(0, 1))
def _index_by_correlator(idx, score, n_items: int, k: int):
    """One event type's forward slots (flat ``[n_items * k]``, donated)
    as (items, scores, offsets) of `ResidentIndicators`: ONE device sort
    by (correlator, item), and the runs' lengths counted."""
    keys = jnp.where((idx >= 0) & (idx < n_items), idx, n_items)
    counts = jnp.zeros((n_items + 1,), jnp.int32).at[keys].add(1)
    keys, items, scores = jax.lax.sort(
        (keys, jnp.arange(idx.shape[0], dtype=jnp.int32) // k, score),
        num_keys=2)
    del keys
    starts = jnp.cumsum(counts) - counts
    # run n_items (the history's padding row) is empty: it starts and ends
    # where the padded slots begin
    return items, scores, jnp.concatenate([starts, starts[-1:]])


def place_indicators(indicators, popularity=None) -> ResidentIndicators:
    """Put ``indicators`` ({event name: `Indicators`}, in scoring order) on
    the device as an index by correlator, once (deploy / warm-up): an
    event type's forward slots cross and are sorted there, so the host
    sorts nothing. The sort holds three times its operands (11.3 GB for
    9.4M items x 50), so each event type's index waits on the host while
    the next is sorted and goes back at the end; the last one's never
    leaves: the device never holds both forms of the model, nor a sort
    beside an index. Span ``ur.place`` (tags ``events``, ``items``,
    ``bytes``)."""
    names = tuple(indicators)
    n_items = int(next(iter(indicators.values())).idx.shape[0])
    with telemetry.span("ur.place", events=len(names),
                        items=n_items) as sp:
        post_item, post_score, offsets = [], [], []
        for at, ind in enumerate(indicators.values()):
            rows, k = ind.idx.shape
            if rows != n_items:
                raise ValueError("indicators of one model index one catalog")
            if rows * k >= 2 ** 31:
                raise ValueError(f"{rows * k} indicator slots are over what "
                                 f"an int32 posting offset addresses")
            idx = np.asarray(ind.idx, np.int32).ravel()
            score = np.asarray(ind.score, np.float32).ravel()
            if idx.size < _RUN_CHUNK:
                # the tail's slices are `_RUN_CHUNK` long whatever the
                # index holds: a small model's arrays are filled up to one
                fill = (0, _RUN_CHUNK - idx.size)
                idx = np.pad(idx, fill, constant_values=-1)
                score = np.pad(score, fill)
            placed = _index_by_correlator(idx, score, n_items=n_items,
                                          k=max(k, 1))
            # either way the sort has ended before anything else is put
            items, scores, off = (jax.block_until_ready(placed)
                                  if at == len(names) - 1
                                  else jax.device_get(placed))
            post_item.append(items)
            post_score.append(scores)
            offsets.append(off)
        resident = ResidentIndicators(names, n_items, *jax.device_put((
            tuple(post_item), tuple(post_score), tuple(offsets),
            None if popularity is None
            else np.asarray(popularity, np.float32))))
        jax.block_until_ready(resident.offsets)
        sp.tag(bytes=resident.nbytes)
    return resident


@functools.partial(jax.jit, static_argnames=("k",))
def _ur_rank(scores, boost, base, rows, k: int):
    """The query's rules over a score row and the k best of what is left:
    ``boost`` multiplies, ``base`` (True = suppressed) and the packed
    ``rows`` of `ops/topk.suppress_rows` exclude, and only scores over 0
    are answers (the others come back as -inf). One executable a (``k``,
    rule-row step), whatever scored the row: the history's postings or
    the popularity."""
    scores = jnp.where(base, -jnp.inf, scores * boost)
    scores = suppress_rows(scores, rows)
    scores = jnp.where(scores > 0, scores, -jnp.inf)
    block_len = select_block_len(scores.shape[0], k)
    if block_len:
        return select_topk(scores, k, block_len)
    return jax.lax.top_k(scores, k)


def _add_runs(acc, post_item, post_score, offsets, history):
    """``acc`` plus the postings of the runs of ``history`` (int32 ``[H]``
    rows of one event type, padded with the row ``n_items``), and how
    many they were. The first `_RUN_HEAD` postings of every run in ONE
    gather and scatter-add; the rest of the long runs slice by slice,
    `_RUN_CHUNK` postings a loop step, which a step's place among the
    runs' summed slice counts names (no run of no further posting takes
    a step)."""
    starts = offsets[history]
    lens = offsets[history + 1] - starts
    j = jnp.arange(_RUN_HEAD, dtype=jnp.int32)[None, :]
    live = j < lens[:, None]
    source = jnp.where(live, starts[:, None] + j, 0).ravel()
    acc = acc.at[post_item[source]].add(
        jnp.where(live.ravel(), post_score[source], 0.0))

    steps = (jnp.maximum(lens - _RUN_HEAD, 0) + _RUN_CHUNK - 1) // _RUN_CHUNK
    ends = jnp.cumsum(steps)
    lane = jnp.arange(_RUN_CHUNK, dtype=jnp.int32)
    last = post_item.shape[0] - _RUN_CHUNK

    def step(t, acc):
        run = jnp.sum(ends <= t)
        done = _RUN_HEAD + (t - (ends[run] - steps[run])) * _RUN_CHUNK
        lo = starts[run] + done
        at = jnp.minimum(lo, last)       # a slice never passes the end
        live = (lane >= lo - at) & (lane < lo - at + lens[run] - done)
        items = jax.lax.dynamic_slice(post_item, (at,), (_RUN_CHUNK,))
        scores = jax.lax.dynamic_slice(post_score, (at,), (_RUN_CHUNK,))
        return acc.at[jnp.where(live, items, 0)].add(
            jnp.where(live, scores, 0.0))

    return jax.lax.fori_loop(0, ends[-1], step, acc), jnp.sum(lens)


@functools.partial(jax.jit, static_argnames=("n_items",))
def _ur_score(post_item, post_score, offsets, history, n_items: int):
    """``history`` int32 ``[event types, H]`` (padded with the row
    ``n_items``): the postings that name a row of an event type's history
    summed into one float32 score an item (`_add_runs`, an event type
    after the other). Returns (scores[n_items], postings read). One
    executable a step of `_HISTORY_LADDER`."""
    acc = jnp.zeros((n_items,), jnp.float32)
    total = jnp.int32(0)
    for e in range(history.shape[0]):
        acc, n = _add_runs(acc, post_item[e], post_score[e], offsets[e],
                           history[e])
        total += n
    return acc, total


@functools.lru_cache(maxsize=None)
def _no_boost(n_items: int):
    """Device-resident all-ones boost, one per catalog size."""
    return jax.device_put(np.ones((n_items,), np.float32))


@functools.lru_cache(maxsize=None)
def _no_rows(n_items: int):
    """Device-resident packed row lists that suppress nothing."""
    return jax.device_put(pack_rows((), None, ROW_LADDER[0], n_items))


def _step_of(ladder: tuple, n: int) -> int:
    """The first step of ``ladder`` that holds ``n``, or the power of two
    that does where ``n`` is over the ladder's top."""
    return next((s for s in ladder if n <= s),
                1 << max(int(n) - 1, 0).bit_length())


def _rules_on_device(exclude, boost, n_items: int):
    """(boost, base, rows) as the kernels take them. ``exclude`` is what
    `models/_filters.build_exclude` makes: None, a `RowExclude` (its rows
    are packed and put under ``topk.mask_put``, as for the ALS scan), or
    a dense ``bool[n_items]`` host mask (lists over the row ladder's top,
    item dates), put whole under the same span."""
    base, rows = None, _no_rows(n_items)
    if isinstance(exclude, RowExclude):
        base = exclude.base
        if len(exclude.deny) or exclude.allow is not None:
            rows = put_rows(exclude, n_items)
    elif exclude is not None:
        with telemetry.span("topk.mask_put", bytes=exclude.nbytes):
            base = jax.device_put(np.asarray(exclude, bool))
    return (_no_boost(n_items) if boost is None else boost,
            no_exclude_mask(n_items) if base is None else base, rows)


def _rank(scores, rules, k: int):
    """`_ur_rank` of a score row at ``k``'s step of `_NUM_LADDER`."""
    return _ur_rank(scores, *rules,
                    k=min(_step_of(_NUM_LADDER, k), scores.shape[0]))


def _fetch(out, k: int):
    """The answer on the host, cut to ``k``. Span ``topk.wait``: the
    device's queue, the kernel and the readback, as after the ALS scan."""
    with telemetry.span("topk.wait"):
        out = jax.device_get(out)
    return (out[0][:k], out[1][:k]) + tuple(out[2:])


def score_rows(resident: ResidentIndicators, history, k: int,
               exclude=None, boost=None):
    """The k best items for a history, by the resident indicators:
    ``history`` {event name: catalog rows} (an event type the indicators
    lack counts nothing; duplicates count once), ``exclude`` as
    `_rules_on_device` takes it, ``boost`` a resident ``float32[n_items]``
    multiplier or None. Returns (scores[k'], items[k'], postings read) as
    host values, best first; a place beyond the items that scored over 0
    holds -inf.

    Two dispatches, `_ur_score` (the sums) then `_ur_rank` (the rules and
    the selection), and one fetch. Spans ``ur.score`` (tags ``rows``,
    ``step``, ``k``, ``postings``) around ``ur.dispatch`` (both enqueues;
    a first shape's compile shows here) and ``topk.wait``; counters
    ``pio_ur_queries_total{path}``, ``pio_ur_history_rows_total``,
    ``pio_ur_postings_read_total``."""
    n_items = resident.n_items
    k = min(int(k), n_items)
    lists = [np.unique(np.asarray(history.get(name, ()), np.int32))
             for name in resident.names]
    n_rows = sum(map(len, lists))
    step = _step_of(_HISTORY_LADDER, max(map(len, lists)))
    packed = np.full((len(lists), step), n_items, np.int32)
    for e, rows_e in enumerate(lists):
        packed[e, :len(rows_e)] = rows_e
    with telemetry.span("ur.score", rows=n_rows, step=step, k=k) as sp:
        rules = _rules_on_device(exclude, boost, n_items)
        with telemetry.span("ur.dispatch"):
            acc, postings = _ur_score(
                resident.post_item, resident.post_score, resident.offsets,
                packed, n_items=n_items)
            out = _rank(acc, rules, k)
        scores, items, postings = _fetch((*out, postings), k)
        sp.tag(postings=int(postings))
    _M_UR_QUERIES.labels("history").inc()
    _M_UR_ROWS.inc(n_rows)
    _M_UR_POSTINGS.inc(int(postings))
    return scores, items, int(postings)


def popular_rows(resident: ResidentIndicators, k: int, exclude=None,
                 boost=None):
    """The k most popular items under the same rules (the backfill of a
    query without history): (scores[k'], items[k']). Span ``ur.backfill``
    around ``ur.dispatch`` and ``topk.wait``."""
    n_items = resident.n_items
    k = min(int(k), n_items)
    with telemetry.span("ur.backfill", k=k):
        rules = _rules_on_device(exclude, boost, n_items)
        with telemetry.span("ur.dispatch"):
            out = _rank(resident.popularity, rules, k)
        scores, items = _fetch(out, k)
    _M_UR_QUERIES.labels("backfill").inc()
    return scores, items


def compile_ladders(resident: ResidentIndicators, num: int) -> None:
    """Deploy time (a model's ``warm_up``): every executable a query can
    meet, compiled by running it once. The sums, a step of
    `_HISTORY_LADDER` each; the rules and the selection, a step of
    `_NUM_LADDER` (and ``num``'s own) by a step of the rule rows'
    `ops/topk.ROW_LADDER` each, which the backfill shares."""
    def history(step):
        rows = np.arange(min(step, resident.n_items), dtype=np.int32)
        return dict.fromkeys(resident.names, rows)

    for step in _HISTORY_LADDER:
        score_rows(resident, history(step), num)
    for k in sorted({*_NUM_LADDER, _step_of(_NUM_LADDER, num)}):
        for rule_rows in ROW_LADDER:
            # a list of this step's shape: one row over the step below
            exclude = RowExclude(
                None, np.zeros(rule_rows // 8 + 1, np.int32), None)
            score_rows(resident, history(1), k, exclude)
            if resident.popularity is not None:
                popular_rows(resident, k, exclude)
