"""ALS kernel tests: bucketed row layout correctness, half-step
equivalence with a dense NumPy reference, convergence, implicit feedback,
and sharding over the 8-device CPU mesh (SURVEY.md §4 device-free CI
trick)."""

import numpy as np
import pytest

from incubator_predictionio_tpu.ops.rowblocks import (
    fill_buckets,
    length_ladder,
    plan_layout,
)
from incubator_predictionio_tpu.ops.als import (
    _INIT_SCRATCH_ROWS,
    ALSParams,
    _fresh_init,
    _zeros_on_device,
    predict_rmse,
    train_als,
)
from incubator_predictionio_tpu.parallel.mesh import (
    DATA_AXIS,
    default_mesh,
    mesh_from_devices,
)


def _toy_ratings(n_users=60, n_items=40, density=0.3, seed=0):
    rng = np.random.default_rng(seed)
    xu = rng.standard_normal((n_users, 4))
    xi = rng.standard_normal((n_items, 4))
    full = xu @ xi.T + 0.01 * rng.standard_normal((n_users, n_items))
    mask = rng.random((n_users, n_items)) < density
    u, i = np.nonzero(mask)
    return u.astype(np.int32), i.astype(np.int32), full[u, i].astype(np.float32)


def _reconstruct_dense(plan, arrs, inv_col_slot, n_rows, n_cols, sentinel):
    """Rebuild the dense rating matrix from the bucketed slabs (tests the
    layout round-trips every entry exactly once, incl. overflow rows)."""
    dense = np.zeros((n_rows, n_cols))
    row_of_slot = np.full(plan.total_slots, -1, np.int64)
    row_of_slot[plan.slot_of_row] = np.arange(plan.n_rows)
    bucket_base = np.concatenate([[0], np.cumsum(plan.bucket_rows)])
    for b, (cols, vals) in enumerate(zip(arrs.cols, arrs.vals)):
        R_b = plan.bucket_rows[b]
        for idx in range(cols.shape[0]):
            shard, rib = divmod(idx, R_b)
            slot = shard * plan.rows_per_shard + bucket_base[b] + rib
            row = row_of_slot[slot]
            for c, v in zip(cols[idx], vals[idx]):
                if c != sentinel:
                    dense[row, inv_col_slot[c]] += v
    Rv = plan.v_rows_per_shard
    for idx in range(arrs.v_cols.shape[0]):
        shard = idx // Rv
        parent_local = plan.v_parent[idx]
        row = row_of_slot[shard * plan.rows_per_shard + parent_local]
        for c, v in zip(arrs.v_cols[idx], arrs.v_vals[idx]):
            if c != sentinel:
                dense[row, inv_col_slot[c]] += v
    return dense


def test_layout_roundtrip():
    u, i, r = _toy_ratings()
    counts_u = np.bincount(u, minlength=60)
    counts_i = np.bincount(i, minlength=40)
    plan_u = plan_layout(counts_u, n_shards=8)
    plan_i = plan_layout(counts_i, n_shards=8)
    arrs = fill_buckets(plan_u, u, i, r, col_slot_map=plan_i.slot_of_row,
                        sentinel=plan_i.total_slots)
    inv = np.full(plan_i.total_slots, -1, np.int64)
    inv[plan_i.slot_of_row] = np.arange(40)
    dense = _reconstruct_dense(plan_u, arrs, inv, 60, 40,
                               plan_i.total_slots)
    ref = np.zeros((60, 40))
    ref[u, i] = r
    np.testing.assert_allclose(dense, ref, rtol=1e-6)
    assert (plan_u.counts_slot[plan_u.slot_of_row] == counts_u).all()


def test_layout_overflow_rows():
    """Rows longer than overflow_len split into virtual rows + remainder
    and still round-trip exactly."""
    rng = np.random.default_rng(1)
    # row 0: 70 entries with overflow_len=32 → 2 virtual + remainder 6
    # row 1: exactly 64 entries → 1 virtual + remainder 32 (never empty)
    # row 2: 3 entries; row 3: empty
    rows = np.concatenate([np.zeros(70), np.ones(64), np.full(3, 2)]).astype(np.int64)
    cols = rng.integers(0, 50, len(rows)).astype(np.int64)
    vals = rng.random(len(rows)).astype(np.float32)
    counts = np.bincount(rows, minlength=4)
    plan = plan_layout(counts, n_shards=2, overflow_len=32)
    assert plan.v_chunks_of_row.tolist() == [2, 1, 0, 0]
    cmap = np.arange(50)  # identity counterpart map
    arrs = fill_buckets(plan, rows, cols, vals, col_slot_map=cmap,
                        sentinel=50)
    inv = np.arange(50)
    dense = _reconstruct_dense(plan, arrs, inv, 4, 50, 50)
    ref = np.zeros((4, 50))
    np.add.at(ref, (rows, cols), vals)
    np.testing.assert_allclose(dense, ref, rtol=1e-6)


@pytest.mark.parametrize("fill_vals", [True, False])
def test_fill_buckets_native_matches_numpy(fill_vals):
    """The C++ single-pass scatter (pio_fill_entries) must be
    bit-identical to the numpy argsort path — including overflow rows,
    multi-shard plans, a local-shard (shard0 > 0) fill, and the
    fill_vals=False (binary-ratings) branch where neither path builds
    value slabs."""
    from incubator_predictionio_tpu import native as pionative

    if not pionative.available():
        pytest.skip("no C++ toolchain")
    rng = np.random.default_rng(7)
    n_rows, n_cols, nnz = 200, 90, 20_000
    row = rng.integers(0, n_rows, nnz)
    col = rng.integers(0, n_cols, nnz)
    row[:3000] = 5  # overflow row (overflow_len=512)
    val = rng.random(nnz).astype(np.float32)
    counts = np.bincount(row, minlength=n_rows)
    cplan = plan_layout(np.bincount(col, minlength=n_cols), 4)
    plan = plan_layout(counts, 4, overflow_len=512)
    kw = dict(fill_vals=fill_vals)

    def flat(a):
        return [*a.cols, a.v_cols, *a.vals, a.v_vals]

    a_np = fill_buckets(plan, row, col, val, cplan.slot_of_row,
                        cplan.total_slots, use_native=False, **kw)
    a_nc = fill_buckets(plan, row, col, val, cplan.slot_of_row,
                        cplan.total_slots, use_native=True, **kw)
    if not fill_vals:
        assert a_np.vals == () and a_nc.vals == ()
        assert a_np.v_vals.size == 0 and a_nc.v_vals.size == 0
    for x, y in zip(flat(a_np), flat(a_nc)):
        assert np.array_equal(x, y)

    # local-shard fill (multi-host contract): only shard 2's rows
    rpl = -(-n_rows // 4)
    m = (row >= 2 * rpl) & (row < 3 * rpl)
    for mode in (False, True):
        a_loc = fill_buckets(plan, row[m], col[m], val[m],
                             cplan.slot_of_row, cplan.total_slots,
                             shard0=2, n_local_shards=1, use_native=mode,
                             **kw)
        if mode:
            for x, y in zip(flat(prev), flat(a_loc)):
                assert np.array_equal(x, y)
        prev = a_loc

    # out-of-shard rows must raise on both paths
    for mode in (False, True):
        with pytest.raises(ValueError):
            fill_buckets(plan, row, col, val, cplan.slot_of_row,
                         cplan.total_slots, shard0=2, n_local_shards=1,
                         use_native=mode, **kw)


def test_length_ladder_shape():
    lad = length_ladder(500)
    assert lad[0] == 8 and (np.diff(lad) > 0).all()
    assert (lad % 8 == 0).all()
    assert lad[-1] >= 500
    # capped at overflow
    assert length_ladder(10**9)[-1] == 2048


def test_plan_m_divisibility():
    counts = np.random.default_rng(0).integers(0, 20, 37)
    plan = plan_layout(counts, n_shards=2, m_div=4)
    assert (2 * plan.rows_per_shard) % 4 == 0
    assert plan.rows_per_shard % 4 == 0


def _numpy_als_step(y, u, i, r, n_users, reg):
    """Dense reference: solve users given item factors (plain lambda)."""
    k = y.shape[1]
    x = np.zeros((n_users, k), dtype=np.float64)
    for uu in range(n_users):
        sel = u == uu
        if not sel.any():
            continue
        yy = y[i[sel]]
        a = yy.T @ yy + reg * np.eye(k)
        b = yy.T @ r[sel]
        x[uu] = np.linalg.solve(a, b)
    return x


def test_half_step_matches_dense_reference():
    """One full train iteration from a fixed init must match the dense
    NumPy normal-equation solve on both sides."""
    u, i, r = _toy_ratings(n_users=30, n_items=20)
    params = ALSParams(rank=4, num_iterations=1, reg=0.1, seed=7)
    out = train_als(u, i, r, 30, 20, params)

    # replicate init: global-row-order draw (layout-independent)
    plan_u = plan_layout(np.bincount(u, minlength=30), 8)
    plan_i = plan_layout(np.bincount(i, minlength=20), 8)
    x0, y0 = _fresh_init(params, plan_u, plan_i, 30, 20)
    y0_global = y0[plan_i.slot_of_row]

    x_ref = _numpy_als_step(y0_global.astype(np.float64), u, i, r, 30, 0.1)
    y_ref = _numpy_als_step(
        x_ref, i, u, r, 20, 0.1
    )  # items solved against fresh users
    np.testing.assert_allclose(out.user_factors, x_ref, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(out.item_factors, y_ref, rtol=2e-3, atol=2e-4)


def test_als_converges():
    u, i, r = _toy_ratings(n_users=80, n_items=50, density=0.4, seed=3)
    params = ALSParams(rank=8, num_iterations=12, reg=0.05, seed=1)
    out = train_als(u, i, r, 80, 50, params)
    rmse = predict_rmse(out, u, i, r)
    assert rmse < 0.15, f"ALS failed to fit training data, rmse={rmse}"


def test_als_lambda_scaling_nratings():
    u, i, r = _toy_ratings(n_users=30, n_items=20)
    params = ALSParams(rank=4, num_iterations=5, reg=0.01,
                       lambda_scaling="nratings")
    out = train_als(u, i, r, 30, 20, params)
    assert np.isfinite(out.user_factors).all()
    assert predict_rmse(out, u, i, r) < 0.5


def test_als_implicit():
    rng = np.random.default_rng(5)
    u = rng.integers(0, 40, 600).astype(np.int32)
    i = rng.integers(0, 30, 600).astype(np.int32)
    r = np.ones(600, dtype=np.float32)  # implicit view counts
    params = ALSParams(rank=8, num_iterations=8, reg=0.1,
                       implicit_prefs=True, alpha=40.0)
    out = train_als(u, i, r, 40, 30, params)
    assert np.isfinite(out.user_factors).all()
    # observed pairs should score higher than random unobserved pairs
    obs = np.einsum("nk,nk->n", out.user_factors[u], out.item_factors[i]).mean()
    ru = rng.integers(0, 40, 600)
    ri = rng.integers(0, 30, 600)
    rnd = np.einsum("nk,nk->n", out.user_factors[ru], out.item_factors[ri]).mean()
    assert obs > rnd


def test_als_on_explicit_submesh():
    """Runs on a 4-device submesh (vs the default 8) — mesh plumbing."""
    import jax

    mesh = mesh_from_devices(devices=jax.devices()[:4])
    u, i, r = _toy_ratings()
    out = train_als(u, i, r, 60, 40, ALSParams(rank=4, num_iterations=3), mesh=mesh)
    assert out.user_factors.shape == (60, 4)
    assert np.isfinite(out.user_factors).all()


def test_als_chunking_is_invariant():
    """entries-per-step chunking (chunk_tiles × block_len) slices bucket
    slabs over ROWS, so it cannot change the math — results must match
    the unchunked run to f32 reduction-order tolerance (batch shape
    changes XLA's accumulation schedule, nothing more)."""
    u, i, r = _toy_ratings(n_users=50, n_items=30, density=0.4, seed=9)
    base = ALSParams(rank=6, num_iterations=3, reg=0.05)
    chunked = ALSParams(rank=6, num_iterations=3, reg=0.05,
                        block_len=8, chunk_tiles=4)  # 32 entries/step
    out_a = train_als(u, i, r, 50, 30, base)
    out_b = train_als(u, i, r, 50, 30, chunked)
    np.testing.assert_allclose(
        out_a.user_factors, out_b.user_factors, rtol=1e-3, atol=1e-5
    )


def test_als_wide_rank_half_step_matches_dense():
    """Rank > 96 exercises the wide-solve routing and the fused chunk
    sizing at large k (on CPU the solve falls back to XLA Cholesky; the
    TPU wide kernel is pinned by interpret-mode tests). One iteration vs
    the dense NumPy normal equations."""
    rng = np.random.default_rng(5)
    n_users, n_items, nnz = 300, 120, 6000
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    key = u.astype(np.int64) * n_items + i
    _, first = np.unique(key, return_index=True)
    u, i = u[first], i[first]
    r = (rng.random(len(u)) * 4 + 1).astype(np.float32)

    from incubator_predictionio_tpu.ops.als import _fresh_init
    from incubator_predictionio_tpu.ops.rowblocks import plan_layout

    params = ALSParams(rank=100, num_iterations=1, reg=0.1, seed=3,
                       block_len=8)
    mesh = mesh_from_devices(devices=__import__("jax").devices("cpu")[:2])
    out = train_als(u, i, r, n_users, n_items, params, mesh=mesh)

    plan_u = plan_layout(np.bincount(u, minlength=n_users), 2)
    plan_i = plan_layout(np.bincount(i, minlength=n_items), 2)
    x0, y0 = _fresh_init(params, plan_u, plan_i, n_users, n_items)
    y0_g = y0[plan_i.slot_of_row].astype(np.float64)

    def np_step(y, rows, cols, vals, n_rows, reg):
        k = y.shape[1]
        x = np.zeros((n_rows, k))
        for rr in range(n_rows):
            sel = rows == rr
            if not sel.any():
                continue
            yy = y[cols[sel]]
            x[rr] = np.linalg.solve(yy.T @ yy + reg * np.eye(k),
                                    yy.T @ vals[sel])
        return x

    x_ref = np_step(y0_g, u, i, r, n_users, 0.1)
    y_ref = np_step(x_ref, i, u, r, n_items, 0.1)
    np.testing.assert_allclose(out.user_factors, x_ref, rtol=5e-3, atol=5e-4)
    # item side solves against bf16-rounded user factors (second half-
    # step compounds the compute-dtype error at k=100): 2e-3 abs bound
    np.testing.assert_allclose(out.item_factors, y_ref, rtol=5e-3, atol=2e-3)


def test_als_overflow_rows_train():
    """A pathologically heavy row (> overflow_len entries) trains and
    matches the dense reference."""
    rng = np.random.default_rng(6)
    n_users, n_items = 12, 2100
    # user 0 rates 2100 items (forces overflow split at 2048); others few
    u0 = np.zeros(2100, np.int64)
    i0 = np.arange(2100, dtype=np.int64)
    u1 = rng.integers(1, n_users, 300)
    i1 = rng.integers(0, n_items, 300)
    u = np.concatenate([u0, u1]).astype(np.int32)
    i = np.concatenate([i0, i1]).astype(np.int32)
    r = rng.random(len(u)).astype(np.float32)
    params = ALSParams(rank=4, num_iterations=1, reg=0.1, seed=2)
    out = train_als(u, i, r, n_users, n_items, params)

    plan_u = plan_layout(np.bincount(u, minlength=n_users), 8)
    plan_i = plan_layout(np.bincount(i, minlength=n_items), 8)
    assert plan_u.v_rows_per_shard > 0  # the overflow path engaged
    x0, y0 = _fresh_init(params, plan_u, plan_i, n_users, n_items)
    x_ref = _numpy_als_step(y0[plan_i.slot_of_row].astype(np.float64),
                            u, i, r, n_users, 0.1)
    np.testing.assert_allclose(out.user_factors, x_ref, rtol=2e-3, atol=2e-4)


# --- the init: what the loop never reads is not materialised ---------------

def _legacy_init(params, plan_u, plan_i, n_users, n_items):
    """The oracle: _fresh_init as it stood before the user block was drawn
    through a scratch (two whole draws, each scaled, cast and scattered)."""
    k = params.rank
    rng = np.random.default_rng(params.seed)
    x0 = np.zeros((plan_u.total_slots, k), np.float32)
    y0 = np.zeros((plan_i.total_slots, k), np.float32)
    x0[plan_u.slot_of_row] = (
        rng.standard_normal((n_users, k)) / np.sqrt(k)).astype(np.float32)
    y0[plan_i.slot_of_row] = (
        rng.standard_normal((n_items, k)) / np.sqrt(k)).astype(np.float32)
    return x0, y0


def _init_case(n_users, k, keep_users):
    n_items = n_users // 2 + 37  # the largest takes two chunks as well
    rng = np.random.default_rng(n_users + k)
    # three shards: none of the row counts divides, so filler slots exist
    plan_u = plan_layout(rng.integers(0, 5, n_users), 3)
    plan_i = plan_layout(rng.integers(1, 9, n_items), 3)
    params = ALSParams(rank=k, seed=11)
    legacy = _legacy_init(params, plan_u, plan_i, n_users, n_items)
    got = _fresh_init(params, plan_u, plan_i, n_users, n_items,
                      keep_users=keep_users)
    return plan_u, plan_i, legacy, got


# below, equal to, and above without being a multiple of the scratch length
_INIT_USERS = (_INIT_SCRATCH_ROWS - 3000, _INIT_SCRATCH_ROWS,
               2 * _INIT_SCRATCH_ROWS + 1234)


@pytest.mark.parametrize("keep_users", [False, True], ids=["dropped", "kept"])
@pytest.mark.parametrize("k", [4, 128])
@pytest.mark.parametrize("n_users", _INIT_USERS)
def test_fresh_init_y0_is_the_one_stream(n_users, k, keep_users):
    """The user block is drawn sample for sample whether kept or dropped:
    y0 comes from where the stream stands after it, bit for bit."""
    _, plan_i, (_, y_legacy), (_, y0) = _init_case(n_users, k, keep_users)
    assert y0.dtype == np.float32 and y0.shape == y_legacy.shape
    assert np.array_equal(y0, y_legacy)
    filler = np.ones(plan_i.total_slots, bool)
    filler[plan_i.slot_of_row] = False
    assert filler.any() and not y0[filler].any()


@pytest.mark.parametrize("keep_users", [False, True], ids=["dropped", "kept"])
@pytest.mark.parametrize("k", [4, 128])
@pytest.mark.parametrize("n_users", _INIT_USERS)
def test_fresh_init_x0_kept_bitwise_or_dropped(n_users, k, keep_users):
    """Kept, the chunks give the x0 of one whole draw, filler slots exactly
    0; dropped, no host array is made and the loop's x0 is zeros born on
    the device, of the same shape and dtype."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    plan_u, _, (x_legacy, _), (x0, _) = _init_case(n_users, k, keep_users)
    if keep_users:
        assert x0.dtype == np.float32
        assert np.array_equal(x0, x_legacy)
        filler = np.ones(plan_u.total_slots, bool)
        filler[plan_u.slot_of_row] = False
        assert filler.any() and not x0[filler].any()
        return
    assert x0 is None
    for n_dev, spec in ((1, P()), (3, P(DATA_AXIS, None))):
        mesh = mesh_from_devices(devices=jax.devices()[:n_dev])
        sharding = NamedSharding(mesh, spec)
        z = _zeros_on_device(x_legacy.shape, sharding)
        assert z.shape == x_legacy.shape and z.dtype == np.float32
        assert z.sharding.is_equivalent_to(sharding, z.ndim)
        assert not np.asarray(z).any()


def _init_spans(since_ns):
    from incubator_predictionio_tpu.common import telemetry

    return [s.tags for s in telemetry.spans_snapshot()
            if s.name == "als.init" and s.t0_ns >= since_ns]


@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
@pytest.mark.parametrize("n_iters", [1, 3])
def test_train_never_reads_x0(monkeypatch, n_iters, implicit):
    """The same loop handed the legacy random x0 or the zeros gives the
    same factors, bit for bit: a sweep overwrites x before it reads it
    (implicit mode's first half-step needs YᵀY of y0 only)."""
    import time

    from incubator_predictionio_tpu.ops import als

    u, i, r = _toy_ratings(n_users=50, n_items=30, density=0.3, seed=4)
    if implicit:
        r = np.ones_like(r)
    params = ALSParams(rank=8, num_iterations=n_iters, reg=0.05, seed=5,
                       implicit_prefs=implicit, alpha=10.0)
    since = time.perf_counter_ns()
    zeros = train_als(u, i, r, 50, 30, params)
    assert _init_spans(since) == [{"users": "dropped", "overlap": "layout"}]

    handed = []

    def legacy(params, plan_u, plan_i, n_users, n_items, keep_users=True):
        x0, y0 = _legacy_init(params, plan_u, plan_i, n_users, n_items)
        handed.append(x0)
        return x0, y0

    # in turn, the init is _fresh_init's call (the worker has its own)
    monkeypatch.setattr(als, "_fresh_init", legacy)
    monkeypatch.setenv("PIO_PIPELINE", "off")
    random = train_als(u, i, r, 50, 30, params)
    assert len(handed) == 1 and handed[0].any()
    assert np.array_equal(zeros.user_factors, random.user_factors)
    assert np.array_equal(zeros.item_factors, random.item_factors)


@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_train_of_no_iteration_returns_the_legacy_init(implicit):
    """num_iterations == 0: the result IS the init, so the user block is
    kept, and equals the draw in global row order."""
    import time

    u, i, r = _toy_ratings(n_users=50, n_items=30, density=0.3, seed=4)
    params = ALSParams(rank=8, num_iterations=0, seed=5,
                       implicit_prefs=implicit)
    since = time.perf_counter_ns()
    out = train_als(u, i, r, 50, 30, params)
    assert _init_spans(since) == [{"users": "kept", "overlap": "none"}]
    rng = np.random.default_rng(5)
    x = (rng.standard_normal((50, 8)) / np.sqrt(8)).astype(np.float32)
    y = (rng.standard_normal((30, 8)) / np.sqrt(8)).astype(np.float32)
    assert np.array_equal(out.user_factors, x)
    assert np.array_equal(out.item_factors, y)


# --- the init beside the layout: same stream, same factors ------------------

@pytest.mark.parametrize("nan_guard", [False, True], ids=["fused", "guarded"])
@pytest.mark.parametrize("n_dev", [1, 2], ids=["one-device", "mesh2"])
@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_overlapped_init_gives_the_factors_of_the_init_in_turn(
        monkeypatch, implicit, n_dev, nan_guard):
    """The worker's draw beside the layout and PIO_PIPELINE=off's init
    after it: the same factors, bit for bit, on one device and a mesh,
    through the fused loop and the guarded one."""
    import time

    import jax

    u, i, r = _toy_ratings(n_users=70, n_items=45, density=0.3, seed=8)
    if implicit:
        r = np.ones_like(r)
    params = ALSParams(rank=8, num_iterations=2, reg=0.05, seed=9,
                       implicit_prefs=implicit, alpha=10.0)
    mesh = mesh_from_devices(devices=jax.devices()[:n_dev])
    monkeypatch.delenv("PIO_PIPELINE", raising=False)
    since = time.perf_counter_ns()
    beside = train_als(u, i, r, 70, 45, params, mesh=mesh,
                       nan_guard=nan_guard)
    assert _init_spans(since) == [{"users": "dropped", "overlap": "layout"}]
    monkeypatch.setenv("PIO_PIPELINE", "off")
    since = time.perf_counter_ns()
    in_turn = train_als(u, i, r, 70, 45, params, mesh=mesh,
                        nan_guard=nan_guard)
    assert _init_spans(since) == [{"users": "dropped", "overlap": "none"}]
    assert np.array_equal(beside.user_factors, in_turn.user_factors)
    assert np.array_equal(beside.item_factors, in_turn.item_factors)
    assert beside.item_factors.any()


@pytest.mark.parametrize("k", [4, 128])
def test_the_workers_rows_are_fresh_inits_y0(k):
    """What the worker draws with no plan, placed into the plan's slots,
    is _fresh_init's y0 bit for bit, at more rows a side than the scratch
    holds (so both blocks cross a chunk boundary)."""
    from incubator_predictionio_tpu.ops.als import _InitAhead

    n_users = 2 * _INIT_SCRATCH_ROWS + 1234
    n_items = _INIT_SCRATCH_ROWS + 777
    rng = np.random.default_rng(k)
    plan_u = plan_layout(rng.integers(0, 5, n_users), 3)
    plan_i = plan_layout(rng.integers(1, 9, n_items), 3)
    params = ALSParams(rank=k, seed=11)
    _, want = _fresh_init(params, plan_u, plan_i, n_users, n_items,
                          keep_users=False)
    _, legacy = _legacy_init(params, plan_u, plan_i, n_users, n_items)
    with _InitAhead(params, n_users, n_items) as init:
        got = init.y0(plan_i)
    assert got.dtype == np.float32 and np.array_equal(got, want)
    assert np.array_equal(got, legacy)


def _pio_threads():
    import threading

    return [t.name for t in threading.enumerate()
            if t.name.startswith("pio-")]


def test_a_failed_layout_stops_and_joins_the_worker(monkeypatch):
    """plan_and_fill_both raises while the worker is still in the users'
    block: the flag ends the draw at its next chunk, the thread is joined
    before the exception leaves train_als, and the exception is the
    layout's own."""
    import time

    from incubator_predictionio_tpu.ops import als

    drawn = []
    real = als._init_stream

    def counting(params, blocks, stop=None):
        done = real(params, blocks, stop)
        drawn.append(done)
        return done

    def broken(*a, **kw):
        time.sleep(0.05)     # the worker is well into the users' block
        raise RuntimeError("layout broke")

    monkeypatch.setattr(als, "_init_stream", counting)
    monkeypatch.setattr(als, "plan_and_fill_both", broken)
    monkeypatch.delenv("PIO_PIPELINE", raising=False)
    u, i, r = _toy_ratings(n_users=50, n_items=30, density=0.3, seed=4)
    # 400 chunks of users: seconds of drawing if nothing stopped it
    n_users = 400 * _INIT_SCRATCH_ROWS
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="layout broke"):
        train_als(u, i, r, n_users, 30,
                  ALSParams(rank=128, num_iterations=2, seed=5))
    assert _pio_threads() == []
    assert drawn == [False]              # stopped, not run to its end
    assert time.perf_counter() - t0 < 5.0


def test_the_workers_own_exception_leaves_train_als(monkeypatch):
    """An exception inside the worker's draw is re-raised at the join, as
    itself, and no thread is left."""
    from incubator_predictionio_tpu.ops import als

    def broken(params, blocks, stop=None):
        raise MemoryError("no room for the rows")

    monkeypatch.setattr(als, "_init_stream", broken)
    monkeypatch.delenv("PIO_PIPELINE", raising=False)
    u, i, r = _toy_ratings(n_users=50, n_items=30, density=0.3, seed=4)
    with pytest.raises(MemoryError, match="no room for the rows"):
        train_als(u, i, r, 50, 30, ALSParams(rank=8, num_iterations=2))
    assert _pio_threads() == []
