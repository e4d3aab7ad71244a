"""Packed slab transfers + the device-resident slab cache.

Single-device meshes upload the layout's per-bucket slabs as 2-3
dtype-grouped buffers and unpack them as static slices inside the
jitted loop (ops/als.py _pack_flat: few large transfers instead of
~70 small ones). These tests pin:

- numerical identity: the packed single-device path solves the same
  problem as the per-slab multi-device path (same factors within
  reduction-order tolerance);
- the content-hash device cache: repeat trains over identical data
  reuse device buffers (no re-upload), changed data misses, and a
  changed regularization re-uploads only the (tiny) lam slab while the
  big index slabs still hit.
"""

import numpy as np
import jax
import pytest

from incubator_predictionio_tpu.ops import als as als_mod
from incubator_predictionio_tpu.ops.als import ALSParams, train_als
from incubator_predictionio_tpu.parallel.mesh import (
    default_mesh, mesh_from_devices,
)


def _data(nnz=20_000, n_users=500, n_items=200, seed=0, binary=False):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    r = (np.ones(nnz, np.float32) if binary
         else (rng.random(nnz).astype(np.float32) * 4 + 1))
    return u, i, r


@pytest.mark.parametrize("binary", [False, True])
def test_packed_single_device_matches_multi_device(binary):
    u, i, r = _data(binary=binary)
    params = ALSParams(rank=8, num_iterations=3, reg=0.1, seed=1,
                       implicit_prefs=binary, alpha=1.0,
                       compute_dtype="float32")
    m1 = mesh_from_devices(devices=[jax.devices()[0]])
    assert m1.devices.size == 1  # the packed path
    f1 = train_als(u, i, r, n_users=500, n_items=200, params=params,
                   mesh=m1)
    f8 = train_als(u, i, r, n_users=500, n_items=200, params=params,
                   mesh=default_mesh())
    np.testing.assert_allclose(f1.user_factors, f8.user_factors,
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(f1.item_factors, f8.item_factors,
                               rtol=2e-3, atol=2e-4)


def test_device_slab_cache_hits_and_misses(monkeypatch):
    als_mod._dev_buf_cache.clear()
    als_mod._dev_buf_cache_order.clear()
    puts = []
    real_put = jax.device_put

    def counting_put(x, target=None):
        puts.append(np.asarray(x).nbytes if hasattr(x, "nbytes") else 0)
        return real_put(x, target)

    monkeypatch.setattr(als_mod.jax, "device_put", counting_put)
    u, i, r = _data()
    params = ALSParams(rank=8, num_iterations=2, reg=0.1, seed=1,
                       compute_dtype="float32")
    m1 = mesh_from_devices(devices=[jax.devices()[0]])

    train_als(u, i, r, n_users=500, n_items=200, params=params, mesh=m1)
    n_first = len(puts)
    assert n_first > 0

    # identical data + params: every slab hits; only y0 is re-put (the x0
    # of a train that runs a sweep is zeros born on the device)
    puts.clear()
    train_als(u, i, r, n_users=500, n_items=200, params=params, mesh=m1)
    assert len(puts) == 1  # the item init y0, nothing else

    # changed reg: the lam slab (small f4) misses, index slabs hit
    puts.clear()
    params2 = ALSParams(rank=8, num_iterations=2, reg=0.5, seed=1,
                        compute_dtype="float32")
    train_als(u, i, r, n_users=500, n_items=200, params=params2, mesh=m1)
    assert len(puts) == 2  # y0 and the re-hashed f4 buffer

    # changed ratings: the value-carrying buffer misses too
    puts.clear()
    r2 = r.copy()
    r2[0] += 1.0
    train_als(u, i, r2, n_users=500, n_items=200, params=params, mesh=m1)
    assert len(puts) >= 2

    # PIO_ALS_DEVICE_CACHE=0 disables caching entirely
    als_mod._dev_buf_cache.clear()
    als_mod._dev_buf_cache_order.clear()
    monkeypatch.setenv("PIO_ALS_DEVICE_CACHE", "0")
    puts.clear()
    train_als(u, i, r, n_users=500, n_items=200, params=params, mesh=m1)
    first = len(puts)
    puts.clear()
    train_als(u, i, r, n_users=500, n_items=200, params=params, mesh=m1)
    assert len(puts) == first  # no reuse
    assert not als_mod._dev_buf_cache


def test_device_slab_cache_evicts_over_budget(monkeypatch):
    als_mod._dev_buf_cache.clear()
    als_mod._dev_buf_cache_order.clear()
    monkeypatch.setattr(als_mod, "_DEV_BUF_CACHE_BYTES", 1024)
    dev = jax.devices()[0]
    a = np.arange(200, dtype=np.int32)      # 800 B
    b = np.arange(100, dtype=np.int32)      # 400 B
    als_mod._cached_dev_put(a, dev)
    als_mod._cached_dev_put(b, dev)         # 1200 B > 1024 → evict a
    assert len(als_mod._dev_buf_cache) == 1
    # the survivor is b
    ((key, _arr),) = als_mod._dev_buf_cache.items()
    assert key[2] == b.shape


def test_executable_cache_survives_candidate_sweeps():
    """Eval sweeps vary reg / iterations / seed per candidate; none of
    those shape the compiled program (reg flows in as the lam data,
    n_iters is a traced operand, seed is host init), so the train-fn
    cache must serve ONE entry across the sweep — recompiling per
    candidate was a multi-second tax per eval point."""
    als_mod._train_fn_cache.clear()
    u, i, r = _data()
    m1 = mesh_from_devices(devices=[jax.devices()[0]])
    base = dict(rank=8, compute_dtype="float32")
    for reg, iters, seed in [(0.1, 2, 1), (0.5, 2, 1), (0.1, 3, 2),
                             (0.9, 1, 7)]:
        train_als(u, i, r, n_users=500, n_items=200,
                  params=ALSParams(reg=reg, num_iterations=iters,
                                   seed=seed, **base), mesh=m1)
    assert len(als_mod._train_fn_cache) == 1
    # a shaping field (rank) DOES key a new executable
    train_als(u, i, r, n_users=500, n_items=200,
              params=ALSParams(rank=16, num_iterations=1,
                               compute_dtype="float32"), mesh=m1)
    assert len(als_mod._train_fn_cache) == 2
    # and regularization actually took effect across the sweep
    f_lo = train_als(u, i, r, n_users=500, n_items=200,
                     params=ALSParams(reg=0.001, num_iterations=3,
                                      **base), mesh=m1)
    f_hi = train_als(u, i, r, n_users=500, n_items=200,
                     params=ALSParams(reg=50.0, num_iterations=3,
                                      **base), mesh=m1)
    assert (np.linalg.norm(f_hi.user_factors)
            < 0.5 * np.linalg.norm(f_lo.user_factors))


def test_device_slab_cache_is_per_device():
    """--parallel-candidates gives each worker its own single-device
    mesh; the content-hash cache keys on the DEVICE too, so candidate
    A's slabs on device 0 are never handed to candidate B training on
    device 1 (a cross-device hit would either crash placement or
    silently move the train). Both devices end up with their own
    cached copies and identical results."""
    als_mod._dev_buf_cache.clear()
    als_mod._dev_buf_cache_order.clear()
    devs = jax.devices()
    if len(devs) < 2:
        import pytest as _pytest

        _pytest.skip("needs >=2 devices (conftest provides 8 virtual)")
    u, i, r = _data()
    params = ALSParams(rank=8, num_iterations=2, reg=0.1, seed=1,
                       compute_dtype="float32")
    f0 = train_als(u, i, r, n_users=500, n_items=200, params=params,
                   mesh=mesh_from_devices(devices=[devs[0]]))
    n_after_first = len(als_mod._dev_buf_cache)
    assert n_after_first > 0
    f1 = train_als(u, i, r, n_users=500, n_items=200, params=params,
                   mesh=mesh_from_devices(devices=[devs[1]]))
    # device 1 missed device 0's entries: the cache grew by the same
    # slab count again, keyed to the second device
    assert len(als_mod._dev_buf_cache) == 2 * n_after_first
    dev_ids = {k[3] for k in als_mod._dev_buf_cache}
    assert dev_ids == {devs[0].id, devs[1].id}
    np.testing.assert_array_equal(f0.user_factors, f1.user_factors)
