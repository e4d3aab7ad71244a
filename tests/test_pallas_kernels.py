"""Pallas kernel parity tests (interpret mode on the CPU test platform).

The compiled path is exercised on real TPU by bench.py; here the same
kernel body runs under the Pallas interpreter against the XLA Cholesky
reference (SURVEY.md §4: device-free CI via the forced-CPU platform).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from incubator_predictionio_tpu.ops.pallas_kernels import (  # noqa: E402
    _solve_reference,
    batched_spd_solve,
)


def _random_spd(n, k, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, k, k)).astype(np.float32) * scale
    a = np.einsum("nij,nkj->nik", m, m) + np.eye(k, dtype=np.float32)
    b = rng.standard_normal((n, k)).astype(np.float32)
    return a, b


@pytest.mark.parametrize(
    "n,k", [(5, 10), (300, 32), (130, 7), (1, 1), (513, 16),
            # k=80: the lanes path's widest slab (C=128, kp=80);
            # k=128 and k=100 (kp rounds to 104): the wide manual-DMA
            # path, with and without k-padding
            (40, 80), (24, 128), (9, 100)])
def test_interpret_matches_cholesky(n, k):
    a, b = _random_spd(n, k, seed=n + k)
    x_ref = np.asarray(_solve_reference(jnp.asarray(a), jnp.asarray(b)))
    x_pal = np.asarray(
        batched_spd_solve(jnp.asarray(a), jnp.asarray(b),
                          use_pallas=True, interpret=True)
    )
    np.testing.assert_allclose(x_pal, x_ref, rtol=2e-4, atol=2e-4)


def test_non_multiple_batch_padding():
    # Batch sizes that straddle the 512-slab boundary (a silent-truncation
    # regression guard: 138496 = 270.5 slabs of 512 once exposed exactly
    # this bug on hardware).
    for n in (511, 513, 1025):
        a, b = _random_spd(n, 8, seed=n)
        x_ref = np.asarray(_solve_reference(jnp.asarray(a), jnp.asarray(b)))
        x_pal = np.asarray(
            batched_spd_solve(jnp.asarray(a), jnp.asarray(b),
                              use_pallas=True, interpret=True)
        )
        np.testing.assert_allclose(x_pal, x_ref, rtol=2e-4, atol=2e-4)


def test_auto_select_falls_back_off_tpu():
    # On the CPU test platform the auto path must use the XLA reference.
    a, b = _random_spd(64, 12, seed=3)
    x = np.asarray(batched_spd_solve(jnp.asarray(a), jnp.asarray(b)))
    x_ref = np.asarray(_solve_reference(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(x, x_ref, rtol=1e-5, atol=1e-5)


def test_solve_inside_jit_and_grad_free_context():
    a, b = _random_spd(40, 16, seed=9)

    @jax.jit
    def f(a, b):
        return batched_spd_solve(a, b, use_pallas=True, interpret=True)

    x = np.asarray(f(jnp.asarray(a), jnp.asarray(b)))
    x_ref = np.asarray(_solve_reference(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(x, x_ref, rtol=2e-4, atol=2e-4)


def _als_systems(n, k, c, lam, seed, dense=False):
    """Normal equations as ALS builds them: a rank-c Gram (c ratings a
    row) plus the λ·c ridge, and optionally a dense YᵀY (the implicit
    path's shared term)."""
    rng = np.random.default_rng(seed)
    y = (rng.standard_normal((n, c, k)) / np.sqrt(k)).astype(np.float32)
    a = np.einsum("nci,ncj->nij", y, y) + lam * c * np.eye(k, dtype=np.float32)
    if dense:
        g = (rng.standard_normal((4 * k, k)) / np.sqrt(k)).astype(np.float32)
        a = a + (g.T @ g)[None]
    b = rng.standard_normal((n, k)).astype(np.float32)
    return a, b


def _rel_err_vs_float64(a, b):
    x = np.asarray(batched_spd_solve(jnp.asarray(a), jnp.asarray(b),
                                     use_pallas=True, interpret=True))
    ref = np.linalg.solve(a.astype(np.float64),
                          b.astype(np.float64)[..., None])[..., 0]
    return np.linalg.norm(x - ref, axis=1) / np.linalg.norm(ref, axis=1)


@pytest.mark.parametrize("c,lam,dense", [(1, 0.01, False), (8, 0.1, False),
                                         (64, 0.01, False), (8, 0.01, True)])
def test_rank128_als_systems_match_float64(c, lam, dense):
    # the wide path's blocked elimination on the systems it solves in
    # training, held to a float64 solve
    a, b = _als_systems(6, 128, c, lam, seed=c, dense=dense)
    assert _rel_err_vs_float64(a, b).max() < 2e-5


@pytest.mark.parametrize("n,k", [(20, 8), (20, 16), (200, 128)],
                         ids=["one-block", "two-blocks", "two-wide-slabs"])
def test_blocks_and_slabs_match_float64(n, k):
    # k = 8 is one block of pivots, k = 16 two; 200 systems at k = 128 are
    # two 128-wide slabs, the second padded with identity systems
    a, b = _als_systems(n, k, 4, 0.05, seed=n + k)
    assert _rel_err_vs_float64(a, b).max() < 2e-5


def test_solve_path_selection():
    from incubator_predictionio_tpu.ops.pallas_kernels import solve_path

    assert solve_path(128, "tpu") == "pallas"
    assert solve_path(10, "tpu") == "pallas"
    assert solve_path(129, "tpu") == "cholesky"
    assert solve_path(128, "cpu") == "cholesky"


def test_als_loop_span_names_the_solve():
    from incubator_predictionio_tpu.common import telemetry
    from incubator_predictionio_tpu.ops.als import ALSParams, train_als
    from incubator_predictionio_tpu.parallel.mesh import mesh_from_devices

    rng = np.random.default_rng(45)
    u = rng.integers(0, 12, 80).astype(np.int32)
    i = rng.integers(0, 9, 80).astype(np.int32)
    r = rng.random(80).astype(np.float32)
    mesh = mesh_from_devices(devices=jax.devices("cpu")[:1])
    train_als(u, i, r, 12, 9, ALSParams(rank=4, num_iterations=1), mesh=mesh)
    loop = [s for s in telemetry.spans_snapshot() if s.name == "als.loop"][-1]
    assert loop.tags["solve"] == "cholesky"  # the CPU mesh's solve
