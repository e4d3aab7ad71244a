"""Driver-condition tests for __graft_entry__.

A mesh can be built over CPU devices in a process whose DEFAULT backend
is a TPU. Round 1 failed exactly there: the Pallas solve kernel was
auto-selected from ``jax.default_backend()`` and crashed with "Only
interpret mode is supported on CPU backend". These tests pin the
contract: kernel selection follows the MESH's platform, never the
process default.
"""

import sys
import os

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.parametrize("n_devices", [8, 4])
def test_dryrun_multichip_runs(n_devices):
    """The exact entry point the driver calls, at the driver's size —
    and at the size of a four-chip host (where the heavy-item fixture
    used to ask 2,000 users for 2,500 distinct raters)."""
    import __graft_entry__

    __graft_entry__.dryrun_multichip(n_devices)


def test_dryrun_multichip_refuses_too_few_devices():
    """No silent switch to another platform: fewer devices than asked
    for is an error naming the platform JAX selected."""
    import __graft_entry__

    with pytest.raises(RuntimeError, match="need 64 devices, the cpu"):
        __graft_entry__.dryrun_multichip(64)


def test_entry_compiles():
    import jax

    import __graft_entry__

    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)


def test_train_als_cpu_mesh_with_tpu_default_backend(monkeypatch):
    """default_backend()=="tpu", mesh is CPU.

    conftest puts the test process on the CPU platform, which on round-1
    code silently disabled the Pallas path and masked the failure. Here
    default_backend() is forced to say "tpu"; train_als must still run
    pure-XLA because the MESH devices are CPU.
    """
    import jax

    from incubator_predictionio_tpu.ops.als import ALSParams, train_als
    from incubator_predictionio_tpu.parallel.mesh import mesh_from_devices

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.default_backend() == "tpu"  # the lie is in place

    mesh = mesh_from_devices(devices=jax.devices("cpu")[:8])
    rng = np.random.default_rng(0)
    nnz = 320
    u = rng.integers(0, 32, nnz).astype(np.int32)
    i = rng.integers(0, 24, nnz).astype(np.int32)
    r = rng.random(nnz).astype(np.float32)
    out = train_als(
        u, i, r, 32, 24,
        ALSParams(rank=8, num_iterations=1, block_len=8, chunk_tiles=2),
        mesh=mesh,
    )
    assert np.isfinite(out.user_factors).all()
    assert np.isfinite(out.item_factors).all()


def test_spd_solve_explicit_use_pallas_false_ignores_backend(monkeypatch):
    """batched_spd_solve(use_pallas=False) must never touch pallas_call."""
    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops.pallas_kernels import batched_spd_solve

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(1)
    m = rng.standard_normal((4, 8, 8)).astype(np.float32)
    a = np.einsum("nij,nkj->nik", m, m) + 8 * np.eye(8, dtype=np.float32)
    b = rng.standard_normal((4, 8)).astype(np.float32)
    x = np.asarray(batched_spd_solve(jnp.asarray(a), jnp.asarray(b),
                                     use_pallas=False))
    np.testing.assert_allclose(a @ x[..., None], b[..., None], rtol=2e-4,
                               atol=2e-4)
