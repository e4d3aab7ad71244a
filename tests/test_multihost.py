"""Multi-host (multi-process) training end to end.

The distributed communication backend is jax.distributed: a coordination
service over DCN plus XLA collectives (Gloo on the CPU test platform, ICI
on a TPU pod). This test launches TWO separate Python processes, each
seeing 2 local devices, forms the 4-device global mesh across them, runs
the real `train_als` (its shard_map collectives cross the process
boundary), and checks the factors match a single-process run bit-for-bit
(same math, same layout — only the transport differs).

Reference parity: the analog of Spark driver/executor RPC + shuffle
(SURVEY.md §2.10), exercised the way the reference's Docker integration
harness exercises multi-node: real processes on one box.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_workers(out_path, mode, extra_args=()):
    """Start the 2-process jax.distributed worker pair; returns procs."""
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "mh_als_worker.py")
    port = _free_port()
    env_base = {
        **os.environ,
        "PIO_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
        "PIO_NUM_PROCESSES": "2",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "JAX_PLATFORMS": "cpu",
    }
    procs = []
    for pid in range(2):
        env = {**env_base, "PIO_PROCESS_ID": str(pid)}
        procs.append(subprocess.Popen(
            [sys.executable, worker, out_path, mode, *map(str, extra_args)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        ))
    return procs


def _join_workers(procs, timeout=240):
    """Reap the worker pair; never leaks processes and never raises on a
    hung peer (a worker stuck in a collective after its partner died is
    killed and reported as '<timed out>' so the caller can still show
    the partner's log tail)."""
    outs = []
    try:
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout)
                outs.append(out.decode(errors="replace"))
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
                outs.append("<timed out>\n" + out.decode(errors="replace"))
    finally:
        # A deadlocked collective must not leak workers pinning the
        # coordinator port for the rest of the run.
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


def _mh_data():
    rng = np.random.default_rng(11)
    n_users, n_items, nnz = 40, 30, 600
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    r = (rng.integers(1, 11, nnz) / 2.0).astype(np.float32)
    return u, i, r, n_users, n_items


@pytest.mark.parametrize("mode", ["full", "full-ones"])
def test_two_process_training_matches_single_process(tmp_path, mode):
    """Every worker holds the whole dataset (shared-store reads, the
    merged feed of `pio train --num-workers N`) and runs `train_als` on
    the mesh spanning both processes; factors must match the
    single-process run. mode="full-ones": all-ones ratings — both
    processes must pick the binary (value-slab-elided) signature."""
    # No pytest-timeout in this image; the communicate(timeout=240) below
    # is the hang guard.
    out_path = str(tmp_path / "mh_factors.npz")
    procs = _launch_workers(out_path, mode)
    outs = _join_workers(procs)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    assert os.path.exists(out_path), outs[0][-2000:]
    mh = np.load(out_path)

    # Single-process reference on the SAME 4-device layout: the sharded
    # layouts (padding, row->shard assignment) depend only on device
    # count, so factors must agree to float tolerance.
    from incubator_predictionio_tpu.ops.als import ALSParams, train_als
    from incubator_predictionio_tpu.parallel.mesh import mesh_from_devices
    import jax

    u, i, r, n_users, n_items = _mh_data()
    if mode == "full-ones":
        r = np.ones_like(r)
    mesh = mesh_from_devices(devices=jax.devices()[:4])
    ref = train_als(u, i, r, n_users, n_items,
                    ALSParams(rank=4, num_iterations=3, seed=5),
                    mesh=mesh)

    np.testing.assert_allclose(mh["user"], ref.user_factors, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(mh["item"], ref.item_factors, rtol=2e-4, atol=2e-5)


def test_two_process_2d_mesh_matches_single_process(tmp_path):
    """MODEL_AXIS × multi-process composition: a (d, m) = (2, 2) mesh
    SPANNING two processes — factor matrices row-sharded over the model
    axis, each process holding the whole dataset. Must match a
    single-process run on the same mesh shape."""
    out_path = str(tmp_path / "mh2d_factors.npz")
    procs = _launch_workers(out_path, "full2d")
    outs = _join_workers(procs)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    assert os.path.exists(out_path), outs[0][-2000:]
    mh = np.load(out_path)

    from incubator_predictionio_tpu.ops.als import ALSParams, train_als
    from incubator_predictionio_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, mesh_from_devices,
    )
    import jax

    u, i, r, n_users, n_items = _mh_data()
    mesh = mesh_from_devices(
        shape=(2, 2), axis_names=(DATA_AXIS, MODEL_AXIS),
        devices=jax.devices()[:4])
    ref = train_als(u, i, r, n_users, n_items,
                    ALSParams(rank=4, num_iterations=3, seed=5), mesh=mesh)
    np.testing.assert_allclose(mh["user"], ref.user_factors, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(mh["item"], ref.item_factors, rtol=2e-4, atol=2e-5)


def test_two_process_kill_and_resume(tmp_path):
    """Kill both merged-feed trainers mid-run, then resume from the
    last orbax snapshot: the resumed run must finish and match an
    uninterrupted single-process reference (chunked resume is
    bitwise-identical math through the same traced executable)."""
    import time

    ckpt_dir = str(tmp_path / "ckpt")
    out_path = str(tmp_path / "resumed.npz")
    n_iters = 6

    # Phase 1: train with per-iteration snapshots, kill once one exists.
    procs = _launch_workers(str(tmp_path / "phase1.npz"), "full-ckpt",
                            (ckpt_dir, n_iters, 0))
    try:
        deadline = time.time() + 180
        snapshot_seen = False
        while time.time() < deadline:
            if any(p.poll() is not None and p.returncode != 0 for p in procs):
                break  # a worker died on its own — surface its output below
            steps = [d for d in (os.listdir(ckpt_dir)
                                 if os.path.isdir(ckpt_dir) else [])
                     if d.isdigit()]
            if steps:
                snapshot_seen = True
                break
            time.sleep(0.5)
        if not snapshot_seen and any(p.poll() is not None and p.returncode != 0
                                     for p in procs):
            outs = _join_workers(procs, timeout=10)
            raise AssertionError(f"phase-1 worker died:\n{outs[0][-3000:]}\n"
                                 f"{outs[-1][-3000:]}")
        assert snapshot_seen, "no snapshot appeared within 180s"
        time.sleep(0.5)  # let the commit settle past the atomic rename
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        _join_workers(procs, timeout=30)

    # Phase 2: fresh coordinator, resume from the snapshot, run to end.
    procs = _launch_workers(out_path, "full-ckpt",
                            (ckpt_dir, n_iters, 1))
    outs = _join_workers(procs)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"resume worker failed:\n{out[-3000:]}"
    assert os.path.exists(out_path), outs[0][-2000:]
    resumed = np.load(out_path)

    from incubator_predictionio_tpu.ops.als import ALSParams, train_als
    from incubator_predictionio_tpu.parallel.mesh import mesh_from_devices
    import jax

    u, i, r, n_users, n_items = _mh_data()
    mesh = mesh_from_devices(devices=jax.devices()[:4])
    ref = train_als(u, i, r, n_users, n_items,
                    ALSParams(rank=4, num_iterations=n_iters, seed=5),
                    mesh=mesh)
    np.testing.assert_allclose(resumed["user"], ref.user_factors,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(resumed["item"], ref.item_factors,
                               rtol=2e-4, atol=2e-5)
