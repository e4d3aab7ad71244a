"""Worker for test_multihost.py: one training process of a 2-process
jax.distributed run. Trains the same tiny ALS problem over the GLOBAL
mesh and (process 0) writes the factors for the parent to compare.

Every mode is the merged feed `pio train --num-workers N` runs: each
worker holds the whole dataset (shared-store reads) and calls `train_als`
on a mesh spanning both processes.

Modes (argv[2]):
  full       — a 1-D data mesh
  full-ones  — a 1-D data mesh, all-ones ratings (binary signature)
  full2d     — a 2-D (d, m) ALX mesh: MODEL_AXIS factor sharding across
               the two processes
  full-ckpt  — a 1-D data mesh with a CheckpointHook saving every
               iteration; argv[3]=ckpt_dir, argv[4]=n_iters,
               argv[5]=resume(0|1). Used by the kill-and-resume test.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

from incubator_predictionio_tpu.parallel.distributed import (  # noqa: E402
    initialize_distributed,
)

initialize_distributed()

import numpy as np  # noqa: E402

from incubator_predictionio_tpu.ops.als import (  # noqa: E402
    ALSParams,
    train_als,
)
from incubator_predictionio_tpu.parallel.mesh import (  # noqa: E402
    DATA_AXIS,
    MODEL_AXIS,
    mesh_from_devices,
)


def _data(seed=11):
    rng = np.random.default_rng(seed)
    n_users, n_items, nnz = 40, 30, 600
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    r = (rng.integers(1, 11, nnz) / 2.0).astype(np.float32)
    return u, i, r, n_users, n_items


def main() -> int:
    out_path = sys.argv[1]
    mode = sys.argv[2] if len(sys.argv) > 2 else "full"
    u, i, r, n_users, n_items = _data()
    params = ALSParams(rank=4, num_iterations=3, seed=5)

    if mode == "full":
        mesh = mesh_from_devices(devices=jax.devices())
        out = train_als(u, i, r, n_users, n_items, params, mesh=mesh)
    elif mode == "full-ones":
        # All-ones ratings: every process must pick the binary
        # (value-slab-elided) jit signature and the elided global
        # assembly must match the single-process result.
        r = np.ones_like(r)
        mesh = mesh_from_devices(devices=jax.devices())
        out = train_als(u, i, r, n_users, n_items, params, mesh=mesh)
    elif mode == "full2d":
        # 2-D (d, m) = (2, 2) mesh spanning both processes: each process
        # owns one data shard AND the factor matrices are MODEL_AXIS
        # row-sharded (the ALX layout).
        mesh = mesh_from_devices(
            shape=(2, 2), axis_names=(DATA_AXIS, MODEL_AXIS),
            devices=jax.devices())
        out = train_als(u, i, r, n_users, n_items, params, mesh=mesh)
    elif mode == "full-ckpt":
        from incubator_predictionio_tpu.workflow.checkpoint import CheckpointHook

        ckpt_dir = sys.argv[3]
        n_iters = int(sys.argv[4])
        resume = sys.argv[5] == "1"
        params = ALSParams(rank=4, num_iterations=n_iters, seed=5)
        mesh = mesh_from_devices(devices=jax.devices())
        hook = CheckpointHook(ckpt_dir, every_n=1)
        out = train_als(u, i, r, n_users, n_items, params, mesh=mesh,
                        checkpoint_hook=hook, resume=resume)
        hook.close()
    else:
        raise SystemExit(f"unknown mode {mode}")

    if jax.process_index() == 0:
        np.savez(out_path, user=out.user_factors, item=out.item_factors)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
