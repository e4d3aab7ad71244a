"""One supervised gang worker for tests/test_gang_supervisor.py (and
bench_gang.py): merged-feed ALS under parallel/supervisor.py — every
worker holds the whole dataset and runs `train_als` on a mesh spanning
the gang, as `pio train --num-workers N --feed merged` does.

The supervisor provides all the wiring via environment
(PIO_COORDINATOR_ADDRESS / PIO_NUM_PROCESSES / PIO_PROCESS_ID /
PIO_WORKER_HEARTBEAT_FILE / PIO_GANG_WORKER); chaos arrives per worker
through PIO_FAULT_SPEC (`train.sweep:crash:N` SIGKILLs mid-training,
`train.sweep:latency:N:S` slows sweeps so an external SIGSTOP/SIGTERM
can land mid-run deterministically).

Usage: gang_als_worker.py <out.npz> <ckpt_dir> <n_iters> [--resume]

Same data/params as tests/mh_als_worker.py, so the factors are directly
comparable to a single-process `train_als` reference.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

from incubator_predictionio_tpu.parallel.distributed import (  # noqa: E402
    initialize_distributed,
)
from incubator_predictionio_tpu.parallel.supervisor import (  # noqa: E402
    DRAIN_EXIT_CODE,
    GangDrainRequested,
    install_worker_signal_handlers,
)

initialize_distributed()
# AFTER distributed init: jax's coordination service registers XLA's
# preemption-sync SIGTERM handler during initialize — installing ours
# later makes the drain semantics ("checkpoint at the next boundary,
# then exit") win the sigaction instead of orbax's run-to-completion
# preemption sync.
install_worker_signal_handlers()
# No beat here: the first beat comes from the training loop AFTER the
# first sweep (which includes compile) — the supervisor's stall detector
# arms at the first beat, and its init grace covers everything earlier.

import numpy as np  # noqa: E402

from incubator_predictionio_tpu.ops.als import (  # noqa: E402
    ALSParams,
    train_als,
)
from incubator_predictionio_tpu.parallel.mesh import (  # noqa: E402
    mesh_from_devices,
)
from incubator_predictionio_tpu.workflow.checkpoint import (  # noqa: E402
    CheckpointHook,
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
    ckpt_dir = sys.argv[2]
    n_iters = int(sys.argv[3])
    resume = "--resume" in sys.argv[4:]

    u, i, r, n_users, n_items = _data()
    params = ALSParams(rank=4, num_iterations=n_iters, seed=5)
    mesh = mesh_from_devices(devices=jax.devices())

    hook = CheckpointHook(ckpt_dir, every_n=1)
    try:
        out = train_als(u, i, r, n_users, n_items, params, mesh=mesh,
                        checkpoint_hook=hook, resume=resume)
    except GangDrainRequested as e:
        print(f"[worker] drained at step {e.step}", flush=True)
        hook.close()
        return DRAIN_EXIT_CODE
    hook.close()

    if jax.process_index() == 0:
        np.savez(out_path, user=out.user_factors, item=out.item_factors)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
