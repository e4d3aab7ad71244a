"""Phase breakdown of the Similar-Product (config 3) warm train.

VERDICT r3 weak #4: the config-3 warm number sits ~1M ev/s while the
flagship runs 17.9M steady-state on nearly identical device math. This
isolates WHERE the warm seconds go — host layout build (bincount +
plan_layout + native fill_buckets), upload, compile (expected ~0 warm),
and steady-state device iterations — read from train_als's own spans
at the exact bench_templates scale (100k users x 20k items, 5M views,
rank 32 x 10 implicit iterations).

Run on a QUIET host (no concurrent pytest/bench): `python
tools/profile_similar.py [repeats]`.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    repeats = int(sys.argv[1]) if len(sys.argv) > 1 else 2
    from incubator_predictionio_tpu.ops.als import (
        ALSParams, train_als, train_phase_seconds,
    )
    from incubator_predictionio_tpu.workflow.context import (
        enable_compilation_cache,
    )

    enable_compilation_cache()  # also turns compiles into xla.compile spans

    n_users, n_items, nnz = 100_000, 20_000, 5_000_000
    rng = np.random.default_rng(2)
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = (n_items * rng.random(nnz) ** 2).astype(np.int32)
    i = np.minimum(i, n_items - 1)
    r = np.ones(nnz, np.float32)
    params = ALSParams(rank=32, num_iterations=10, reg=0.01,
                       implicit_prefs=True, alpha=1.0, seed=3)

    for attempt in range(repeats):
        since_ns = time.perf_counter_ns()
        t0 = time.perf_counter()
        train_als(u, i, r, n_users=n_users, n_items=n_items, params=params)
        total = time.perf_counter() - t0
        timings = train_phase_seconds(since_ns)
        accounted = sum(timings.values())
        timings["host_prep_seconds"] = total - accounted
        label = "cold" if attempt == 0 else f"warm{attempt}"
        print(f"[{label}] total {total:.3f}s  "
              + "  ".join(f"{k.replace('_seconds', '')}={v:.3f}s"
                          for k, v in sorted(timings.items()))
              + f"  -> {nnz / total:,.0f} ev/s", flush=True)


if __name__ == "__main__":
    main()
