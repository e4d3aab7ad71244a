"""The whole retrain's share of the chip's bf16 peak: the operations that the
configuration's sweeps need (work.als_sweep_flops) over the mean wall of the
window's run_train calls. Host work and idle time are in the denominator: this
is the step's MFU, not a kernel's. Source: the harness's span, shapes."""

import work


def read(record):
    walls = record.window_span_seconds("run_train")
    if not walls or not record.peaks:
        return None
    c = record.config
    flops = work.als_sweep_flops(c["n_ratings"], c["n_users"], c["n_items"],
                                 c["rank"])["total"] * c["numIterations"]
    wall = sum(walls) / len(walls)
    return 100.0 * flops / wall / record.peaks["bf16_flops_per_s"]
