"""The whole retrain's share of the chip's peak: the least time the chip could
take for the train's count matrices (work_cco.py: the larger of the needed
operations over the int8 peak and the needed bytes over the HBM peak) over the
mean wall of the window's run_train calls. Host work, idle time, scoring and
selection are all in the denominator: this is the step's share, not a
kernel's. Source: the harness's span, the data's own counts."""

import bench_ur_engine
import work_cco


def read(record):
    walls = record.window_span_seconds("run_train")
    work = bench_ur_engine.INPUTS.get(f"events-{record.seed}", {}).get("work")
    if not walls or not work or not record.peaks:
        return None
    least = work_cco.roofline_seconds(work, record.peaks)["seconds"]
    return 100.0 * least * len(walls) / sum(walls)
