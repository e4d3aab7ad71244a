"""The counting scans' share of their roofline: the least time the chip could
take for the traced train's count matrices (work_cco.py, from the data alone:
the larger of 2 x sum_u d_p(u) d_s(u) operations a pair over the int8 peak and
the events' bytes plus one write and one read of each [I, I] int32 matrix
over the HBM peak; the bytes bind) over the device time of the counting
executable (``_cco_count_multi``). The dense kernel does 2 U I^2 operations a pair and
moves every matrix once a user range, so this reads low by design: it is the
room a sparser or triangle-only kernel has. Source: the device trace's
module line."""

import bench_ur_engine
import cco_spans
import work_cco


def read(record):
    spent = cco_spans.count_seconds(record)
    work = bench_ur_engine.INPUTS.get(f"events-{record.seed}", {}).get("work")
    if spent is None or not work or not record.peaks:
        return None
    least = work_cco.roofline_seconds(work, record.peaks)["seconds"]
    return 100.0 * least / spent
