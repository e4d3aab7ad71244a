"""The Pallas SPD solve's share of its roofline: the least time the chip
could take for the traced sweeps' solves (work.py: the larger of operations
over the bf16 peak and normal-equation bytes over the HBM peak; the bytes
bind at rank 128) over the summed device time of the solve kernel's events.
Source: the device trace's op line."""

import re

import work

#: the Mosaic kernels of ops/pallas_kernels.py as the trace's op line names
#: them (the jitted wrappers ``_solve_slabs_wide`` / ``_solve_lanes``, a
#: number appended); a kernel that a later PR renames gets a new metric file
SOLVE_OP = re.compile(r"^_solve_(slabs_wide|lanes)\b")


def read(record):
    iters = record.window.get("traced_iterations")
    if not record.trace or not iters or not record.peaks:
        return None
    spent = sum(v for name, v in record.trace["op_seconds"].items()
                if SOLVE_OP.search(name))
    if spent <= 0:
        return None
    c = record.config
    flops = (c["n_users"] + c["n_items"]) * work.spd_solve_flops(c["rank"])
    nbytes = work.als_solve_bytes(c["n_users"], c["n_items"], c["rank"])
    least = work.roofline_seconds(flops, nbytes, record.peaks)["seconds"]
    return 100.0 * least * iters / spent
