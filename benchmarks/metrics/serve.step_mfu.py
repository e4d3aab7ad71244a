"""The serving window's share of the chip's bf16 peak: 2 * n_items * rank
operations for every answered query that scanned the catalog (known users)
over the window's wall. Source: the load generator's counts, shapes."""

import work


def read(record):
    win = record.window
    if not record.peaks or "summary" not in win:
        return None
    c = record.config
    job, res = win["job"], win["result"]
    scans = sum(1 for user, status in zip(job["user"], res["status"])
                if status == 200 and user.isdigit())
    if not scans:
        return None
    flops = scans * work.topk_scan_flops(c["n_items"], c["rank"])
    return 100.0 * flops / win["wall_s"] / record.peaks["bf16_flops_per_s"]
