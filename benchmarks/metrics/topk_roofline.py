"""The top-k scan's share of its roofline: the catalog's bytes over the HBM
peak (the bytes bind: 2 operations per 4 bytes read) over the mean device
time of the _topk_scores executable in the traced seconds. Source: the device
trace's module line."""

import re

import work

TOPK_MODULE = re.compile(r"jit__topk_scores\b")


def read(record):
    if not record.trace or not record.peaks:
        return None
    secs = sum(v for n, v in record.trace["module_seconds"].items()
               if TOPK_MODULE.search(n))
    calls = sum(v for n, v in record.trace["module_counts"].items()
                if TOPK_MODULE.search(n))
    if not calls or secs <= 0:
        return None
    c = record.config
    least = work.roofline_seconds(
        work.topk_scan_flops(c["n_items"], c["rank"]),
        work.topk_scan_bytes(c["n_items"], c["rank"]), record.peaks)
    return 100.0 * least["seconds"] / (secs / calls)
