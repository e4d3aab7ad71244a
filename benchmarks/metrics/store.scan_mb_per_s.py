"""Megabytes of event log a second of ``store.scan``: the bytes that
``pio_store_scan_bytes_total`` counted since the end of set-up (the
deployment notes the counter there) over the summed ``store.scan`` spans of
the window's trains. Source: the program's counter and spans."""

import sys

import program_spans
import store_spans


def read(record):
    engine = sys.modules.get("bench_eventlog_engine")
    before = getattr(engine, "STORE", {}).get("bytes_before_window")
    now = store_spans.counter_value(store_spans.SCAN_BYTES)
    spent = sum(program_spans.seconds(s)
                for tree in program_spans.train_trees(record)
                for s in program_spans.named(tree, "store.scan"))
    if before is None or now is None or spent <= 0 or now <= before:
        return None
    return (now - before) / 1e6 / spent
