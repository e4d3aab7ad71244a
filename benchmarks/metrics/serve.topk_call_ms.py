"""Median wall of ShardedCatalog.top_k over the window: dispatch, device and
readback of one query's scan. Source: the harness's span around the call."""

import statistics


def read(record):
    calls = record.window_span_seconds("top_k")
    return 1e3 * statistics.median(calls) if calls else None
