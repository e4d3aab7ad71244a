"""Median of ``topk.wait``: the ``jax.device_get`` after the top-k's
dispatch, that is the device's queue behind other queries, the scan and the
readback, over the window's answered requests that scanned the catalog.
Source: the program's own span."""

import program_spans


def read(record):
    return program_spans.median(
        program_spans.request_span_ms(record, "topk.wait"))
