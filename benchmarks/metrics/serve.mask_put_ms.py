"""Median, over the window's answered requests that shipped one, of
``topk.mask_put``: the ``device_put`` of a mask built on the host in
``ops/topk.top_k_items`` (a mask resident on the device takes none). Nothing
where the program has no such span. Source: the program's own span."""

import program_spans


def read(record):
    return program_spans.median(
        program_spans.request_span_ms(record, "topk.mask_put"))
