"""Median, over the window's answered requests that built one, of
``query.mask_build``: ``models/_filters.build_exclude_mask`` turning the
query's rules and the store's answers into a ``bool[n_items]`` on the
handler's thread. Nothing where the program has no such span. Source: the
program's own span."""

import program_spans


def read(record):
    return program_spans.median(
        program_spans.request_span_ms(record, "query.mask_build"))
