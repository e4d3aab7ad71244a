"""Median, over the window's answered requests without history, of
``ur.backfill``: `ops/llr.popular_rows`, the popularity ranking under the
query's rules, dispatch to fetched answer. Nothing where the program has no
such span. Source: the program's own span."""

import program_spans


def read(record):
    return program_spans.median(
        program_spans.request_span_ms(record, "ur.backfill"))
