"""Median, over the window's answered requests that scored a history, of
``ur.score``: `ops/llr.score_rows` from the rules' put and the two
dispatches to the fetched answer. Nothing where the program has no such
span. Source: the program's own span."""

import program_spans


def read(record):
    return program_spans.median(
        program_spans.request_span_ms(record, "ur.score"))
