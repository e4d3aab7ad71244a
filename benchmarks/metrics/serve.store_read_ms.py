"""Median, over the window's answered requests that read the event store, of
the time inside ``query.store_read``: the request's blocking
``LEventStore.find_by_entity`` reads summed (the ecommerce template makes
two, ``what=unavailable`` and ``what=seen``). Nothing where the program has no
such span. Source: the program's own span."""

import program_spans


def read(record):
    return program_spans.median(
        program_spans.request_span_ms(record, "query.store_read"))
