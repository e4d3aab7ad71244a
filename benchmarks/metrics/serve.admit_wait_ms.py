"""95th percentile of how long an admitted query waited for an executor
thread (``query.admit_wait``: from the admission gate to the worker picking
it up), over the window's answered requests. Source: the program's own
span."""

import loadgen
import program_spans


def read(record):
    waits = program_spans.request_span_ms(record, "query.admit_wait")
    return loadgen.percentile(waits, 95) if waits else None
