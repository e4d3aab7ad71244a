"""Milliseconds of the window in which the cyclic garbage collector ran in
the serving process (the interpreter lock held, every thread waiting): the
summed ``gc_ms`` of the window's ``loop.beat`` spans, collections of every
generation and thread, the short ones too. Source: the program's own
``gc.callbacks`` hook, read by its loop monitor."""

import runtime_spans


def read(record):
    values = runtime_spans.beat_values(record, "gc_ms")
    return None if values is None else sum(values)
