"""Seconds of one train in which the cyclic garbage collector ran (in
whatever thread; the interpreter lock held): the tag ``gc_ms`` that
``run_train`` puts on its ``train.run`` root at close, averaged over the
window's trains. Source: the program's own ``gc.callbacks`` hook."""

import runtime_spans


def read(record):
    return runtime_spans.train_gc_seconds(record)
