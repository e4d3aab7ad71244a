"""Host seconds of ops.als._fresh_init (the float64 draw of both factor
matrices, the cast and the scatter into layout slots), averaged over the
window's trains. Source: the program's own span ``als.init``."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "als.init")
