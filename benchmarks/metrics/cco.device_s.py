"""Seconds from the cross-occurrence program's dispatch until ``device_get``
hands back the [I, k] indicators, as the host sees them, averaged over the
window's trains: the uploads' tail, the counting scans, G², top-k and the
readback. Source: the program's own span ``cco.device``."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "cco.device")
