"""Host seconds of bringing the event log into the process (the file's read
and the codec's pass over it, or a snapshot's load; about nothing for a scan
the process has cached), averaged over the window's trains. Source: the
program's own span ``store.scan`` (``store.parse`` lies inside it)."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "store.scan")
