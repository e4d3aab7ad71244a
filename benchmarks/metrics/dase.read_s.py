"""Host seconds of the DataSource's training read (``read_training``: app
lookup, the log's scan, selection, ids to rows), averaged over the window's
trains. Source: the program's own span ``dase.read``."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "dase.read")
