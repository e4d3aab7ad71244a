"""Host seconds of summing a train's events into one entry a (user, item)
pair (``models/similar_product.count_pairs``: one stable sort of int64 keys,
the pairs kept in first-seen order), averaged over the window's trains.
Source: the program's own span ``prep.pair_counts``; nothing where the
program has none."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "prep.pair_counts")
