"""Device seconds of scoring and selection in the traced train: Dunning's G²
over every [stripe, I] block of the resident count matrices and the k best of
each row (the executable ``_cco_select``). Source: the device trace's module
line."""

import cco_spans


def read(record):
    return cco_spans.select_seconds(record)
