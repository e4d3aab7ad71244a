"""Host seconds of fetching both factor matrices from the device and
un-permuting them into row order (``als.readback``), averaged over the
window's trains. Source: the program's own span."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "als.readback")
