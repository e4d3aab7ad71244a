"""Host seconds of packing the slabs and handing them and the initial
factors to the device (``als.pack`` + ``als.upload``), averaged over the
window's trains. The program adds no barrier after the puts: what the
transfer still owes when they return lies in ``als.loop_s``. Source: the
program's own spans."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "als.pack", "als.upload")
