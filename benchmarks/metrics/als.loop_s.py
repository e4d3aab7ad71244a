"""Seconds from the train step's dispatch to its factors being ready, as
the host sees them (every ``als.loop`` of a train summed), averaged over the
window's trains. Against ``als.sweep_s`` x numIterations the rest is
dispatch and the transfer's tail. Source: the program's own span."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "als.loop")
