"""Host seconds the calling thread of ops.als.train_als still waits for the
init that a worker thread draws beside the layout and the pack: the join and
the placing of the item rows into layout slots, averaged over the window's
trains. It is what of ``als.init_s`` is left on the critical path of a train
(``als.init_s`` itself is the worker's own wall from this metric on, and no
summand of ``retrain_s``). Source: the program's own span ``als.init_wait``;
nothing where no train left one (the init ran in turn, or the checkout is
from before the span)."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "als.init_wait")
