"""Host seconds of laying the events out for the device: ``cco.dedupe``
(distinct (user, item) pairs of each event, sorted) + ``cco.partition`` (the
[ranges, E] slabs, heavy users apart, and the start of the uploads), averaged
over the window's trains. Source: the program's own spans."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "cco.dedupe",
                                            "cco.partition")
