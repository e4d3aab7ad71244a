"""Host seconds between the scanned columns and the COO triple: the live
and event-name masks, the time sort and the ratings (``store.select``), the
dense row numbers and both string BiMaps (``store.index``), averaged over
the window's trains. Source: the program's own spans."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "store.select",
                                            "store.index")
