"""Host seconds of turning the trained models into the stored artifact:
``dase.serialize`` (pickle) + ``dase.persist`` (sha256, wrap, insert),
averaged over the window's trains. Source: the program's own spans."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "dase.serialize",
                                            "dase.persist")
