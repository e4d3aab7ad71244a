"""Host seconds of replaying an entity type's ``$set`` / ``$unset`` /
``$delete`` events into its properties at train time
(``JSONLEvents.aggregate_columnar``: the selection, one ``json.loads`` an
event on one thread, a ``PropertyMap`` an entity), averaged over the
window's trains. Source: the program's own span ``store.aggregate``;
nothing where the program has none."""

import program_spans


def read(record):
    return program_spans.mean_train_seconds(record, "store.aggregate")
