"""The ``similarproduct-views`` deployment's engine (the ``bench_engine``
pattern).

DataSource, ``SimilarProductAlgorithm``, the model, the artifact and the
workflow are the STOCK ones of the similar-product template:
``read_training`` is ``SimilarProductDataSource``'s, so the train reads its
``view`` events through ``PEventStore.find_ratings``, sums them a pair and
replays the items' ``$set`` / ``$unset`` / ``$delete`` through
``PEventStore.aggregate_properties``. One thing is the benchmark's, as in
the event-log sibling: where the ``Storage`` of that read comes from
(``STORE["storage"]``, the sibling's dict, so that ``store.scan_mb_per_s``
finds its note of the counter here too).

``count_pairs`` is imported by name: the configuration states the factors of
COUNTED pairs (upstream's ``reduceByKey``). A checkout whose template hands
ALS one entry an event cannot run it, and says so here, at import, in a
second and with a non-zero exit, not after ten minutes with a result that
is not ``correct``.
"""

from __future__ import annotations

import dataclasses

import bench_eventlog_engine
from incubator_predictionio_tpu.controller import Engine
from incubator_predictionio_tpu.models.similar_product import (
    SimilarProductAlgorithm, SimilarProductDataSource, TrainingData,
    count_pairs,
)

STORE = bench_eventlog_engine.STORE
#: what of the program this configuration cannot run without
REQUIRES = (count_pairs,)


class ViewLogDataSource(SimilarProductDataSource):
    def read_training(self, ctx) -> TrainingData:
        return super().read_training(
            dataclasses.replace(ctx, storage=STORE["storage"]))


def retrain_engine() -> Engine:
    return Engine(data_source_class=ViewLogDataSource,
                  algorithm_class_map={"als": SimilarProductAlgorithm})
