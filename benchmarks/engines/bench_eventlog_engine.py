"""The event-log deployment's engine (the ``bench_engine`` pattern).

DataSource, Preparator, ``ALSAlgorithm``, the model, the artifact and the
workflow are the STOCK ones of the recommendation template: ``read_training``
is ``RecommendationDataSource``'s, so the train reads ``rate`` and ``buy``
events through ``PEventStore.find_ratings`` and gets string ``BiMap``s. One
thing is the benchmark's: where the ``Storage`` of that read comes from.
``benchmarks/run.py`` keeps every repository on the MEMORY source; the
events of the run live in the program's ``JSONL`` source on disk, which the
deployment file opened (``STORE["storage"]``), so the stock ``read_training``
is handed the workflow's context with that store in it.

``STORE["bytes_before_window"]`` is the deployment's own note of
``pio_store_scan_bytes_total`` at the end of set-up, for the metric that
reads the window's bytes.
"""

from __future__ import annotations

import dataclasses

from incubator_predictionio_tpu.controller import Engine
from incubator_predictionio_tpu.models.recommendation import (
    ALSAlgorithm, RecommendationDataSource, TrainingData,
)

STORE: dict[str, object] = {}


class EventLogDataSource(RecommendationDataSource):
    def read_training(self, ctx) -> TrainingData:
        return super().read_training(
            dataclasses.replace(ctx, storage=STORE["storage"]))


def retrain_engine() -> Engine:
    return Engine(data_source_class=EventLogDataSource,
                  algorithm_class_map={"als": ALSAlgorithm})
