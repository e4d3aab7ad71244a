"""The Universal Recommender deployment's engine (the ``bench_engine``
pattern): the STOCK ``URAlgorithm``, ``URModel``, workflow and artifact of
the universal-recommender template. Only the DataSource differs: it hands
over the two (user, item) COO pairs that the harness made from the seed, with
IdentityBiMaps on both sides and no item properties (the event store is
bypassed, as in the ALS cells).

The harness hands inputs over through ``INPUTS`` (one process, no pickling
of hundreds of megabytes through a parameter dict); the parameter names the
entry.
"""

from __future__ import annotations

import dataclasses

from incubator_predictionio_tpu.controller import DataSource, Engine, Params
from incubator_predictionio_tpu.data.storage.bimap import IdentityBiMap
from incubator_predictionio_tpu.models.universal_recommender import (
    TrainingData, URAlgorithm,
)

#: key -> {"events": {name: (user, item)}, "n_users", "n_items"}, filled by
#: the deployment file before run_train is called
INPUTS: dict[str, dict] = {}


@dataclasses.dataclass(frozen=True)
class InputParams(Params):
    key: str = ""


class EventPairsDataSource(DataSource):
    params_cls = InputParams

    def read_training(self, ctx) -> TrainingData:
        d = INPUTS[self.params.key]
        return TrainingData(dict(d["events"]), IdentityBiMap(d["n_users"]),
                            IdentityBiMap(d["n_items"]), {})


def retrain_engine() -> Engine:
    return Engine(data_source_class=EventPairsDataSource,
                  algorithm_class_map={"ur": URAlgorithm})
