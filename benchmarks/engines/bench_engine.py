"""The benchmark's engines (the tools/chip_smoke_engine pattern).

The algorithm, the model, the workflow and the artifact are the STOCK ones
of the recommendation template. Only the DataSource differs, and for the
serve configurations ``train``:

- ``retrain_engine``: the DataSource hands over the COO triple that the
  harness made from the seed (the event store is bypassed), with
  IdentityBiMaps on both sides; ALSAlgorithm.train is untouched.
- ``serve_engine``: ``train`` returns an ALSModel whose factors the harness
  drew from the seed (training 9.4M items at rank 128 is not set-up); the
  model is then persisted, verified, loaded, gated and warmed by the normal
  path.

The harness hands inputs over through ``INPUTS`` (one process, no pickling
of gigabytes through a parameter dict); the parameter names the entry.
"""

from __future__ import annotations

import dataclasses

from incubator_predictionio_tpu.controller import DataSource, Engine, Params
from incubator_predictionio_tpu.data.storage.bimap import IdentityBiMap
from incubator_predictionio_tpu.models.recommendation import (
    ALSAlgorithm, ALSModel, TrainingData,
)
from incubator_predictionio_tpu.ops.als import ALSFactors

#: key -> inputs, filled by benchmarks/run.py before it calls run_train
INPUTS: dict[str, dict] = {}


@dataclasses.dataclass(frozen=True)
class InputParams(Params):
    key: str = ""


class TripleDataSource(DataSource):
    params_cls = InputParams

    def read_training(self, ctx) -> TrainingData:
        d = INPUTS[self.params.key]
        return TrainingData(d["user"], d["item"], d["rating"],
                            IdentityBiMap(d["n_users"]),
                            IdentityBiMap(d["n_items"]))


class SeededFactorsAlgorithm(ALSAlgorithm):
    """Stock predict / persistence / restore; ``train`` hands back the
    factors drawn from the seed."""

    def train(self, ctx, pd) -> ALSModel:
        d = INPUTS[pd.key]
        uf, itf = d["user_factors"], d["item_factors"]
        return ALSModel(
            factors=ALSFactors(uf, itf, uf.shape[0], itf.shape[0]),
            users=IdentityBiMap(uf.shape[0]),
            items=IdentityBiMap(itf.shape[0]))


class KeyDataSource(DataSource):
    params_cls = InputParams

    def read_training(self, ctx):
        return self.params


def retrain_engine() -> Engine:
    return Engine(data_source_class=TripleDataSource,
                  algorithm_class_map={"als": ALSAlgorithm})


def serve_engine() -> Engine:
    return Engine(data_source_class=KeyDataSource,
                  algorithm_class_map={"als": SeededFactorsAlgorithm})
