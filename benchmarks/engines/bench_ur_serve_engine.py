"""The served Universal Recommender deployment's engine (the
``bench_ecomm_engine`` pattern).

``URAlgorithm.predict``, ``URModel.recommend``, the artifact and the
workflow are the STOCK ones of the universal-recommender template. Two
things are the benchmark's:

- ``train`` hands back a URModel whose indicators the harness drew from the
  seed (counting co-occurrences over 9.4M x 9.4M is not set-up), with
  IdentityBiMaps on both sides, the generator's category of each item and
  its buy counts as the popularity ranking; the model is then persisted,
  verified, loaded, gated and warmed by the normal path, which is where the
  index by correlator is built and put on the device;
- where the model's ``Storage`` comes from: the SQLITE event store the
  deployment file opened (``STORE["storage"]``), as in the e-commerce
  sibling, never ``benchmarks/run.py``'s MEMORY source.

The harness hands inputs over through ``INPUTS`` (one process, no pickling
of gigabytes through a parameter dict); the parameter names the entry.
"""

from __future__ import annotations

from bench_engine import InputParams, KeyDataSource

from incubator_predictionio_tpu.controller import Engine
from incubator_predictionio_tpu.data.storage.bimap import IdentityBiMap
from incubator_predictionio_tpu.models.universal_recommender import (
    URAlgorithm, URModel,
)
from incubator_predictionio_tpu.ops.llr import Indicators

#: key -> {"indicators": {event name: (idx, score)}, "n_users",
#: "item_categories", "popularity", "app_name"}, filled by the deployment
#: file before run_train is called. ``STORE`` holds the run's ``storage``
#: and outlives ``release``: the served model reads it.
INPUTS: dict[str, dict] = {}
STORE: dict[str, object] = {}

__all__ = ["INPUTS", "STORE", "InputParams", "serve_engine"]


class SeededURAlgorithm(URAlgorithm):
    """Stock predict / persistence; ``train`` hands back the indicators
    drawn from the seed, ``restore_model`` points the model at the event
    store of the run."""

    def train(self, ctx, pd) -> URModel:
        d = INPUTS[pd.key]
        indicators = {name: Indicators(idx=idx, score=score)
                      for name, (idx, score) in d["indicators"].items()}
        n_items = next(iter(indicators.values())).idx.shape[0]
        model = URModel(
            indicators=indicators, users=IdentityBiMap(d["n_users"]),
            items=IdentityBiMap(n_items),
            item_categories=d["item_categories"], app_name=d["app_name"],
            event_names=tuple(indicators), popularity=d["popularity"])
        model._storage = STORE["storage"]
        return model

    def restore_model(self, stored, ctx) -> URModel:
        model = super().restore_model(stored, ctx)
        model._storage = STORE["storage"]
        return model


def serve_engine() -> Engine:
    return Engine(data_source_class=KeyDataSource,
                  algorithm_class_map={"ur": SeededURAlgorithm})
