"""The e-commerce deployment's engine (the ``bench_engine`` pattern).

``ECommerceAlgorithm.predict``, ``ECommerceModel.recommend``, the artifact
and the workflow are the STOCK ones of the ecommerce template. Two things are
the benchmark's:

- ``train`` hands back an ECommerceModel whose factors the harness drew from
  the seed (training 9.4M items at rank 128 is not set-up), with
  IdentityBiMaps on both sides and the generator's category of each item; the
  model is then persisted, verified, loaded, gated and warmed by the normal
  path;
- where the model's ``Storage`` comes from. ``benchmarks/run.py`` keeps
  every repository on the MEMORY source, whose event ``find`` copies and
  sorts the whole table a call. The served model reads the event store the
  deployment file opened: the program's default kind of source, SQLITE on
  disk, indexed by entity (``STORE["storage"]``).

The harness hands inputs over through ``INPUTS`` (one process, no pickling of
gigabytes through a parameter dict); the parameter names the entry.
"""

from __future__ import annotations

from bench_engine import InputParams, KeyDataSource

from incubator_predictionio_tpu.controller import Engine
from incubator_predictionio_tpu.data.storage.bimap import IdentityBiMap
from incubator_predictionio_tpu.models.ecommerce import (
    ECommerceAlgorithm, ECommerceModel,
)
from incubator_predictionio_tpu.ops.als import ALSFactors

#: key -> {"user_factors", "item_factors", "item_categories", "app_name"},
#: filled by the deployment file before run_train is called. ``STORE`` holds
#: the run's ``storage`` and outlives ``release``: the served model reads it.
INPUTS: dict[str, dict] = {}
STORE: dict[str, object] = {}

__all__ = ["INPUTS", "STORE", "InputParams", "serve_engine"]


class SeededECommerceAlgorithm(ECommerceAlgorithm):
    """Stock predict / persistence; ``train`` hands back the factors drawn
    from the seed, ``restore_model`` points the model at the event store of
    the run."""

    def train(self, ctx, pd) -> ECommerceModel:
        d = INPUTS[pd.key]
        uf, itf = d["user_factors"], d["item_factors"]
        model = ECommerceModel(
            factors=ALSFactors(uf, itf, uf.shape[0], itf.shape[0]),
            users=IdentityBiMap(uf.shape[0]),
            items=IdentityBiMap(itf.shape[0]),
            item_categories=d["item_categories"],
            app_name=d["app_name"],
            seen_event_names=tuple(self.params.seen_events))
        model._storage = STORE["storage"]
        return model

    def restore_model(self, stored, ctx) -> ECommerceModel:
        model = super().restore_model(stored, ctx)
        model._storage = STORE["storage"]
        return model


def serve_engine() -> Engine:
    return Engine(data_source_class=KeyDataSource,
                  algorithm_class_map={"ecomm": SeededECommerceAlgorithm})
