"""Engine for chip_smoke.py's full-width phases.

`pio train --engine-dir tools/chip_smoke_engine` puts this directory on
sys.path and resolves engine.json's ``"engineFactory":
"smoke_engine.engine"`` (the templates/vanilla pattern). The algorithm is
the STOCK recommendation ALSAlgorithm; only the DataSource differs — it
returns bench.py's seeded MovieLens-shaped synthetic triple instead of
reading 20M events back out of the event store (importing ~5 GB of JSONL
is minutes of work a smoke is not for; the event-store feed is covered at
ML-100k shape by the smoke's quickstart phase).
"""

from __future__ import annotations

import dataclasses

from bench import SCALES, synth_ratings  # repo root: on sys.path via bin/pio

from incubator_predictionio_tpu.controller import DataSource, Engine, Params
from incubator_predictionio_tpu.data.storage.bimap import BiMap
from incubator_predictionio_tpu.models.recommendation import (
    ALSAlgorithm, TrainingData,
)


@dataclasses.dataclass(frozen=True)
class SynthParams(Params):
    scale: str = "ml20m"  # a key of bench.SCALES
    seed: int = 7


class SynthDataSource(DataSource):
    params_cls = SynthParams

    def read_training(self, ctx) -> TrainingData:
        n_users, n_items, nnz = SCALES[self.params.scale]
        u, i, r = synth_ratings(n_users, n_items, nnz, self.params.seed)
        return TrainingData(
            u, i, r,
            BiMap({str(j): j for j in range(n_users)}),
            BiMap({str(j): j for j in range(n_items)}),
        )


def engine() -> Engine:
    return Engine(data_source_class=SynthDataSource,
                  algorithm_class_map={"als": ALSAlgorithm})
