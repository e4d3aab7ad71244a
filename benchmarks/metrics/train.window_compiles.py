"""Backend compiles (or loads from the persistent compile cache) inside the
window's trains: ``xla.compile`` spans under their ``train.run`` roots.
Nothing compiles inside a measured window, so this reads 0. Source: the
program's own ``jax.monitoring`` listener, which also feeds
``pio_xla_compiles_total``."""

import program_spans


def read(record):
    trains = program_spans.train_trees(record)
    if not trains:
        return None
    return sum(len(program_spans.named(tree, program_spans.COMPILE))
               for tree in trains)
