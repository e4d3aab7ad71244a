"""Backend compiles (or loads from the persistent compile cache) between
the window's first request and its last answer: ``xla.compile`` spans, in
whatever thread. Every ``num`` is warmed in set-up, so this reads 0. Source:
the program's own ``jax.monitoring`` listener, which also feeds
``pio_xla_compiles_total``."""

import program_spans


def read(record):
    requests = program_spans.request_trees(record)
    if not requests:
        return None
    roots = [tree[0] for tree in requests]
    return program_spans.compiles_between(
        program_spans.snapshot(), min(r.t0_ns for r in roots),
        max(r.t1_ns for r in roots))
