"""Median host time of one answered request inside the server: the root
span's self time (JSON, plugins, cache key, the answer's encoding) plus
``query.featurize`` and ``query.serve``; admission wait and the predict stage
are not in it. Source: the program's own spans."""

import program_spans


def read(record):
    return program_spans.median(program_spans.request_values_ms(
        record, program_spans.host_seconds))
