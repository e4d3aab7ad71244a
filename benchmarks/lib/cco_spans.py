"""What the ``cco.*`` trace-fed metrics share: the device time of the
cross-occurrence program's two halves in the traced train, by the names the
trace's module line gives the two executables of ``ops/llr.py``'s
full-matrix paths (one pair or every pair of a primary, one device or a
mesh). A program whose executables have other names (a checkout from before
the halves were two dispatches; the striped path, where counting and
selection alternate stripe by stripe inside one executable) gives nothing,
and every reader here then returns nothing.
"""

from __future__ import annotations

import re

#: the counting scans: every pair of one primary (fused), or one pair
COUNT_MODULE = re.compile(r"jit__cco_count_multi\b")
#: G² and the k best of every row of the resident count matrices
SELECT_MODULE = re.compile(r"jit__cco_select\b")


def _module_seconds(record, pattern):
    if not record.trace:
        return None
    found = [v for name, v in record.trace["module_seconds"].items()
             if pattern.search(name)]
    return sum(found) if found else None


def count_seconds(record):
    """Device seconds of building the count matrices in the traced train:
    the scatters, the int8 contractions, the accumulators' traffic and the
    scans' own overhead."""
    return _module_seconds(record, COUNT_MODULE)


def select_seconds(record):
    """Device seconds of scoring and selection in the traced train."""
    return _module_seconds(record, SELECT_MODULE)
