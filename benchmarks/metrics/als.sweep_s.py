"""Device seconds of ONE ALS sweep: the traced train executable's time on the
device over the iterations it ran. Source: the device trace's module line."""

import re

#: the jitted training loop as the trace names it (ops/als.py: jit(packed)
#: on one device, jit(loop) on a mesh)
TRAIN_MODULE = re.compile(r"jit_(packed|loop)\b")


def train_module_seconds(record):
    if not record.trace:
        return None
    found = [v for name, v in record.trace["module_seconds"].items()
             if TRAIN_MODULE.search(name)]
    return sum(found) if found else None


def read(record):
    total = train_module_seconds(record)
    iters = record.window.get("traced_iterations")
    if total is None or not iters:
        return None
    return total / iters
