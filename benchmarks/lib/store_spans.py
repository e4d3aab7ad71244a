"""What the ``event_store`` metrics share: the training read of a window's
train as the program's own spans give it (``dase.read`` and, beneath it,
``store.scan`` with ``store.parse`` inside, ``store.select``,
``store.index``), and the value of a program counter. A program without
those spans or that counter (a checkout from before them) gives nothing, and
every reader here then returns nothing.
"""

from __future__ import annotations

import program_spans
import trace_reduce

READ = "dase.read"
#: the spans beneath ``dase.read`` that name a piece of its work
WORK = ("store.scan", "store.select", "store.index")
SCAN_BYTES = "pio_store_scan_bytes_total"


def counter_value(name: str):
    """The sum over a counter family's children; nothing where the program
    has no such family."""
    try:
        from incubator_predictionio_tpu.common import telemetry

        families = telemetry.registry().collect()
    except (ImportError, AttributeError):
        return None
    for fam in families:
        if fam.name == name:
            return sum(child.value() for _labels, child in fam.samples())
    return None


def read_coverage(tree):
    """(seconds of the train's ``dase.read``, share of it that the union of
    the ``store.*`` work spans covers, the scan's ``source`` tag, seconds
    by span name, ``store.parse`` among them); nothing for a train without
    them."""
    reads = program_spans.named(tree, READ)
    scans = program_spans.named(tree, "store.scan")
    if not reads or not scans:
        return None
    read = reads[0]
    work = trace_reduce.merge_intervals(
        (max(s.t0_ns, read.t0_ns), min(s.t1_ns, read.t1_ns))
        for s in program_spans.named(tree, *WORK))
    parts: dict[str, float] = {}
    for s in program_spans.named(tree, "store.parse", *WORK):
        name = s.name + "." + s.tags["step"] if "step" in (s.tags or {}) \
            else s.name
        parts[name] = parts.get(name, 0.0) + program_spans.seconds(s)
    return (program_spans.seconds(read),
            sum(b - a for a, b in work) / (read.t1_ns - read.t0_ns),
            (scans[0].tags or {}).get("source"), parts)
