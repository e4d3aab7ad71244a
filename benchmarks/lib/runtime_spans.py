"""What the ``runtime`` metrics read: the program's spans of the process
itself, beneath the server and the trainer. They are roots of ONE trace of
their own (no request's or train's tree holds them), told apart by name:

- ``loop.beat``: once a second of the server's event loop, what its monitor
  saw (tags ``loop``, ``ticks``, ``lag_med_ms``, ``lag_max_ms``, ``cpu_ms``,
  ``loop_cpu_ms``, ``gc_ms``: every collection of that second);
- ``loop.stall``: from when a wake-up of that monitor was due to when it
  ran, where that is 5 ms or more (tags ``loop``, ``lag_ms``, ...);
- a ``train.run`` root says at close how long the collector held it up
  (tags ``gc_ms``, ``gc_collections``).

The serve window is ``serve.busy_host_share``'s: the first window root's
start to the last root's end. A program without these spans (a checkout
from before them) gives no beat and no tag, and every reader here then
returns nothing; a window with beats and no stall reads 0.0.
"""

from __future__ import annotations

import program_spans

BEAT = "loop.beat"
STALL = "loop.stall"
#: the ``loop`` tag of the engine server's monitor
LOOP = "engine"


def serve_window(record):
    """(start, end) in ns of the window's requests; nothing without them."""
    roots = [tree[0] for tree in program_spans.request_trees(record)]
    if not roots:
        return None
    return min(r.t0_ns for r in roots), max(r.t1_ns for r in roots)


def loop_spans(name: str, lo_ns: int, hi_ns: int) -> list:
    """The engine loop's spans called ``name`` that touch [lo, hi]."""
    return [s for s in program_spans.snapshot()
            if s.name == name and s.parent_id is None
            and (s.tags or {}).get("loop") == LOOP
            and s.t1_ns > lo_ns and s.t0_ns < hi_ns]


def window_beats(record, window=None):
    """The beats lying wholly inside the serve window (28 or 29 of a 30 s
    window); nothing where the program beats not."""
    window = window or serve_window(record)
    if window is None:
        return None
    lo, hi = window
    return [s for s in loop_spans(BEAT, lo, hi)
            if lo <= s.t0_ns and s.t1_ns <= hi] or None


def beat_values(record, tag: str):
    beats = window_beats(record)
    return None if beats is None else [float(s.tags[tag]) for s in beats]


def stall_ms(record):
    """Summed ms of the window in which the loop could not run: each
    ``loop.stall`` cut to the window. 0.0 where the loop beat and never
    stalled."""
    window = serve_window(record)
    if window_beats(record, window) is None:
        return None
    lo, hi = window
    return sum(min(s.t1_ns, hi) - max(s.t0_ns, lo)
               for s in loop_spans(STALL, lo, hi)) * 1e-6


def train_gc_seconds(record):
    """Mean over the window's trains of the root's ``gc_ms`` tag, in
    seconds."""
    tagged = [tree[0].tags["gc_ms"] * 1e-3
              for tree in program_spans.train_trees(record)
              if "gc_ms" in (tree[0].tags or {})]
    return sum(tagged) / len(tagged) if tagged else None
