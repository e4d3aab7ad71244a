"""What the per-layer metrics read of the program's OWN spans.

The program keeps every finished span in a ring in memory
(``incubator_predictionio_tpu.common.telemetry.spans_snapshot()``): tuples
``(trace_id, span_id, parent_id, name, t0_ns, t1_ns, tags)`` on
``time.perf_counter_ns``, the clock of the harness's own window spans. A
metric file runs in the process that ran the program, after the window, so
it reads the ring directly. A program without the ring (a checkout from
before the spans) gives no spans, and every reader here then returns
nothing: the line leaves the metric out.

- the window's trains are the ``train.run`` roots lying inside the harness's
  ``run_train`` window spans (the traced extra train lies outside them);
- the window's requests are the last ``attempted`` ``http POST
  /queries.json`` roots by start (the warm-up queries come before them);
- a span's self time is its duration less the union of its children.
"""

from __future__ import annotations

import statistics

import trace_reduce

TRAIN_ROOT = "train.run"
REQUEST_ROOT = "http POST /queries.json"
COMPILE = "xla.compile"


def snapshot() -> list:
    """The program's ring, oldest first; empty where it has none."""
    try:
        from incubator_predictionio_tpu.common import telemetry

        return telemetry.spans_snapshot()
    except (ImportError, AttributeError):
        return []


def seconds(span) -> float:
    return (span.t1_ns - span.t0_ns) * 1e-9


def trees(spans, roots) -> list[list]:
    """For each root, the spans of its trace (the root first)."""
    by_trace: dict = {}
    for s in spans:
        by_trace.setdefault(s.trace_id, []).append(s)
    return [[r] + [s for s in by_trace[r.trace_id] if s is not r]
            for r in roots]


def window_trains(spans, window_spans) -> list[list]:
    """One tree per ``train.run`` root inside a ``run_train`` window span
    (``window_spans``: the harness's ``(name, t0_s, t1_s)``)."""
    windows = [(t0 * 1e9, t1 * 1e9) for name, t0, t1 in window_spans
               if name == "run_train"]
    roots = [s for s in spans if s.name == TRAIN_ROOT and s.parent_id is None
             and any(a <= s.t0_ns and s.t1_ns <= b for a, b in windows)]
    return trees(spans, roots)


def window_requests(spans, attempted: int) -> list[list]:
    """One tree per request of the window: the last ``attempted`` roots."""
    roots = sorted((s for s in spans
                    if s.name == REQUEST_ROOT and s.parent_id is None),
                   key=lambda s: s.t0_ns)
    return trees(spans, roots[-attempted:] if attempted > 0 else [])


def named(tree, *names) -> list:
    return [s for s in tree if s.name in names]


def self_seconds(span, tree) -> float:
    """Duration less the union of the direct children, cut to the span."""
    kids = trace_reduce.merge_intervals(
        (max(s.t0_ns, span.t0_ns), min(s.t1_ns, span.t1_ns))
        for s in tree if s.parent_id == span.span_id)
    return (span.t1_ns - span.t0_ns - sum(b - a for a, b in kids)) * 1e-9


#: spans of the training table that only group others
CONTAINERS = ("dase.algo_train",)


def covered_share(tree) -> float:
    """Share of the root covered by the union of the spans beneath it that
    name a piece of work (all but the grouping ones): what is left lies
    between the spans."""
    root = tree[0]
    work = trace_reduce.merge_intervals(
        (max(s.t0_ns, root.t0_ns), min(s.t1_ns, root.t1_ns))
        for s in tree[1:] if s.name not in CONTAINERS)
    return sum(b - a for a, b in work) / (root.t1_ns - root.t0_ns)


# -- what the metric files call ------------------------------------------------


def train_trees(record) -> list[list]:
    return window_trains(snapshot(), record.window_spans)


def request_trees(record) -> list[list]:
    summary = record.window.get("summary")
    if not summary:
        return []
    return window_requests(snapshot(), int(summary["attempted"]))


def mean_train_seconds(record, *names):
    """Mean over the window's trains of the summed spans named ``names``;
    nothing where no train of the window left such a span."""
    sums = [sum(seconds(s) for s in found) for found in
            (named(tree, *names) for tree in train_trees(record)) if found]
    return sum(sums) / len(sums) if sums else None


def answered(tree) -> bool:
    return (tree[0].tags or {}).get("status") == 200


def request_values_ms(record, value) -> list[float]:
    """``value(tree)`` in ms for each answered request of the window that
    has one (``value`` returns seconds or None)."""
    got = (value(tree) for tree in request_trees(record) if answered(tree))
    return [1e3 * v for v in got if v is not None]


def request_span_ms(record, name: str) -> list[float]:
    """ms of the span ``name`` in each answered request that has it."""
    def value(tree):
        found = named(tree, name)
        return sum(seconds(s) for s in found) if found else None

    return request_values_ms(record, value)


def host_seconds(tree):
    """The request's time in host code of the server: the root's self time
    (JSON, plugins, cache key, the answer's encoding) plus featurize and
    serve. Nothing for a request that never reached the stages."""
    if not named(tree, "query.predict"):
        return None
    return self_seconds(tree[0], tree) + sum(
        seconds(s) for s in named(tree, "query.featurize", "query.serve"))


def busy_host_share_percent(request_trees_):
    """Share of the window (first root's start to last root's end) in which
    at least one request was in the house and none was waiting on the
    device (no ``topk.wait`` open)."""
    if not request_trees_:
        return None
    roots = [tree[0] for tree in request_trees_]
    lo, hi = min(r.t0_ns for r in roots), max(r.t1_ns for r in roots)
    house = trace_reduce.merge_intervals((r.t0_ns, r.t1_ns) for r in roots)
    waits = trace_reduce.merge_intervals(
        (s.t0_ns, s.t1_ns) for tree in request_trees_
        for s in named(tree, "topk.wait"))
    # idle_gaps of the house given the waits = in the house, not waiting
    busy = sum(gb - ga
               for a, b in house
               for ga, gb in trace_reduce.gaps_of(
                   [w for w in waits if w[1] > a and w[0] < b], a, b))
    return 100.0 * busy / (hi - lo)


def compiles_between(spans, lo_ns: int, hi_ns: int) -> int:
    return sum(1 for s in spans
               if s.name == COMPILE and lo_ns <= s.t1_ns and s.t0_ns <= hi_ns)


def median(values):
    """Nothing for no values (a metric without readings is left out)."""
    return statistics.median(values) if values else None
