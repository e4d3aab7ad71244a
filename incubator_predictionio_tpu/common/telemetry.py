"""Unified telemetry: metric registry, Prometheus exposition, tracing.

After three PRs every layer reported health its own way — `stats.py`
counters, ad-hoc `/status` fields, `PipelineStats`, breaker snapshots —
none of it scrapable or correlated per request. Both TensorFlow (Abadi
et al., 2016) and the Spark-ML performance study (PAPERS.md 1605.08695,
Awan et al.) land on the same operational lesson: a distributed ML
system you cannot measure is a system you cannot optimize or operate.
This module is the one measurement substrate every layer records into:

- **Metric registry** — process-wide :class:`Registry` of counter /
  gauge / histogram families with Prometheus-style label sets.
  Counters are lock-*sharded* (per-thread-bucket locks, summed on
  read) so the ingest hot path never serializes on one metric lock;
  histograms use fixed log2 buckets whose index is a ``bit_length``,
  not a ``log``/bisect, and latency is fed from
  ``time.perf_counter_ns`` integers. With ``PIO_METRICS=0`` every
  record call returns before touching state — and the paired
  :func:`timer_start` returns the cached small int 0, so a disabled
  hot path adds **no allocations per request** (guard-tested).
- **Prometheus exposition** — :meth:`Registry.render` produces the
  text format (``# HELP``/``# TYPE``, escaped labels, cumulative
  ``_bucket``/``_sum``/``_count``) served by the event server, the
  engine server, and the dashboard at ``GET /metrics``.
- **Spans** — :func:`span` (a context manager) and :func:`add_span`
  (start and end known afterwards) are the one primitive that training
  (``train.run`` → ``dase.*`` → ``als.*``) and serving (``http POST
  /queries.json`` → ``query.*`` → ``topk.*``) both use. A span is
  ``(trace_id, span_id, parent_id, name, t0_ns, t1_ns, tags)`` on
  ``time.perf_counter_ns``; the parent rides a ``contextvars`` slot, so
  it crosses ``copy_context()`` into the query executor. Every finished
  span goes into a bounded process-wide ring (:func:`spans_snapshot`),
  gated by ``PIO_METRICS`` like the histograms; while open it also
  holds a ``jax.profiler.TraceAnnotation("pio:<name>")`` once
  :func:`install_xla_hooks` was handed jax (this module never imports
  it), so a profiler session shows host spans beside device ops.
- **The runtime beneath the spans** — what stands still when a whole
  process stalls is under every span: the thread that would close it
  is not running. A ``gc.callbacks`` hook counts every collection and
  its pause (``py.gc`` spans for the long ones), and
  :func:`loop_monitor` has an aiohttp application time its own event
  loop's wake-ups (``loop.stall``, ``loop.beat``). These are the
  process's spans, not a request's: roots of the one trace
  :data:`PROCESS_TRACE`, which no tree of a request or a train holds.
- **Sampled export** — ``PIO_TRACE`` sets a sample rate; a sampled
  request gets a trace id (honoring an incoming ``X-Pio-Trace-Id``,
  which — whenever tracing is enabled at all — bypasses the
  probability roll so a caller can follow one request through every
  tier; ``PIO_TRACE`` unset/0 stays fully off) and its spans are also
  written as JSON lines to ``PIO_TRACE_SINK`` (a path, or ``stderr``).

Per-instance JSON views (ingest ``snapshot()``, ``stats.json``) remain
per-server-instance; the registry is process-cumulative, which is what
a scraper expects.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import contextvars
import gc
import itertools
import json
import os
import random
import resource
import sys
import threading
import time
import uuid
from typing import Callable, Iterable, NamedTuple, Optional

from . import envknobs

__all__ = [
    "CounterFamily", "GaugeFamily", "HistogramFamily", "Registry",
    "Span", "span", "add_span", "spans_snapshot", "install_xla_hooks",
    "PROCESS_TRACE", "gc_totals", "gc_share", "loop_monitor",
    "RING_SIZE", "Trace", "TraceRecorder", "TRACE_HEADER",
    "current_trace", "activate_trace", "deactivate_trace",
    "metrics_enabled", "set_metrics_enabled", "timer_start",
    "registry", "render_all", "sample_trace", "configure_tracer",
    "trace_middleware",
]

TRACE_HEADER = "X-Pio-Trace-Id"


# ---------------------------------------------------------------------------
# enablement
# ---------------------------------------------------------------------------

def _env_flag(name: str, default: bool) -> bool:
    return envknobs.env_flag(name, default)


class _State:
    """Mutable module state behind one attribute load (the hot-path
    check is ``if not _STATE.metrics_on: return``)."""

    __slots__ = ("metrics_on", "annotation")


_STATE = _State()
_STATE.metrics_on = _env_flag("PIO_METRICS", True)
#: jax.profiler.TraceAnnotation once install_xla_hooks was handed jax
_STATE.annotation = None


def metrics_enabled() -> bool:
    return _STATE.metrics_on


def set_metrics_enabled(on: bool) -> None:
    """Flip metric recording at runtime (bench A/B, tests). The
    collector's hook goes in and out with it; a loop monitor that is
    running ticks on and records nothing."""
    _STATE.metrics_on = bool(on)
    _watch_gc(_STATE.metrics_on)


def timer_start() -> int:
    """Start a latency timer: ``perf_counter_ns`` when metrics are on,
    the cached small int ``0`` when off. The 0 sentinel makes the
    paired ``Histogram.observe_since`` a no-op, and — critically for
    the disabled-path guarantee — allocates nothing."""
    if _STATE.metrics_on:
        return time.perf_counter_ns()
    return 0


# ---------------------------------------------------------------------------
# metric children
# ---------------------------------------------------------------------------

_N_SHARDS = 8  # power of two; see _shard_index


def _shard_index() -> int:
    # thread idents are pointer-ish (low bits aligned-zero), so shift
    # before masking or every thread lands in shard 0
    return (threading.get_ident() >> 6) & (_N_SHARDS - 1)


class Counter:
    """Monotonic counter, lock-sharded: each thread bucket has its own
    (lock, value) cell, reads sum the shards. Concurrent writers on
    different shards never contend; same-shard writers serialize only
    against each other, not against every metric in the process."""

    __slots__ = ("_shards",)

    def __init__(self):
        self._shards = tuple(
            (threading.Lock(), [0]) for _ in range(_N_SHARDS))

    def inc(self, n: float = 1) -> None:
        if not _STATE.metrics_on:
            return
        lock, box = self._shards[_shard_index()]
        with lock:
            box[0] += n

    def value(self) -> float:
        total = 0
        for lock, box in self._shards:
            with lock:
                total += box[0]
        return total


class Gauge:
    """Last-write-wins gauge. Not gated on ``metrics_enabled`` — gauges
    are set from cold paths (pipeline end, breaker snapshots, compile
    accounting), never per-request."""

    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram:
    """Fixed log2-bucket histogram over integer raw units.

    Bucket ``j`` has upper bound ``2**(lo_exp + j)`` raw units; the
    index is ``(v - 1).bit_length() - lo_exp`` — the smallest bound
    that is ``>= v``, computed without logs, division, or a bisect
    (bucket-boundary math is golden-tested). Values past the top
    bucket land in ``+Inf``. ``scale`` converts raw units to the
    exposition unit (1e-9 for ns→seconds histograms, 1 for sizes).
    """

    __slots__ = ("_lock", "lo_exp", "n_buckets", "scale", "counts",
                 "sum_raw")

    def __init__(self, lo_exp: int, n_buckets: int, scale: float):
        self._lock = threading.Lock()
        self.lo_exp = lo_exp
        self.n_buckets = n_buckets
        self.scale = scale
        self.counts = [0] * (n_buckets + 1)  # [+Inf] is the last slot
        self.sum_raw = 0

    def bucket_index(self, v: int) -> int:
        if v <= 1:
            return 0 if self.lo_exp >= 0 else max(0, -self.lo_exp)
        i = (v - 1).bit_length() - self.lo_exp
        if i < 0:
            return 0
        return min(i, self.n_buckets)

    def observe_raw(self, v: int) -> None:
        """Record one observation of ``v`` raw units (ns for latency
        histograms, a plain count for size histograms)."""
        if not _STATE.metrics_on:
            return
        i = self.bucket_index(v)
        with self._lock:
            self.counts[i] += 1
            self.sum_raw += v

    def observe_since(self, t0: int) -> None:
        """Record the elapsed ns since a :func:`timer_start` result;
        a 0 start (metrics were off at timer creation) is a no-op."""
        if t0:
            self.observe_raw(time.perf_counter_ns() - t0)

    def snapshot(self) -> tuple[list[int], int, int]:
        """(bucket counts, total count, raw sum) under the lock."""
        with self._lock:
            counts = list(self.counts)
            return counts, sum(counts), self.sum_raw

    def upper_bound(self, j: int) -> float:
        """Exposition-unit upper bound of bucket ``j``."""
        return (2.0 ** (self.lo_exp + j)) * self.scale


# ---------------------------------------------------------------------------
# metric families
# ---------------------------------------------------------------------------

class _Family:
    """Named metric with a label schema; children cached per label
    values. The children dict is read lock-free (GIL-safe ``get``) and
    written under a lock — the hot path after warm-up is one dict get."""

    kind = "untyped"

    def __init__(self, name: str, help_: str, labelnames: tuple = ()):
        self.name = name
        self.help = help_
        self.labelnames = tuple(labelnames)
        self._children: dict = {}
        self._lock = threading.Lock()

    def _new_child(self):  # pragma: no cover - overridden
        raise NotImplementedError

    def labels(self, *values) -> object:
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: expected {len(self.labelnames)} label "
                f"value(s) {self.labelnames}, got {values!r}")
        key = tuple(str(v) for v in values)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.get(key)
                if child is None:
                    child = self._new_child()
                    self._children[key] = child
        return child

    def samples(self) -> Iterable[tuple[tuple, object]]:
        """(label values, child) pairs, stable-sorted for exposition."""
        return sorted(self._children.items())


class CounterFamily(_Family):
    kind = "counter"

    def _new_child(self) -> Counter:
        return Counter()


class GaugeFamily(_Family):
    kind = "gauge"

    def _new_child(self) -> Gauge:
        return Gauge()


class HistogramFamily(_Family):
    kind = "histogram"

    #: default latency shape: 2**10 ns (~1 us) .. 2**35 ns (~34 s)
    DEFAULT_LO_EXP = 10
    DEFAULT_N_BUCKETS = 26

    def __init__(self, name: str, help_: str, labelnames: tuple = (),
                 lo_exp: int = DEFAULT_LO_EXP,
                 n_buckets: int = DEFAULT_N_BUCKETS,
                 scale: float = 1e-9):
        super().__init__(name, help_, labelnames)
        self._shape = (lo_exp, n_buckets, scale)

    def _new_child(self) -> Histogram:
        return Histogram(*self._shape)


# ---------------------------------------------------------------------------
# registry + exposition
# ---------------------------------------------------------------------------

def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _escape_help(v: str) -> str:
    return v.replace("\\", "\\\\").replace("\n", "\\n")


def _fmt(v) -> str:
    if isinstance(v, int):
        return str(v)
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _labels_text(names: tuple, values: tuple, extra: str = "") -> str:
    parts = [f'{n}="{_escape_label(v)}"' for n, v in zip(names, values)]
    if extra:
        parts.append(extra)
    if not parts:
        return ""
    return "{" + ",".join(parts) + "}"


def render_families(families: Iterable[_Family]) -> str:
    """Prometheus text exposition format 0.0.4 for ``families``."""
    out: list[str] = []
    for fam in families:
        out.append(f"# HELP {fam.name} {_escape_help(fam.help)}")
        out.append(f"# TYPE {fam.name} {fam.kind}")
        for values, child in fam.samples():
            if fam.kind == "histogram":
                counts, total, sum_raw = child.snapshot()
                cum = 0
                for j in range(child.n_buckets):
                    cum += counts[j]
                    le = f'le="{_fmt(child.upper_bound(j))}"'
                    out.append(
                        f"{fam.name}_bucket"
                        f"{_labels_text(fam.labelnames, values, le)} {cum}")
                inf = 'le="+Inf"'
                out.append(
                    f"{fam.name}_bucket"
                    f"{_labels_text(fam.labelnames, values, inf)} {total}")
                out.append(
                    f"{fam.name}_sum"
                    f"{_labels_text(fam.labelnames, values)} "
                    f"{_fmt(sum_raw * child.scale)}")
                out.append(
                    f"{fam.name}_count"
                    f"{_labels_text(fam.labelnames, values)} {total}")
            else:
                out.append(
                    f"{fam.name}{_labels_text(fam.labelnames, values)} "
                    f"{_fmt(child.value())}")
    return "\n".join(out) + "\n" if out else ""


class Registry:
    """Named family registry plus render-time collectors.

    Families are process-cumulative objects created once
    (``counter``/``gauge``/``histogram`` are get-or-create, so module
    A and module B asking for the same name share the family).
    *Collectors* are callables returning families built at render time
    — for state owned elsewhere (circuit breakers, a server instance's
    per-instance stats). Collectors register under a key and REPLACE
    any previous registrant of that key, so a test spinning up a fresh
    server replaces the old server's collector instead of duplicating
    metric names in the exposition.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._families: dict[str, _Family] = {}
        self._collectors: dict[str, Callable[[], Iterable[_Family]]] = {}

    def _family(self, cls, name: str, help_: str, labelnames: tuple,
                **kwargs) -> _Family:
        # histogram() always passes the full shape; None for other kinds
        shape = ((kwargs["lo_exp"], kwargs["n_buckets"], kwargs["scale"])
                 if cls is HistogramFamily else None)
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = cls(name, help_, labelnames, **kwargs)
                self._families[name] = fam
            elif (not isinstance(fam, cls)
                  or fam.labelnames != tuple(labelnames)
                  or getattr(fam, "_shape", None) != shape):
                raise ValueError(
                    f"metric {name!r} re-registered with a different "
                    f"type/labels/shape")
            return fam

    def counter(self, name: str, help_: str,
                labelnames: tuple = ()) -> CounterFamily:
        return self._family(CounterFamily, name, help_, labelnames)

    def gauge(self, name: str, help_: str,
              labelnames: tuple = ()) -> GaugeFamily:
        return self._family(GaugeFamily, name, help_, labelnames)

    def histogram(self, name: str, help_: str, labelnames: tuple = (),
                  lo_exp: int = HistogramFamily.DEFAULT_LO_EXP,
                  n_buckets: int = HistogramFamily.DEFAULT_N_BUCKETS,
                  scale: float = 1e-9) -> HistogramFamily:
        return self._family(HistogramFamily, name, help_, labelnames,
                            lo_exp=lo_exp, n_buckets=n_buckets, scale=scale)

    def register_collector(self, key: str,
                           fn: Callable[[], Iterable[_Family]]) -> None:
        with self._lock:
            self._collectors[key] = fn

    def unregister_collector(self, key: str) -> None:
        with self._lock:
            self._collectors.pop(key, None)

    def collect(self) -> list[_Family]:
        with self._lock:
            families = sorted(self._families.values(),
                              key=lambda f: f.name)
            collectors = list(self._collectors.values())
        seen = {f.name for f in families}
        for fn in collectors:
            try:
                extra = list(fn())
            except Exception:  # noqa: BLE001 - exposition must not 500
                continue
            for fam in extra:
                if fam.name not in seen:
                    seen.add(fam.name)
                    families.append(fam)
        return families

    def render(self) -> str:
        """The full Prometheus text page for this registry."""
        return render_families(self.collect())


_REGISTRY = Registry()


def registry() -> Registry:
    """The process-wide default registry every layer records into."""
    return _REGISTRY


def render_all() -> str:
    return _REGISTRY.render()


# ---------------------------------------------------------------------------
# spans: one primitive for training and serving
# ---------------------------------------------------------------------------

#: finished spans kept in memory for a reader in the process
RING_SIZE = 65536

#: perf_counter_ns -> epoch ns, for the sink's ``startUs``
_EPOCH_OFFSET_NS = time.time_ns() - time.perf_counter_ns()


class Span(NamedTuple):
    """One finished span. ``t0_ns``/``t1_ns`` are ``perf_counter_ns``
    readings; ``trace_id`` is shared by the spans of one request or one
    train; ``parent_id`` is None on a root."""

    trace_id: object
    span_id: int
    parent_id: Optional[int]
    name: str
    t0_ns: int
    t1_ns: int
    tags: Optional[dict]


_SPAN_VAR: "contextvars.ContextVar[Optional[_OpenSpan]]" = \
    contextvars.ContextVar("pio_span", default=None)
_TRACE_VAR: "contextvars.ContextVar[Optional[Trace]]" = \
    contextvars.ContextVar("pio_trace", default=None)
_RING: "collections.deque[Span]" = collections.deque(maxlen=RING_SIZE)
_IDS = itertools.count(1)


def _lineage(trace_id=None) -> tuple:
    """(sampled Trace or None, trace id, parent id) of a span opening in
    this context: beneath the span that is open, else a root."""
    parent = _SPAN_VAR.get()
    if parent is not None:
        return parent._trace, parent.trace_id, parent.span_id
    trace = _TRACE_VAR.get()
    if trace is not None:
        trace_id = trace.trace_id
    elif trace_id is None:
        trace_id = next(_IDS)
    return trace, trace_id, None


def _finish(rec: Span, trace: "Optional[Trace]") -> None:
    _RING.append(rec)
    if trace is not None:
        trace.add(rec)


class _OpenSpan:
    """The context manager :func:`span` returns while metrics are on.
    Entering binds it as the ``contextvars`` parent of what opens
    beneath it (across ``copy_context()`` into an executor thread too)
    and, once jax was handed over (:func:`install_xla_hooks`), holds a
    ``TraceAnnotation("pio:<name>")`` so a profiler session shows the
    span beside the device's ops."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "tags",
                 "t0_ns", "dur_ns", "_trace", "_token", "_ann")

    def __init__(self, name: str, trace_id, tags: Optional[dict]):
        self.name = name
        self.trace_id = trace_id
        self.tags = tags
        self.dur_ns = 0

    def tag(self, **tags) -> None:
        """Tags only known while the span is open (an HTTP status)."""
        if self.tags is None:
            self.tags = tags
        else:
            self.tags.update(tags)

    def __enter__(self) -> "_OpenSpan":
        self._trace, self.trace_id, self.parent_id = _lineage(self.trace_id)
        self.span_id = next(_IDS)
        self._token = _SPAN_VAR.set(self)
        ann = _STATE.annotation
        if ann is not None:
            self._ann = ann = ann("pio:" + self.name)
            ann.__enter__()
        else:
            self._ann = None
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        t1 = time.perf_counter_ns()
        self.dur_ns = t1 - self.t0_ns
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        _SPAN_VAR.reset(self._token)
        _finish(Span(self.trace_id, self.span_id, self.parent_id, self.name,
                     self.t0_ns, t1, self.tags), self._trace)
        return False


class _NoopSpan:
    """What :func:`span` returns with ``PIO_METRICS=0``: one shared
    object, nothing recorded, nothing allocated."""

    __slots__ = ()
    dur_ns = 0

    def tag(self, **tags) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, et, ev, tb) -> bool:
        return False


_NOOP_SPAN = _NoopSpan()


def span(name: str, *, trace_id=None, **tags):
    """Context manager around one piece of work::

        with telemetry.span("als.init"):
            x0, y0 = _fresh_init(...)

    The finished :class:`Span` goes into the process-wide ring
    (:func:`spans_snapshot`) and, for a ``PIO_TRACE``-sampled request,
    to the sink. Its parent is the span open in this context; a root
    starts a new trace under ``trace_id`` (a train gives its
    engine-instance id) or the next small integer. Gated by
    ``PIO_METRICS`` like every other record call."""
    if not _STATE.metrics_on:
        return _NOOP_SPAN
    return _OpenSpan(name, trace_id, tags or None)


def add_span(name: str, t0_ns: int, t1_ns: int, *, trace_id=None,
             **tags) -> None:
    """A span whose start and end (``perf_counter_ns``) are only known
    afterwards: a compile reported by its listener, the wait of an
    admitted query for a worker. A child of the span open in this
    context; not in the profiler's trace (an annotation cannot be
    backdated). With ``trace_id`` it is a root of that trace instead,
    whatever is open here: the process's own spans (a collection that
    ran inside some request's handler is not that request's work) go
    under :data:`PROCESS_TRACE` so."""
    if not _STATE.metrics_on:
        return
    if trace_id is None:
        trace, trace_id, parent_id = _lineage()
    else:
        trace = parent_id = None
    _finish(Span(trace_id, next(_IDS), parent_id, name, t0_ns, t1_ns,
                 tags or None), trace)


def spans_snapshot() -> list[Span]:
    """A copy of the ring, oldest first (at most RING_SIZE spans)."""
    return list(_RING.copy())


def install_xla_hooks(jax) -> None:
    """Take from an imported ``jax`` what this module must not import
    (the event server records here and never loads jax): the profiler's
    ``TraceAnnotation`` for open spans, and ``jax.monitoring`` listeners
    that turn every backend compile into an ``xla.compile`` span and the
    ``pio_xla_*`` counters. ``workflow/context.py`` calls it where it
    enables the compile cache; once per process."""
    if _STATE.annotation is not None:
        return
    _STATE.annotation = jax.profiler.TraceAnnotation
    reg = registry()
    compiles = reg.counter(
        "pio_xla_compiles_total",
        "XLA backend compiles (a load from the persistent compile cache "
        "counts: it is what the caller waited for).").labels()
    seconds = reg.counter(
        "pio_xla_compile_seconds_total",
        "Seconds spent in XLA backend compiles or cache loads.").labels()
    cache = reg.counter(
        "pio_xla_cache_events_total",
        "Persistent compile cache events as jax.monitoring names them "
        "(cache_hits, cache_misses, ...).", ("event",))

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            t1 = time.perf_counter_ns()
            compiles.inc()
            seconds.inc(secs)
            add_span("xla.compile", t1 - int(secs * 1e9), t1,
                     seconds=secs)

    def on_event(event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/"):
            cache.labels(event.rsplit("/", 1)[1]).inc()

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# the runtime beneath the spans: the collector's pauses, the loop's lag
# ---------------------------------------------------------------------------

#: trace id of the process's own spans (``py.gc``, ``loop.stall``,
#: ``loop.beat``): roots that belong to no request and no train
PROCESS_TRACE = "pio.process"

#: a collection that paused for this long leaves a ``py.gc`` span, and so
#: does every collection of the oldest generation
GC_SPAN_NS = 1_000_000
_GC_OLDEST = 2

#: the loop monitor asks to be woken this often, calls a wake-up that is
#: this late a stall, and sums up once in this much loop time
LOOP_TICK_NS = 50_000_000
LOOP_STALL_NS = 5_000_000
LOOP_BEAT_NS = 1_000_000_000

# What the hook writes: plain cells, because it runs inside whatever code
# triggered the collection, which may hold a Counter's shard lock.
# Collections do not nest (the interpreter's ``collecting`` flag spans
# both callbacks), so one start cell is enough.
_GC_COUNT = [0, 0, 0]
_GC_PAUSE_NS = [0, 0, 0]
_GC_OPEN = [0, None]  # start (perf_counter_ns), the annotation held


def _on_gc(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` hook. Takes no lock; a collection that leaves
    no span allocates no container."""
    if phase == "start":
        ann = _STATE.annotation
        if ann is not None and info["generation"] == _GC_OLDEST:
            _GC_OPEN[1] = ann = ann("pio:py.gc")
            ann.__enter__()
        _GC_OPEN[0] = time.perf_counter_ns()
        return
    t1 = time.perf_counter_ns()
    t0, ann = _GC_OPEN
    if ann is not None:
        _GC_OPEN[1] = None
        ann.__exit__(None, None, None)
    if not t0:  # installed between a collection's two calls
        return
    _GC_OPEN[0] = 0  # before the booking: a reader between them reads low
    gen = info["generation"]
    _GC_COUNT[gen] += 1
    _GC_PAUSE_NS[gen] += t1 - t0
    if t1 - t0 >= GC_SPAN_NS or gen == _GC_OLDEST:
        add_span("py.gc", t0, t1, trace_id=PROCESS_TRACE, generation=gen,
                 collected=info["collected"],
                 uncollectable=info["uncollectable"],
                 thread=threading.get_ident())


def _collect_gc() -> list[_Family]:
    count = CounterFamily(
        "pio_gc_collections_total",
        "Collections of the cyclic garbage collector, by generation.",
        ("generation",))
    pause = CounterFamily(
        "pio_gc_pause_seconds_total",
        "Seconds the collector ran, by generation: every thread of the "
        "process waits, the interpreter lock is held.", ("generation",))
    for gen in range(len(_GC_COUNT)):
        count.labels(gen).inc(_GC_COUNT[gen])
        pause.labels(gen).inc(_GC_PAUSE_NS[gen] * 1e-9)
    return [count, pause]


def _watch_gc(on: bool) -> None:
    """Put the hook into ``gc.callbacks`` and its two families into the
    exposition, or take both out."""
    if on == (_on_gc in gc.callbacks):
        return
    if on:
        gc.callbacks.append(_on_gc)
        _REGISTRY.register_collector("runtime.gc", _collect_gc)
    else:
        gc.callbacks.remove(_on_gc)
        _REGISTRY.unregister_collector("runtime.gc")


_watch_gc(_STATE.metrics_on)


def gc_totals() -> tuple[int, int]:
    """(collections, ns paused) since the hook went in, every generation:
    what :func:`gc_share` takes the difference of."""
    return sum(_GC_COUNT), sum(_GC_PAUSE_NS)


@contextlib.contextmanager
def gc_share(open_span):
    """Tag ``open_span`` at close with the collections that ended while
    this was open (``gc_collections``) and their pauses (``gc_ms``), in
    whatever thread: two reads, exact, the short pauses included."""
    n0, p0 = gc_totals()
    try:
        yield
    finally:
        n1, p1 = gc_totals()
        open_span.tag(gc_ms=(p1 - p0) * 1e-6, gc_collections=n1 - n0)


class _LoopReading:
    """What a tick of the loop monitor reads besides the clock."""

    __slots__ = ("cpu_ns", "loop_cpu_ns", "gc_ns", "nivcsw", "majflt")

    def __init__(self, now_ns: int, gc_floor_ns: int = 0):
        self.cpu_ns = time.process_time_ns()
        self.loop_cpu_ns = time.thread_time_ns()
        # The collector's time so far, a collection that is open included:
        # a collecting thread gives the interpreter lock up as it enters
        # the hook's ``stop`` call, so the loop wakes from a long pause
        # BEFORE that pause is booked. Booked later from the hook's own
        # clock it may come out microseconds shorter: never step back.
        booked, open_t0 = sum(_GC_PAUSE_NS), _GC_OPEN[0]
        self.gc_ns = max(gc_floor_ns,
                         booked + (now_ns - open_t0 if open_t0 else 0))
        usage = resource.getrusage(resource.RUSAGE_SELF)
        self.nivcsw, self.majflt = usage.ru_nivcsw, usage.ru_majflt


async def _monitor_loop(loop_name: str) -> None:
    """Ask to be woken every LOOP_TICK_NS and note how late the wake-up
    came. A tick reads the clocks once; every difference a span carries
    is taken between two ticks, so a stall's readings hold the tick
    before it was due as well (a quiet second's beat says what that
    costs)."""
    reg = registry()
    lag_hist = reg.histogram(
        "pio_event_loop_lag_seconds",
        "How late the server's event loop woke its monitor, asked for "
        "every 50 ms: what a timer or a hop through the loop waits.",
        ("loop",)).labels(loop_name)
    stalled = reg.counter(
        "pio_event_loop_stall_seconds_total",
        "Seconds of wake-ups that came 5 ms or more late: time in which "
        "the event loop could not run.", ("loop",)).labels(loop_name)
    lags: list[int] = []
    beat_t0 = time.perf_counter_ns()
    last = beat = _LoopReading(beat_t0)
    while True:
        due = time.perf_counter_ns() + LOOP_TICK_NS
        await asyncio.sleep(LOOP_TICK_NS * 1e-9)
        now = time.perf_counter_ns()
        lag = max(0, now - due)
        read = _LoopReading(now, last.gc_ns)
        lag_hist.observe_raw(lag)
        lags.append(lag)
        if lag >= LOOP_STALL_NS:
            stalled.inc(lag * 1e-9)
            add_span("loop.stall", due, now, trace_id=PROCESS_TRACE,
                     loop=loop_name, lag_ms=lag * 1e-6,
                     loop_cpu_ms=(read.loop_cpu_ns - last.loop_cpu_ns) * 1e-6,
                     cpu_ms=(read.cpu_ns - last.cpu_ns) * 1e-6,
                     gc_ms=(read.gc_ns - last.gc_ns) * 1e-6,
                     nivcsw=read.nivcsw - last.nivcsw,
                     majflt=read.majflt - last.majflt)
        last = read
        if now - beat_t0 >= LOOP_BEAT_NS:
            lags.sort()
            add_span("loop.beat", beat_t0, now, trace_id=PROCESS_TRACE,
                     loop=loop_name, ticks=len(lags),
                     lag_med_ms=lags[len(lags) // 2] * 1e-6,
                     lag_max_ms=lags[-1] * 1e-6,
                     cpu_ms=(read.cpu_ns - beat.cpu_ns) * 1e-6,
                     loop_cpu_ms=(read.loop_cpu_ns - beat.loop_cpu_ns) * 1e-6,
                     gc_ms=(read.gc_ns - beat.gc_ns) * 1e-6)
            lags.clear()
            beat, beat_t0 = read, now


def loop_monitor(loop_name: str):
    """An aiohttp ``cleanup_ctx`` entry: the application's event loop
    times its own wake-ups from start-up to clean-up (``loop.stall`` and
    ``loop.beat`` spans, ``pio_event_loop_*``). Servers append it where
    they append :func:`trace_middleware`; with ``PIO_METRICS=0`` no task
    is started."""

    async def _ctx(app):
        if not _STATE.metrics_on:
            yield
            return
        task = asyncio.get_running_loop().create_task(
            _monitor_loop(loop_name), name=f"pio-loop-monitor-{loop_name}")
        try:
            yield
        finally:
            task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await task

    return _ctx


# ---------------------------------------------------------------------------
# sampled request tracing: which requests' spans also go to the sink
# ---------------------------------------------------------------------------

class Trace:
    """One sampled request: collects its finished spans, flushed once
    at the end.

    Spans are buffered in-process and written as JSON lines in one
    flush so a trace's spans land contiguously in the sink even under
    concurrent requests. A span that ends after the flush (a worker
    that overran its deadline) is written on its own."""

    __slots__ = ("trace_id", "_recorder", "_spans", "_lock")

    def __init__(self, trace_id: str, recorder: "TraceRecorder"):
        self.trace_id = trace_id
        self._recorder = recorder
        self._spans: Optional[list[Span]] = []
        self._lock = threading.Lock()

    def add(self, rec: Span) -> None:
        with self._lock:
            if self._spans is not None:
                self._spans.append(rec)
                return
        self._recorder.emit([rec])

    def flush(self) -> None:
        with self._lock:
            spans, self._spans = self._spans, None
        if spans:
            self._recorder.emit(spans)


def _sink_line(rec: Span) -> str:
    line = {
        "traceId": rec.trace_id,
        "span": rec.name,
        "startUs": (rec.t0_ns + _EPOCH_OFFSET_NS) // 1000,
        "durUs": (rec.t1_ns - rec.t0_ns) // 1000,
        "spanId": rec.span_id,
        "parentId": rec.parent_id,
    }
    if rec.tags:
        line["tags"] = rec.tags
    return json.dumps(line, separators=(",", ":")) + "\n"


class TraceRecorder:
    """``PIO_TRACE``-rate span sampler writing JSON lines to a sink.

    ``PIO_TRACE``: unset/0 → off; ``1``/``on`` → every request; a
    float in (0, 1) → that sampling probability. ``PIO_TRACE_SINK``:
    a file path (lines appended under a lock) or ``stderr`` (default).
    With tracing enabled, an incoming ``X-Pio-Trace-Id`` skips the
    probability roll — the upstream tier already decided this request
    is worth following. With ``PIO_TRACE`` unset/0 the header is
    ignored: off means off, clients cannot force span writes."""

    def __init__(self, rate: Optional[float] = None,
                 sink: Optional[str] = None):
        if rate is None:
            raw = envknobs.env_str("PIO_TRACE", "")
            if raw in ("", "0", "off", "false", "no"):
                rate = 0.0
            elif raw in ("1", "on", "true", "yes"):
                rate = 1.0
            else:
                rate = envknobs.env_float("PIO_TRACE", 0.0)
        self.rate = max(0.0, min(1.0, float(rate)))
        self.sink = (sink
                     or envknobs.env_str("PIO_TRACE_SINK", "", lower=False)
                     or "stderr")
        self._lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        return self.rate > 0.0

    def sample(self, incoming_id: Optional[str] = None) -> Optional[Trace]:
        if not self.rate:
            return None
        if incoming_id:
            return Trace(incoming_id[:64], self)
        if self.rate < 1.0 and random.random() >= self.rate:
            return None
        return Trace(uuid.uuid4().hex[:16], self)

    def emit(self, spans: list[Span]) -> None:
        data = "".join(_sink_line(s) for s in spans)
        try:
            with self._lock:
                if self.sink == "stderr":
                    sys.stderr.write(data)
                else:
                    with open(self.sink, "a", encoding="utf-8") as f:
                        f.write(data)
        except OSError:  # noqa: PERF203 - a dead sink must not fail requests
            pass


_TRACER: Optional[TraceRecorder] = None
_TRACER_LOCK = threading.Lock()


def _tracer() -> TraceRecorder:
    global _TRACER
    if _TRACER is None:
        with _TRACER_LOCK:
            if _TRACER is None:
                _TRACER = TraceRecorder()
    return _TRACER


def configure_tracer(rate: Optional[float] = None,
                     sink: Optional[str] = None) -> TraceRecorder:
    """(Re)build the process tracer — re-reads PIO_TRACE / PIO_TRACE_SINK
    for arguments left None. Tests and `pio` verbs use this after
    changing the environment."""
    global _TRACER
    with _TRACER_LOCK:
        _TRACER = TraceRecorder(rate, sink)
        return _TRACER


def sample_trace(incoming_id: Optional[str] = None) -> Optional[Trace]:
    """Sampling decision for one request (None → not traced)."""
    return _tracer().sample(incoming_id)


def current_trace() -> Optional[Trace]:
    """The active request's Trace, if sampled. Propagates across
    ``asyncio.to_thread`` (contextvars are copied into the executor),
    which is how the serving stages inside ``Deployment.query`` see
    the trace the HTTP layer started."""
    return _TRACE_VAR.get()


def activate_trace(tr: Trace):
    return _TRACE_VAR.set(tr)


def deactivate_trace(token) -> None:
    _TRACE_VAR.reset(token)


def trace_middleware():
    """aiohttp middleware: open the request's root span (the ring sees
    every request while metrics are on), sample it for the sink, bind
    the trace into the handler's context, stamp ``X-Pio-Trace-Id`` on a
    sampled response and flush. Servers append this to their middleware
    list."""
    from aiohttp import web

    @web.middleware
    async def _trace_mw(request, handler):
        tr = sample_trace(request.headers.get(TRACE_HEADER))
        token = activate_trace(tr) if tr is not None else None
        try:
            with span(f"http {request.method} {request.path}") as root:
                status = 500
                try:
                    resp = await handler(request)
                    status = resp.status
                    if tr is not None:
                        resp.headers[TRACE_HEADER] = tr.trace_id
                    return resp
                except web.HTTPException as e:
                    status = e.status
                    if tr is not None:
                        e.headers[TRACE_HEADER] = tr.trace_id
                    raise
                finally:
                    root.tag(status=status)
        finally:
            if tr is not None:
                deactivate_trace(token)
                tr.flush()

    return _trace_mw
