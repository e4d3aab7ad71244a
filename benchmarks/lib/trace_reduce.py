"""From a profiler trace (.xplane.pb) to what the per-layer metrics read:
the union of device-busy intervals, per-name device durations, the longest
idle gaps with the host span that was open, and the harness's own host spans.

The harness marks host spans with ``jax.profiler.TraceAnnotation`` under names
that start with ``bench:``, so they lie on the trace's own clock.
"""

from __future__ import annotations

import glob
import os

SPAN_PREFIX = "bench:"
#: the device line whose events are single operations; the other device
#: lines (steps, modules, framework ops) overlap it and are not added
OPS_LINE = "XLA Ops"
#: the device line whose events are whole executables (jitted programs)
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_trace(path: str) -> dict:
    """``{"devices": {plane: [(name, start_s, end_s), ...]}, "modules": the
    same for whole executables, "spans":
    [(name, start_s, end_s), ...], "lines": {plane: [line names]}}``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict[str, list] = {}
    modules: dict[str, list] = {}
    spans: list = []
    lines: dict[str, list] = {}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        lines[plane.name] = [ln.name for ln in plane.lines]
        for line in plane.lines:
            if is_device:
                if line.name == OPS_LINE:
                    ops = devices.setdefault(plane.name, [])
                elif line.name == MODULES_LINE:
                    ops = modules.setdefault(plane.name, [])
                else:
                    continue
                for e in line.events:
                    ops.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    return {"devices": devices, "modules": modules, "spans": spans,
            "lines": lines}


def merge_intervals(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps_of(busy: list[tuple[float, float]], lo: float, hi: float):
    """The idle intervals of [lo, hi] given the merged busy intervals."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def short_name(op_name: str) -> str:
    """An op as the breakdown shows it: the trace names a device op by its
    whole HLO line; what stands before `` = `` identifies it."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def self_seconds(ops) -> dict[str, list]:
    """Per short name ``[self seconds, count]``. The op line nests a loop's
    body inside the loop's own event: an op's self time is its duration less
    its children's, so the self times add up to the busy time."""
    out: dict[str, list] = {}
    stack: list[list] = []          # [name, end, self]

    def close(entry):
        acc = out.setdefault(entry[0], [0.0, 0])
        acc[0] += entry[2]
        acc[1] += 1

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([short_name(name), e, e - s])
    while stack:
        close(stack.pop())
    return out


def idle_by_span(gaps, spans) -> dict[str, float]:
    """Idle seconds by what the host was doing: each gap is cut at the span
    boundaries inside it, and each piece goes to the innermost (shortest)
    host span open over it, ``host`` where none is."""
    out: dict[str, float] = {}
    for lo, hi in gaps:
        cuts = {lo, hi}
        for _name, s, e in spans:
            cuts.update(t for t in (s, e) if lo < t < hi)
        cuts = sorted(cuts)
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            open_now = [(e - s, name) for name, s, e in spans if s <= mid < e]
            name = min(open_now)[1] if open_now else "host"
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce_trace(trace: dict, window_span: str) -> dict:
    """Busy and idle over the window that the span ``window_span`` marks.

    Returns ``busy_s`` (averaged over the device planes), ``window_s``,
    ``op_seconds`` {short op name: SELF seconds summed over planes / planes},
    ``op_counts``, ``device_ops`` and ``idle_gaps`` (at most 10 each, the
    longest first; gaps of the first device plane)."""
    windows = [(s, e) for name, s, e in trace["spans"] if name == window_span]
    if not windows:
        raise ValueError(f"the trace has no span {window_span!r}")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    planes = sorted(trace["devices"])
    if not planes:
        raise ValueError("the trace has no device plane with an "
                         f"{OPS_LINE!r} line: {trace['lines']}")
    busy_total = 0.0
    op_seconds: dict[str, float] = {}
    op_counts: dict[str, int] = {}
    first_gaps = []
    for n, plane in enumerate(planes):
        ops = [(name, max(s, lo), min(e, hi))
               for name, s, e in trace["devices"][plane]
               if min(e, hi) > max(s, lo)]
        busy = merge_intervals((s, e) for _, s, e in ops)
        busy_total += sum(e - s for s, e in busy)
        for name, (secs, count) in self_seconds(ops).items():
            op_seconds[name] = op_seconds.get(name, 0.0) + secs
            op_counts[name] = op_counts.get(name, 0) + count
        if n == 0:
            first_gaps = gaps_of(busy, lo, hi)
    n_planes = len(planes)
    op_seconds = {k: v / n_planes for k, v in op_seconds.items()}
    module_seconds: dict[str, float] = {}
    module_counts: dict[str, int] = {}
    for plane in trace.get("modules", {}):
        for name, s, e in trace["modules"][plane]:
            if min(e, hi) > max(s, lo):
                module_seconds[name] = (module_seconds.get(name, 0.0)
                                        + (min(e, hi) - max(s, lo)) / n_planes)
                module_counts[name] = module_counts.get(name, 0) + 1
    gap_by_span = idle_by_span(
        first_gaps, [sp for sp in trace["spans"] if sp[0] != window_span])
    top = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_total / n_planes,
        "window_s": hi - lo,
        "op_seconds": op_seconds,
        "op_counts": op_counts,
        "module_seconds": module_seconds,
        "module_counts": module_counts,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in sorted(
            gap_by_span.items(), key=lambda kv: -kv[1])[:10]],
        "n_device_planes": n_planes,
    }


def idle_share_percent(reduced: dict | None):
    """Share of the traced window in which no operation ran on the device;
    nothing where there is no trace or nothing ran (never 0 for a share)."""
    if not reduced or reduced["window_s"] <= 0 or reduced["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
