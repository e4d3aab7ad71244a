"""Summarize CPU/TPU crossover sweeps from BASELINE.json.

Reads the ``measured_{cpu,tpu}_sweep_{classification,text}`` entries
that ``PIO_BENCH_SWEEP=...`` runs of bench_templates.py persist, prints
a side-by-side table per config with the speedup at each ladder point,
and names the crossover (first point where the accelerator wins).

Usage: python tools/crossover.py [BASELINE.json]
"""

from __future__ import annotations

import json
import sys


def summarize(doc: dict) -> str:
    pub = doc.get("published", {})
    lines = []
    for sweep in ("classification", "text"):
        cpu = pub.get(f"measured_cpu_sweep_{sweep}")
        acc_name = "tpu"
        acc = pub.get(f"measured_tpu_sweep_{sweep}")
        if not cpu or not acc:
            lines.append(f"## {sweep}: sweep incomplete "
                         f"(cpu={'yes' if cpu else 'no'}, "
                         f"accel={'yes' if acc else 'no'})")
            continue
        shared = [p for p in cpu if p in acc]
        if not shared:
            lines.append(f"## {sweep}: CPU and {acc_name} sweeps share no "
                         "ladder points — re-run with matching "
                         "PIO_BENCH_SWEEP_POINTS")
            lines.append("")
            continue
        lines.append(f"## {sweep} (events-or-docs/sec/chip)")
        lines.append(f"| scale | CPU | {acc_name.upper()} | speedup |")
        lines.append("|---|---|---|---|")
        ratios = []
        for point in shared:
            c, a = cpu[point], acc[point]
            ratio = a / c if c else float("inf")
            ratios.append((point, ratio))
            lines.append(f"| {point} | {c:,.0f} | {a:,.0f} | {ratio:.2f}x |")
        # "wins from X upward" must be SUSTAINED: the earliest point
        # after which every later ladder point also wins — a single
        # early >1.0 followed by a dip is not a crossover.
        crossover = None
        for i, (point, _r) in enumerate(ratios):
            if all(r > 1.0 for _, r in ratios[i:]):
                crossover = point
                break
        if crossover is not None:
            lines.append(f"**Crossover: {acc_name.upper()} wins from "
                         f"{crossover} through the end of the measured "
                         "ladder.**")
        else:
            lines.append("**No sustained crossover in the measured ladder: "
                         "CPU wins at (or ties) the largest measured "
                         "points (publish this honestly).**")
        lines.append("")
    return "\n".join(lines)


if __name__ == "__main__":
    path = sys.argv[1] if len(sys.argv) > 1 else "BASELINE.json"
    with open(path) as f:
        print(summarize(json.load(f)))
