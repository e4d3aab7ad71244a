"""How late the engine server's event loop wakes a task that asked for it:
the median over the window's ``loop.beat`` spans (one a second) of
``lag_med_ms``, each the median of that second's twenty wake-ups. What every
hop of a request through the loop waits, stalls aside. Source: the program's
own loop monitor (``telemetry.loop_monitor``)."""

import program_spans
import runtime_spans


def read(record):
    return program_spans.median(
        runtime_spans.beat_values(record, "lag_med_ms"))
