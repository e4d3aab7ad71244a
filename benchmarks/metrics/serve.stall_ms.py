"""Milliseconds of the window in which the engine server's event loop could
not run: the summed ``loop.stall`` spans (a wake-up 5 ms or more late, from
when it was due to when it ran) between the first request's arrival and the
last answer. Each span's tags say why (``gc_ms``, ``loop_cpu_ms``,
``cpu_ms``); 0.0 where nothing stalled. Source: the program's own loop
monitor."""

import runtime_spans


def read(record):
    return runtime_spans.stall_ms(record)
