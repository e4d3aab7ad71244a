"""Wall seconds of rowblocks.plan_and_fill_both (the host layout of both
sides), averaged over the window's trains. Source: the harness's span."""


def read(record):
    fills = record.window_span_seconds("plan_and_fill_both")
    return sum(fills) / len(fills) if fills else None
