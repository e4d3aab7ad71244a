"""Share of the traced run_train in which no operation ran on the device.
Source: the device trace (busy union over the traced window)."""

import trace_reduce


def read(record):
    return trace_reduce.idle_share_percent(record.trace)
