"""95th percentile of how late the load generator sent a request (actual send
minus due): a starved generator must not read as a fast server. Source: the
load generator's own clock."""

import loadgen


def read(record):
    summary = record.window.get("summary")
    if not summary:
        return None
    return loadgen.percentile(summary["late_ms"], 95)
