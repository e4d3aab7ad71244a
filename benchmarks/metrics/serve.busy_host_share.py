"""Share of the window (first request's arrival to the last answer) in
which at least one request was inside the server and none was waiting on the
device (no ``topk.wait`` open): requests in the house, nothing given to the
device. What is left of ``device.idle_share.serve`` above it is an empty
server. Source: the program's own spans."""

import program_spans


def read(record):
    return program_spans.busy_host_share_percent(
        program_spans.request_trees(record))
