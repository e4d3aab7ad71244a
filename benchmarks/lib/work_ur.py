"""The bytes and operations a Universal Recommender query NEEDS, from the
query and the data alone: what ``ur.score_roofline`` and
``ur.serve_step_mfu`` divide by.

Counted: 8 B for every (item, slot) of the indicators whose correlator is
in the query's history of that event type (the item's row and the float32
score: whatever the layout, an answer cannot be right without reading each
of them, and nothing else of the 7.5 GB), one multiply-add for each, and
8 B an item returned. For a query without history: 8 B for each of the
``num`` most popular items returned.

NOT counted, because an implementation merely chooses them: offsets or any
other index structure, a dense score row of the catalog's length (its
zeroing, the passes of the rules and of the selection over it), padding to a
ladder step, the slots of rows the history does not name. A share computed
from these counts therefore cannot pass 100% by over-counting: an engine
that touched only what is counted would read 100%.

``WINDOW`` is filled by the deployment file when it makes a window's
request bodies (it knows each request's history and each item's posting
length): ``postings[k]`` and ``path[k]`` (``history`` or ``backfill``) of
the k-th request of the schedule, and ``program_postings_before``, what the
program's own counter `POSTINGS_READ` read at that moment (None where the
program has none).
"""

from __future__ import annotations

import work

WINDOW: dict = {}

#: the program's count of the index entries its scoring calls read
POSTINGS_READ = "pio_ur_postings_read_total"
BYTES_A_POSTING = 8
BYTES_AN_ANSWER = 8


def query_work(postings: int, num: int) -> dict:
    """{"bytes", "flops"} one query needs: ``postings`` slots name a row of
    its history, ``num`` items are returned."""
    return {"bytes": BYTES_A_POSTING * postings + BYTES_AN_ANSWER * num,
            "flops": 2.0 * postings}


def least_seconds(postings: int, num: int, peaks: dict) -> float:
    w = query_work(postings, num)
    return work.roofline_seconds(w["flops"], w["bytes"], peaks)["seconds"]
