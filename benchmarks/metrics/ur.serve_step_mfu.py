"""The serving window's share of the chip: the least chip seconds for
every answered query's needed work (``work_ur``: 8 B and a multiply-add for
each slot that names a row of the query's history, 8 B an item returned;
the bytes bind) over the window's wall. Source: the program's counter
``pio_ur_postings_read_total`` since the window's bodies were made (the
deployment notes it there), held against the deployment's own count of what
the answered requests need from the data alone: where the program read more
than that (it always reads the load generator's few unmeasured requests
before the window besides), the needed count stands, so the share counts
neither work that was not done nor work that no answer needs. Nothing where
the program has no such counter."""

import store_spans
import work_ur


def read(record):
    win = record.window
    needed, before = (work_ur.WINDOW.get(k)
                      for k in ("postings", "program_postings_before"))
    now = store_spans.counter_value(work_ur.POSTINGS_READ)
    if not record.peaks or "summary" not in win or None in (needed, before,
                                                            now):
        return None
    job, res = win["job"], win["result"]
    if len(needed) != len(res["status"]):
        return None
    answered = [(p, int(num)) for p, num, status in zip(
        needed, job["num"], res["status"]) if status == 200]
    postings = min(int(now - before), sum(p for p, _ in answered))
    least = work_ur.least_seconds(postings, sum(n for _, n in answered),
                                  record.peaks)
    return 100.0 * least / win["wall_s"] if answered else None
