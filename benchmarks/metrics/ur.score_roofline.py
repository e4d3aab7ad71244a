"""The scoring call's share of its roofline: the least seconds for the
needed bytes of a history-scored query (``work_ur``; the mean over the
window's answered ones) over the mean device time of one call's two
executables, ``jit__ur_score`` (the sums) and ``jit__ur_rank`` (the rules
and the selection), in the traced seconds; a backfill's ``jit__ur_rank``
is taken out by the calls' counts. Nothing where the trace holds no such
module. Source: the device trace's module line."""

import re

import work_ur

SCORE = re.compile(r"jit__ur_score\b")
RANK = re.compile(r"jit__ur_rank\b")


def read(record):
    needed, path = (work_ur.WINDOW.get(k) for k in ("postings", "path"))
    win = record.window
    if not record.trace or not record.peaks or needed is None \
            or "result" not in win:
        return None

    def module(pattern):
        t = record.trace
        return (sum(v for n, v in t["module_seconds"].items()
                    if pattern.search(n)),
                sum(v for n, v in t["module_counts"].items()
                    if pattern.search(n)))

    (score_s, calls), (rank_s, ranks) = module(SCORE), module(RANK)
    scored = [work_ur.least_seconds(p, int(num), record.peaks)
              for p, num, how, status in zip(
                  needed, win["job"]["num"], path, win["result"]["status"])
              if status == 200 and how == "history"]
    if not calls or not ranks or score_s <= 0 or not scored:
        return None
    a_call = score_s / calls + rank_s / ranks
    return 100.0 * (sum(scored) / len(scored)) / a_call
