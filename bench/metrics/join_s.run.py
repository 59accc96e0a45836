"""Seconds per query in the join, host compaction included: the program's
``stage_times["join"]`` (`repro.core.join`), averaged over the traced
window's queries. A query that escalated reports its last attempt."""


def read(run):
    t = [q.stats.stage_times["join"] for q in run.queries
         if "join" in q.stats.stage_times]
    return sum(t) / len(t) if t else None
