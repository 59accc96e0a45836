"""Seconds per query in exploration: the program's ``stage_times["explore"]``
(`repro.core.engine` ``_explore``), averaged over the traced window's
queries. A query that escalated reports its last attempt."""


def read(run):
    t = [q.stats.stage_times["explore"] for q in run.queries
         if "explore" in q.stats.stage_times]
    return sum(t) / len(t) if t else None
