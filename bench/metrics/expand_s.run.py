"""Seconds per query in the expansion half of exploration: the program's
``stage_times["expand"]`` (`repro.core.engine` ``_explore``: from each
STwig's expansion dispatch to the host read of its root count), averaged
over the traced window's queries. A query that escalated reports its last
attempt; a program without the stage gives nothing."""


def read(run):
    t = [q.stats.stage_times["expand"] for q in run.queries
         if "expand" in q.stats.stage_times]
    return sum(t) / len(t) if t else None
