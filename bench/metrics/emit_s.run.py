"""Seconds per query in the emission half of exploration: the program's
``stage_times["emit"]`` (`repro.core.engine` ``_explore``: every STwig's
round loop, each round to the host read of its row count), averaged over
the traced window's queries. A query that escalated reports its last
attempt; a program without the stage gives nothing."""


def read(run):
    t = [q.stats.stage_times["emit"] for q in run.queries
         if "emit" in q.stats.stage_times]
    return sum(t) / len(t) if t else None
