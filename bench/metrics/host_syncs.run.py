"""Device-to-host reads per query: the program's ``MatchStats.host_syncs``
(`repro.runtime.spans.host_get`), summed over every attempt of a query
that escalated, averaged over the traced window's queries. A program
without the counter gives nothing."""


def read(run):
    n = [s for q in run.queries
         if (s := getattr(q.stats, "host_syncs", None)) is not None]
    return sum(n) / len(n) if n else None
