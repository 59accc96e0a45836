"""Seconds per query that adaptive escalation threw away: the program's
``MatchStats.attempt_s`` (`repro.runtime.resilience.adaptive_run`, each
attempt with the back-off after it) less its last, returned, attempt,
averaged over the traced window's queries. A program without the field
gives nothing."""


def read(run):
    t = [sum(a[:-1]) for q in run.queries
         if (a := getattr(q.stats, "attempt_s", None))]
    return sum(t) / len(t) if t else None
