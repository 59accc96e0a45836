"""Closed loop: the whole window, to the answer of the query in flight when
it closed, over the queries answered in it (host clock)."""


def read(run):
    return run.window_s / len(run.queries) if run.queries else None
