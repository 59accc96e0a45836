"""Where a query's time goes: spans on the profiler's clock, stage times
and a host-sync count in its `MatchStats`.

Every span is a `jax.profiler.TraceAnnotation` named ``repro.<name>``. It
lands on the trace's host plane, on the same clock as the device's
``XLA Ops``, and costs under a microsecond per enter and exit when no
profiler session is active, so spans are always on.

  * `query` draws a query's id (``qid``) and opens ``repro.query``; every
    span opened inside it, on the same thread, carries that ``qid``.
    `scope` puts spans under an id already drawn (a stream's setup and
    its later blocks).
  * `span(name, **meta)` is a span and nothing else.
  * `stage(stats, name)` is a span that also adds its wall seconds to
    ``stats.stage_times[name]`` (re-entrant: times add up).
  * `host_get(stats, x)` is the query path's one device-to-host read: a
    ``jax.device_get`` counted in ``stats.host_syncs``.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import time

import jax

__all__ = ["host_get", "new_qid", "query", "scope", "span", "stage"]

PREFIX = "repro."

_QIDS = itertools.count(1)
_QID: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "repro_qid", default=None
)


def new_qid() -> int:
    """The next query id of this process."""
    return next(_QIDS)


@contextlib.contextmanager
def scope(qid: int):
    """Spans opened inside carry ``qid``."""
    token = _QID.set(qid)
    try:
        yield
    finally:
        _QID.reset(token)


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """The span ``repro.<name>``, with ``meta`` and the current query's id
    as its metadata. ``set_metadata`` on the entered span adds values known
    only later."""
    qid = _QID.get()
    if qid is not None:
        meta["qid"] = qid
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


@contextlib.contextmanager
def query():
    """``repro.query`` around one query, under a fresh id."""
    with scope(new_qid()), span("query"):
        yield


@contextlib.contextmanager
def stage(stats, name: str):
    """``repro.<name>``, its wall seconds added to
    ``stats.stage_times[name]``."""
    t0 = time.perf_counter()
    try:
        with span(name):
            yield
    finally:
        stats.stage_times[name] = (
            stats.stage_times.get(name, 0.0) + time.perf_counter() - t0
        )


def host_get(stats, x):
    """``jax.device_get(x)``, counted as one host sync of ``stats``'
    query. A pytree ``x`` is read in one sync."""
    stats.host_syncs += 1
    return jax.device_get(x)
