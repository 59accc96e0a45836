"""Typed query results: `MatchResult`, structured `MatchStats`, `MatchPage`.

The engines used to report execution details in an untyped ``stats`` dict;
these dataclasses make the schema explicit. ``MatchStats`` still supports
``stats["key"]`` access as a deprecation bridge for pre-facade callers —
it now emits `DeprecationWarning` on every use.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.deprecation import warn_dict_stats_access


@dataclasses.dataclass
class MatchStats:
    """Execution statistics for one query run.

    Per-STwig lists are indexed in exploration (Algorithm 2) order.
    `repro.runtime.spans` fills ``stage_times`` and ``host_syncs``; a run
    through `repro.runtime.resilience.adaptive_run` sums ``host_syncs``
    over all its attempts and times each in ``attempt_s``.
    """

    backend: str = "local"             # "local" | "sharded"
    time_s: float = 0.0
    retries: int = 0                   # adaptive capacity-growth re-runs
    # why a partial result is partial — a `repro.runtime.resilience
    # .DegradeReason` value string ("deadline" | "budget" |
    # "overflow-ceiling" | "shard-fault"); None for complete results and
    # for plain first-K truncation (adaptive=False is semantics, not
    # degradation)
    degrade_reason: str | None = None
    # wall seconds per execution stage ("explore", and within it "expand"
    # and "emit"; "fetch", "join", "materialize"), accumulated across
    # blocks on the streaming path
    stage_times: dict[str, float] = dataclasses.field(default_factory=dict)
    # per-shard health after chaos/fault handling: shard -> "ok" | "slow" |
    # "dead" | "recovered" | "truncated" (sharded backend only)
    shard_health: dict[int, str] = dataclasses.field(default_factory=dict)
    # the grow-able capacities the final (possibly escalated) plan ran at
    final_caps: dict[str, int] = dataclasses.field(default_factory=dict)
    # fetch attempts beyond the first while recovering from shard faults
    fetch_retries: int = 0
    # block-parameterized join steps this query executed on the streaming
    # path (0 on one-shot runs); per-query — the engines' cumulative
    # `join_block_calls` counters sum these across all streams. The query
    # server's scheduler accounts its join quanta with this field.
    join_blocks: int = 0
    rounds: list[int] = dataclasses.field(default_factory=list)
    stwig_rows: list[int] = dataclasses.field(default_factory=list)
    # matching roots per STwig; both backends populate it (sharded reports
    # the max over shards — the shard that drives the round count)
    stwig_roots: list[int] = dataclasses.field(default_factory=list)
    # rows the STwig expansions ran over, summed over the STwigs: each
    # STwig's root candidates as its plan sized them (per shard)
    expand_rows: int = 0
    join_order: list[tuple[int, ...]] = dataclasses.field(default_factory=list)
    n_shards: int = 1
    # device-to-host reads on the query path (`repro.runtime.spans.host_get`)
    host_syncs: int = 0
    # wall seconds of each adaptive attempt, the back-off sleep after it
    # included; the last is the returned attempt, so sum(attempt_s[:-1]) is
    # what escalation threw away. Empty when no attempt ran.
    attempt_s: list[float] = dataclasses.field(default_factory=list)

    # -------- deprecation bridge: the old dict-style access keeps working,
    # but warns — `tests/test_api.py` pins the warning
    def __getitem__(self, key: str):
        warn_dict_stats_access(key)
        try:
            return getattr(self, key)
        except AttributeError:
            raise KeyError(key) from None

    def get(self, key: str, default=None):
        warn_dict_stats_access(key)
        return getattr(self, key, default)


def first_k_met(plan, rows: np.ndarray) -> bool:
    """True when a first-K plan (``max_matches`` K > 0) has its K rows. Its
    answer is then whole even if a capacity overflowed on the way: an
    overflow only drops matches, and the rows found are K real ones."""
    return bool(plan.max_matches) and rows.shape[0] >= plan.max_matches


@dataclasses.dataclass
class MatchResult:
    rows: np.ndarray          # (n_matches, n_qnodes) ORIGINAL node ids
    n_matches: int
    # False if a capacity overflowed (a partial set) before a first-K
    # query found its K rows
    complete: bool
    stats: MatchStats

    @property
    def degrade_reason(self) -> str | None:
        """Typed reason this result is partial (None when complete or when
        partial is first-K semantics, not degradation)."""
        return self.stats.degrade_reason


@dataclasses.dataclass
class MatchPage:
    """One page of a streaming (first-K, pipelined) run."""

    rows: np.ndarray          # (n_rows, n_qnodes) ORIGINAL node ids
    index: int                # 0-based page number
    complete: bool            # False if this page's block overflowed a cap
    # the query-level stats object, shared by every page of one stream
    # (retries, final caps, stage times, shard health accumulate there)
    stats: "MatchStats | None" = None

    @property
    def n_rows(self) -> int:
        return int(self.rows.shape[0])
