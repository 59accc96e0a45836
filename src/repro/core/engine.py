"""Single-shard subgraph matching engine (the per-machine executor).

Orchestration is host-side (the paper's query proxy); every dense step is a
jitted JAX function keyed by its static plan spec in a session-owned
`ExecutableCache`. The distributed engine (`repro.core.dist`) wraps the same
match/join steps in ``shard_map``.

.. deprecated::
    Constructing `SubgraphMatcher` directly is deprecated — open a
    `repro.api.GraphSession` instead; it selects the backend, owns the
    executable cache, and exposes the compile/run split.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import join as join_lib
from repro.core.backend import Kernels, n_words, resolve_kernels
from repro.core.cache import ExecutableCache
from repro.core.deprecation import warn_direct_construction
from repro.core import match as match_lib
from repro.core.match import Bindings, ShardGraph, STwigTable, apply_binding_update
from repro.core.plan import QueryPlan, STwigSpec, caps_from_plan, make_plan
from repro.core.query import QueryGraph
from repro.core.result import MatchPage, MatchResult, MatchStats, first_k_met
from repro.core.stream import stream_blocks
from repro.graphstore.partition import PartitionedGraph
from repro.runtime.resilience import RetryPolicy, adaptive_run, grow_caps
from repro.runtime.spans import host_get, span, stage

__all__ = [
    "MatchResult",
    "MatchStats",
    "MatchPage",
    "SubgraphMatcher",
    # canonical homes are repro.runtime.resilience / repro.core.plan;
    # re-exported here for the engine-level callers that always used them
    "grow_caps",
    "caps_from_plan",
]


def _concat_tables(tables: list[STwigTable]) -> join_lib.JoinTable:
    """Concatenate per-round tables into one join input (host-orchestrated).

    The concatenated capacity is ``n_rounds * spec.rows_cap`` — deliberately
    larger than the per-round plan capacity: rounds exist precisely so one
    round's block never overflows, and the join phase's own ``out_cap``
    bounds everything downstream.
    """
    cols = jnp.concatenate([t.cols for t in tables], axis=0)
    valid = jnp.concatenate([t.valid for t in tables], axis=0)
    n_rows = sum((t.n_rows for t in tables), jnp.int32(0))
    overflow = functools.reduce(
        jnp.logical_or, [t.overflow for t in tables], jnp.bool_(False)
    )
    return join_lib.JoinTable(cols=cols, valid=valid, n_rows=n_rows, overflow=overflow)


def stwig_step_fns(spec: STwigSpec, kernels: Kernels, n_words_: int):
    """The two halves of one STwig step as the local engine jits them:
    ``expand(g, bind)`` once per STwig, then ``emit(g, exp, round_idx=r)``
    per round of roots (a round then costs its roots, not another pass
    over every edge). Each is a named function, so that its device program
    is named after it (``jit_expand_stwig_shard``, ``jit_emit_stwig_round``)."""

    def expand_stwig_shard(g, bind):
        return match_lib.expand_stwig_shard(g, bind, spec=spec, kernels=kernels)

    def emit_stwig_round(g, exp, round_idx):
        return match_lib.emit_stwig_round(
            g, exp, spec=spec, round_idx=round_idx, n_words_=n_words_,
            kernels=kernels,
        )

    return expand_stwig_shard, emit_stwig_round


@dataclasses.dataclass(eq=False)
class _LocalStreamState:
    """Per-query stream state for the local backend: exploration ran once,
    tables/schemas/order are fixed, and blocks of the first table in join
    order are joined lazily by `SubgraphMatcher._stream_block`."""

    plan: QueryPlan
    stats: MatchStats
    tables: list
    schemas: list
    order: tuple[int, ...]
    explore_overflow: bool
    cap: int  # row capacity of the blocked table (the block loop bound)
    valid_host: np.ndarray  # (cap,) host bool mask of the blocked table


class SubgraphMatcher:
    """Single-device matcher over a (possibly 1-shard) partitioned graph."""

    def __init__(
        self,
        pg: PartitionedGraph,
        shard: int = 0,
        *,
        cache: ExecutableCache | None = None,
        kernels: "str | Kernels | None" = None,
        chaos=None,
    ):
        warn_direct_construction("SubgraphMatcher")
        assert 0 <= shard < pg.n_shards
        self.pg = pg
        self.cache = cache if cache is not None else ExecutableCache()
        # the kernel backend every dense step draws from; reassignable at
        # any time — executables are keyed by (static spec, kernels.name),
        # so switching backends mid-session cannot poison the cache
        self.kernels = resolve_kernels(kernels)
        # optional seeded fault injector (repro.runtime.chaos). The local
        # backend has no fetches, so only slow-step delays and forced
        # overflow apply; the wrapped kernels' distinct name keeps chaos
        # executables out of clean cache entries.
        self.chaos = chaos
        if chaos is not None:
            self.kernels = chaos.wrap_kernels(self.kernels)
        # cumulative device invocations of the per-block join chain (the
        # streaming path); lets callers assert early-stopped streams skip work
        self.join_block_calls = 0
        self.g = ShardGraph(
            labels=jnp.asarray(pg.labels[shard]),
            indptr=jnp.asarray(pg.indptr[shard]),
            indices=jnp.asarray(pg.indices[shard]),
            n_local=jnp.int32(pg.n_local[shard]),
            nodes_by_label=jnp.asarray(pg.nodes_by_label[shard]),
            label_indptr=jnp.asarray(pg.label_indptr[shard]),
            shard_id=jnp.int32(shard),
            all_labels=jnp.asarray(pg.all_labels),
        )

    # -------------------------------------------------- cached executables
    def _match_fns(self, spec: STwigSpec):
        """The two jitted halves of one STwig (`stwig_step_fns`). Both are
        "match" entry points for the cost budgets."""
        kern = self.kernels
        n_w = n_words(self.pg.n_total + 1)
        return tuple(
            self.cache.get(
                ("match", spec, kern.name, half, n_w),
                lambda i=i: jax.jit(stwig_step_fns(spec, kern, n_w)[i]),
            )
            for i, half in enumerate(("expand", "emit"))
        )

    def _join_fn(
        self, schema_a, schema_b, out_cap: int, dup_cap: int,
        a_cap: int, b_cap: int,
    ):
        """Returns (jitted join fn, merged schema). The schema is static — it
        must not pass through jit. ``a_cap``/``b_cap`` are the operand table
        capacities: they shape the traced program (a blocked build side is
        narrower than a full table), so they belong to the logical key — one
        logical key, one trace."""
        kern = self.kernels

        def build():
            merged, _ = schema_a.merge(schema_b)

            def sort_merge_join(a, b):
                return join_lib.sort_merge_join(
                    a,
                    b,
                    schema_a,
                    schema_b,
                    out_cap=out_cap,
                    dup_cap=dup_cap,
                    kernels=kern,
                )[0]

            return jax.jit(sort_merge_join), merged

        return self.cache.get(
            ("join", schema_a, schema_b, out_cap, dup_cap, a_cap, b_cap,
             kern.name),
            build,
        )

    # ------------------------------------------------------------------ API
    def plan(self, query: QueryGraph, **kw) -> QueryPlan:
        return make_plan(query, self.pg.freq, **kw)

    def match(
        self,
        query: QueryGraph,
        plan: QueryPlan | None = None,
        *,
        adaptive: bool = True,
        max_retries: int = 6,
        guard: "QueryGuard | None" = None,
        retry_policy: "RetryPolicy | None" = None,
        **kw,
    ) -> MatchResult:
        """Match with adaptive capacity growth: if any block capacity
        overflows (paper §4.2: block sizes are set by available memory), the
        plan is re-made with doubled capacities and the query re-runs. When
        an explicit ``plan`` is given, escalation starts from that plan's
        caps (like `CompiledQuery.run`) instead of being disabled. With
        ``adaptive=False`` the first (possibly partial) result is returned
        with ``complete=False`` — the paper's first-K pipelined semantics.

        Escalation runs through `repro.runtime.resilience.adaptive_run`:
        ``guard`` bounds the query by deadline/memory budget at the retry
        boundaries, ``retry_policy`` adds jittered backoff and stops cap
        growth at the budgets.json byte ceiling — both optional, both
        defaulting to the historical behaviour (no deadline, checked-in
        ceiling)."""
        policy = retry_policy or RetryPolicy(max_retries=max_retries)
        plan0 = plan if plan is not None else self.plan(query, **kw)
        return adaptive_run(
            lambda: self._match_once(query, plan0),
            lambda caps: self._match_once(query, None, **caps),
            caps_from_plan(plan0, kw),
            n_qnodes=query.n_nodes,
            backend="local",
            policy=policy,
            guard=guard,
            adaptive=adaptive,
        )

    def match_stream(
        self,
        query: QueryGraph,
        plan: QueryPlan | None = None,
        *,
        block_rows: int = 1024,
        **kw,
    ) -> Iterator[MatchPage]:
        """Pipelined first-K execution (paper §6.1) — thin wrapper over the
        shared streaming driver (`repro.core.stream.stream_blocks`), kept
        for direct (deprecated) engine use. See the driver for the block
        semantics; both the local and sharded engines stream through it."""
        yield from stream_blocks(self, query, plan, block_rows=block_rows, **kw)

    # -------------------------------------------------- streaming interface
    def _stream_setup(
        self, query: QueryGraph, plan: QueryPlan | None = None, **kw
    ) -> "_LocalStreamState":
        """Run exploration once and pick the blocked (first-in-join-order)
        table; everything the per-block join step needs is returned as one
        reusable state object."""
        plan = plan or self.plan(query, **kw)
        stats = MatchStats(backend="local")
        with stage(stats, "explore"):
            tables, schemas, explore_overflow = self._explore(plan, stats)
        if self.chaos is not None and self.chaos.forced_overflow():
            explore_overflow = True
        order = tuple(join_lib.select_join_order(schemas, stats.stwig_rows))
        # probe-side compaction: every block join re-probes the non-blocked
        # tables, and probe cost scales with their capacity, not their row
        # count — shrink them once here (setup is already host-synced)
        for idx in order[1:]:
            tables[idx] = join_lib.compact_table(tables[idx], stats)
        first = tables[order[0]]
        return _LocalStreamState(
            plan=plan,
            stats=stats,
            tables=tables,
            schemas=schemas,
            order=order,
            explore_overflow=explore_overflow,
            cap=int(first.cols.shape[0]),
            # one host copy of the blocked table's validity: empty blocks are
            # then skipped without any per-block device round-trip
            valid_host=np.asarray(host_get(stats, first.valid)),
        )

    def _stream_block(
        self, state: "_LocalStreamState", lo: int, block_rows: int
    ) -> tuple[np.ndarray, bool]:
        """Join rows ``[lo, lo+block_rows)`` of the blocked table through the
        join chain and materialize the block's matches."""
        if not state.valid_host[lo : lo + block_rows].any():
            return np.zeros((0, state.plan.n_qnodes), np.int64), False
        if self.chaos is not None:
            d = self.chaos.block_delay()
            if d > 0:
                time.sleep(d)
        first = state.tables[state.order[0]]
        blk = join_lib.block_table(first, lo, block_rows)
        self.join_block_calls += 1
        state.stats.join_blocks += 1
        with stage(state.stats, "join"):
            acc, acc_schema = blk, state.schemas[state.order[0]]
            for idx in state.order[1:]:
                fn, merged = self._join_fn(
                    acc_schema,
                    state.schemas[idx],
                    state.plan.join_rows_cap,
                    state.plan.join_dup_cap,
                    int(acc.cols.shape[0]),
                    int(state.tables[idx].cols.shape[0]),
                )
                acc, acc_schema = fn(acc, state.tables[idx]), merged
        with stage(state.stats, "materialize"):
            rows = self._materialize(acc, acc_schema, 0, state.stats)
        return rows, bool(host_get(state.stats, acc.overflow))

    # ------------------------------------------------------ execution phases
    def _explore(
        self, plan: QueryPlan, stats: MatchStats
    ) -> tuple[list[join_lib.JoinTable], list[join_lib.Schema], bool]:
        """STwig exploration in Algorithm-2 order → per-STwig join inputs.

        Each STwig runs in ``repro.stwig``: the stage ``expand`` ends with
        the host read of its root count, so it holds the expansion the
        query waited for; the stage ``emit`` holds every round
        (``repro.round``), each ending with the host read of its row
        count."""
        n_bits = self.pg.n_total + 1
        bind = Bindings.fresh(plan.n_qnodes, n_bits)
        tables: list[join_lib.JoinTable] = []
        schemas: list[join_lib.Schema] = []
        overflow = False
        for i, spec in enumerate(plan.specs):
            expand, emit = self._match_fns(spec)
            rows = spec.expand_rows(self.g.cap)
            stats.expand_rows += rows
            with span(
                "stwig", i=i, root_rows=rows, node_slots=self.g.cap
            ) as stwig_span:
                with stage(stats, "expand"):
                    exp = expand(self.g, bind)
                    n_roots = int(host_get(stats, exp.n_roots))
                stwig_span.set_metadata(n_roots=n_roots)
                round_tables: list[STwigTable] = []
                contrib = None
                r = 0
                with stage(stats, "emit"):
                    while True:
                        with span("round", r=r):
                            table, c = emit(self.g, exp, round_idx=jnp.int32(r))
                            # a round's valid rows lead its table: keep only
                            # those (rounds of a hub-heavy STwig are mostly
                            # empty, and rounds x rows_cap would not fit the
                            # device)
                            n = int(host_get(stats, table.n_rows))
                        if n or not round_tables:
                            keep = join_lib.fitted_cap(n, spec.rows_cap)
                            round_tables.append(table._replace(
                                cols=table.cols[:keep], valid=table.valid[:keep]
                            ))
                        cw = c.words
                        contrib = cw if contrib is None else jnp.bitwise_or(contrib, cw)
                        r += 1
                        if r * spec.root_cap >= n_roots:
                            break
                bind = apply_binding_update(bind, spec, contrib)
                jt = _concat_tables(round_tables)
                tables.append(jt)
                schemas.append(
                    join_lib.Schema(
                        qnodes=spec.qnodes,
                        qlabels=(spec.root_label,) + spec.child_labels,
                    )
                )
                stats.stwig_rows.append(int(host_get(stats, jt.n_rows)))
                stats.stwig_roots.append(
                    int(host_get(stats, round_tables[0].n_roots)))
                stats.rounds.append(r)
                overflow |= bool(host_get(stats, jt.overflow))
        return tables, schemas, overflow

    def _materialize(
        self, acc: join_lib.JoinTable, acc_schema: join_lib.Schema,
        max_matches: int, stats: MatchStats,
    ) -> np.ndarray:
        """Device join table → host rows of ORIGINAL ids in query-node order."""
        cols = np.asarray(host_get(stats, acc.cols))
        valid = np.asarray(host_get(stats, acc.valid))
        rows_new = cols[valid]
        if max_matches and rows_new.shape[0] > max_matches:
            rows_new = rows_new[:max_matches]
        perm = np.argsort(np.asarray(acc_schema.qnodes))
        rows_new = rows_new[:, perm]
        rows_old = np.where(
            rows_new < self.pg.n_total,
            self.pg.new_to_old[np.minimum(rows_new, self.pg.n_total - 1)],
            -1,
        )
        return rows_old.astype(np.int64)

    def _match_once(
        self,
        query: QueryGraph,
        plan: QueryPlan | None = None,
        retry_policy=None,  # fetch recovery is a sharded concern; accepted
        # so the facade drives both engines uniformly
        **kw,
    ) -> MatchResult:
        t0 = time.perf_counter()
        plan = plan or self.plan(query, **kw)
        stats = MatchStats(backend="local")
        with stage(stats, "explore"):
            tables, schemas, overflow = self._explore(plan, stats)
        if self.chaos is not None and self.chaos.forced_overflow():
            overflow = True

        # ---- join phase ----------------------------------------------------
        with stage(stats, "join"):
            tables = [join_lib.compact_table(t, stats) for t in tables]
            order = join_lib.select_join_order(schemas, stats.stwig_rows)
            acc, acc_schema = tables[order[0]], schemas[order[0]]
            for idx in order[1:]:
                fn, merged = self._join_fn(
                    acc_schema, schemas[idx], plan.join_rows_cap,
                    plan.join_dup_cap,
                    int(acc.cols.shape[0]), int(tables[idx].cols.shape[0]),
                )
                acc, acc_schema = fn(acc, tables[idx]), merged
            overflow |= bool(host_get(stats, acc.overflow))

        # ---- materialize (original ids, query-node column order) ----------
        with stage(stats, "materialize"):
            rows_old = self._materialize(acc, acc_schema, plan.max_matches, stats)
        stats.join_order = [tuple(schemas[i].qnodes) for i in order]
        stats.time_s = time.perf_counter() - t0
        return MatchResult(
            rows=rows_old,
            n_matches=int(rows_old.shape[0]),
            complete=first_k_met(plan, rows_old) or not overflow,
            stats=stats,
        )
