"""Distributed, parallel subgraph matching (paper §4.3) via shard_map.

Each mesh shard along the ``data`` axis plays the role of one Trinity
machine: it owns one graph partition, explores STwigs over local roots in
parallel, contributes to the replicated binding bitsets with an OR
all-reduce, fetches remote STwig tables bounded by its load set (Theorem 4),
and joins locally. The head STwig (Theorem 5) is never fetched remotely, so
per-shard result sets are provably disjoint — the final union needs no
deduplication, exactly as in the paper.

Two join paths share that structure: one fused shard_map program for
one-shot `match` runs, and — for streaming (§6.1) — a run-once phase
(exploration + load-set fetch, results cached on device per query) followed
by a block-parameterized join step that joins only head rows ``[lo, lo+B)``
per shard_map call, so early-stopping consumers skip the remaining blocks.

.. deprecated::
    Constructing `DistributedMatcher` directly is deprecated — open a
    `repro.api.GraphSession` with ``backend="sharded"`` instead.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.core import join as join_lib
from repro.core.backend import Kernels, n_words, resolve_kernels
from repro.core.cache import ExecutableCache
from repro.core.collectives import fetch_load_set, or_allreduce
from repro.core.deprecation import warn_direct_construction
from repro.core.match import (
    Bindings,
    ShardGraph,
    emit_stwig_round,
    expand_stwig_shard,
)
from repro.core.plan import QueryPlan, STwigSpec, caps_from_plan, make_plan
from repro.core.query import QueryGraph
from repro.core.result import MatchPage, MatchResult, MatchStats, first_k_met
from repro.core.stream import stream_blocks
from repro.runtime.chaos import ShardFaultError
from repro.runtime.resilience import DegradeReason, RetryPolicy, adaptive_run
from repro.runtime.spans import host_get, span, stage
from repro.graphstore.cluster_graph import ClusterGraphIndex
from repro.graphstore.partition import PartitionedGraph

AXIS = "data"


class _StackedGraph:
    """Device-resident stacked per-shard graph arrays (leading axis = shard)."""

    def __init__(self, pg: PartitionedGraph, mesh: Mesh):
        sh = NamedSharding(mesh, P(AXIS))
        rep = NamedSharding(mesh, P())
        self.labels = jax.device_put(pg.labels, sh)
        self.indptr = jax.device_put(pg.indptr, sh)
        self.indices = jax.device_put(pg.indices, sh)
        self.n_local = jax.device_put(pg.n_local, sh)
        self.nodes_by_label = jax.device_put(pg.nodes_by_label, sh)
        self.label_indptr = jax.device_put(pg.label_indptr, sh)
        self.all_labels = jax.device_put(pg.all_labels, rep)

    def tree(self):
        return (
            self.labels,
            self.indptr,
            self.indices,
            self.n_local,
            self.nodes_by_label,
            self.label_indptr,
            self.all_labels,
        )


def _local_shard_graph(tree) -> ShardGraph:
    labels, indptr, indices, n_local, nodes_by_label, label_indptr, all_labels = tree
    return ShardGraph(
        labels=labels[0],
        indptr=indptr[0],
        indices=indices[0],
        n_local=n_local[0],
        nodes_by_label=nodes_by_label[0],
        label_indptr=label_indptr[0],
        shard_id=lax.axis_index(AXIS).astype(jnp.int32),
        all_labels=all_labels,
    )


@dataclasses.dataclass(eq=False)
class DistributedMatcher:
    """The multi-machine engine. Requires len(mesh.devices) == pg.n_shards."""

    pg: PartitionedGraph
    mesh: Mesh
    cgi: ClusterGraphIndex = None  # type: ignore[assignment]
    cache: ExecutableCache = None  # type: ignore[assignment]
    kernels: "str | Kernels | None" = None
    # optional seeded fault injector (repro.runtime.chaos): consulted at
    # the host-side fetch/join boundaries, never inside shard_map programs
    chaos: object = None

    def __post_init__(self):
        warn_direct_construction("DistributedMatcher")
        assert self.mesh.devices.size == self.pg.n_shards, (
            self.mesh.devices.size,
            self.pg.n_shards,
        )
        if self.cgi is None:
            self.cgi = ClusterGraphIndex.build(self.pg)
        if self.cache is None:
            self.cache = ExecutableCache()
        # kernel backend for every per-shard dense step; reassignable —
        # executables are keyed by (static spec, kernels.name)
        self.kernels = resolve_kernels(self.kernels)
        if self.chaos is not None:
            self.kernels = self.chaos.wrap_kernels(self.kernels)
        self._g = _StackedGraph(self.pg, self.mesh)
        self._rep = NamedSharding(self.mesh, P())
        # cumulative device invocations of the block-parameterized join step
        # (the streaming path); lets callers assert early stops skip work
        self.join_block_calls = 0

    # ------------------------------------------------------- jitted steps
    def _match_fns(self, spec: STwigSpec):
        """The two shard_map'd halves of one STwig, as on the local engine:
        expand once (each shard's expansion stays on its device), then
        emit per round of roots. Both are "dist_match" entry points."""
        name = self.kernels.name
        return (
            self.cache.get(
                ("dist_match", spec, name, "expand"),
                lambda: self._build_expand_step(spec),
            ),
            self.cache.get(
                ("dist_match", spec, name, "emit"),
                lambda: self._build_emit_step(spec),
            ),
        )

    def _build_expand_step(self, spec: STwigSpec):
        gspecs = (P(AXIS),) * 6 + (P(),)
        kern = self.kernels

        def body(tree, bind_words):
            g = _local_shard_graph(tree)
            exp = expand_stwig_shard(g, Bindings(bind_words), spec, kern)
            n_roots_max = lax.pmax(exp.n_roots, AXIS)
            return jax.tree.map(lambda x: x[None], exp), n_roots_max

        return jax.jit(
            shard_map(
                body,
                mesh=self.mesh,
                in_specs=(gspecs, P()),
                out_specs=(P(AXIS), P()),
                # Pallas kernels declare outputs without a varying axis,
                # which shard_map's static VMA check rejects
                check_vma=False,
            )
        )

    def _build_emit_step(self, spec: STwigSpec):
        gspecs = (P(AXIS),) * 6 + (P(),)
        kern = self.kernels
        n_w = n_words(self.pg.n_total + 1)

        def body(tree, exp, round_idx):
            g = _local_shard_graph(tree)
            exp = jax.tree.map(lambda x: x[0], exp)
            table, contrib = emit_stwig_round(
                g, exp, spec, round_idx, n_w, kernels=kern
            )
            contrib_w = or_allreduce(contrib.words, AXIS)
            overflow_any = lax.pmax(table.overflow.astype(jnp.int32), AXIS) > 0
            return (
                table.cols[None],
                table.valid[None],
                table.n_rows[None],
                contrib_w,
                overflow_any,
            )

        return jax.jit(
            shard_map(
                body,
                mesh=self.mesh,
                in_specs=(gspecs, P(AXIS), P()),
                out_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P()),
                # the OR-allreduce butterfly (ppermute) produces replicated
                # values shard_map's static VMA check cannot infer
                check_vma=False,
            )
        )

    def _join_step(
        self,
        schemas: tuple,
        order: tuple[int, ...],
        head_pos: int,
        out_cap: int,
        dup_cap: int,
        caps: tuple[int, ...],
        ring_radii: tuple[int, ...] | None = None,
    ):
        key = (
            "dist_join",
            schemas,
            order,
            head_pos,
            out_cap,
            dup_cap,
            caps,
            ring_radii,
            self.kernels.name,
        )
        return self.cache.get(
            key,
            lambda: self._build_join_step(
                schemas, order, head_pos, out_cap, dup_cap, ring_radii
            ),
        )

    def _build_join_step(
        self, schemas, order, head_pos, out_cap, dup_cap, ring_radii
    ):
        """One shard_map'd function running the whole join phase per shard.

        ``ring_radii`` (per STwig) selects the §Perf distance-bounded
        ppermute variant: bytes moved scale with the load-set radius instead
        of the cluster size (valid when the cluster graph is a ring — the
        engine checks applicability host-side)."""
        kern = self.kernels

        def body(tables, valids, load_masks):
            # tables[i]: (1, cap_i, w_i); load_masks: (1, T, S)
            load = load_masks[0]
            locs: list[join_lib.JoinTable] = []
            for i in range(len(schemas)):
                cols_i, valid_i = tables[i][0], valids[i][0]
                if i == head_pos:
                    cols_f, valid_f = cols_i, valid_i
                else:
                    cols_f, valid_f = fetch_load_set(
                        cols_i,
                        valid_i,
                        load[i],
                        AXIS,
                        ring_radius=None if ring_radii is None else ring_radii[i],
                    )
                locs.append(
                    join_lib.JoinTable(
                        cols=cols_f,
                        valid=valid_f,
                        n_rows=jnp.sum(valid_f, dtype=jnp.int32),
                        overflow=jnp.bool_(False),
                    )
                )
            acc, acc_schema = locs[order[0]], schemas[order[0]]
            for idx in order[1:]:
                acc, acc_schema = join_lib.sort_merge_join(
                    acc,
                    locs[idx],
                    acc_schema,
                    schemas[idx],
                    out_cap=out_cap,
                    dup_cap=dup_cap,
                    kernels=kern,
                )
            return acc.cols[None], acc.valid[None], acc.n_rows[None], acc.overflow[None]

        return jax.jit(
            shard_map(
                body,
                mesh=self.mesh,
                in_specs=((P(AXIS),) * len(schemas), (P(AXIS),) * len(schemas), P(AXIS)),
                out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
                # Pallas calls inside the mapped body defeat the static
                # replication check (same situation as the match step)
                check_vma=False,
            )
        )

    def _gather_step(
        self,
        n_tables: int,
        head_pos: int,
        caps: tuple[int, ...],
        ring_radii: tuple[int, ...] | None,
    ):
        key = ("dist_gather", n_tables, head_pos, caps, ring_radii)
        return self.cache.get(
            key, lambda: self._build_gather_step(n_tables, head_pos, ring_radii)
        )

    def _build_gather_step(self, n_tables, head_pos, ring_radii):
        """Fetch every non-head STwig table, bounded by the per-shard load
        sets (Theorem 4), in ONE shard_map program.

        Run once per streamed query: the fetched tables are kept on device
        and reused by every subsequent block-join call, so streaming pays
        the communication cost once, not per block. The head table is never
        fetched (Theorem 5) — that is what keeps per-shard pages disjoint.
        """

        def body(tables, valids, load_masks):
            load = load_masks[0]
            outs_c, outs_v = [], []
            for i in range(n_tables):
                if i == head_pos:
                    continue
                cols_f, valid_f = fetch_load_set(
                    tables[i][0],
                    valids[i][0],
                    load[i],
                    AXIS,
                    ring_radius=None if ring_radii is None else ring_radii[i],
                )
                outs_c.append(cols_f[None])
                outs_v.append(valid_f[None])
            return tuple(outs_c), tuple(outs_v)

        n_out = n_tables - 1
        return jax.jit(
            shard_map(
                body,
                mesh=self.mesh,
                in_specs=(
                    (P(AXIS),) * n_tables,
                    (P(AXIS),) * n_tables,
                    P(AXIS),
                ),
                out_specs=((P(AXIS),) * n_out, (P(AXIS),) * n_out),
            )
        )

    def _join_block_step(
        self,
        schemas: tuple,
        order: tuple[int, ...],
        out_cap: int,
        dup_cap: int,
        head_cap: int,
        gathered_caps: tuple[int, ...],
        block_rows: int,
    ):
        key = (
            "dist_join_block",
            schemas,
            order,
            out_cap,
            dup_cap,
            head_cap,
            gathered_caps,
            block_rows,
            self.kernels.name,
        )
        return self.cache.get(
            key,
            lambda: self._build_join_block_step(
                schemas, order, out_cap, dup_cap, block_rows
            ),
        )

    def _build_join_block_step(self, schemas, order, out_cap, dup_cap, block_rows):
        """The block-parameterized join step (paper §6.1 pipelining inside
        shard_map): join only head-table rows ``[lo, lo+block_rows)`` against
        the pre-fetched tables, one shard_map call per block.

        ``lo`` is a replicated traced scalar, so one trace (cached per
        (schemas, caps, block size) in the session's `ExecutableCache`)
        serves every block of the query — blocks differ only in data. The
        join order starts at the head STwig: blocks partition each shard's
        local head rows, every output row descends from exactly one of them,
        and the head is never fetched remotely (Theorem 5), so pages are
        disjoint within a shard and across shards.
        """
        head_pos = order[0]
        kern = self.kernels
        # position of each spec's table in the gathered (non-head) tuple
        g_index = {
            i: j
            for j, i in enumerate(
                i for i in range(len(schemas)) if i != head_pos
            )
        }

        def body(head_cols, head_valid, g_cols, g_valids, lo):
            head = join_lib.JoinTable(
                cols=head_cols[0],
                valid=head_valid[0],
                n_rows=jnp.sum(head_valid[0], dtype=jnp.int32),
                overflow=jnp.bool_(False),
            )
            acc = join_lib.block_table(head, lo, block_rows)
            acc_schema = schemas[head_pos]
            for idx in order[1:]:
                j = g_index[idx]
                tbl = join_lib.JoinTable(
                    cols=g_cols[j][0],
                    valid=g_valids[j][0],
                    n_rows=jnp.sum(g_valids[j][0], dtype=jnp.int32),
                    overflow=jnp.bool_(False),
                )
                acc, acc_schema = join_lib.sort_merge_join(
                    acc,
                    tbl,
                    acc_schema,
                    schemas[idx],
                    out_cap=out_cap,
                    dup_cap=dup_cap,
                    kernels=kern,
                )
            return acc.cols[None], acc.valid[None], acc.n_rows[None], acc.overflow[None]

        n_g = len(schemas) - 1
        return jax.jit(
            shard_map(
                body,
                mesh=self.mesh,
                in_specs=(
                    P(AXIS),
                    P(AXIS),
                    (P(AXIS),) * n_g,
                    (P(AXIS),) * n_g,
                    P(),
                ),
                out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
                # Pallas calls inside the mapped body defeat the static
                # replication check (same situation as the match step)
                check_vma=False,
            )
        )

    # ----------------------------------------------------------------- API
    def plan(self, query: QueryGraph, **kw) -> QueryPlan:
        return make_plan(query, self.pg.freq, **kw)

    @staticmethod
    def ring_radii_for(load: np.ndarray) -> tuple[int, ...] | None:
        """If every STwig's load set fits in a ring window (shards within
        ring-distance r of each other), return per-STwig radii; else None.
        Hash partitions have complete cluster graphs → None (all-gather is
        optimal there); locality-aware partitions get bounded rings."""
        T, S, _ = load.shape
        radii = []
        for t in range(T):
            ks, js = np.nonzero(load[t])
            d = np.minimum((ks - js) % S, (js - ks) % S)
            r = int(d.max()) if len(d) else 0
            if r > (S - 1) // 2:
                return None
            radii.append(r)
        # beneficial only if strictly smaller than a full gather
        return tuple(radii) if max(radii) < (S - 1) // 2 or S <= 4 else None

    def match(
        self,
        query: QueryGraph,
        plan: QueryPlan | None = None,
        *,
        adaptive: bool = True,
        max_retries: int = 6,
        use_ring: bool = False,
        guard: "QueryGuard | None" = None,
        retry_policy: RetryPolicy | None = None,
        **kw,
    ) -> MatchResult:
        """Adaptive matching through the shared resilience loop
        (`repro.runtime.resilience.adaptive_run`): same escalation
        semantics as the local engine, plus ``retry_policy`` also paces
        the fetch-recovery retries when a chaos injector is attached."""
        policy = retry_policy or RetryPolicy(max_retries=max_retries)
        plan0 = plan if plan is not None else self.plan(query, **kw)
        return adaptive_run(
            lambda: self._match_once(
                query, plan=plan0, use_ring=use_ring, retry_policy=policy
            ),
            lambda caps: self._match_once(
                query, use_ring=use_ring, retry_policy=policy, **caps
            ),
            caps_from_plan(plan0, kw),
            n_qnodes=query.n_nodes,
            backend="sharded",
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
        """Truly pipelined streaming for the sharded backend — thin wrapper
        over the shared driver (`repro.core.stream.stream_blocks`), kept
        for direct (deprecated) engine use.

        Exploration and the load-set fetch run once; the per-block join step
        then joins only head-table rows ``[lo, lo+block_rows)`` per
        shard_map call, so a consumer that stops early never pays for the
        remaining blocks' joins. The head STwig is never fetched remotely
        (Theorem 5), so per-shard pages stay disjoint and their union equals
        the one-shot run."""
        yield from stream_blocks(self, query, plan, block_rows=block_rows, **kw)

    # -------------------------------------------------- streaming interface
    def _stream_setup(
        self,
        query: QueryGraph,
        plan: QueryPlan | None = None,
        use_ring: bool = False,
        retry_policy: RetryPolicy | None = None,
        **kw,
    ) -> "_ShardedStreamState":
        """The run-once half of a streamed query: exploration, load sets and
        the remote-table fetch all happen here; the returned state caches
        the fetched tables on device for every subsequent block join. A
        shard fault at the fetch (chaos-injected) degrades every page of
        the stream: the state carries the shard-fault reason and the
        driver marks pages ``complete=False``."""
        plan = plan or self.plan(query, **kw)
        stats = MatchStats(backend="sharded", n_shards=self.pg.n_shards)
        with stage(stats, "explore"):
            all_cols, all_valids, overflow = self._explore(plan, stats)
        if self.chaos is not None and self.chaos.forced_overflow():
            overflow = True
        load, load_masks = self._load_masks(query, plan)
        with stage(stats, "fetch"):
            all_valids, fault = self._chaos_gate(
                stats, retry_policy or RetryPolicy(), all_valids, plan.head
            )
        if fault:
            stats.degrade_reason = DegradeReason.SHARD_FAULT.value
        schemas = tuple(
            join_lib.Schema(
                qnodes=s.qnodes, qlabels=(s.root_label,) + s.child_labels
            )
            for s in plan.specs
        )
        # blocks are cut on the head table, so the join order must start
        # there (disjointness across shards comes from head locality)
        order = tuple(
            join_lib.select_join_order(
                list(schemas), stats.stwig_rows, start=plan.head
            )
        )
        ring_radii = self.ring_radii_for(load) if use_ring else None
        caps = tuple(int(c.shape[1]) for c in all_cols)
        with stage(stats, "fetch"):
            if len(schemas) > 1:
                gfn = self._gather_step(
                    len(schemas), plan.head, caps, ring_radii
                )
                g_cols, g_valids = gfn(
                    tuple(all_cols), tuple(all_valids), load_masks
                )
            else:
                g_cols, g_valids = (), ()
        stats.join_order = [schemas[i].qnodes for i in order]
        head_valid = all_valids[plan.head]
        # one host copy of the head validity mask: blocks where no shard has
        # a valid head row are provably empty and skipped without any device
        # call (matching the local backend's empty-block behaviour)
        head_any = np.asarray(host_get(stats, head_valid)).any(axis=0)
        return _ShardedStreamState(
            plan=plan,
            stats=stats,
            schemas=schemas,
            order=order,
            head_cols=all_cols[plan.head],
            head_valid=head_valid,
            head_valid_any=head_any,
            gathered_cols=tuple(g_cols),
            gathered_valids=tuple(g_valids),
            explore_overflow=overflow,
            cap=int(all_cols[plan.head].shape[1]),
        )

    def _stream_block(
        self, state: "_ShardedStreamState", lo: int, block_rows: int
    ) -> tuple[np.ndarray, bool]:
        """One pipelined block: join head rows ``[lo, lo+block_rows)`` of
        every shard against the cached fetched tables and union the
        (disjoint) per-shard results host-side."""
        if not state.head_valid_any[lo : lo + block_rows].any():
            return np.zeros((0, state.plan.n_qnodes), np.int64), False
        if self.chaos is not None:
            d = self.chaos.block_delay()
            if d > 0:
                time.sleep(d)
        jfn = self._join_block_step(
            state.schemas,
            state.order,
            state.plan.join_rows_cap,
            state.plan.join_dup_cap,
            state.cap,
            tuple(int(c.shape[1]) for c in state.gathered_cols),
            block_rows,
        )
        self.join_block_calls += 1
        state.stats.join_blocks += 1
        with stage(state.stats, "join"):
            cols, valid, n_rows, ovf = jfn(
                state.head_cols,
                state.head_valid,
                state.gathered_cols,
                state.gathered_valids,
                jnp.int32(lo),
            )
        with stage(state.stats, "materialize"):
            rows = self._union_rows(
                cols, valid, state.schemas, state.order, 0, state.stats
            )
        return rows, bool(np.any(host_get(state.stats, ovf)))

    # ------------------------------------------------------ execution phases
    def _explore(self, plan: QueryPlan, stats: MatchStats):
        """STwig exploration (Algorithm 2 order) on every shard at once.

        Returns stacked per-shard tables: ``all_cols[i]`` has shape
        (S, rounds_i * rows_cap_i, width_i) with the shard axis leading.
        """
        bind = jax.device_put(
            Bindings.fresh(plan.n_qnodes, self.pg.n_total + 1).words, self._rep
        )
        overflow = False
        all_cols, all_valids = [], []
        for i, spec in enumerate(plan.specs):
            expand, emit = self._match_fns(spec)
            # the spans of the local engine's `_explore`
            rows = spec.expand_rows(self.pg.cap)
            stats.expand_rows += rows
            with span(
                "stwig", i=i, root_rows=rows, node_slots=self.pg.cap
            ) as stwig_span:
                with stage(stats, "expand"):
                    exp, n_roots_max = expand(self._g.tree(), bind)
                    n_roots = int(host_get(stats, n_roots_max))
                stwig_span.set_metadata(n_roots=n_roots)
                round_cols, round_valids = [], []
                contrib = None
                n_rows_tot = 0
                r = 0
                with stage(stats, "emit"):
                    while True:
                        with span("round", r=r):
                            cols, valid, n_rows, cw, ovf = emit(
                                self._g.tree(), exp, jnp.int32(r)
                            )
                            # each shard's valid rows lead its table: keep
                            # the rows the fullest shard needs, and no empty
                            # round past the first
                            n_rows = np.asarray(host_get(stats, n_rows))
                            overflow |= bool(host_get(stats, ovf))
                        if n_rows.any() or not round_cols:
                            keep = join_lib.fitted_cap(int(n_rows.max()), spec.rows_cap)
                            round_cols.append(cols[:, :keep])
                            round_valids.append(valid[:, :keep])
                        contrib = cw if contrib is None else jnp.bitwise_or(contrib, cw)
                        n_rows_tot += int(n_rows.sum())
                        r += 1
                        if r * spec.root_cap >= n_roots:
                            break
                # apply binding replacement on the replicated bitset
                new_bind = bind
                for pos, qn in enumerate(spec.qnodes):
                    new_bind = new_bind.at[qn].set(contrib[pos])
                bind = jax.device_put(new_bind, self._rep)
                # concatenate rounds along the per-shard row axis
                all_cols.append(jnp.concatenate(round_cols, axis=1))
                all_valids.append(jnp.concatenate(round_valids, axis=1))
                stats.stwig_rows.append(n_rows_tot)
                # parity with the local backend's stats (max over shards: the
                # round count is driven by the most loaded shard)
                stats.stwig_roots.append(n_roots)
                stats.rounds.append(r)
        return all_cols, all_valids, overflow

    def _load_masks(self, query: QueryGraph, plan: QueryPlan):
        """Load sets (Theorem 4), host + device-sharded ``(S, T, S)`` form."""
        load = self.cgi.load_sets(query.label_pairs(), plan.head_dists)
        # reorder to (S, T, S): shard-major for sharding along the mesh axis
        masks = jax.device_put(
            np.transpose(load, (1, 0, 2)), NamedSharding(self.mesh, P(AXIS))
        )
        return load, masks

    def _union_rows(
        self, cols, valid, schemas, order, max_matches: int, stats: MatchStats
    ) -> np.ndarray:
        """Disjoint per-shard union → host rows of ORIGINAL ids in query-node
        column order (the sharded counterpart of `SubgraphMatcher._materialize`)."""
        cols_h = np.asarray(host_get(stats, cols)).reshape(-1, cols.shape[-1])
        valid_h = np.asarray(host_get(stats, valid)).reshape(-1)
        rows_new = cols_h[valid_h]
        if max_matches and rows_new.shape[0] > max_matches:
            rows_new = rows_new[:max_matches]
        final_qnodes = _final_schema(schemas, order)
        perm = np.argsort(np.asarray(final_qnodes))
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
        use_ring: bool = False,
        retry_policy: RetryPolicy | None = None,
        **kw,
    ) -> MatchResult:
        t0 = time.perf_counter()
        plan = plan or self.plan(query, **kw)
        stats = MatchStats(backend="sharded", n_shards=self.pg.n_shards)
        with stage(stats, "explore"):
            all_cols, all_valids, overflow = self._explore(plan, stats)
        load, load_masks = self._load_masks(query, plan)
        with stage(stats, "fetch"):
            all_valids, fault = self._chaos_gate(
                stats, retry_policy or RetryPolicy(), all_valids, plan.head
            )
        if self.chaos is not None and self.chaos.forced_overflow():
            overflow = True

        schemas = tuple(
            join_lib.Schema(
                qnodes=s.qnodes, qlabels=(s.root_label,) + s.child_labels
            )
            for s in plan.specs
        )
        order = tuple(
            join_lib.select_join_order(list(schemas), stats.stwig_rows)
        )
        with stage(stats, "join"):
            all_cols, all_valids = zip(*(
                join_lib.compact_stacked(c, v, stats)
                for c, v in zip(all_cols, all_valids)
            ))
        caps = tuple(int(c.shape[1]) for c in all_cols)
        ring_radii = self.ring_radii_for(load) if use_ring else None
        with stage(stats, "join"):
            jfn = self._join_step(
                schemas,
                order,
                plan.head,
                plan.join_rows_cap,
                plan.join_dup_cap,
                caps,
                ring_radii,
            )
            cols, valid, n_rows, ovf = jfn(
                tuple(all_cols), tuple(all_valids), load_masks
            )
            overflow |= bool(np.any(host_get(stats, ovf)))

        # ---- union across shards (already disjoint) ------------------------
        with stage(stats, "materialize"):
            rows_old = self._union_rows(
                cols, valid, schemas, order, plan.max_matches, stats
            )
        stats.time_s = time.perf_counter() - t0
        stats.join_order = [schemas[i].qnodes for i in order]
        if fault:
            stats.degrade_reason = DegradeReason.SHARD_FAULT.value
        return MatchResult(
            rows=rows_old,
            n_matches=int(rows_old.shape[0]),
            complete=not fault and (first_k_met(plan, rows_old) or not overflow),
            stats=stats,
        )

    # -------------------------------------------------- fault handling
    def _chaos_gate(
        self, stats: MatchStats, policy: RetryPolicy, all_valids, head_pos: int
    ):
        """The host-side fetch boundary: consult the chaos injector (when
        attached), retry dead fetches with the policy's jittered backoff,
        and degrade to the surviving shards' rows by masking the faulty
        shard's stacked validity. Returns (all_valids, faulted). Runs
        BEFORE the gather/join shard_map programs — an SPMD program can't
        lose a shard mid-flight, a memory cloud loses it at fetch time."""
        stats.shard_health = {k: "ok" for k in range(self.pg.n_shards)}
        chaos = self.chaos
        if chaos is None:
            return all_valids, False
        fault = False
        ev = chaos.fetch_delay()
        if ev is not None:
            k, d = ev
            time.sleep(d)
            stats.shard_health[k] = "slow"
        attempt = 0
        while True:
            try:
                chaos.try_fetch()
                if attempt > 0:
                    stats.shard_health[chaos.config.dead_shard] = "recovered"
                break
            except ShardFaultError as e:
                if attempt >= policy.fetch_retries:
                    all_valids = self._mask_shard(stats, all_valids, e.shard)
                    stats.shard_health[e.shard] = "dead"
                    fault = True
                    break
                policy.sleep(attempt, policy.fetch_backoff_s)
                attempt += 1
                stats.fetch_retries += 1
        tr = chaos.truncation()
        if tr is not None:
            k, keep = tr
            all_valids = self._mask_shard(
                stats, all_valids, k, head_pos=head_pos, keep_frac=keep
            )
            if stats.shard_health.get(k) == "ok":
                stats.shard_health[k] = "truncated"
            fault = True
        return all_valids, fault

    def _mask_shard(
        self,
        stats: MatchStats,
        all_valids,
        shard: int,
        head_pos: int | None = None,
        keep_frac: float | None = None,
    ):
        """Invalidate (all of, or the tail of) one shard's rows in the
        stacked validity masks — host-side, so results built from the
        masked tables are a correct subset of the true row set, never a
        wrong one. With ``keep_frac`` (the truncated-payload fault) the
        head table is left intact: it is never fetched (Theorem 5), so a
        transfer can't truncate it."""
        sh = NamedSharding(self.mesh, P(AXIS))
        out = []
        for i, v in enumerate(all_valids):
            if keep_frac is not None and i == head_pos:
                out.append(v)
                continue
            vh = np.array(host_get(stats, v))
            if keep_frac is None:
                vh[shard] = False
            else:
                vh[shard, int(keep_frac * vh.shape[1]):] = False
            out.append(jax.device_put(vh, sh))
        return out


@dataclasses.dataclass(eq=False)
class _ShardedStreamState:
    """Per-query stream state for the sharded backend.

    Exploration and the load-set fetch ran once; ``head_cols``/``head_valid``
    are the stacked (S, head_cap, w) local head tables and
    ``gathered_cols``/``gathered_valids`` the per-shard fetched tables, all
    kept on device. `DistributedMatcher._stream_block` joins head rows
    ``[lo, lo+B)`` per call — the lazy half of the pipeline.
    """

    plan: QueryPlan
    stats: MatchStats
    schemas: tuple
    order: tuple[int, ...]
    head_cols: jnp.ndarray
    head_valid: jnp.ndarray
    head_valid_any: np.ndarray  # (cap,) host bool: any shard valid at row i
    gathered_cols: tuple
    gathered_valids: tuple
    explore_overflow: bool
    cap: int  # per-shard head-table row capacity (the block loop bound)


def _final_schema(schemas, order) -> tuple[int, ...]:
    acc = schemas[order[0]]
    for i in order[1:]:
        acc, _ = acc.merge(schemas[i])
    return acc.qnodes
