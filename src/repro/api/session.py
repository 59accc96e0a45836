"""`GraphSession`: one facade over the local and distributed engines.

The session is the unit of engine state: it owns the partitioned graph, the
backend engine, and the keyed `ExecutableCache` shared by every query
compiled in it — so a workload of similar queries pays each jit trace once,
and the cache dies with the session instead of living in module globals.
"""
from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np

from repro.api.compiled import CompiledQuery
from repro.core.backend import Kernels, resolve_kernels
from repro.core.cache import ExecutableCache
from repro.core.deprecation import facade_construction
from repro.core.engine import SubgraphMatcher
from repro.core.plan import QueryPlan
from repro.core.query import QueryGraph
from repro.core.result import MatchResult
from repro.graphstore.csr import Graph
from repro.graphstore.partition import PartitionedGraph

BACKENDS = ("auto", "local", "sharded")


class GraphSession:
    """A query session over one graph. Use `GraphSession.open`, not the
    constructor. Usable as a context manager; `close()` drops the executable
    cache."""

    def __init__(self, pg: PartitionedGraph, engine, backend: str, cache: ExecutableCache):
        self.pg = pg
        self.backend = backend
        self.cache = cache
        self._engine = engine

    # ------------------------------------------------------------- factory
    @classmethod
    def open(
        cls,
        graph_or_pg: Graph | PartitionedGraph,
        *,
        backend: str = "auto",
        kernels: "str | Kernels" = "auto",
        n_shards: int | None = None,
        mesh=None,
        partition_mode: str = "hash",
        cache_size: int = 512,
        chaos=None,
    ) -> "GraphSession":
        """Open a session, selecting and wrapping the right engine.

        ``backend="auto"`` picks "sharded" when a mesh is given or the
        partition has multiple shards (and enough devices exist), else
        "local". A raw `Graph` is partitioned here: into 1 shard for the
        local backend, ``n_shards`` (default: all devices) for sharded.

        ``kernels`` selects the kernel backend every dense inner step draws
        from — ``"auto"`` (the per-op table `TPU_OPS` on TPU, jnp
        elsewhere), ``"jnp"``, ``"pallas"``, ``"pallas-interpret"``, or a
        registered `Kernels` instance (`repro.core.backend`). The choice
        keys every cached executable, so sessions can be compared across
        kernel backends without recompiling each other's programs away.

        ``chaos`` attaches a seeded fault injector
        (`repro.runtime.chaos.ChaosInjector`) to the engine: injected
        faults (slow/dead shard, truncated fetch, forced overflow) are
        handled by the resilience layer and surface as typed partial
        results. The injector wraps the kernel backend under a distinct
        name, so chaos executables never collide with clean ones.
        """
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        kern = resolve_kernels(kernels)
        import jax

        n_dev = len(jax.devices())
        if backend == "auto":
            if mesh is not None:
                backend = "sharded"
            elif isinstance(graph_or_pg, PartitionedGraph):
                pg_shards = graph_or_pg.n_shards
                if pg_shards > n_dev:
                    raise ValueError(
                        f"partition has {pg_shards} shards but only {n_dev} "
                        f"device(s) are available — re-partition to ≤{n_dev} "
                        "shards (1 for the local backend) or add devices"
                    )
                backend = "sharded" if pg_shards > 1 else "local"
            elif n_shards is not None and n_shards > 1:
                backend = "sharded"
            else:
                backend = "local"

        if isinstance(graph_or_pg, PartitionedGraph):
            pg = graph_or_pg
        else:
            if backend == "local":
                shards = 1
            else:
                shards = n_shards or (mesh.devices.size if mesh is not None else n_dev)
            pg = PartitionedGraph.build(graph_or_pg, shards, mode=partition_mode)

        cache = ExecutableCache(maxsize=cache_size)
        if backend == "local":
            if pg.n_shards != 1:
                raise ValueError(
                    f"local backend needs a 1-shard partition, got {pg.n_shards} "
                    "shards (use backend='sharded' or re-partition)"
                )
            with facade_construction():
                engine = SubgraphMatcher(
                    pg, cache=cache, kernels=kern, chaos=chaos
                )
        else:
            from jax.sharding import Mesh

            from repro.core.dist import DistributedMatcher

            if mesh is None:
                if pg.n_shards > n_dev:
                    raise ValueError(
                        f"sharded backend needs ≥{pg.n_shards} devices, have {n_dev}"
                    )
                mesh = Mesh(np.array(jax.devices()[: pg.n_shards]), ("data",))
            with facade_construction():
                engine = DistributedMatcher(
                    pg, mesh, cache=cache, kernels=kern, chaos=chaos
                )
        return cls(pg, engine, backend, cache)

    # ----------------------------------------------------------- query API
    def compile(self, query: QueryGraph, **caps) -> CompiledQuery:
        """Plan ``query`` (Algorithm 2 + head selection + static capacities)
        without running it. ``caps`` are `make_plan` keywords (``child_cap``,
        ``join_rows_cap``, ``max_matches``, ...). Executables are built
        lazily on first run and cached in the session by their static spec,
        so recompiling an identical query is free."""
        plan = self._engine.plan(query, **caps)
        return CompiledQuery(session=self, query=query, plan=plan, caps=caps)

    def run(
        self,
        query: QueryGraph,
        *,
        adaptive: bool = True,
        deadline_s: float | None = None,
        memory_budget_bytes: float | None = None,
        retry_policy=None,
        **caps,
    ) -> MatchResult:
        """One-shot convenience: ``compile(query).run()``. ``deadline_s`` /
        ``memory_budget_bytes`` bound the query (a trip returns a partial
        result with a typed ``stats.degrade_reason``); ``retry_policy``
        tunes adaptive escalation (`repro.runtime.resilience`)."""
        return self.compile(query, **caps).run(
            adaptive=adaptive,
            deadline_s=deadline_s,
            memory_budget_bytes=memory_budget_bytes,
            retry_policy=retry_policy,
        )

    def stream(
        self,
        query: QueryGraph,
        *,
        page_size: int = 256,
        max_matches: int | None = None,
        block_rows: int | None = None,
        deadline_s: float | None = None,
        engine_kw: dict | None = None,
        **caps,
    ):
        """One-shot convenience: ``compile(query).stream(...)`` — pipelined
        first-K pages on either backend. ``block_rows`` is forwarded to
        `CompiledQuery.stream` (the latency/throughput knob),
        ``deadline_s`` bounds the stream at block boundaries, ``engine_kw``
        carries backend options (e.g. ``{"use_ring": True}``), and ``caps``
        go to `compile`."""
        return self.compile(query, **caps).stream(
            page_size=page_size,
            max_matches=max_matches,
            block_rows=block_rows,
            deadline_s=deadline_s,
            **(engine_kw or {}),
        )

    def serve(self, **cfg) -> "QueryServer":
        """Open a continuous-batching `QueryServer` over this session
        (DESIGN.md §7). ``cfg`` keywords are `ServerConfig` fields::

            server = session.serve(max_inflight=8, deadline_s=0.5)
            outcomes = server.serve(queries)       # synchronous batch
            with session.serve() as srv:           # background scheduler
                t = srv.submit(q)
                out = t.result()

        Concurrent queries with identical plan shapes share one traced
        executable via this session's `ExecutableCache`; the server
        interleaves their block joins on the one device and enforces
        per-query deadlines/budgets so overload degrades per query, never
        globally."""
        from repro.runtime.server import QueryServer, ServerConfig

        return QueryServer(self, ServerConfig(**cfg))

    def run_batch(
        self,
        queries: Sequence[QueryGraph] | Iterable[QueryGraph],
        *,
        adaptive: bool = True,
        **caps,
    ) -> list[MatchResult]:
        """Run a workload, amortizing compilation: all queries are planned
        up front and executed against the shared executable cache, so
        queries with identical STwig specs / join schemas reuse each other's
        jitted programs. Results are returned in input order and are
        identical to sequential `run` calls."""
        compiled = [self.compile(q, **caps) for q in queries]
        return [cq.run(adaptive=adaptive) for cq in compiled]

    # ------------------------------------------------------------ plumbing
    @property
    def engine(self):
        """The wrapped backend engine (for low-level access; prefer the
        facade methods)."""
        return self._engine

    @property
    def kernels(self) -> Kernels:
        """The kernel backend the engine's dense steps draw from."""
        return self._engine.kernels

    def set_kernels(self, kernels: "str | Kernels") -> "GraphSession":
        """Switch the kernel backend for subsequent runs. Safe mid-session:
        executables are keyed by (static spec, kernels name), so previously
        compiled programs survive and a later switch back reuses them."""
        self._engine.kernels = resolve_kernels(kernels)
        return self

    def replan(self, query: QueryGraph, **caps) -> QueryPlan:
        return self._engine.plan(query, **caps)

    def close(self) -> None:
        self.cache.clear()

    def __enter__(self) -> "GraphSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"GraphSession(backend={self.backend!r}, "
            f"kernels={self.kernels.name!r}, n_shards={self.pg.n_shards}, "
            f"cache={len(self.cache)} executables)"
        )
