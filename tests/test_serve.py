"""Continuous-batching `QueryServer` (DESIGN.md §7): interleaved block
joins match sequential runs, executable sharing across bucket-mates,
per-query deadline isolation, the served/partial/failed split, and the
deprecation warnings behind the `repro.api` redesign.

Fast tests here never touch the device (config validation, admission
shedding, warnings); the end-to-end interleave/parity tests are slow, and
the sharded-backend parity run is a subprocess with 8 forced CPU devices
(per the dry-run isolation rule).
"""
import json
import pathlib
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.api import (
    GraphSession,
    QueryServer,
    ServerConfig,
    summarize_outcomes,
)
from repro.core.result import MatchStats
from repro.graphstore import PartitionedGraph, generators

from helpers import dfs_query, nx_oracle

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")
TESTS = str(pathlib.Path(__file__).resolve().parent)


# ---------------------------------------------------------------- fast unit


def test_server_config_validation():
    with pytest.raises(ValueError):
        ServerConfig(max_inflight=0)
    with pytest.raises(ValueError):
        ServerConfig(block_rows=0)
    with pytest.raises(ValueError):
        ServerConfig(max_matches=-1)
    with pytest.raises(ValueError):
        ServerConfig(deadline_s=-0.5)


def test_api_exports_server_surface():
    import repro.api as api
    import repro.api.serve as serve_mod

    for name in ("QueryServer", "ServerConfig", "QueryOutcome", "Ticket",
                 "summarize_outcomes"):
        assert name in api.__all__
        assert getattr(api, name) is getattr(serve_mod, name)


def test_session_serve_builds_configured_server():
    g = generators.rmat(60, 180, 4, seed=0, symmetrize=True)
    s = GraphSession.open(g)
    server = s.serve(max_inflight=3, block_rows=64)
    assert isinstance(server, QueryServer)
    assert server.session is s
    assert server.config.max_inflight == 3
    assert server.config.block_rows == 64


def test_expired_deadline_sheds_at_admission_without_device_work():
    """A query whose deadline expired while queued is degraded per-query at
    admission — typed reason, no stream ever opened, server healthy."""
    g = generators.rmat(60, 180, 4, seed=0, symmetrize=True)
    s = GraphSession.open(g)
    rng = np.random.default_rng(1)
    q = None
    while q is None:
        q = dfs_query(g, rng, 3)
    server = s.serve(max_inflight=2)
    tickets = [server.submit(q, deadline_s=0.0) for _ in range(3)]
    server.run_until_idle()
    outcomes = [t.result(timeout=1) for t in tickets]
    assert all(o.status == "partial" for o in outcomes)
    assert all(o.stats.degrade_reason == "deadline" for o in outcomes)
    assert all(o.n_matches == 0 for o in outcomes)
    assert server.stats.setup_quanta == 0      # no exploration ever ran
    assert server.stats.join_quanta == 0
    assert server.stats.global_degradations == 0
    assert summarize_outcomes(outcomes) == {
        "served": 0, "partial": 3, "failed": 0, "n_matches": 0,
    }


def test_met_first_k_budget_is_served_despite_overflow():
    """The server streams at the compiled caps and never escalates: a query
    whose caps overflow is still served whole once its first-K budget is
    met, and partial when the budget is one row more than the caps allow."""
    g = generators.rmat(120, 420, 4, seed=7, symmetrize=True)
    s = GraphSession.open(g)
    q = dfs_query(g, np.random.default_rng(0), 4)
    capped = s.compile(q, max_matches=0, child_cap=2).run(adaptive=False)
    assert not capped.complete and capped.n_matches > 0
    k = capped.n_matches
    server = s.serve(max_inflight=2)
    met = server.submit(q, max_matches=k, child_cap=2)
    short = server.submit(q, max_matches=k + 1, child_cap=2)
    server.run_until_idle()
    met, short = met.result(timeout=1), short.result(timeout=1)
    assert met.status == "served" and met.n_matches == k
    assert set(map(tuple, met.rows.tolist())) <= nx_oracle(g, q)
    assert short.status == "partial" and short.n_matches == k
    assert short.stats.degrade_reason is None


def test_direct_engine_construction_warns():
    from repro.core.dist import DistributedMatcher  # noqa: F401
    from repro.core.engine import SubgraphMatcher

    g = generators.rmat(60, 180, 4, seed=0, symmetrize=True)
    pg = PartitionedGraph.build(g, 1)
    with pytest.warns(DeprecationWarning, match="GraphSession"):
        SubgraphMatcher(pg)


def test_session_open_does_not_warn():
    g = generators.rmat(60, 180, 4, seed=0, symmetrize=True)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        GraphSession.open(g)
    ours = [w for w in rec
            if issubclass(w.category, DeprecationWarning)
            and "GraphSession" in str(w.message)]
    assert not ours, "the facade must construct engines without warnings"


def test_dict_style_stats_access_warns():
    stats = MatchStats(backend="local")
    with pytest.warns(DeprecationWarning, match="stats.time_s"):
        assert stats["time_s"] == stats.time_s
    with pytest.warns(DeprecationWarning):
        assert stats.get("nope", 42) == 42
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        assert stats.time_s == 0.0  # attribute access stays clean


# ----------------------------------------------------- slow end-to-end local


@pytest.fixture(scope="module")
def session():
    g = generators.rmat(150, 500, 4, seed=7, symmetrize=True)
    return g, GraphSession.open(g)


@pytest.fixture(scope="module")
def queries(session):
    g, _ = session
    rng = np.random.default_rng(0)
    out = []
    while len(out) < 4:
        q = dfs_query(g, rng, 4)
        if q is not None:
            out.append(q)
    return out


@pytest.mark.slow
def test_interleaved_streams_match_sequential(session, queries):
    """>=4 in-flight streams, block quanta interleaved by the scheduler:
    every query's page union must equal its sequential run — disjoint
    pages, no duplicates, no cross-query contamination."""
    g, s = session
    server = s.serve(max_inflight=4, block_rows=8, max_matches=0)
    outcomes = server.serve(queries, child_cap=32)
    assert len(outcomes) == len(queries)
    assert server.stats.admitted == len(queries)
    for q, o in zip(queries, outcomes):
        ref = s.run(q, max_matches=0, child_cap=32)
        assert ref.complete
        assert o.status == "served", (o.status, o.error)
        assert o.result.complete
        got = set(map(tuple, o.rows.tolist()))
        assert got == set(map(tuple, ref.rows.tolist()))
        assert o.n_matches == len(got)  # disjoint pages, no duplicates
        assert o.stats.join_blocks >= 1
    # every dispatched join quantum is attributed to exactly one query
    assert server.stats.join_quanta == sum(
        o.stats.join_blocks for o in outcomes
    )
    assert server.stats.global_degradations == 0


@pytest.mark.slow
def test_bucket_mates_share_executables(session, queries):
    """Same-shape concurrent queries hit one bucket: after the first query
    warms the bucket, serving bucket-mates adds zero cache misses."""
    _, s = session
    q = queries[0]
    server = s.serve(max_inflight=4, block_rows=8, max_matches=0)
    server.serve([q], child_cap=32)          # first query pays the traces
    misses0 = s.cache.misses
    outcomes = server.serve([q, q, q, q], child_cap=32)
    assert all(o.status == "served" for o in outcomes)
    assert s.cache.misses == misses0, "bucket-mates must not re-trace"
    assert len({o.bucket for o in outcomes}) == 1


@pytest.mark.slow
def test_deadline_trip_never_degrades_bucket_mates(session, queries):
    """One in-flight query tripping its deadline degrades that query only:
    its bucket-mates finish complete and the server counts no global
    degradation (the per-query SLO the server exists to enforce)."""
    _, s = session
    q = queries[0]
    with s.serve(max_inflight=5, block_rows=8, max_matches=0) as server:
        mates = [server.submit(q, child_cap=32) for _ in range(4)]
        victim = server.submit(q, deadline_s=1e-6, child_cap=32)
        outcomes = [t.result(timeout=120) for t in mates]
        loser = victim.result(timeout=120)
    assert loser.status == "partial"
    assert loser.stats.degrade_reason == "deadline"
    for o in outcomes:
        assert o.status == "served", (o.status, o.error)
        assert o.result.complete
        assert o.stats.degrade_reason is None
    assert server.stats.global_degradations == 0


@pytest.mark.slow
def test_per_query_failure_is_isolated(session, queries):
    """An exception inside one query's quanta yields a failed outcome for
    that query; the others are served and the scheduler survives."""
    _, s = session
    server = s.serve(max_inflight=3, block_rows=8, max_matches=0)
    t_good = server.submit(queries[1], child_cap=32)
    t_bad = server.submit(queries[0], child_cap=32)
    # sabotage the bad entry so its setup quantum raises: a non-numeric
    # block size trips a TypeError inside open_stream
    with server._lock:
        entry = next(e for e in server._pending if e.ticket is t_bad)
    entry.block_rows = "bogus"
    server.run_until_idle()
    assert t_bad.result(timeout=1).status == "failed"
    assert "TypeError" in t_bad.result(timeout=1).error
    good = t_good.result(timeout=1)
    assert good.status == "served"
    assert server.stats.failed == 1 and server.stats.served == 1
    assert server.stats.global_degradations == 0


@pytest.mark.slow
def test_first_k_budget_stops_join_work(session, queries):
    """A budget-met stream is closed mid-flight: strictly fewer join quanta
    than full enumeration of the same query."""
    _, s = session
    q = queries[0]
    full_server = s.serve(max_inflight=1, block_rows=4, max_matches=0)
    (full,) = full_server.serve([q], child_cap=32)
    assert full.status == "served"
    if full.n_matches < 2 or full.stats.join_blocks < 2:
        pytest.skip("need >=2 non-empty blocks to observe an early stop")
    k_server = s.serve(max_inflight=1, block_rows=4, max_matches=1)
    (first,) = k_server.serve([q], child_cap=32)
    assert first.n_matches == 1
    assert first.stats.join_blocks < full.stats.join_blocks
    assert k_server.stats.join_quanta < full_server.stats.join_quanta


# ------------------------------------------------- slow sharded (8 devices)

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 --xla_cpu_use_fusion_emitters=false"
)
import json, sys
import numpy as np
sys.path.insert(0, %r)
from helpers import dfs_query, nx_oracle
from repro.api import GraphSession
from repro.graphstore import PartitionedGraph, generators

out = {}
g = generators.rmat(160, 520, 4, seed=3, symmetrize=True)
pg = PartitionedGraph.build(g, 8)
session = GraphSession.open(pg, backend="sharded")
rng = np.random.default_rng(5)
queries = []
while len(queries) < 4:
    q = dfs_query(g, rng, 4)
    if q is not None:
        queries.append(q)

server = session.serve(max_inflight=4, block_rows=8, max_matches=0)
outcomes = server.serve(queries, child_cap=32)
checks = []
for q, o in zip(queries, outcomes):
    got = set(map(tuple, o.rows.tolist()))
    checks.append(
        o.status == "served"
        and got == nx_oracle(g, q)
        and o.n_matches == len(got)
    )
out["sharded_interleave_exact"] = all(checks) and len(checks) == 4
out["global_degradations"] = server.stats.global_degradations
out["join_quanta_attributed"] = server.stats.join_quanta == sum(
    o.stats.join_blocks for o in outcomes
)
print(json.dumps(out))
""" % (TESTS,)


@pytest.mark.slow
def test_sharded_interleaved_streams_match_oracle():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sharded_interleave_exact"]
    assert out["global_degradations"] == 0
    assert out["join_quanta_attributed"]
