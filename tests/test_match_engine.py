"""Single-shard engine vs the networkx oracle (exact result sets)."""
import numpy as np
import pytest

from repro.core import QueryGraph, SubgraphMatcher
from repro.graphstore import PartitionedGraph, generators

from helpers import dfs_query, nx_oracle, random_query


@pytest.fixture(scope="module")
def small_graph():
    g = generators.rmat(120, 420, 4, seed=7, symmetrize=True)
    return g, SubgraphMatcher(PartitionedGraph.build(g, 1))


def test_dfs_queries_exact(small_graph):
    g, m = small_graph
    rng = np.random.default_rng(0)
    checked = 0
    for _ in range(6):
        q = dfs_query(g, rng, 4)
        if q is None:
            continue
        res = m.match(q, max_matches=0)
        assert res.complete
        assert set(map(tuple, res.rows.tolist())) == nx_oracle(g, q)
        checked += 1
    assert checked >= 3


def test_random_queries_exact(small_graph):
    g, m = small_graph
    rng = np.random.default_rng(1)
    for _ in range(3):
        q = random_query(4, 5, 4, rng)
        res = m.match(q, max_matches=0)
        assert res.complete
        assert set(map(tuple, res.rows.tolist())) == nx_oracle(g, q)


def test_duplicate_label_query(small_graph):
    """Queries with repeated labels exercise the injectivity filters."""
    g, m = small_graph
    # triangle-ish query with two nodes sharing a label
    q = QueryGraph.build([0, 0, 1], [(0, 1), (0, 2), (1, 2)])
    res = m.match(q, max_matches=0)
    assert res.complete
    got = set(map(tuple, res.rows.tolist()))
    assert got == nx_oracle(g, q)
    for row in got:
        assert len(set(row)) == len(row), "isomorphism requires distinct nodes"


def test_max_matches_truncation(small_graph):
    g, m = small_graph
    rng = np.random.default_rng(3)
    q = dfs_query(g, rng, 3)
    full = m.match(q, max_matches=0)
    trunc = m.match(q, max_matches=5)
    assert trunc.n_matches <= 5
    assert set(map(tuple, trunc.rows.tolist())) <= set(
        map(tuple, full.rows.tolist())
    )


def test_adaptive_retry_reports(small_graph):
    g, m = small_graph
    rng = np.random.default_rng(4)
    q = None
    while q is None:
        q = dfs_query(g, rng, 4)
    res = m.match(q, max_matches=0, child_cap=2)  # force initial overflow
    assert res.complete  # adaptive retries must recover completeness
    assert set(map(tuple, res.rows.tolist())) == nx_oracle(g, q)


def test_no_matches():
    g = generators.grid_2d(5, 5, 2, seed=0)
    m = SubgraphMatcher(PartitionedGraph.build(g, 1))
    # a 4-clique query cannot embed in a grid
    q = QueryGraph.build([0, 0, 0, 0], [(a, b) for a in range(4) for b in range(a + 1, 4)])
    res = m.match(q, max_matches=0)
    assert res.complete and res.n_matches == 0


def test_first_k_met_is_complete_despite_overflow(small_graph):
    """A capacity overflow only drops matches, so a first-K query that still
    finds its K rows is complete without escalating; one row more than the
    overflowing plan finds is not."""
    from repro.api import GraphSession

    g, _ = small_graph
    s = GraphSession.open(g)
    q = dfs_query(g, np.random.default_rng(0), 4)
    capped = s.compile(q, max_matches=0, child_cap=2).run(adaptive=False)
    assert not capped.complete and capped.n_matches > 0
    k = capped.n_matches
    met = s.compile(q, max_matches=k, child_cap=2).run()
    assert met.complete and met.n_matches == k and met.stats.retries == 0
    assert set(map(tuple, met.rows.tolist())) <= nx_oracle(g, q)
    short = s.compile(q, max_matches=k + 1, child_cap=2).run(adaptive=False)
    assert not short.complete and short.n_matches == k
