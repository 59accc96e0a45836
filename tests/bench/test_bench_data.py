"""The copied generator and samplers, the graph cache, and the plain
reference with its control."""
import numpy as np
import pytest

from bench import graphs, reference, traffic
from helpers import nx_oracle
from repro import workloads
from repro.graphstore import generators


def sample(g, kind, n, rng):
    return traffic.sample_templates(
        g, {"templates": [{"kind": kind, "nodes": n}]}, rng)[0]


CFG = {"name": "t", "n_nodes": 2000, "n_edges": 8000, "n_labels": 24,
       "rmat_a": 0.57, "rmat_b": 0.19, "rmat_c": 0.19}


@pytest.mark.parametrize("seed", [0, 7])
def test_make_graph_is_the_programs_rmat(seed):
    got = graphs.make_graph(CFG, np.random.default_rng(seed))
    want = generators.rmat(2000, 8000, 24, seed=seed)
    for name in ("labels", "indptr", "indices"):
        np.testing.assert_array_equal(getattr(got, name), getattr(want, name))


def test_load_graph_caches_by_seed(tmp_path, monkeypatch):
    monkeypatch.setattr(graphs, "CACHE_DIR", tmp_path)
    seed = 2**31 + 12345
    assert graphs.ensure_cached(CFG, seed) > 0.0
    assert graphs.ensure_cached(CFG, seed) == 0.0
    g = graphs.load_graph(CFG, seed)
    other = graphs.load_graph(CFG, seed + 1)
    want = graphs.make_graph(CFG, graphs.rng_for(seed, 0))
    for name in ("labels", "indptr", "indices"):
        np.testing.assert_array_equal(getattr(g, name), getattr(want, name))
    assert g.indices.shape != other.indices.shape or (g.indices != other.indices).any()


@pytest.mark.parametrize("kind", ["dfs", "path"])
def test_samplers_are_the_programs(kind):
    g = generators.rmat(2000, 8000, 24, seed=3)
    ours = traffic.SAMPLERS[kind]
    theirs = {"dfs": workloads.dfs_query, "path": workloads.path_query}[kind]
    a, b = np.random.default_rng(5), np.random.default_rng(5)
    for n in (3, 4, 6, 8):
        assert ours(g, a, n) == theirs(g, b, n)


@pytest.mark.parametrize("seed", [1, 2])
def test_first_k_finds_what_the_oracle_finds(seed):
    g = generators.rmat(400, 1600, 4, seed=seed)
    index = reference.EdgeIndex(g)
    rng = np.random.default_rng(seed)
    for kind, n in (("dfs", 4), ("path", 4), ("dfs", 5), ("path", 3)):
        q = sample(g, kind, n, rng)
        want = nx_oracle(g, q)
        got = reference.first_k(g, index, q, 10**6)
        assert {tuple(r) for r in got.tolist()} == want
        assert reference.bad_rows(index, q, got) == 0
        few = reference.first_k(g, index, q, 7)
        assert few.shape[0] == min(7, len(want))
        assert {tuple(r) for r in few.tolist()} <= want


def test_bad_rows_counts_each_broken_guarantee():
    g = generators.rmat(400, 1600, 4, seed=4)
    index = reference.EdgeIndex(g)
    q = sample(g, "dfs", 4, np.random.default_rng(0))
    rows = reference.first_k(g, index, q, 20)
    assert rows.shape[0] >= 4 and reference.bad_rows(index, q, rows) == 0
    a, b = q.edges[0]
    broken = [rows.copy() for _ in range(4)]
    broken[0][0, a] = (rows[0, a] + 1) % g.n_nodes          # label or edge
    broken[1][1, b] = broken[1][1, a]                       # not injective
    broken[2] = np.concatenate([rows, rows[:1]])            # a repeat
    broken[3][2] = -1                                       # out of range
    for x in broken:
        assert reference.bad_rows(index, q, x) >= 1


def test_the_control_is_found_wrong():
    """The reference in the program's place, one query edge dropped: its
    answers break the edge guarantee, and the comparison says so."""
    g = generators.rmat(3000, 12000, 6, seed=9)
    index = reference.EdgeIndex(g)
    rng = np.random.default_rng(9)
    qs = [sample(g, "dfs", 5, rng), sample(g, "path", 4, rng)]
    answers = [reference.Answer(i, reference.first_k(g, index, q, 64, drop_edge=True))
               for i, q in enumerate(qs)]
    checks = reference.compare(g, qs, answers, 64)
    assert checks.bad_rows > 0 and not checks.correct
    sound = [reference.Answer(i, reference.first_k(g, index, q, 64))
             for i, q in enumerate(qs)]
    assert reference.compare(g, qs, sound, 64).correct


def test_a_short_answer_is_a_count_gap():
    """Every answer is due min(K, matches) rows, whatever the program said
    of it: one row short is a gap of one, and the gaps of a window add."""
    g = generators.rmat(3000, 12000, 6, seed=9)
    index = reference.EdgeIndex(g)
    q = sample(g, "path", 3, np.random.default_rng(1))
    rows = reference.first_k(g, index, q, 64)
    assert reference.compare(g, [q], [reference.Answer(0, rows)], 64).correct
    checks = reference.compare(g, [q], [reference.Answer(0, rows[:-1])], 64)
    assert checks.count_gap == 1 and not checks.correct
    over = reference.compare(g, [q], [reference.Answer(0, rows)] * 2
                             + [reference.Answer(0, rows[:-2])], 64)
    assert over.count_gap == 2 and over.bad_rows == 0


@pytest.mark.parametrize("n", [4, 6])
def test_star_templates_are_stars_and_follow_the_seed(n):
    g = generators.rmat(3000, 12000, 6, seed=2)
    mix = {"templates": [{"kind": "dfs", "nodes": n, "star": True}] * 3}
    a = traffic.sample_templates(g, mix, graphs.rng_for(2**31 + 5, 1))
    b = traffic.sample_templates(g, mix, graphs.rng_for(2**31 + 5, 1))
    assert a == b and len(set(map(repr, a))) == 3
    for q in a:
        assert q.n_nodes == n and len(q.edges) == n - 1
        assert all(0 in e for e in q.edges)
