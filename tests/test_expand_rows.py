"""The STwig expansion over its root candidates only.

`expand_stwig_shard` runs per-root work on ``rows = spec.expand_rows(cap)``
rows, the node slots with the root's label read from the shard's label
index, not on every node slot, and writes each row to its node slot. Held
here against a plain numpy oracle of steps 1-3; a plan whose ``root_rows``
is too small for the graph flags overflow (and escalation, which replans
on the session's graph, recovers); and the local engine answers as the
networkx oracle on a graph whose node slots outnumber an STwig's rows
(the sharded engine's case is in ``test_distributed.py``, which runs on
several devices in a subprocess).
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.api import GraphSession
from repro.core.backend import n_words
from repro.core.match import Bindings, ShardGraph, expand_stwig_shard
from repro.core.plan import STwigSpec, make_plan
from repro.graphstore import PartitionedGraph, generators
from repro.kernels.bitset.ref import bitset_test_np

from helpers import dfs_query, nx_oracle, path_query

# small tables: XLA:CPU takes minutes to compile a join of 2^16-row tables
CAPS = dict(max_matches=0, emission_budget=2**11, join_rows_cap=2**11,
            join_dup_cap=16)


def _shard_graph(pg: PartitionedGraph) -> ShardGraph:
    return ShardGraph(
        labels=jnp.asarray(pg.labels[0]), indptr=jnp.asarray(pg.indptr[0]),
        indices=jnp.asarray(pg.indices[0]), n_local=jnp.int32(pg.n_local[0]),
        nodes_by_label=jnp.asarray(pg.nodes_by_label[0]),
        label_indptr=jnp.asarray(pg.label_indptr[0]), shard_id=jnp.int32(0),
        all_labels=jnp.asarray(pg.all_labels),
    )


def _star_spec(root_label, child_labels, root_rows, *, root_bound=False,
               child_bound=None, child_cap=4) -> STwigSpec:
    k = len(child_labels)
    return STwigSpec(
        root_label=root_label, child_labels=tuple(child_labels), root_qnode=0,
        child_qnodes=tuple(range(1, k + 1)), root_bound=root_bound,
        child_bound=tuple(child_bound or (False,) * k), root_cap=8,
        child_cap=child_cap, rows_cap=8 * child_cap**k,
        same_label_child_pairs=tuple(
            (i, j) for i in range(k) for j in range(i + 1, k)
            if child_labels[i] == child_labels[j]),
        root_label_child_positions=tuple(
            i for i in range(k) if child_labels[i] == root_label),
        child_need=tuple(child_labels.count(l) for l in child_labels),
        root_rows=root_rows,
    )


def _expansion_oracle(pg: PartitionedGraph, spec: STwigSpec, words: np.ndarray):
    """Steps 1-3 in numpy, one node slot at a time; only the first
    ``rows`` nodes with the root's label are roots."""
    cap, n_total = pg.cap, pg.n_total
    labels, indptr, indices = pg.labels[0], pg.indptr[0], pg.indices[0]
    rows, k, C = spec.expand_rows(cap), spec.n_children, spec.child_cap
    found = [v for v in range(int(pg.n_local[0])) if labels[v] == spec.root_label]
    cand = np.full((k, cap + 1, C), n_total, np.int32)
    cnt = np.zeros((k, cap + 1), np.int32)
    root_mask = np.zeros(cap, bool)
    for v in found[:rows]:
        if spec.root_bound and not bitset_test_np(
                words[spec.root_qnode], np.array([v]))[0]:
            continue
        seg = indices[indptr[v]:indptr[v + 1]]
        for i in range(k):
            m = pg.all_labels[seg] == spec.child_labels[i]
            if spec.child_bound[i]:
                m &= bitset_test_np(words[spec.child_qnodes[i]], seg)
            cnt[i, v] = m.sum()
            cand[i, v, :min(C, m.sum())] = seg[m][:C]
        root_mask[v] = all(cnt[i, v] >= spec.child_need[i] for i in range(k))
    return dict(
        cand=cand, cnt=cnt, root_mask=root_mask,
        rank=np.cumsum(root_mask) - root_mask, n_roots=root_mask.sum(),
        overflow=cnt.max() > C or len(found) > rows,
    )


def _graph(case: str):
    g = generators.rmat(1500, 6000, 6, seed=5, symmetrize=True)
    if case == "no-candidates":
        g.labels[g.labels == 5] = 0
    if case == "label-fills-every-slot":
        g.labels[:] = 0
    pad = 64 if case in ("edge-and-node-padding", "label-fills-every-slot") else 8
    return PartitionedGraph.build(g, 1, pad_to_multiple=pad)


# case -> (root label, child labels, root bound, child bound)
CASES = {
    "rows-below-cap": (1, (2, 3, 4), False, None),
    "no-candidates": (5, (1, 2), False, None),
    "label-fills-every-slot": (0, (0, 0), False, None),
    "edge-and-node-padding": (2, (3, 2), False, None),
    "bound-root": (3, (1, 4), True, None),
    "bound-and-same-label-children": (4, (1, 1, 2), True, (True, False, True)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_expansion_matches_numpy_oracle(case):
    pg = _graph(case)
    root, children, root_bound, child_bound = CASES[case]
    spec = _star_spec(root, children, max(1, int(pg.freq[root])),
                      root_bound=root_bound, child_bound=child_bound)
    rows = spec.expand_rows(pg.cap)
    if case == "label-fills-every-slot":
        assert rows == pg.n_local[0] < pg.cap
    else:
        assert rows < pg.cap / 2
    rng = np.random.default_rng(len(case))
    words = rng.integers(0, 2**32, (spec.width, n_words(pg.n_total + 1)),
                         dtype=np.uint32)
    exp = expand_stwig_shard(_shard_graph(pg), Bindings(jnp.asarray(words)), spec)
    want = _expansion_oracle(pg, spec, words)
    for name, value in want.items():
        np.testing.assert_array_equal(np.asarray(getattr(exp, name)), value,
                                      err_msg=name)
    assert (int(exp.n_roots) == 0) == (case == "no-candidates")


@pytest.mark.parametrize("short", [1, 17], ids=["one-row-short", "many-rows-short"])
def test_too_few_rows_set_overflow(short):
    """A plan made on another graph can have fewer rows than this graph has
    root candidates: the expansion keeps the first ``rows`` of them and
    says so, never dropping a root in silence."""
    pg = _graph("rows-below-cap")
    count = int(pg.freq[1])
    degree = int(np.diff(pg.indptr[0]).max())  # no child list overflows
    for root_rows, flagged in ((count, False), (count - short, True)):
        spec = _star_spec(1, (2, 3), root_rows, child_cap=degree)
        words = np.zeros((3, n_words(pg.n_total + 1)), np.uint32)
        exp = expand_stwig_shard(_shard_graph(pg), Bindings(jnp.asarray(words)), spec)
        want = _expansion_oracle(pg, spec, words)
        assert bool(exp.overflow) == bool(want["overflow"]) == flagged
        for name, value in want.items():
            np.testing.assert_array_equal(np.asarray(getattr(exp, name)), value,
                                          err_msg=name)


def test_plan_sizes_rows_from_label_counts():
    g = generators.rmat(3000, 9000, 100, seed=1, symmetrize=True)
    s = GraphSession.open(g, kernels="jnp")
    specs = [sp for q in _queries(g, 0, 2) for sp in make_plan(q, s.pg.freq).specs]
    for spec in specs:
        assert spec.root_rows == max(1, int(s.pg.freq[spec.root_label]))
        assert spec.expand_rows(s.pg.cap) == min(spec.root_rows, s.pg.cap)


def _queries(g, seed, n):
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        make = (dfs_query, path_query)[len(out) % 2]
        q = make(g, rng, 4 + len(out) % 2)
        if q is not None:
            out.append(q)
    return out


def test_local_engine_answers_with_rows_below_cap():
    """The local engine on a graph whose 3000 node slots outnumber every
    STwig's rows (some 30 per label): exact answers, bound roots included (paths of four
    or more nodes take two STwigs or more), and ``expand_rows`` counts the
    rows the plan gave."""
    g = generators.rmat(3000, 9000, 100, seed=1, symmetrize=True)
    s = GraphSession.open(g, kernels="jnp")
    bound_roots = 0
    for q in _queries(g, 2, 4):
        compiled = s.compile(q, **CAPS)
        specs = compiled.plan.specs
        assert all(sp.expand_rows(s.pg.cap) < s.pg.cap for sp in specs)
        bound_roots += sum(sp.root_bound for sp in specs)
        res = compiled.run()
        assert res.complete
        assert set(map(tuple, res.rows.tolist())) == nx_oracle(g, q)
        assert res.stats.expand_rows == sum(sp.expand_rows(s.pg.cap) for sp in specs)
    assert bound_roots > 0


def test_plan_from_another_graph_escalates_to_a_whole_answer():
    """An explicit plan with too few rows flags overflow; escalation
    replans on the session's graph and returns the whole answer."""
    g = generators.rmat(3000, 9000, 100, seed=1, symmetrize=True)
    s = GraphSession.open(g, kernels="jnp")
    q = _queries(g, 4, 1)[0]
    compiled = s.compile(q, **CAPS)
    tight = dataclasses.replace(compiled.plan, specs=tuple(
        dataclasses.replace(sp, root_rows=1) for sp in compiled.plan.specs))
    engine = s.engine
    partial = engine.match(q, tight, adaptive=False)
    assert not partial.complete
    whole = engine.match(q, tight)
    assert whole.complete and whole.stats.retries >= 1
    assert set(map(tuple, whole.rows.tolist())) == nx_oracle(g, q)
