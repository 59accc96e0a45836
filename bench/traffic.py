"""The one generator every traffic mix is read by.

A mix is a JSON file in ``bench/traffic``. One client calls
``session.compile(q, max_matches=K).run()`` on the mix's templates in
order, each after the last answer. Its keys:

  ``max_matches``  K, the first-K budget of every query (paper §6.1).
  ``templates``    the query templates, sampled from the seeded graph:
                   ``{"kind": "dfs" | "path", "nodes": n}``, and for a DFS
                   query ``"star": true`` to keep only those that are stars
                   (a start node with ``n - 1`` neighbours or more). Every
                   seed gets the same kinds, sizes and shapes.
  ``trace_seconds``  the length of the traced window of a ``--trace 1`` run.
  ``assumed``      where the mix departs from its source, and why (not read).

`dfs_query` and `path_query` are copies of `repro.workloads`' samplers.
"""
from __future__ import annotations

import numpy as np

from repro.core.query import QueryGraph
from repro.graphstore.csr import Graph


def dfs_query(g: Graph, rng: np.random.Generator, n_nodes: int) -> QueryGraph | None:
    """Paper §6.1 DFS query: traverse from a random node, keep the first
    ``n_nodes`` visited (None if the start node is too isolated)."""
    start = int(rng.integers(g.n_nodes))
    nodes, edges, seen = [start], [], {start}
    stack = [start]
    while stack and len(nodes) < n_nodes:
        v = stack.pop()
        for u in g.neighbors(v):
            u = int(u)
            if u not in seen and len(nodes) < n_nodes:
                seen.add(u)
                nodes.append(u)
                edges.append((v, u))
                stack.append(u)
    if len(nodes) < 2:
        return None
    remap = {v: i for i, v in enumerate(nodes)}
    return QueryGraph.build(
        [int(g.labels[v]) for v in nodes],
        [(remap[a], remap[b]) for a, b in edges],
    )


def path_query(g: Graph, rng: np.random.Generator, n_nodes: int) -> QueryGraph | None:
    """A simple-path query sampled from the data graph (always matchable)."""
    v = int(rng.integers(g.n_nodes))
    nodes = [v]
    while len(nodes) < n_nodes:
        nbrs = [int(u) for u in g.neighbors(nodes[-1]) if int(u) not in nodes]
        if not nbrs:
            return None
        nodes.append(nbrs[int(rng.integers(len(nbrs)))])
    return QueryGraph.build(
        [int(g.labels[v]) for v in nodes],
        [(i, i + 1) for i in range(n_nodes - 1)],
    )


SAMPLERS = {"dfs": dfs_query, "path": path_query}


def sample_templates(g: Graph, mix: dict, rng: np.random.Generator) -> list[QueryGraph]:
    """One matchable query per template of ``mix``, of its kind and size."""
    out: list[QueryGraph] = []
    for t in mix["templates"]:
        for _ in range(1000):
            q = SAMPLERS[t["kind"]](g, rng, t["nodes"])
            if q is None or q.n_nodes != t["nodes"] or q in out:
                continue
            if not t.get("star") or all(0 in e for e in q.edges):
                out.append(q)
                break
        else:
            raise ValueError(f"no {t['kind']} query of {t['nodes']} nodes "
                             "could be sampled from this graph")
    return out
