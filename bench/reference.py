"""The plain reference that decides ``correct``, and its control.

Nothing here imports the program: a query is read by its ``n_nodes``,
``labels`` and ``edges``, a graph by its CSR arrays. The semantics are those of the paper
(§2): a match of query q is an injective map of q's nodes to data nodes that
keeps every label and every query edge; a first-K answer holds
``min(K, number of matches)`` distinct matches.

`EdgeIndex` and the row predicate are copied from ``chip_smoke.py``
(``EdgeIndex``, ``embedding_errors``), counting bad rows instead of naming
the first. `first_k` enumerates matches depth first in numpy, in chunks,
inside per-node candidate sets that a neighbour-label count prunes, and
stops at K. `compare` holds every answer of a window against them.

The control is `first_k` with ``drop_edge=True``: the last query node in
its order is matched from its candidate set alone, without the edge to its
parent. It breaks the guarantee that every query edge is a data edge, and `compare`
has to find its answers wrong.
"""
from __future__ import annotations

import dataclasses

import numpy as np

EXPAND_CHUNK = 1 << 21  # candidate pairs one extension step may hold


def _adjacency(q) -> list[set[int]]:
    adj: list[set[int]] = [set() for _ in range(q.n_nodes)]
    for u, v in q.edges:
        adj[u].add(v)
        adj[v].add(u)
    return adj


class EdgeIndex:
    """Sorted ``src * n + dst`` keys of a host CSR graph: an edge test that
    shares no code with the matcher."""

    def __init__(self, g):
        src = np.repeat(np.arange(g.n_nodes, dtype=np.int64), np.diff(g.indptr))
        self.n = g.n_nodes
        self.labels = g.labels
        keys = src * g.n_nodes + g.indices.astype(np.int64)
        if keys.size and not (keys[1:] > keys[:-1]).all():
            keys = np.unique(keys)
        self.keys = keys

    def has(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        k = u.astype(np.int64) * self.n + v.astype(np.int64)
        pos = np.minimum(np.searchsorted(self.keys, k), len(self.keys) - 1)
        return self.keys[pos] == k


def bad_rows(index: EdgeIndex, q, rows: np.ndarray) -> int:
    """Rows of an answer that are not matches of ``q``, plus repeats."""
    rows = np.asarray(rows, dtype=np.int64)
    if rows.shape[0] == 0:
        return 0
    if rows.ndim != 2 or rows.shape[1] != q.n_nodes:
        return int(rows.shape[0])
    ok = ((rows >= 0) & (rows < index.n)).all(axis=1)
    safe = np.where(ok[:, None], rows, 0)
    ok &= (index.labels[safe] == np.asarray(q.labels)[None, :]).all(axis=1)
    srt = np.sort(safe, axis=1)
    ok &= ~(srt[:, 1:] == srt[:, :-1]).any(axis=1)
    for a, b in q.edges:
        ok &= index.has(safe[:, a], safe[:, b])
    repeats = rows.shape[0] - np.unique(rows, axis=0).shape[0]
    return int((~ok).sum()) + int(repeats)


def candidates(g, q) -> list[np.ndarray]:
    """Per query node, the data nodes of its label that have, for each
    label among its query neighbours, at least as many neighbours of that
    label as it has query neighbours of it. Every match maps each query node
    into its set, so enumerating inside the sets loses no match; a hub that
    lacks one label is ruled out before any of its neighbours are tried."""
    adj = _adjacency(q)
    src = np.repeat(np.arange(g.n_nodes), np.diff(g.indptr))
    nbr_label = g.labels[g.indices]
    count: dict[int, np.ndarray] = {}
    out = []
    for v in range(q.n_nodes):
        ok = g.labels == q.labels[v]
        need: dict[int, int] = {}
        for u in adj[v]:
            need[q.labels[u]] = need.get(q.labels[u], 0) + 1
        for lab, m in need.items():
            if lab not in count:
                count[lab] = np.bincount(src[nbr_label == lab], minlength=g.n_nodes)
            ok &= count[lab] >= m
        out.append(ok)
    return out


def _order(q, n_cand: list[int]) -> tuple[list[int], list[int]]:
    """Query nodes in breadth-first order from the one with the fewest
    candidates, neighbours with fewer candidates first, and each one's
    parent (its first neighbour earlier in the order)."""
    adj = _adjacency(q)
    start = int(np.argmin(n_cand))
    order, parent = [start], [-1]
    seen = {start}
    i = 0
    while i < len(order):
        for u in sorted(adj[order[i]], key=lambda u: (n_cand[u], u)):
            if u not in seen:
                seen.add(u)
                order.append(u)
                parent.append(order[i])
        i += 1
    if len(order) != q.n_nodes:
        raise ValueError("the reference matches connected queries only")
    return order, parent


def first_k(g, index: EdgeIndex, q, k: int, *,
            drop_edge: bool = False) -> np.ndarray:
    """Up to ``k`` distinct matches of ``q`` (all of them when fewer exist),
    as rows of original node ids in query-node order. ``drop_edge`` is the
    control (see the module docstring)."""
    cand_ok = candidates(g, q)
    order, parent = _order(q, [int(c.sum()) for c in cand_ok])
    n = q.n_nodes
    pos = {v: i for i, v in enumerate(order)}
    adj = _adjacency(q)
    extra = [[pos[u] for u in adj[v] if pos[u] < i and u != parent[i]]
             for i, v in enumerate(order)]
    deg = np.diff(g.indptr)

    found: list[np.ndarray] = []
    n_found = 0
    stack = [np.flatnonzero(cand_ok[order[0]]).astype(np.int64)[:, None]]
    while stack and n_found < k:
        part = stack.pop()
        j = part.shape[1]
        if j == n:
            found.append(part[: k - n_found])
            n_found += found[-1].shape[0]
            continue
        v = order[j]
        free = drop_edge and j == n - 1
        if free:
            pool = np.flatnonzero(cand_ok[v]).astype(np.int64)
            width = np.full(part.shape[0], pool.size, dtype=np.int64)
        else:
            width = deg[part[:, pos[parent[j]]]]
        if width.sum() > EXPAND_CHUNK and part.shape[0] > 1:
            half = part.shape[0] // 2
            stack += [part[half:], part[:half]]
            continue
        rep = np.repeat(np.arange(part.shape[0]), width)
        offs = np.arange(rep.size) - np.repeat(np.cumsum(width) - width, width)
        if free:
            new = pool[offs]
        else:
            new = g.indices[g.indptr[part[rep, pos[parent[j]]]] + offs].astype(np.int64)
        ok = cand_ok[v][new]
        ok &= ~(part[rep] == new[:, None]).any(axis=1)
        if not free:
            for p in extra[j]:
                ok &= index.has(part[rep, p], new)
        grown = np.concatenate([part[rep[ok]], new[ok][:, None]], axis=1)
        if grown.shape[0]:
            stack.append(grown)
    rows = (np.concatenate(found) if found
            else np.zeros((0, n), dtype=np.int64))[:k]
    inv = np.argsort(order)
    return rows[:, inv]


@dataclasses.dataclass
class Answer:
    """One answer of the window: its template and rows."""

    template: int
    rows: np.ndarray


@dataclasses.dataclass
class Checks:
    """The numbers compared, each with its limit (all exact: limit 0)."""

    bad_rows: int = 0       # returned rows that are no match, or repeats
    count_gap: int = 0      # |rows - min(K, matches)| over every answer

    LIMITS = {"bad_rows": 0, "count_gap": 0}

    @property
    def correct(self) -> bool:
        return all(getattr(self, k) <= v for k, v in self.LIMITS.items())

    def as_dict(self) -> dict:
        return {k: {"value": getattr(self, k), "limit": v}
                for k, v in self.LIMITS.items()}


def compare(g, templates: list, answers: list[Answer], k: int) -> Checks:
    """Hold every answer against the reference. A first-K call with
    adaptive escalation is due ``min(K, matches)`` rows; one that ended
    partial, whatever the program says of it, falls short of them."""
    index = EdgeIndex(g)
    ref_n = {t: first_k(g, index, templates[t], k).shape[0]
             for t in sorted({a.template for a in answers})}
    out = Checks()
    for a in answers:
        out.bad_rows += bad_rows(index, templates[a.template], a.rows)
        out.count_gap += abs(int(a.rows.shape[0]) - ref_n[a.template])
    return out
