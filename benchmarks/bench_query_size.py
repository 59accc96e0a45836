"""Fig 8(a,b,c): query time vs query size (DFS + random) and vs edge count.
Patents-like R-MAT (scaled); pipeline termination at 1024 matches (§6.1)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import (
    avg_query_time,
    build_matcher,
    dfs_query,
    emit,
    random_query,
)
from repro.graphstore.generators import patents_like


def main(scale: float = 0.008, n_queries: int = 3) -> None:
    g = patents_like(scale, seed=2)
    m = build_matcher(g)
    rng = np.random.default_rng(0)

    # Fig 8(a): DFS queries, node count 3..8
    for nq in range(3, 9):
        qs = [q for q in (dfs_query(g, rng, nq) for _ in range(n_queries)) if q]
        if not qs:
            continue
        t, cnt = avg_query_time(m, qs)
        emit(f"dfs_query_n{nq}", t * 1e6, f"avg_matches={cnt:.0f}")

    import jax
    jax.clear_caches()
    # Fig 8(b): random queries, node count 5..10, E = 2N
    for nq in range(5, 11):
        qs = [random_query(nq, 2 * nq, g.n_labels, rng) for _ in range(n_queries)]
        t, cnt = avg_query_time(m, qs)
        emit(f"random_query_n{nq}", t * 1e6, f"avg_matches={cnt:.0f}")

    jax.clear_caches()
    # Fig 8(c): edge count 10..20 at N=10
    for ne in range(10, 21, 2):
        qs = [random_query(10, ne, g.n_labels, rng) for _ in range(n_queries)]
        t, cnt = avg_query_time(m, qs)
        emit(f"random_query_e{ne}", t * 1e6, f"avg_matches={cnt:.0f}")


if __name__ == "__main__":
    main()
