"""§Perf iteration 3 — load-set collective on the paper's own engine.

Baseline (paper-faithful): masked all-gather — every shard receives every
other shard's STwig table, rows outside the load set masked (with a random
hash partition the cluster graph is complete, so this IS optimal).
Optimized (beyond-paper): distance-bounded ppermute ring on locality-aware
partitions — bytes scale with the load-set radius, not the cluster size.

Measures wall time on 8 simulated machines + analytic bytes-moved.
"""
from __future__ import annotations

from benchmarks.common import run_cpu_worker

WORKER = r"""
import time
import numpy as np
from repro.api import GraphSession
from repro.graphstore import PartitionedGraph, generators
from repro.core import QueryGraph

# ring-of-cliques + range partition → sparse (ring) cluster graph
g = generators.ring_of_cliques(n_cliques=8, clique_size=40, n_labels=4, seed=0)
pg = PartitionedGraph.build(g, 8, mode="range")
session = GraphSession.open(pg, backend="sharded")
dm = session.engine
q = QueryGraph.build(labels=[0, 1, 2, 3], edges=[(0, 1), (1, 2), (2, 3), (0, 2)])

compiled = session.compile(q, max_matches=0)
plan = compiled.plan
load = dm.cgi.load_sets(q.label_pairs(), plan.head_dists)
radii = dm.ring_radii_for(load)
print(f"# ring radii per STwig: {radii}")

for use_ring, name in ((False, "allgather"), (True, "ring")):
    r0 = compiled.run(adaptive=False, use_ring=use_ring)  # warmup
    t0 = time.perf_counter()
    for _ in range(3):
        res = compiled.run(adaptive=False, use_ring=use_ring)
    dt = (time.perf_counter() - t0) / 3
    # analytic bytes/shard: allgather = (S-1)*rows; ring = 2*max_radius*rows
    S = 8
    tbl_bytes = sum(
        r * 4 * (w + 1)
        for r, w in [(plan.specs[i].rows_cap, plan.specs[i].width)
                     for i in range(len(plan.specs)) if i != plan.head]
    )
    if use_ring and radii is not None:
        moved = sum(2 * radii[i] * plan.specs[i].rows_cap * 4 * (plan.specs[i].width + 1)
                    for i in range(len(plan.specs)) if i != plan.head)
    else:
        moved = (S - 1) * tbl_bytes
    print(f"loadset_{name},{dt*1e6:.1f},matches={res.n_matches};bytes_per_shard={moved}")
"""


def main() -> None:
    run_cpu_worker(WORKER, ("loadset_", "#"), timeout=2000)


if __name__ == "__main__":
    main()
