"""Fig 9: speed-up vs machine count (1→8 simulated machines).

Runs in a subprocess because the worker needs multiple XLA host devices
while the bench session keeps one.
"""
from __future__ import annotations

from benchmarks.common import run_cpu_worker

WORKER = r"""
import time
import numpy as np
from repro.api import GraphSession
from repro.graphstore import PartitionedGraph, generators
from repro.workloads import dfs_query

g = generators.rmat(60_000, 16 * 60_000, 64, seed=7)

rng = np.random.default_rng(11)
queries = []
while len(queries) < 3:
    q = dfs_query(g, rng, 6)
    if q is not None:
        queries.append(q)

for S in (1, 2, 4, 8):
    pg = PartitionedGraph.build(g, S)
    session = GraphSession.open(pg)  # auto: local for S=1, sharded otherwise
    # warmup then measure
    for q in queries:
        session.run(q, max_matches=1024, adaptive=False)
    t0 = time.perf_counter()
    for q in queries:
        session.run(q, max_matches=1024, adaptive=False)
    dt = (time.perf_counter() - t0) / len(queries)
    print(f"speedup_machines_{S},{dt*1e6:.1f},")
"""


def main() -> None:
    run_cpu_worker(WORKER, ("speedup_",), timeout=3000)


if __name__ == "__main__":
    main()
