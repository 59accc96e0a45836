"""End-to-end driver (the paper's kind: online subgraph-query serving).

Builds a patents-shaped graph, then serves a mixed workload of DFS + random
queries through the continuous-batching `QueryServer` (`repro.api.serve`):
up to ``--max-inflight`` queries are in flight at once, their block-join
quanta interleaved on the one device, each bounded by a first-K budget
(first 1024 matches) and an optional per-query deadline. Queries with
identical plan shapes share jitted executables via the session cache —
no serving loop is constructed by hand here.

    PYTHONPATH=src python examples/serve_queries.py [--n-queries 40]
"""
import argparse
import time

import numpy as np

from repro.api import GraphSession, summarize_outcomes
from repro.graphstore import generators
from repro.runtime import compile_cache
from repro.workloads import mixed_workload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-queries", type=int, default=40)
    ap.add_argument("--nodes", type=int, default=100_000)
    ap.add_argument("--degree", type=int, default=16)
    ap.add_argument("--labels", type=int, default=64)
    ap.add_argument("--max-inflight", type=int, default=8)
    ap.add_argument("--deadline-ms", type=float, default=0.0)
    args = ap.parse_args()

    print(f"loading graph: {args.nodes} nodes, deg {args.degree} ...")
    t0 = time.perf_counter()
    g = generators.rmat(args.nodes, args.degree * args.nodes, args.labels, seed=0)
    session = GraphSession.open(g, backend="local")
    print(f"loaded in {time.perf_counter()-t0:.1f}s ({g.n_edges} edges)")

    rng = np.random.default_rng(0)
    workload = mixed_workload(g, args.n_queries, n_labels=args.labels, rng=rng)

    server = session.serve(
        max_inflight=args.max_inflight,
        max_matches=1024,
        deadline_s=args.deadline_ms / 1e3 if args.deadline_ms else None,
    )
    t0 = time.perf_counter()
    outcomes = server.serve(workload)
    wall = time.perf_counter() - t0

    s = summarize_outcomes(outcomes)
    ttfp_ms = np.sort([o.ttfp_s * 1e3 for o in outcomes if o.ttfp_s is not None])
    print(f"\n{s['served']} served / {s['partial']} partial / "
          f"{s['failed']} failed in {wall:.1f}s "
          f"({len(workload)/wall:.2f} qps, {s['n_matches']} total matches)")
    if len(ttfp_ms):
        print(f"time-to-first-page p50={ttfp_ms[len(ttfp_ms)//2]:.0f}ms "
              f"p90={ttfp_ms[int(len(ttfp_ms)*0.9)]:.0f}ms "
              f"p99={ttfp_ms[min(len(ttfp_ms)-1, int(len(ttfp_ms)*0.99))]:.0f}ms")
    print(f"scheduler: {server.stats.join_quanta} block-join quanta across "
          f"{len(server.stats.buckets)} shape buckets "
          f"({server.stats.warm_admissions} warm admissions, "
          f"{server.stats.global_degradations} global degradations)")
    print(f"executable cache: {session.cache.hits} hits, "
          f"{session.cache.misses} misses over the workload")
    print("(first-admitted queries pay the jit compiles; bucket-mates and "
          "steady-state queries reuse the session's executable cache)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
