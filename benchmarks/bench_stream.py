"""§6.1 pipelined first-K streaming: time-to-first-page and early-stop work
skipped, on both backends.

Reported rows (``name,us_per_call,derived``):

  * ``stream_ttfp_{backend}``      — wall time until the first page of a
    ``stream(page_size=64)`` materializes (us), vs ``run_full_{backend}``,
    the one-shot ``run(max_matches=0)`` time;
  * ``stream_early_skip_{backend}`` — block-join device calls spent by a
    first-page-only consumer; ``derived`` shows ``skipped=X/Y`` — the
    fraction of the full stream's block joins an early stop never ran.

Runs in a subprocess because the sharded half needs multiple XLA host
devices while the bench session keeps one.
"""
from __future__ import annotations

from benchmarks.common import run_cpu_worker

WORKER = r"""
import time
import numpy as np
from repro.api import GraphSession
from repro.graphstore import PartitionedGraph, generators
from repro.workloads import path_query

g = generators.rmat(8_000, 48_000, 24, seed=7, symmetrize=True)
rng = np.random.default_rng(11)

for backend, n_shards in (("local", 1), ("sharded", 8)):
    session = GraphSession.open(
        PartitionedGraph.build(g, n_shards), backend=backend
    )
    q = None
    while q is None:
        q = path_query(g, rng, 4)
    # pick caps the query actually fits in: stream vs run comparisons are
    # only valid on complete (non-overflowing) results
    child_cap = 16
    while True:
        cq = session.compile(q, max_matches=0, child_cap=child_cap)
        full = cq.run(adaptive=False)  # also warms every fused executable
        if full.complete or child_cap >= 128:
            break
        child_cap *= 2
    assert full.complete, "query overflows even at child_cap=128"

    t0 = time.perf_counter()
    full = cq.run(adaptive=False)
    run_full = time.perf_counter() - t0

    # ~8 blocks of real work so an early stop has something to skip:
    # provably-empty blocks cost nothing on either backend, so size blocks
    # off the blocked table's VALID row count (head STwig when sharded,
    # smallest table locally; valid rows compact to the front).
    if backend == "sharded":
        blocked = cq.plan.head
    else:
        blocked = min(
            range(len(full.stats.stwig_rows)),
            key=lambda i: full.stats.stwig_rows[i],
        )
    # sharded valid rows split across 8 shards, so divide further to keep
    # several non-empty blocks on the busiest shard
    B = max(1, full.stats.stwig_rows[blocked] // (32 if backend == "sharded" else 8) + 1)

    eng = session.engine
    list(cq.stream(page_size=64, max_matches=0, block_rows=B))  # warm traces

    c0 = eng.join_block_calls
    t0 = time.perf_counter()
    gen = cq.stream(page_size=64, max_matches=0, block_rows=B)
    first = next(gen, None)
    ttfp = time.perf_counter() - t0
    early_calls = eng.join_block_calls - c0
    list(gen)
    full_calls = eng.join_block_calls - c0

    print(f"stream_ttfp_{backend},{ttfp*1e6:.1f},n_first={0 if first is None else first.n_rows}")
    print(f"run_full_{backend},{run_full*1e6:.1f},n_matches={full.n_matches}")
    skipped = full_calls - early_calls
    print(f"stream_early_skip_{backend},{early_calls},skipped={skipped}/{full_calls}")
"""


def main() -> None:
    run_cpu_worker(WORKER, ("stream_", "run_full_"), timeout=3000)


if __name__ == "__main__":
    main()
