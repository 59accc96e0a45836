"""Shared benchmark machinery.

Real datasets (US Patents, WordNet) are unavailable offline; each benchmark
uses R-MAT graphs with matched node/edge/label counts and notes it. Output
rows follow the harness convention: ``name,us_per_call,derived``.

Query generators live in `repro.workloads` (re-exported here for the bench
scripts); matching goes through the `GraphSession` facade.
"""
from __future__ import annotations

import pathlib
import subprocess
import sys
import time

import numpy as np

from repro.api import GraphSession
from repro.graphstore import PartitionedGraph, generators
from repro.workloads import dfs_query, random_query  # noqa: F401  (re-export)

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def run_cpu_worker(code: str, prefixes: tuple[str, ...], *, timeout: float) -> None:
    """Run a multi-device figure reproduction in a child process on 8
    forced host-CPU devices, echoing the child's result rows that start
    with ``prefixes``. The child is pinned to the CPU (``JAX_PLATFORMS``),
    so it never reaches for a chip the parent may hold. Raises if the
    child fails, so the suite counts as failed."""
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env={
            "PYTHONPATH": SRC,
            "PATH": "/usr/bin:/bin",
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"worker failed: {proc.stderr[-400:].strip()}")
    for line in proc.stdout.strip().splitlines():
        if line.startswith(prefixes):
            print(line)


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


def timed(fn, *, repeats: int = 3):
    fn()  # warmup (jit compile)
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats


def build_matcher(g, n_shards: int = 1) -> GraphSession:
    """Open a `GraphSession` over ``g`` (name kept for the bench scripts)."""
    return GraphSession.open(
        PartitionedGraph.build(g, n_shards),
        backend="local" if n_shards == 1 else "sharded",
    )


def avg_query_time(
    session: GraphSession,
    queries,
    *,
    max_matches: int = 1024,
    adaptive: bool = False,
) -> tuple[float, float]:
    """Mean wall-time + mean matches over a query set (pipeline semantics:
    first `max_matches` per query, as in the paper's experiments)."""
    times, counts = [], []
    for q in queries:
        t0 = time.perf_counter()
        res = session.run(q, max_matches=max_matches, adaptive=adaptive)
        times.append(time.perf_counter() - t0)
        counts.append(res.n_matches)
    return float(np.mean(times)), float(np.mean(counts))
