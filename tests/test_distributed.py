"""Distributed matcher (shard_map, 8 simulated machines): exactness vs the
oracle, disjointness of per-shard results, and the OR-allreduce collective;
and on 2 of them, exactness where an STwig expands over fewer rows than a
shard has node slots.

Multi-device tests run in a subprocess so the main test session keeps a
single CPU device (per the dry-run isolation rule).
"""
import json
import pathlib
import subprocess
import sys

import pytest

# whole-module: multi-device subprocess end-to-end runs
pytestmark = pytest.mark.slow

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")
TESTS = str(pathlib.Path(__file__).resolve().parent)

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 --xla_cpu_use_fusion_emitters=false"
)
import json, sys
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
sys.path.insert(0, %r)
from helpers import dfs_query, nx_oracle
from repro.graphstore import PartitionedGraph, generators
from repro.core import QueryGraph
from repro.core.dist import DistributedMatcher
from repro.core.collectives import or_allreduce
from jax import shard_map
from jax.sharding import PartitionSpec as P

out = {}

# --- OR-allreduce butterfly == gather-reduce ---------------------------
mesh = Mesh(np.array(jax.devices()), ("data",))
rng = np.random.default_rng(0)
words = rng.integers(0, 2**32, (8, 64), dtype=np.uint32)
f = jax.jit(shard_map(
    lambda w: or_allreduce(w[0], "data")[None],
    mesh=mesh, in_specs=(P("data"),), out_specs=P("data"), check_vma=False,
))
got = np.asarray(f(words))
want = np.bitwise_or.reduce(words, axis=0)
out["or_allreduce_ok"] = bool((got == want[None]).all())

# --- distributed == oracle, per-shard disjointness ----------------------
g = generators.rmat(160, 520, 4, seed=3, symmetrize=True)
pg = PartitionedGraph.build(g, 8)
dm = DistributedMatcher(pg, mesh)
rng = np.random.default_rng(5)
checks = []
for _ in range(3):
    q = dfs_query(g, rng, 4)
    if q is None:
        continue
    res = dm.match(q, max_matches=0)
    got = set(map(tuple, res.rows.tolist()))
    want = nx_oracle(g, q)
    checks.append(got == want and res.complete
                  and len(res.rows) == len(got))  # no duplicates in union
out["dist_exact"] = all(checks) and len(checks) >= 2

# --- two shards of 1,500-odd node slots, more than any STwig's rows ------
from test_expand_rows import CAPS, _queries
g = generators.rmat(3000, 9000, 100, seed=1, symmetrize=True)
pg = PartitionedGraph.build(g, 2)
dm = DistributedMatcher(pg, Mesh(np.array(jax.devices()[:2]), ("data",)))
rows, exact = [], []
for q in _queries(g, 2, 4):
    plan = dm.plan(q, **CAPS)
    rows += [sp.expand_rows(pg.cap) for sp in plan.specs]
    res = dm.match(q, plan)
    got = set(map(tuple, res.rows.tolist()))
    exact.append(res.complete and got == nx_oracle(g, q)
                 and len(res.rows) == len(got))
out["rows_below_cap"] = bool(rows) and max(rows) < pg.cap
out["rows_below_cap_exact"] = bool(exact) and all(exact)
print(json.dumps(out))
""" % (TESTS,)


@pytest.fixture(scope="module")
def dist_results():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"},
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_or_allreduce(dist_results):
    assert dist_results["or_allreduce_ok"]


def test_distributed_matches_oracle_no_dedup(dist_results):
    assert dist_results["dist_exact"]


def test_distributed_matches_oracle_with_rows_below_cap(dist_results):
    """Each shard expands an STwig over its root candidates only, bounded
    by the plan's global ``root_rows``: fewer rows than the shard's node
    slots, and the answers stay exact."""
    assert dist_results["rows_below_cap"]
    assert dist_results["rows_below_cap_exact"]
