"""The `repro.api` facade: backend selection, compile/run split, batched
execution, streaming first-K pages, and local-vs-sharded parity.

The parity test runs in a subprocess so the main session keeps a single CPU
device (per the dry-run isolation rule).
"""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

# whole-module: subprocess 8-device parity/stream runs take minutes
pytestmark = pytest.mark.slow

from repro.api import GraphSession
from repro.core import QueryGraph
from repro.graphstore import PartitionedGraph, generators

from helpers import dfs_query, nx_oracle

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


@pytest.fixture(scope="module")
def session():
    g = generators.rmat(150, 500, 4, seed=7, symmetrize=True)
    return g, GraphSession.open(g)


@pytest.fixture(scope="module")
def queries(session):
    g, _ = session
    rng = np.random.default_rng(0)
    out = []
    while len(out) < 3:
        q = dfs_query(g, rng, 4)
        if q is not None:
            out.append(q)
    return out


def test_open_selects_local_backend(session):
    g, s = session
    assert s.backend == "local"
    assert s.pg.n_shards == 1
    # a multi-shard partition cannot be served by the local backend
    with pytest.raises(ValueError):
        GraphSession.open(PartitionedGraph.build(g, 4), backend="local")
    with pytest.raises(ValueError):
        GraphSession.open(g, backend="nonsense")


def test_facade_run_matches_oracle(session, queries):
    g, s = session
    for q in queries:
        res = s.run(q, max_matches=0)
        assert res.complete
        assert set(map(tuple, res.rows.tolist())) == nx_oracle(g, q)
        assert res.stats.backend == "local"
        assert res.stats.time_s > 0
        # dict-style deprecation bridge on MatchStats
        assert res.stats["time_s"] == res.stats.time_s
        with pytest.raises(KeyError):
            res.stats["not_a_field"]


def test_compile_run_split_reuses_executables(session, queries):
    _, s = session
    cq = s.compile(queries[0], max_matches=0)
    cq.run()
    h0, m0 = s.cache.hits, s.cache.misses
    res = cq.run()
    assert res.complete
    assert s.cache.misses == m0, "rerun of a compiled query must not re-jit"
    assert s.cache.hits > h0


def test_run_batch_equivalent_to_sequential(session, queries):
    _, s = session
    batch = s.run_batch(queries, max_matches=0)
    assert len(batch) == len(queries)
    for q, br in zip(queries, batch):
        sr = s.compile(q, max_matches=0).run()
        assert br.n_matches == sr.n_matches
        assert set(map(tuple, br.rows.tolist())) == set(map(tuple, sr.rows.tolist()))


def test_stream_pages_concat_equals_run(session, queries):
    _, s = session
    # generous caps so the compiled plan is already complete (streaming is
    # first-K: it never escalates capacities)
    cq = s.compile(queries[0], max_matches=0, child_cap=32)
    res = cq.run()
    assert res.complete and res.stats.retries == 0
    pages = list(cq.stream(page_size=16, max_matches=0))
    rows = (
        np.concatenate([p.rows for p in pages], axis=0)
        if pages
        else np.zeros((0, queries[0].n_nodes), np.int64)
    )
    assert all(p.complete for p in pages)
    assert all(p.rows.shape[0] == 16 for p in pages[:-1])  # full pages
    assert rows.shape[0] == res.n_matches  # disjoint pages, no duplicates
    assert set(map(tuple, rows.tolist())) == set(map(tuple, res.rows.tolist()))


def test_stream_first_k_stops_early(session, queries):
    _, s = session
    cq = s.compile(queries[0], max_matches=0, child_cap=32)
    full = cq.run()
    k = max(1, full.n_matches // 2)
    # a page size that does NOT divide k: the limit must hold mid-page too
    page = max(1, k // 3) + (1 if k % max(1, k // 3 + 1) == 0 else 0)
    got = list(cq.stream(page_size=page, max_matches=k))
    assert sum(p.rows.shape[0] for p in got) == min(k, full.n_matches)
    assert {tuple(r) for p in got for r in p.rows.tolist()} <= set(
        map(tuple, full.rows.tolist())
    )
    # explicit non-divisible pairing regardless of the graph's match count
    if full.n_matches >= 5:
        got2 = list(cq.stream(page_size=3, max_matches=5))
        assert [p.rows.shape[0] for p in got2] == [3, 2]


def test_stream_early_stop_skips_join_blocks(session, queries):
    """Stopping a stream early must skip the remaining blocks' join work,
    observable as strictly fewer block-join invocations on the engine."""
    _, s = session
    cq = s.compile(queries[0], max_matches=0, child_cap=32)
    full = cq.run(adaptive=False)
    assert full.complete
    if full.n_matches < 2:
        pytest.skip("need >=2 matches to observe an early stop")
    # size blocks so the valid rows of the blocked table span >=2 blocks
    n_min = min(full.stats.stwig_rows)
    block = max(1, n_min // 2)
    eng = s.engine
    c0 = eng.join_block_calls
    pages = list(cq.stream(page_size=1, max_matches=0, block_rows=block))
    full_calls = eng.join_block_calls - c0
    assert sum(p.rows.shape[0] for p in pages) == full.n_matches
    if full_calls < 2:
        pytest.skip("matches fit one block on this graph")
    c1 = eng.join_block_calls
    gen = cq.stream(page_size=1, max_matches=1, block_rows=block)
    assert next(gen, None) is not None
    gen.close()
    assert eng.join_block_calls - c1 < full_calls


def test_stream_reports_incomplete_on_overflow(session, queries):
    """Streaming never escalates capacities, so an overflowing plan must
    surface `complete=False` on some page — even if no rows survive."""
    _, s = session
    cq = s.compile(queries[0], max_matches=0, child_cap=2)
    ref = cq.run(adaptive=False)
    if ref.complete:
        pytest.skip("plan did not overflow on this graph")
    pages = list(cq.stream(page_size=16))
    assert pages, "incomplete stream yielded no pages at all"
    assert not all(p.complete for p in pages)


def test_adaptive_growth_through_facade(session, queries):
    g, s = session
    # child_cap=2 forces an initial overflow; adaptive replanning must recover
    res = s.compile(queries[0], max_matches=0, child_cap=2).run(adaptive=True)
    assert res.complete and res.stats.retries >= 1
    assert set(map(tuple, res.rows.tolist())) == nx_oracle(g, queries[0])


PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 --xla_cpu_use_fusion_emitters=false"
)
import json, sys, time
import numpy as np
sys.path.insert(0, %r)
from helpers import dfs_query, path_query
from repro.api import GraphSession
from repro.graphstore import PartitionedGraph, generators

g = generators.rmat(160, 520, 4, seed=3, symmetrize=True)
pg = PartitionedGraph.build(g, 8)
sharded = GraphSession.open(pg)            # auto -> sharded over 8 devices
local = GraphSession.open(g, backend="local")

out = {"backend": sharded.backend, "parity": [], "roots_parity": [],
       "stream_ok": [], "stream_complete": [], "early_skips_work": [],
       "stream_cache_reuse": [], "multi_stwig_streamed": False,
       "ttfp_s": [], "batch_ok": True}
rng = np.random.default_rng(5)
queries = []
while len(queries) < 1:
    q = dfs_query(g, rng, 4)
    if q is not None:
        queries.append(q)
# path queries decompose into >=2 STwigs: the streamed join chain and the
# gather-once load-set fetch are actually exercised
while len(queries) < 2:
    q = path_query(g, rng, 4)
    if q is not None and len(sharded.compile(q).plan.specs) >= 2:
        queries.append(q)

for q in queries:
    rs = sharded.run(q, max_matches=0)
    rl = local.run(q, max_matches=0)
    out["parity"].append(
        rs.complete and rl.complete
        and set(map(tuple, rs.rows.tolist())) == set(map(tuple, rl.rows.tolist()))
    )
    # stats parity: both backends populate stwig_roots (sharded reports the
    # per-shard max, so local — which sees the whole graph — is an upper bound)
    out["roots_parity"].append(
        len(rs.stats.stwig_roots) == len(rs.stats.rounds) == len(rl.stats.stwig_roots)
        and all(0 < s <= l for s, l in zip(rs.stats.stwig_roots, rl.stats.stwig_roots))
    )

    cq = sharded.compile(q, max_matches=0, child_cap=48)
    ref = cq.run(adaptive=False)
    assert ref.complete, "caps too small for stream comparison"
    eng = sharded.engine
    # provably-empty blocks are skipped host-side, so cut ~3 blocks from the
    # span of head rows that are valid on SOME shard (rows compact to the
    # front, so the span's first and last blocks are always non-empty)
    probe = eng._stream_setup(q, cq.plan)
    span = int(np.nonzero(probe.head_valid_any)[0][-1]) + 1
    assert span >= 4, "degenerate head table"
    B = span // 3 + 1
    c0 = eng.join_block_calls
    t0 = time.perf_counter()
    gen = cq.stream(page_size=16, max_matches=0, block_rows=B)
    first = next(gen, None)
    out["ttfp_s"].append(time.perf_counter() - t0)
    pages = ([first] if first is not None else []) + list(gen)
    full_calls = eng.join_block_calls - c0
    rows = (np.concatenate([p.rows for p in pages], axis=0)
            if pages else np.zeros((0, q.n_nodes), np.int64))
    out["stream_ok"].append(
        sum(p.n_rows for p in pages) == ref.n_matches  # disjoint pages
        and set(map(tuple, rows.tolist())) == set(map(tuple, ref.rows.tolist()))
    )
    out["stream_complete"].append(all(p.complete for p in pages))
    # consuming only the first page must invoke the block join step strictly
    # fewer times than producing every match does
    c1 = eng.join_block_calls
    gen = cq.stream(page_size=1, max_matches=1, block_rows=B)
    got_first = next(gen, None) is not None
    gen.close()
    early_calls = eng.join_block_calls - c1
    out["early_skips_work"].append(
        got_first and 1 <= early_calls < full_calls
    )
    # identical re-stream (first page is enough): the gather and block-join
    # steps were cached in the session's ExecutableCache, so no new traces
    misses1 = sharded.cache.misses
    gen = cq.stream(page_size=16, max_matches=0, block_rows=B)
    next(gen, None)
    gen.close()
    out["stream_cache_reuse"].append(sharded.cache.misses == misses1)
    out["multi_stwig_streamed"] |= len(cq.plan.specs) >= 2

batch = sharded.run_batch(queries, max_matches=0)
for q, br in zip(queries, batch):
    sr = sharded.run(q, max_matches=0)
    if set(map(tuple, br.rows.tolist())) != set(map(tuple, sr.rows.tolist())):
        out["batch_ok"] = False
print(json.dumps(out))
""" % (str(pathlib.Path(__file__).resolve().parent),)


@pytest.fixture(scope="module")
def parity_results():
    proc = subprocess.run(
        [sys.executable, "-c", PARITY_SCRIPT],
        capture_output=True,
        text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"},
        timeout=1800,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_local_vs_sharded_parity(parity_results):
    assert parity_results["backend"] == "sharded"
    assert parity_results["parity"] and all(parity_results["parity"])


def test_sharded_stats_roots_parity(parity_results):
    # the sharded backend populates MatchStats.stwig_roots like the local one
    assert parity_results["roots_parity"] and all(parity_results["roots_parity"])


def test_sharded_stream_and_batch(parity_results):
    assert all(parity_results["stream_ok"])
    assert all(parity_results["stream_complete"])
    assert parity_results["batch_ok"]
    # at least one streamed query had a multi-STwig plan, so the gather-once
    # + block-join pipeline (not just head paging) was exercised
    assert parity_results["multi_stwig_streamed"]


def test_sharded_stream_is_pipelined(parity_results):
    # first-page-only consumption ran strictly fewer block-join device calls
    # than full consumption: early stopping skips real work inside shard_map
    assert parity_results["early_skips_work"] and all(
        parity_results["early_skips_work"]
    )
    # block steps retrace once per (schemas, caps, block size): an identical
    # re-stream hits the session ExecutableCache only
    assert all(parity_results["stream_cache_reuse"])
    # time-to-first-page smoke: the first page materialized and was timed
    assert all(t > 0 for t in parity_results["ttfp_s"])
