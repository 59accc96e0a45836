#!/usr/bin/env python3
"""Bring-up smoke run of the STwig matcher on a TPU, end to end.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the sharded engine on four chips

One process drives every phase on the chip it holds. On one chip:

  1. kernels — each op that ``kernels="auto"`` takes from a Pallas kernel
     runs on a seeded input and must equal the jnp reference;
  2. oracle — a seeded 2,000-node graph, opened with `GraphSession.open`
     defaults; every match of small DFS and path queries must equal the
     networkx oracle of ``tests/helpers.py``;
  3. patents — the US-Patents-shaped graph (3,774,768 nodes, 16,522,438
     edges, 418 labels; the paper's §6 real dataset) from ``--seed``,
     opened with the defaults. Eight seeded queries (DFS of 6-10 nodes and
     paths of 4-6) run through ``session.compile(q, max_matches=1024).run()``
     and then through ``session.serve(max_inflight=8)``. Every ``run`` must
     be complete, every returned row must pass a numpy embedding check
     (labels equal, every query edge in the CSR, ids injective), and each
     served outcome must agree with its ``run`` (`served_disagreement`).

With ``--chips 4`` only the sharded phase runs: the same graph partitioned
four ways on ``backend="sharded"`` and the first of the same eight queries
(a DFS query), run once (a cold query compiles a dozen full-width
steps on each engine, and a four-chip host costs four chips), complete on
both engines, its rows checked as above and compared with the one-shard
local run in the same process.

The last line of standard output is ``{"ok": true, "device": {...}}``. It
is printed only when every check passed on a TPU; otherwise the run exits
non-zero without it. ``JAX_COMPILATION_CACHE_DIR`` places the compile
cache; without it the cache is ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

FIRST_K = 1024  # paper §6.1: first 1024 matches


class SmokeFailure(Exception):
    """A phase or a check failed."""


T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:8.1f}s] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------- row checks
class EdgeIndex:
    """Sorted ``src * n + dst`` keys of a host CSR graph: an edge test that
    shares no code with the matcher."""

    def __init__(self, g):
        import numpy as np

        src = np.repeat(np.arange(g.n_nodes, dtype=np.int64), np.diff(g.indptr))
        self.n = g.n_nodes
        self.labels = g.labels
        self.keys = np.sort(src * g.n_nodes + g.indices.astype(np.int64))

    def has(self, u, v):
        import numpy as np

        k = u.astype(np.int64) * self.n + v.astype(np.int64)
        pos = np.minimum(np.searchsorted(self.keys, k), len(self.keys) - 1)
        return self.keys[pos] == k


def embedding_errors(index: EdgeIndex, q, rows) -> str | None:
    """Why ``rows`` are not embeddings of ``q``, or None if all are."""
    import numpy as np

    rows = np.asarray(rows, dtype=np.int64)
    if rows.shape[0] == 0:
        return None
    if rows.ndim != 2 or rows.shape[1] != q.n_nodes:
        return f"rows of shape {rows.shape} for a {q.n_nodes}-node query"
    if (rows < 0).any() or (rows >= index.n).any():
        return "node id out of range"
    if not (index.labels[rows] == np.asarray(q.labels)[None, :]).all():
        return "label mismatch"
    srt = np.sort(rows, axis=1)
    if (srt[:, 1:] == srt[:, :-1]).any():
        return "ids not injective"
    for a, b in q.edges:
        if not index.has(rows[:, a], rows[:, b]).all():
            return f"query edge ({a}, {b}) missing from the graph"
    if len({tuple(r) for r in rows.tolist()}) != rows.shape[0]:
        return "duplicate rows"
    return None


def disagreement(a_rows, b_rows, k: int) -> str | None:
    """Two complete first-``k`` answers to one query disagree when they
    differ in size, or, below ``k`` rows, in their row sets."""
    if len(a_rows) != len(b_rows):
        return f"{len(a_rows)} rows vs {len(b_rows)}"
    if len(a_rows) < k and {tuple(r) for r in a_rows.tolist()} != {
        tuple(r) for r in b_rows.tolist()
    }:
        return "row sets differ"
    return None


def served_disagreement(res, out) -> str | None:
    """How a served outcome disagrees with the (complete) ``run`` result of
    the same query. The server streams at the compiled caps and never
    escalates them, so the one partial it may give is a capacity overflow,
    with no degrade reason, on a query whose ``run`` had to escalate; its
    rows are then some of the query's matches, which a ``run`` of fewer
    than K rows holds all of. Any other partial disagrees."""
    if out.result.complete:
        return disagreement(res.rows, out.rows, FIRST_K)
    if out.result.stats.degrade_reason is not None:
        return f"served partial ({out.result.stats.degrade_reason})"
    if res.stats.retries == 0:
        return "served partial, but run needed no escalation"
    if len(res.rows) < FIRST_K and not {tuple(r) for r in out.rows.tolist()} <= {
        tuple(r) for r in res.rows.tolist()
    }:
        return "served rows that are not among run's"
    return None


# ----------------------------------------------------------------- queries
def make_queries(g, seed: int, n_dfs: int = 4, n_path: int = 4,
                 dfs_nodes=(6, 10), path_nodes=(4, 6)):
    """Seeded DFS and path queries sampled from ``g`` (always matchable)."""
    import numpy as np

    from repro.workloads import dfs_query, path_query

    rng = np.random.default_rng(seed)
    out = []
    for kind, gen, count, (lo, hi) in (
        ("dfs", dfs_query, n_dfs, dfs_nodes),
        ("path", path_query, n_path, path_nodes),
    ):
        made = 0
        for _ in range(200 * count):
            if made == count:
                break
            nq = int(rng.integers(lo, hi + 1))
            q = gen(g, rng, nq)
            if q is not None and q.n_nodes == nq:
                out.append((f"q{len(out)}:{kind}{nq}", q))
                made += 1
        check(made == count, f"could not sample {count} {kind} queries")
    return out


# ------------------------------------------------------------------ phases
def phase_kernels(kernels, seed: int) -> None:
    """Each op the backend takes from a Pallas kernel equals the jnp op."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.backend import get_kernels

    table = getattr(kernels, "table", {})
    log(f"kernels: backend {kernels.name!r}, per-op implementation {table}")
    ref = get_kernels("jnp")
    rng = np.random.default_rng(seed)
    words = jnp.asarray(
        rng.integers(0, 2**32, 4096, dtype=np.uint64).astype(np.uint32)
    )
    mask = jnp.asarray(rng.random(4096 * 32) < 0.3)
    cases = {
        "bitset_unpack": (words,),
        "bitset_pack": (mask,),
    }
    for op, args in cases.items():
        if table.get(op) != "pallas":
            continue
        got = np.asarray(getattr(kernels, op)(*args))
        want = np.asarray(getattr(ref, op)(*args))
        check(np.array_equal(got, want), f"kernel {op}: Pallas != jnp")
        log(f"kernels: {op} Pallas == jnp on {args[0].shape[0]} elements")


def phase_oracle(seed: int) -> None:
    """Full match sets on a small seeded graph equal the networkx oracle."""
    import numpy as np

    from repro.api import GraphSession
    from repro.graphstore import generators
    from tests.helpers import nx_oracle

    g = generators.rmat(2000, 8000, 24, seed=seed)
    session = GraphSession.open(g)
    index = EdgeIndex(g)
    queries = make_queries(g, seed, n_dfs=3, n_path=2,
                           dfs_nodes=(3, 5), path_nodes=(3, 4))
    for name, q in queries:
        res = session.compile(q, max_matches=0).run()
        got = {tuple(int(x) for x in r) for r in np.asarray(res.rows)}
        want = nx_oracle(g, q)
        err = embedding_errors(index, q, res.rows)
        check(err is None, f"oracle {name}: {err}")
        check(res.complete, f"oracle {name}: incomplete result")
        check(got == want, f"oracle {name}: {len(got)} matches, oracle {len(want)}")
        log(f"oracle: {name} {len(got)} matches == networkx oracle")


def run_queries(session, queries, index, label: str, warm_runs: bool = True):
    """``compile(q, max_matches=1024).run()`` per query, cold then (with
    ``warm_runs``) warm; returns the results and the summed cold-minus-warm
    (compile) time, zero without warm runs."""
    results = {}
    compile_s = 0.0
    for name, q in queries:
        cq = session.compile(q, max_matches=FIRST_K)
        t0 = time.perf_counter()
        res = cq.run()
        cold = time.perf_counter() - t0
        times = f"cold_s={cold:.3f}"
        if warm_runs:
            t0 = time.perf_counter()
            cq.run()
            warm = time.perf_counter() - t0
            compile_s += max(cold - warm, 0.0)
            times += f" warm_s={warm:.3f}"
        err = embedding_errors(index, q, res.rows)
        st = res.stats
        status = "complete" if res.complete else f"partial ({st.degrade_reason})"
        log(
            f"{label}: {name} matches={res.n_matches} status={status} "
            f"{times} retries={st.retries} "
            f"caps={st.final_caps} stwig_rows={st.stwig_rows} "
            f"rounds={st.rounds}"
        )
        check(err is None, f"{label} {name}: {err}")
        check(res.complete, f"{label} {name}: {status}")
        results[name] = res
    return results, compile_s


def peak_bytes(devices) -> dict:
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        out[d.id] = stats.get("peak_bytes_in_use")
    return out


def phase_patents(seed: int, scale: float = 1.0) -> None:
    import jax

    from repro.api import GraphSession
    from repro.graphstore import generators

    t0 = time.perf_counter()
    g = generators.patents_like(scale, seed=seed)
    t_gen = time.perf_counter() - t0
    log(f"patents: nodes={g.n_nodes} edges={g.n_edges} (CSR slots, "
        f"symmetrised) labels={g.n_labels}")
    t0 = time.perf_counter()
    session = GraphSession.open(g)
    t_open = time.perf_counter() - t0
    log(f"patents: set-up generate_s={t_gen:.3f} partition_open_s={t_open:.3f} "
        f"backend={session.backend} kernels={session.kernels.name}")
    phase_kernels(session.kernels, seed)

    index = EdgeIndex(g)
    queries = make_queries(g, seed)
    results, compile_s = run_queries(session, queries, index, "run")
    log(f"patents: compile_s={compile_s:.3f} (cold minus warm, summed over "
        f"{len(queries)} queries; executables cached={len(session.cache)})")

    t0 = time.perf_counter()
    with session.serve(max_inflight=8, max_matches=FIRST_K) as server:
        tickets = [(name, server.submit(q)) for name, q in queries]
        outcomes = {name: t.result() for name, t in tickets}
    serve_s = time.perf_counter() - t0
    for name, q in queries:
        out = outcomes[name]
        res = results[name]
        log(
            f"serve: {name} matches={out.n_matches} status={out.status} "
            f"reason={out.result.stats.degrade_reason} wall_s={out.wall_s:.3f}"
        )
        check(out.status != "failed", f"serve {name}: failed: {out.error}")
        err = embedding_errors(index, q, out.rows)
        check(err is None, f"serve {name}: {err}")
        diff = served_disagreement(res, out)
        check(diff is None, f"serve {name}: run and serve disagree: {diff}")
    log(f"serve: {len(queries)} queries in {serve_s:.3f} s, each agrees "
        f"with run")
    log(f"device: peak_bytes_in_use={peak_bytes(jax.devices()[:1])}")


def phase_sharded(seed: int, chips: int, scale: float = 1.0) -> None:
    import jax

    from repro.api import GraphSession
    from repro.graphstore import generators

    devices = jax.devices()
    t0 = time.perf_counter()
    g = generators.patents_like(scale, seed=seed)
    log(f"sharded: nodes={g.n_nodes} edges={g.n_edges} labels={g.n_labels} "
        f"generate_s={time.perf_counter() - t0:.3f}")
    t0 = time.perf_counter()
    sharded = GraphSession.open(g, backend="sharded", n_shards=chips)
    t_sh = time.perf_counter() - t0
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use") for d in devices}
    log(f"sharded: partition_open_s={t_sh:.3f} shards={sharded.pg.n_shards} "
        f"mesh={[d.id for d in sharded.engine.mesh.devices.flat]} "
        f"bytes_in_use_per_device={in_use}")
    t0 = time.perf_counter()
    local = GraphSession.open(g, backend="local")
    log(f"sharded: local 1-shard partition_open_s={time.perf_counter() - t0:.3f}")

    index = EdgeIndex(g)
    queries = make_queries(g, seed)[:1]
    res_sh, _ = run_queries(sharded, queries, index, "sharded", warm_runs=False)
    res_lo, _ = run_queries(local, queries, index, "local", warm_runs=False)
    for name, _ in queries:
        a, b = res_sh[name], res_lo[name]
        diff = disagreement(a.rows, b.rows, FIRST_K)
        check(diff is None, f"sharded {name}: sharded and local disagree: {diff}")
    log(f"sharded: {len(queries)}/{len(queries)} queries agree with the "
        f"local run")
    log(f"device: peak_bytes_in_use={peak_bytes(devices[:chips])}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 2

    from repro.runtime import compile_cache

    log(f"device: platform={platform} kind={devices[0].device_kind} "
        f"count={len(devices)} compile_cache={compile_cache.enable()}")
    try:
        if args.chips == 1:
            phase_oracle(args.seed)
            phase_patents(args.seed)
        else:
            phase_sharded(args.seed, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
