"""Serving launcher: subgraph-query serving (the paper's workload) or LM
decode serving, selected by --arch family.

    PYTHONPATH=src python -m repro.launch.serve --arch stwig --n-queries 20
    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --tokens 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import GraphSession, summarize_outcomes
from repro.configs import get
from repro.configs.base import LMConfig
from repro.graphstore import generators
from repro.models import transformer as tf
from repro.runtime import compile_cache
from repro.workloads import dfs_query


def serve_stwig(args) -> None:
    cfg = get("stwig").smoke() if args.smoke else get("stwig").config
    n = min(cfg.n_nodes, args.max_nodes)
    print(f"loading {n}-node graph ...")
    g = generators.rmat(n, cfg.avg_degree * n, cfg.n_labels, seed=0)
    session = GraphSession.open(g, backend="local")
    rng = np.random.default_rng(0)
    workload = [q for q in (dfs_query(g, rng, 6) for _ in range(args.n_queries))
                if q is not None]

    server = session.serve(
        max_inflight=args.max_inflight,
        max_matches=cfg.max_matches,
        deadline_s=args.deadline_ms / 1e3 if args.deadline_ms else None,
    )
    t0 = time.perf_counter()
    outcomes = server.serve(workload)
    wall = time.perf_counter() - t0
    for o in outcomes:
        # a partial answer must say so (and why): first-K truncation has no
        # degrade reason, a guard trip / shard fault carries a typed one
        status = ""
        if o.status != "served":
            status = f"  [{o.status}: {o.stats.degrade_reason or o.error or 'overflow'}]"
        ttfp = "-" if o.ttfp_s is None else f"{o.ttfp_s*1e3:.0f} ms"
        print(f"  {o.n_matches} matches, first page in {ttfp}{status}")
    # a query counts as served only when it completed cleanly — guard
    # trips/overflows are partial, per-query exceptions are failed
    s = summarize_outcomes(outcomes)
    print(f"{s['served']} served / {s['partial']} partial / {s['failed']} "
          f"failed in {wall:.1f}s "
          f"({len(outcomes)/wall:.2f} qps over {server.stats.join_quanta} "
          f"block-join quanta, {server.stats.global_degradations} global "
          f"degradations; cache: {session.cache.hits} hits / "
          f"{session.cache.misses} misses)")


def serve_lm(args) -> None:
    entry = get(args.arch)
    cfg: LMConfig = entry.smoke()
    params = tf.init(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (args.batch, 8), 0, cfg.vocab_size)
    logits, cache = tf.prefill(cfg, params, prompt)
    cache_full = tf.init_cache(cfg, args.batch, 8 + args.tokens)
    data = tuple(
        jax.lax.dynamic_update_slice(z, c.astype(z.dtype), (0,) * z.ndim)
        for z, c in zip(cache_full.data, cache.data)
    )
    cache = cache_full.replace_data(data)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    step = jax.jit(lambda p, c, t, pos: tf.decode_step(cfg, p, c, t, pos))
    out = [tok]
    t0 = time.perf_counter()
    for i in range(args.tokens):
        logits, cache = step(params, cache, tok, jnp.int32(8 + i))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    print(f"decoded {args.tokens} tokens × batch {args.batch} in {dt:.2f}s "
          f"({args.tokens*args.batch/dt:.0f} tok/s on CPU, smoke config)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stwig")
    ap.add_argument("--n-queries", type=int, default=10)
    ap.add_argument("--max-nodes", type=int, default=50_000)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-query deadline (0 = none); expired queries "
                    "return partial results marked [partial: deadline]")
    ap.add_argument("--max-inflight", type=int, default=8,
                    help="concurrent queries the server interleaves "
                    "block-join quanta across (continuous batching)")
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True, help="the config's smoke size (--no-smoke "
                    "for its full size)")
    args = ap.parse_args()
    compile_cache.enable()
    if args.arch == "stwig":
        serve_stwig(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
