"""Collectives for the distributed matcher.

The paper's cluster exchanges are (i) binding-set unions across machines and
(ii) load-set-bounded fetches of remote STwig tables. On a TPU mesh these
become:

  * ``or_allreduce`` — recursive-doubling butterfly of bitwise-OR over packed
    binding bitsets (log2(S) ppermute rounds, each moving the full bitset;
    XLA has no OR all-reduce primitive). Falls back to all-gather+reduce for
    non-power-of-two axis sizes.
  * ``gather_load_set`` — the faithful load-set fetch: all-gather the table
    and mask rows from shards outside F_{k,t} (Theorem 4). With a random
    hash partition the cluster graph is complete and this IS the paper's
    communication pattern; ``gather_load_set_ring`` (perf variant) moves
    only distance-bounded hops on sparse cluster graphs.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax


def or_allreduce(words: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Bitwise-OR all-reduce across a mesh axis."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return words
    if n & (n - 1) == 0:
        k = 1
        while k < n:
            perm = [(i, i ^ k) for i in range(n)]
            words = words | lax.ppermute(words, axis_name, perm)
            k *= 2
        return words
    g = lax.all_gather(words, axis_name)
    out = g[0]
    for i in range(1, n):
        out = out | g[i]
    return out


def bool_allreduce_any(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    return lax.pmax(x.astype(jnp.int32), axis_name) > 0


def gather_load_set(
    cols: jnp.ndarray,
    valid: jnp.ndarray,
    load_row: jnp.ndarray,
    axis_name: str,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fetch remote STwig tables, keeping rows only from shards in this
    shard's load set. cols (cap, w), valid (cap,), load_row (S,) bool."""
    # tiled gathers land as (S*cap, w) / (S*cap,) with no reshape: on the
    # TPU, flattening the (S, cap, ...) stack of a narrow or bool array is
    # a relayout that takes the compiler tens of seconds at STwig-table size
    cap = cols.shape[0]
    g_cols = lax.all_gather(cols, axis_name, tiled=True)    # (S*cap, w)
    g_valid = lax.all_gather(valid, axis_name, tiled=True)  # (S*cap,)
    src = lax.broadcasted_iota(jnp.int32, g_valid.shape, 0) // np.int32(cap)
    return g_cols, g_valid & jnp.take(load_row, src)


def fetch_load_set(
    cols: jnp.ndarray,
    valid: jnp.ndarray,
    load_row: jnp.ndarray,
    axis_name: str,
    *,
    ring_radius: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One STwig-table fetch bounded by this shard's load set (Theorem 4):
    the distance-bounded ring exchange when a radius is given (the engine
    verified applicability host-side), the faithful all-gather otherwise.
    Single dispatch point shared by the fused join and the per-block
    streaming gather step."""
    if ring_radius is not None:
        return gather_load_set_ring(cols, valid, load_row, axis_name, ring_radius)
    return gather_load_set(cols, valid, load_row, axis_name)


def gather_load_set_ring(
    cols: jnp.ndarray,
    valid: jnp.ndarray,
    load_row: jnp.ndarray,
    axis_name: str,
    max_dist: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Distance-bounded variant: ``2*max_dist`` ppermute hops instead of a
    full all-gather. Output capacity is (2*max_dist+1) * cap — communication
    and memory proportional to the load-set radius, not the cluster size.

    Only valid when the cluster graph is (a subgraph of) the shard ring —
    e.g. range partitioning of a graph with ring/band locality. The engine
    checks applicability host-side before selecting this path.
    """
    S = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    h = min(max_dist, (S - 1) // 2)
    outs_c = [cols]
    outs_v = [valid & load_row[idx]]
    fwd_c, fwd_v = cols, valid
    bwd_c, bwd_v = cols, valid
    up = [(i, (i + 1) % S) for i in range(S)]
    down = [(i, (i - 1) % S) for i in range(S)]
    for d in range(1, h + 1):
        fwd_c = lax.ppermute(fwd_c, axis_name, up)
        fwd_v = lax.ppermute(fwd_v, axis_name, up)
        src_f = (idx - d) % S
        outs_c.append(fwd_c)
        outs_v.append(fwd_v & load_row[src_f])
        bwd_c = lax.ppermute(bwd_c, axis_name, down)
        bwd_v = lax.ppermute(bwd_v, axis_name, down)
        src_b = (idx + d) % S
        outs_c.append(bwd_c)
        outs_v.append(bwd_v & load_row[src_b])
    return (
        jnp.concatenate(outs_c, axis=0),
        jnp.concatenate(outs_v, axis=0),
    )
