"""Join phase (paper §4.2 step 3): join-order selection + pipelined joins.

Intermediate STwig tables are joined on their shared query nodes. We use a
sort-merge join (TPU-friendly: one sort + lower-bound + windowed probe)
with static capacities. The probe — lower bound, window expansion, and
exact-key verification — is a `Kernels` op (`repro.core.backend`):
`repro.kernels.hash_join` is the Pallas implementation and
`repro.kernels.hash_join.ref` the jnp reference this module defaults to.

Two of the paper's optimizations appear here:
  * join order selection — greedy smallest-intermediate-first over runtime
    row counts (the paper applies a sample-based cost model [14]; our counts
    are exact since every table reports `n_rows`);
  * block-based pipelined join — the engine feeds the first table in blocks
    and stops once `max_matches` results are produced (§6.1 runs terminate
    after 1024 matches).

Rows are *subgraph-isomorphism* embeddings: any two query nodes with equal
labels must map to distinct data nodes; the filter runs incrementally at
every join (different-label pairs are distinct for free).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.backend import Kernels, get_kernels
from repro.kernels.scan import compact_rows, cumsum, radix_argsort
from repro.runtime.spans import host_get, span


class JoinTable(NamedTuple):
    cols: jnp.ndarray    # (cap, width) int32 global ids (ghost-padded)
    valid: jnp.ndarray   # (cap,) bool
    n_rows: jnp.ndarray  # () int32 exact (pre-truncation) count
    overflow: jnp.ndarray  # () bool


@dataclasses.dataclass(frozen=True)
class Schema:
    qnodes: tuple[int, ...]
    qlabels: tuple[int, ...]  # labels of those query nodes

    def merge(self, other: "Schema") -> tuple["Schema", tuple[int, ...]]:
        shared = tuple(q for q in other.qnodes if q in self.qnodes)
        extra = tuple(
            (q, l)
            for q, l in zip(other.qnodes, other.qlabels)
            if q not in self.qnodes
        )
        merged = Schema(
            self.qnodes + tuple(q for q, _ in extra),
            self.qlabels + tuple(l for _, l in extra),
        )
        return merged, shared


def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer (uint32)."""
    x = x.astype(jnp.uint32)
    x ^= x >> 16
    x *= jnp.uint32(0x85EBCA6B)
    x ^= x >> 13
    x *= jnp.uint32(0xC2B2AE35)
    x ^= x >> 16
    return x


def _combine_keys(cols: jnp.ndarray, positions: tuple[int, ...]) -> jnp.ndarray:
    """Mix the key columns into one uint32 sort key. Collisions are possible
    (they only cost probe-window slots: exact column equality is always
    verified at probe time)."""
    k = jnp.zeros(cols.shape[0], dtype=jnp.uint32)
    for p in positions:
        k = _mix32(k ^ _mix32(cols[:, p].astype(jnp.uint32)))
        k = k * jnp.uint32(5) + jnp.uint32(0xE6546B64)
    return k


def sort_merge_join(
    a: JoinTable,
    b: JoinTable,
    schema_a: Schema,
    schema_b: Schema,
    *,
    out_cap: int,
    dup_cap: int,
    kernels: Kernels | None = None,
) -> tuple[JoinTable, Schema]:
    """R_a ⋈ R_b on shared query nodes; output capacity ``out_cap``;
    at most ``dup_cap`` equal-key rows on the build (a) side per probe.
    ``kernels`` selects the probe backend (default: jnp reference) and must
    be bound statically before ``jit``."""
    kern = kernels if kernels is not None else get_kernels("jnp")
    merged_schema, shared = schema_a.merge(schema_b)
    assert shared, "join between disconnected tables"
    pos_a = tuple(schema_a.qnodes.index(q) for q in shared)
    pos_b = tuple(schema_b.qnodes.index(q) for q in shared)

    BIG = jnp.uint32(0xFFFFFFFF)
    key_a = jnp.where(a.valid, _combine_keys(a.cols, pos_a), BIG)
    key_b = _combine_keys(b.cols, pos_b)
    # an int32 permutation (argsort's is int64 under x64 and would widen
    # every downstream gather), from a radix sort the TPU compiles quickly
    ka, order = radix_argsort(key_a)
    a_valid_s = a.valid[order]

    # build-side duplicate-run overflow detection
    run_start = jnp.concatenate(
        [jnp.ones((1,), bool), ka[1:] != ka[:-1]]
    ) | ~a_valid_s
    run_id = cumsum(run_start) - 1
    run_len = jnp.zeros(ka.shape[0], jnp.int32).at[run_id].add(1)
    dup_overflow = jnp.max(jnp.where(a_valid_s, run_len[run_id], np.int32(0))) > dup_cap

    # windowed probe with exact-key verification — one fused backend op.
    # Columns are handled one 1-D int32 array at a time: a narrow
    # (rows, width) array is padded to 128 lanes on the TPU, which at the
    # flattened probe width (capB * dup_cap rows) costs gigabytes
    W = dup_cap
    a_keys_s = jnp.stack([jnp.take(a.cols[:, p], order) for p in pos_a], axis=1)
    b_keys = jnp.stack([b.cols[:, p] for p in pos_b], axis=1)
    hit, probe_c = kern.hash_join_probe(
        ka, a_keys_s, a_valid_s, key_b, b_keys, b.valid, dup_cap=W
    )
    a_rows = order[probe_c]

    # merged row values: all of a's columns + b's extra columns
    extra_pos_b = tuple(
        i for i, q in enumerate(schema_b.qnodes) if q not in schema_a.qnodes
    )
    nb = b.cols.shape[0]
    # the flattened mask stays int32 0/1: a bool array that crosses a
    # reshape at this width takes the TPU compiler 15 s, int32 under one
    flat_hit = hit.astype(jnp.int32).reshape(-1)
    a_rows_f = a_rows.reshape(-1)
    merged_cols = [
        jnp.take(a.cols[:, p], a_rows_f) for p in range(a.cols.shape[1])
    ] + [
        jnp.broadcast_to(b.cols[:, p][:, None], (nb, W)).reshape(-1)
        for p in extra_pos_b
    ]

    # isomorphism (injectivity) filter on equal-label column pairs
    labs = merged_schema.qlabels
    wm = len(merged_schema.qnodes)
    for i in range(wm):
        for j in range(i + 1, wm):
            if labs[i] == labs[j]:
                flat_hit = jnp.where(
                    merged_cols[i] != merged_cols[j], flat_hit, np.int32(0)
                )

    n_rows = jnp.sum(flat_hit, dtype=jnp.int32)
    ghost = jnp.max(a.cols)  # any value; rows are masked by `valid`
    cols, valid = compact_rows(merged_cols, flat_hit, out_cap, ghost)
    overflow = (n_rows > out_cap) | dup_overflow | a.overflow | b.overflow

    return (
        JoinTable(cols=cols, valid=valid, n_rows=n_rows, overflow=overflow),
        merged_schema,
    )


def block_table(table: JoinTable, lo, block_rows: int) -> JoinTable:
    """Rows ``[lo, lo+block_rows)`` of a join table as a fixed-shape block.

    ``lo`` may be a traced scalar: rows are read through a clamped gather so
    one trace serves every block of a given size, and indices past the
    table's capacity are masked invalid (the clamp would otherwise re-read
    the last row and duplicate matches). This is the build side of the
    paper's block-based pipelined join (§4.2 step 3 / §6.1): blocks
    partition the table's valid rows, and every join output row descends
    from exactly one build-side row, so per-block results are disjoint and
    their union equals the unblocked join.
    """
    cap = int(table.cols.shape[0])
    idx = jnp.asarray(lo, jnp.int32) + jnp.arange(block_rows, dtype=jnp.int32)
    safe = jnp.minimum(idx, cap - 1)
    valid = table.valid[safe] & (idx < cap)
    return JoinTable(
        cols=table.cols[safe],
        valid=valid,
        n_rows=jnp.sum(valid, dtype=jnp.int32),
        overflow=jnp.bool_(False),
    )


def fitted_cap(n_rows: int, cap: int, min_cap: int = 64) -> int:
    """The power of two, at least ``min_cap`` and at most ``cap``, that
    holds ``n_rows`` rows: the capacity a sparse table is cut down to."""
    new_cap = min_cap
    while new_cap < n_rows:
        new_cap *= 2
    return min(new_cap, cap)


def _compact_host(cols: np.ndarray, valid: np.ndarray, min_cap: int):
    """Numpy core of the compactions below, over ``cols (S, cap, w)`` and
    ``valid (S, cap)``; None when the capacity would not shrink."""
    counts = valid.sum(axis=1)
    new_cap = fitted_cap(int(counts.max(initial=0)), cols.shape[1], min_cap)
    if new_cap >= cols.shape[1]:
        return None
    out_cols = np.zeros((cols.shape[0], new_cap, cols.shape[2]), cols.dtype)
    out_valid = np.zeros((cols.shape[0], new_cap), bool)
    for s in range(cols.shape[0]):
        keep = np.nonzero(valid[s])[0]
        out_cols[s, : len(keep)] = cols[s, keep]
        out_valid[s, : len(keep)] = True
    return out_cols, out_valid


def compact_table(table: JoinTable, stats, min_cap: int = 64) -> JoinTable:
    """Squeeze a table's valid rows to the front and shrink its capacity to
    the smallest power of two that holds them all (host-side, in the span
    ``repro.compact``; its two reads are host syncs of ``stats``' query, so
    callers must already be off the async path).

    STwig tables are allocated at worst-case capacity (``rows_cap`` per
    exploration round) but are usually sparse, and a join pays
    O(cap × dup_cap) in window expansion and scatter regardless of how many
    rows are real: at full graph width an uncompacted multi-round table
    makes a join that does not fit a 16 GB chip. Both the one-shot and the
    streaming paths compact before joining. Lossless by construction: the
    compact capacity covers every valid row, rows keep their order, and
    the exact-count/overflow flags are carried over unchanged.
    """
    with span("compact"):
        out = _compact_host(
            np.asarray(host_get(stats, table.cols))[None],
            np.asarray(host_get(stats, table.valid))[None],
            min_cap,
        )
        if out is None:
            return table
        return JoinTable(
            cols=jnp.asarray(out[0][0]),
            valid=jnp.asarray(out[1][0]),
            n_rows=table.n_rows,
            overflow=table.overflow,
        )


def compact_stacked(cols, valid, stats, min_cap: int = 64):
    """`compact_table` for per-shard stacked tables ``cols (S, cap, w)`` /
    ``valid (S, cap)``: every shard's valid rows move to the front, and the
    common capacity shrinks to the power of two that holds the fullest
    shard. The results keep the inputs' shardings."""
    import jax

    with span("compact"):
        out = _compact_host(
            np.asarray(host_get(stats, cols)),
            np.asarray(host_get(stats, valid)),
            min_cap,
        )
        if out is None:
            return cols, valid
        # cols and valid share the leading shard axis, so one sharding serves
        return (
            jax.device_put(out[0], cols.sharding),
            jax.device_put(out[1], cols.sharding),
        )


def select_join_order(
    schemas: list[Schema], counts: list[int], start: int | None = None
) -> list[int]:
    """Greedy smallest-intermediate-first join order (host-side).

    Start from the smallest table (or a forced start, e.g. a blocked first
    table in pipelined mode); repeatedly pick the connected table whose
    estimated output (count scaled by shared-key count) is smallest.
    """
    n = len(schemas)
    remaining = set(range(n))
    first = start if start is not None else min(remaining, key=lambda i: counts[i])
    order = [first]
    remaining.discard(first)
    joined = set(schemas[first].qnodes)
    while remaining:
        connected = [i for i in remaining if joined & set(schemas[i].qnodes)]
        pool = connected or list(remaining)
        # more shared keys → more selective; fewer rows → cheaper
        nxt = min(
            pool,
            key=lambda i: (-len(joined & set(schemas[i].qnodes)), counts[i]),
        )
        order.append(nxt)
        remaining.discard(nxt)
        joined |= set(schemas[nxt].qnodes)
    return order
