"""Vectorized MatchSTwig (paper Algorithm 1) — the TPU-native exploration.

The paper's per-root loop

    for n in Index.getID(r):  c = Cloud.Load(n); filter children by label/binding

becomes one edge-parallel pass over the shard's CSR arrays:

  1. root candidates  = (label == r) ∧ binding-bit(root)           (node-parallel)
  2. child candidates = (label[dst] == l_i) ∧ binding-bit(dst)     (edge-parallel)
  3. per-root candidate lists via segment-rank compaction (scatter)
  4. STwig emission   = masked cross-product over per-root lists
  5. binding update   = scatter-OR into packed bitsets

Everything is fixed-capacity (see plan.py); the function reports exact counts
and overflow flags so the engine can run more rounds. Every dense inner op —
bitset membership, the fused step-2/3 filter + compaction
(`repro.kernels.stwig_expand` on the Pallas backend), binding builds — goes
through the `Kernels` backend passed in (`repro.core.backend`); the default
``"jnp"`` backend is the reference oracle and the portable path.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.backend import Kernels, get_kernels, n_words
from repro.core.plan import STwigSpec
from repro.kernels.scan import compact_rows, exclusive_cumsum


class ShardGraph(NamedTuple):
    """One shard's slice of the partitioned graph (all jnp arrays)."""

    labels: jnp.ndarray        # (cap,) int32, pad = n_labels
    indptr: jnp.ndarray        # (cap+1,) int32
    indices: jnp.ndarray       # (edge_cap,) int32 global ids, pad = n_total
    edge_src: jnp.ndarray      # (edge_cap,) int32 local rows, pad = cap
    n_local: jnp.ndarray       # () int32
    n_local_edges: jnp.ndarray  # () int32
    shard_id: jnp.ndarray      # () int32
    all_labels: jnp.ndarray    # (n_total+1,) int32 (replicated)

    @property
    def cap(self) -> int:
        return self.labels.shape[0]

    @property
    def edge_cap(self) -> int:
        return self.indices.shape[0]

    @property
    def n_total(self) -> int:
        return self.all_labels.shape[0] - 1


class STwigTable(NamedTuple):
    """Fixed-capacity STwig match table G(q_i) for one shard/round."""

    cols: jnp.ndarray     # (rows_cap, width) int32 global ids, pad = ghost
    valid: jnp.ndarray    # (rows_cap,) bool
    n_rows: jnp.ndarray   # () int32 exact count (may exceed rows_cap)
    n_roots: jnp.ndarray  # () int32 total matching roots on this shard
    overflow: jnp.ndarray  # () bool — any capacity exceeded this round


class Bindings(NamedTuple):
    """Packed binding bitsets H_x for every query node (replicated)."""

    words: jnp.ndarray  # (n_qnodes, n_words) uint32

    @staticmethod
    def fresh(n_qnodes: int, n_bits: int) -> "Bindings":
        return Bindings(jnp.zeros((n_qnodes, n_words(n_bits)), jnp.uint32))


class STwigExpansion(NamedTuple):
    """Steps 1-3 of one STwig on one shard: what every round of root chunks
    shares. Computed once per STwig; each round then only emits its chunk."""

    cand: jnp.ndarray       # (k, cap+1, C) int32 candidate ids, ghost-padded
    cnt: jnp.ndarray        # (k, cap+1) int32 exact counts, pad row zero
    root_mask: jnp.ndarray  # (cap,) bool roots with every required child
    rank: jnp.ndarray       # (cap,) int32 exclusive rank among those roots
    n_roots: jnp.ndarray    # () int32
    overflow: jnp.ndarray   # () bool — some count exceeds child_cap


def expand_stwig_shard(
    g: ShardGraph,
    bind: Bindings,
    spec: STwigSpec,
    kernels: Kernels | None = None,
) -> STwigExpansion:
    """Root candidates and per-root child candidate lists of one STwig on
    one shard (steps 1-3, one edge-parallel pass over the shard's CSR).
    ``kernels`` selects the backend for the dense inner ops (default: the
    jnp reference set) and must be bound statically before ``jit``."""
    kern = kernels if kernels is not None else get_kernels("jnp")
    cap, edge_cap = g.cap, g.edge_cap
    n_total = g.n_total
    k = spec.n_children
    C = spec.child_cap

    node_slot = jnp.arange(cap, dtype=jnp.int32)
    gid = g.shard_id.astype(jnp.int32) * cap + node_slot

    # ---- step 1: root candidate mask (node-parallel) ----------------------
    root_mask = (g.labels == spec.root_label) & (node_slot < g.n_local)
    if spec.root_bound:
        root_mask &= kern.bitset_lookup(bind.words[spec.root_qnode], gid)

    # ---- steps 2-3: per-child candidate filter + per-root compaction ------
    e_pos = jnp.arange(edge_cap, dtype=jnp.int32)
    e_valid = e_pos < g.n_local_edges
    root_ok_e = e_valid & jnp.take(root_mask, g.edge_src, mode="clip") & (
        g.edge_src < cap
    )
    dst_labels = jnp.take(g.all_labels, g.indices, mode="clip")
    # (cap+2,) CSR bounds: row r's edges at [indptr[r], indptr[r+1]), the
    # ghost row cap owning the pad tail [indptr[cap], edge_cap)
    indptr_pad = jnp.concatenate(
        [g.indptr, jnp.full((1,), np.int32(edge_cap), jnp.int32)]
    )
    words_k = jnp.stack([bind.words[q] for q in spec.child_qnodes])
    cand, cnt = kern.stwig_expand(
        words_k,
        g.indices,
        dst_labels,
        indptr_pad,
        root_ok_e,
        child_labels=spec.child_labels,
        child_bound=spec.child_bound,
        child_cap=C,
        cap=cap,
        n_total=n_total,
    )  # (k, cap+1, C) ghost-padded candidate ids / (k, cap) counts

    # ---- prune roots missing required children ----------------------------
    for i in range(k):
        root_mask &= cnt[i] >= spec.child_need[i]
    return STwigExpansion(
        cand=cand,
        cnt=jnp.concatenate([cnt, jnp.zeros((k, 1), jnp.int32)], axis=1),
        root_mask=root_mask,
        rank=exclusive_cumsum(root_mask),
        n_roots=jnp.sum(root_mask, dtype=jnp.int32),
        overflow=jnp.max(cnt) > C,
    )


def emit_stwig_round(
    g: ShardGraph,
    exp: STwigExpansion,
    spec: STwigSpec,
    round_idx: jnp.ndarray,
    n_words_: int,
    kernels: Kernels | None = None,
) -> tuple[STwigTable, Bindings]:
    """Round ``round_idx`` of one STwig's root chunks (steps 4-5): the match
    table of ``spec.root_cap`` roots and *this shard's contribution* to the
    bindings of the STwig's query nodes (``n_words_`` words each)."""
    kern = kernels if kernels is not None else get_kernels("jnp")
    cap, n_total = g.cap, g.n_total
    k = spec.n_children
    C, R = spec.child_cap, spec.root_cap
    node_slot = jnp.arange(cap, dtype=jnp.int32)

    # ---- select this round's chunk of roots --------------------------------
    lo = round_idx.astype(jnp.int32) * R
    sel = exp.root_mask & (exp.rank >= lo) & (exp.rank < lo + R)
    chunk_pos = jnp.where(sel, exp.rank - lo, np.int32(R))
    roots_sel = jnp.full((R,), cap, dtype=jnp.int32)
    roots_sel = roots_sel.at[chunk_pos].set(node_slot, mode="drop")
    root_live = roots_sel < cap
    root_gid = jnp.where(
        root_live, g.shard_id.astype(jnp.int32) * cap + roots_sel, np.int32(n_total)
    )

    cand_sel = [jnp.take(exp.cand[i], roots_sel, axis=0, mode="clip") for i in range(k)]
    cnt_sel = [jnp.take(exp.cnt[i], roots_sel, mode="clip") for i in range(k)]

    # ---- step 4: masked cross-product emission -----------------------------
    grid = jnp.indices((C,) * k, dtype=jnp.int32).reshape(k, -1)  # (k, P)
    P = grid.shape[1]
    child_vals = [
        jnp.take_along_axis(cand_sel[i], grid[i][None, :], axis=1)
        for i in range(k)
    ]  # each (R, P)
    ok = root_live[:, None] & jnp.ones((R, P), bool)
    for i in range(k):
        ok &= grid[i][None, :] < cnt_sel[i][:, None]
    for i, j in spec.same_label_child_pairs:
        ok &= grid[i][None, :] != grid[j][None, :]
    for i in spec.root_label_child_positions:
        ok &= child_vals[i] != root_gid[:, None]
    # int32 0/1: a bool array that crosses a reshape is slow to compile on
    # the TPU
    flat_ok = ok.astype(jnp.int32).reshape(-1)
    rows = [jnp.broadcast_to(root_gid[:, None], (R, P)).reshape(-1)] + [
        v.reshape(-1) for v in child_vals
    ]  # width columns of (R*P,)

    n_rows = jnp.sum(flat_ok, dtype=jnp.int32)
    cols, valid = compact_rows(rows, flat_ok, spec.rows_cap, np.int32(n_total))
    overflow = (n_rows > spec.rows_cap) | exp.overflow

    # ---- step 5: binding contributions (scatter-OR) ------------------------
    contrib = jnp.stack(
        [kern.bitset_build(cols[:, p], valid, n_words_) for p in range(spec.width)]
    )  # (width, W)

    table = STwigTable(
        cols=cols, valid=valid, n_rows=n_rows, n_roots=exp.n_roots,
        overflow=overflow,
    )
    return table, Bindings(contrib)


def apply_binding_update(
    bind: Bindings, spec: STwigSpec, contrib_words: jnp.ndarray
) -> Bindings:
    """Replace the binding rows of this STwig's query nodes with the (already
    cross-shard-reduced) contribution. Replacement is valid because emitted
    columns are always subsets of prior bindings for bound nodes (§4.2)."""
    words = bind.words
    for pos, q in enumerate(spec.qnodes):
        words = words.at[q].set(contrib_words[pos])
    return Bindings(words)
