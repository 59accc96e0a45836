"""Vectorized MatchSTwig (paper Algorithm 1) — the TPU-native exploration.

The paper's per-root loop

    for n in Index.getID(r):  c = Cloud.Load(n); filter children by label/binding

becomes one pass over the shard's CSR arrays:

  1. root candidates  = Index.getID(r) from the shard's label index, the
                        plan's ``root_rows`` of them, ∧ binding-bit(root)
  2. child candidates = (label[dst] == l_i) ∧ binding-bit(dst)     (edge-parallel)
  3. per-root candidate lists from each candidate's CSR segment    (per candidate)
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
    n_local: jnp.ndarray       # () int32
    # label index: label l's local ids, ascending, at
    # nodes_by_label[label_indptr[l]:label_indptr[l+1]]
    nodes_by_label: jnp.ndarray  # (cap,) int32, pad = cap
    label_indptr: jnp.ndarray  # (n_labels+1,) int32
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
    overflow: jnp.ndarray   # () bool — some count exceeds child_cap, or
    #                         more root candidates than the plan's rows


def root_candidates(g: ShardGraph, spec: STwigSpec) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The shard's node slots with the root's label, ascending, read from
    its label index: the first ``spec.expand_rows(cap)`` of them (pad
    ``cap``) and how many there are, which may be more."""
    rows = spec.expand_rows(g.cap)
    first = g.label_indptr[spec.root_label]
    n_cand = g.label_indptr[spec.root_label + 1] - first
    pos = jnp.arange(rows, dtype=jnp.int32)
    ids = jnp.take(g.nodes_by_label, first + pos, mode="clip")
    return jnp.where(pos < n_cand, ids, np.int32(g.cap)), n_cand


def expand_stwig_shard(
    g: ShardGraph,
    bind: Bindings,
    spec: STwigSpec,
    kernels: Kernels | None = None,
) -> STwigExpansion:
    """Root candidates and per-root child candidate lists of one STwig on
    one shard (steps 1-3). The per-child filter runs over the edge slots;
    everything else runs over the root candidates only, whose rows are
    then written to their node slots. ``kernels`` selects the backend for
    the dense inner ops (default: the jnp reference set) and must be bound
    statically before ``jit``."""
    kern = kernels if kernels is not None else get_kernels("jnp")
    cap, edge_cap = g.cap, g.edge_cap
    n_total = g.n_total
    k = spec.n_children
    C = spec.child_cap
    iE = np.int32(edge_cap)

    # ---- step 1: root candidates (per candidate) ---------------------------
    root_ids, n_cand = root_candidates(g, spec)
    live = root_ids < cap
    if spec.root_bound:
        gid = g.shard_id.astype(jnp.int32) * cap + root_ids
        live &= kern.bitset_lookup(bind.words[spec.root_qnode], gid)

    # ---- steps 2-3: per-child candidate filter + per-root compaction ------
    # each candidate's CSR segment; a pad or unbound root owns [E, E)
    lo = jnp.where(live, jnp.take(g.indptr, root_ids, mode="clip"), iE)
    hi = jnp.where(live, jnp.take(g.indptr, root_ids + 1, mode="clip"), iE)
    dst_labels = jnp.take(g.all_labels, g.indices, mode="clip")
    words_k = jnp.stack([bind.words[q] for q in spec.child_qnodes])
    cand, cnt = kern.stwig_expand(
        words_k,
        g.indices,
        dst_labels,
        lo,
        hi,
        child_labels=spec.child_labels,
        child_bound=spec.child_bound,
        child_cap=C,
        n_total=n_total,
    )  # (k, rows+1, C) ghost-padded candidate ids / (k, rows) counts

    # ---- prune roots missing required children ----------------------------
    ok = live
    for i in range(k):
        ok &= cnt[i] >= spec.child_need[i]

    # ---- each candidate's row to its node slot -----------------------------
    # one 1-D int32 scatter per column (see `repro.kernels.scan`); the pad
    # candidates, id cap, all write the ghost row its ghost ids and count 0
    def to_slots(col, fill):
        return jnp.full((cap + 1,), fill, jnp.int32).at[root_ids].set(
            col, indices_are_sorted=True
        )

    cand_slots = jnp.stack([
        jnp.stack([to_slots(cand[i, :-1, p], np.int32(n_total)) for p in range(C)],
                  axis=1)
        for i in range(k)
    ])
    root_mask = to_slots(ok.astype(jnp.int32), np.int32(0))[:cap] > 0
    return STwigExpansion(
        cand=cand_slots,
        cnt=jnp.stack([to_slots(cnt[i], np.int32(0)) for i in range(k)]),
        root_mask=root_mask,
        rank=exclusive_cumsum(root_mask),
        n_roots=jnp.sum(ok, dtype=jnp.int32),
        overflow=(jnp.max(cnt) > C) | (n_cand > root_ids.shape[0]),
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
    root_ids, _ = root_candidates(g, spec)

    # ---- select this round's chunk of roots (among the candidates) ---------
    lo = round_idx.astype(jnp.int32) * R
    rank = jnp.take(exp.rank, root_ids, mode="clip")
    sel = jnp.take(exp.root_mask, root_ids, mode="fill", fill_value=False)
    sel &= (rank >= lo) & (rank < lo + R)
    chunk_pos = jnp.where(sel, rank - lo, np.int32(R))
    roots_sel = jnp.full((R,), cap, dtype=jnp.int32)
    roots_sel = roots_sel.at[chunk_pos].set(root_ids, mode="drop")
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
    cols = [jnp.broadcast_to(root_gid[:, None], (R, P)).reshape(-1)] + [
        v.reshape(-1) for v in child_vals
    ]  # width columns of (R*P,)

    n_rows = jnp.sum(flat_ok, dtype=jnp.int32)
    cols, valid = compact_rows(cols, flat_ok, spec.rows_cap, np.int32(n_total))
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
