"""Pure-jnp oracle for the fused STwig expansion (MatchSTwig steps 2-3).

Factored out of `repro.core.match.expand_stwig_shard`'s per-child loop so the
logic exists once, behind the `Kernels` registry: per-child candidate-edge
filtering (dst-label equality ∧ binding-bit membership) followed by
compaction into fixed-capacity candidate lists, one per row. A row is one
root candidate, given by the bounds ``[lo, hi)`` of its CSR edge segment;
the caller passes only the candidates (the nodes with the root's label),
so nothing past the per-edge filter runs once per node slot.

Compaction is scatter-free. Per child the pass builds two edge-length
arrays — ``ic`` (inclusive cumsum of the survivor mask) and ``nxt``
(reverse cummin of survivor edge index, i.e. the first surviving edge at or
after each position, sentinel ``E``) — then, per row:

  * the exact count comes from two boundary gathers into ``ic``
    (``ic[hi-1] - ic[lo-1]``), and
  * the candidate list comes from a ``child_cap``-step gather chain through
    ``nxt``: ``e0 = nxt[lo]``, ``e_{p+1} = nxt[e_p + 1]`` — the row's first
    ``child_cap`` survivors in edge order, no sort, no scatter.

The survivor mask covers every edge, not only the rows' segments: a row
reads the prefix count only inside its own segment, and chain steps past
its count are cut, so survivors of other roots' edges change nothing.

Each phase of each child runs under a `jax.named_scope`
(``stwig_expand/child<i>/survivors`` for the passes over every edge slot,
``.../gather`` for the chain, ``.../cap`` for the cut at the count), so a
profiler trace's device operations say which phase they belong to.

Contract (shared with the Pallas kernel):
  * ``lo``, ``hi`` are ``(rows,)`` int32 segment bounds into the edge
    arrays, ``0 <= lo <= hi <= E``; a pad row is the empty ``[E, E)``.
  * ``cand[c, r, p]`` is the ``p``-th (in edge order) surviving destination
    of row ``r`` for child ``c``; unused slots hold the ghost id
    ``n_total``. Row ``rows`` is all ghost ids, for callers that index pad
    rows there.
  * ``cnt[c, r]`` is the EXACT per-row candidate count — it may exceed
    ``child_cap`` (the caller uses that to flag overflow); only the first
    ``child_cap`` candidates are materialized.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import scan as _scan
from repro.kernels.bitset.ref import lookup_reference


def stwig_expand_reference(
    words_k: jnp.ndarray,     # (k, W) uint32 binding bitsets, row per child
    dst_ids: jnp.ndarray,     # (E,) int32 edge destination global ids
    dst_labels: jnp.ndarray,  # (E,) int32 destination labels
    lo: jnp.ndarray,          # (rows,) int32 segment starts, pad E
    hi: jnp.ndarray,          # (rows,) int32 segment ends, pad E
    *,
    child_labels: tuple[int, ...],
    child_bound: tuple[bool, ...],
    child_cap: int,
    n_total: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns ``cand (k, rows+1, child_cap)`` and ``cnt (k, rows)``."""
    k = len(child_labels)
    C = child_cap
    E = dst_ids.shape[0]
    # np.int32 literals: a bare Python int branch arrives as an int64
    # scalar under x64 (staticcheck jaxpr-dtype-width)
    iE = np.int32(E)
    slots = jnp.arange(C, dtype=jnp.int32)
    edge_idx = jnp.arange(E, dtype=jnp.int32)
    ghost_row = jnp.full((1, C), np.int32(n_total), jnp.int32)
    cands, cnts = [], []
    for i in range(k):
        scope = f"stwig_expand/child{i}/"
        with jax.named_scope(scope + "survivors"):
            m = dst_labels == np.int32(child_labels[i])
            if child_bound[i]:
                m &= lookup_reference(words_k[i], dst_ids)
            ic = _scan.cumsum(m)
            nxt = _scan.suffix_min(jnp.where(m, edge_idx, iE), iE)
            # nxt_pad[E] = E so the chain saturates at the sentinel
            nxt_pad = jnp.concatenate([nxt, jnp.full((1,), iE, jnp.int32)])
            base = jnp.where(
                lo > 0, jnp.take(ic, jnp.maximum(lo - 1, 0), mode="clip"),
                np.int32(0),
            )
            last = jnp.where(
                hi > 0, jnp.take(ic, jnp.maximum(hi - 1, 0), mode="clip"),
                np.int32(0),
            )
            cnt = last - base  # (rows,) exact counts
        with jax.named_scope(scope + "gather"):
            # one 1-D gather of destinations per chain step: a single
            # gather through the stacked (rows, C) index takes the TPU
            # compiler about a minute at some widths (cap 2^20, E 2^23)
            e = jnp.take(nxt_pad, jnp.minimum(lo, iE), mode="clip")
            vals = []
            for p in range(C):
                if p:
                    e = jnp.take(
                        nxt_pad, jnp.minimum(e + np.int32(1), iE), mode="clip"
                    )
                vals.append(
                    jnp.take(dst_ids, jnp.minimum(e, iE - np.int32(1)), mode="clip")
                )
        with jax.named_scope(scope + "cap"):
            c_i = jnp.where(
                slots[None, :] < cnt[:, None],
                jnp.stack(vals, axis=1),  # (rows, C)
                np.int32(n_total),
            )
        cands.append(jnp.concatenate([c_i, ghost_row]))
        cnts.append(cnt)
    return jnp.stack(cands), jnp.stack(cnts)
