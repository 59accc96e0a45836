"""Pure-jnp oracle for the fused STwig expansion (MatchSTwig steps 2-3).

Factored out of `repro.core.match.expand_stwig_shard`'s per-child loop so the
logic exists once, behind the `Kernels` registry: per-child candidate-edge
filtering (dst-label equality ∧ binding-bit membership ∧ root candidacy)
followed by per-root compaction into fixed-capacity candidate lists.

Compaction is scatter-free. Per child the pass builds two edge-length
arrays — ``ic`` (inclusive cumsum of the survivor mask) and ``nxt``
(reverse cummin of survivor edge index, i.e. the first surviving edge at or
after each position, sentinel ``E``) — then, because the CSR ``indptr``
gives each root's edge segment ``[lo, hi)`` directly:

  * exact counts come from two boundary gathers into ``ic``
    (``ic[hi-1] - ic[lo-1]``), and
  * the candidate list comes from a ``child_cap``-step gather chain through
    ``nxt``: ``e0 = nxt[lo]``, ``e_{p+1} = nxt[e_p + 1]`` — each root's
    first ``child_cap`` survivors in edge order, no sort, no scatter.

All ``k`` children share the one pass structure (the mask/cumsum/cummin
stage is per child but nothing is re-ranked per root), which is what makes
this the fast CPU hot path: the old formulation scattered every edge into a
``(cap+1, child_cap)`` table per child and re-ranked via segment sums.

Contract (shared with the Pallas kernel):
  * ``cand[c, r, p]`` is the ``p``-th (in edge order) surviving destination
    of root row ``r`` for child ``c``; unused slots hold the ghost id
    ``n_total``. Row ``cap`` is a write-off row for padded edges.
  * ``cnt[c, r]`` is the EXACT per-root candidate count — it may exceed
    ``child_cap`` (the caller uses that to flag overflow); only the first
    ``child_cap`` candidates are materialized.
  * ``indptr`` is ``(cap+2,)`` int32 CSR bounds over the edge arrays:
    root ``r``'s edges live at ``[indptr[r], indptr[r+1])`` and the ghost
    row ``cap`` owns the pad tail ``[indptr[cap], indptr[cap+1] == E)``.
    Edges NOT grouped by root violate the contract (the engine's
    `ShardGraph` arrays are CSR by construction).
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.kernels import scan as _scan
from repro.kernels.bitset.ref import lookup_reference


def stwig_expand_reference(
    words_k: jnp.ndarray,     # (k, W) uint32 binding bitsets, row per child
    dst_ids: jnp.ndarray,     # (E,) int32 edge destination global ids
    dst_labels: jnp.ndarray,  # (E,) int32 destination labels
    indptr: jnp.ndarray,      # (cap+2,) int32 CSR bounds incl. pad tail
    root_ok: jnp.ndarray,     # (E,) bool root-candidacy per edge
    *,
    child_labels: tuple[int, ...],
    child_bound: tuple[bool, ...],
    child_cap: int,
    cap: int,
    n_total: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns ``cand (k, cap+1, child_cap)`` and ``cnt (k, cap)``."""
    k = len(child_labels)
    C = child_cap
    E = dst_ids.shape[0]
    # np.int32 literals: a bare Python int branch arrives as an int64
    # scalar under x64 (staticcheck jaxpr-dtype-width)
    iE = np.int32(E)
    lo = indptr[:-1]  # (cap+1,)
    hi = indptr[1:]
    slots = jnp.arange(C, dtype=jnp.int32)
    edge_idx = jnp.arange(E, dtype=jnp.int32)
    cands, cnts = [], []
    for i in range(k):
        m = root_ok & (dst_labels == np.int32(child_labels[i]))
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
        cnt = last - base  # (cap+1,) exact counts
        # one 1-D gather of destinations per chain step: a single gather
        # through the stacked (cap+1, C) index takes the TPU compiler about
        # a minute at some widths (cap 2^20, E 2^23)
        e = jnp.take(nxt_pad, jnp.minimum(lo, iE), mode="clip")
        vals = []
        for p in range(C):
            if p:
                e = jnp.take(nxt_pad, jnp.minimum(e + np.int32(1), iE), mode="clip")
            vals.append(
                jnp.take(dst_ids, jnp.minimum(e, iE - np.int32(1)), mode="clip")
            )
        c_i = jnp.where(
            slots[None, :] < cnt[:, None],
            jnp.stack(vals, axis=1),  # (cap+1, C)
            np.int32(n_total),
        )
        cands.append(c_i)
        cnts.append(cnt[:cap])
    return jnp.stack(cands), jnp.stack(cnts)
