"""Pallas TPU kernels fusing MatchSTwig steps 2-3 (paper Algorithm 1).

Three short kernels replace the old single kernel's serial edge walk with
per-tile vectorized compaction:

  * **mask+ics** — forward pass over edge tiles: the per-child candidate
    filter (dst-label equality ∧ binding-bit membership, bitsets
    VMEM-resident, out-of-range ids masked False), then an
    in-tile log-doubling inclusive prefix sum of the stacked ``(k, be)``
    mask. A ``(k, 1)`` carry output revisited with a constant index map
    threads the running totals across the sequential grid, so the prefix
    sums are global and overflow-past-``child_cap`` semantics are
    unchanged — counts keep growing past the materialized capacity.
  * **nxt** — the same grid traversed in REVERSE via the block index map:
    an in-tile log-doubling suffix-min of the survivor edge index
    (sentinel = padded length) plus a carried minimum gives, per position,
    the first surviving edge at or after it.
  * **emit** — a grid over tiles of rows (root candidates, each given by
    its segment bounds ``[lo, hi)``) with the full ``(k, epad)`` prefix
    arrays VMEM-resident: per row, exact counts from two boundary gathers
    into the prefix sums, the candidate list from a ``child_cap``-step
    vectorized gather chain through ``nxt``, and one whole-block store per
    output. No scalar dynamic stores anywhere.

Edge arrays are padded to a tile multiple (pad dst = ghost ``n_total``,
pad label -1, which no child has), so any edge count — including
odd/prime ``E`` — keeps full-width tiles; the old fallback halved the tile
size until it divided ``E``, collapsing to 1-edge tiles for prime ``E``.
Row tiles are padded the same way (empty segments at the pad sentinel)
and sliced off the outputs.

Oracle: `repro.kernels.stwig_expand.ref.stwig_expand_reference` (same
scatter-free formulation in pure jnp).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitset.ref import lookup_reference


def _mask_ics_kernel(
    w_ref,      # (k, W) uint32 binding bitsets
    dst_ref,    # (be,) int32 destination ids
    lab_ref,    # (be,) int32 destination labels
    mask_ref,   # (k, be) bool out — survivor mask
    ics_ref,    # (k, be) int32 out — global inclusive cumsum of the mask
    carry_ref,  # (k, 1) int32 — running totals, revisited every tile
    *,
    child_labels: tuple[int, ...],
    child_bound: tuple[bool, ...],
    be: int,
):
    k = len(child_labels)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.zeros(carry_ref.shape, jnp.int32)

    ids = dst_ref[...]
    labs = lab_ref[...]
    words = w_ref[...]
    masks = []
    for c in range(k):
        m = labs == np.int32(child_labels[c])
        if child_bound[c]:
            m &= lookup_reference(words[c], ids)
        masks.append(m)
    mk = jnp.stack(masks)                       # (k, be)
    x = mk.astype(jnp.int32)
    s = 1
    while s < be:                               # log-doubling inclusive scan
        x = x + jnp.pad(x, ((0, 0), (s, 0)))[:, :be]
        s *= 2
    x = x + carry_ref[...]
    mask_ref[...] = mk
    ics_ref[...] = x
    carry_ref[...] = x[:, -1:]


def _nxt_kernel(mask_ref, nxt_ref, carry_ref, *, k, be, n_tiles, epad):
    """Reverse traversal (index map runs tiles last-to-first): per position,
    the smallest surviving global edge index at or after it (sentinel
    ``epad``), via in-tile suffix-min + carried minimum."""
    t = pl.program_id(0)                        # 0 => LAST tile

    @pl.when(t == 0)
    def _init():
        carry_ref[...] = jnp.full(carry_ref.shape, np.int32(epad), jnp.int32)

    tile = (n_tiles - 1) - t                    # actual tile index
    gidx = np.int32(be) * tile.astype(jnp.int32) + jax.lax.broadcasted_iota(
        jnp.int32, (k, be), 1
    )
    y = jnp.where(mask_ref[...], gidx, np.int32(epad))
    s = 1
    while s < be:
        y = jnp.minimum(
            y,
            jnp.pad(y, ((0, 0), (0, s)), constant_values=np.int32(epad))[:, s:],
        )
        s *= 2
    y = jnp.minimum(y, carry_ref[...])
    nxt_ref[...] = y
    carry_ref[...] = y[:, :1]


def _emit_kernel(
    lo_ref,    # (rt,) int32 segment starts
    hi_ref,    # (rt,) int32 segment ends
    ics_ref,   # (k, epad) int32 — whole array resident
    nxt_ref,   # (k, epad) int32 — whole array resident
    dst_ref,   # (epad,) int32 — whole array resident
    cand_ref,  # (k, rt, C) int32 out
    cnt_ref,   # (k, rt) int32 out
    *,
    k: int,
    C: int,
    n_total: int,
    epad: int,
):
    lo = lo_ref[...]
    hi = hi_ref[...]
    iep = np.int32(epad)
    cands, cnts = [], []
    for c in range(k):
        ic = ics_ref[c]
        nx = nxt_ref[c]
        base = jnp.where(
            lo > 0, jnp.take(ic, jnp.maximum(lo - 1, 0), mode="clip"),
            np.int32(0),
        )
        last = jnp.where(
            hi > 0, jnp.take(ic, jnp.maximum(hi - 1, 0), mode="clip"),
            np.int32(0),
        )
        cnt = last - base                       # (rt,) exact counts
        e = jnp.where(
            lo < iep,
            jnp.take(nx, jnp.minimum(lo, iep - np.int32(1)), mode="clip"),
            iep,
        )
        es = [e]
        for _ in range(C - 1):
            q = e + np.int32(1)
            e = jnp.where(
                q < iep,
                jnp.take(nx, jnp.minimum(q, iep - np.int32(1)), mode="clip"),
                iep,
            )
            es.append(e)
        ee = jnp.stack(es, axis=1)              # (rt, C)
        slots = jax.lax.broadcasted_iota(jnp.int32, ee.shape, 1)
        cv = jnp.where(
            slots < cnt[:, None],
            jnp.take(dst_ref[...], jnp.minimum(ee, iep - np.int32(1)),
                     mode="clip"),
            np.int32(n_total),
        )
        cands.append(cv)
        cnts.append(cnt)
    cand_ref[...] = jnp.stack(cands)
    cnt_ref[...] = jnp.stack(cnts)


def stwig_expand(
    words_k: jnp.ndarray,     # (k, W) uint32
    dst_ids: jnp.ndarray,     # (E,) int32
    dst_labels: jnp.ndarray,  # (E,) int32
    lo: jnp.ndarray,          # (rows,) int32 segment starts, pad E
    hi: jnp.ndarray,          # (rows,) int32 segment ends, pad E
    *,
    child_labels: tuple[int, ...],
    child_bound: tuple[bool, ...],
    child_cap: int,
    n_total: int,
    be: int = 2048,
    rt: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused filter + compaction: ``cand (k, rows+1, C)``, ``cnt (k, rows)``."""
    k = len(child_labels)
    assert k >= 1 and words_k.shape[0] == k
    C = child_cap
    E = dst_ids.shape[0]
    be = min(be, max(E, 1))
    n_tiles = -(-E // be) if E else 1
    epad = n_tiles * be
    pad_e = epad - E
    if pad_e:  # full-width tiles for any E (prime E included)
        dst_ids = jnp.pad(dst_ids, (0, pad_e), constant_values=np.int32(n_total))
        dst_labels = jnp.pad(dst_labels, (0, pad_e), constant_values=np.int32(-1))

    mask, ics, _ = pl.pallas_call(
        functools.partial(
            _mask_ics_kernel,
            child_labels=tuple(child_labels),
            child_bound=tuple(child_bound),
            be=be,
        ),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(words_k.shape, lambda i: (0, 0)),
            pl.BlockSpec((be,), lambda i: (i,)),
            pl.BlockSpec((be,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((k, be), lambda i: (0, i)),
            pl.BlockSpec((k, be), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, epad), jnp.bool_),
            jax.ShapeDtypeStruct((k, epad), jnp.int32),
            jax.ShapeDtypeStruct((k, 1), jnp.int32),
        ],
        interpret=interpret,
    )(words_k, dst_ids, dst_labels)

    nxt, _ = pl.pallas_call(
        functools.partial(_nxt_kernel, k=k, be=be, n_tiles=n_tiles, epad=epad),
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((k, be), lambda i, n=n_tiles: (0, n - 1 - i))],
        out_specs=[
            pl.BlockSpec((k, be), lambda i, n=n_tiles: (0, n - 1 - i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, epad), jnp.int32),
            jax.ShapeDtypeStruct((k, 1), jnp.int32),
        ],
        interpret=interpret,
    )(mask)

    # pad rows of the last tile get the empty segment [epad, epad), so
    # they emit zero counts and all-ghost rows; one of them is the
    # contract's ghost row
    R = lo.shape[0] + 1
    rt = min(rt, R)
    r_tiles = -(-R // rt)
    rpad = r_tiles * rt - lo.shape[0]
    if rpad:
        lo = jnp.pad(lo, (0, rpad), constant_values=np.int32(epad))
        hi = jnp.pad(hi, (0, rpad), constant_values=np.int32(epad))

    cand, cnt = pl.pallas_call(
        functools.partial(_emit_kernel, k=k, C=C, n_total=n_total, epad=epad),
        grid=(r_tiles,),
        in_specs=[
            pl.BlockSpec((rt,), lambda i: (i,)),
            pl.BlockSpec((rt,), lambda i: (i,)),
            pl.BlockSpec((k, epad), lambda i: (0, 0)),
            pl.BlockSpec((k, epad), lambda i: (0, 0)),
            pl.BlockSpec((epad,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((k, rt, C), lambda i: (0, i, 0)),
            pl.BlockSpec((k, rt), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, r_tiles * rt, C), jnp.int32),
            jax.ShapeDtypeStruct((k, r_tiles * rt), jnp.int32),
        ],
        interpret=interpret,
    )(lo, hi, ics, nxt, dst_ids)
    return cand[:, :R], cnt[:, :R - 1]
