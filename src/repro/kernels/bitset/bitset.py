"""Pallas TPU kernels for packed binding bitsets (DESIGN.md §2; registered
as the ``pallas`` backend's bitset ops in `repro.core.backend`).

Two layouts matter in the matcher:
  * *range* ops — root-candidate masks over the shard's own contiguous id
    block: fully vectorized unpack/pack (bit algebra over aligned tiles).
  * *gather* ops — membership tests for arbitrary (remote) ids:
    ``bitset_lookup`` gathers one word per id from the VMEM-resident bitset
    (TPU dynamic-gather; ids tiled over the grid).

The packed uint32 convention matches ``repro.graphstore.labels``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the reference lookup is pure jnp on values, so the kernels reuse it on
# their VMEM blocks — one copy of the masked bit-twiddle, everywhere
from repro.kernels.bitset.ref import WORD_BITS, lookup_reference


# ------------------------------------------------------- range ops (2-D)
# Mosaic lays a block out as (sublane, lane) tiles: a 1-D bool block that is
# reshaped between words and bits is refused ("unsupported shape cast"), and
# so is a lane reduction over uint32. The range kernels therefore work on
# int32 2-D blocks (pack: ``(W, 32)`` bit rows to a ``(W, 1)`` word column;
# unpack: four words per row to 128-lane bit rows); the uint32/bool views
# are taken outside the kernel, and rows are padded to whole ``bw``-row
# blocks.
_LANES = 128


def _blocks(W: int, bw: int) -> tuple[int, int]:
    bw = min(bw, -(-W // 8) * 8)
    return bw, -(-W // bw) * bw


def _unpack_kernel(w_ref, o_ref):
    # four words per 128-lane row: lane j holds bit j % 32 of word j // 32,
    # so the row-major output is already the flat bit order
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    w = w_ref[...]
    bits = jnp.zeros(o_ref.shape, jnp.int32)
    for c in range(_LANES // WORD_BITS):
        own = jax.lax.shift_right_logical(w[:, c : c + 1], lane % WORD_BITS) & 1
        bits = jnp.where(lane // WORD_BITS == c, own, bits)
    o_ref[...] = bits


def bitset_unpack(words: jnp.ndarray, *, bw: int = 512, interpret: bool = False):
    """(W,) uint32 → (W*32,) bool, tiled over word blocks."""
    W = words.shape[0]
    per_row = _LANES // WORD_BITS
    rows = -(-W // per_row)
    bw, rp = _blocks(rows, bw)
    w = jax.lax.bitcast_convert_type(words, jnp.int32)
    w = jnp.pad(w, (0, rp * per_row - W)).reshape(rp, per_row)
    bits = pl.pallas_call(
        _unpack_kernel,
        grid=(rp // bw,),
        in_specs=[pl.BlockSpec((bw, per_row), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bw, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, _LANES), jnp.int32),
        interpret=interpret,
    )(w)
    # (rows, 128) flattens without a relayout; a (W, 32) view would not
    return bits.reshape(rp * _LANES)[: W * WORD_BITS] != 0


def _pack_kernel(m_ref, o_ref):
    shifts = jax.lax.broadcasted_iota(jnp.int32, m_ref.shape, 1)
    # distinct bits, so the int32 sum is their OR (bit 31 wraps to the sign)
    o_ref[...] = jnp.sum(m_ref[...] << shifts, axis=1, keepdims=True)


def bitset_pack(mask: jnp.ndarray, *, bw: int = 512, interpret: bool = False):
    """(n,) bool (n % 32 == 0) → (n/32,) uint32."""
    n = mask.shape[0]
    assert n % WORD_BITS == 0
    W = n // WORD_BITS
    bw, wp = _blocks(W, bw)
    m = mask.astype(jnp.int32).reshape(W, WORD_BITS)
    m = jnp.pad(m, ((0, wp - W), (0, 0)))
    words = pl.pallas_call(
        _pack_kernel,
        grid=(wp // bw,),
        in_specs=[pl.BlockSpec((bw, WORD_BITS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bw, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((wp, 1), jnp.int32),
        interpret=interpret,
    )(m)
    return jax.lax.bitcast_convert_type(words[:W, 0], jnp.uint32)


# ----------------------------------------------------------------- lookup
def _lookup_kernel(w_ref, id_ref, o_ref):
    # w_ref: VMEM-resident bitset
    o_ref[...] = lookup_reference(w_ref[...], id_ref[...])


def bitset_lookup(
    words: jnp.ndarray,
    ids: jnp.ndarray,
    *,
    bi: int = 2048,
    interpret: bool = False,
):
    """Membership test for arbitrary int32 ids. Negative or out-of-range ids
    are masked to ``False`` in-kernel (an earlier version clipped them onto
    word 0 / the last word, silently aliasing adversarial ids onto real
    bits). The bitset stays VMEM-resident across id tiles — per-shard
    bitsets are ≤ a few MB at production shard counts."""
    n = ids.shape[0]
    bi = min(bi, n)
    while n % bi:
        bi //= 2
    return pl.pallas_call(
        _lookup_kernel,
        grid=(n // bi,),
        in_specs=[
            pl.BlockSpec(words.shape, lambda i: (0,)),
            pl.BlockSpec((bi,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((bi,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.bool_),
        interpret=interpret,
    )(words, ids)


# --------------------------------------------------------- candidate filter
def _cand_filter_kernel(w_ref, id_ref, lab_ref, rok_ref, o_ref, *, child_label):
    """Fused MatchSTwig step-2: per edge, dst-label equality ∧ binding-bit
    test ∧ root-candidacy — one VMEM pass instead of three XLA ops."""
    ids = id_ref[...]
    o_ref[...] = (
        rok_ref[...]
        & (lab_ref[...] == child_label)
        & lookup_reference(w_ref[...], ids)
    )


def candidate_filter(
    words: jnp.ndarray,       # (W,) uint32 binding bitset (VMEM-resident)
    dst_ids: jnp.ndarray,     # (E,) int32 edge destination ids
    dst_labels: jnp.ndarray,  # (E,) int32 destination labels
    root_ok: jnp.ndarray,     # (E,) bool root-candidacy per edge
    child_label: int,
    *,
    bi: int = 2048,
    interpret: bool = False,
) -> jnp.ndarray:
    n = dst_ids.shape[0]
    bi = min(bi, n)
    while n % bi:
        bi //= 2
    return pl.pallas_call(
        functools.partial(_cand_filter_kernel, child_label=child_label),
        grid=(n // bi,),
        in_specs=[
            pl.BlockSpec(words.shape, lambda i: (0,)),
            pl.BlockSpec((bi,), lambda i: (i,)),
            pl.BlockSpec((bi,), lambda i: (i,)),
            pl.BlockSpec((bi,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((bi,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.bool_),
        interpret=interpret,
    )(words, dst_ids, dst_labels, root_ok)
