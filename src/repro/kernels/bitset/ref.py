"""Canonical packed-bitset ops: the pure-jnp reference implementations and
the numpy host-side helpers.

This module is the single source of truth for the packed-uint32 convention —
bit ``i`` of word ``i // 32`` is ``(w >> (i % 32)) & 1``, bitsets cover
global ids ``[0, n_total]`` inclusive of the always-zero ghost id (DESIGN.md
§2). ``repro.graphstore.labels`` re-exports the helpers and the ``jnp``
`Kernels` backend (`repro.core.backend`) registers the reference ops; no
other module does its own bit twiddling.

Out-of-range semantics: ``lookup_reference`` (and the Pallas kernel it is
the oracle for) maps negative or past-the-end ids to ``False`` — an id that
names no bit is a member of no set. (An earlier version clipped, silently
aliasing bad ids onto word 0 / the last word.)
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

WORD_BITS = 32


def n_words(n_bits: int) -> int:
    return (n_bits + WORD_BITS - 1) // WORD_BITS


# --------------------------------------------------------------------- numpy
def pack_bitset(mask: np.ndarray) -> np.ndarray:
    """Pack a bool array (n,) into uint32 words (ceil(n/32),)."""
    n = mask.shape[0]
    pad = (-n) % WORD_BITS
    m = np.concatenate([mask.astype(np.uint8), np.zeros(pad, np.uint8)])
    bits = m.reshape(-1, WORD_BITS).astype(np.uint32)
    shifts = np.arange(WORD_BITS, dtype=np.uint32)
    return (bits << shifts).sum(axis=1, dtype=np.uint32)


def unpack_bitset(words: np.ndarray, n_bits: int) -> np.ndarray:
    shifts = np.arange(WORD_BITS, dtype=np.uint32)
    bits = (words[:, None] >> shifts) & np.uint32(1)
    return bits.reshape(-1)[:n_bits].astype(bool)


def bitset_test_np(words: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Host-side membership test; ids must be in range (no masking)."""
    w = words[ids // WORD_BITS]
    return ((w >> (ids % WORD_BITS).astype(np.uint32)) & np.uint32(1)).astype(bool)


# ----------------------------------------------------------------------- jnp
def unpack_reference(words: jnp.ndarray) -> jnp.ndarray:
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (words[:, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(-1).astype(jnp.bool_)


def pack_reference(mask: jnp.ndarray) -> jnp.ndarray:
    n = mask.shape[0]
    lanes = mask.astype(jnp.uint32).reshape(n // WORD_BITS, WORD_BITS)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return jnp.sum(lanes << shifts, axis=1, dtype=jnp.uint32)


def lookup_reference(words: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Vectorized membership test. Negative or out-of-range ids are ``False``
    (not clipped onto a real word)."""
    # np.int32 keeps the word-index math at 32 bits even under x64, where a
    # bare Python literal would arrive as an int64 scalar operand
    wb = np.int32(WORD_BITS)
    in_range = (ids >= 0) & (ids < np.int32(words.shape[0] * WORD_BITS))
    w = jnp.take(words, ids // wb, mode="clip")
    bit = ((w >> (ids % wb).astype(jnp.uint32)) & jnp.uint32(1)) > 0
    return bit & in_range


def build_reference(ids: jnp.ndarray, valid: jnp.ndarray, nwords: int) -> jnp.ndarray:
    """Build a packed bitset from (possibly duplicated) ids with a validity
    mask; ids outside ``[0, nwords * 32)`` are dropped. XLA has no
    scatter-OR combiner over packed words (duplicate ids landing in one
    word would need an OR accumulator), so this is the closest single-pass
    shape: one int32 lane scatter (duplicate-safe: all updates write the
    same 1), then a shift-OR fold of 32 lanes per word. Lanes are laid out
    bit-major, ``(32, stride)`` with a 1024-aligned stride, so the fold is
    32 contiguous rows. On the TPU compiler a bool or uint8 scatter, or a
    fold over a ``(nwords, 32)`` view, takes seconds to compile at
    million-node widths; this shape takes under one."""
    n_bits = nwords * WORD_BITS
    stride = -(-nwords // 1024) * 1024
    wb = np.int32(WORD_BITS)
    ok = valid & (ids >= 0) & (ids < np.int32(n_bits))
    idx = jnp.where(ok, (ids % wb) * np.int32(stride) + ids // wb,
                    np.int32(WORD_BITS * stride))
    lanes = jnp.zeros((WORD_BITS * stride,), jnp.int32).at[idx].set(
        np.int32(1), mode="drop"
    )
    shifts = jax.lax.broadcasted_iota(jnp.int32, (WORD_BITS, stride), 0)
    # distinct bits, so the int32 sum is their OR (bit 31 wraps to the sign)
    words = jnp.sum(lanes.reshape(WORD_BITS, stride) << shifts, axis=0)
    return words[:nwords].astype(jnp.uint32)


def or_reference(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.bitwise_or(a, b)


def popcount_reference(words: jnp.ndarray) -> jnp.ndarray:
    """Total number of set bits (binding-set cardinality)."""
    return jnp.sum(_popcount32(words))


def _popcount32(w: jnp.ndarray) -> jnp.ndarray:
    w = w - ((w >> 1) & jnp.uint32(0x55555555))
    w = (w & jnp.uint32(0x33333333)) + ((w >> 2) & jnp.uint32(0x33333333))
    w = (w + (w >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (w * jnp.uint32(0x01010101)) >> 24


def candidate_filter_reference(words, dst_ids, dst_labels, root_ok, child_label):
    """Oracle for the fused MatchSTwig step-2 filter (matches core.match)."""
    return root_ok & (dst_labels == child_label) & lookup_reference(words, dst_ids)
