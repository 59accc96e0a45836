"""Prefix scans built from shifts, and the row compaction built on them,
for programs the TPU compiler takes at full graph width.

XLA lowers ``jnp.cumsum`` / ``lax.cummin`` to a windowed reduction whose
TPU compile time grows with the array length: about 5 s at 2^18 int32
elements and 25 s at 2^20 (v5e, compiled ahead of time), so one
exploration step over a 33M-slot edge array would not compile in a
session's lifetime. These scans are log-doubling (Hillis-Steele) passes
over a ``(rows, 128)`` view, plus the same scan over the row totals: a
fixed handful of elementwise fusions whatever the length, each compiling
in about a second. Every device-side prefix sum and suffix minimum of the
matcher goes through here.

`radix_argsort` sorts uint32 keys by 32 stable one-bit splits, each an
exclusive prefix sum and a scatter, in one ``fori_loop``: ``lax.sort``
takes 15-20 s to compile on the TPU from 2^16 keys up, this about half a
second.

`compact_rows` is the masked stream compaction of STwig emission and join
output. It scatters one int32 column at a time: the TPU compiler takes
16 s for one ``(2^18, 4)`` row scatter and 8 s for a bool scatter of the
same length, and a fraction of a second for each 1-D int32 one.
"""
from __future__ import annotations

from typing import Callable

import numpy as np

import jax.numpy as jnp
from jax import lax

LANES = 128


def _doubling(x: jnp.ndarray, op: Callable, ident, reverse: bool) -> jnp.ndarray:
    """Inclusive scan along the last axis by log-doubling shifts."""
    n = x.shape[-1]
    lead = x.shape[:-1]
    s = 1
    while s < n:
        fill = jnp.full(lead + (s,), ident, x.dtype)
        if reverse:
            shifted = jnp.concatenate([x[..., s:], fill], axis=-1)
        else:
            shifted = jnp.concatenate([fill, x[..., :-s]], axis=-1)
        x = op(x, shifted)
        s *= 2
    return x


def scan(x: jnp.ndarray, op: Callable, ident, *, reverse: bool = False) -> jnp.ndarray:
    """Inclusive scan of a 1-D array under the associative ``op`` whose
    identity is ``ident``; ``reverse=True`` scans from the end."""
    n = x.shape[0]
    ident = np.asarray(ident, x.dtype)
    if n <= 8 * LANES:
        return _doubling(x, op, ident, reverse)
    rows = -(-n // LANES)
    pad = rows * LANES - n
    if pad:  # identity padding at the end leaves every real prefix as is
        x = jnp.concatenate([x, jnp.full((pad,), ident, x.dtype)])
    y = _doubling(x.reshape(rows, LANES), op, ident, reverse)
    totals = scan(y[:, 0] if reverse else y[:, -1], op, ident, reverse=reverse)
    fill = jnp.full((1,), ident, x.dtype)
    carry = (
        jnp.concatenate([totals[1:], fill])
        if reverse
        else jnp.concatenate([fill, totals[:-1]])
    )
    return op(y, carry[:, None]).reshape(-1)[:n]


def cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive int32 prefix sum (bool inputs count as 0/1)."""
    return scan(x.astype(jnp.int32), jnp.add, 0)


def exclusive_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Exclusive int32 prefix sum: the rank of each set entry."""
    x = x.astype(jnp.int32)
    return cumsum(x) - x


def suffix_min(x: jnp.ndarray, ident) -> jnp.ndarray:
    """``out[i] = min(x[i:])``, with ``ident`` the largest value."""
    return scan(x, jnp.minimum, ident, reverse=True)


def radix_argsort(key: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stable ascending sort of ``(n,)`` uint32 keys: the sorted keys and
    the int32 permutation that sorts them (equal keys keep their order)."""
    n = key.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    def split(b, carry):
        key, perm = carry
        one = ((key >> b.astype(jnp.uint32)) & np.uint32(1)).astype(jnp.int32)
        zeros_before = exclusive_cumsum(1 - one)
        n_zeros = zeros_before[-1] + 1 - one[-1]
        pos = jnp.where(one > 0, n_zeros + idx - zeros_before, zeros_before)
        return (
            jnp.zeros_like(key).at[pos].set(key, unique_indices=True),
            jnp.zeros_like(perm).at[pos].set(perm, unique_indices=True),
        )

    if n == 0:
        return key, idx
    return lax.fori_loop(0, 32, split, (key, idx))


def compact_rows(
    cols: list[jnp.ndarray], keep: jnp.ndarray, out_cap: int, fill
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Move the entries of the ``(n,)`` int32 columns ``cols`` where the
    int32 0/1 mask ``keep`` is 1 to the front of an ``(out_cap,
    len(cols))`` table in order, ``fill`` elsewhere; entries past
    ``out_cap`` are dropped. Returns the table and its ``(out_cap,)``
    validity mask."""
    pos = jnp.where(keep > 0, exclusive_cumsum(keep), np.int32(out_cap))
    out = [
        jnp.full((out_cap,), fill, jnp.int32).at[pos].set(c, mode="drop")
        for c in cols
    ]
    valid = jnp.zeros((out_cap,), jnp.int32).at[pos].set(
        np.int32(1), mode="drop"
    )
    return jnp.stack(out, axis=1), valid > 0
