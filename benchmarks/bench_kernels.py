"""Kernel backend microbenchmarks: jnp vs pallas-interpret, per registry op.

Reported rows (``name,us_per_call,derived``): one row per (op, backend),
``derived`` = ``Mrows_s=X`` — millions of processed rows (ids, edges, or
probe keys) per second. On CPU the interpret numbers mostly measure the
Pallas interpreter, not TPU kernels — the point of the suite is (a) a
regression floor for the jnp reference path and (b) a like-for-like harness
that reports real speedups once a TPU is attached (`kernels="pallas"`).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed
from repro.core.backend import get_kernels, n_words

BACKENDS = ("jnp", "pallas-interpret")


def _block(fn):
    """Call + block_until_ready on every leaf."""

    def run():
        out = fn()
        for leaf in jax.tree_util.tree_leaves(out):
            leaf.block_until_ready()

    return run


def _bench_op(name: str, rows: int, make_call) -> None:
    for backend in BACKENDS:
        kern = get_kernels(backend)
        call = jax.jit(make_call(kern))
        us = timed(_block(call)) * 1e6
        emit(f"kernel_{name}_{backend}", us, f"Mrows_s={rows / us:.2f}")


def main() -> None:
    rng = np.random.default_rng(0)

    # ---- bitset ops -------------------------------------------------------
    W = 4096                      # 128 Ki-bit bitset
    n_bits = W * 32
    words = jnp.asarray(rng.integers(0, 2**32, W, dtype=np.uint32))
    mask = jnp.asarray(rng.random(n_bits) < 0.3)
    ids = jnp.asarray(rng.integers(0, n_bits, 1 << 16), jnp.int32)
    valid = jnp.asarray(rng.random(1 << 16) < 0.8)

    _bench_op("bitset_unpack", W, lambda k: lambda: k.bitset_unpack(words))
    _bench_op("bitset_pack", n_bits, lambda k: lambda: k.bitset_pack(mask))
    _bench_op(
        "bitset_lookup", ids.shape[0], lambda k: lambda: k.bitset_lookup(words, ids)
    )
    _bench_op(
        "bitset_build",
        ids.shape[0],
        lambda k: lambda: k.bitset_build(ids, valid, W),
    )

    # ---- candidate filter / stwig_expand ----------------------------------
    E, cap, n_total, C = 1 << 15, 4096, n_bits - 1, 4
    src = np.sort(rng.integers(0, cap, E)).astype(np.int32)
    # the CSR segments of one root candidate in 8 node slots
    indptr = np.searchsorted(src, np.arange(cap + 1)).astype(np.int32)
    roots = np.arange(0, cap, 8)
    lo, hi = jnp.asarray(indptr[roots]), jnp.asarray(indptr[roots + 1])
    dst = jnp.asarray(rng.integers(0, n_total, E), jnp.int32)
    labs = jnp.asarray(rng.integers(0, 8, E), jnp.int32)
    rok = jnp.asarray(rng.random(E) < 0.8)
    words_k = jnp.asarray(rng.integers(0, 2**32, (2, n_words(n_total + 1)), dtype=np.uint32))

    _bench_op(
        "candidate_filter",
        E,
        lambda k: lambda: k.candidate_filter(words, dst, labs, rok, 3),
    )
    _bench_op(
        "stwig_expand",
        E,
        lambda k: lambda: k.stwig_expand(
            words_k,
            dst,
            labs,
            lo,
            hi,
            child_labels=(3, 5),
            child_bound=(True, False),
            child_cap=C,
            n_total=n_total,
        ),
    )

    # ---- hash-join probe --------------------------------------------------
    capA, capB, nk, dup = 1 << 14, 1 << 14, 2, 16
    ka = jnp.asarray(np.sort(rng.integers(0, 1 << 20, capA)).astype(np.uint32))
    akeys = jnp.asarray(rng.integers(0, 1 << 16, (capA, nk)), jnp.int32)
    avalid = jnp.asarray(rng.random(capA) < 0.9)
    kb = jnp.asarray(rng.integers(0, 1 << 20, capB), jnp.uint32)
    bkeys = jnp.asarray(rng.integers(0, 1 << 16, (capB, nk)), jnp.int32)
    bvalid = jnp.asarray(rng.random(capB) < 0.9)

    _bench_op(
        "hash_join_probe",
        capB,
        lambda k: lambda: k.hash_join_probe(
            ka, akeys, avalid, kb, bkeys, bvalid, dup_cap=dup
        ),
    )


if __name__ == "__main__":
    main()
