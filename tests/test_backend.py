"""Kernel backend layer: registry semantics, pallas(interpret) ≡ jnp parity
for every registry op, the bitset_lookup out-of-range regression, and
end-to-end jnp vs pallas-interpret equivalence through the facade."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.backend import (
    Kernels,
    available_backends,
    get_kernels,
    n_words,
    resolve_kernels,
)

JNP = get_kernels("jnp")
PAL = get_kernels("pallas-interpret")


# ----------------------------------------------------------------- registry
def test_registry_names_and_resolution():
    assert {"jnp", "pallas", "pallas-interpret"} <= set(available_backends())
    assert get_kernels("jnp") is get_kernels("jnp")  # singletons
    assert resolve_kernels(JNP) is JNP               # instances pass through
    assert resolve_kernels("jnp").name == "jnp"
    assert resolve_kernels(None).name in ("jnp", "pallas")  # auto
    with pytest.raises(ValueError, match="unknown kernel backend"):
        get_kernels("no-such-backend")


def test_labels_reexports_are_registry_ops():
    # graphstore must hold no bitset logic of its own — its names must BE
    # the canonical reference ops (guards against silent re-divergence)
    from repro.graphstore import labels
    from repro.kernels.bitset import ref

    assert labels.jnp_bitset_test is ref.lookup_reference
    assert labels.jnp_bitset_build is ref.build_reference
    assert labels.pack_bitset is ref.pack_bitset
    assert labels.unpack_bitset is ref.unpack_bitset


# ---------------------------------------------------------------- op parity
def _rand_words(rng, W, rows=None):
    shape = (W,) if rows is None else (rows, W)
    return jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))


def test_parity_bitset_pack_unpack():
    rng = np.random.default_rng(0)
    words = _rand_words(rng, 128)
    bits_j = JNP.bitset_unpack(words)
    bits_p = PAL.bitset_unpack(words)
    assert (np.asarray(bits_j) == np.asarray(bits_p)).all()
    assert (
        np.asarray(JNP.bitset_pack(bits_j)) == np.asarray(PAL.bitset_pack(bits_j))
    ).all()
    assert (np.asarray(PAL.bitset_pack(bits_p)) == np.asarray(words)).all()


def test_parity_bitset_lookup_and_adversarial_ids():
    rng = np.random.default_rng(1)
    W = 64
    words = _rand_words(rng, W)
    n_bits = W * 32
    # in-range, boundary, negative, far out-of-range, INT_MIN/MAX
    ids = jnp.asarray(
        np.concatenate(
            [
                rng.integers(0, n_bits, 256),
                [0, n_bits - 1, n_bits, n_bits + 31, -1, -32, -(2**31), 2**31 - 1],
            ]
        ),
        jnp.int32,
    )
    got_j = np.asarray(JNP.bitset_lookup(words, ids))
    got_p = np.asarray(PAL.bitset_lookup(words, ids))
    assert (got_j == got_p).all()
    # regression: every out-of-range id is False, it aliases no real bit
    oor = (np.asarray(ids) < 0) | (np.asarray(ids) >= n_bits)
    assert not got_j[oor].any()
    assert not got_p[oor].any()
    # in-range ids agree with the host-side reference
    from repro.kernels.bitset.ref import bitset_test_np

    ids_in = np.asarray(ids)[~oor]
    assert (got_j[~oor] == bitset_test_np(np.asarray(words), ids_in)).all()


def test_parity_bitset_build():
    rng = np.random.default_rng(2)
    nwords = 32
    n_bits = nwords * 32
    ids = jnp.asarray(rng.integers(0, n_bits, 500), jnp.int32)
    valid = jnp.asarray(rng.random(500) < 0.7)
    a = np.asarray(JNP.bitset_build(ids, valid, nwords))
    b = np.asarray(PAL.bitset_build(ids, valid, nwords))
    assert (a == b).all()
    # semantic check: exactly the valid ids' bits are set
    want = np.zeros(n_bits, bool)
    want[np.asarray(ids)[np.asarray(valid)]] = True
    got = np.asarray(JNP.bitset_unpack(jnp.asarray(a)))
    assert (got == want).all()


def test_parity_candidate_filter():
    rng = np.random.default_rng(3)
    W, E = 64, 512
    words = _rand_words(rng, W)
    ids = jnp.asarray(rng.integers(-8, W * 32 + 8, E), jnp.int32)  # some OOR
    labs = jnp.asarray(rng.integers(0, 4, E), jnp.int32)
    rok = jnp.asarray(rng.random(E) < 0.7)
    a = np.asarray(JNP.candidate_filter(words, ids, labs, rok, 2))
    b = np.asarray(PAL.candidate_filter(words, ids, labs, rok, 2))
    assert (a == b).all()


def _rows_of(indptr, roots, E):
    """``(lo, hi)`` segment bounds of the rows ``roots`` (node slots, -1 for
    a pad row, which owns the empty ``[E, E)``)."""
    roots = np.asarray(roots)
    lo = np.where(roots >= 0, indptr[np.maximum(roots, 0)], E)
    hi = np.where(roots >= 0, indptr[np.maximum(roots, 0) + 1], E)
    return lo.astype(np.int32), hi.astype(np.int32)


def _expand_inputs(rng, cap=41, E=160, n_total=300, k=2, child_cap=3,
                   n_labels=4, n_rows=12, n_pad=3):
    src = np.sort(rng.integers(0, cap, E)).astype(np.int32)
    # (cap+1,) CSR bounds over the edge arrays
    indptr = np.searchsorted(src, np.arange(cap + 1)).astype(np.int32)
    dst = rng.integers(0, n_total, E).astype(np.int32)
    labs = rng.integers(0, n_labels, E).astype(np.int32)
    W = n_words(n_total + 1)
    words = rng.integers(0, 2**32, (k, W), dtype=np.uint32)
    # candidate rows: some node slots in order, then pad rows
    roots = np.concatenate([
        np.sort(rng.choice(cap, n_rows, replace=False)), np.full(n_pad, -1)
    ])
    lo, hi = _rows_of(indptr, roots, E)
    args = tuple(jnp.asarray(x) for x in (words, dst, labs, lo, hi))
    kw = dict(
        child_labels=(1, 2),
        child_bound=(True, False),
        child_cap=child_cap,
        n_total=n_total,
    )
    return args, kw


def _expand_oracle_np(args, kw):
    """Host-side reference: per row r, the surviving dsts of the edges in
    [lo[r], hi[r]) in edge order; exact counts; a last all-ghost row."""
    from repro.kernels.bitset.ref import bitset_test_np

    words, dst, labs, lo, hi = (np.asarray(a) for a in args)
    k = len(kw["child_labels"])
    rows, C, n_total = lo.shape[0], kw["child_cap"], kw["n_total"]
    cand = np.full((k, rows + 1, C), n_total, np.int32)
    cnt = np.zeros((k, rows), np.int32)
    for c in range(k):
        m = labs == kw["child_labels"][c]
        if kw["child_bound"][c]:
            m &= bitset_test_np(words[c], dst)
        for r in range(rows):
            sel = dst[lo[r]:hi[r]][m[lo[r]:hi[r]]]
            cnt[c, r] = len(sel)
            cand[c, r, : min(len(sel), C)] = sel[:C]
    return cand, cnt


def _assert_expand_parity(args, kw):
    cj, nj = JNP.stwig_expand(*args, **kw)
    cp, np_ = PAL.stwig_expand(*args, **kw)
    assert (np.asarray(nj) == np.asarray(np_)).all()
    assert (np.asarray(cj) == np.asarray(cp)).all()
    co, no = _expand_oracle_np(args, kw)
    assert (np.asarray(nj) == no).all()
    assert (np.asarray(cj) == co).all()


def test_parity_stwig_expand():
    for seed in range(3):
        args, kw = _expand_inputs(np.random.default_rng(seed))
        _assert_expand_parity(args, kw)


def test_stwig_expand_counts_grow_past_child_cap():
    """cnt is EXACT even when a root has more survivors than child_cap —
    the overflow signal the engine's adaptive retry keys on."""
    # row 0 owns every edge, labels/bitsets fully permissive; rows 1-2 are
    # an empty segment and a pad row
    E, n_total = 64, 100
    lo = np.array([0, E // 2, E], np.int32)
    hi = np.array([E, E // 2, E], np.int32)
    dst = np.arange(E, dtype=np.int32)
    labs = np.full(E, 1, np.int32)
    words = np.full((2, n_words(n_total + 1)), 0xFFFFFFFF, np.uint32)
    args = tuple(jnp.asarray(x) for x in (words, dst, labs, lo, hi))
    kw = dict(child_labels=(1, 1), child_bound=(True, False), child_cap=3,
              n_total=n_total)
    for kern in (JNP, PAL):
        cand, cnt = kern.stwig_expand(*args, **kw)
        assert np.asarray(cnt).shape == (2, 3)
        assert np.asarray(cand).shape == (2, 4, 3)
        assert (np.asarray(cnt)[:, 0] == E).all()      # exact, not clamped
        assert (np.asarray(cnt)[:, 1:] == 0).all()
        assert (np.asarray(cand)[:, 0] == [0, 1, 2]).all()  # first C, in order
        assert (np.asarray(cand)[:, 1:] == n_total).all()
    _assert_expand_parity(args, kw)


def test_stwig_expand_segment_straddles_tiles():
    """A root whose surviving edges straddle an edge-tile boundary must
    compact across the carry (pallas tiles at be; force multiple tiles)."""
    from repro.kernels.stwig_expand.stwig_expand import stwig_expand

    rng = np.random.default_rng(11)
    cap, n_total, be = 3, 400, 16
    E = 3 * be  # three tiles
    # root 1's segment covers the first two tile boundaries
    src = np.concatenate([
        np.zeros(4, np.int32), np.full(E - 8, 1, np.int32),
        np.full(4, 2, np.int32),
    ])
    indptr = np.searchsorted(src, np.arange(cap + 1)).astype(np.int32)
    dst = rng.integers(0, n_total, E).astype(np.int32)
    labs = rng.integers(0, 2, E).astype(np.int32)
    words = rng.integers(0, 2**32, (2, n_words(n_total + 1)), dtype=np.uint32)
    lo, hi = _rows_of(indptr, [0, 1, 2, -1], E)
    args = tuple(jnp.asarray(x) for x in (words, dst, labs, lo, hi))
    kw = dict(child_labels=(1, 0), child_bound=(True, False), child_cap=6,
              n_total=n_total)
    cj, nj = JNP.stwig_expand(*args, **kw)
    cp, np_ = stwig_expand(*args, **kw, be=be, interpret=True)
    assert (np.asarray(nj) == np.asarray(np_)).all()
    assert (np.asarray(cj) == np.asarray(cp)).all()
    co, no = _expand_oracle_np(args, kw)
    assert (np.asarray(nj) == no).all() and (np.asarray(cj) == co).all()


@pytest.mark.parametrize("E", [128, 160, 127])  # pow2, non-pow2, prime
def test_parity_stwig_expand_edge_lengths(E):
    """Pinned regression for the degenerate tile fallback: the old kernel
    halved the tile size until it divided E — be=1 (an E-step grid) for
    prime E. The padded-tile kernel must stay exact for any E, including
    a tile size that does NOT divide E (forced be=32); a child of label 0,
    the label jnp.pad would give the pad edges, sees none of them."""
    from repro.kernels.stwig_expand.stwig_expand import stwig_expand

    args, kw = _expand_inputs(np.random.default_rng(17), E=E)
    kw = dict(kw, child_labels=(0, 2))
    _assert_expand_parity(args, kw)
    cj, nj = JNP.stwig_expand(*args, **kw)
    cp, np_ = stwig_expand(*args, **kw, be=32, interpret=True)
    assert (np.asarray(nj) == np.asarray(np_)).all()
    assert (np.asarray(cj) == np.asarray(cp)).all()


@pytest.mark.parametrize("rows, rt", [(1, 512), (7, 4), (8, 4), (600, 512)],
                         ids=["one-row", "row-tiles-pad", "row-tiles-full",
                              "two-default-tiles"])
def test_parity_stwig_expand_row_tiles(rows, rt):
    """The Pallas emit grid tiles the rows: any row count, including one
    that fills its tiles exactly (the ghost row then starts a tile of its
    own), gives the jnp op's lists and counts."""
    from repro.kernels.stwig_expand.stwig_expand import stwig_expand

    rng = np.random.default_rng(rows)
    cap = max(rows, 41)
    args, kw = _expand_inputs(rng, cap=cap, E=4 * cap, n_rows=rows - 1, n_pad=1)
    cj, nj = JNP.stwig_expand(*args, **kw)
    cp, np_ = stwig_expand(*args, **kw, rt=rt, interpret=True)
    assert np.asarray(cp).shape == (2, rows + 1, kw["child_cap"])
    assert (np.asarray(nj) == np.asarray(np_)).all()
    assert (np.asarray(cj) == np.asarray(cp)).all()
    co, no = _expand_oracle_np(args, kw)
    assert (np.asarray(nj) == no).all() and (np.asarray(cj) == co).all()


def test_parity_hash_join_probe():
    rng = np.random.default_rng(5)
    capA, capB, nk, dup = 128, 96, 2, 8
    ka = np.sort(rng.integers(0, 40, capA)).astype(np.uint32)
    akeys = rng.integers(0, 9, (capA, nk)).astype(np.int32)
    avalid = rng.random(capA) < 0.8
    kb = rng.integers(0, 40, capB).astype(np.uint32)
    bkeys = rng.integers(0, 9, (capB, nk)).astype(np.int32)
    bvalid = rng.random(capB) < 0.8
    args = tuple(
        jnp.asarray(x) for x in (ka, akeys, avalid, kb, bkeys, bvalid)
    )
    hj, ij = JNP.hash_join_probe(*args, dup_cap=dup)
    hp, ip = PAL.hash_join_probe(*args, dup_cap=dup)
    assert (np.asarray(hj) == np.asarray(hp)).all()
    assert (np.asarray(ij) == np.asarray(ip)).all()


def test_parity_hash_join_probe_power_of_two_run_start():
    """Regression: with power-of-two cap_a the in-kernel binary search used
    to run one step short, landing one row before the true run start and
    silently dropping the last duplicate of a full-dup_cap run."""
    ka = jnp.asarray([0, 5, 5, 5, 5, 5, 5, 9], jnp.uint32)  # cap_a = 8 = 2**3
    akeys = jnp.arange(8, dtype=jnp.int32)[:, None] * 0 + 5
    avalid = jnp.ones(8, bool)
    kb = jnp.asarray([5], jnp.uint32)
    bkeys = jnp.asarray([[5]], jnp.int32)
    bvalid = jnp.ones(1, bool)
    args = (ka, akeys, avalid, kb, bkeys, bvalid)
    hj, ij = JNP.hash_join_probe(*args, dup_cap=6)
    hp, ip = PAL.hash_join_probe(*args, dup_cap=6)
    assert (np.asarray(hj) == np.asarray(hp)).all()
    assert (np.asarray(ij) == np.asarray(ip)).all()
    # the window must cover the whole run: rows 1..6 all hit
    assert np.asarray(hp).sum() == 6 and np.asarray(ip)[0, 0] == 1


# --------------------------------------------------------------- end to end
def _row_set(res):
    return set(map(tuple, res.rows.tolist()))


def test_end_to_end_local_jnp_vs_pallas_interpret():
    """Acceptance: identical MatchResult rows for the same graph+query under
    kernels="jnp" and kernels="pallas-interpret" (local backend)."""
    from repro.api import GraphSession
    from repro.graphstore import generators
    from repro.workloads import dfs_query, path_query

    g = generators.rmat(200, 700, 5, seed=4, symmetrize=True)
    rng = np.random.default_rng(7)
    queries = []
    while len(queries) < 2:
        q = dfs_query(g, rng, 4) if len(queries) == 0 else path_query(g, rng, 4)
        if q is not None:
            queries.append(q)

    s_jnp = GraphSession.open(g, backend="local", kernels="jnp")
    s_pal = GraphSession.open(g, backend="local", kernels="pallas-interpret")
    assert s_jnp.kernels.name == "jnp"
    assert s_pal.kernels.name == "pallas-interpret"
    for q in queries:
        r_jnp = s_jnp.run(q, max_matches=0)
        r_pal = s_pal.run(q, max_matches=0)
        assert r_jnp.complete == r_pal.complete
        assert _row_set(r_jnp) == _row_set(r_pal)


SHARDED_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 --xla_cpu_use_fusion_emitters=false"
)
import json, sys
import numpy as np
sys.path.insert(0, %r)
from helpers import path_query
from repro.api import GraphSession
from repro.graphstore import PartitionedGraph, generators

g = generators.rmat(120, 420, 4, seed=6, symmetrize=True)
pg = PartitionedGraph.build(g, 4)
rng = np.random.default_rng(3)
q = None
while q is None:
    q = path_query(g, rng, 3)

rows = {}
for kern in ("jnp", "pallas-interpret"):
    s = GraphSession.open(pg, backend="sharded", kernels=kern)
    res = s.run(q, max_matches=0)
    rows[kern] = sorted(map(tuple, res.rows.tolist()))
print(json.dumps({"equal": rows["jnp"] == rows["pallas-interpret"],
                  "n": len(rows["jnp"])}))
"""


@pytest.mark.slow
def test_end_to_end_sharded_jnp_vs_pallas_interpret():
    """Acceptance (sharded half): identical rows under both kernel backends
    through shard_map. Subprocess so the main session keeps one device."""
    import json
    import pathlib
    import subprocess
    import sys

    here = pathlib.Path(__file__).resolve().parent
    src = str(here.parent / "src")
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED_PARITY_SCRIPT % str(here)],
        capture_output=True,
        text=True,
        timeout=1200,
        env={
            **__import__("os").environ,
            "PYTHONPATH": src,
            "JAX_PLATFORMS": "cpu",
        },
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["equal"], out
    assert out["n"] > 0, "parity on an empty result set proves nothing"


def test_kernel_switch_keys_cache_no_poisoning():
    """One session compares backends: switching kernels mid-session builds
    new executables under new keys; switching back reuses the old ones."""
    from repro.api import GraphSession
    from repro.graphstore import generators
    from repro.workloads import path_query

    g = generators.rmat(150, 500, 4, seed=9, symmetrize=True)
    rng = np.random.default_rng(0)
    q = None
    while q is None:
        q = path_query(g, rng, 3)

    s = GraphSession.open(g, backend="local", kernels="jnp")
    base = _row_set(s.run(q, max_matches=0))
    misses_after_jnp = s.cache.misses

    s.set_kernels("pallas-interpret")
    assert s.compile(q).kernels == "pallas-interpret"
    assert _row_set(s.run(q, max_matches=0)) == base
    assert s.cache.misses > misses_after_jnp  # new executables, new keys

    s.set_kernels("jnp")
    misses_before_back = s.cache.misses
    assert _row_set(s.run(q, max_matches=0)) == base
    assert s.cache.misses == misses_before_back  # fully reused, no poisoning
