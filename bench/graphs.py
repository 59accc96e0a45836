"""Graph deployments made from a configuration and a seed.

`make_graph` is a copy of `repro.graphstore.generators.rmat` and
`repro.graphstore.csr.Graph.from_edges`, kept here so that no later change
to the program moves the benchmark's data. It draws the same random numbers
in the same order and gives the same graph; it drops two steps whose result
is already in hand (a stable sort of edges that `np.unique` left sorted, and
an `np.add.at` that `np.bincount` does faster).

`ensure_cached` keeps each generated graph in ``bench/.graph_cache`` keyed
by configuration, parameters and seed, and `load_graph` reads it from
there, so that a deployment's set-up is loading its graph, not generating
it.
"""
from __future__ import annotations

import hashlib
import json
import os
import pathlib
import time

import numpy as np

from repro.graphstore.csr import Graph

CACHE_DIR = pathlib.Path(__file__).resolve().parent / ".graph_cache"
GRAPH_KEYS = ("n_nodes", "n_edges", "n_labels", "rmat_a", "rmat_b", "rmat_c")


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy generator per (seed, stream); any integer seed."""
    return np.random.default_rng([seed % 2**64, stream])


def make_graph(cfg: dict, rng: np.random.Generator) -> Graph:
    """The R-MAT graph [Chakrabarti et al., SDM'04] of ``cfg``, symmetrised,
    without self-loops or duplicate edges, labels uniform."""
    n, m, n_labels = cfg["n_nodes"], cfg["n_edges"], cfg["n_labels"]
    a, b, c = cfg["rmat_a"], cfg["rmat_b"], cfg["rmat_c"]
    thresholds = np.cumsum([a, b, c, 1.0 - a - b - c])
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for _ in range(int(np.ceil(np.log2(max(n, 2))))):
        quad = np.searchsorted(thresholds, rng.random(m))
        src = (src << 1) | (quad >> 1)
        dst = (dst << 1) | (quad & 1)
    src %= n
    dst %= n
    labels = rng.integers(0, n_labels, size=n, dtype=np.int32)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=indptr[1:])
    return Graph(n_nodes=n, n_labels=n_labels, labels=labels, indptr=indptr,
                 indices=(key % n).astype(np.int32))


def cache_path(cfg: dict, seed: int) -> pathlib.Path:
    params = json.dumps({k: cfg.get(k) for k in GRAPH_KEYS}, sort_keys=True)
    digest = hashlib.sha256(params.encode()).hexdigest()[:12]
    return CACHE_DIR / f"{cfg['name']}-{digest}-{seed}.npz"


def ensure_cached(cfg: dict, seed: int) -> float:
    """Generate and store the graph of ``cfg`` and ``seed`` unless the
    cache holds it; returns the seconds that took (0.0 on a hit)."""
    path = cache_path(cfg, seed)
    if path.exists():
        return 0.0
    t0 = time.perf_counter()
    g = make_graph(cfg, rng_for(seed, 0))
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f"{path.stem}.{os.getpid()}.tmp.npz")
    np.savez(tmp, labels=g.labels, indptr=g.indptr, indices=g.indices)
    os.replace(tmp, path)
    return time.perf_counter() - t0


def load_graph(cfg: dict, seed: int) -> Graph:
    """The graph of ``cfg`` and ``seed``, read from the cache (generated
    into it first on a miss)."""
    ensure_cached(cfg, seed)
    with np.load(cache_path(cfg, seed)) as z:
        return Graph(n_nodes=cfg["n_nodes"], n_labels=cfg["n_labels"],
                     labels=z["labels"], indptr=z["indptr"], indices=z["indices"])
