"""Property tests for the collectives at every axis size 1..8.

CI's multi-device jobs only ever run the collectives at S=8 (a power of
two), so the non-power-of-two all-gather fallback in `or_allreduce` and the
ring clamp `h = min(max_dist, (S-1)//2)` in `gather_load_set_ring` were
untested. One 8-forced-device subprocess builds a sub-mesh of every size
S ∈ 1..8 and checks, per size:

  * ``or_allreduce`` equals the host-side OR reduction (butterfly path for
    powers of two, gather fallback otherwise, identity at S=1);
  * ``gather_load_set_ring`` returns exactly the same valid rows as the
    faithful ``gather_load_set`` whenever the load set respects the ring
    radius — including max_dist larger than the reachable radius (the
    clamp) and max_dist=0 (self only).

Multi-device, so subprocess-isolated (the main session keeps one device).
"""
import json
import pathlib
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 --xla_cpu_use_fusion_emitters=false"
)
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core.collectives import (
    gather_load_set, gather_load_set_ring, or_allreduce,
)

out = {"or": {}, "ring": {}}
W, CAP, COLS = 16, 6, 3

for S in range(1, 9):
    mesh = Mesh(np.array(jax.devices()[:S]), ("data",))
    rng = np.random.default_rng(100 + S)

    # ---- or_allreduce == host OR-reduce --------------------------------
    words = rng.integers(0, 2**32, (S, W), dtype=np.uint32)
    f = jax.jit(shard_map(
        lambda w: or_allreduce(w[0], "data")[None],
        mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False,
    ))
    got = np.asarray(f(words))
    want = np.bitwise_or.reduce(words, axis=0)
    out["or"][S] = bool((got == want[None]).all())

    # ---- ring fetch == all-gather fetch on ring-shaped load sets -------
    cols = rng.integers(0, 1000, (S, CAP, COLS), dtype=np.int32)
    valid = rng.random((S, CAP)) < 0.7
    for max_dist in (0, 1, 2, 5):
        h = min(max_dist, (S - 1) // 2)
        # load sets constrained to the reachable ring distance, random
        # within it (shard i may fetch shard j iff ring_dist(i,j) <= h)
        dist = np.minimum(
            (np.arange(S)[:, None] - np.arange(S)) % S,
            (np.arange(S) - np.arange(S)[:, None]) % S,
        )
        load = (rng.random((S, S)) < 0.8) & (dist <= h)
        np.fill_diagonal(load, True)

        def ring_body(c, v, l):
            gc, gv = gather_load_set_ring(c[0], v[0], l[0], "data", max_dist)
            return gc[None], gv[None]

        def full_body(c, v, l):
            gc, gv = gather_load_set(c[0], v[0], l[0], "data")
            return gc[None], gv[None]

        specs = (P("data"), P("data"), P("data"))
        ring = jax.jit(shard_map(
            ring_body, mesh=mesh, in_specs=specs,
            out_specs=(P("data"), P("data")), check_vma=False,
        ))
        full = jax.jit(shard_map(
            full_body, mesh=mesh, in_specs=specs,
            out_specs=(P("data"), P("data")), check_vma=False,
        ))
        rc, rv = map(np.asarray, ring(cols, valid, load))
        fc, fv = map(np.asarray, full(cols, valid, load))
        ok = True
        for i in range(S):
            ring_rows = sorted(map(tuple, rc[i][rv[i]].tolist()))
            full_rows = sorted(map(tuple, fc[i][fv[i]].tolist()))
            ok &= ring_rows == full_rows
        # capacity contract: (2h+1) * CAP rows after the clamp
        ok &= rc.shape == (S, (2 * h + 1) * CAP, COLS)
        out["ring"][f"{S}:{max_dist}"] = bool(ok)

# ---- cost-model collective bytes == roofline HLO parse ---------------
# (needs a real multi-device mesh: XLA deletes collectives at S=1)
from repro.analysis.staticcheck import costmodel

mesh8 = Mesh(np.array(jax.devices()), ("data",))

def coll_body(v):
    s = jax.lax.psum(v[0], "data")                    # all-reduce
    g = jax.lax.all_gather(v[0], "data", tiled=True)  # all-gather
    p = jax.lax.ppermute(                             # collective-permute
        v[0], "data", perm=[(i, (i + 1) % 8) for i in range(8)]
    )
    return (s + p)[None], g[None]

x = np.arange(8 * 256, dtype=np.float32).reshape(8, 256)
f = shard_map(coll_body, mesh=mesh8, in_specs=(P("data"),),
              out_specs=(P("data"), P("data")), check_vma=False)
xc = costmodel.hlo_cross_check(f, x, n_devices=8)
rel = abs(xc["est_collective_bytes"] - xc["hlo_collective_bytes"]) / max(
    xc["hlo_collective_bytes"], 1.0
)
out["collective_bytes"] = {
    "est": xc["est_collective_bytes"],
    "hlo": xc["hlo_collective_bytes"],
    "rel_err": rel,
}

print(json.dumps(out))
"""


def test_collectives_all_axis_sizes():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env={
            "PYTHONPATH": SRC,
            "PATH": "/usr/bin:/bin",
            "JAX_PLATFORMS": "cpu",
        },
    )
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    bad_or = [s for s, ok in out["or"].items() if not ok]
    bad_ring = [k for k, ok in out["ring"].items() if not ok]
    assert not bad_or, f"or_allreduce mismatch at axis sizes {bad_or}"
    assert not bad_ring, f"ring fetch mismatch at (S:max_dist) {bad_ring}"
    # acceptance: static collective-bytes estimate vs roofline HLO parse
    cb = out["collective_bytes"]
    assert cb["hlo"] > 0, cb
    assert cb["rel_err"] <= 0.10, cb
