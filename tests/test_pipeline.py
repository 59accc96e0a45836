"""GPipe pipeline over the pod axis == sequential layer application.
Runs in a subprocess with 2 host devices (2 pipeline stages)."""
import json
import pathlib
import subprocess
import sys

import pytest

# whole-module: subprocess 2-device pipeline runs
pytestmark = pytest.mark.slow

from repro.launch.pipeline import bubble_fraction

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=2 --xla_cpu_use_fusion_emitters=false"
)
import json
import numpy as np, jax, jax.numpy as jnp
from repro.launch.pipeline import gpipe

mesh = jax.make_mesh((2,), ("pod",))
rng = np.random.default_rng(0)
S, M, mb, d = 2, 4, 8, 16
ws = jnp.asarray(rng.normal(size=(S, d, d)), jnp.float32) * 0.3
x = jnp.asarray(rng.normal(size=(M, mb, d)), jnp.float32)

def stage_fn(w, xb):
    return jnp.tanh(xb @ w)

pipe = gpipe(stage_fn, mesh, axis="pod")
y = pipe(ws, x)

# sequential reference
ref = x
for s in range(S):
    ref = jnp.tanh(ref @ ws[s])
err = float(jnp.max(jnp.abs(y - ref)))
print(json.dumps({"err": err}))
"""


@pytest.mark.parametrize("_", [0])
def test_gpipe_matches_sequential(_):
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"},
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["err"] < 1e-5, out


def test_bubble_fraction():
    assert bubble_fraction(2, 4) == pytest.approx(1 / 5)
    assert bubble_fraction(1, 8) == 0.0
    # more microbatches amortize the bubble
    assert bubble_fraction(4, 32) < bubble_fraction(4, 4)
