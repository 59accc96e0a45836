"""Elastic rescale across real device-count change: a checkpoint written on
an 8-device mesh restores onto a 4-device mesh and training continues to the
same result as an uninterrupted run (subprocess: needs multiple devices)."""
import json
import pathlib
import subprocess
import sys

import pytest

# whole-module: multi-device subprocess end-to-end runs
pytestmark = pytest.mark.slow

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

SCRIPT = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 --xla_cpu_use_fusion_emitters=false"
)
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import optim
from repro.checkpoint import Checkpointer
from repro.runtime import elastic_restore

tmp = tempfile.mkdtemp()
target = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)), jnp.float32)
cfg = optim.AdamWConfig(lr=0.05, weight_decay=0.0)

def make_step(mesh):
    sh = NamedSharding(mesh, P("d"))
    @jax.jit
    def step(params, state):
        loss, g = jax.value_and_grad(lambda p: jnp.mean((p["w"] - target) ** 2))(params)
        params, state, _ = optim.update(cfg, g, state, params)
        return jax.lax.with_sharding_constraint(params, {"w": sh}), state, loss
    return step

# phase 1: 8-device mesh, 10 steps, checkpoint
# Auto axes: JAX 0.9's make_mesh defaults to Explicit ones, which
# with_sharding_constraint refuses
AUTO = (jax.sharding.AxisType.Auto,)
mesh8 = jax.make_mesh((8,), ("d",), axis_types=AUTO)
params = {"w": jax.device_put(jnp.zeros((8, 64), jnp.float32), NamedSharding(mesh8, P("d")))}
state = optim.init(cfg, params)
step8 = make_step(mesh8)
for _ in range(10):
    params, state, loss = step8(params, state)
ck = Checkpointer(tmp, async_save=False)
ck.save(10, (params, state))

# phase 2: "lost half the pod" — restore onto a 4-device mesh, train 10 more
mesh4 = jax.make_mesh(
    (4,), ("d",), devices=np.array(jax.devices()[:4]), axis_types=AUTO
)
sh4 = jax.tree.map(lambda _: NamedSharding(mesh4, P()), (params, state))
sh4[0]["w"] = NamedSharding(mesh4, P("d"))
(restored, step_no) = elastic_restore(ck, (params, state), sh4)
params4, state4 = restored
step4 = make_step(mesh4)
for _ in range(10):
    params4, state4, loss4 = step4(params4, state4)

# reference: uninterrupted 20 steps on 8 devices
params_r = {"w": jax.device_put(jnp.zeros((8, 64), jnp.float32), NamedSharding(mesh8, P("d")))}
state_r = optim.init(cfg, params_r)
for _ in range(20):
    params_r, state_r, loss_r = step8(params_r, state_r)

err = float(np.max(np.abs(np.asarray(params4["w"]) - np.asarray(params_r["w"]))))
print(json.dumps({"step": int(step_no), "err": err,
                  "devices_phase2": len(np.asarray(params4["w"]).shape) and 4}))
"""


def test_elastic_rescale_8_to_4():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"},
        timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["step"] == 10
    assert out["err"] < 1e-5, out
