"""Optimizer substrate: AdamW convergence, int8 moments, schedules, and the
error-feedback gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.optim.compression import EFState, compressed_psum, ef_init


def _optimize(quantize, steps=300):
    cfg = optim.AdamWConfig(lr=0.05, weight_decay=0.0, quantize_moments=quantize)
    target = jnp.asarray(np.random.default_rng(0).normal(size=(8, 16)), jnp.float32)
    params = {"w": jnp.zeros((8, 16), jnp.float32)}
    state = optim.init(cfg, params)

    @jax.jit
    def step(params, state):
        loss, g = jax.value_and_grad(
            lambda p: jnp.mean((p["w"] - target) ** 2)
        )(params)
        params, state, _ = optim.update(cfg, g, state, params)
        return params, state, loss

    for _ in range(steps):
        params, state, loss = step(params, state)
    return float(loss)


def test_adamw_converges():
    assert _optimize(False) < 1e-3


def test_adamw_int8_moments_converge():
    # quantized moments trade precision for 4× state bytes; must still optimize
    assert _optimize(True) < 1e-2


def test_cosine_warmup_shape():
    s = optim.cosine_warmup(jnp.arange(1000), warmup=100, total=1000, floor=0.1)
    assert float(s[0]) < 0.02
    assert float(jnp.max(s)) <= 1.0
    np.testing.assert_allclose(float(s[99]), 1.0, atol=0.05)
    np.testing.assert_allclose(float(s[-1]), 0.1, atol=0.01)


def test_grad_compression_error_feedback():
    """int8+EF compression: a constant gradient stream must accumulate to the
    true sum despite per-step quantization error (EF property)."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(256,)), jnp.float32) * 1e-3

    mesh = jax.make_mesh((1,), ("d",))
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    @jax.jit
    def run(g, err):
        def body(g, err):
            out, ef = compressed_psum(g, EFState(err), "d")
            return out, ef.error

        return shard_map(
            body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )(g, err)

    total = jnp.zeros_like(g)
    err = jnp.zeros_like(g)
    n = 50
    for _ in range(n):
        out, err = run(g, err)
        total = total + out
    # without EF, bias ~ n * quantization_step; with EF it stays ~ 1 step
    np.testing.assert_allclose(
        np.asarray(total), np.asarray(g) * n, atol=2 * float(jnp.max(jnp.abs(g))) / 127
    )


def test_quantized_moment_roundtrip_error():
    from repro.optim.adamw import _dequant, _quant

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    err = jnp.max(jnp.abs(_dequant(_quant(x)) - x))
    per_row_max = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    assert float(err) <= float(jnp.max(per_row_max)) / 127 + 1e-6
