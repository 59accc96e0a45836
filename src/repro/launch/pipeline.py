"""GPipe-style pipeline parallelism over a mesh axis (the multi-pod option).

At 2 pods the cross-pod axis can carry either data parallelism (default
rules) or a pipeline: each pod holds a contiguous stage of layers and
activations travel pod→pod with `ppermute` while microbatches fill the
pipeline (classic GPipe schedule, M + S − 1 ticks, bubble fraction
(S−1)/(M+S−1)).

This module is deliberately model-agnostic: ``stage_fn(stage_params, x)``
applies one stage. ``gpipe`` wraps it in shard_map over the pipe axis;
weights are pre-split with a leading stage axis sharded on that axis, so
each pod only ever holds its own stage (PP memory scaling).

Inference-friendly forward pipeline (training with PP composes with
jax.grad through the scan; the reverse pipeline reuses the same permute
pattern in the transposed direction automatically).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def gpipe(
    stage_fn,
    mesh: Mesh,
    *,
    axis: str = "pod",
    data_axes: tuple[str, ...] = (),
):
    """Build a pipelined apply: (stage_params, x_microbatched) → y.

    stage_params: pytree with leading dim = n_stages (sharded over ``axis``).
    x: (n_micro, mb, ...) microbatched input (replicated over ``axis``,
    optionally sharded over ``data_axes`` on the mb dim).
    Returns y (n_micro, mb, ...) replicated over ``axis``.
    """
    n_stages = mesh.shape[axis]

    def body(params, x):
        # params: leading dim 1 (my stage); x: full (M, mb, ...)
        my_params = jax.tree.map(lambda a: a[0], params)
        M = x.shape[0]
        S = n_stages
        stage = lax.axis_index(axis)
        T = M + S - 1
        fwd = [(i, i + 1) for i in range(S - 1)]

        y0 = jnp.zeros_like(stage_fn(my_params, x[0]))

        def tick(carry, t):
            buf, outs = carry
            mb = t - stage
            active = (mb >= 0) & (mb < M)
            xin = jnp.where(
                stage == 0, x[jnp.clip(mb, 0, M - 1)], buf
            )
            y = stage_fn(my_params, xin)
            y = jnp.where(active, y, jnp.zeros_like(y))
            slot = jnp.clip(mb, 0, M - 1)
            outs = outs.at[slot].set(
                jnp.where(active & (stage == S - 1), y, outs[slot])
            )
            buf_next = lax.ppermute(y, axis, fwd) if S > 1 else y
            return (buf_next, outs), None

        outs0 = jnp.zeros((M,) + y0.shape, y0.dtype)
        (_, outs), _ = lax.scan(tick, (y0, outs0), jnp.arange(T))
        # outs is zero everywhere except the last stage → psum broadcasts it
        return lax.psum(outs, axis) if S > 1 else outs

    # params sharded over the pipe axis; activations replicated over it
    in_specs = (P(axis), P())
    out_specs = P()
    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
    )


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead: (S−1)/(M+S−1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
