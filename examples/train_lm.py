"""Train a small LM with the full production loop: AdamW + cosine schedule,
microbatched gradient accumulation, async checkpoints, restart-on-failure.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import shutil

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.checkpoint import Checkpointer
from repro.configs.base import LMConfig
from repro.data import lm_batch
from repro.models import transformer as tf
from repro.runtime import SimulatedPreemption, TrainSupervisor, compile_cache
from repro.train import make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_example_ckpt")
    ap.add_argument("--inject-failure", action="store_true")
    args = ap.parse_args()
    shutil.rmtree(args.ckpt, ignore_errors=True)

    cfg = LMConfig(
        name="lm-demo", n_layers=4, d_model=256, n_heads=8, n_kv_heads=4,
        d_head=32, d_ff=1024, vocab_size=4096, dtype="float32",
    )
    params = tf.init(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n_params/1e6:.1f}M params")

    opt_cfg = optim.AdamWConfig(lr=1e-3)
    opt_state = optim.init(opt_cfg, params)
    raw = jax.jit(make_train_step(cfg, opt_cfg, total_steps=args.steps, warmup=20))

    def step_fn(state, batch, step):
        p, s = state
        p, s, m = raw(p, s, {"tokens": jnp.asarray(batch["tokens"])}, np.int32(step))
        return (p, s), m

    def batch_fn(step):
        return lm_batch(cfg, args.batch, args.seq, seed=0, step=step)

    sup = TrainSupervisor(
        Checkpointer(args.ckpt), ckpt_every=50,
        fail_at={args.steps // 2: lambda: SimulatedPreemption("injected")}
        if args.inject_failure
        else {},
    )
    try:
        state, hist = sup.run(
            state=(params, opt_state), step_fn=step_fn, batch_fn=batch_fn,
            n_steps=args.steps,
        )
    except SimulatedPreemption:
        print("!! preempted — restarting from latest checkpoint")
        state, hist = sup.run(
            state=(params, opt_state), step_fn=step_fn, batch_fn=batch_fn,
            n_steps=args.steps,
        )
    for h in hist[:: max(1, len(hist) // 8)]:
        print(f"step {h['step']:4d}  loss {h['loss']:.4f}  {h['dt']*1e3:5.0f} ms")
    print(f"final loss {hist[-1]['loss']:.4f} (started ~{np.log(4096):.2f} = ln V)")
    assert hist[-1]["loss"] < np.log(4096), "no learning happened?"


if __name__ == "__main__":
    compile_cache.enable()
    main()
