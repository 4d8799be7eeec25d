"""Training driver: ``python -m repro.launch.train --arch <id> ...``

Composes: config -> model -> sharded train step (pjit over the production
or a custom mesh) -> deterministic data pipeline -> fault-tolerant loop
with async checkpointing.  ``--chiplight`` runs the cross-layer DSE first
and prints the strategy it would deploy (TP/EP mapped to the model axis,
DP/CP to data — see parallel/plan.py).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.data import DataPipeline
from repro.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh_from_plan
from repro.launch.steps import TrainState, init_train_state, \
    make_train_step
from repro.models.common import ExecConfig
from repro.optim import AdamWState
from repro.parallel.sharding import param_specs
from repro.runtime import FaultTolerantLoop
from repro.runtime.compile_cache import use_compile_cache


def build_sharded_train(cfg, ex, mesh, accum=1, base_lr=3e-4):
    step_fn = make_train_step(cfg, ex, base_lr=base_lr, accum=accum)
    params_shape = jax.eval_shape(
        lambda: init_train_state(cfg, ex).params)
    p_specs = param_specs(cfg, params_shape, mesh)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                        is_leaf=lambda x: isinstance(x, P))
    state_sh = TrainState(
        params=p_sh,
        opt=AdamWState(step=NamedSharding(mesh, P()),
                       m=p_sh, v=p_sh))
    # the state comes back on ``state_sh`` too, so the donated state of
    # step n is a valid input of step n + 1
    jitted = jax.jit(step_fn, in_shardings=(state_sh, None),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
    return jitted, state_sh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-runnable)")
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    ex = ExecConfig(ssd_chunk=min(64, args.seq), attn_block=128)

    use_compile_cache()
    mesh = make_mesh_from_plan(tp=1, dp=len(jax.devices()))

    with jax.set_mesh(mesh):
        step_fn, state_sh = build_sharded_train(cfg, ex, mesh,
                                                accum=args.accum,
                                                base_lr=args.lr)
        state = jax.device_put(init_train_state(cfg, ex, seed=args.seed),
                               state_sh)
        pipeline = DataPipeline(cfg, shape, seed=args.seed, ex=ex)
        ckpt = CheckpointManager(args.ckpt_dir)
        loop = FaultTolerantLoop(step_fn, ckpt, pipeline,
                                 checkpoint_every=args.ckpt_every)
        start = 0
        if args.resume:
            state, start = loop.resume_or_init(state)
            print(f"resumed from step {start}")

        def on_metrics(step, metrics, dt):
            if step % 10 == 0 or step <= 3:
                print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"{dt * 1e3:.0f}ms")

        state, last = loop.run(state, args.steps, start_step=start,
                               on_metrics=on_metrics)
        print(f"done at step {last}; stragglers={loop.straggler_steps}")
    return state


if __name__ == "__main__":
    main()
