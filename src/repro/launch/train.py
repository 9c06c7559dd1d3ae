"""Training launcher.

CPU/host-mesh scale (this container) and production-mesh dry-run share the
same code path; the only difference is the mesh and the config size.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_moe_235b_a22b \
      --smoke --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import pipeline_for
from repro.launch.mesh import make_host_mesh
from repro.launch import sharding as SH
from repro.launch.steps import TrainState, build_train_step
from repro.models.api import build_api
from repro.optim.adamw import AdamW
from repro.runtime.fault_tolerance import ResilientTrainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_moe_235b_a22b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    api = build_api(cfg)
    mesh = make_host_mesh()
    print(f"arch={cfg.name} mesh={dict(mesh.shape)} devices={len(jax.devices())}")

    opt = AdamW(lr=args.lr)
    key = jax.random.PRNGKey(args.seed)
    params = api.init(key)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.2f}M")
    state = TrainState(params, opt.init(params))
    step_fn = build_train_step(api, opt)

    pspecs = SH.param_specs(params, cfg, mesh)
    sspecs = TrainState(pspecs, type(state.opt)(
        jax.sharding.PartitionSpec(), pspecs, pspecs))

    pipe = pipeline_for(cfg, args.seq, args.batch, args.seed)

    class _Pipe:  # adapt numpy batches to the model's expected input
        def batch(self, step):
            b = pipe.batch(step)
            if cfg.family == "encdec":
                kb = api.make_batch(jax.random.PRNGKey(step), args.seq,
                                    args.batch, "train")
                return kb
            if cfg.frontend == "audio":
                return api.make_batch(jax.random.PRNGKey(step), args.seq,
                                      args.batch, "train")
            return b

    with jax.set_mesh(mesh):
        jitted = jax.jit(step_fn, in_shardings=(sspecs, None))

        def on_step(step, metrics):
            if step % 5 == 0 or step == 1:
                loss = float(metrics["loss"])
                print(f"step {step:5d} loss {loss:.4f} "
                      f"({time.strftime('%H:%M:%S')})", flush=True)

        if args.ckpt_dir:
            trainer = ResilientTrainer(
                jitted, _Pipe(), CheckpointManager(args.ckpt_dir),
                ckpt_every=args.ckpt_every)
            state, step, metrics = trainer.run(
                state, args.steps, inject_failure_at=args.inject_failure_at,
                on_step=on_step)
        else:
            for step in range(args.steps):
                state, metrics = jitted(state, _Pipe().batch(step))
                on_step(step + 1, metrics)
    print("final loss:", float(metrics["loss"]))


if __name__ == "__main__":
    main()
