"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --scale tiny \
      --steps 200 --batch 8 --seq 256 --gradual

Local runs use a host mesh over the available devices; `--production`
lowers against the 16x16 production mesh instead (dry-run semantics).
HiNM gradual pruning is on by default past --nm-step; `--method noperm`
ablates the permutation.
"""
from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    from repro.configs.base import load_arch
    from repro.core.types import HiNMConfig
    from repro.data import SyntheticLMData
    from repro.launch.mesh import make_host_mesh
    from repro.models import zoo
    from repro.optim import cosine_schedule, make_optimizer
    from repro.train import gradual, loop, steps as tsteps
    from repro.train.abstract import abstract_masks

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--scale", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--gradual", action="store_true")
    ap.add_argument("--method", default="gyro",
                    choices=["gyro", "noperm", "v1", "v2", "icp_only", "ocp_only"])
    ap.add_argument("--nm-step", type=int, default=-1)
    ap.add_argument("--checkpoint-dir", default="checkpoints")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    cfg = load_arch(args.arch)
    if args.scale == "tiny":
        cfg = cfg.reduced(max_seq=args.seq)
    mesh = make_host_mesh()

    key = jax.random.PRNGKey(args.seed)
    params = zoo.init(key, cfg)
    opt = make_optimizer(cfg.optimizer)
    opt_state = opt.init(params)
    masks = jax.tree.map(lambda x: None, params)  # dense until the schedule fires

    data = SyntheticLMData(cfg.vocab, args.seq, args.batch, seed=args.seed)
    lr_fn = cosine_schedule(args.lr, warmup=20, total=args.steps)
    step_fn, _ = tsteps.make_train_step(cfg, mesh, optimizer_name=cfg.optimizer,
                                        lr_fn=lr_fn)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    def batch_iter():
        for b in data.iterator():
            yield {k: jnp.asarray(v) for k, v in b.items()}

    mask_schedule = None
    if args.gradual:
        nm_step = args.nm_step if args.nm_step > 0 else args.steps // 2
        sched = gradual.GradualSchedule(
            target=cfg.hinm,
            vector_end_step=nm_step * 2 // 3,
            nm_step=nm_step,
        )
        mask_schedule = gradual.make_mask_schedule(cfg, sched, method=args.method)

    state = loop.LoopState(params=params, opt_state=opt_state, masks=masks)
    lcfg = loop.LoopConfig(
        total_steps=args.steps,
        checkpoint_every=max(args.steps // 4, 25),
        checkpoint_dir=args.checkpoint_dir,
    )
    with jax.set_mesh(mesh):
        final = loop.run(state, jitted, batch_iter(), lcfg)
    print(f"done at step {final.step}")


if __name__ == "__main__":
    main()
