"""End-to-end driver: train an LM with gradual HiNM pruning + recovery.

  PYTHONPATH=src python examples/train_hinm_lm.py                  # tiny, fast
  PYTHONPATH=src python examples/train_hinm_lm.py --scale 100m --steps 300

The run: dense warmup -> cubic vector-sparsity ramp -> N:M stage switches
on at --nm-step -> masked-dense recovery, with fault-tolerant loop
(checkpoint/resume) and the gyro permutation refresh at the N:M switch.
Compare `--method noperm` to see the permutation's effect on recovery.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from repro.compile_cache import enable_compile_cache
    from repro.configs.base import load_arch
    from repro.data.pipeline import SyntheticLMData
    from repro.launch.mesh import make_host_mesh
    from repro.models import zoo
    from repro.optim import cosine_schedule, make_optimizer
    from repro.train import gradual, loop, steps as tsteps

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="tiny", choices=["tiny", "100m"])
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--method", default="gyro", choices=["gyro", "noperm", "v1", "v2"])
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default="/tmp/hinm_lm_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    base = load_arch("qwen2_0_5b")
    if args.scale == "tiny":
        cfg = base.reduced(max_seq=args.seq)
    else:  # ~100M-parameter config
        cfg = base.reduced(n_layers=8, d_model=512, n_heads=8, n_kv_heads=4,
                           d_ff=2048, vocab=32000, head_dim=64,
                           max_seq=args.seq)
    mesh = make_host_mesh()

    params = zoo.init(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"model: {n_params/1e6:.1f}M params, HiNM target "
          f"{cfg.hinm.total_sparsity:.0%} sparsity, method={args.method}")

    opt = make_optimizer(cfg.optimizer)
    data = SyntheticLMData(cfg.vocab, args.seq, args.batch, seed=0)
    step_fn, _ = tsteps.make_train_step(
        cfg, mesh, optimizer_name=cfg.optimizer,
        lr_fn=cosine_schedule(3e-3, 10, args.steps))
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    sched = gradual.GradualSchedule(
        target=cfg.hinm,
        vector_end_step=args.steps // 3,
        nm_step=args.steps // 2,
        update_every=10,
    )
    mask_cb = gradual.make_mask_schedule(cfg, sched, method=args.method)

    losses = []

    def batches():
        for b in data.iterator():
            yield {k: jnp.asarray(v) for k, v in b.items()}

    state = loop.LoopState(params=params, opt_state=opt.init(params),
                           masks=jax.tree.map(lambda x: None, params))
    lcfg = loop.LoopConfig(total_steps=args.steps,
                           checkpoint_every=max(args.steps // 3, 20),
                           checkpoint_dir=args.ckpt, log_every=10)
    import logging

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    with jax.set_mesh(mesh):
        state = loop.run(state, jitted, batches(), lcfg,
                         on_step=lambda s, m: losses.append(m.get("loss")),
                         mask_schedule=mask_cb)

    dense_best = min(losses[: args.steps // 3])
    final = float(np.mean(losses[-5:]))
    print(f"\nbest dense-phase loss : {dense_best:.4f}")
    print(f"final loss at {cfg.hinm.total_sparsity:.0%} HiNM sparsity: {final:.4f}")
    print(f"recovery gap          : {final - dense_best:+.4f}")


if __name__ == "__main__":
    main()
