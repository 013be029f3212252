"""Quickstart: HiNM sparsity + gyro-permutation on a single weight matrix.

  PYTHONPATH=src python examples/quickstart.py

Walks the paper's pipeline end-to-end on one projection:
  1. build a structured weight + saliency,
  2. run gyro-permutation (OCP + tile-wise ICP) and compare retained
     saliency against no-permutation and the unstructured upper bound,
  3. pack to the HiNM format (vals / vec_idx / nm_idx),
  4. verify the packed matmul (XLA fast path AND the Pallas TPU kernel in
     interpret mode) against the masked-dense oracle,
  5. show the compression ratio the serving path enjoys.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp
import numpy as np

from repro.core import HiNMConfig, packing
from repro.core.baselines import unstructured_retained
from repro.core.gyro import gyro_permute
from repro.kernels import ops, ref


def main():
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    rng = np.random.default_rng(0)
    n_out, n_in = 256, 512
    row = np.exp(rng.normal(scale=0.6, size=(n_out, 1)))
    col = np.exp(rng.normal(scale=0.6, size=(1, n_in)))
    w = (rng.normal(size=(n_out, n_in)) * row * col).astype(np.float32)
    sal = np.abs(w)

    cfg = HiNMConfig(v=32, n=2, m=4, vector_sparsity=0.5)
    print(f"HiNM config: V={cfg.v}, {cfg.n}:{cfg.m}, vector sparsity "
          f"{cfg.vector_sparsity:.0%} -> total {cfg.total_sparsity:.0%}")

    noperm = gyro_permute(sal, cfg, run_ocp=False, run_icp=False)
    gyro = gyro_permute(sal, cfg, ocp_iters=12, icp_iters=10,
                        rng=np.random.default_rng(1))
    upper = unstructured_retained(sal, cfg.total_sparsity)
    print(f"retained saliency:  no-perm {noperm.retained_fraction:.4f}  "
          f"gyro {gyro.retained_fraction:.4f}  unstructured-bound {upper:.4f}")

    # pack with the gyro layout (rows permuted, vec_idx = ICP order)
    w_p = jnp.asarray(w[gyro.out_perm])
    packed = packing.pack(w_p, cfg, col_ids=jnp.asarray(gyro.col_order),
                          sal=jnp.asarray(sal[gyro.out_perm]))
    print(f"packed bytes ratio: {packed.packed_bytes() / packed.dense_bytes():.3f} "
          f"(weight HBM traffic at serve time)")

    x = jnp.asarray(rng.normal(size=(8, n_in)).astype(np.float32))
    y_oracle = ref.hinm_spmm_oracle(x, packed)
    y_xla = ops.hinm_matmul(x, packed, backend="xla")
    y_pallas = ops.hinm_matmul(x, packed, backend="interpret")
    print(f"XLA fast path  max err: {float(jnp.abs(y_xla - y_oracle).max()):.2e}")
    print(f"Pallas kernel  max err: {float(jnp.abs(y_pallas - y_oracle).max()):.2e}")
    print("ok")


if __name__ == "__main__":
    main()
