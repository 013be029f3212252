"""Minimal functional module system.

Params are nested dicts of jnp arrays (plus PackedHiNM nodes on the serve
path). Every model exposes:

  init(key, cfg)                 -> params
  forward(params, cfg, batch)    -> logits          (training/prefill)
  decode_step(params, cfg, cache, tokens) -> (logits, cache)
  hinm_plan(cfg)                 -> list[PruneSpec] (which projections HiNM
                                     prunes, row-permutation freedom, and
                                     producer->consumer coupling)

Linear weights are stored (n_in, n_out) — `x @ w`. The HiNM format is
defined on (n_out, n_in), so packing operates on w.T; `linear()` dispatches
transparently between dense and packed nodes.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.types import PackedHiNM
from repro.kernels import ops as kops

Params = Any  # nested dict pytree


@dataclasses.dataclass(frozen=True)
class PruneSpec:
    """One prunable projection and its permutation coupling.

    path         : '/'-joined path to the linear's param dict (under a layer)
    row_blocks   : OCP is restricted to permutations within contiguous row
                   blocks of n_out/row_blocks (1 = free, n_out//V blocks =
                   effectively no OCP). Used for head-structured outputs.
    can_permute_rows : False for residual-constrained outputs (identity OCP).
    consumers    : paths whose weight *columns* (their n_in) are indexed by
                   this projection's output channels; their columns get
                   permuted by this layer's out_perm before their own
                   packing (free at runtime via vec_idx).
    """

    path: str
    row_blocks: int = 1
    can_permute_rows: bool = True
    consumers: tuple[str, ...] = ()
    # projections whose rows are elementwise-coupled with this one (e.g.
    # SwiGLU gate/up): they share this spec's OCP perm (joint saliency).
    tied: tuple[str, ...] = ()


def uniform_init(key, n_in, n_out, dtype):
    scale = (6.0 / (n_in + n_out)) ** 0.5
    return jax.random.uniform(key, (n_in, n_out), dtype, -scale, scale)


def dense_init(key, n_in: int, n_out: int, dtype=jnp.float32, bias: bool = False):
    p = {"w": uniform_init(key, n_in, n_out, dtype)}
    if bias:
        p["b"] = jnp.zeros((n_out,), dtype)
    return p


def linear(p, x: jax.Array) -> jax.Array:
    """Dense or HiNM-packed projection; packed rows are already consistent
    with consumers (permutations folded offline), so no runtime reorder."""
    if isinstance(p, dict) and isinstance(p.get("w"), PackedHiNM):
        y = kops.hinm_matmul(x, p["w"])
    else:
        w = p["w"]
        y = jnp.einsum("...i,io->...o", x, w.astype(x.dtype))
    if isinstance(p, dict) and "b" in p and p["b"] is not None:
        y = y + p["b"].astype(y.dtype)
    return y


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return {"table": jax.random.normal(key, (vocab, d), dtype) * 0.02}


def embed(p, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def get_path(tree: Params, path: str):
    node = tree
    for part in path.split("/"):
        node = node[part]
    return node


def set_path(tree: Params, path: str, value) -> Params:
    """Functional set — returns a new tree sharing unmodified nodes."""
    parts = path.split("/")

    def rec(node, i):
        if i == len(parts):
            return value
        new = dict(node)
        new[parts[i]] = rec(node[parts[i]], i + 1)
        return new

    return rec(tree, 0)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


def constrain(x: jax.Array, roles: tuple) -> jax.Array:
    """Sharding constraint by role per dim ('dp' | 'tp' | None).

    Resolves roles against the active abstract mesh with divisibility
    checks; silently no-ops without a mesh context (CPU smoke tests) and
    degrades any non-divisible dim to replicated.
    """
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return x
    dp = tuple(a for a in ("pod", "data") if a in am.axis_names)
    spec = []
    for role, dim in zip(roles, x.shape):
        ax = None
        if role == "dp" and dp:
            n = 1
            for a in dp:
                n *= am.shape[a]
            ax = dp if dim % n == 0 else None
        elif role == "tp" and "model" in am.axis_names:
            ax = "model" if dim % am.shape["model"] == 0 else None
        spec.append(ax)
    return jax.lax.with_sharding_constraint(x, jax.sharding.PartitionSpec(*spec))


def constrain_batch(x: jax.Array) -> jax.Array:
    """Pin the leading (batch) dim to the data-parallel mesh axes.

    XLA SPMD propagation can drop the batch sharding around FSDP-sharded
    contractions (replicating activations over 'data'); this constraint at
    block boundaries keeps activations batch-sharded.
    """
    return constrain(x, ("dp",) + (None,) * (x.ndim - 1))
