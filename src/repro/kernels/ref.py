"""Pure-jnp oracles for every Pallas kernel in this package.

Also hosts the XLA "fast path" formulations used on CPU and inside the
dry-run serve_step (their HLO carries the same gather + decompress +
matmul structure the TPU kernel realises, so roofline terms derived from
them are representative).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.types import HiNMConfig, PackedHiNM


def decompress_tiles(
    vals: jax.Array, nm_idx: jax.Array, m: int, n: int
) -> jax.Array:
    """(T, V, Kn) packed values + slots -> (T, V, K) dense kept-column tiles."""
    t, v, kn = vals.shape
    g = kn // n
    v4 = vals.reshape(t, v, g, n)
    s4 = nm_idx.reshape(t, v, g, n).astype(jnp.int32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (t, v, g, n, m), 4)
    dense = (v4[..., None] * (iota == s4[..., None]).astype(vals.dtype)).sum(axis=3)
    return dense.reshape(t, v, g * m)


def hinm_spmm_oracle(x: jax.Array, p: PackedHiNM) -> jax.Array:
    """Ground truth: unpack to masked-dense and matmul. x: (B, n_in)."""
    w = packing.unpack(p)  # (n_out, n_in), rows in packed (OCP) order
    return (x.astype(jnp.float32) @ w.astype(jnp.float32).T).astype(x.dtype)


GATHER_PATH_MAX_ROWS = 1024
TILE_CHUNK_BYTES = 256 * 1024 * 1024


def _gather_matmul(x, vec_idx, vals, nm_idx, mm, nn, out_dtype):
    """(B, n_in) x packed tiles -> (B, T, V): gather + compressed contraction.
    Operands stay in storage dtype; the MXU accumulates in f32."""
    xg = jnp.take(x, vec_idx, axis=1)                      # (B, T', K)
    w = decompress_tiles(vals, nm_idx, mm, nn)             # (T', V, K)
    return jnp.einsum(
        "btk,tvk->btv", xg, w.astype(xg.dtype),
        preferred_element_type=jnp.float32,
    ).astype(out_dtype)


def hinm_spmm_xla(x: jax.Array, p: PackedHiNM, chunk_bytes: int | None = None) -> jax.Array:
    """XLA fast path: per-tile gather + in-register decompress + matmul.

    Mirrors the TPU kernel's dataflow: the `vec_idx` gather plays the
    global->shared indexed load role (free runtime reorder), decompression
    expands the N:M values against their slot indices, and the contraction
    runs over the K kept columns only (the vector-sparsity FLOP saving).

    For large row counts (prefill / train eval) the whole-matrix gather
    would materialise a (B, T, K) activation copy, so tiles are processed
    in chunks with lax.map — bounded memory, same compressed FLOPs. The
    Pallas TPU kernel streams the same dataflow through VMEM.
    """
    cfg = p.config
    b = x.shape[0]
    t, v, kn = p.vals.shape
    k = p.vec_idx.shape[-1]
    if b <= GATHER_PATH_MAX_ROWS:
        y = _gather_matmul(x, p.vec_idx, p.vals, p.nm_idx, cfg.m, cfg.n, x.dtype)
        return y.reshape(b, p.n_out)

    # chunk tiles so the transient (B, tc, K) stays under budget; shapes
    # here are GLOBAL (SPMD), so scale the budget by the device count
    from repro.models import probe_mode

    budget = (chunk_bytes or TILE_CHUNK_BYTES) * max(1, jax.device_count())
    tc = max(1, budget // max(1, b * k * x.dtype.itemsize))
    tc = min(t, tc)
    if probe_mode.enabled():
        tc = t  # single chunk: all FLOPs visible to cost_analysis
    while t % tc:
        tc -= 1
    nchunk = t // tc

    def one(args):
        vi, va, nm = args
        return _gather_matmul(x, vi, va, nm, cfg.m, cfg.n, x.dtype)

    ys = jax.lax.map(one, (
        p.vec_idx.reshape(nchunk, tc, k),
        p.vals.reshape(nchunk, tc, v, kn),
        p.nm_idx.reshape(nchunk, tc, v, kn),
    ))                                                     # (nchunk, B, tc, V)
    return jnp.moveaxis(ys, 0, 1).reshape(b, p.n_out)


def hinm_spmm_shard_map(x: jax.Array, p: PackedHiNM) -> jax.Array | None:
    """Beyond-paper §Perf: explicit shard_map realisation of the packed
    matmul. Tiles are independent (DESIGN.md §2), so with vec_idx/vals
    T-sharded over 'model' and activations batch-sharded over dp, every
    shard's gather+contraction is fully local — ZERO collectives, where
    XLA SPMD's gather partitioner instead all-gathers the full activations
    (the dominant collective in every baseline prefill cell).

    Returns None when preconditions don't hold (no mesh context, tile or
    batch dims don't divide) — caller falls back to the XLA path.
    """
    am = jax.sharding.get_abstract_mesh()
    if am.empty or "model" in getattr(am, "manual_axes", ()):
        return None
    if "model" not in am.axis_names:
        return None
    t, v, kn = p.vals.shape[-3:]
    if p.vals.ndim != 3:  # expert stacks keep the vmapped path
        return None
    nmodel = am.shape["model"]
    if t % nmodel:
        return None
    b = x.shape[0]
    dp = tuple(a for a in ("pod", "data") if a in am.axis_names)
    ndp = 1
    for a in dp:
        ndp *= am.shape[a]
    row_spec = dp if (dp and b % ndp == 0) else None
    P = jax.sharding.PartitionSpec
    cfg = p.config

    def body(xl, vl, nl, il):
        return _gather_matmul(xl, il, vl, nl, cfg.m, cfg.n, x.dtype)

    y = jax.shard_map(
        body,
        mesh=am,
        in_specs=(P(row_spec, None), P("model", None, None),
                  P("model", None, None), P("model", None)),
        out_specs=P(row_spec, "model", None),
        check_vma=False,
    )(x, p.vals, p.nm_idx, p.vec_idx)
    return y.reshape(b, p.n_out)


def scatter_dense(p: PackedHiNM) -> jax.Array:
    """Decompress packed -> masked-dense (n_out, n_in); memory = one dense
    weight (scatter by kept-column ids, stays tile-sharded under SPMD)."""
    return packing.unpack(p)


def nm_select_ref(w: jax.Array, n: int = 2, m: int = 4) -> jax.Array:
    """Oracle for the fused train-time N:M select: keep top-N of each M group
    along the last axis (by |w|), zero the rest."""
    shape = w.shape
    g = w.reshape(shape[:-1] + (shape[-1] // m, m))
    mag = jnp.abs(g)
    order = jnp.argsort(mag, axis=-1, descending=True)
    ranks = jnp.argsort(order, axis=-1)
    return jnp.where((ranks < n), g, 0).reshape(shape)


def gather_cols_ref(x: jax.Array, idx: jax.Array) -> jax.Array:
    """Oracle for the runtime input-channel reorder gather. x:(B,n), idx:(T,K)."""
    return jnp.take(x, idx, axis=1)
