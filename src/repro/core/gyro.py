"""Gyro-permutation (the paper's core algorithm, Section 4).

Two coupled searches, run offline on a per-layer saliency matrix:

  OCP  — output-channel permutation: groups the n_out rows into tiles of V
         so that column-wise vector pruning (followed by N:M) discards as
         little saliency as possible.  Iterates {sampling -> balanced
         K-means clustering -> Hungarian assignment} with an annealed
         sample count (the paper's learning-rate analogy).

  ICP  — tile-wise input-channel permutation: within each tile, permutes
         the K kept column-vectors across the K/M partitions of the N:M
         grouping so the 2:4 stage keeps the most saliency.  One sample
         per partition, no clustering, Hungarian assignment (Section 4.2).

Cost evaluation is the exact Eq. (4) objective and is jit/vmap-accelerated
(the combinatorial solvers stay in numpy — they are offline).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparsity
from repro.core.hungarian import balanced_kmeans, linear_sum_assignment
from repro.core.types import GyroResult, HiNMConfig

CostMode = Literal["hinm", "vector"]


# ---------------------------------------------------------------------------
# jit-accelerated cost kernels
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "cost_mode"))
def _retained(tiles: jax.Array, cfg: HiNMConfig, cost_mode: str) -> jax.Array:
    """Retained saliency of each (V, n_in) tile under the target pattern.

    tiles: (B, V, n_in) -> (B,). The same number as
    ``sum(tile * hinm_mask(tile))`` without building the mask: gather the
    kept columns in their stable order, then sum the top-N of every M
    group (or, for "vector", every kept column).
    """

    def one(tile):
        cols = sparsity.kept_column_ids(tile, cfg)[0]          # (K,)
        kept = jnp.take(tile, cols, axis=1)                     # (V, K)
        if cost_mode == "vector":
            return jnp.sum(kept)
        v, k = kept.shape
        groups = kept.reshape(v, k // cfg.m, cfg.m)
        return jnp.sum(jax.lax.top_k(groups, cfg.n)[0])

    return jax.vmap(one)(tiles)


@functools.partial(jax.jit, static_argnames=("cfg", "cost_mode"))
def _assignment_retained(base_rows: jax.Array, clus_rows: jax.Array,
                         cfg: HiNMConfig, cost_mode: str) -> jax.Array:
    """(P, P) retained saliency of partition i's base rows joined with
    cluster j's rows — the OCP assignment cost, in one dispatch.

    base_rows (P, V-s, n_in), clus_rows (P, s, n_in)."""
    p = clus_rows.shape[0]

    def row(base_i):
        tiles = jnp.concatenate(
            [jnp.broadcast_to(base_i[None], (p,) + base_i.shape), clus_rows],
            axis=1)                                             # (P, V, n_in)
        return _retained(tiles, cfg, cost_mode)

    return jax.lax.map(row, base_rows)


@functools.partial(jax.jit, static_argnames=("n", "m"))
def _nm_retained_groups(groups: jax.Array, n: int, m: int) -> jax.Array:
    """groups: (..., V, M) -> (...,) retained after per-row top-N of M."""
    top = jax.lax.top_k(groups, n)[0]
    return top.sum(axis=(-1, -2))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _channel_pruned_saliency(sal_perm: jax.Array, cfg: HiNMConfig) -> jax.Array:
    """Per-output-channel saliency discarded by the current HiNM mask."""
    mask = sparsity.hinm_mask(sal_perm, cfg)
    return jnp.sum(sal_perm * (1.0 - mask), axis=1)


# ---------------------------------------------------------------------------
# OCP — output-channel permutation
# ---------------------------------------------------------------------------


def _sample_schedule(v: int, iters: int, s0: int | None = None) -> list[int]:
    """Annealed per-partition sample counts (learning-rate analogy)."""
    if s0 is None:
        s0 = max(1, v // 4)
    out = []
    for t in range(iters):
        frac = t / max(iters - 1, 1)
        s = int(round(s0 * (1.0 - frac) + 1 * frac))
        out.append(max(1, min(s, v)))
    return out


def ocp(
    sal: np.ndarray,
    cfg: HiNMConfig,
    iters: int = 24,
    rng: np.random.Generator | None = None,
    cost_mode: CostMode = "hinm",
    s0: int | None = None,
    patience: int = 6,
) -> tuple[np.ndarray, list[float]]:
    """Output-channel permutation search. Returns (perm (n_out,), history)."""
    rng = rng or np.random.default_rng(0)
    sal = np.asarray(sal, dtype=np.float32)
    n_out, n_in = sal.shape
    cfg.validate_shape(n_out, n_in)
    v = cfg.v
    p = n_out // v

    perm = np.arange(n_out)
    sal_j = jnp.asarray(sal)

    def total_retained(perm_np: np.ndarray) -> float:
        tiles = jnp.asarray(sal[perm_np].reshape(p, v, n_in))
        return float(_retained(tiles, cfg, cost_mode).sum())

    best = total_retained(perm)
    history = [best]
    schedule = _sample_schedule(v, iters, s0)
    stall = 0

    for it, s in enumerate(schedule):
        if p == 1:
            break
        # ---- sampling: extract the s worst-fitting channels per partition
        sal_perm = jnp.take(sal_j, jnp.asarray(perm), axis=0)
        misfit = np.asarray(_channel_pruned_saliency(sal_perm, cfg))
        part = perm.reshape(p, v)
        part_misfit = misfit.reshape(p, v)
        # worst-fit with random tie-noise to escape plateaus
        noise = rng.uniform(0.0, 1e-6, size=part_misfit.shape) * (part_misfit.max() + 1.0)
        extract_pos = np.argsort(-(part_misfit + noise), axis=1)[:, :s]  # (P, s)
        extracted = np.take_along_axis(part, extract_pos, axis=1)        # (P, s)
        keep_mask = np.ones((p, v), dtype=bool)
        np.put_along_axis(keep_mask, extract_pos, False, axis=1)
        bases = part[keep_mask].reshape(p, v - s)                        # (P, V-s)

        # ---- clustering: balanced k-means of the P*s samples into P groups
        samples = extracted.reshape(-1)                                  # (P*s,)
        if s == 1:
            clusters = samples.reshape(p, 1)
        else:
            feats = sal[samples]
            labels = balanced_kmeans(feats, p, rng)
            order = np.argsort(labels, kind="stable")
            clusters = samples[order].reshape(p, s)                      # (P, s)

        # ---- assignment: Hungarian on exact Eq.(4) cost
        base_rows = sal[bases.reshape(-1)].reshape(p, v - s, n_in)
        clus_rows = sal[clusters.reshape(-1)].reshape(p, s, n_in)
        totals = base_rows.sum(axis=(1, 2))[:, None] + clus_rows.sum(axis=(1, 2))[None, :]
        ret = np.asarray(_assignment_retained(
            jnp.asarray(base_rows), jnp.asarray(clus_rows), cfg, cost_mode))
        cost = totals.astype(np.float64) - ret
        rows, cols = linear_sum_assignment(cost)

        new_part = np.concatenate([bases, clusters[cols]], axis=1)       # (P, V)
        new_perm = new_part.reshape(-1)
        cand = total_retained(new_perm)
        if cand > best + 1e-9:
            best, perm = cand, new_perm
            stall = 0
        else:
            stall += 1
        history.append(best)
        if stall >= patience:
            break
    return perm, history


# ---------------------------------------------------------------------------
# ICP — tile-wise input-channel (column-vector) permutation
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n", "m"))
def _icp_marginals(tile: jax.Array, n: int, m: int) -> jax.Array:
    """Marginal retained saliency of each column within its M-partition.

    tile: (V, K) -> (G, M) marginal of removing each column from its group.
    Smallest marginal = most replaceable = the ICP sample.
    """
    v, k = tile.shape
    g = k // m
    grp = tile.reshape(v, g, m)
    full = _nm_retained_groups(jnp.moveaxis(grp, 0, 1), n, m)            # (G,)

    def without(slot):
        reduced = jnp.delete(grp, slot, axis=2, assume_unique_indices=True)
        # after removing one column: keep top-N of the remaining M-1
        top = jax.lax.top_k(jnp.moveaxis(reduced, 0, 1), n)[0]
        return top.sum(axis=(-1, -2))                                    # (G,)

    rets = jnp.stack([without(sl) for sl in range(m)], axis=1)           # (G, M)
    return full[:, None] - rets


@functools.partial(jax.jit, static_argnames=("n", "m", "chunk"))
def _icp_cost_matrix(
    rem: jax.Array, cols: jax.Array, n: int, m: int, chunk: int = 64
) -> jax.Array:
    """Eq.(4) cost of placing extracted column j into partition i.

    rem:  (G, V, M-1) remaining columns per partition
    cols: (G, V)      extracted columns
    returns (G, G) cost = total - retained(top-N of M).
    """
    g = rem.shape[0]
    totals = rem.sum(axis=(1, 2))[:, None] + cols.sum(axis=1)[None, :]

    def row(rem_i):
        merged = jnp.concatenate(
            [jnp.broadcast_to(rem_i[None], (g,) + rem_i.shape), cols[:, :, None]],
            axis=2,
        )                                                                 # (G, V, M)
        return _nm_retained_groups(merged, n, m)                          # (G,)

    ret = jax.lax.map(row, rem, batch_size=chunk)                         # (G, G)
    return totals - ret


@functools.partial(jax.jit, static_argnames=("n", "m"))
def _icp_step(tiles: jax.Array, orders: jax.Array, n: int, m: int):
    """One ICP sampling step for every tile, in one dispatch.

    tiles (T, V, K), orders (T, K) -> (extract_slot (T, G), cost (T, G, G)):
    the most replaceable column of each partition under the current order,
    and the Eq.(4) cost of placing extracted column j into partition i."""

    def one(tile, order):
        v, k = tile.shape
        g = k // m
        cur = jnp.take(tile, order, axis=1)
        slot = jnp.argmin(_icp_marginals(cur, n, m), axis=1)          # (G,)
        pos = order.reshape(g, m)
        keep = jnp.arange(m)[None, :] != slot[:, None]                # (G, M)
        rem_pos = jnp.sort(jnp.where(keep, pos, k), axis=1)[:, :m - 1]
        ext_pos = jnp.take_along_axis(pos, slot[:, None], axis=1)[:, 0]
        rem = jnp.moveaxis(tile[:, rem_pos.reshape(-1)].reshape(v, g, m - 1),
                           0, 1)                                       # (G, V, M-1)
        return slot, _icp_cost_matrix(rem, tile[:, ext_pos].T, n, m)

    return jax.lax.map(lambda a: one(*a), (tiles, orders))


@functools.partial(jax.jit, static_argnames=("n", "m"))
def _icp_retained(tiles: jax.Array, orders: jax.Array, n: int, m: int):
    """(T,) retained saliency of each tile grouped in its column order."""

    def one(tile, order):
        v, k = tile.shape
        grp = jnp.take(tile, order, axis=1).reshape(v, k // m, m)
        return _nm_retained_groups(jnp.moveaxis(grp, 0, 1), n, m).sum()

    return jax.lax.map(lambda a: one(*a), (tiles, orders))


def icp(
    sal_gathered: np.ndarray,
    cfg: HiNMConfig,
    iters: int = 16,
    patience: int = 4,
) -> tuple[np.ndarray, np.ndarray]:
    """Permute the K kept columns of every (V, K) tile, all tiles at once.

    sal_gathered (T, V, K) -> (orders (T, K), history (n_iters + 1, T) of
    each tile's best retained saliency). Each iteration extracts the most
    replaceable column of every partition (the ICP sample) and reassigns
    the extracted columns by Hungarian matching; a tile keeps the new order
    only when it retains more, and stops after `patience` stalls. The
    device computes every tile's costs in one dispatch per step; the
    assignments are solved per tile on the host.
    """
    tiles_np = np.asarray(sal_gathered, dtype=np.float32)
    t, v, k = tiles_np.shape
    g = k // cfg.m
    tiles = jnp.asarray(tiles_np)
    orders = np.tile(np.arange(k), (t, 1))
    best = np.array(_icp_retained(tiles, jnp.asarray(orders), cfg.n, cfg.m))
    history = [best.copy()]
    if g == 1:
        return orders, np.stack(history)
    stall = np.zeros(t, dtype=np.int64)
    for _ in range(iters):
        live = np.flatnonzero(stall < patience)
        if live.size == 0:
            break
        # every tile is costed (fixed shapes: one compile); only the live
        # ones are reassigned
        slot, cost = _icp_step(tiles, jnp.asarray(orders), cfg.n, cfg.m)
        slot, cost = np.asarray(slot), np.asarray(cost)
        cand = orders.copy()
        for ti in live:
            pos = orders[ti].reshape(g, cfg.m)
            ext = pos[np.arange(g), slot[ti]]
            keep = np.ones((g, cfg.m), dtype=bool)
            keep[np.arange(g), slot[ti]] = False
            rem_pos = pos[keep].reshape(g, cfg.m - 1)
            _, assign = linear_sum_assignment(cost[ti])
            cand[ti] = np.concatenate([rem_pos, ext[assign][:, None]],
                                      axis=1).reshape(-1)
        ret = np.asarray(_icp_retained(tiles, jnp.asarray(cand), cfg.n, cfg.m))
        better = np.zeros(t, dtype=bool)
        better[live] = ret[live] > best[live] + 1e-9
        orders[better] = cand[better]
        best[better] = ret[better]
        stall[better] = 0
        stall[live[~better[live]]] += 1
        history.append(best.copy())
    return orders, np.stack(history)


# ---------------------------------------------------------------------------
# full gyro-permutation
# ---------------------------------------------------------------------------


def gyro_permute(
    sal: np.ndarray,
    cfg: HiNMConfig,
    ocp_iters: int = 24,
    icp_iters: int = 16,
    rng: np.random.Generator | None = None,
    cost_mode: CostMode = "hinm",
    run_ocp: bool = True,
    run_icp: bool = True,
) -> GyroResult:
    """Full pipeline: OCP -> vector selection -> tile-wise ICP.

    Returns a GyroResult whose `col_order` is the absolute kept-column ids in
    ICP order — i.e. exactly the `vec_idx` the packed format stores.
    """
    rng = rng or np.random.default_rng(0)
    sal = np.asarray(sal, dtype=np.float32)
    n_out, n_in = sal.shape
    cfg.validate_shape(n_out, n_in)
    history: list[float] = []

    if run_ocp:
        out_perm, h = ocp(sal, cfg, iters=ocp_iters, rng=rng, cost_mode=cost_mode)
        history.extend(h)
    else:
        out_perm = np.arange(n_out)

    sal_p = sal[out_perm]
    col_ids = np.asarray(sparsity.kept_column_ids(jnp.asarray(sal_p), cfg))  # (T, K)
    t, k = col_ids.shape
    sal_t = sal_p.reshape(t, cfg.v, n_in)
    gathered = np.take_along_axis(sal_t, col_ids[:, None, :], axis=2)        # (T,V,K)

    if run_icp:
        orders, _ = icp(gathered, cfg, iters=icp_iters)
        col_order = np.take_along_axis(col_ids, orders, axis=1)
    else:
        col_order = col_ids

    mask = sparsity.hinm_mask_from_columns(
        jnp.asarray(sal_p), jnp.asarray(col_order), cfg
    )
    retained = float(jnp.sum(jnp.asarray(sal_p) * mask))
    history.append(retained)
    return GyroResult(
        out_perm=out_perm,
        col_order=col_order.astype(np.int32),
        retained=retained,
        total=float(sal.sum()),
        history=history,
    )
