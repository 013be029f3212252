"""Pallas TPU kernel: fused N:M magnitude select (training-time pruning).

Keeps the top-N-of-each-M group along the last axis by |w| and zeroes the
rest, in one VMEM pass. Used by the gradual-pruning train step, where the
mask is recomputed from the live weights every pruning interval.

Rank computation is an O(M^2) compare-reduce (M is 4): rank_i = #{j :
|w_j| > |w_i|  or  (|w_j| == |w_i| and j < i)} — sort-free, VPU-friendly,
and bit-exact against the argsort-based oracle in ref.py. The group stays
on the lane axis (Mosaic refuses lane-splitting reshapes): group partner
i + d (mod M) of lane l is the block rotated by -d, or by M - d where the
offset wraps inside the group, so each compare is two lane rotations and a
select. Groups never straddle a block (column blocks hold whole groups),
so a rotation's wrap at the block edge is never selected.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _kernel(w_ref, out_ref, *, nn: int, mm: int):
    w = w_ref[...]                                     # (Rblk, Cblk)
    c = w.shape[1]
    mag = jnp.abs(w.astype(jnp.float32))
    pos = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, w.shape, 1), mm)
    rank = jnp.zeros(w.shape, jnp.int32)
    for d in range(1, mm):
        ahead = pltpu.roll(mag, c - d, 1)              # lane l -> l + d
        behind = pltpu.roll(mag, mm - d, 1)            # lane l -> l + d - M
        wraps = pos + d >= mm                          # partner index < i
        other = jnp.where(wraps, behind, ahead)
        beats = (other > mag) | ((other == mag) & wraps)
        rank = rank + beats.astype(jnp.int32)
    out_ref[...] = jnp.where(rank < nn, w, jnp.zeros_like(w))


@functools.partial(
    jax.jit, static_argnames=("nn", "mm", "rblk", "cblk", "interpret")
)
def nm_select(
    w: jax.Array,
    *,
    nn: int = 2,
    mm: int = 4,
    rblk: int = 256,
    cblk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Top-N-of-M select along the last axis of a 2-D array.

    Blocks tile the TPU layout: a dimension that fits one block is taken
    whole (a block spanning the array is always tileable); a larger one is
    cut into ``rblk``-row blocks (a multiple of 8) and ``cblk``-column
    blocks (a multiple of 128 and of M), and padded with zero rows /
    whole zero groups to a block multiple, which are sliced off again.
    """
    rows, cols = w.shape
    if cols % mm != 0:
        raise ValueError(f"cols={cols} % M={mm} != 0")
    if rows > rblk:
        rblk = max(8, rblk // 8 * 8)
    else:
        rblk = rows
    step = LANES * mm // math.gcd(LANES, mm)
    if cols > cblk:
        cblk = max(step, cblk // step * step)
    else:
        cblk = cols
    pr, pc = -rows % rblk, -cols % cblk
    wp = jnp.pad(w, ((0, pr), (0, pc))) if pr or pc else w
    out = pl.pallas_call(
        functools.partial(_kernel, nn=nn, mm=mm),
        grid=(wp.shape[0] // rblk, wp.shape[1] // cblk),
        in_specs=[pl.BlockSpec((rblk, cblk), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((rblk, cblk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(wp.shape, w.dtype),
        interpret=interpret,
    )(wp)
    return out[:rows, :cols] if pr or pc else out
