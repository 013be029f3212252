"""Pallas TPU kernel: HiNM sparse matmul  y = x @ W_packed^T.

TPU adaptation of the paper's SpMM. Per grid cell (one output tile x one
batch block):

  1. *Indexed gather* — the tile's `vec_idx` row sits in SMEM and drives
     one dynamic-offset row load per kept column out of the (n_in, Bblk)
     activation block resident in VMEM. This is the TPU analogue of the
     paper's global->shared indexed load: a permuted `vec_idx` (the ICP
     order) costs exactly the same as an identity one, so the runtime
     input-channel reorder is free.
  2. *N:M slot split instead of a decompress* — the gathered rows land in
     M "slot planes": plane j row p holds the activation row of dense
     column ``(p // N) * M + j``, i.e. the column that packed value p
     occupies when its 2-bit slot index is j. The packed values then
     contract against the planes as they are stored, masked per plane:

         y = sum_j where(nm_idx == j, vals, 0) @ plane_j

     Nothing is reshaped across the lane axis (Mosaic refuses lane-
     splitting reshapes), and the contraction touches M * Kn = N * K rows,
     the dense layer's n_in at 50 % vector sparsity. TPU has no sparse
     MXU, so the N:M level buys HBM bandwidth, not FLOPs.
  3. *Dense MXU contraction* — each plane contraction runs in the storage
     dtype on the MXU with f32 accumulation.

Grid ``(B / Bblk, T, Kn / kc)``: batch blocks outermost, so the
activation block is fetched once per batch block; output tiles next; the
packed rows of a tile innermost, ``kc = KN_CHUNK`` rows per step when a
layer has more (see `chunk_rows`), accumulated in an f32 VMEM scratch.
Chunking bounds the slot planes and weight blocks whatever the width.

Layouts: activations enter as xT (n_in, B) in f32 (the dynamic single-row
load needs a 32-bit sublane layout; the values are the bf16 activations
exactly) so the gather runs on the sublane axis; `vec_idx` enters as
(T, 1, K) int32 and reaches SMEM one chunk's columns at a time; outputs
leave as (n_out, B) with rows in packed (OCP) order.

VMEM per grid step is `vmem_bytes`; the kernel asks the compiler for that
plus a quarter (at least 32 MiB, at most VMEM_CAP_BYTES). The resident
activation block grows with n_in: 128 lanes of a 32768-wide layer take
32 MiB double-buffered, inside v5e's 128 MiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BBLK = 256
# packed rows (Kn) contracted per grid step; wider layers are cut into
# chunks of this many rows along an inner reduction grid axis
KN_CHUNK = 512
# working set the batch block is sized against (256 lanes where it fits)
VMEM_BUDGET_BYTES = 8 * 1024 * 1024
# most VMEM one kernel may ask the compiler for: v5e has 128 MiB per core,
# the rest is left to the compiler's own scratch
VMEM_CAP_BYTES = 96 * 1024 * 1024


class VMEMBudgetError(ValueError):
    """Even the smallest batch block's working set exceeds VMEM_CAP_BYTES."""


def _kernel(idx_ref, x_ref, vals_ref, nm_ref, out_ref, planes_ref, acc_ref,
            *, nn: int, mm: int, ct):
    kc = vals_ref.shape[-1]
    kcols = idx_ref.shape[-1]
    c = pl.program_id(2)

    def gather(q, carry):
        # 8 plane rows p = 8q .. 8q+7 per iteration; rows of one N-group
        # share their source column, so each is loaded once and repeated
        base = pl.multiple_of(q * 8, 8)
        for j in range(mm):
            rows = []
            for s in range(8):
                if 8 % nn == 0 and s % nn:
                    rows.append(rows[-1])
                    continue
                col = jnp.minimum((base + s) // nn * mm + j, kcols - 1)
                rows.append(x_ref[pl.ds(idx_ref[0, 0, col], 1), :])
            planes_ref[j, pl.ds(base, 8), :] = jnp.concatenate(rows, axis=0)
        return carry

    jax.lax.fori_loop(0, kc // 8, gather, 0)

    vals = vals_ref[0]                                 # (V, kc)
    slots = nm_ref[0].astype(jnp.int32)                # (V, kc)
    acc = None
    for j in range(mm):
        part = jax.lax.dot_general(
            jnp.where(slots == j, vals, jnp.zeros_like(vals)).astype(ct),
            planes_ref[j].astype(ct),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # (V, Bblk)
        acc = part if acc is None else acc + part

    @pl.when(c == 0)
    def _first():
        acc_ref[...] = acc

    @pl.when(c > 0)
    def _more():
        acc_ref[...] += acc

    @pl.when(c == pl.num_programs(2) - 1)
    def _out():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def chunk_rows(kn: int) -> tuple[int, int]:
    """(rows per chunk, padded Kn): one chunk of Kn rounded up to 8 rows
    when it is at most KN_CHUNK, else KN_CHUNK-row chunks (a multiple of
    128 lanes, so the weight blocks tile) with Kn padded to whole chunks."""
    kn8 = -(-kn // 8) * 8
    if kn8 <= KN_CHUNK:
        return kn8, kn8
    return KN_CHUNK, -(-kn // KN_CHUNK) * KN_CHUNK


def _vmem_terms(n_in: int, k: int, itemsize: int, v: int, nn: int,
                mm: int) -> tuple[int, int]:
    """(fixed bytes, bytes per batch column) of one grid step's VMEM."""
    kc, _ = chunk_rows(k // mm * nn)
    n_in8 = -(-n_in // 8) * 8
    return (3 * v * kc * (itemsize + 1),
            2 * n_in8 * 4 + mm * kc * 4 + v * (4 + 2 * itemsize))


def vmem_bytes(n_in: int, k: int, bblk: int, itemsize: int = 2, *,
               v: int = 32, nn: int = 2, mm: int = 4) -> int:
    """VMEM working set of one grid step (real itemsizes; the activation
    block is the f32 copy the gather reads):

      xT block      2 * n_in8 * bblk * 4       (double-buffered)
      slot planes   mm * kc * bblk * 4
      weights       2 * v * kc * (itemsize + 1) + v * kc * itemsize
      accum / out   v * bblk * 4 + 2 * v * bblk * itemsize
    """
    fixed, per_col = _vmem_terms(n_in, k, itemsize, v, nn, mm)
    return fixed + per_col * bblk


def pick_bblk(n_in: int, k: int, b: int, itemsize: int = 2, *, v: int = 32,
              nn: int = 2, mm: int = 4) -> int:
    """Batch (lane) block for the kernel.

    A batch of at most 128 columns is one block spanning the whole lane
    axis (a block equal to the array's last dim is always tileable).
    Larger batches take a multiple of 128 lanes: 256 where the working set
    (`vmem_bytes`) stays under VMEM_BUDGET_BYTES, else 128, the lane
    tiling's minimum. The wrapper pads the batch to a multiple of the
    block. Raises VMEMBudgetError where the chosen block's working set
    exceeds VMEM_CAP_BYTES (an activation block wider than ~90k rows).
    """
    from repro.kernels import ops

    if b <= LANES:
        bblk = b
    else:
        fixed, per_col = _vmem_terms(n_in, k, itemsize, v, nn, mm)
        bblk = LANES * ops.pick_tile(
            DEFAULT_BBLK // LANES, fixed, per_col * LANES,
            budget=VMEM_BUDGET_BYTES, floor=1, divide=False)
    need = vmem_bytes(n_in, k, bblk, itemsize, v=v, nn=nn, mm=mm)
    if need > VMEM_CAP_BYTES:
        raise VMEMBudgetError(
            f"hinm_spmm n_in={n_in} K={k}: a {bblk}-column batch block needs "
            f"{need} bytes of VMEM, over the {VMEM_CAP_BYTES}-byte cap")
    return bblk


@functools.partial(
    jax.jit, static_argnames=("nn", "mm", "bblk", "interpret", "out_dtype")
)
def hinm_spmm(
    x_t: jax.Array,       # (n_in, B) activations, transposed
    vals: jax.Array,      # (T, V, Kn)
    nm_idx: jax.Array,    # (T, V, Kn) int8
    vec_idx: jax.Array,   # (T, K) int32
    *,
    nn: int = 2,
    mm: int = 4,
    bblk: int | None = None,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Returns y_t (n_out, B) = W_packed @ x, rows in packed order."""
    n_in, b = x_t.shape
    t, v, kn = vals.shape
    k = vec_idx.shape[-1]
    if kn != k // mm * nn:
        raise ValueError(f"Kn={kn} inconsistent with K={k}, {nn}:{mm}")
    out_dtype = out_dtype or x_t.dtype
    itemsize = jnp.dtype(vals.dtype).itemsize
    bblk = bblk or pick_bblk(n_in, k, b, itemsize, v=v, nn=nn, mm=mm)
    ct = jnp.promote_types(vals.dtype, x_t.dtype)
    x_t = x_t.astype(jnp.float32)
    if b % bblk:
        x_t = jnp.pad(x_t, ((0, 0), (0, bblk - b % bblk)))
    bp = x_t.shape[1]
    kc, knp = chunk_rows(kn)
    kcols = k if knp == kc else kc // nn * mm
    vec_idx = vec_idx.astype(jnp.int32)
    if knp != kn:  # zero values contribute nothing, whatever row they read
        vals = jnp.pad(vals, ((0, 0), (0, 0), (0, knp - kn)))
        nm_idx = jnp.pad(nm_idx, ((0, 0), (0, 0), (0, knp - kn)))
        if knp != kc:
            vec_idx = jnp.pad(vec_idx, ((0, 0), (0, knp // nn * mm - k)))
    need = vmem_bytes(n_in, k, bblk, itemsize, v=v, nn=nn, mm=mm)

    out = pl.pallas_call(
        functools.partial(_kernel, nn=nn, mm=mm, ct=ct),
        # batch blocks outermost: the activation block is fetched once per
        # batch block while the weight tiles and their chunks stream
        grid=(bp // bblk, t, knp // kc),
        in_specs=[
            pl.BlockSpec((1, 1, kcols), lambda bi, i, c: (i, 0, c),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n_in, bblk), lambda bi, i, c: (0, bi)),
            pl.BlockSpec((1, v, kc), lambda bi, i, c: (i, 0, c)),
            pl.BlockSpec((1, v, kc), lambda bi, i, c: (i, 0, c)),
        ],
        out_specs=pl.BlockSpec((v, bblk), lambda bi, i, c: (i, bi)),
        out_shape=jax.ShapeDtypeStruct((t * v, bp), out_dtype),
        scratch_shapes=[pltpu.VMEM((mm, kc, bblk), jnp.float32),
                        pltpu.VMEM((v, bblk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=min(VMEM_CAP_BYTES,
                                 max(32 * 1024 * 1024, need * 5 // 4))),
        interpret=interpret,
    )(vec_idx.reshape(t, 1, -1), x_t, vals, nm_idx)
    return out[:, :b]
