"""Pallas TPU kernel: paged-attention decode over the serve slot pool.

The serving hot path resolves a slot's KV rows through its block table
``bt`` — the runtime analogue of the HiNM kernel's ``vec_idx``
(models/paging.py).  The jnp reference path materialises the full logical
view first (``pool[bt]`` gather: O(n_bt * page) rows copied per step, per
layer) and then runs chunked online-softmax attention over the copy.  This
kernel fuses the two: the grid walks the block table directly, one program
per slot streaming that slot's pages HBM->VMEM via scalar-prefetched index
maps, with flash-style online-softmax accumulation per KV head in VMEM
scratch — the contiguous view is never built.

Grid ``(B, n_bt // pp)``: the last (innermost) dimension streams the
slot's block-table entries, ``pp`` pages per step.  Each page arrives as
one whole ``(page, KV, hd)`` block (a block's two minor dims must tile or
span the array, so a page is never split by head) and every KV head of
the slot is served from it.  ``pp`` is picked with the same VMEM-budget
discipline as ``hinm_spmm.pick_bblk`` (see ``ops.pick_tile``): the
per-page working set (padded k/v blocks + f32 upcasts + score tile) is
halved against the budget, so large pages or head dims degrade to fewer
pages per step instead of spilling VMEM.  Each page is fetched by an index
map that reads ``bt[b, i*pp + j]`` from SMEM (``PrefetchScalarGridSpec``)
— a permuted block table costs exactly the same as an identity one, the
paper's indexed-gather trick applied to the KV cache.  The slots' kpos
rows are resolved through the block table outside the kernel (int32s for
the batch's pages only) and enter as ``(B, n_bt, 1, page)`` so each page's
row is a tileable block.

Masking folds every paged-pool invariant into one comparison chain:

  * sentinel pages (unallocated block-table tail) hold ``kpos = 2**30``,
    so ``kpos <= q_pos`` masks them with no extra branch;
  * rollback-swept rows (rejected speculative writes) had their ``kpos``
    reset to the sentinel and mask identically;
  * a sliding window adds ``kpos > q_pos - window`` (hybrid rings).

Queries enter pre-scaled f32 as ``(B, KV, s*G, hd)`` — s decode rows per
slot (s=1 decode, s=k+1 speculative verify; the causal mask hides each
row's future rows exactly as the gather path does).  K/V stay in the pool
storage dtype until the per-page VMEM upcast, matching the reference
``_attn_qchunk`` dataflow, and the epilogue divides by
``max(l, 1e-30)`` like the reference so fully-masked rows agree bitwise.

``interpret=True`` runs the same kernel through the Pallas interpreter so
CPU CI validates it against the gather path (kernels/ops routes this).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# both dots contract f32 operands at full f32 precision (several MXU
# passes), as the gather reference does under "highest"; the default
# would round them to one bf16 pass
HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(bt_ref, q_ref, qpos_ref, kpos_ref, *refs, pp: int, window: int):
    k_refs = refs[:pp]
    v_refs = refs[pp:2 * pp]
    o_ref = refs[2 * pp]
    m_ref, l_ref, acc_ref = refs[2 * pp + 1:]
    kvh = q_ref.shape[1]
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = qpos_ref[0]                                # (Gs, 1) int32
    for j in range(pp):
        kp = kpos_ref[0, j]                           # (1, page) int32
        msk = kp <= qpos
        if window:
            msk &= kp > qpos - window
        for h in range(kvh):
            q = q_ref[0, h]                           # (Gs, hd) f32 pre-scaled
            kj = k_refs[j][0, :, h, :].astype(jnp.float32)   # (page, hd)
            vj = v_refs[j][0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kj, dimension_numbers=(((1,), (1,)), ((), ())),
                precision=HIGHEST,
                preferred_element_type=jnp.float32)           # (Gs, page)
            s = jnp.where(msk, s, NEG_INF)
            m_prev = m_ref[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[h][:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, vj, dimension_numbers=(((1,), (0,)), ((), ())),
                precision=HIGHEST, preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(i == pl.num_programs(1) - 1)
    def _fin():
        for h in range(kvh):
            o_ref[0, h] = (acc_ref[h] / jnp.maximum(l_ref[h][:, :1], 1e-30)
                           ).astype(o_ref.dtype)


def _vmem_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) tile after (sublane, 128-lane) padding;
    a 32-bit sublane tile holds 8 rows, packed dtypes proportionally more."""
    sub = 8 * max(1, 4 // itemsize)
    return -(-rows // sub) * sub * -(-cols // LANES) * LANES * itemsize


def pick_pp(n_bt: int, page: int, hd: int, gs: int, itemsize: int,
            kvh: int = 1) -> int:
    """Pages streamed per grid step, VMEM-budgeted like ``pick_bblk``.

    Per-page working set: the double-buffered (page, KV, hd) k/v blocks in
    storage dtype, padded to the VMEM tiling (the KV dim sits on the
    sublane axis), the kpos row, and per head the f32 upcasts plus the
    (Gs, page) score / probability / mask transients. Fixed per-program
    cost: the pre-scaled q tile, the f32 accumulator and output tile, and
    the (Gs, 128) m/l statistic scratch, all per KV head.
    """
    from repro.kernels import ops

    fixed = kvh * (3 * _vmem_bytes(gs, hd, 4) + 2 * _vmem_bytes(gs, LANES, 4))
    per_page = (2 * 2 * page * _vmem_bytes(kvh, hd, itemsize)
                + 2 * _vmem_bytes(1, page, 4)
                + 2 * _vmem_bytes(page, hd, 4)
                + 3 * _vmem_bytes(gs, page, 4))
    return ops.pick_tile(n_bt, fixed, per_page, start=8)


@functools.partial(jax.jit, static_argnames=("window", "pp", "interpret"))
def paged_decode_attn(
    q: jax.Array,          # (B, s, H, hd) — s decode rows per slot
    k_pool: jax.Array,     # (n_pages, page, KV, hd)
    v_pool: jax.Array,     # (n_pages, page, KV, hd)
    kpos_pool: jax.Array,  # (n_pages, page) int32
    bt: jax.Array,         # (B, n_bt) int32 block table
    q_pos: jax.Array,      # (B, s) int32 absolute query positions
    *,
    window: int = 0,
    pp: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Block-table-resolved decode attention. Returns (B, s, H, hd)."""
    b, s, h, hd = q.shape
    n_pages, page, kvh, _ = k_pool.shape
    g = h // kvh
    gs = s * g
    n_bt = bt.shape[1]
    pp = pp or pick_pp(n_bt, page, hd, gs, jnp.dtype(k_pool.dtype).itemsize,
                       kvh)

    scale = hd ** -0.5
    # row layout (s, G) flattened s-major: row r belongs to query s-index
    # r // G, so its position is q_pos repeated G times along the row axis
    qf = (q.astype(jnp.float32) * scale).reshape(b, s, kvh, g, hd)
    qf = jnp.moveaxis(qf, 2, 1).reshape(b, kvh, gs, hd)
    qpos = jnp.repeat(q_pos.astype(jnp.int32), g, axis=1)[..., None]
    bt = bt.astype(jnp.int32)
    # the slots' kpos rows, resolved through the block table (B*n_bt*page
    # int32s — the batch's pages, not the pool), one (1, page) row per page
    kpos_view = jnp.take(kpos_pool, bt, axis=0)[:, :, None, :]

    def pool_map(j):
        return lambda bi, ii, tbl: (tbl[bi, ii * pp + j], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_bt // pp),
        in_specs=(
            [pl.BlockSpec((1, kvh, gs, hd), lambda bi, ii, tbl: (bi, 0, 0, 0)),
             pl.BlockSpec((1, gs, 1), lambda bi, ii, tbl: (bi, 0, 0)),
             pl.BlockSpec((1, pp, 1, page), lambda bi, ii, tbl: (bi, ii, 0, 0))]
            + [pl.BlockSpec((1, page, kvh, hd), pool_map(j)) for j in range(pp)]
            + [pl.BlockSpec((1, page, kvh, hd), pool_map(j)) for j in range(pp)]
        ),
        out_specs=pl.BlockSpec((1, kvh, gs, hd),
                               lambda bi, ii, tbl: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, gs, LANES), jnp.float32),   # running max m
            pltpu.VMEM((kvh, gs, LANES), jnp.float32),   # running sum l
            pltpu.VMEM((kvh, gs, hd), jnp.float32),      # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, pp=pp, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, gs, hd), jnp.float32),
        interpret=interpret,
    )(bt, qf, qpos, kpos_view, *([k_pool] * pp), *([v_pool] * pp))

    out = jnp.moveaxis(out.reshape(b, kvh, s, g, hd), 1, 2)
    return out.reshape(b, s, h, hd).astype(q.dtype)
