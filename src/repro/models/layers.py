"""Shared transformer layers: RoPE, GQA attention (chunked online-softmax),
MLPs. All pure jnp; memory-bounded attention via a lax.scan over KV blocks
so 32k-sequence training shapes compile without materialising (S, S) scores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import module as nn
from repro.models import probe_mode

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd), positions: (B, S) -> rotated x."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x32a, x32b = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x32a * cos - x32b * sin, x32b * cos + x32a * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention — chunked online softmax (flash-style in pure jnp)
# ---------------------------------------------------------------------------


def _seq_parallel_decode_attn(q, ck, cv, q_pos, kpos, window: int):
    """Sequence-parallel decode attention (beyond-paper §Perf).

    The KV cache is S-sharded over 'model'; each shard computes attention
    over its local slots and the shards combine with a max/sum-stat psum —
    O(B*H*hd) bytes instead of all-gathering the cache (GBs per layer).
    Returns None when preconditions fail (no mesh / S doesn't divide).
    """
    am = jax.sharding.get_abstract_mesh()
    if am.empty or "model" not in am.axis_names:
        return None
    b, sq, h, hd = q.shape
    smax, kv = ck.shape[1], ck.shape[2]
    nmodel = am.shape["model"]
    if sq != 1 or smax % nmodel or smax // nmodel < 1:
        return None
    if kpos.ndim != 2:  # legacy shared-position caches are not supported
        return None
    dp = tuple(a for a in ("pod", "data") if a in am.axis_names)
    ndp = 1
    for a in dp:
        ndp *= am.shape[a]
    row = dp if (dp and b % ndp == 0) else None
    g = h // kv
    P = jax.sharding.PartitionSpec

    def body(q_l, k_l, v_l, kpos_l, qpos_l):
        bl = q_l.shape[0]  # local batch shard
        qf = (q_l.astype(jnp.float32) * hd ** -0.5).reshape(bl, kv, g, hd)
        kf = k_l.astype(jnp.float32)                      # (B, S_loc, KV, hd)
        s = jnp.einsum("bkgd,bckd->bkgc", qf, kf)         # (B, KV, G, S_loc)
        msk = kpos_l <= qpos_l[:, :1]                     # (B, S_loc) per slot
        if window:
            msk &= kpos_l > (qpos_l[:, :1] - window)
        s = jnp.where(msk[:, None, None, :], s, NEG_INF)
        m_l = jnp.max(s, axis=-1)
        m_g = jax.lax.pmax(m_l, "model")
        p = jnp.exp(s - m_g[..., None])
        l_g = jax.lax.psum(p.sum(-1), "model")
        o_g = jax.lax.psum(
            jnp.einsum("bkgc,bckd->bkgd", p, v_l.astype(jnp.float32)), "model"
        )
        out = o_g / jnp.maximum(l_g[..., None], 1e-30)
        return out.reshape(bl, 1, h, hd).astype(q_l.dtype)

    return jax.shard_map(
        body,
        mesh=am,
        in_specs=(P(row, None, None, None), P(row, "model", None, None),
                  P(row, "model", None, None), P(row, "model"), P(row, None)),
        out_specs=P(row, None, None, None),
        check_vma=False,
    )(q, ck, cv, kpos, q_pos)


def _attn_qchunk(
    qf: jax.Array,           # (B, Sq, KV, G, hd) f32, pre-scaled
    kb: jax.Array,           # (B, nblk, blk, KV, hd) f32
    vb: jax.Array,
    q_pos: jax.Array,        # (B, Sq)
    pb: jax.Array,           # (B, nblk, blk)
    causal: bool,
    window: int,
) -> jax.Array:
    """Online-softmax over KV blocks for one query chunk."""
    b, sq, kv, g, hd = qf.shape
    kv_block = kb.shape[2]

    def step(carry, blk):
        m_prev, l_prev, o_prev = carry
        kc, vc, pc = blk                                   # (B, blk, KV, hd) ...
        kc = kc.astype(jnp.float32)                        # per-block upcast only
        vc = vc.astype(jnp.float32)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qf, kc)        # (B,Sq,KV,G,blk)
        msk = pc[:, None, :] <= q_pos[:, :, None] if causal else jnp.ones(
            (b, sq, kv_block), dtype=bool
        )
        if window:
            msk &= pc[:, None, :] > (q_pos[:, :, None] - window)
        s = jnp.where(msk[:, :, None, None, :], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1)
        o_new = o_prev * corr[..., None] + jnp.einsum("bqkgc,bckd->bqkgd", p, vc)
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, sq, kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kv, g), jnp.float32)
    o0 = jnp.zeros((b, sq, kv, g, hd), jnp.float32)
    (m, l, o), _ = jax.lax.scan(
        step,
        (m0, l0, o0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.moveaxis(pb, 1, 0)),
        unroll=True if probe_mode.enabled() else 1,
    )
    return o / jnp.maximum(l[..., None], 1e-30)


def _attn_chunked(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Sk, KV, hd)
    v: jax.Array,            # (B, Sk, KV, hd)
    q_pos: jax.Array,        # (B, Sq) absolute positions of queries
    k_pos: jax.Array,        # (B, Sk) absolute positions of keys
    causal: bool,
    window: int,             # 0 = unlimited
    kv_block: int = 512,
    q_block: int = 512,
    aligned: bool = False,   # q_pos/k_pos are the standard arange (training)
) -> jax.Array:
    """Flash-style attention in pure jnp: lax.map over query blocks, online
    softmax over KV blocks inside — peak memory is one (qblk, kvblk) score
    tile per device, never (S, S)."""
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(b, sq, kv, g, hd)

    from repro.perf_knobs import KNOBS

    skip_blocks = causal and aligned and KNOBS.causal_block_skip
    if probe_mode.enabled() and not skip_blocks:
        # one-shot: every FLOP visible to cost_analysis. The block-skipping
        # path instead keeps its static chunking (whose skipped blocks ARE
        # the true FLOP count) with the inner scans unrolled.
        kv_block, q_block = sk, sq

    nblk = max(1, sk // kv_block)
    if sk % kv_block != 0:
        nblk, kv_block = 1, sk
    kb = k.reshape(b, nblk, kv_block, kv, hd)   # stays in storage dtype;
    vb = v.reshape(b, nblk, kv_block, kv, hd)   # upcast happens per block
    pb = k_pos.reshape(b, nblk, kv_block)

    nq = max(1, sq // q_block)
    if sq % q_block != 0:
        nq, q_block = 1, sq
    if nq == 1:
        out = _attn_qchunk(qf, kb, vb, q_pos, pb, causal, window)
        return out.reshape(b, sq, h, hd).astype(q.dtype)

    qc = jnp.moveaxis(qf.reshape(b, nq, q_block, kv, g, hd), 1, 0)
    pc = jnp.moveaxis(q_pos.reshape(b, nq, q_block), 1, 0)

    if skip_blocks:
        # causal block skipping (§Perf): positions are the standard arange,
        # so query chunk i attends to a STATIC prefix of KV blocks — the
        # upper-triangle blocks are never computed (2x attention FLOPs on
        # long-sequence training). Python loop => static slices,
        # differentiable; per-chunk checkpoint keeps flash-bwd memory.
        from functools import partial

        @partial(jax.checkpoint, static_argnums=(2, 3))
        def one_prefix(qi, pi, lo, hi, kbf, vbf, pbf):
            # slice INSIDE the remat region: the residual is the shared
            # full K/V (one buffer), not per-chunk slice copies
            return _attn_qchunk(qi, kbf[:, lo:hi], vbf[:, lo:hi], pi,
                                pbf[:, lo:hi], True, window)

        outs = []
        for i in range(nq):
            hi = min(nblk, ((i + 1) * q_block + kv_block - 1) // kv_block)
            lo = 0
            if window:
                lo = max(0, (i * q_block - window) // kv_block)
            outs.append(one_prefix(qc[i], pc[i], lo, hi, kb, vb, pb))
        out = jnp.stack(outs)                               # (nq, B, qblk, KV, G, hd)
        out = jnp.moveaxis(out, 0, 1).reshape(b, sq, h, hd)
        return out.astype(q.dtype)

    # checkpoint per query chunk: the backward pass recomputes each chunk's
    # probabilities instead of saving the full (S, S) tensor (flash bwd)
    @jax.checkpoint
    def one(args):
        qi, pi = args
        return _attn_qchunk(qi, kb, vb, pi, pb, causal, window)

    out = jax.lax.map(one, (qc, pc))                        # (nq, B, qblk, KV, G, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def attention(
    params: dict,
    x: jax.Array,                 # (B, S, D)
    positions: jax.Array,         # (B, S)
    cfg,
    cache: dict | None = None,    # decode: {"k","v" (B,Smax,KV,hd),
                                  #          "pos" (B,), "kpos" (B,Smax)}
    kv_block: int = 1024,
    bidirectional: bool = False,
    spec: bool = False,           # multi-token speculative verify write
) -> tuple[jax.Array, dict | None]:
    """GQA attention with RoPE. Returns (out (B,S,D), updated cache)."""
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = nn.linear(params["wq"], x).reshape(b, s, h, hd)
    k = nn.linear(params["wk"], x).reshape(b, s, kvh, hd)
    v = nn.linear(params["wv"], x).reshape(b, s, kvh, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = _attn_chunked(
            q, k, v, positions, positions, not bidirectional, cfg.window, kv_block,
            aligned=not bidirectional,
        )
        new_cache = None
    elif "bt" in cache:
        # Paged pool (serve slot pool): the slot's rows live in shared
        # physical pages resolved through its block table `bt` — a runtime
        # vec_idx for the cache. The new row is written straight to its
        # physical page; the gather `pool[bt]` then yields a contiguous
        # lane view for the same chunked attention as the stripe path.
        # Writes whose logical page falls outside the slot's allocation
        # (idle lanes keep stepping inside a decode chunk) are redirected
        # to the scratch page, which no block table ever references.
        from repro.models import paging

        if s != 1 and not spec:
            raise ValueError(
                "paged KV caches only support single-token decode here; "
                "multi-token writes go through the speculative verify "
                "branch (zoo.verify_step passes spec=True) and prefill "
                "runs on a stripe template")
        pos = cache["pos"]                                  # (B,) int32
        bt, alloc = cache["bt"], cache["alloc"]
        page = cache["k"].shape[1]                          # (n_pages, page, KV, hd)
        if s > 1 and cfg.window:
            # a wrapped multi-token write would clobber rows earlier
            # queries still need (hybrid verifies sequentially instead)
            raise ValueError("multi-token spec write cannot wrap a "
                             "windowed ring; use sequential verify")
        # single-token decode and speculative verify share one addressing
        # (also the sweep addressing of paging.rollback_attn_paged): all s
        # rows land through the block table in one dispatch, rows past the
        # allocation redirected to scratch.  For s > 1 the causal mask then
        # hides each row's future rows exactly, so one attend sees the same
        # KV set — in the same layout order, hence bitwise the same online
        # softmax — as s sequential single-token steps would.
        phys_s, off, valid = paging.spec_row_locations(
            bt, alloc, pos, s, page, window=bool(cfg.window))
        phys_w = jnp.where(valid, phys_s, paging.SCRATCH_PAGE)
        ck = cache["k"].at[phys_w, off].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[phys_w, off].set(v.astype(cache["v"].dtype))
        ckpos = cache["kpos"].at[phys_w, off].set(positions.astype(jnp.int32))
        from repro.perf_knobs import KNOBS

        out = None
        if KNOBS.paged_attn != "off":
            # Pallas kernel: resolves the block table inside the grid —
            # sentinel pages and swept rows mask through the same kpos
            # comparisons, so no gather copy is ever built. Returns None
            # when the backend defers to the gather path (auto off-TPU).
            from repro.kernels import ops as kops

            out = kops.paged_attention(q, ck, cv, ckpos, bt, positions,
                                       window=cfg.window,
                                       backend=KNOBS.paged_attn)
        if out is None:
            k_view = paging.gather_view(ck, bt)
            v_view = paging.gather_view(cv, bt)
            kpos_view = paging.gather_view(ckpos, bt)
            out = _attn_chunked(q, k_view, v_view, positions, kpos_view,
                                True, cfg.window, kv_block)
        new_cache = {"k": ck, "v": cv, "kpos": ckpos, "pos": pos + s,
                     "bt": bt, "alloc": alloc}
    else:
        # Cache slots are a ring buffer when a sliding window bounds the
        # live KV set (smax = window); per-slot absolute positions ("kpos")
        # drive the causal/window mask, so slot index never aliases time.
        # `pos` and `kpos` carry a batch dimension — each batch lane is an
        # independent request slot (continuous batching): lanes may sit at
        # different decode positions, so every cache write is a per-lane
        # dynamic_update_slice at that lane's own offset.
        pos = cache["pos"]                                  # (B,) int32
        smax = cache["k"].shape[1]
        if s >= smax:
            # prefill longer than the (windowed) cache: attend over the fresh
            # K/V directly and retain only the trailing `smax` entries,
            # rolled so the ring invariant slot == pos % smax holds for the
            # decode steps that follow.
            out = _attn_chunked(q, k, v, positions, positions, True, cfg.window, kv_block)
            shift = jax.lax.rem(positions[:, -smax].astype(jnp.int32), smax)
            ck = jax.vmap(lambda kb, sh: jnp.roll(kb, sh, axis=0))(
                k[:, -smax:].astype(cache["k"].dtype), shift)
            cv = jax.vmap(lambda vb, sh: jnp.roll(vb, sh, axis=0))(
                v[:, -smax:].astype(cache["v"].dtype), shift)
            new_kpos = jax.vmap(jnp.roll)(
                positions[:, -smax:].astype(jnp.int32), shift)
        elif spec and s > 1:
            # speculative verify on a stripe: scatter the s candidate rows
            # at each lane's own offsets (rows past the stripe end are
            # dropped by the scatter — they can only be over-reservation
            # rows the acceptance cap already rejects), then attend once
            # with causal masking.  Same bitwise-equivalence argument as
            # the paged spec write; windowed rings verify sequentially.
            if cfg.window:
                raise ValueError("multi-token spec write cannot wrap a "
                                 "windowed ring; use sequential verify")
            idx = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            bidx = jnp.arange(b)[:, None]
            ck = cache["k"].at[bidx, idx].set(k.astype(cache["k"].dtype))
            cv = cache["v"].at[bidx, idx].set(v.astype(cache["v"].dtype))
            new_kpos = cache["kpos"].at[bidx, idx].set(
                positions.astype(jnp.int32))
            out = _attn_chunked(q, ck, cv, positions, new_kpos, True,
                                cfg.window, kv_block)
        else:
            slot = jax.lax.rem(pos, smax) if cfg.window else pos
            ck = jax.vmap(
                lambda cb, kb, st: jax.lax.dynamic_update_slice(cb, kb, (st, 0, 0))
            )(cache["k"], k.astype(cache["k"].dtype), slot)
            cv = jax.vmap(
                lambda cb, vb, st: jax.lax.dynamic_update_slice(cb, vb, (st, 0, 0))
            )(cache["v"], v.astype(cache["v"].dtype), slot)
            new_kpos = jax.vmap(
                lambda kp, pr, st: jax.lax.dynamic_update_slice(kp, pr, (st,))
            )(cache["kpos"], positions.astype(jnp.int32), slot)
            from repro.perf_knobs import KNOBS

            out = None
            if s == 1 and KNOBS.seq_parallel_decode:
                out = _seq_parallel_decode_attn(q, ck, cv, positions, new_kpos,
                                                cfg.window)
            if out is None:
                out = _attn_chunked(q, ck, cv, positions, new_kpos, True,
                                    cfg.window, kv_block)
        new_cache = {"k": ck, "v": cv, "pos": pos + s, "kpos": new_kpos}
    out = out.reshape(b, s, h * hd)
    return nn.linear(params["wo"], out), new_cache


def attention_init(key, cfg, d_in: int | None = None):
    d = d_in or cfg.d_model
    ks = nn.split_keys(key, 4)
    return {
        "wq": nn.dense_init(ks[0], d, cfg.attn_out_dim, cfg.dtype, bias=cfg.qkv_bias),
        "wk": nn.dense_init(ks[1], d, cfg.kv_out_dim, cfg.dtype, bias=cfg.qkv_bias),
        "wv": nn.dense_init(ks[2], d, cfg.kv_out_dim, cfg.dtype, bias=cfg.qkv_bias),
        "wo": nn.dense_init(ks[3], cfg.attn_out_dim, cfg.d_model, cfg.dtype),
    }


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_init(key, cfg, d_ff: int | None = None):
    f = d_ff or cfg.d_ff
    ks = nn.split_keys(key, 3)
    if cfg.act == "swiglu":
        return {
            "wg": nn.dense_init(ks[0], cfg.d_model, f, cfg.dtype),
            "wu": nn.dense_init(ks[1], cfg.d_model, f, cfg.dtype),
            "wd": nn.dense_init(ks[2], f, cfg.d_model, cfg.dtype),
        }
    return {
        "wu": nn.dense_init(ks[0], cfg.d_model, f, cfg.dtype, bias=True),
        "wd": nn.dense_init(ks[1], f, cfg.d_model, cfg.dtype, bias=True),
    }


def mlp(params: dict, x: jax.Array, cfg) -> jax.Array:
    if cfg.act == "swiglu":
        gate = jax.nn.silu(nn.linear(params["wg"], x).astype(jnp.float32))
        up = nn.linear(params["wu"], x).astype(jnp.float32)
        return nn.linear(params["wd"], (gate * up).astype(x.dtype))
    h = jax.nn.gelu(nn.linear(params["wu"], x).astype(jnp.float32))
    return nn.linear(params["wd"], h.astype(x.dtype))


def norm_init(cfg, d: int | None = None):
    d = d or cfg.d_model
    return nn.rmsnorm_init(d, cfg.dtype) if cfg.norm == "rmsnorm" else nn.layernorm_init(d, cfg.dtype)


def norm(params, x, cfg):
    return nn.rmsnorm(params, x) if cfg.norm == "rmsnorm" else nn.layernorm(params, x)
