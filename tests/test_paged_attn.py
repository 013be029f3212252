"""Paged-attention decode kernel + packed-serving unit tests.

The kernel (`kernels/paged_attn`) resolves KV tiles straight through the
block table inside the Pallas grid; these tests pin it against the jnp
reference — `pool[bt]` gather + `layers._attn_chunked` — across the
serving geometries (GQA/MHA, windowed rings, sentinel pages, rollback-
swept rows, multi-token spec verify, bf16 pools), all under
``backend="interpret"`` so CPU CI executes the real kernel logic.
End-to-end token identity through the Scheduler lives in
`serve_conformance.py` (kernel on and off); this file covers the kernel
contract itself plus the packed-params hooks (`zoo.pack_params` /
`zoo.unpack_params`) and the serving-mode resolution knob.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import load_arch
from repro.core import packing
from repro.core.types import HiNMConfig, PackedHiNM
from repro.kernels import ops
from repro.kernels.paged_attn import pick_pp
from repro.models import layers, paging, zoo
from repro.models import module as nn

RNG = np.random.default_rng(0)


def _paged_case(b, s, kvh, g, hd, page, n_bt, n_pages, window, dtype,
                sweep=2, seed=0):
    """Build a randomly allocated paged pool + block tables.

    Every slot gets a random page allocation and a random live row count;
    `sweep` interior rows are reset to the kpos sentinel (exactly what a
    speculative rollback leaves behind), unallocated bt entries point at
    the sentinel page, and q sits at the slot's next `s` positions.
    """
    rng = np.random.default_rng(seed)
    h = kvh * g
    pool_shape = (n_pages, page, kvh, hd)
    kp = jnp.asarray(rng.normal(size=pool_shape), dtype)
    vp = jnp.asarray(rng.normal(size=pool_shape), dtype)
    kpos = np.full((n_pages, page), paging.KPOS_SENTINEL, np.int32)
    bt = np.full((b, n_bt), paging.SENTINEL_PAGE, np.int32)
    free = list(range(paging.N_RESERVED, n_pages))
    rng.shuffle(free)
    positions = []
    for bi in range(b):
        n_alloc = int(rng.integers(1, n_bt + 1))
        pages = [free.pop() for _ in range(n_alloc)]
        bt[bi, :n_alloc] = pages
        live = int(rng.integers(1, n_alloc * page + 1))
        for r in range(live):
            kpos[pages[r // page], r % page] = r
        for r in rng.choice(live, size=min(sweep, live), replace=False):
            if r != live - 1:  # keep the newest row: q attends to itself
                kpos[pages[r // page], r % page] = paging.KPOS_SENTINEL
        positions.append([live - 1 + i for i in range(s)])
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), dtype)
    return (q, kp, vp, jnp.asarray(kpos), jnp.asarray(bt),
            jnp.asarray(positions, jnp.int32))


def _gather_ref(q, kp, vp, kpos, bt, q_pos, window):
    k_view = paging.gather_view(kp, bt)
    v_view = paging.gather_view(vp, bt)
    p_view = paging.gather_view(kpos, bt)
    return layers._attn_chunked(q, k_view, v_view, q_pos, p_view,
                                True, window, 1024)


CASES = [
    # b  s kvh g  hd page n_bt n_pages window dtype        tol
    (3, 1, 2, 2, 32, 8, 4, 16, 0, jnp.float32, 5e-6),   # GQA decode
    (2, 1, 4, 1, 16, 4, 8, 40, 0, jnp.float32, 5e-6),   # MHA, many pages
    (3, 1, 2, 2, 32, 8, 4, 16, 16, jnp.float32, 5e-6),  # sliding window
    (2, 3, 2, 2, 32, 8, 4, 16, 0, jnp.float32, 5e-6),   # spec verify s=3
    (2, 4, 2, 2, 16, 16, 2, 8, 0, jnp.float32, 5e-6),   # s=4, page=16
    (3, 1, 2, 4, 64, 16, 4, 16, 0, jnp.bfloat16, 5e-2),  # bf16 pool
    (1, 1, 2, 2, 32, 8, 1, 4, 0, jnp.float32, 5e-6),    # single page
]


@pytest.mark.parametrize(
    "b,s,kvh,g,hd,page,n_bt,n_pages,window,dtype,tol", CASES)
def test_kernel_matches_gather(b, s, kvh, g, hd, page, n_bt, n_pages,
                               window, dtype, tol):
    q, kp, vp, kpos, bt, q_pos = _paged_case(
        b, s, kvh, g, hd, page, n_bt, n_pages, window, dtype)
    out = ops.paged_attention(q, kp, vp, kpos, bt, q_pos, window=window,
                              backend="interpret")
    ref = _gather_ref(q, kp, vp, kpos, bt, q_pos, window)
    assert out.dtype == q.dtype
    err = float(jnp.abs(out.astype(jnp.float32)
                        - ref.astype(jnp.float32)).max())
    assert err < tol, err


def test_kernel_sentinel_heavy():
    """A slot whose allocation is almost entirely sentinel/swept rows:
    only the newest row survives, so attention must reduce to exactly
    that row's V — every other lane masks through the kpos sentinel."""
    q, kp, vp, kpos, bt, q_pos = _paged_case(
        2, 1, 2, 2, 32, 8, 4, 16, 0, jnp.float32, seed=3)
    kpos_np = np.asarray(kpos).copy()
    bt_np = np.asarray(bt)
    for bi in range(2):
        newest = int(q_pos[bi, 0])
        for r in range(newest):
            pg = bt_np[bi, r // 8]
            kpos_np[pg, r % 8] = paging.KPOS_SENTINEL
    kpos = jnp.asarray(kpos_np)
    out = ops.paged_attention(q, kp, vp, kpos, bt, q_pos, backend="interpret")
    ref = _gather_ref(q, kp, vp, kpos, bt, q_pos, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)
    for bi in range(2):
        newest = int(q_pos[bi, 0])
        pg, off = bt_np[bi, newest // 8], newest % 8
        want = np.asarray(vp)[pg, off]                      # (KV, hd)
        got = np.asarray(out)[bi, 0].reshape(2, 2, 32)      # (KV, G, hd)
        np.testing.assert_allclose(got, np.broadcast_to(want[:, None],
                                                        got.shape), atol=5e-6)


def test_kernel_backend_dispatch():
    q, kp, vp, kpos, bt, q_pos = _paged_case(
        2, 1, 2, 2, 32, 8, 4, 16, 0, jnp.float32)
    # gather/off defer to the jnp path by returning None
    assert ops.paged_attention(q, kp, vp, kpos, bt, q_pos,
                               backend="off") is None
    assert ops.paged_attention(q, kp, vp, kpos, bt, q_pos,
                               backend="gather") is None
    # auto off-TPU defers too (CPU CI)
    if jax.devices()[0].platform != "tpu":
        assert ops.paged_attention(q, kp, vp, kpos, bt, q_pos,
                                   backend="auto") is None
    with pytest.raises(ValueError, match="paged-attention backend"):
        ops.paged_attention(q, kp, vp, kpos, bt, q_pos, backend="nope")
    # the compiled kernel, forced off-TPU, is an error — never the
    # interpreter in disguise ("interpret" asks for that by name)
    if jax.devices()[0].platform != "tpu":
        for forced in ("pallas", "on"):
            with pytest.raises(ops.TPUUnavailableError):
                ops.paged_attention(q, kp, vp, kpos, bt, q_pos,
                                    backend=forced)


# ---------------------------------------------------------------------------
# VMEM tile picking
# ---------------------------------------------------------------------------


def test_pick_tile():
    # fits whole -> whole; halves until under budget; divisibility holds
    assert ops.pick_tile(8, 0, 100, budget=1000) == 8
    assert ops.pick_tile(8, 0, 300, budget=1000) == 2
    assert ops.pick_tile(12, 0, 100, budget=500, divide=True) == 3
    # fixed cost alone over budget -> floor (never 0)
    assert ops.pick_tile(8, 2000, 100, budget=1000) == 1
    assert ops.pick_tile(8, 2000, 100, budget=1000, floor=4) == 4
    # start caps the initial tile
    assert ops.pick_tile(64, 0, 1, budget=1 << 30, start=8) == 8


def test_pick_pp_within_budget():
    from repro.kernels.paged_attn import _vmem_bytes

    for n_bt, page, hd, gs, kvh in [(4, 16, 32, 8, 2), (32, 64, 128, 16, 1),
                                    (128, 256, 128, 8, 2), (9, 64, 64, 7, 2)]:
        pp = pick_pp(n_bt, page, hd, gs, 2, kvh)
        assert 1 <= pp <= min(8, n_bt) and n_bt % pp == 0
        per_page = (2 * 2 * page * _vmem_bytes(kvh, hd, 2)
                    + 2 * _vmem_bytes(1, page, 4) + 2 * _vmem_bytes(page, hd, 4)
                    + 3 * _vmem_bytes(gs, page, 4))
        fixed = kvh * (3 * _vmem_bytes(gs, hd, 4) + 2 * _vmem_bytes(gs, 128, 4))
        assert pp == 1 or fixed + per_page * pp <= ops.VMEM_BUDGET_BYTES
    # bf16 (2, 64) rows pad to a (16, 128) tile: 16x the logical bytes
    assert _vmem_bytes(2, 64, 2) == 16 * 128 * 2
    assert _vmem_bytes(8, 128, 4) == 8 * 128 * 4


# ---------------------------------------------------------------------------
# paged-branch write contract (layers.attention)
# ---------------------------------------------------------------------------


def _mini_attn_setup(window=0):
    cfg = load_arch("qwen2_0_5b").reduced(
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
        vocab=64, head_dim=16, window=window)
    ks = nn.split_keys(jax.random.PRNGKey(0), 4)
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    params = {"wq": nn.dense_init(ks[0], d, h * hd, cfg.dtype),
              "wk": nn.dense_init(ks[1], d, cfg.n_kv_heads * hd, cfg.dtype),
              "wv": nn.dense_init(ks[2], d, cfg.n_kv_heads * hd, cfg.dtype),
              "wo": nn.dense_init(ks[3], h * hd, d, cfg.dtype)}
    page, n_pages = 4, 8
    cache = {
        "k": jnp.zeros((n_pages, page, cfg.n_kv_heads, hd), cfg.dtype),
        "v": jnp.zeros((n_pages, page, cfg.n_kv_heads, hd), cfg.dtype),
        "kpos": jnp.full((n_pages, page), paging.KPOS_SENTINEL, jnp.int32),
        "bt": jnp.asarray([[2, 3]], jnp.int32),
        "alloc": jnp.asarray([2], jnp.int32),
        "pos": jnp.asarray([1], jnp.int32),
    }
    return cfg, params, cache


def test_paged_multitoken_requires_spec():
    """s > 1 against a paged cache is only legal on the speculative-verify
    branch (zoo.verify_step passes spec=True); the error must say where
    multi-token writes actually go, so the message is pinned here."""
    cfg, params, cache = _mini_attn_setup()
    x = jnp.zeros((1, 2, cfg.d_model), cfg.dtype)
    positions = jnp.asarray([[1, 2]], jnp.int32)
    with pytest.raises(ValueError, match=r"speculative verify[\s\S]*"
                                         r"zoo\.verify_step passes spec=True"):
        layers.attention(params, x, positions, cfg, cache=cache)
    # the same call IS legal as a spec-verify write
    out, new_cache = layers.attention(params, x, positions, cfg,
                                      cache=cache, spec=True)
    assert out.shape == (1, 2, cfg.d_model)
    assert int(new_cache["pos"][0]) == 3


def test_paged_spec_write_rejects_windowed_ring():
    cfg, params, cache = _mini_attn_setup(window=8)
    x = jnp.zeros((1, 2, cfg.d_model), cfg.dtype)
    positions = jnp.asarray([[1, 2]], jnp.int32)
    with pytest.raises(ValueError, match="windowed ring"):
        layers.attention(params, x, positions, cfg, cache=cache, spec=True)


# ---------------------------------------------------------------------------
# packed-serving params hooks
# ---------------------------------------------------------------------------


def _packed_model():
    from repro.train import pruning

    cfg = load_arch("qwen2_0_5b").reduced(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab=256, head_dim=32)
    params = zoo.init(jax.random.PRNGKey(0), cfg)
    _, _, packed, _ = pruning.prune_model(params, cfg, ocp_iters=1,
                                          icp_iters=1)
    return cfg, params, packed


def _packed_leaves(tree):
    return [l for l in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, PackedHiNM))
        if isinstance(l, PackedHiNM)]


def test_pack_unpack_params_hooks():
    cfg, dense, packed = _packed_model()
    n0 = len(_packed_leaves(packed))
    assert n0 > 0

    # pack_params on dense params packs every planned projection
    pk = zoo.pack_params(cfg, dense)
    assert len(_packed_leaves(pk)) == n0
    # already-packed leaves pass through untouched (same objects)
    pk2 = zoo.pack_params(cfg, packed)
    assert all(a is b for a, b in zip(jax.tree.leaves(pk2),
                                      jax.tree.leaves(packed)))
    # unpack_params restores dense leaves everywhere
    up = zoo.unpack_params(cfg, packed)
    assert len(_packed_leaves(up)) == 0

    # the dense fallback is numerically exact: masked-dense matmul ==
    # packed matmul on the same weight (this is the property the serving
    # fallback knob relies on — NOT roundtrip re-packing, which regroups
    # an ICP-permuted packing's columns and is lossy by construction)
    p0 = jax.tree.map(lambda a: a[0], _packed_leaves(packed)[0])
    n_in = int(packing.unpack(p0).shape[1])
    x = jnp.asarray(np.random.default_rng(1).normal(size=(3, n_in)),
                    cfg.dtype)
    y_p = nn.linear({"w": p0}, x)
    y_d = nn.linear({"w": packing.unpack(p0).T}, x)
    np.testing.assert_allclose(np.asarray(y_p, np.float32),
                               np.asarray(y_d, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_unpermuted_pack_roundtrip_stable():
    """Packing a masked-dense weight whose sparsity already matches the
    default ascending-column grouping is idempotent — the guarantee the
    pack_params docstring states (a gyro/ICP-permuted packing does NOT
    roundtrip: re-packing regroups its columns)."""
    w = jnp.asarray(RNG.normal(size=(16, 64)), jnp.float32)
    h = HiNMConfig(v=8, n=2, m=4, vector_sparsity=0.5)
    wm = packing.unpack(packing.pack(w, h))
    again = packing.unpack(packing.pack(wm, h))
    np.testing.assert_array_equal(np.asarray(again), np.asarray(wm))


def test_resolve_packed_mode(monkeypatch):
    from repro.serve.scheduler import resolve_packed_mode

    monkeypatch.delenv("REPRO_SERVE_PACKED", raising=False)
    assert resolve_packed_mode("auto") == "auto"
    assert resolve_packed_mode(True) == "pack"
    assert resolve_packed_mode(False) == "dense"
    assert resolve_packed_mode("dense") == "dense"
    with pytest.raises(ValueError, match="REPRO_SERVE_PACKED|packed"):
        resolve_packed_mode("bogus")
    # the env var overrides whatever the constructor was given
    monkeypatch.setenv("REPRO_SERVE_PACKED", "1")
    assert resolve_packed_mode("dense") == "pack"
    monkeypatch.setenv("REPRO_SERVE_PACKED", "0")
    assert resolve_packed_mode(True) == "dense"
    monkeypatch.setenv("REPRO_SERVE_PACKED", "junk")
    with pytest.raises(ValueError):
        resolve_packed_mode("auto")
