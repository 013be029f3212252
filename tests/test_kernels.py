"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing
from repro.core.types import HiNMConfig
from repro.kernels import ops, ref
from repro.kernels import hinm_spmm as spmm_mod
from repro.kernels.hinm_spmm import hinm_spmm, pick_bblk
from repro.kernels.nm_select import nm_select


def make_packed(rng, n_out, n_in, v=8, sv=0.5, dtype=jnp.float32):
    cfg = HiNMConfig(v=v, n=2, m=4, vector_sparsity=sv)
    w = jnp.asarray(rng.normal(size=(n_out, n_in)).astype(np.float32)).astype(dtype)
    return w, packing.pack(w, cfg)


SHAPES = [
    # (n_out, n_in, batch, V)
    (16, 16, 4, 8),
    (64, 48, 10, 8),
    (32, 64, 33, 16),   # batch not divisible by block
    (128, 96, 7, 32),
    (64, 128, 129, 8),
    (32, 2304, 4, 32),  # Kn=576: two KN_CHUNK row chunks, the second padded
]


@pytest.mark.parametrize("n_out,n_in,b,v", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hinm_spmm_sweep(rng, n_out, n_in, b, v, dtype):
    w, p = make_packed(rng, n_out, n_in, v=v, dtype=dtype)
    x = jnp.asarray(rng.normal(size=(b, n_in)).astype(np.float32)).astype(dtype)
    y_ref = ref.hinm_spmm_oracle(x.astype(jnp.float32), packing.pack(w.astype(jnp.float32), p.config))
    y_ker = hinm_spmm(
        x.T, p.vals, p.nm_idx, p.vec_idx, nn=2, mm=4, interpret=True,
        out_dtype=jnp.float32,
    ).T
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(y_ker), np.asarray(y_ref), rtol=tol, atol=tol * 10
    )


@pytest.mark.parametrize("sv", [0.25, 0.5, 0.75])
def test_hinm_spmm_sparsity_levels(rng, sv):
    w, p = make_packed(rng, 32, 32, v=8, sv=sv)
    x = jnp.asarray(rng.normal(size=(5, 32)).astype(np.float32))
    y_ref = ref.hinm_spmm_oracle(x, p)
    y_ker = ops.hinm_matmul(x, p, backend="interpret")
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref), rtol=1e-5, atol=1e-5)


def test_hinm_spmm_xla_paths_agree(rng):
    """Small-batch gather path == large-batch tile-chunked path == oracle."""
    w, p = make_packed(rng, 32, 48, v=8)
    for b in (8, 2048):
        x = jnp.asarray(rng.normal(size=(b, 48)).astype(np.float32))
        y0 = ref.hinm_spmm_oracle(x, p)
        y1 = ref.hinm_spmm_xla(x, p)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=2e-5, atol=2e-5)


def test_hinm_matmul_leading_dims(rng):
    w, p = make_packed(rng, 16, 16, v=8)
    x = jnp.asarray(rng.normal(size=(2, 3, 16)).astype(np.float32))
    y = ops.hinm_matmul(x, p, backend="interpret")
    assert y.shape == (2, 3, 16)
    y2 = ops.hinm_matmul(x.reshape(6, 16), p, backend="xla").reshape(2, 3, 16)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(8, 16), (32, 64), (7, 12), (128, 512)])
@pytest.mark.parametrize("nn,mm", [(2, 4), (1, 4), (1, 2)])
def test_nm_select_sweep(rng, shape, nn, mm):
    if shape[1] % mm:
        pytest.skip("cols not divisible by M")
    w = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    out_ref = ref.nm_select_ref(w, nn, mm)
    out_ker = nm_select(w, nn=nn, mm=mm, interpret=True)
    assert np.array_equal(np.asarray(out_ker), np.asarray(out_ref))


def test_nm_select_ties_deterministic():
    w = jnp.asarray([[1.0, 1.0, 1.0, 1.0]])
    out = nm_select(w, interpret=True)
    assert np.array_equal(np.asarray(out), [[1.0, 1.0, 0.0, 0.0]])


def test_pick_bblk_respects_budget():
    # a decode-sized batch is one block over the whole lane axis
    assert pick_bblk(896, 448, 8) == 8
    assert pick_bblk(128, 64, 4) == 4
    # larger batches take 128-lane multiples; the full working set (f32
    # activation block double-buffered, f32 slot planes of one row chunk,
    # vals + int8 slots double-buffered plus one masked copy, f32
    # accumulator + output) stays under the budget the block is sized
    # against where 256 lanes fit, and under the VMEM cap at any width
    v, nn, mm, it = 32, 2, 4, 2
    for n_in, k, cap in ((4864, 2432, spmm_mod.VMEM_BUDGET_BYTES),
                         (32768, 16384, spmm_mod.VMEM_CAP_BYTES)):
        b = pick_bblk(n_in, k, 1024)
        assert b % 128 == 0
        kc = min(k // mm * nn, spmm_mod.KN_CHUNK)
        ws = (2 * n_in * b * 4 + mm * kc * b * 4 + 3 * v * kc * (it + 1)
              + v * b * (4 + 2 * it))
        assert ws == spmm_mod.vmem_bytes(n_in, k, b)
        assert ws <= cap
    assert spmm_mod.VMEM_CAP_BYTES < 128 * 1024 * 1024  # v5e VMEM per core


def test_pick_bblk_pinned_representative_shapes():
    """Pin the chosen batch block for representative (n_in, k, B, itemsize)
    shapes so VMEM-formula regressions are caught, not silently absorbed.
    The wide layers (grok-1's 32768 down projection, qwen2.5-14b's 13824)
    take the 128-lane floor, whose working set stays under the VMEM cap
    because the kernel cuts Kn into KN_CHUNK-row chunks."""
    assert pick_bblk(32768, 16384, 1024, 2) == 128
    assert pick_bblk(13824, 5120, 2048, 2) == 128
    assert pick_bblk(5120, 2560, 1024, 2) == 128
    assert pick_bblk(896, 448, 512, 2) == 256
    assert pick_bblk(4864, 2432, 1024, 2) == 128
    assert pick_bblk(5120, 2560, 1024, 4) == 128
    assert pick_bblk(1024, 512, 256, 4) == 256
    assert pick_bblk(896, 448, 129, 2) == 256
    assert pick_bblk(128, 64, 4, 2) == 4


@pytest.mark.parametrize("n_in,b", [(200_000, 1024), (400_000, 64)])
def test_pick_bblk_too_wide_raises(n_in, b):
    """A layer whose smallest batch block cannot sit in VMEM is refused by
    name rather than handed to the compiler."""
    with pytest.raises(spmm_mod.VMEMBudgetError, match="VMEM"):
        pick_bblk(n_in, n_in // 2, b)


@pytest.mark.parametrize("kernel", ["hinm_matmul", "nm_apply"])
def test_forced_pallas_without_tpu_raises(rng, monkeypatch, kernel):
    """"pallas" means the compiled kernel: where JAX's default backend is
    not a TPU it raises a named error instead of running the interpreter
    behind the caller's back (only "interpret" asks for that; the paged-
    attention case is in test_paged_attn.test_kernel_backend_dispatch)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    with pytest.raises(ops.TPUUnavailableError, match="interpret"):
        if kernel == "hinm_matmul":
            _, p = make_packed(rng, 16, 16, v=8)
            ops.hinm_matmul(jnp.ones((4, 16)), p, backend="pallas")
        else:
            ops.nm_apply(jnp.ones((8, 16)), backend="pallas")


def test_decompress_tiles_matches_unpack(rng):
    w, p = make_packed(rng, 16, 16, v=8)
    tiles = ref.decompress_tiles(p.vals, p.nm_idx, p.config.m, p.config.n)
    dense = ref.scatter_dense(p)
    t, v_, k = tiles.shape
    gathered = jnp.take_along_axis(
        dense.reshape(t, v_, -1), p.vec_idx[:, None, :], axis=2
    )
    np.testing.assert_allclose(np.asarray(tiles), np.asarray(gathered), rtol=1e-6)
