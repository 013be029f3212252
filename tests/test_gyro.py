"""Gyro-permutation behaviour: bijectivity, monotone retention, ablations."""
import numpy as np
import pytest

from repro.core import baselines
from repro.core.gyro import gyro_permute, icp, ocp
from repro.core.types import HiNMConfig

CFG = HiNMConfig(v=8, n=2, m=4, vector_sparsity=0.5)


def structured_sal(rng, m=32, n=32):
    """Saliency with planted row/column structure (gyro has signal to find)."""
    row_scale = np.exp(rng.normal(size=(m, 1)))
    col_scale = np.exp(rng.normal(size=(1, n)))
    return (np.abs(rng.normal(size=(m, n))) * row_scale * col_scale).astype(np.float32)


def test_ocp_returns_bijection(rng):
    sal = structured_sal(rng)
    perm, hist = ocp(sal, CFG, iters=6, rng=rng)
    assert sorted(perm.tolist()) == list(range(32))
    assert all(b >= a - 1e-6 for a, b in zip(hist, hist[1:]))  # monotone


def test_icp_tile_bijection_and_improvement(rng):
    tile = structured_sal(rng, 8, 16)
    orders, hist = icp(tile[None], CFG, iters=8)    # one tile
    assert sorted(orders[0].tolist()) == list(range(16))
    assert hist[-1, 0] >= hist[0, 0] - 1e-6


def test_gyro_beats_noperm(rng):
    sal = structured_sal(rng, 32, 32)
    base = gyro_permute(sal, CFG, rng=np.random.default_rng(1),
                        run_ocp=False, run_icp=False)
    full = gyro_permute(sal, CFG, ocp_iters=10, icp_iters=10,
                        rng=np.random.default_rng(1))
    assert full.retained >= base.retained
    assert full.retained_fraction <= 1.0


def test_gyro_components_additive(rng):
    """OCP-only and ICP-only each at least match noperm; both together at
    least match each alone (on structured saliency)."""
    sal = structured_sal(rng, 32, 32)
    r = {}
    for name, kw in [
        ("noperm", dict(run_ocp=False, run_icp=False)),
        ("icp", dict(run_ocp=False)),
        ("ocp", dict(run_icp=False)),
        ("gyro", dict()),
    ]:
        r[name] = gyro_permute(sal, CFG, ocp_iters=8, icp_iters=8,
                               rng=np.random.default_rng(2), **kw).retained
    assert r["icp"] >= r["noperm"] - 1e-5
    assert r["ocp"] >= r["noperm"] - 1e-5
    assert r["gyro"] >= max(r["icp"], r["ocp"]) - 1e-3


def test_ablation_variants_run(rng):
    sal = structured_sal(rng, 16, 16)
    v1 = baselines.hinm_v1(sal, CFG, np.random.default_rng(0))
    v2 = baselines.hinm_v2(sal, CFG, np.random.default_rng(0), ocp_iters=4)
    gy = gyro_permute(sal, CFG, ocp_iters=8, icp_iters=8,
                      rng=np.random.default_rng(0))
    for res in (v1, v2, gy):
        assert sorted(res.out_perm.tolist()) == list(range(16))
        assert 0 < res.retained <= res.total
    # the paper's central ablation claim, on structured data
    assert gy.retained >= v1.retained - 1e-3


def test_col_order_is_valid_vec_idx(rng):
    sal = structured_sal(rng, 16, 16)
    res = gyro_permute(sal, CFG, ocp_iters=4, icp_iters=4, rng=rng)
    k = CFG.kept_columns(16)
    assert res.col_order.shape == (2, k)
    for row in res.col_order:
        assert len(set(row.tolist())) == k  # no duplicate columns per tile


def test_unstructured_upper_bounds_hinm(rng):
    sal = structured_sal(rng, 32, 32)
    gy = gyro_permute(sal, CFG, ocp_iters=8, icp_iters=8, rng=rng)
    unst = baselines.unstructured_retained(sal, CFG.total_sparsity)
    assert gy.retained_fraction <= unst + 1e-6


@pytest.mark.parametrize("cost_mode", ["hinm", "vector"])
def test_tile_cost_equals_masked_sum(rng, cost_mode):
    """The search's scatter-free tile cost is the retained saliency the
    pattern's own mask defines: sum(tile * mask(tile))."""
    import jax
    import jax.numpy as jnp

    from repro.core import gyro, sparsity

    tiles = jnp.asarray(structured_sal(rng, m=5 * CFG.v, n=64)
                        .reshape(5, CFG.v, 64))
    mask = sparsity.hinm_mask if cost_mode == "hinm" else sparsity.vector_mask
    want = jax.vmap(lambda t: jnp.sum(t * mask(t, CFG)))(tiles)
    got = gyro._retained(tiles, CFG, cost_mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
