"""The main-path kernels compile for a TPU v5e at qwen2_0_5b's widths.

Nothing runs: each test lowers and compiles a kernel for a described (not
attached) `v5e:2x2` topology with the TPU compiler that ships with JAX, so
what Mosaic refuses (untileable blocks, unsupported reshapes or gathers,
VMEM overruns, kernels XLA cannot partition) fails here instead of on the
chip. The topology is described inside a fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.base import load_arch
from repro.core.types import PackedHiNM
from repro.kernels import ops
from repro.kernels.hinm_spmm import hinm_spmm
from repro.kernels.nm_select import nm_select
from repro.kernels.paged_attn import paged_decode_attn

CFG = load_arch("qwen2_0_5b")
D, FF = CFG.d_model, CFG.d_ff
ATTN, KV = CFG.n_heads * CFG.head_dim, CFG.n_kv_heads * CFG.head_dim
# (n_in, n_out) of every projection the packed model serves
PROJECTIONS = {"q": (D, ATTN), "kv": (D, KV), "o": (ATTN, D),
               "gate_up": (D, FF), "down": (FF, D)}
# the widest down projections of the repo's larger configs (qwen2.5-14b,
# grok-1): the resident activation block is largest there
WIDE = {"qwen2_5_14b_down": (13824, 5120), "grok_1_down": (32768, 6144)}
PAGE, N_BT, N_PAGES, SLOTS = 64, 9, 80, 8   # the smoke's served pool


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep these compiles out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _packed_shapes(n_in, n_out, sharding):
    h = CFG.hinm
    t, k = n_out // h.v, h.kept_columns(n_in)
    kn = k // h.m * h.n

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (s((t, h.v, kn), CFG.dtype), s((t, h.v, kn), jnp.int8),
            s((t, k), jnp.int32))


@pytest.mark.parametrize("batch", [8, 512], ids=["decode8", "prefill512"])
@pytest.mark.parametrize("proj", list(PROJECTIONS))
def test_hinm_spmm_compiles(one_chip, proj, batch):
    n_in, n_out = PROJECTIONS[proj]
    vals, nm_idx, vec_idx = _packed_shapes(n_in, n_out, one_chip)
    x_t = jax.ShapeDtypeStruct((n_in, batch), CFG.dtype, sharding=one_chip)
    _compile(lambda x, a, b, c: hinm_spmm(x, a, b, c, nn=CFG.hinm.n,
                                          mm=CFG.hinm.m),
             x_t, vals, nm_idx, vec_idx)


@pytest.mark.parametrize("proj", list(WIDE))
def test_hinm_spmm_compiles_wide(one_chip, proj):
    n_in, n_out = WIDE[proj]
    vals, nm_idx, vec_idx = _packed_shapes(n_in, n_out, one_chip)
    x_t = jax.ShapeDtypeStruct((n_in, 512), CFG.dtype, sharding=one_chip)
    _compile(lambda x, a, b, c: hinm_spmm(x, a, b, c, nn=CFG.hinm.n,
                                          mm=CFG.hinm.m),
             x_t, vals, nm_idx, vec_idx)


@pytest.mark.parametrize("s", [1, 4], ids=["decode", "verify4"])
def test_paged_decode_attn_compiles(one_chip, s):
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (N_PAGES, PAGE, CFG.n_kv_heads, CFG.head_dim)
    _compile(paged_decode_attn,
             sd((SLOTS, s, CFG.n_heads, CFG.head_dim), CFG.dtype),
             sd(pool, CFG.dtype), sd(pool, CFG.dtype),
             sd((N_PAGES, PAGE), jnp.int32), sd((SLOTS, N_BT), jnp.int32),
             sd((SLOTS, s), jnp.int32))


def test_nm_select_compiles(one_chip):
    w = jax.ShapeDtypeStruct((D, FF), CFG.dtype, sharding=one_chip)
    _compile(lambda a: nm_select(a, nn=CFG.hinm.n, mm=CFG.hinm.m), w)


def test_hinm_matmul_compiles_per_device_on_a_4_chip_mesh(topo, monkeypatch):
    """Under a 4-device mesh context `ops.hinm_matmul` runs the compiled
    kernel per device in a shard_map (Mosaic kernels are not
    auto-partitioned): the sharded serving path on four chips. The test
    steers `ops` onto its TPU branch; JAX's default backend here is CPU."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices), ("data",))
    rep = NamedSharding(mesh, P())
    n_in, n_out = PROJECTIONS["gate_up"]
    vals, nm_idx, vec_idx = _packed_shapes(n_in, n_out, rep)
    x = jax.ShapeDtypeStruct((SLOTS, n_in), CFG.dtype,
                             sharding=NamedSharding(mesh, P("data")))

    def fn(x, vals, nm_idx, vec_idx):
        w = PackedHiNM(vals, vec_idx, nm_idx, n_out, n_in, CFG.hinm)
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return ops.hinm_matmul(x, w)

    text = _compile(fn, x, vals, nm_idx, vec_idx)
    assert "all-gather" not in text  # rows stay on their own device
