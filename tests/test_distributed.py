"""Distribution layer: sharding-rule validity for every arch x mesh, and an
8-fake-device pjit execution in a subprocess (device count is locked at
first jax import, so the multi-device run must be out-of-process)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ARCH_IDS, load_arch
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import zoo
from repro.optim import make_optimizer
from repro.train import abstract as abst

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def meshes():
    # AbstractMesh: spec resolution without needing 512 real devices
    return [jax.sharding.AbstractMesh((16, 16), ("data", "model")),
            jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_divisible(arch, meshes):
    """Every resolved spec divides its dim — for all archs and both meshes."""
    cfg = load_arch(arch)
    pshape = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0), cfg))
    for mesh in meshes:
        for tree in (pshape, abst.abstract_packed(pshape, cfg)):
            specs = shd.param_specs(tree, mesh, cfg)
            flat_l = jax.tree.leaves(tree)
            flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
            assert len(flat_l) == len(flat_s)
            for leaf, spec in zip(flat_l, flat_s):
                for dim, ax in zip(leaf.shape, tuple(spec)):
                    if ax is None:
                        continue
                    n = 1
                    for a in (ax if isinstance(ax, tuple) else (ax,)):
                        n *= mesh.shape[a]
                    assert dim % n == 0, (arch, leaf.shape, tuple(spec))


@pytest.mark.parametrize("arch", ["qwen2_5_14b", "grok_1_314b"])
def test_opt_state_specs_match_shapes(arch, meshes):
    cfg = load_arch(arch)
    pshape = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0), cfg))
    opt = make_optimizer(cfg.optimizer)
    oshape = jax.eval_shape(opt.init, pshape)
    pspecs = shd.param_specs(pshape, meshes[0], cfg)
    ospecs = shd.opt_state_specs(oshape, pspecs)
    flat_o = jax.tree.leaves(oshape)
    flat_s = jax.tree.leaves(ospecs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_o) == len(flat_s)
    for leaf, spec in zip(flat_o, flat_s):
        assert len(tuple(spec)) in (0, leaf.ndim)


SUBPROCESS_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
from repro.configs.base import load_arch
from repro.models import zoo
from repro.optim import make_optimizer
from repro.train import steps as tsteps
from repro.data.pipeline import SyntheticLMData

cfg = load_arch("qwen2_0_5b").reduced(n_layers=2, d_model=64, n_heads=4,
                                      n_kv_heads=2, d_ff=128, vocab=256,
                                      head_dim=16)
with jax.set_mesh(mesh):
    params = zoo.init(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adamw")
    opt_state = opt.init(params)
    masks = jax.tree.map(lambda x: None, params)
    data = SyntheticLMData(cfg.vocab, 32, 8, seed=0)
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    pshape = jax.eval_shape(lambda: params)
    oshape = jax.eval_shape(lambda: opt_state)
    bshape = jax.eval_shape(lambda: batch)
    step_fn, _ = tsteps.make_train_step(cfg, mesh)
    jitted, in_specs, _ = tsteps.shard_train_step(step_fn, cfg, mesh, pshape, oshape,
                                                  masks, bshape, donate=False)
    from jax.sharding import NamedSharding, PartitionSpec as P
    named = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(params, named(in_specs[0]))
    opt_state = jax.device_put(opt_state, named(in_specs[1]))
    batch = jax.device_put(batch, named(in_specs[3]))
    losses = []
    for i in range(3):
        params, opt_state, metrics, _ = jitted(params, opt_state, masks, batch, i, None)
        losses.append(float(metrics["loss"]))
assert np.isfinite(losses).all(), losses
assert losses[2] < losses[0], losses
print("PJIT_OK", losses[0], losses[2])
"""


def test_pjit_train_step_executes_on_8_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "PJIT_OK" in out.stdout, out.stdout + out.stderr


def test_batch_and_cache_specs():
    mesh = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    cfg = load_arch("qwen2_5_14b")
    batch = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32),
             "odd": jax.ShapeDtypeStruct((3, 4), jnp.float32)}
    bs = shd.batch_specs(batch, mesh)
    assert tuple(bs["tokens"])[0] == ("pod", "data")
    assert tuple(bs["odd"]) == (None, None)

    cache = jax.eval_shape(lambda: zoo.make_cache(cfg, 128, 4096))
    cs = shd.cache_specs(cache, mesh, cfg)
    kspec = tuple(cs["k"])
    assert kspec[1] == ("pod", "data")
    assert "model" in (kspec[2], kspec[3])


def test_cache_specs_paged_layout():
    """Paged decode caches resolve on a data-only serving mesh: pool leaves
    shard their PAGE axis, block tables / counters their slot axis; a
    non-divisible page count degrades to replication; the xlstm recurrent
    tree (no attention leaves) resolves instead of crashing."""
    from repro.models import paging

    mesh = jax.sharding.AbstractMesh((4,), ("data",))
    cfg = load_arch("qwen2_0_5b").reduced(n_layers=2, d_model=64, n_heads=4,
                                          n_kv_heads=2, d_ff=128, vocab=128,
                                          head_dim=16)
    geom = paging.shard_geometry(10, 4)
    assert geom["n_pages"] % 4 == 0 and geom["n_pages"] >= 12
    cache = jax.eval_shape(lambda: zoo.make_cache(
        cfg, 4, 64, page=16, n_pages=geom["n_pages"]))
    cs = shd.cache_specs(cache, mesh, cfg)
    for pool in ("k", "v", "kpos"):   # (L, n_pages, page, ...)
        assert tuple(cs[pool])[1] == "data", pool
        assert tuple(cs[pool])[2] is None, pool  # never split inside a page
    for slot in ("bt", "alloc", "pos"):  # (L, B[, n_bt])
        assert tuple(cs[slot])[1] == "data", slot

    # page count not divisible by the mesh -> pool replicates, slots keep
    # their batch sharding (the rule engine never emits an invalid spec)
    odd = jax.eval_shape(lambda: zoo.make_cache(cfg, 4, 64, page=16, n_pages=13))
    co = shd.cache_specs(odd, mesh, cfg)
    assert tuple(co["k"])[1] is None
    assert tuple(co["bt"])[1] == "data"

    # pure-recurrent family: every leaf is state (batch over dp); this is
    # the in-process half of the xlstm stripe-fallback regression
    xcfg = load_arch("xlstm_125m").reduced()
    xcache = jax.eval_shape(lambda: zoo.make_cache(xcfg, 4, 32))
    xs = shd.cache_specs(xcache, mesh, xcfg)
    for spec in jax.tree.leaves(xs, is_leaf=lambda x: isinstance(x, P)):
        assert tuple(spec)[1] == "data"
