"""Dry-run cell construction: (arch config, shape, mesh) -> lowered step.

A "cell" is one (architecture x input-shape) point of the assignment
matrix. Kinds:
  train    -> masked-dense HiNM train step (params + opt state + masks)
  prefill  -> serving prefill over packed HiNM weights (fills the cache)
  decode   -> serving decode step over packed HiNM weights (one token)

Everything is abstract (ShapeDtypeStruct): no arrays are allocated.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ArchConfig
from repro.data.pipeline import make_batch_specs
from repro.distributed import sharding as shd
from repro.models import zoo
from repro.optim import make_optimizer
from repro.train import abstract as abst
from repro.train import steps as tsteps


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    jitted: Any
    args: tuple
    skipped: str = ""


def shape_applicable(cfg: ArchConfig, shape_name: str) -> str:
    """'' if the cell runs; otherwise the documented skip reason."""
    seq, batch, kind = SHAPES[shape_name]
    if shape_name in cfg.skip_shapes:
        return "excluded by config"
    if shape_name == "long_500k" and not cfg.sub_quadratic():
        return "full quadratic attention at 524k seq is out of scope (DESIGN.md §6)"
    return ""


def _named(tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if s is not None else None,
        tree,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )


def pick_microbatches(cfg: ArchConfig, seq: int, batch: int, mesh,
                      budget_bytes: float = 4e9) -> int:
    """Grad-accumulation factor so the remat'd layer-input activation stack
    (L x B_loc x S x D x 2B) stays under ~4 GB/device. M must divide the
    per-device batch so every microbatch still shards evenly."""
    from repro.models import probe_mode

    if probe_mode.enabled():
        return 1  # cost probes: no accumulation loop
    dp = 1
    for a in shd.batch_axes(mesh):
        dp *= mesh.shape[a]
    b_loc = max(1, batch // dp)
    stack = cfg.n_layers * b_loc * seq * cfg.d_model * 2
    m = 1
    while stack / m > budget_bytes and m < b_loc and b_loc % (m * 2) == 0:
        m *= 2
    return m


def build_train_cell(cfg: ArchConfig, shape_name: str, mesh,
                     shape_override: tuple[int, int] | None = None) -> Cell:
    seq, batch, _ = SHAPES[shape_name]
    if shape_override:
        seq, batch = shape_override
    params_shape = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0), cfg))
    opt = make_optimizer(cfg.optimizer)
    opt_shape = jax.eval_shape(opt.init, params_shape)
    masks_shape = abst.abstract_masks(params_shape, cfg)
    batch_shape = make_batch_specs(
        seq, batch, cfg.vocab, cfg.frontend, cfg.d_model, cfg.frontend_tokens
    )
    mb = pick_microbatches(cfg, seq, batch, mesh)
    step_fn, _ = tsteps.make_train_step(
        cfg, mesh, optimizer_name=cfg.optimizer, microbatches=mb
    )
    jitted, _, _ = tsteps.shard_train_step(
        step_fn, cfg, mesh, params_shape, opt_shape, masks_shape, batch_shape
    )
    args = (params_shape, opt_shape, masks_shape, batch_shape,
            jax.ShapeDtypeStruct((), jnp.int32), None)
    return Cell(cfg.name, shape_name, "train", jitted, args)


def _serve_shapes(cfg: ArchConfig, shape_name: str,
                  shape_override: tuple[int, int] | None = None):
    seq, batch, kind = SHAPES[shape_name]
    if shape_override:
        seq, batch = shape_override
    params_shape = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0), cfg))
    packed_shape = abst.abstract_packed(params_shape, cfg)
    kw = {}
    if cfg.family == "encdec":
        kw["t_enc"] = seq
        cache_seq = max(seq // 4, 8)
    else:
        cache_seq = seq
    if cfg.family in ("hybrid",):
        cache_seq = seq  # window-bounded internally
    cache_shape = jax.eval_shape(
        lambda: zoo.make_cache(cfg, batch, cache_seq, **kw)
    )
    return params_shape, packed_shape, cache_shape, seq, batch


def build_decode_cell(cfg: ArchConfig, shape_name: str, mesh,
                      shape_override: tuple[int, int] | None = None) -> Cell:
    packed = _serve_shapes(cfg, shape_name, shape_override)
    _, packed_shape, cache_shape, seq, batch = packed

    def decode_fn(params, tokens, cache):
        return zoo.decode_step(params, cfg, tokens, cache)

    jitted, _, _ = tsteps.shard_serve_step(
        decode_fn, cfg, mesh, packed_shape, cache_shape, batch
    )
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    return Cell(cfg.name, shape_name, "decode", jitted,
                (packed_shape, tokens, cache_shape))


def build_prefill_cell(cfg: ArchConfig, shape_name: str, mesh,
                       shape_override: tuple[int, int] | None = None) -> Cell:
    _, packed_shape, cache_shape, seq, batch = _serve_shapes(
        cfg, shape_name, shape_override)
    pspecs = shd.param_specs(packed_shape, mesh, cfg)
    cspecs = shd.cache_specs(cache_shape, mesh, cfg)

    if cfg.family == "encdec":
        tok_len = seq // 4
        embeds = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.bfloat16)
    elif cfg.frontend == "patch":
        tok_len = seq - cfg.frontend_tokens
        embeds = jax.ShapeDtypeStruct((batch, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    else:
        tok_len = seq
        embeds = None
    tokens = jax.ShapeDtypeStruct((batch, tok_len), jnp.int32)

    def prefill_fn(params, tokens, cache, embeds=None):
        last, new_cache = zoo.prefill(params, cfg, tokens, cache, embeds=embeds)
        return zoo.logits_fn(params, cfg, last), new_cache

    bspec = shd.batch_specs({"t": tokens}, mesh)["t"]
    in_shardings = [_named(pspecs, mesh), _named(bspec, mesh), _named(cspecs, mesh)]
    args = [packed_shape, tokens, cache_shape]
    if embeds is not None:
        espec = shd.batch_specs({"e": embeds}, mesh)["e"]
        in_shardings.append(_named(espec, mesh))
        args.append(embeds)
    logits_spec = P(tuple(bspec)[0], "model")
    jitted = jax.jit(
        prefill_fn,
        in_shardings=tuple(in_shardings),
        out_shardings=(_named(logits_spec, mesh), _named(cspecs, mesh)),
        donate_argnums=(2,),
    )
    return Cell(cfg.name, shape_name, "prefill", jitted, tuple(args))


def build_cell(cfg: ArchConfig, shape_name: str, mesh,
               shape_override: tuple[int, int] | None = None) -> Cell:
    skip = shape_applicable(cfg, shape_name)
    if skip:
        return Cell(cfg.name, shape_name, SHAPES[shape_name][2], None, (), skipped=skip)
    kind = SHAPES[shape_name][2]
    with jax.set_mesh(mesh):
        if kind == "train":
            return build_train_cell(cfg, shape_name, mesh, shape_override)
        if kind == "prefill":
            return build_prefill_cell(cfg, shape_name, mesh, shape_override)
        return build_decode_cell(cfg, shape_name, mesh, shape_override)


def lower_cell(cell: Cell, mesh):
    with jax.set_mesh(mesh):
        return cell.jitted.lower(*cell.args)
