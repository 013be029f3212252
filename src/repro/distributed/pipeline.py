"""Pipeline parallelism over the 'pod' axis (DESIGN.md §3, optional).

A GPipe-style microbatch pipeline built on shard_map + ppermute: layer
stages are sharded over the pipeline axis and microbatches stream through
a single pipe register. Per step the schedule runs
(n_micro + n_stages - 1) ticks; at tick t stage s applies its layers to
microbatch (t - s), then the register rotates one stage forward. The last
stage banks finished microbatches; a psum replicates the banked output.

The production dry-run keeps pod=DP (the realistic choice at 2 pods); this
executor exists for deeper pods / DCN-bound regimes and is exercised at
toy scale by tests/test_pipeline.py. Forward-only (serving/eval); training
needs the 1F1B reverse schedule — noted as future work.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipeline_apply(
    stage_fn: Callable,        # (stage_params, x_mb) -> x_mb
    stage_params,              # pytree stacked over a leading stage axis
    x: jax.Array,              # (n_micro, mb, ...) microbatched input
    mesh,
    axis: str = "pod",
) -> jax.Array:
    """Stream microbatches through all pipeline stages. Returns outputs in
    microbatch order, replicated over `axis`."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]

    def body(params_local, x_all):
        params_local = jax.tree.map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(axis)
        ticks = n_micro + n_stages - 1
        reg = jnp.zeros_like(x_all[0])
        outbuf = jnp.zeros_like(x_all)

        def tick(t, carry):
            reg, outbuf = carry
            mb_id = t - s
            active = (mb_id >= 0) & (mb_id < n_micro)
            feed = x_all[jnp.clip(t, 0, n_micro - 1)]
            inp = jnp.where(s == 0, feed, reg)
            out = stage_fn(params_local, inp)
            out = jnp.where(active, out, reg)
            # last stage banks the microbatch it just finished
            fin_id = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            bank = jnp.where((s == n_stages - 1) & active, out, 0.0)
            outbuf = outbuf.at[fin_id].add(bank)
            reg = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return reg, outbuf

        _, outbuf = jax.lax.fori_loop(0, ticks, tick, (reg, outbuf))
        # only the last stage banked anything; psum replicates the result
        return jax.lax.psum(outbuf, axis)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)
