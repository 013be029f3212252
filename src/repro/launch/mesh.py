"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state. The single-pod mesh is 16x16 = 256 chips
("data", "model"); the multi-pod mesh prepends a "pod" axis (2 pods = 512
chips). Data parallelism runs over ("pod", "data") — the pod axis carries
only the gradient all-reduce (DCN-friendly), while FSDP parameter sharding
stays inside a pod on "data" (ICI).
"""
from __future__ import annotations

import jax



def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axes: sharding is propagated by XLA and
    pinned by ``with_sharding_constraint`` (JAX's own default is Explicit)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = max(1, n // model)
    return make_mesh((data, model), ("data", "model"))
