"""Public jit'd wrappers around the Pallas kernels, with CPU routing.

`backend="auto"` uses the compiled Pallas kernel on TPU and the XLA
fast-path formulation elsewhere (same dataflow, so CPU tests and dry-run
HLO remain representative). `backend="pallas"` means the compiled kernel
and nothing else: asked for where JAX's default backend is not a TPU it
raises `TPUUnavailableError` instead of running the interpreter.
`backend="interpret"` runs the Pallas kernel under the interpreter — the
correctness-validation path, asked for by name only by tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import PackedHiNM
from repro.kernels import hinm_spmm as _spmm
from repro.kernels import nm_select as _nmsel
from repro.kernels import ref as _ref


class TPUUnavailableError(RuntimeError):
    """The compiled Pallas backend was asked for where JAX's default
    backend is not a TPU."""


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _require_tpu(what: str) -> None:
    if not _on_tpu():
        raise TPUUnavailableError(
            f"{what}: backend 'pallas' compiles for a TPU, but JAX's default "
            f"backend is {jax.default_backend()!r}; ask for 'interpret' to "
            f"run the kernel under the Pallas interpreter")


# conservative half of v5e VMEM — shared by every kernel's tile picker
VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def pick_tile(n_units: int, fixed_bytes: int, per_unit_bytes: int, *,
              budget: int = VMEM_BUDGET_BYTES, start: int | None = None,
              floor: int = 1, divide: bool = True) -> int:
    """Largest tile with ``fixed + per_unit * tile`` under the VMEM budget.

    The shared discipline behind ``hinm_spmm.pick_bblk`` and
    ``paged_attn.pick_pp``: start from ``min(start, n_units)`` and halve
    until the working set fits (and, when ``divide``, the tile divides
    ``n_units`` so the grid needs no remainder handling). Never returns
    less than ``floor`` — a single minimal tile must fit by construction.
    """
    t = max(floor, int(n_units) if start is None else min(int(start), int(n_units)))
    while t > floor and (fixed_bytes + per_unit_bytes * t > budget
                        or (divide and n_units % t)):
        t = max(floor, t // 2)
    return t


def _count_dispatch(name: str, **labels: str) -> None:
    """Tally a kernel backend decision in the global metrics registry.

    Runs at trace time — once per XLA trace, not per step — so the
    counters attribute dispatch decisions at zero steady-state cost.
    Imported lazily: `repro.serve.telemetry` must not be a module-level
    dependency of the kernel layer (the serve package sits above it in the
    import graph)."""
    from repro.serve.telemetry import metrics as _tm

    _tm.GLOBAL.counter(name, labels=labels).inc()


def paged_attention(
    q: jax.Array,          # (B, s, H, hd)
    k_pool: jax.Array,     # (n_pages, page, KV, hd)
    v_pool: jax.Array,     # (n_pages, page, KV, hd)
    kpos_pool: jax.Array,  # (n_pages, page) int32
    bt: jax.Array,         # (B, n_bt) int32
    q_pos: jax.Array,      # (B, s) int32
    *,
    window: int = 0,
    backend: str = "auto",
) -> jax.Array | None:
    """Block-table-resolved decode attention over a paged KV pool.

    Returns (B, s, H, hd), or None when the chosen backend defers to the
    caller's jnp ``pool[bt]`` gather path ("off", or "auto" off-TPU —
    interpret mode is a correctness harness, not a CPU fast path).
    "pallas" / "on" off-TPU raise `TPUUnavailableError`.

    Every call lands a labeled count in the process-global telemetry
    registry (decision + deferral reason).  This function runs at trace
    time — once per XLA trace, not per decode step — so the counters
    report *dispatch decisions*, exactly the attribution the serving
    observability layer wants, at zero steady-state cost.
    """
    if backend in ("off", "gather"):
        _count_dispatch("paged_attn_dispatch", decision="gather", reason="knob-off")
        return None
    if backend == "auto":
        if not _on_tpu():
            _count_dispatch("paged_attn_dispatch", decision="gather",
                            reason="auto-no-tpu")
            return None
        backend = "pallas"
        _count_dispatch("paged_attn_dispatch", decision="pallas",
                        reason="auto-tpu")
    elif backend in ("pallas", "on"):
        _require_tpu("paged_attention")
        _count_dispatch("paged_attn_dispatch", decision="pallas",
                        reason="forced")
    elif backend == "interpret":
        _count_dispatch("paged_attn_dispatch", decision="interpret",
                        reason="forced")
    else:
        raise ValueError(f"unknown paged-attention backend {backend!r}")
    from repro.kernels import paged_attn as _pattn

    return _pattn.paged_decode_attn(
        q, k_pool, v_pool, kpos_pool, bt, q_pos, window=window,
        interpret=backend == "interpret")


def hinm_matmul(
    x: jax.Array,
    p: PackedHiNM,
    backend: str = "auto",
    chunk_bytes: int | None = None,
) -> jax.Array:
    """y (..., n_out) = x (..., n_in) @ W_packed^T (rows in packed order).

    Every call tallies ``hinm_matmul_dispatch{backend, shape}`` at trace
    time (shape = ``n_in x n_out``), so a run can show which backend served
    each projection shape. Under a multi-device abstract mesh context
    (``jax.sharding.use_abstract_mesh``, as the serving ``Scheduler``
    traces on a mesh) the compiled kernel runs per device inside a
    ``shard_map`` — Mosaic kernels are not auto-partitioned — with the
    rows split over every mesh axis when they divide, replicated
    otherwise, and the packed weights replicated.
    """
    lead = x.shape[:-1]
    xb = x.reshape(-1, x.shape[-1])
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "xla"
    _count_dispatch("hinm_matmul_dispatch", backend=backend,
                    shape=f"{p.n_in}x{p.n_out}")
    if backend == "xla":
        from repro.perf_knobs import KNOBS

        y = None
        if KNOBS.packed_shard_map:
            y = _ref.hinm_spmm_shard_map(xb, p)
        if y is None:
            y = _ref.hinm_spmm_xla(xb, p, chunk_bytes=chunk_bytes)
    elif backend in ("pallas", "interpret"):
        if backend == "pallas":
            _require_tpu("hinm_matmul")

        def kernel(xl, vals, nm_idx, vec_idx):
            return _spmm.hinm_spmm(
                xl.T, vals, nm_idx, vec_idx, nn=p.config.n, mm=p.config.m,
                interpret=backend == "interpret", out_dtype=x.dtype).T

        am = jax.sharding.get_abstract_mesh()
        if am.empty or am.size == 1:
            y = kernel(xb, p.vals, p.nm_idx, p.vec_idx)
        else:
            P = jax.sharding.PartitionSpec
            rows = P(am.axis_names) if xb.shape[0] % am.size == 0 else P()
            y = jax.shard_map(
                kernel, mesh=am, in_specs=(rows, P(), P(), P()),
                out_specs=rows, check_vma=False,
            )(xb, p.vals, p.nm_idx, p.vec_idx)
    elif backend == "oracle":
        y = _ref.hinm_spmm_oracle(xb, p)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return y.reshape(*lead, p.n_out)


def nm_apply(w: jax.Array, nn: int = 2, mm: int = 4, backend: str = "auto") -> jax.Array:
    """Apply N:M magnitude selection along the last axis (any leading dims)."""
    lead = w.shape[:-1]
    wb = w.reshape(-1, w.shape[-1])
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "xla"
    if backend == "xla":
        out = _ref.nm_select_ref(wb, nn, mm)
    elif backend in ("pallas", "interpret"):
        if backend == "pallas":
            _require_tpu("nm_apply")
        out = _nmsel.nm_select(wb, nn=nn, mm=mm,
                               interpret=backend == "interpret")
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return out.reshape(*lead, w.shape[-1])
