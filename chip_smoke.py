"""Serve the whole qwen2_0_5b, gyro-pruned and packed, on one TPU chip.

  python chip_smoke.py              # one chip: prune, pack, serve, check
  python chip_smoke.py --chips 4    # the page-sharded pool on a 4-chip mesh
                                    # against the same requests on one chip

The quickest proof that the system still starts on the chip. One process
drives the main path through the calls `examples/serve_hinm.py` makes, at
the model's full published size (24 layers, d_model 896, GQA 14/2,
d_ff 4864, vocab 151,936; no cut of any kind), with random weights from
`--seed`:

  zoo.init -> pruning.prune_model(method="gyro") -> Scheduler (paged pool,
  page 64, continuous batching) -> SlotKVCache -> hinm_spmm / paged_attn

and then checks on the chip that
  * every paged-attention decision was the compiled Pallas kernel (no
    `pool[bt]` gather, no scheduler deferral) and every projection matmul
    ran the compiled `hinm_spmm` on its PackedHiNM weights;
  * `hinm_spmm` matches `ref.hinm_spmm_oracle` at every projection shape;
  * `paged_decode_attn` matches the `paging.gather_view` reference at the
    served cache shape; `nm_select` matches `ref.nm_select_ref`;
  * the first-step logits of served prompts match a float32 forward of the
    masked-dense model (`zoo.unpack_params`, matmul precision "highest").

`--chips 4` runs only the sharded path and what it is compared with: the
same 8 greedy requests through `Scheduler(mesh=<4-device data mesh>)` with
a page-sharded pool, and on one of those chips alone through the same
gather attention path (the weights packed by `zoo.pack_params`, without
the host-side gyro search); greedy tokens must match (or each request's
first differing token, the first of them named by
`flightrec.diff_records`, must be a near-tie within the logits
tolerance), and the pool's pages must sit on all four devices.

Any failed check, exception or missing accelerator exits non-zero without
the result line. The last line of standard output is, on success, exactly
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ARCH = "qwen2_0_5b"
PAGE = 64
NEW_TOKENS = 32
# prompt lengths of the 8 requests (128..512 tokens); odd rids sample.
# rids 0-2 share a length so one batched forward checks their logits.
PROMPT_LENS = (384, 384, 384, 128, 512, 160, 448, 256)
LOGIT_RIDS = (0, 1, 2)
# --chips 4: greedy requests whose prompts share one length bucket (256),
# so each side compiles one prefill per group width and no sampler
FOUR_CHIP_LENS = (256, 240, 224, 208, 200, 232, 248, 216)
MAX_SEQ = 576                     # 512 + 32 rounded up to whole pages
OCP_ITERS = ICP_ITERS = 1

# Tolerances, each with its reason.
# hinm_spmm vs the f32 oracle: the kernel multiplies bf16 values exactly
# into f32 and accumulates in f32 on the MXU; the oracle sums the same
# products in another order. Order alone moves an f32 sum of K <= 2432
# terms by well under 1e-4 of its scale; a wrong gather or slot index
# moves outputs by their own size.
SPMM_REL_TOL = 1e-3
# paged_decode_attn vs the gather reference (f32, precision "highest"):
# q is f32, the bf16 pool upcasts exactly, and the kernel's dots contract
# at f32 precision, so the two differ by f32 rounding and exp() alone
# (~1e-6 on O(1) outputs, averages of unit-normal V rows). The control is
# the same reference at the chip's default precision, one bf16 MXU pass
# per dot (2**-9 relative per product): ~1e-3. The bound sits between the
# two, so a kernel that dropped to bf16 passes fails it; a wrong page, row
# or mask moves an output by O(1).
ATTN_ABS_TOL = 1e-4
# served logits vs the f32 masked-dense forward: weights are the same
# bf16 numbers in both, but the served path stores every activation and
# the KV cache in bf16 (unit roundoff 2**-9) through 24 layers. The bound
# is on max|err| over the vocabulary relative to the reference logits'
# standard deviation. bf16 storage measured 0.02 of it at 2 layers and
# 0.04 at 24 (XLA on CPU, same code path, narrower widths, 4096-entry
# vocab); 0.15 leaves room for the full vocabulary's longer tail. Storage
# in fp8 (roundoff 2**-4, 32x bf16's) or a dropped term lands far above.
LOGIT_REL_TOL = 0.15


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# model, workload, serving


def build_model(cfg, seed: int):
    """zoo.init from the seed, then gyro prune + pack (timed)."""
    import jax

    from repro.models import zoo
    from repro.train import pruning

    params = zoo.init(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    _, _, packed, report = pruning.prune_model(
        params, cfg, method="gyro", ocp_iters=OCP_ITERS, icp_iters=ICP_ITERS)
    jax.block_until_ready(packed)
    log(f"prune: gyro ocp_iters={OCP_ITERS} icp_iters={ICP_ITERS} "
        f"{time.perf_counter() - t0:.1f}s wall, mean retained saliency "
        f"{report.mean_retained:.4f} at {cfg.hinm.total_sparsity:.0%} sparsity")
    return packed


def workload(cfg, seed: int, lens=PROMPT_LENS, sample: bool = True):
    import numpy as np

    from repro.serve import Request, SamplingParams

    rng = np.random.default_rng(seed)
    reqs = []
    for i, n in enumerate(lens):
        sampled = sample and i % 2 == 1
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, (n,)).astype(np.int32),
            params=SamplingParams(max_new_tokens=NEW_TOKENS,
                                  temperature=0.8 if sampled else 0.0,
                                  top_k=50 if sampled else 0),
            arrival=i // 2))
    return reqs


class CompileClock:
    """Backend compile seconds and counts, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def serve(sched, reqs, clock: CompileClock, label: str):
    """Drive one wave of requests to completion; returns finished requests."""
    import jax

    s0, c0 = clock.seconds, clock.count
    t0 = time.perf_counter()
    done = sched.run(reqs)
    jax.block_until_ready(sched.kv.cache)
    wall = time.perf_counter() - t0
    n_tok = sum(r.n_generated for r in done)
    log(f"{label}: {len(done)} requests, {n_tok} tokens generated in "
        f"{wall:.2f}s wall; {clock.count - c0} compiles took "
        f"{clock.seconds - s0:.2f}s of it")
    check(len(done) == len(reqs), f"{label}: {len(done)}/{len(reqs)} finished")
    check(all(r.n_generated == NEW_TOKENS for r in done),
          f"{label}: a request stopped short of {NEW_TOKENS} tokens")
    return done


def dispatch_counters(name: str) -> dict:
    from repro.serve.telemetry import metrics as tm

    return {tuple(sorted(c.labels.items())): c.value
            for c in tm.GLOBAL if c.name == name and c.kind == "counter"}


# ---------------------------------------------------------------------------
# checks on the chip


def projection_weights(cfg, packed):
    """{(n_in, n_out): PackedHiNM of layer 0} for every planned projection."""
    import jax

    from repro.models import module as nn
    from repro.models import zoo
    from repro.perm.graph import get_container

    out = {}
    for key, sel, spec in zoo.perm_graph(cfg).instances():
        w = nn.get_path(get_container(packed, key, sel), spec.path)["w"]
        while w.vals.ndim > 3:                      # layer stack -> layer 0
            w = jax.tree.map(lambda a: a[0], w)
        out.setdefault((w.n_in, w.n_out), w)
    return out


def check_spmm(cfg, packed, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.hinm_spmm import hinm_spmm

    rng = np.random.default_rng(seed)
    for (n_in, n_out), w in sorted(projection_weights(cfg, packed).items()):
        for b in (8, 512):
            x = jnp.asarray(rng.normal(size=(b, n_in)), jnp.bfloat16)
            got = hinm_spmm(x.T, w.vals, w.nm_idx, w.vec_idx, nn=w.config.n,
                            mm=w.config.m, out_dtype=jnp.float32).T
            with jax.default_matmul_precision("highest"):
                want = ref.hinm_spmm_oracle(x.astype(jnp.float32), w)
            err = float(jnp.abs(got - want).max())
            scale = float(jnp.abs(want).max())
            log(f"hinm_spmm {n_in}x{n_out} B={b}: max|err| {err:.3e} "
                f"of max|y| {scale:.3e}")
            check(err <= SPMM_REL_TOL * scale,
                  f"hinm_spmm {n_in}x{n_out} B={b} off the oracle")


def check_paged_attn(cfg, sched, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.models import layers, paging

    k_pool = sched.kv.cache["k"][0]                  # layer 0's pool
    n_pages, page, kvh, hd = k_pool.shape
    b, n_bt = sched.kv.cache["bt"].shape[1:]
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.normal(size=k_pool.shape), k_pool.dtype)
    vp = jnp.asarray(rng.normal(size=k_pool.shape), k_pool.dtype)
    kpos = np.full((n_pages, page), paging.KPOS_SENTINEL, np.int32)
    bt = np.full((b, n_bt), paging.SENTINEL_PAGE, np.int32)
    free = rng.permutation(np.arange(paging.N_RESERVED, n_pages))
    live = rng.integers(4, n_bt * page + 1, size=b)
    live = np.minimum(live, (n_pages - paging.N_RESERVED) // b * page)
    nxt = 0
    for i in range(b):
        pages = free[nxt:nxt + -(-live[i] // page)]
        nxt += len(pages)
        bt[i, :len(pages)] = pages
        for r in range(live[i]):
            kpos[pages[r // page], r % page] = r
    for s in (1, 4):
        q_pos = jnp.asarray(live[:, None] - s + np.arange(s), jnp.int32)
        q = jnp.asarray(rng.normal(size=(b, s, cfg.n_heads, hd)), jnp.float32)
        args = (q, kp, vp, jnp.asarray(kpos), jnp.asarray(bt), q_pos)
        got = ops.paged_attention(*args, backend="pallas")

        def gather_ref():
            return layers._attn_chunked(
                q, paging.gather_view(kp, args[4]).astype(jnp.float32),
                paging.gather_view(vp, args[4]).astype(jnp.float32), q_pos,
                paging.gather_view(args[3], args[4]), True, 0, 1024)

        with jax.default_matmul_precision("highest"):
            want = gather_ref()
        control = gather_ref()                 # default precision: bf16 passes
        err = float(jnp.abs(got - want).max())
        err_c = float(jnp.abs(control - want).max())
        log(f"paged_decode_attn B={b} s={s} pool {n_pages}x{page}x{kvh}x{hd}"
            f": max|err| {err:.3e} (bound {ATTN_ABS_TOL:.0e}); bf16-pass "
            f"control {err_c:.3e}")
        check(err <= ATTN_ABS_TOL, f"paged_decode_attn s={s} off the gather")


def check_nm_select(seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    w = jnp.asarray(np.random.default_rng(seed).normal(size=(896, 4864)),
                    jnp.bfloat16)
    same = bool((ops.nm_apply(w, backend="pallas")
                 == ref.nm_select_ref(w, 2, 4)).all())
    log(f"nm_select 896x4864: identical to the oracle: {same}")
    check(same, "nm_select differs from the oracle")


def served_logits(sched, prompts):
    """First-step logits of the served path: the scheduler's packed params
    through prefill into its own stripe template (prefill always runs on
    stripes; the pool insert only moves rows) and the vocab projection
    (bf16, compiled kernels)."""
    import jax
    import jax.numpy as jnp

    from repro.models import zoo

    cfg = sched.cfg

    @jax.jit
    def fn(params, tokens, cache):
        last, _ = zoo.prefill(params, cfg, tokens, cache)
        return zoo.logits_fn(params, cfg, last)[:, :cfg.vocab]

    return fn(sched.params, jnp.asarray(prompts),
              sched.kv.template(len(prompts))).astype(jnp.float32)


def reference_logits(cfg, params, prompts):
    """The same prompts through a float32 forward of the masked-dense
    model; logits of each prompt's last position."""
    import jax
    import jax.numpy as jnp

    from repro.models import zoo

    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    dense = zoo.unpack_params(cfg, params)
    dense = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, dense)

    @jax.jit
    def fn(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = zoo.forward(params, cfg32, tokens, remat=False)
            return zoo.logits_fn(params, cfg32, x[:, -1])[:, :cfg.vocab]

    return fn(dense, jnp.asarray(prompts))


def check_logits(sched, reqs) -> None:
    """The served prefill's logits against the f32 reference, and the
    scheduler's own first token, for greedy requests, against the
    reference's argmax: equal, or a near-tie within the same bound."""
    import jax.numpy as jnp
    import numpy as np

    prompts = np.stack([r.prompt for r in reqs])
    got = served_logits(sched, prompts)
    want = reference_logits(sched.cfg, sched.params, prompts)
    for i, r in enumerate(reqs):
        err = float(jnp.abs(got[i] - want[i]).max())
        spread = float(want[i].std())
        top = int(want[i].argmax())
        short = float(want[i][top] - want[i][r.tokens[0]])
        log(f"logits rid={r.rid} prompt {len(r.prompt)}: max|err| {err:.4f}, "
            f"ref std {spread:.4f} (ratio {err / spread:.4f}); argmax "
            f"served {int(got[i].argmax())} ref {top}; scheduler's first "
            f"token {r.tokens[0]}, {short:.4f} under the ref max")
        check(err <= LOGIT_REL_TOL * spread,
              f"rid {r.rid}: served logits off the f32 reference")
        check(r.params.temperature > 0 or short <= LOGIT_REL_TOL * spread,
              f"rid {r.rid}: the scheduler's first token {r.tokens[0]} is "
              f"not the reference argmax {top} nor within the bound of it")


# ---------------------------------------------------------------------------
# the two runs


def one_chip(cfg, seed: int) -> None:
    import jax

    from repro.perf_knobs import KNOBS
    from repro.serve import Scheduler
    from repro.serve.telemetry import metrics as tm

    check(KNOBS.paged_attn == "auto", f"paged_attn knob is {KNOBS.paged_attn}")
    packed = build_model(cfg, seed)
    clock = CompileClock()
    tm.reset_global()
    sched = Scheduler(cfg, packed, max_slots=len(PROMPT_LENS),
                      max_seq=MAX_SEQ, page=PAGE, policy="continuous",
                      flightrec=True)
    check(sched.packed_mode == "auto", f"packed mode {sched.packed_mode}")
    check(sched.kv.paged and sched.kv.page == PAGE, "pool is not paged")
    defer = [ev.data.get("defer") for ev in sched.flight.events
             if ev.kind == "dispatch"]
    log(f"scheduler: paged_attn {sched.paged_attn}, defer {defer}, "
        f"pool {sched.kv.n_pages} pages of {PAGE}")
    check(defer == [None], f"paged attention deferred: {defer}")

    first = serve(sched, workload(cfg, seed), clock, "serve wave 1 (compiles)")
    serve(sched, workload(cfg, seed + 1), clock, "serve wave 2 (steady)")
    st = sched.stats
    log(f"decode: {st.decode_tokens_per_second:.1f} tok/s over "
        f"{st.decode_steps} batched steps; weight bytes packed/dense "
        f"{st.weight_bytes_ratio:.3f}")

    attn = dispatch_counters("paged_attn_dispatch")
    mm = dispatch_counters("hinm_matmul_dispatch")
    log(f"paged_attn_dispatch: {attn}")
    log(f"hinm_matmul_dispatch: {mm}")
    check(attn and all(dict(k)["decision"] == "pallas" for k in attn),
          f"paged attention did not run the compiled kernel: {attn}")
    shapes = {f"{n_in}x{n_out}" for n_in, n_out in projection_weights(
        cfg, packed)}
    ran = {dict(k)["shape"] for k in mm}
    check(ran == shapes, f"projections served {ran}, planned {shapes}")
    check(all(dict(k)["backend"] == "pallas" for k in mm),
          f"a projection matmul ran off the compiled kernel: {mm}")

    check_spmm(cfg, packed, seed)
    check_paged_attn(cfg, sched, seed)
    check_nm_select(seed)
    check_logits(sched, sorted(
        (r for r in first if r.rid in LOGIT_RIDS), key=lambda r: r.rid))
    jax.block_until_ready(sched.kv.cache)


def four_chips(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.models import paging, zoo
    from repro.perf_knobs import knobs
    from repro.serve import Scheduler
    from repro.serve.flightrec import diff_records

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    # the gyro search is host-side set-up, not a phase that exists across
    # chips: pack with the serving hook (magnitude HiNM, same format)
    packed = zoo.pack_params(cfg, zoo.init(jax.random.PRNGKey(seed), cfg))
    clock = CompileClock()
    n_bt = MAX_SEQ // PAGE
    n_pages = paging.shard_geometry(len(FOUR_CHIP_LENS) * n_bt, 4)["n_alloc"]
    kw = dict(max_slots=len(FOUR_CHIP_LENS), max_seq=MAX_SEQ, page=PAGE,
              n_pages=n_pages, policy="continuous", flightrec=True)

    def requests():
        return workload(cfg, seed, FOUR_CHIP_LENS, sample=False)

    def attn_route(sched):
        return [(ev.data["backend"], ev.data["defer"])
                for ev in sched.flight.events if ev.kind == "dispatch"]

    mesh = make_mesh((4,), ("data",))
    sharded = Scheduler(cfg, packed, mesh=mesh, **kw)
    check(sharded.kv.page_sharded, "the pool is not page-sharded")
    shards = sharded.kv.cache["k"].addressable_shards
    placed = sorted((s.index[1].start, s.device.id) for s in shards)
    log(f"sharded pool: {sharded.kv.n_pages} pages; (first page, device) "
        f"per shard {placed}; attention {attn_route(sharded)}")
    check(len({d for _, d in placed}) == 4
          and len({p for p, _ in placed}) == 4
          and all(s.data.shape[1] == sharded.kv.n_pages // 4 for s in shards),
          "pool pages are not spread over all four devices")
    done_s = serve(sharded, requests(), clock, "sharded (4 chips)")

    # like for like: the page-sharded pool attends through the SPMD gather
    # path, so the one-chip run takes the same path; only sharding differs
    with jax.default_device(jax.devices()[0]), knobs(paged_attn="off"):
        single = Scheduler(cfg, packed, **kw)
        log(f"one chip: attention {attn_route(single)}")
        check(attn_route(single) == [("off", None)]
              and attn_route(sharded) == [("off", "page-sharded-pool")],
              "the two runs do not attend through the same gather path")
        done_1 = serve(single, requests(), clock, "one chip")
    by_rid = {r.rid: r for r in done_1}
    by_rid_s = {r.rid: r for r in done_s}
    diverged = {rid: next(i for i, (a, b) in enumerate(
        zip(r.tokens, by_rid[rid].tokens)) if a != b)
        for rid, r in by_rid_s.items() if r.tokens != by_rid[rid].tokens}
    log(f"greedy requests token-identical sharded vs one chip: "
        f"{len(done_s) - len(diverged)}/{len(done_s)}")
    if not diverged:
        return

    def token_events(sched):   # page ids differ by design on a sharded pool
        return [ev for ev in sched.flight.events
                if ev.kind in ("commit", "emit", "finish")]

    first = diff_records(token_events(sharded), token_events(single)).first
    log(f"first divergence: "
        f"{first.describe() if first else 'none among the token events'}")
    # each diverging request's first differing token must be a near-tie:
    # the two tokens' logits on one chip, after the common prefix, closer
    # than the bound the served path is held to against the f32 reference.
    # One bf16 forward of the packed model (causal, so right-padding the
    # prefixes to MAX_SEQ changes nothing at their last positions).
    rids = sorted(diverged)
    prefix = np.zeros((len(rids), MAX_SEQ), np.int32)
    last = []
    for row, rid in enumerate(rids):
        p = np.concatenate([by_rid[rid].prompt,
                            by_rid[rid].tokens[:diverged[rid]]])
        prefix[row, :len(p)] = p
        last.append(len(p) - 1)

    @jax.jit
    def logits_at(params, tokens, last):
        x = zoo.forward(params, cfg, tokens, remat=False)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        return zoo.logits_fn(params, cfg, x)[:, :cfg.vocab].astype(jnp.float32)

    with jax.default_device(jax.devices()[0]):
        logits = logits_at(single.params, jnp.asarray(prefix),
                           jnp.asarray(last, jnp.int32))
    for row, rid in enumerate(rids):
        i = diverged[rid]
        a, b = by_rid_s[rid].tokens[i], by_rid[rid].tokens[i]
        gap = float(abs(logits[row, a] - logits[row, b]))
        spread = float(logits[row].std())
        log(f"rid {rid} token {i}: sharded {a} vs one chip {b}; one-chip "
            f"forward argmax {int(logits[row].argmax())}, logit gap "
            f"{gap:.4f}, std {spread:.4f} (ratio {gap / spread:.4f}, bound "
            f"{LOGIT_REL_TOL})")
        check(gap <= LOGIT_REL_TOL * spread,
              f"rid {rid}: divergence at token {i} is not a near-tie")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the page-sharded 4-chip path and the "
                         "one-chip run it is compared with")
    args = ap.parse_args()

    if "REPRO_SERVE_PACKED" in os.environ:
        print("chip_smoke.py: REPRO_SERVE_PACKED is set; the smoke serves "
              "the packed tree as pruned, unset it", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke.py: no repro package under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax

    dev = jax.devices()[0]
    log(f"devices: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(jax.devices())}")
    if dev.platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    from repro.configs.base import load_arch

    log(f"compile cache: {enable_compile_cache()}")
    cfg = load_arch(ARCH)
    log(f"model: {cfg.name} layers {cfg.n_layers} d_model {cfg.d_model} "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads} d_ff {cfg.d_ff} "
        f"vocab {cfg.vocab} {jax.numpy.dtype(cfg.dtype).name}; "
        f"HiNM V={cfg.hinm.v} {cfg.hinm.n}:{cfg.hinm.m} "
        f"vector sparsity {cfg.hinm.vector_sparsity}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(cfg, args.seed)
        else:
            one_chip(cfg, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
