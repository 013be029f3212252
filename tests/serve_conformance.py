"""Cross-family serving conformance suite: paged == stripe == isolated.

The serving contract is that no runtime optimisation may change tokens.
For every family x cache layout x (sharded / unsharded), a staggered
mixed-length workload driven through the continuous-batching `Scheduler`
must decode token-identically to isolated per-request batch-1 greedy
decode.  This module is the single reusable harness for that contract —
`test_serve.py`'s ad-hoc equivalence tests migrated here — plus the
sharded-pool churn property and the xlstm stripe-fallback regression.

Sharded cases need a multi-device jax.  The device count is locked at the
first jax import, so when this process has fewer than `N_DEVICES` devices
each sharded case re-runs this file as a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; the CI
multi-device job sets that flag for the whole pytest process and the
cases run inline (no subprocess) on the fake 4-device host mesh.
"""
from __future__ import annotations

import functools
import os
import re
import subprocess
import sys

# subprocess entry: the fake multi-device host platform must be configured
# before jax initialises (harmless if the parent already exported it)
if __name__ == "__main__":
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np

try:
    import pytest
except ImportError:  # `python tests/serve_conformance.py <mode>` driver
    pytest = None

from repro.launch.mesh import make_mesh
from repro.configs.base import load_arch
from repro.models import paging, zoo
from repro.serve import (ModelDrafter, Request, SamplingParams, Scheduler,
                         SlotKVCache, SpecConfig)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_DEVICES = 4

# families with a real paged layout; "ssm" (pure recurrent) is covered by
# the stripe-fallback regression instead
FAMILIES = ("transformer", "hybrid", "encdec")

_CASES = {
    # staggered arrivals + mixed prompt lengths + fewer slots than requests
    # exercise admission grouping, slot reuse, and page-gated admission
    "transformer": dict(arch="qwen2_0_5b",
                        reduced=dict(n_layers=2, d_model=64, n_heads=4,
                                     n_kv_heads=2, d_ff=128, vocab=128,
                                     head_dim=16),
                        packed=True, page=16, prompt_lens=(5, 8, 11, 8, 14),
                        max_new=7, seed=17),
    # 20 > window=16: the ring wraps inside its pages (roll-insert too)
    "hybrid": dict(arch="recurrentgemma_9b",
                   reduced=dict(window=16, n_layers=3),
                   page=8, prompt_lens=(8, 20, 12), max_new=6, seed=37),
    "encdec": dict(arch="seamless_m4t_medium", reduced={},
                   page=16, prompt_lens=(5, 9, 7), max_new=6, seed=37,
                   n_frames=6, cache_kw={"t_enc": 6}),
    "ssm": dict(arch="xlstm_125m",
                reduced=dict(n_layers=2, d_model=64, n_heads=4, vocab=128),
                page=16, prompt_lens=(5, 9, 7), max_new=6, seed=41),
}

MAX_SEQ = 64


def greedy_isolated(cfg, params, prompt, n, max_seq, eos=-1, embeds=None,
                    cache_kw=None):
    """Reference decode: raw batch-1 prefill + python token loop."""
    cache = zoo.make_cache(cfg, 1, max_seq, **(cache_kw or {}))
    emb = None if embeds is None else jnp.asarray(np.asarray(embeds)[None])
    last, cache = zoo.prefill(params, cfg, jnp.asarray(prompt[None]), cache,
                              embeds=emb)
    lg = zoo.logits_fn(params, cfg, last)[:, : cfg.vocab]
    toks = [int(jnp.argmax(lg, -1)[0])]
    while len(toks) < n and toks[-1] != eos:
        lg, cache = zoo.decode_step(
            params, cfg, jnp.asarray([[toks[-1]]], jnp.int32), cache)
        toks.append(int(jnp.argmax(lg[:, : cfg.vocab], -1)[0]))
    return toks


@functools.lru_cache(maxsize=None)
def _model(family):
    c = _CASES[family]
    cfg = load_arch(c["arch"]).reduced(**c["reduced"])
    params = zoo.init(jax.random.PRNGKey(c["seed"]), cfg)
    if c.get("packed"):  # the HiNM serving path, not just dense decode
        from repro.train import pruning

        _, _, params, _ = pruning.prune_model(params, cfg, ocp_iters=2,
                                              icp_iters=2)
    return cfg, params


@functools.lru_cache(maxsize=None)
def _workload(family):
    c = _CASES[family]
    cfg, _ = _model(family)
    rng = np.random.default_rng(c["seed"])
    prompts = tuple(rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
                    for n in c["prompt_lens"])
    embeds = None
    if c.get("n_frames"):
        embeds = tuple(
            rng.standard_normal((c["n_frames"], cfg.d_model)).astype(np.float32)
            for _ in prompts)
    return prompts, embeds


def scheduler_tokens(family, layout, mesh=None, n_pages="auto",
                     max_slots=4, decode_chunk=4, spec=None):
    """Drive the family workload through a Scheduler; returns (tokens list
    per request, scheduler).  layout: "paged" | "stripe" ("stripe" is the
    PR 2 baseline: exact-length admission, per-slot max_seq stripes);
    spec: a SpecConfig for speculative draft/verify decode."""
    c = _CASES[family]
    cfg, params = _model(family)
    prompts, embeds = _workload(family)
    kw = dict(cache_kw=c.get("cache_kw"))
    if layout == "paged":
        kw.update(page=c["page"], n_pages=n_pages)
    else:
        kw.update(page=None, bucket=False)
    sched = Scheduler(cfg, params, max_slots=max_slots, max_seq=MAX_SEQ,
                      decode_chunk=decode_chunk, mesh=mesh, spec=spec,
                      flightrec=True, **kw)
    reqs = [Request(rid=i, prompt=p, params=SamplingParams(max_new_tokens=c["max_new"]),
                    embeds=None if embeds is None else embeds[i], arrival=i)
            for i, p in enumerate(prompts)]
    sched.run(reqs)
    return [r.tokens for r in reqs], sched


@functools.lru_cache(maxsize=None)
def isolated_tokens(family):
    c = _CASES[family]
    cfg, params = _model(family)
    prompts, embeds = _workload(family)
    return [greedy_isolated(cfg, params, p, c["max_new"], MAX_SEQ,
                            embeds=None if embeds is None else embeds[i],
                            cache_kw=c.get("cache_kw"))
            for i, p in enumerate(prompts)]


def _pool_leaf(cache):
    """The k pool leaf of the first paged attn stack in a cache pytree."""
    for node in jax.tree_util.tree_leaves(cache, is_leaf=paging.is_paged):
        if paging.is_paged(node):
            return node["k"]
    return None


def _mesh_size(mesh):
    return int(np.prod(list(mesh.shape.values()))) if mesh is not None else 1


# ---------------------------------------------------------------------------
# flight-record triage: a conformance failure is a determinism failure, so
# every scheduler here records its decision stream (serve/flightrec) and a
# token mismatch dumps the records plus the first diverging event instead
# of a bare token diff
# ---------------------------------------------------------------------------

TRIAGE_DIR = os.environ.get("REPRO_TRIAGE_DIR", os.path.join(REPO, "triage"))


def _fail_with_triage(label, msg, **scheds):
    """Dump each named scheduler's flight record to TRIAGE_DIR as JSONL;
    with two records, also write a rendered first-divergence report.  The
    paired runs differ in configuration by design (paged vs stripe, kernel
    vs gather), so the construction-time `config`/`dispatch` events are
    excluded from the diff — the first *workload* decision that diverged
    is the triage lead.  Raises AssertionError naming that event."""
    from repro.serve import diff_records

    os.makedirs(TRIAGE_DIR, exist_ok=True)
    safe = re.sub(r"[^A-Za-z0-9_.-]", "_", label)
    recs = {}
    for name, s in scheds.items():
        if s is not None and getattr(s, "flight", None) is not None:
            s.flight.dump(os.path.join(TRIAGE_DIR, f"{safe}.{name}.jsonl"))
            recs[name] = [e for e in s.flight.events
                          if e.kind not in ("config", "dispatch")]
    lines = [msg, f"flight records {sorted(recs)} -> {TRIAGE_DIR}"]
    if len(recs) >= 2:
        (na, a), (nb, b) = list(recs.items())[:2]
        rep = diff_records(a, b)
        path = os.path.join(TRIAGE_DIR, f"{safe}.diff.txt")
        with open(path, "w") as f:
            f.write(f"a = {na}, b = {nb} "
                    "(config/dispatch events excluded: the runs differ "
                    "there by design)\n" + rep.render() + "\n")
        if rep.first is not None:
            lines.append("first diverging event: " + rep.first.describe())
        lines.append(f"triage report: {path}")
    raise AssertionError("\n".join(lines))


def assert_conformance(family, mesh=None):
    """paged == stripe == isolated, on `mesh` (None = unsharded)."""
    iso = isolated_tokens(family)
    paged, sp = scheduler_tokens(family, "paged", mesh=mesh)
    stripe, ss = scheduler_tokens(family, "stripe", mesh=mesh)
    assert sp.kv.paged, f"{family}: paged layout did not engage"
    # bucketed admission engages exactly where it is sound: attention-only
    # prefill stacks bucket, recurrent blocks admit at exact length
    assert sp.bucket == zoo.supports_bucketed_prefill(sp.cfg)
    if paged != iso:
        _fail_with_triage(f"conformance_{family}_paged",
                          f"{family}: paged decode diverged from isolated",
                          paged=sp, stripe=ss)
    if stripe != iso:
        _fail_with_triage(f"conformance_{family}_stripe",
                          f"{family}: stripe decode diverged from isolated",
                          stripe=ss, paged=sp)
    # all pages drained back to the free list once the workload finishes
    assert sp.kv.n_free_pages == sp.kv.n_alloc_pages
    if mesh is not None:
        assert sp.kv.specs is not None and ss.kv.specs is not None
        if _mesh_size(mesh) > 1:
            # the pool must actually live page-sharded on the mesh, not
            # silently replicate (the equivalence would then prove nothing)
            pool_k = _pool_leaf(sp.kv.cache)
            assert not pool_k.sharding.is_fully_replicated, \
                f"{family}: page pool replicated on a {_mesh_size(mesh)}-device mesh"
    if family == "transformer":
        # page-constrained pool: admission waits on free pages, tokens
        # must still be identical (FIFO, no starvation)
        tight, st = scheduler_tokens(family, "paged", mesh=mesh, n_pages=6)
        assert tight == iso
        assert st.kv.n_free_pages == st.kv.n_alloc_pages


def assert_kernel_conformance(family, mesh=None, replicate=False):
    """The Pallas paged-attention kernel must be token-invisible.

    Runs the paged workload with ``knobs(paged_attn="interpret")`` — the
    kernel resolving KV tiles through the block table, under the Pallas
    interpreter so CPU CI executes the real kernel logic — and asserts
    token identity with the gather-path/isolated reference, for plain
    decode and for speculative verify (the s = k+1 multi-token branch).

    On a >1-device mesh the pool is page-sharded and the single-device
    kernel must auto-downgrade to the SPMD gather path (tokens still
    identical); with ``replicate=True`` (knob ``paged_attn_sharded``) the
    pools replicate instead and the kernel stays engaged under the mesh.
    """
    from repro.perf_knobs import knobs

    iso = isolated_tokens(family)
    kn = dict(paged_attn="interpret", paged_attn_sharded=replicate)
    with knobs(**kn):
        toks, sp = scheduler_tokens(family, "paged", mesh=mesh)
    assert sp.kv.paged, f"{family}: paged layout did not engage"
    if mesh is not None and _mesh_size(mesh) > 1 and not replicate:
        # page-sharded pool: the kernel cannot address a split pool, the
        # Scheduler must fall back to the gather path rather than crash
        assert sp.paged_attn == "off", sp.paged_attn
        assert sp.kv.page_sharded
    else:
        assert sp.paged_attn == "interpret", sp.paged_attn
        if replicate and mesh is not None and _mesh_size(mesh) > 1:
            # the knob replicated the pools (kernel-compatible layout);
            # this is the one sanctioned exception to the page-sharding
            # assertion in assert_conformance
            assert _pool_leaf(sp.kv.cache).sharding.is_fully_replicated
            assert not sp.kv.page_sharded
    if toks != iso:
        # pair the kernel record with a gather-path run of the SAME
        # workload: the streams match event-for-event up to the first
        # tile the kernel resolved differently
        _, ref = scheduler_tokens(family, "paged", mesh=mesh)
        _fail_with_triage(f"kernel_{family}",
                          f"{family}: kernel decode diverged from isolated",
                          kernel=sp, gather=ref)

    with knobs(**kn):
        stoks, ss = scheduler_tokens(family, "paged", mesh=mesh,
                                     spec=SpecConfig(k=3))
    if stoks != iso:
        _, ref = scheduler_tokens(family, "paged", mesh=mesh,
                                  spec=SpecConfig(k=3))
        _fail_with_triage(
            f"kernel_spec_{family}",
            f"{family}: kernel speculative decode diverged from isolated",
            kernel=ss, gather=ref)
    assert ss.stats.verify_steps > 0


def assert_spec_conformance(family, mesh=None):
    """Speculative greedy decode must be token-identical to non-speculative
    decode: the n-gram drafter guesses, the multi-token verify scores, and
    the commit/rollback keeps exactly the accepted prefix — on both cache
    layouts (and sharded pools when `mesh` is given), in BOTH the fused
    single-dispatch scan (default) and the unfused per-cycle dispatch
    chain (`SpecConfig.fused=False`), so fused == unfused == isolated."""
    iso = isolated_tokens(family)
    for layout in ("paged", "stripe"):
        toks, sp = scheduler_tokens(family, layout, mesh=mesh,
                                    spec=SpecConfig(k=3))
        assert toks == iso, \
            f"{family}/{layout}: speculative decode diverged from isolated"
        assert sp.stats.verify_steps > 0          # the spec path actually ran
        assert sp.stats.decode_tokens > 0
        # the fused scan really fused: one spec dispatch per decode step
        # covers all of that step's draft/verify cycles
        d = sp.telemetry.registry.counter("serve_spec_dispatches").value
        assert sp.spec.fused and d * sp._spec_cycles == sp.stats.verify_steps, \
            f"{family}/{layout}: {d} spec dispatches for " \
            f"{sp.stats.verify_steps} cycles — the scan did not fuse"
        if layout == "paged":
            assert sp.kv.paged
            # accept/reject churn must leave page accounting exact
            assert sp.kv.n_free_pages == sp.kv.n_alloc_pages
        if mesh is not None:
            assert sp.kv.specs is not None
        # unfused debugging fallback: token-identical by contract
        utoks, su = scheduler_tokens(family, layout, mesh=mesh,
                                     spec=SpecConfig(k=3, fused=False))
        assert utoks == iso, \
            f"{family}/{layout}: unfused spec decode diverged from isolated"
        assert su.stats.verify_steps > 0
        # per-request verify work is cadence-invariant: fused (many cycles
        # per dispatch) and unfused judge exactly the same draft tokens
        assert (su.stats.draft_proposed, su.stats.draft_accepted) == \
            (sp.stats.draft_proposed, sp.stats.draft_accepted)


def run_self_draft(family="transformer"):
    """A draft model identical to the target must have its every greedy
    proposal accepted: the strongest end-to-end pin of the draft-model
    path (draft prefill, K+1-step propose, lockstep cache rollback) —
    acceptance 1.0 and k+1 tokens per ridden verify, token-identically."""
    cfg, params = _model(family)
    iso = isolated_tokens(family)
    k = 3
    toks, sp = scheduler_tokens(family, "paged",
                                spec=SpecConfig(k=k, drafter=ModelDrafter(cfg, params)))
    assert toks == iso
    st = sp.stats
    assert st.acceptance_rate == 1.0, st.acceptance_rate
    assert st.draft_proposed > 0
    # every ridden verify emits its full k+1 tokens (max_new - 1 decode
    # tokens per request arrive in ceil((max_new - 1) / (k + 1)) verifies)
    for n_gen in (len(t) for t in toks):
        assert n_gen == _CASES[family]["max_new"]
    assert st.tokens_per_verify_step > 1.0
    # the whole draft pool drained alongside the target pool
    assert sp.draft_kv.n_free == sp.draft_kv.n_slots
    assert (sp.draft_kv.slot_len == 0).all()


# ---------------------------------------------------------------------------
# prefix sharing + chunked prefill: sharing must be token-invisible
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _share_workload(family):
    """Shared-prefix prompts: requests 0-2 share `2.5 * page` tokens and
    run past three full pages, so the divergent third page is indexed and
    later arrivals copy-on-write its shared head; 3-4 share exactly two
    full pages (pure full-page hits), all with distinct suffixes."""
    c = _CASES[family]
    cfg, _ = _model(family)
    page = c["page"]
    rng = np.random.default_rng(c["seed"] + 1)
    base = rng.integers(0, cfg.vocab, (2 * page + page // 2,)).astype(np.int32)
    prompts = []
    for i in range(5):
        # i < 3: base + a tail long enough that page 2 (base tail rows +
        # private suffix) is a FULL page -> registered -> CoW donor
        tail = rng.integers(0, cfg.vocab, (page // 2 + i,)).astype(np.int32)
        cut = len(base) if i < 3 else 2 * page
        prompts.append(np.concatenate([base[:cut], tail]))
    embeds = None
    if c.get("n_frames"):
        embeds = tuple(
            rng.standard_normal((c["n_frames"], cfg.d_model)).astype(np.float32)
            for _ in prompts)
    return tuple(prompts), embeds


def share_tokens(family, mesh=None, prefix_share="auto", prefill_chunk=None,
                 spec=None, async_admission="auto"):
    """Drive the shared-prefix workload; returns (tokens, scheduler)."""
    c = _CASES[family]
    cfg, params = _model(family)
    prompts, embeds = _share_workload(family)
    sched = Scheduler(cfg, params, max_slots=4, max_seq=MAX_SEQ,
                      decode_chunk=4, mesh=mesh, spec=spec, page=c["page"],
                      n_pages="auto", cache_kw=c.get("cache_kw"),
                      prefix_share=prefix_share, prefill_chunk=prefill_chunk,
                      async_admission=async_admission, flightrec=True)
    reqs = [Request(rid=i, prompt=p,
                    params=SamplingParams(max_new_tokens=c["max_new"]),
                    embeds=None if embeds is None else embeds[i], arrival=i)
            for i, p in enumerate(prompts)]
    sched.run(reqs)
    return [r.tokens for r in reqs], sched


@functools.lru_cache(maxsize=None)
def isolated_share_tokens(family):
    c = _CASES[family]
    cfg, params = _model(family)
    prompts, embeds = _share_workload(family)
    return [greedy_isolated(cfg, params, p, c["max_new"], MAX_SEQ,
                            embeds=None if embeds is None else embeds[i],
                            cache_kw=c.get("cache_kw"))
            for i, p in enumerate(prompts)]


def assert_share_conformance(family, mesh=None):
    """Prefix sharing, CoW and chunked prefill must not change one token:
    shared == unshared == isolated, with exact refcount accounting and a
    pool that drains to pristine once the index is dropped.  Families
    without bitwise-sharable K/V rows must downgrade "auto" silently."""
    iso = isolated_share_tokens(family)
    off, s_off = share_tokens(family, mesh=mesh, prefix_share=False)
    assert off == iso, f"{family}: sharing-off run diverged from isolated"
    on, sp = share_tokens(family, mesh=mesh)
    if on != iso:
        _fail_with_triage(f"share_{family}",
                          f"{family}: prefix sharing changed tokens",
                          shared=sp, unshared=s_off)
    if not zoo.supports_prefix_share(sp.cfg):
        assert sp.prefix is None  # "auto" downgraded silently
        return
    assert sp.prefix is not None
    kv, st = sp.kv, sp.stats
    # the sharing machinery actually engaged: full-page hits, a divergent
    # tail copy, and a hit rate the workload design guarantees
    assert st.prefix_hit_tokens > 0
    assert st.prefix_hit_rate > 0
    assert kv.cow_copies > 0, "divergent tails never exercised CoW"
    # refcount conservation, then pristine once retention is dropped
    assert kv.n_free_pages + kv.n_referenced_pages == kv.n_alloc_pages
    sp.clear_prefix_cache()
    assert kv.n_free_pages == kv.n_alloc_pages
    kpos = np.asarray(kv.cache["kpos"])
    assert (kpos[:, paging.N_RESERVED:] == paging.KPOS_SENTINEL).all(), \
        "a drained pool kept real kpos rows (missed last-reference sweep)"

    # chunked prefill interleaved with decode: still token-identical
    ch, sc = share_tokens(family, mesh=mesh, prefill_chunk=_CASES[family]["page"])
    assert ch == iso, f"{family}: chunked prefill changed tokens"
    assert sc.stats.prefill_chunks > 0

    # speculative decode over shared pages + chunked admission
    sk, ss = share_tokens(family, mesh=mesh, spec=SpecConfig(k=3),
                          prefill_chunk=_CASES[family]["page"])
    assert sk == iso, f"{family}: spec decode over shared pages diverged"
    assert ss.stats.verify_steps > 0
    assert ss.stats.prefix_hit_tokens > 0


def assert_spec_share_conformance(family, mesh=None):
    """Speculation composed with the admission machinery — the two pins:

    (1) spec x chunked prefill x prefix sharing decodes token-identically
    to isolated, in BOTH the fused scan and the unfused dispatch chain,
    and under synchronous admission — mid-prefill lanes are excluded from
    draft/verify (`spec.acceptance` zeroes cnt AND judged for inactive
    lanes), so a slot still in extension prefill never gets verify rows
    written or junk folded into its acceptance stats;
    (2) prefix-shared admission must not starve the n-gram drafter: the
    history corpus seeds from the COMPLETE prompt (`spec.seed_history`),
    including rows served by page mapping rather than prefill, so
    acceptance under sharing matches the unshared run exactly."""
    iso = isolated_share_tokens(family)
    page = _CASES[family]["page"]
    off, s_off = share_tokens(family, mesh=mesh, prefix_share=False,
                              spec=SpecConfig(k=3))
    assert off == iso, f"{family}: spec sharing-off run diverged"
    on, s_on = share_tokens(family, mesh=mesh, spec=SpecConfig(k=3))
    assert on == iso, f"{family}: spec over shared pages changed tokens"
    if not zoo.supports_prefix_share(s_on.cfg):
        assert s_on.prefix is None  # "auto" downgraded silently
        return
    assert s_on.stats.prefix_hit_tokens > 0
    # pin (2): per-slot draft/verify work is admission-invariant, so the
    # aggregate (proposed, accepted) pair must match EXACTLY — a drafter
    # whose history misses the page-mapped prompt rows fails here first
    assert (s_on.stats.draft_proposed, s_on.stats.draft_accepted) == \
        (s_off.stats.draft_proposed, s_off.stats.draft_accepted), \
        f"{family}: sharing changed acceptance " \
        f"({s_on.stats.acceptance_rate:.3f} vs {s_off.stats.acceptance_rate:.3f})"
    assert s_on.stats.draft_accepted > 0, \
        f"{family}: acceptance collapsed under prefix sharing"
    # pin (1): chunked prefill interleaves mid-prefill lanes with live
    # spec decode — fused, unfused, and synchronous admission
    for kw in (dict(spec=SpecConfig(k=3)),
               dict(spec=SpecConfig(k=3, fused=False)),
               dict(spec=SpecConfig(k=3), async_admission=False)):
        ch, sc = share_tokens(family, mesh=mesh, prefill_chunk=page, **kw)
        assert ch == iso, \
            f"{family}: spec x chunked x shared diverged ({kw})"
        assert sc.stats.prefill_chunks > 0
        assert sc.stats.verify_steps > 0
        assert (sc.stats.draft_proposed, sc.stats.draft_accepted) == \
            (s_off.stats.draft_proposed, s_off.stats.draft_accepted), \
            f"{family}: chunked/shared admission changed acceptance ({kw})"


# ---------------------------------------------------------------------------
# churn property: random admit/release against the (sharded) paged pool
# ---------------------------------------------------------------------------


def run_churn(seed, mesh=None, n_ops=40):
    """Random admit/share/rollback/release churn against a paged
    SlotKVCache: refcount accounting must match an independent host model
    at every step (conservation law: a page is on a free list exactly when
    its modelled refcount is zero), speculative rollbacks (random
    accept/reject prefixes over a slot's trailing rows) must keep
    byte/page/slot_len accounting untouched and sweep the rejected rows
    exactly, shared admits (`map_slot` onto a live donor's full pages,
    with random copy-on-write tails) must be row-exact for both owners
    through any release order, no page may leak rows after drain, and
    pool bytes never move (the pool never reallocates)."""
    cfg, _ = _model("transformer")
    # n_pages=10 -> 12 with the reserved pair: already divides a 4-way mesh,
    # so sharded and unsharded pools are byte-identical
    kv = SlotKVCache(cfg, 4, MAX_SEQ, page=8, n_pages=10, mesh=mesh)
    assert kv.paged and kv.n_pages == 12
    bytes0 = kv.pool_bytes()
    tpl = kv.template(1)
    ar = jnp.arange(MAX_SEQ, dtype=jnp.int32)
    rng = np.random.default_rng(seed)
    # slot -> [current rows, reserved rows, floor]: `floor` is the lowest
    # row a rollback may rewind to — rows below it live in pages another
    # owner maps (mapped-in shared pages, or full pages donated away), the
    # analogue of the scheduler never rolling back into prompt rows
    live: dict[int, list[int]] = {}
    model_ref: dict[int, int] = {}  # page -> expected refcount

    def check():
        for p in range(paging.N_RESERVED, kv.n_pages):
            assert kv.page_ref(p) == model_ref.get(p, 0), \
                f"page {p}: ref {kv.page_ref(p)} != model {model_ref.get(p, 0)}"
        n_ref = sum(1 for v in model_ref.values() if v > 0)
        assert kv.n_free_pages == kv.n_alloc_pages - n_ref, \
            f"free-list drift: {kv.n_free_pages} free, {n_ref} referenced"
        assert kv.n_referenced_pages == n_ref
        assert kv.pool_bytes() == bytes0  # the pool never reallocates

    def slot_rows_on_device(slot):
        """Real (non-sentinel) kpos rows of `slot`, via its block table."""
        kpos = np.asarray(kv.cache["kpos"])[0]
        bt = np.asarray(kv.cache["bt"])[0, slot]
        alloc = int(np.asarray(kv.cache["alloc"])[0, slot])
        rows = [kpos[bt[p // kv.page], p % kv.page]
                for p in range(alloc * kv.page)]
        return [i for i, r in enumerate(rows) if r != paging.KPOS_SENTINEL]

    for _ in range(n_ops):
        roll = rng.random()
        can_roll = [s for s in sorted(live) if live[s][0] - live[s][2] >= 1]
        donors = [s for s in sorted(live) if live[s][0] >= kv.page]
        if can_roll and roll < 0.25:
            # speculative commit/rollback: treat the slot's last n_spec
            # rows as verify-written candidates and keep a random prefix
            # (never rewinding below the slot's sharing floor)
            slot = int(rng.choice(can_roll))
            rows_now, _, floor = live[slot]
            n_spec = int(rng.integers(1, min(rows_now - floor, 6) + 1))
            keep_n = int(rng.integers(0, n_spec + 1))
            pos0 = np.zeros((kv.n_slots,), np.int32)
            keep = np.zeros((kv.n_slots,), np.int32)
            for s, (r, _, _) in live.items():  # untouched slots: empty window
                pos0[s] = r
            pos0[slot], keep[slot] = rows_now - n_spec, keep_n
            free_before = kv.n_free_pages
            kv.rollback(pos0, keep, n_spec)
            live[slot][0] = rows_now - n_spec + keep_n
            kv.slot_len[slot] = live[slot][0]
            # rollback moves no pages and reallocates nothing
            assert kv.n_free_pages == free_before
            assert kv.slot_capacity(slot) == live[slot][1]
            # the device pos counter rewound with the sweep
            assert int(np.asarray(kv.cache["pos"])[0, slot]) == live[slot][0]
        elif donors and kv.n_free > 0 and 0.25 <= roll < 0.45:
            # shared admit: map a new slot onto a random prefix of a live
            # donor's full pages, optionally CoW-ing a divergent tail out
            # of the donor's next page
            donor = int(rng.choice(donors))
            d_rows = live[donor][0]
            d_pages = kv.slot_pages(donor)
            n_share = int(rng.integers(1, d_rows // kv.page + 1))
            shared = d_pages[:n_share]
            shared_rows = n_share * kv.page
            cow_src, cow_rows = None, 0
            rem = d_rows - shared_rows
            if rem > 0 and rng.random() < 0.5:
                cow_src = d_pages[n_share]
                cow_rows = int(rng.integers(1, rem + 1))
            mapped = shared_rows + cow_rows
            reserve = min(MAX_SEQ, mapped + int(rng.integers(1, 16)))
            n_fresh = kv.pages_needed(reserve) - n_share
            if n_fresh < 1 or n_fresh > kv.n_free_pages:
                check()  # a refused mapping must not move accounting
                continue
            slot = kv.acquire()
            pages = kv.map_slot(slot, shared, shared_rows, reserve,
                                cow_src=cow_src, cow_rows=cow_rows)
            assert pages[:n_share] == shared  # prefix order preserved
            for p in pages:  # shared ref++, fresh 0 -> 1
                model_ref[p] = model_ref.get(p, 0) + 1
            # the donor's donated full pages may never be rolled back
            # (the sharer is attending to them); the CoW source page
            # stays donor-private — the sharer holds a copy
            live[donor][2] = max(live[donor][2], shared_rows)
            live[slot] = [mapped, reserve, mapped]
            assert kv.slot_len[slot] == mapped
            assert kv.slot_capacity(slot) == reserve
        elif kv.n_free > 0 and (not live or roll < 0.65):
            rows = int(rng.integers(1, 33))
            reserve = min(MAX_SEQ, rows + int(rng.integers(0, 16)))
            if not kv.can_admit(reserve):
                check()  # a refused admission must not move accounting
                continue
            slot = kv.acquire()
            # a stripe carrying `rows` real kpos rows, so live pages hold
            # real positions and the leak check below is meaningful
            stripe = dict(
                tpl,
                kpos=jnp.where(ar[None, None, :] < rows, ar[None, None, :],
                               paging.KPOS_SENTINEL),
                pos=jnp.full_like(tpl["pos"], rows))
            kv.insert(slot, stripe, rows, reserve=reserve)
            live[slot] = [rows, reserve, 0]
            for p in kv.slot_pages(slot):
                assert model_ref.get(p, 0) == 0  # fresh pages only
                model_ref[p] = 1
            assert kv.slot_len[slot] == rows
            assert kv.slot_capacity(slot) == reserve
        elif live:
            slot = int(rng.choice(sorted(live)))
            pages = kv.slot_pages(slot)
            kv.release(slot)
            live.pop(slot)
            for p in pages:
                model_ref[p] -= 1
            assert kv.slot_len[slot] == 0 and kv.slot_capacity(slot) == 0
        check()

    # before draining: every live slot holds exactly its tracked rows —
    # rollbacks swept the rejected suffixes and nothing else, and a
    # released co-owner's pages were NOT swept under the survivors
    for slot, (rows_now, _, _) in live.items():
        assert slot_rows_on_device(slot) == list(range(rows_now)), \
            f"slot {slot}: device rows diverged after rollback/share churn"
    for slot in sorted(live):
        for p in kv.slot_pages(slot):
            model_ref[p] -= 1
        kv.release(slot)
    assert all(v == 0 for v in model_ref.values()), "refcounts leaked"
    assert kv.n_free_pages == kv.n_alloc_pages, "leaked pages after drain"
    assert kv.n_free == kv.n_slots
    assert (kv.slot_len == 0).all()
    kpos = np.asarray(kv.cache["kpos"])
    assert (kpos[:, paging.N_RESERVED:] == paging.KPOS_SENTINEL).all(), \
        "a freed page kept real kpos rows (would leak into a recycled slot)"
    assert (kpos[:, paging.SENTINEL_PAGE] == paging.KPOS_SENTINEL).all()


# ---------------------------------------------------------------------------
# xlstm: pure recurrent families fall back to stripes under a mesh
# ---------------------------------------------------------------------------


def run_xlstm_fallback(mesh):
    """Requesting a paged pool on a pure-recurrent family must fall back to
    stripes transparently — including under a sharded mesh, where
    cache_specs must resolve the recurrent state tree (no attention
    leaves) instead of crashing — and decode token-identically."""
    toks, sched = scheduler_tokens("ssm", "paged", mesh=mesh)
    assert not sched.kv.paged  # transparent stripe fallback
    if mesh is not None:
        assert sched.kv.specs is not None  # cache_specs resolved the tree
    assert toks == isolated_tokens("ssm")


# ---------------------------------------------------------------------------
# pytest entry points
# ---------------------------------------------------------------------------


def _sharded_case(mode: str) -> None:
    """Run `mode` on a 4-device mesh: inline when this process already has
    enough devices (CI multi-device job), else in a subprocess with the
    host-platform device-count flag."""
    if len(jax.devices()) >= N_DEVICES:
        _drive(mode, make_mesh((N_DEVICES,), ("data",)))
        return
    # merge with inherited flags, but override any smaller device count
    # (we only reach here when this process has < N_DEVICES devices)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    flags = (flags + " --xla_force_host_platform_device_count=4").strip()
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""),
               XLA_FLAGS=flags)
    out = subprocess.run([sys.executable, os.path.abspath(__file__), mode],
                         env=env, capture_output=True, text=True, timeout=600)
    assert f"CONFORMANCE_OK {mode}" in out.stdout, out.stdout + out.stderr


def _drive(mode: str, mesh) -> None:
    if mode.startswith("conformance:"):
        assert_conformance(mode.split(":", 1)[1], mesh=mesh)
    elif mode.startswith("kernel:"):
        assert_kernel_conformance(mode.split(":", 1)[1], mesh=mesh)
    elif mode.startswith("kernelrepl:"):
        assert_kernel_conformance(mode.split(":", 1)[1], mesh=mesh,
                                  replicate=True)
    elif mode.startswith("spec:"):
        assert_spec_conformance(mode.split(":", 1)[1], mesh=mesh)
    elif mode.startswith("share:"):
        assert_share_conformance(mode.split(":", 1)[1], mesh=mesh)
    elif mode.startswith("specshare:"):
        assert_spec_share_conformance(mode.split(":", 1)[1], mesh=mesh)
    elif mode == "churn":
        for seed in (0, 1, 2):
            run_churn(seed, mesh=mesh)
    elif mode == "xlstm":
        run_xlstm_fallback(mesh)
    else:
        raise ValueError(mode)


if pytest is not None:

    @pytest.mark.parametrize("family", FAMILIES + ("ssm",))
    def test_conformance_unsharded(family):
        if family == "ssm":
            run_xlstm_fallback(None)  # fallback is the ssm conformance case
        else:
            assert_conformance(family, mesh=None)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_conformance_sharded(family):
        _sharded_case(f"conformance:{family}")

    @pytest.mark.parametrize("family", FAMILIES)
    def test_conformance_kernel_unsharded(family):
        assert_kernel_conformance(family, mesh=None)

    def test_conformance_kernel_sharded_downgrade():
        # page-sharded pool on a real 4-device mesh: kernel -> gather
        _sharded_case("kernel:transformer")

    def test_conformance_kernel_sharded_replicated():
        # paged_attn_sharded knob: replicated pools, kernel under the mesh
        _sharded_case("kernelrepl:transformer")

    @pytest.mark.parametrize("family", FAMILIES)
    def test_spec_conformance_unsharded(family):
        assert_spec_conformance(family, mesh=None)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_spec_conformance_sharded(family):
        _sharded_case(f"spec:{family}")

    def test_spec_self_draft_model():
        run_self_draft("transformer")

    @pytest.mark.parametrize("family", FAMILIES)
    def test_share_conformance_unsharded(family):
        assert_share_conformance(family, mesh=None)

    def test_share_conformance_sharded():
        # prefix sharing + CoW + chunked prefill on a page-sharded pool
        _sharded_case("share:transformer")

    @pytest.mark.parametrize("family", FAMILIES)
    def test_spec_share_conformance_unsharded(family):
        assert_spec_share_conformance(family, mesh=None)

    def test_spec_share_conformance_sharded():
        # spec x chunked prefill x prefix sharing on a page-sharded pool
        _sharded_case("specshare:transformer")

    def test_spec_unsupported_family():
        cfg, params = _model("ssm")
        with pytest.raises(ValueError, match="no\\s+speculative"):
            Scheduler(cfg, params, max_slots=2, max_seq=MAX_SEQ,
                      spec=SpecConfig(k=2))

    from _hypothesis_compat import given, integers, settings

    @settings(max_examples=6, deadline=None)
    @given(seed=integers(0, 100))
    def test_churn_property(seed):
        run_churn(seed, mesh=None)
        # 1-device mesh: the sharded code path (specs, device_put,
        # constrained writes) without multi-device execution
        run_churn(seed, mesh=make_mesh((1,), ("data",)))

    def test_churn_sharded():
        _sharded_case("churn")

    def test_xlstm_stripe_fallback_sharded():
        _sharded_case("xlstm")


if __name__ == "__main__":
    _mode = sys.argv[1] if len(sys.argv) > 1 else "conformance:transformer"
    assert len(jax.devices()) >= N_DEVICES, \
        f"{len(jax.devices())} devices; the driver needs the XLA flag"
    _drive(_mode, make_mesh((N_DEVICES,), ("data",)))
    print(f"CONFORMANCE_OK {_mode}")
