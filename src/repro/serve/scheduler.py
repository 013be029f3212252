"""Continuous-batching scheduler with a device-resident decode loop.

The decode batch is a fixed-width pool of request slots (`SlotKVCache`).
Every scheduler step:

  1. admission — queued requests are prefilled (grouped by prompt-length
     bucket, padded with sentinel-masked rows so one jit serves the whole
     bucket) and inserted into free slots; a paged pool also gates
     admission on free KV pages. `policy="static"` instead gang-admits
     only when the pool is idle (the naive baseline the benchmark
     compares against);
  2. decode — one jitted chunk of `decode_chunk` steps runs as a
     `lax.scan` over `zoo.decode_step` + on-device sampling, with per-slot
     EOS / length early-exit masking.  The only host transfer is the
     (chunk, slots) emitted-token matrix once per chunk — not the
     per-token `np.asarray` sync of the old engine;
  3. harvest — emitted tokens are appended to their requests, finished
     slots are reset and returned to the free list.

Inactive lanes keep stepping inside a chunk (fixed-shape batch); their
cache writes land under their own lane's `kpos` mask and are wiped by the
slot reset on reuse, so they can never leak into a later request.

Sampling draws use per-slot, per-position keys (`sampler.fold_keys`): a
request's stochastic stream depends only on its seed and token index,
never on slot assignment or co-residents.

With `spec=SpecConfig(...)` the decode phase runs draft/verify cycles
instead of single-token chunks (`serve/spec`): a drafter proposes `k`
tokens per slot, one multi-token verify forward scores them all (one
packed-weight read for up to k+1 emitted tokens per slot), and
`SlotKVCache.rollback` commits the accepted prefix while sweeping the
rejected rows.  Greedy and "match"-mode stochastic requests emit the
exact non-speculative stream.

With prefix sharing on (`prefix_share`, default-auto on paged attention
pools) admission first walks a host-side radix index over token prefixes
(`serve/prefix`): full pages another request already cached are MAPPED
into the new slot's block table (refcount++, zero K/V movement), a
divergent tail page is copied (CoW), and only the unshared suffix is
prefilled — through `zoo.extend_step`, the multi-token decode write path
speculative verify already proved bitwise-equivalent to sequential
decode.  `prefill_chunk=N` additionally splits long suffixes into N-row
chunks advanced one per scheduler step, interleaved with decode chunks
(the decode jit sweeps mid-prefill slots' junk rows like a rejected
speculation), so a long admission no longer spikes co-resident TTFT.

With `mesh=...` the same loop runs sharded: the paged pool shards its
page axis and the block tables their slot axis (`sharding.cache_specs`),
params and per-slot decode state ride along replicated, and every jitted
cache update pins its output back to the pool layout — admission and
release stay host-side while page writes stay device-resident.  `n_pages`
defaults to `"auto"` (occupancy-derived provisioning) so admission
actually gates on free pages; pass `None` for full stripe capacity.
"""
from __future__ import annotations

import collections
import contextlib
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import PackedHiNM
from repro.models import zoo
from repro.serve import sampler
from repro.serve import spec as spec_mod
from repro.serve.flightrec import resolve_flightrec
from repro.serve.kv import SlotKVCache
from repro.serve.prefix import PrefixIndex
from repro.serve.request import Request, RequestState, SamplingParams, ServeStats
from repro.serve.telemetry import resolve_telemetry


def resolve_packed_mode(arg="auto") -> str:
    """Resolve the serving weight-format knob to pack | dense | auto.

    ``REPRO_SERVE_PACKED`` (env) overrides the constructor argument:
    "1"/"pack"/"packed" packs every planned projection at engine
    construction (hinm_spmm becomes the projection path), "0"/"dense"
    unpacks PackedHiNM weights back to masked-dense (the fallback knob),
    "auto"/unset serves the params exactly as handed in."""
    env = os.environ.get("REPRO_SERVE_PACKED")
    if env is not None and env != "":
        arg = env
    if arg in (True, 1, "1", "pack", "packed", "true"):
        return "pack"
    if arg in (False, 0, "0", "dense", "false"):
        return "dense"
    if arg in (None, "", "auto"):
        return "auto"
    raise ValueError(f"unknown packed-weights mode {arg!r}")


def param_bytes(params) -> tuple[int, int]:
    """(packed, dense-equivalent) byte footprint of a param pytree."""
    packed = dense = 0
    for leaf in jax.tree.leaves(params, is_leaf=lambda x: isinstance(x, PackedHiNM)):
        if isinstance(leaf, PackedHiNM):
            packed += leaf.packed_bytes()
            dense += leaf.dense_bytes()
        else:
            b = leaf.size * jnp.dtype(leaf.dtype).itemsize
            packed += b
            dense += b
    return packed, dense


class Scheduler:
    def __init__(self, cfg, params, max_slots: int = 4, max_seq: int = 512,
                 decode_chunk: int = 8, rng_seed: int = 0,
                 policy: str = "continuous", cache_kw: dict | None = None,
                 page: int | None = 64, n_pages: int | str | None = "auto",
                 bucket: bool | None = None, bucket_min: int = 8, mesh=None,
                 spec: "spec_mod.SpecConfig | None" = None,
                 packed: bool | str = "auto", telemetry=None,
                 prefix_share: bool | str = "auto",
                 prefill_chunk: int | None = None,
                 async_admission: bool | str = "auto",
                 flightrec=None):
        if policy not in ("continuous", "static"):
            raise ValueError(f"unknown admission policy {policy!r}")
        self.cfg = cfg
        self.mesh = mesh
        # observability bundle (serve/telemetry): None/"auto" defers to
        # KNOBS.telemetry (off by default). The registry is live either
        # way — trace-time instruments (compile counts, kernel dispatch)
        # are free per step; `enabled` gates the wall-clock histograms
        # and request-lifecycle span recording on the hot path.
        self.telemetry = resolve_telemetry(telemetry)
        # flight recorder (serve/flightrec): the structured DECISION log
        # telemetry aggregates away — every admission, page, prefix, spec
        # and dispatch decision as a causally-keyed event stream that can
        # be dumped, replayed and diffed.  Off by default (None/False);
        # True builds a fresh recorder; an instance is shared as-is.
        # Chrome-trace instant bridging only engages when telemetry spans
        # are being recorded anyway — a bare recorder stays trace-free.
        self.flight = resolve_flightrec(
            flightrec,
            tracer=self.telemetry.tracer if self.telemetry.enabled else None)
        m = self.telemetry.registry
        self._m_prefill_traces = m.counter("serve_prefill_traces")
        self._m_admit_wait = m.histogram("serve_admission_wait_seconds")
        self._m_step = m.histogram("serve_decode_step_seconds")
        self._m_host_gap = m.histogram("serve_host_gap_seconds")
        self._m_spec_draft = m.histogram("serve_spec_draft_seconds")
        self._m_spec_verify = m.histogram("serve_spec_verify_seconds")
        self._m_spec_accept = m.histogram(
            "serve_spec_window_acceptance", lo=1e-4, growth=1.2, n_buckets=50)
        self._m_hit_tokens = m.counter("serve_prefix_hit_tokens")
        self._m_chunks = m.counter("serve_prefill_chunks")
        self._m_evictions = m.counter("serve_prefix_evictions")
        # dispatch-shape instruments (free: they count calls, not time).
        # serve_spec_dispatches = device dispatches issued by the spec
        # decode phase (the fused scan is ONE per step; the unfused chain
        # is 3-4 per cycle); serve_overlap_admissions = admission groups
        # whose prefill was dispatched while a decode chunk was in flight;
        # serve_inflight_syncs = blocking host syncs issued while a chunk
        # was in flight (the async path's regression canary — must be 0).
        self._m_spec_dispatch = m.counter("serve_spec_dispatches")
        self._m_overlap_admit = m.counter("serve_overlap_admissions")
        self._m_inflight_syncs = m.counter("serve_inflight_syncs")
        # serve-time weight packing (one-time, here at construction):
        # "pack" routes every planned q/k/v/o + MLP projection through
        # hinm_spmm for prefill, decode and spec-verify; "dense" is the
        # fallback knob (PackedHiNM unpacked to masked-dense matmuls)
        self.packed_mode = resolve_packed_mode(packed)
        if self.packed_mode == "pack":
            params = zoo.pack_params(cfg, params)
        elif self.packed_mode == "dense":
            params = zoo.unpack_params(cfg, params)
        if mesh is not None:
            # decode runs data-parallel over the mesh with replicated
            # weights (page/slot-axis sharding is the cache's job; tensor-
            # parallel serving would compose via param_specs); placing
            # params here keeps every jitted step on one device set
            params = jax.device_put(
                params, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()))
        self.params = params
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.decode_chunk = decode_chunk
        self.policy = policy
        self._vocab = cfg.vocab
        eos = getattr(cfg, "eos_id", -1)
        # out-of-vocab EOS (e.g. full-tokenizer ids on reduced test configs)
        # disables EOS termination rather than matching a wrong token
        self.default_eos = eos if 0 <= eos < cfg.vocab else -1
        # prompt-length bucketing: pad admission prefill to power-of-two
        # buckets (one jit per bucket, not per distinct prompt length).
        # Auto-off for recurrent families (pads would enter the state) and
        # windowed configs (the stripe ring-roll path assumes real
        # positions in every prefill row).
        can_bucket = zoo.supports_bucketed_prefill(cfg) and not cfg.window
        if bucket and not can_bucket:
            raise ValueError(f"{cfg.family!r} prefill cannot be length-bucketed")
        self.bucket = can_bucket if bucket is None else bucket
        self.bucket_min = bucket_min

        # --- speculative decoding (serve/spec) ---
        self.spec = spec
        self.drafter = None
        self.draft_kv = None
        self._draft_params = None
        if spec is not None:
            if not zoo.supports_spec_decode(cfg):
                raise ValueError(
                    f"{cfg.family!r} (window={cfg.window}) has no "
                    "speculative verify path")
            if spec.k < 1:
                raise ValueError("SpecConfig.k must be >= 1")
            if spec.k + 1 > max_seq:
                raise ValueError("SpecConfig.k + 1 exceeds max_seq")
            if spec.cycles is not None and spec.cycles < 1:
                raise ValueError("SpecConfig.cycles must be >= 1 (or None "
                                 "for the decode_chunk-derived default)")
            # fused scan: cycles are nearly free (no dispatch round-trip per
            # cycle), so one cycle per chunk step keeps the per-dispatch
            # token floor at the non-spec chunk's decode_chunk tokens/lane.
            # Unfused: every cycle costs 3-4 dispatches, so keep about one
            # chunk's worth of emitted rows per step.
            self._spec_cycles = (
                spec.cycles if spec.cycles is not None
                else (decode_chunk if spec.fused
                      else max(1, decode_chunk // (spec.k + 1))))
            d = spec.drafter
            if d == "ngram":
                d = spec_mod.NgramDrafter(spec.ngram)
            elif d == "model":
                d = spec_mod.ModelDrafter.from_zoo(cfg, rng_seed)
            if getattr(d, "kind", None) not in ("ngram", "model"):
                raise ValueError(
                    f"unknown drafter {d!r}: pass \"ngram\", \"model\", or a "
                    "Drafter instance with kind in ('ngram', 'model')")
            self.drafter = d
            if d.kind == "model":
                dparams = d.params
                if mesh is not None:
                    dparams = jax.device_put(
                        dparams, jax.sharding.NamedSharding(
                            mesh, jax.sharding.PartitionSpec()))
                self._draft_params = dparams
                # the draft model keeps its own stripe pool, rolled back in
                # lockstep with the target so both caches always hold the
                # same committed token stream
                self.draft_kv = SlotKVCache(d.cfg, max_slots, max_seq,
                                            mesh=mesh,
                                            metrics=self.telemetry.registry,
                                            metrics_labels={"pool": "draft"},
                                            flight=self.flight,
                                            flight_label="draft")

        self.kv = SlotKVCache(cfg, max_slots, max_seq, page=page,
                              n_pages=n_pages, mesh=mesh,
                              metrics=self.telemetry.registry,
                              flight=self.flight,
                              **(cache_kw or {}))
        # paged-attention kernel routing, resolved once per scheduler: the
        # family must expose the shared pool layout, and a page-sharded
        # pool defers to the SPMD gather path (the kernel is a single-
        # device program) unless KNOBS.paged_attn_sharded replicated the
        # pool. The jitted closures below trace under this resolved mode.
        from repro.perf_knobs import KNOBS

        self.paged_attn = KNOBS.paged_attn
        defer = None
        if not self.kv.paged:
            defer = "pool-not-paged"
        elif not zoo.supports_paged_attn_kernel(cfg):
            defer = "family-unsupported"
        elif self.kv.page_sharded and not KNOBS.paged_attn_sharded:
            defer = "page-sharded-pool"
        if defer is not None:
            self.paged_attn = "off"
            if KNOBS.paged_attn != "off":  # an actual downgrade, not a knob
                m.counter("serve_paged_attn_deferred",
                          labels={"reason": defer}).inc()
        if self.flight is not None:
            # the kernel-dispatch decision, attributable per scheduler:
            # what was asked for, what actually runs, and why it deferred
            self.flight.emit("dispatch", requested=KNOBS.paged_attn,
                             backend=self.paged_attn, defer=defer)
        # enc-dec pools cache the encoder output at fixed width t_enc
        # (pass cache_kw={"t_enc": ...} to right-size it for the workload)
        self._t_enc = (cache_kw or {}).get("t_enc") or max_seq

        # --- prefix sharing + chunked prefill (extension admission) ---
        # Both ride the multi-token decode write path (`zoo.extend_step`),
        # so they need a paged pool on a family whose K/V rows are
        # per-(token, position) pure — `zoo.supports_prefix_share` — and
        # the continuous policy (static gang admission is the naive
        # baseline and stays byte-for-byte the PR 2 pipeline).  "auto"
        # downgrades transparently; an explicit True raises loudly.
        can_extend = (self.kv.paged and zoo.supports_prefix_share(cfg)
                      and policy == "continuous")
        if prefix_share == "auto":
            prefix_share = can_extend
        if prefix_share and not can_extend:
            raise ValueError(
                f"prefix sharing needs a paged pool + a prefix-sharing "
                f"family under continuous admission (family={cfg.family!r}, "
                f"window={cfg.window}, paged={self.kv.paged}, "
                f"policy={policy!r})")
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1 (or None)")
            if not can_extend:
                raise ValueError(
                    f"chunked prefill needs a paged pool + a prefix-sharing "
                    f"family under continuous admission "
                    f"(family={cfg.family!r}, paged={self.kv.paged}, "
                    f"policy={policy!r})")
        self.prefix_share = bool(prefix_share)
        self.prefill_chunk = prefill_chunk
        self.prefix = (PrefixIndex(self.kv.page, flight=self.flight)
                       if self.prefix_share else None)

        # --- async (double-buffered) admission ---
        # While a decode chunk is in flight on device, the host prepares
        # the NEXT admission group — builds its padded token arrays and
        # dispatches its prefill — instead of idling until the chunk's
        # emit sync.  The group's first-token sync and slot arming happen
        # at the START of the next step (`_commit_admissions`), when its
        # prefill has long finished under the chunk.  Admission issues no
        # blocking sync while a chunk is in flight ("serve_inflight_syncs"
        # stays 0).  "auto" = on under the continuous policy; static gang
        # admission stays the synchronous naive baseline.
        if async_admission == "auto":
            async_admission = policy == "continuous"
        if async_admission and policy != "continuous":
            raise ValueError("async admission requires the continuous "
                             "admission policy (static gang admission is "
                             "the synchronous baseline)")
        self.async_admission = bool(async_admission)
        # overlapped groups awaiting their first-token sync, plus the slot
        # and page budget they reserved (commit must never find the pool
        # drained by an extension admission racing in between)
        self._pending_admits: list[tuple] = []
        self._pending_slots = 0
        self._pending_pages = 0
        self._chunk_in_flight = False
        # slots mid-extension-prefill: they hold pages but no decode lane
        self._prefilling: dict[int, Request] = {}
        self._extend_jits: dict[tuple, object] = {}

        self._queue: collections.deque[Request] = collections.deque()
        self._running: dict[int, Request] = {}
        self._active_host = np.zeros((max_slots,), bool)
        # host mirror of each slot's draft cap (spec stats accounting)
        self._keff_host = np.zeros((max_slots,), np.int64)
        self._build()
        self._reset_state(rng_seed)
        pb, db = param_bytes(params)
        self.stats = ServeStats(0.0, 0.0, 0, pb, db)
        if self.flight is not None:
            # configuration fingerprint: replaying a record on a scheduler
            # built differently diverges HERE, as the first event, instead
            # of surfacing as a deep token mystery
            self.flight.emit(
                "config", family=cfg.family, vocab=int(cfg.vocab),
                max_slots=max_slots, max_seq=max_seq,
                decode_chunk=decode_chunk, policy=policy,
                page=self.kv.page if self.kv.paged else None,
                n_pages=self.kv.n_pages if self.kv.paged else None,
                bucket=self.bucket, packed=self.packed_mode,
                paged_attn=self.paged_attn, prefix_share=self.prefix_share,
                prefill_chunk=self.prefill_chunk,
                async_admission=self.async_admission, rng_seed=rng_seed,
                sharded=self.mesh is not None,
                spec=None if spec is None else {
                    "k": spec.k, "fused": bool(spec.fused),
                    "cycles": self._spec_cycles,
                    "drafter": self.drafter.kind})

    # -- jitted kernels -----------------------------------------------------

    @contextlib.contextmanager
    def _trace_ctx(self):
        """Trace-time context of every jitted model forward: the resolved
        paged-attention backend and, with a mesh, that mesh as the abstract
        mesh — the compiled Pallas kernels are not auto-partitioned, so
        ``ops`` runs them per device in a ``shard_map`` over it."""
        from repro.perf_knobs import knobs

        with knobs(paged_attn=self.paged_attn):
            if self.mesh is None:
                yield
            else:
                with jax.sharding.use_abstract_mesh(self.mesh.abstract_mesh):
                    yield

    def _build(self) -> None:
        cfg, vocab, chunk = self.cfg, self._vocab, self.decode_chunk

        # `stochastic` is a static flag: all-greedy batches compile to a
        # plain argmax and skip the per-step top-k/top-p sort / categorical
        # draw (O(V log V) per lane — real money at full-tokenizer vocabs).
        # Every draw folds (request seed, token index) into the base key,
        # so streams are slot- and co-resident-independent.

        def prefill_fn(params, tokens, cache, embeds, base_key, seeds, temp,
                       topk, topp, n_rows, stochastic):
            self._m_prefill_traces.inc()  # runs at trace time only
            with self._trace_ctx():
                last, cache = zoo.prefill(params, cfg, tokens, cache,
                                          embeds=embeds, n_rows=n_rows)
                logits = zoo.logits_fn(params, cfg, last)[:, :vocab]
            logits = logits.astype(jnp.float32)
            if stochastic:
                keys = sampler.fold_keys(base_key, seeds,
                                         jnp.zeros_like(seeds))
                first = sampler.sample(keys, logits, temp, topk, topp)
            else:
                first = sampler.greedy(logits)
            return first, cache

        self._prefill = jax.jit(prefill_fn, static_argnames=("stochastic",))

        def chunk_fn(params, cache, tok, active, rem, temp, topk, topp, eos,
                     seeds, gens, base_key, protect, stochastic, guarded):
            # `guarded` (static) compiles in only while some slot is mid-
            # chunked-prefill: inactive lanes still advance pos and write
            # junk rows every scan step, which would corrupt a protected
            # slot's committed prefix — so the chunk ends with the same
            # sweep a fully-rejected speculation uses (keep=0 rewinds the
            # protected lanes, keep=chunk commits everyone else exactly
            # where the scan left them).
            pos_entry = zoo.cache_position(cfg, cache) if guarded else None

            def step(carry, _):
                cache, tok, active, rem, gens = carry
                logits, cache = zoo.decode_step(params, cfg, tok, cache)
                logits = logits[:, :vocab].astype(jnp.float32)
                if stochastic:
                    keys = sampler.fold_keys(base_key, seeds, gens)
                    nxt = sampler.sample(keys, logits, temp, topk, topp)
                else:
                    nxt = sampler.greedy(logits)
                emit = jnp.where(active, nxt, -1)
                gens = gens + active.astype(jnp.int32)
                rem = rem - active.astype(jnp.int32)
                hit_eos = active & (eos >= 0) & (nxt == eos)
                active = active & ~hit_eos & (rem > 0)
                tok = jnp.where(active, nxt, tok[:, 0])[:, None]
                return (cache, tok, active, rem, gens), emit

            with self._trace_ctx():
                carry, emits = jax.lax.scan(
                    step, (cache, tok, active, rem, gens), None, length=chunk)
            if guarded:
                keep = jnp.where(protect, 0, jnp.int32(chunk))
                swept = zoo.cache_rollback(cfg, carry[0], None, pos_entry,
                                           keep, chunk)
                carry = (swept,) + carry[1:]
            if self.kv.shardings is not None:
                # pin the scanned cache back to its page/slot-axis layout so
                # chunked decode can't drift the pool off its shards
                carry = (jax.lax.with_sharding_constraint(
                    carry[0], self.kv.shardings),) + carry[1:]
            return carry + (emits,)

        self._chunk = jax.jit(chunk_fn, donate_argnums=(1, 2, 3, 4, 10),
                              static_argnames=("stochastic", "guarded"))

        def set_slot(tok, active, rem, temp, topk, topp, eos, seeds, gens,
                     keff, match, hist, hlen, slot, first, r, t, k, p, e, sd,
                     ke, mf, prow, plen):
            return (tok.at[slot, 0].set(first), active.at[slot].set(True),
                    rem.at[slot].set(r), temp.at[slot].set(t),
                    topk.at[slot].set(k), topp.at[slot].set(p),
                    eos.at[slot].set(e), seeds.at[slot].set(sd),
                    gens.at[slot].set(1), keff.at[slot].set(ke),
                    match.at[slot].set(mf), hist.at[slot].set(prow),
                    hlen.at[slot].set(plen))

        self._set_slot = jax.jit(
            set_slot, donate_argnums=tuple(range(13)))

        if self.spec is None:
            return

        s_width = self.spec.k + 1

        def verify_fn(params, cache, drafts, tok, active, rem, temp, topk,
                      topp, eos, seeds, gens, keff, match, hist, hlen,
                      base_key, stochastic, any_reject):
            pos0 = zoo.cache_position(cfg, cache)
            tokens = jnp.concatenate([tok, drafts], axis=1)
            with self._trace_ctx():
                logits, cache, undo = zoo.verify_step(params, cfg, tokens,
                                                      cache)
            logits = logits[..., :vocab].astype(jnp.float32)
            emits, cnt, judged, tok, active, rem, gens = spec_mod.acceptance(
                logits, drafts, tok, base_key=base_key, seeds=seeds,
                gens=gens, temp=temp, topk=topk, topp=topp, eos=eos, rem=rem,
                active=active, k_eff=keff, match=match, stochastic=stochastic,
                any_reject=any_reject)
            hist, hlen = spec_mod.append_history(hist, hlen, emits, cnt)
            return (self.kv._constrain(cache), undo, pos0, emits, cnt, judged,
                    tok, active, rem, gens, hist, hlen)

        self._verify = jax.jit(verify_fn, donate_argnums=(1, 3, 4, 5, 11, 14, 15),
                               static_argnames=("stochastic", "any_reject"))

        if self.drafter.kind == "ngram":
            n = self.drafter.n

            def propose_fn(hist, hlen, tok):
                return spec_mod.ngram_propose(hist, hlen, tok,
                                              self.spec.k, n=n)

            self._propose = jax.jit(propose_fn)
        else:
            dcfg = self.drafter.cfg
            vcap = min(dcfg.vocab, vocab)
            k_draft = self.spec.k

            def draft_propose_fn(dparams, dcache, tok):
                dpos0 = zoo.cache_position(dcfg, dcache)

                def stp(carry, _):
                    dc, t = carry
                    lg, dc = zoo.decode_step(dparams, dcfg, t, dc)
                    nxt = jnp.argmax(
                        lg[:, :vcap], axis=-1).astype(jnp.int32)[:, None]
                    return (dc, nxt), nxt[:, 0]

                # s_width steps: the extra step writes the last draft's own
                # KV row, so the draft cache tracks the target row-for-row
                # and the same accept count rolls both back
                (dc, _), ds = jax.lax.scan(stp, (dcache, tok), None,
                                           length=s_width)
                return (jnp.moveaxis(ds, 0, 1)[:, :k_draft], dpos0,
                        self.draft_kv._constrain(dc))

            self._draft_propose = jax.jit(draft_propose_fn,
                                          donate_argnums=(1,))

            def draft_prefill_fn(dparams, tokens, dcache, n_rows):
                _, dc = zoo.prefill(dparams, dcfg, tokens, dcache,
                                    n_rows=n_rows)
                return dc

            self._draft_prefill = jax.jit(draft_prefill_fn)

        # --- fused draft/verify scan (SpecConfig.fused, the default) ---
        # The whole cycle — draft(k) -> multi-token verify -> accept ->
        # cache rollback -> history append — runs as ONE `lax.scan` body,
        # device-resident for `self._spec_cycles` cycles per dispatch,
        # with the draft cache carried through the scan alongside the
        # target cache.  The only host sync stays the stacked emit matrix
        # once per step, and the per-cycle dispatch chain (draft jit +
        # verify jit + 1-2 rollback dispatches) collapses to one dispatch.
        # The mid-prefill guard carries over by construction: `acceptance`
        # zeroes `cnt` for inactive lanes (chunked-prefill slots included),
        # so the in-scan rollback rewinds their junk verify rows with
        # keep=0 EVERY cycle and `append_history` writes them nothing —
        # exactly what SlotKVCache.rollback gave the unfused chain.
        cycles = self._spec_cycles
        k_spec = self.spec.k

        def _fused_cycle(params, cache, tok, active, rem, temp, topk, topp,
                         eos, seeds, gens, keff, match, hist, hlen,
                         base_key, drafts, stochastic, any_reject):
            pos0 = zoo.cache_position(cfg, cache)
            tokens = jnp.concatenate([tok, drafts], axis=1)
            logits, cache, undo = zoo.verify_step(params, cfg, tokens, cache)
            logits = logits[..., :vocab].astype(jnp.float32)
            emits, cnt, judged, tok, active, rem, gens = spec_mod.acceptance(
                logits, drafts, tok, base_key=base_key, seeds=seeds,
                gens=gens, temp=temp, topk=topk, topp=topp, eos=eos,
                rem=rem, active=active, k_eff=keff, match=match,
                stochastic=stochastic, any_reject=any_reject)
            hist, hlen = spec_mod.append_history(hist, hlen, emits, cnt)
            cache = zoo.cache_rollback(cfg, cache, undo, pos0, cnt, s_width)
            return cache, tok, active, rem, gens, hist, hlen, emits, cnt, judged

        if self.drafter.kind == "ngram":
            n_gram = self.drafter.n

            def spec_fused_fn(params, cache, tok, active, rem, temp, topk,
                              topp, eos, seeds, gens, keff, match, hist,
                              hlen, base_key, stochastic, any_reject):
                def cycle(carry, _):
                    cache, tok, active, rem, gens, hist, hlen = carry
                    drafts = spec_mod.ngram_propose(hist, hlen, tok, k_spec,
                                                    n=n_gram)
                    (cache, tok, active, rem, gens, hist, hlen, emits, cnt,
                     judged) = _fused_cycle(
                        params, cache, tok, active, rem, temp, topk, topp,
                        eos, seeds, gens, keff, match, hist, hlen, base_key,
                        drafts, stochastic, any_reject)
                    return ((cache, tok, active, rem, gens, hist, hlen),
                            (emits, cnt, judged))

                with self._trace_ctx():
                    carry, outs = jax.lax.scan(
                        cycle, (cache, tok, active, rem, gens, hist, hlen),
                        None, length=cycles)
                cache, tok, active, rem, gens, hist, hlen = carry
                return (self.kv._constrain(cache), tok, active, rem, gens,
                        hist, hlen) + outs

            self._spec_fused = jax.jit(
                spec_fused_fn, donate_argnums=(1, 2, 3, 4, 10, 13, 14),
                static_argnames=("stochastic", "any_reject"))
        else:
            def spec_fused_fn(params, dparams, cache, dcache, tok, active,
                              rem, temp, topk, topp, eos, seeds, gens, keff,
                              match, hist, hlen, base_key, stochastic,
                              any_reject):
                def cycle(carry, _):
                    cache, dcache, tok, active, rem, gens, hist, hlen = carry
                    dpos0 = zoo.cache_position(dcfg, dcache)

                    def stp(c, _):
                        dc, t = c
                        lg, dc = zoo.decode_step(dparams, dcfg, t, dc)
                        nxt = jnp.argmax(
                            lg[:, :vcap], axis=-1).astype(jnp.int32)[:, None]
                        return (dc, nxt), nxt[:, 0]

                    (dcache, _), ds = jax.lax.scan(stp, (dcache, tok), None,
                                                   length=s_width)
                    drafts = jnp.moveaxis(ds, 0, 1)[:, :k_draft]
                    (cache, tok, active, rem, gens, hist, hlen, emits, cnt,
                     judged) = _fused_cycle(
                        params, cache, tok, active, rem, temp, topk, topp,
                        eos, seeds, gens, keff, match, hist, hlen, base_key,
                        drafts, stochastic, any_reject)
                    # same accept count rewinds the draft stripe in lockstep
                    dcache = zoo.cache_rollback(dcfg, dcache, None, dpos0,
                                                cnt, s_width)
                    return ((cache, dcache, tok, active, rem, gens, hist,
                             hlen), (emits, cnt, judged))

                with self._trace_ctx():
                    carry, outs = jax.lax.scan(
                        cycle,
                        (cache, dcache, tok, active, rem, gens, hist, hlen),
                        None, length=cycles)
                cache, dcache, tok, active, rem, gens, hist, hlen = carry
                return (self.kv._constrain(cache),
                        self.draft_kv._constrain(dcache), tok, active, rem,
                        gens, hist, hlen) + outs

            self._spec_fused = jax.jit(
                spec_fused_fn, donate_argnums=(2, 3, 4, 5, 6, 12, 15, 16),
                static_argnames=("stochastic", "any_reject"))

    def _extend(self, width: int, sample: bool, stochastic: bool):
        """Jitted extension prefill, one trace per (width-bucket, sample,
        stochastic): write `width` token rows per lane from each slot's
        current position through the multi-token decode path, then sweep
        exactly like a speculation — lanes keep their true `keep` rows
        (padded chunk rows and every non-prefilling lane's junk writes
        rewind), so co-resident decode state is bitwise untouched.  With
        `sample` the final chunk also projects each lane's last real row
        and draws the first token with the SAME (seed, index 0) key
        admission prefill uses — chunked and monolithic admission emit
        identical streams."""
        key = (width, sample, stochastic)
        jit = self._extend_jits.get(key)
        if jit is None:
            cfg, vocab = self.cfg, self._vocab

            def extend_fn(params, cache, tokens, keep, base_key, seeds,
                          temp, topk, topp):
                pos0 = zoo.cache_position(cfg, cache)
                with self._trace_ctx():
                    x, cache, undo = zoo.extend_step(params, cfg, tokens,
                                                     cache)
                cache = zoo.cache_rollback(cfg, cache, undo, pos0, keep,
                                           width)
                first = None
                if sample:
                    idx = jnp.maximum(keep - 1, 0)[:, None, None]
                    last = jnp.take_along_axis(x, idx, axis=1)[:, 0]
                    logits = zoo.logits_fn(params, cfg, last)[:, :vocab]
                    logits = logits.astype(jnp.float32)
                    if stochastic:
                        keys = sampler.fold_keys(base_key, seeds,
                                                 jnp.zeros_like(seeds))
                        first = sampler.sample(keys, logits, temp, topk, topp)
                    else:
                        first = sampler.greedy(logits)
                return self.kv._constrain(cache), first

            jit = self._extend_jits[key] = jax.jit(extend_fn,
                                                   donate_argnums=(1,))
        return jit

    def _reset_state(self, rng_seed: int) -> None:
        s = self.max_slots
        self._tok = jnp.zeros((s, 1), jnp.int32)
        self._active = jnp.zeros((s,), bool)
        self._rem = jnp.zeros((s,), jnp.int32)
        self._temp = jnp.zeros((s,), jnp.float32)
        self._topk = jnp.zeros((s,), jnp.int32)
        self._topp = jnp.zeros((s,), jnp.float32)
        self._eos = jnp.full((s,), -1, jnp.int32)
        self._seeds = jnp.zeros((s,), jnp.int32)
        self._gens = jnp.zeros((s,), jnp.int32)
        self._keff = jnp.zeros((s,), jnp.int32)
        self._match = jnp.ones((s,), bool)
        # per-slot token history (prompt + emitted): the n-gram drafter's
        # lookup corpus; sized for prompt + max_new, which max_seq bounds
        self._hist = jnp.zeros((s, self.max_seq), jnp.int32)
        self._hlen = jnp.zeros((s,), jnp.int32)
        # base PRNG key: never split — every draw folds in (request seed,
        # token index), so streams are reproducible per request
        self._key = jax.random.PRNGKey(rng_seed)
        if self.mesh is not None:
            # per-slot decode state rides along replicated: the chunk jit
            # then sees one device set (sharded pool + replicated state)
            rep = jax.sharding.NamedSharding(self.mesh,
                                             jax.sharding.PartitionSpec())
            (self._tok, self._active, self._rem, self._temp, self._topk,
             self._topp, self._eos, self._seeds, self._gens, self._keff,
             self._match, self._hist, self._hlen, self._key) = jax.device_put(
                (self._tok, self._active, self._rem, self._temp, self._topk,
                 self._topp, self._eos, self._seeds, self._gens, self._keff,
                 self._match, self._hist, self._hlen, self._key), rep)
        self._active_host[:] = False
        self._keff_host[:] = 0
        # end timestamp of the last decode dispatch+sync: the gap until
        # the next dispatch is pure host time (admission, harvest, python)
        self._last_sync = None

    def reset(self, rng_seed: int = 0) -> None:
        """Drop all queued/running requests and restore pristine state."""
        self._queue.clear()
        self._running.clear()
        self._prefilling.clear()
        self._pending_admits.clear()
        self._pending_slots = 0
        self._pending_pages = 0
        self._chunk_in_flight = False
        if self.prefix is not None:
            self.prefix = PrefixIndex(self.kv.page, flight=self.flight)
        self.kv.reset_all()
        if self.draft_kv is not None:
            self.draft_kv.reset_all()
        self._reset_state(rng_seed)
        self.stats = ServeStats(
            0.0, 0.0, 0, self.stats.packed_param_bytes, self.stats.dense_param_bytes)

    # -- request lifecycle --------------------------------------------------

    @property
    def prefill_traces(self) -> int:
        """Deprecated alias for the ``serve_prefill_traces`` registry
        counter (distinct XLA traces of the admission prefill — the
        compile-count column in benchmarks/serve_bench.py). Compile-count
        tracking lives in `self.telemetry.registry` with the other
        instruments; read the counter there instead."""
        warnings.warn(
            "Scheduler.prefill_traces is deprecated: read the "
            "'serve_prefill_traces' counter from the telemetry registry "
            "(scheduler.telemetry.registry) instead",
            DeprecationWarning, stacklevel=2)
        return int(self._m_prefill_traces.value)

    def clear_prefix_cache(self) -> int:
        """Drop every retained prefix (the index's page references).  Pages
        no live slot maps return to the free list immediately; shared ones
        follow when their last slot releases.  Returns pages freed now."""
        if self.prefix is None:
            return 0
        return self.prefix.clear(self.kv)

    def metrics_snapshot(self, include_global: bool = True) -> dict:
        """JSON-able snapshot of every instrument this scheduler feeds."""
        return self.telemetry.snapshot(include_global=include_global)

    @property
    def n_pending(self) -> int:
        return (len(self._queue) + len(self._prefilling)
                + len(self._running)
                + sum(len(rec[0]) for rec in self._pending_admits))

    def _cache_rows(self, req: Request) -> int:
        """Decoder-cache rows this request's prefill occupies. encdec embeds
        feed the encoder (cached separately as enc_out); vlm embeds are
        prepended to the decoder sequence."""
        extra = 0
        if req.embeds is not None and self.cfg.family != "encdec":
            extra = req.embeds.shape[0]
        return len(req.prompt) + extra

    def _reserve_rows(self, req: Request) -> int:
        """Cache rows this request may legally grow to (page budget)."""
        return self._cache_rows(req) + req.params.max_new_tokens

    def _bucket_len(self, n_tokens: int, extra: int) -> int:
        """Power-of-two prompt-length bucket, clamped so bucket + non-token
        rows (vlm embeds) still fit the prefill stripe."""
        b = self.bucket_min
        while b < n_tokens:
            b *= 2
        return max(n_tokens, min(b, self.max_seq - extra))

    def submit(self, req: Request) -> None:
        rows = self._cache_rows(req)
        if rows + req.params.max_new_tokens > self.max_seq:
            raise ValueError(
                f"request {req.rid}: {rows} prompt rows + max_new_tokens "
                f"{req.params.max_new_tokens} exceeds max_seq {self.max_seq}")
        if (self.kv.paged and self.kv.pages_needed(self._reserve_rows(req))
                > self.kv.n_alloc_pages):
            raise ValueError(
                f"request {req.rid}: needs more KV pages than the pool "
                f"allocates — raise n_pages")
        if (self.cfg.family == "encdec" and req.embeds is not None
                and req.embeds.shape[0] > self._t_enc):
            raise ValueError(
                f"request {req.rid}: {req.embeds.shape[0]} encoder frames "
                f"exceed the pool's t_enc {self._t_enc}")
        if req.params.spec_accept not in ("match", "reject"):
            raise ValueError(
                f"request {req.rid}: unknown spec_accept "
                f"{req.params.spec_accept!r}")
        req.state = RequestState.QUEUED
        req.submit_time = time.perf_counter()
        self._queue.append(req)
        if self.flight is not None:
            # the full admission schedule rides in this one event: prompt,
            # sampling params, seed, arrival — everything `flightrec.replay`
            # needs to rebuild the workload
            p = req.params
            self.flight.emit(
                "submit", rid=req.rid,
                prompt=[int(t) for t in req.prompt], arrival=req.arrival,
                max_new=p.max_new_tokens, temperature=float(p.temperature),
                top_k=int(p.top_k), top_p=float(p.top_p), eos=p.eos_id,
                seed=p.seed, spec_k=p.spec_k, spec_accept=p.spec_accept,
                embeds=req.embeds is not None)

    def _eff_eos(self, req: Request) -> int:
        if req.params.eos_id is not None:
            return req.params.eos_id if 0 <= req.params.eos_id < self._vocab else -1
        return self.default_eos

    def _eff_seed(self, req: Request) -> int:
        return req.params.seed if req.params.seed is not None else req.rid

    def _eff_keff(self, req: Request) -> int:
        if self.spec is None:
            return 0
        k = req.params.spec_k
        return self.spec.k if k is None else max(0, min(k, self.spec.k))

    def _finish(self, req: Request, finished: list[Request]) -> None:
        req.state = RequestState.FINISHED
        req.finish_time = time.perf_counter()
        eos = self._eff_eos(req)
        req.finish_reason = "eos" if (eos >= 0 and req.tokens and req.tokens[-1] == eos) else "length"
        self.stats.requests_finished += 1
        if req.finish_reason == "eos":
            self.stats.finished_at_eos += 1
        self.stats.observe_finish(req)
        if self.telemetry.enabled and req.first_token_time:
            # the decode span was opened when the lane armed (so abandoned
            # requests still export a valid, auto-closed span); requests
            # that finished at their first token never armed one
            open_span = next((s for s in reversed(req.spans)
                              if s.name == "decode" and s.t1 is None), None)
            if open_span is not None:
                self.telemetry.tracer.end(
                    open_span, req.finish_time, tokens=req.n_generated,
                    reason=req.finish_reason)
            else:
                self.telemetry.tracer.request_span(
                    req, "decode", req.first_token_time, req.finish_time,
                    tokens=req.n_generated, reason=req.finish_reason)
        if self.flight is not None:
            self.flight.emit("finish", rid=req.rid, reason=req.finish_reason,
                             n=req.n_generated, tokens=list(req.tokens))
        finished.append(req)

    def _extension_plan(self, req: Request):
        """(take_extension_path, PrefixMatch | None) for a queued request.

        A request extends in-pool — pages mapped first, suffix prefilled
        through `zoo.extend_step` — when its prompt hits the prefix index
        (shared pages make the stripe-scatter insert wrong: it would
        overwrite co-owned rows) or when chunking is on and the prompt
        exceeds one chunk.  Everything else (embeds requests, misses,
        short prompts) takes the classic bucketed group prefill."""
        if req.embeds is not None:
            return False, None
        m = None
        if self.prefix is not None:
            # always leave >= 1 row to prefill: the first sampled token
            # needs logits, and a fully-shared prompt would yield none
            m = self.prefix.match(req.prompt, len(req.prompt) - 1)
            if m.total_rows == 0:
                m = None
        chunked = (self.prefill_chunk is not None
                   and len(req.prompt) > self.prefill_chunk)
        return (m is not None or chunked), m

    def _ensure_pages(self, need: int, protect=()) -> bool:
        """Free-list pressure valve: retained prefixes are reclaimable
        memory, so a short admission evicts LRU index entries (pages only
        the index references) until `need` pages are free.  `protect`
        shields the pages of the admission's own pending match — evicting
        one would free a page its block table is about to map."""
        short = need - self.kv.n_free_pages
        if short > 0 and self.prefix is not None:
            freed = self.prefix.evict(self.kv, short, protect=protect)
            if freed:
                self._m_evictions.inc(freed)
        return need <= self.kv.n_free_pages

    def _admit(self, finished: list[Request]) -> None:
        if self.policy == "static" and self._running:
            return  # gang admission: wait for the whole pool to drain
        # overlapped admission groups hold reservations: their slots/pages
        # are drawn only at commit, so gate on what is genuinely left
        while self._queue and self.kv.n_free - self._pending_slots > 0:
            ext, m = self._extension_plan(self._queue[0])
            if ext:
                n_shared = len(m.page_ids) if m else 0
                need = (self.kv.pages_needed(
                    self._reserve_rows(self._queue[0])) - n_shared)
                protect = () if m is None else tuple(m.page_ids) + (
                    () if m.cow_src is None else (m.cow_src,))
                if not self._ensure_pages(need + self._pending_pages,
                                          protect):
                    return  # FIFO head waits for releases, no starvation
                self._start_extension(self._queue.popleft(), m)
                continue
            # group the queue head by (prompt-length bucket, embeds shape):
            # one batched prefill per group instead of k batch-1 prefills.
            # With bucketing on, every length in a bucket shares both the
            # group and the jit; without it the signature is the exact
            # length (fixed-batch compat stays a single (B, S) prefill).
            def sig(r):
                n = len(r.prompt)
                extra = self._cache_rows(r) - n
                return ((self._bucket_len(n, extra) if self.bucket else n),
                        None if r.embeds is None else r.embeds.shape)

            # paged pool: admission is also gated on free pages — a request
            # whose page budget doesn't fit waits at the queue head (FIFO,
            # no starvation) until releases (or prefix-cache eviction)
            # refill the free list
            head_reserve = self._reserve_rows(self._queue[0])
            if self.kv.paged:
                head_need = self.kv.pages_needed(head_reserve)
                self._ensure_pages(head_need + self._pending_pages)
                if (head_need + self._pending_pages > self.kv.n_free_pages):
                    return
            pages_left = self.kv.n_free_pages - self._pending_pages
            if self.kv.paged:
                pages_left -= self.kv.pages_needed(head_reserve)
            group = [self._queue.popleft()]
            while (self._queue
                   and len(group) < self.kv.n_free - self._pending_slots
                   and sig(self._queue[0]) == sig(group[0])
                   and not self._extension_plan(self._queue[0])[0]):
                if self.kv.paged:
                    need = self.kv.pages_needed(
                        self._reserve_rows(self._queue[0]))
                    if need > pages_left:
                        break
                    pages_left -= need
                group.append(self._queue.popleft())
            self._admit_group(group, finished)

    def _admit_group(self, group: list[Request], finished: list[Request]) -> None:
        """Prefill an admission group and arm its slots.

        The host work (array building), the prefill dispatch and the
        first-token sync used to be one synchronous block.  They are now
        two phases: **prepare** (everything up to and including the
        dispatch — no sync) and **commit** (`_commit_group`: the one
        first-token sync per group, then slot arming).  Synchronous mode
        commits immediately; with async admission a group prepared while
        a decode chunk is in flight is queued and committed at the start
        of the next step, its prefill having overlapped the chunk."""
        k = len(group)
        t0 = time.perf_counter()  # host array prep counts as prefill work
        for req in group:
            req.state = RequestState.PREFILLING
            req.admit_time = t0
        if self.bucket:
            # pad every prompt to the group's shared length bucket and the
            # group itself to a power-of-two width: one jit per
            # (bucket, width-bucket) instead of one per distinct shape.
            # Padded rows/lanes are sentinel-masked and discarded.
            n0 = len(group[0].prompt)
            s_b = self._bucket_len(n0, self._cache_rows(group[0]) - n0)
            k_b = 1
            while k_b < k:
                k_b *= 2
            tokens = np.zeros((k_b, s_b), np.int32)
            n_rows = np.zeros((k_b,), np.int32)
            d_rows = np.zeros((k_b,), np.int32)
            for i in range(k_b):
                r = group[min(i, k - 1)]
                tokens[i, : len(r.prompt)] = r.prompt
                n_rows[i] = self._cache_rows(r)
                d_rows[i] = len(r.prompt)
            tokens = jnp.asarray(tokens)
            n_rows_dev = jnp.asarray(n_rows)
            d_rows_dev = jnp.asarray(d_rows)
            def pad(a):
                return (np.concatenate([a, np.repeat(a[-1:], k_b - k, axis=0)])
                        if k_b > k else a)

            embeds = (None if group[0].embeds is None
                      else jnp.asarray(pad(np.stack([r.embeds for r in group]))))
            temps = pad(np.asarray([r.params.temperature for r in group],
                                   np.float32))
            topks = pad(np.asarray([r.params.top_k for r in group], np.int32))
            topps = pad(np.asarray([r.params.top_p for r in group], np.float32))
            seeds = pad(np.asarray([self._eff_seed(r) for r in group], np.int32))
        else:
            k_b = k
            tokens = jnp.asarray(np.stack([r.prompt for r in group]), jnp.int32)
            n_rows_dev = None
            d_rows_dev = None
            embeds = (None if group[0].embeds is None
                      else jnp.asarray(np.stack([r.embeds for r in group])))
            temps = np.asarray([r.params.temperature for r in group], np.float32)
            topks = np.asarray([r.params.top_k for r in group], np.int32)
            topps = np.asarray([r.params.top_p for r in group], np.float32)
            seeds = np.asarray([self._eff_seed(r) for r in group], np.int32)
        with self.telemetry.annotation("serve_prefill"):
            first, cache_k = self._prefill(
                self.params, tokens, self.kv.template(k_b), embeds, self._key,
                jnp.asarray(seeds), jnp.asarray(temps), jnp.asarray(topks),
                jnp.asarray(topps), n_rows_dev,
                stochastic=bool((temps[:k] > 0).any()))
        draft_cache_k = None
        if self.draft_kv is not None:
            # the draft model prefills the same prompts into its own pool
            # (token rows only: a modality frontend is the target's)
            draft_cache_k = self._draft_prefill(
                self._draft_params, tokens, self.draft_kv.template(k_b),
                d_rows_dev)
        t1 = time.perf_counter()
        self.stats.prefill_rows += sum(self._cache_rows(r) for r in group)
        if self.telemetry.enabled:
            blen = int(tokens.shape[1])
            tr = self.telemetry.tracer
            self.telemetry.registry.histogram(
                "serve_prefill_seconds",
                labels={"bucket": str(blen)}).observe(t1 - t0)
            tr.span("scheduler", f"prefill[b{blen}]", t0, t1,
                    requests=k, bucket=blen)
            for req in group:
                self._m_admit_wait.observe(req.admit_time - req.submit_time)
                tr.request_span(req, "queued", req.submit_time, req.admit_time)
                tr.request_span(req, f"prefill[b{blen}]", t0, t1)
        if self.flight is not None:
            # one event per admission group: membership, bucket geometry,
            # and whether the prepare phase overlapped an in-flight chunk
            # (its `commit` events then land at the NEXT step's start —
            # the async prepare/commit pairing, visible in the stream)
            self.flight.emit(
                "admit", group=[r.rid for r in group],
                bucket=int(tokens.shape[1]), width=k_b,
                overlap=bool(self.async_admission and self._chunk_in_flight))
        rec = (group, first, cache_k, draft_cache_k)
        if self.async_admission and self._chunk_in_flight:
            # overlapped: the prepare window ran UNDER the in-flight decode
            # chunk, so its wall time is hidden device-side — charging it
            # to prefill_seconds as well would double-count the makespan.
            # Reserve the group's slots/pages and hand off to next step's
            # `_commit_admissions` (no sync here — that's the whole point).
            self._pending_admits.append(rec)
            self._pending_slots += k
            if self.kv.paged:
                self._pending_pages += sum(
                    self.kv.pages_needed(self._reserve_rows(r))
                    for r in group)
            self._m_overlap_admit.inc()
            return
        self.stats.prefill_seconds += t1 - t0
        self._commit_group(rec, finished)

    def _commit_admissions(self, finished: list[Request]) -> None:
        """Land every admission group prepared under the previous decode
        chunk: one first-token sync per group (the prefill itself finished
        while the chunk ran), then the usual slot arming."""
        if not self._pending_admits:
            return
        pending, self._pending_admits = self._pending_admits, []
        self._pending_slots = 0
        self._pending_pages = 0
        for rec in pending:
            self._commit_group(rec, finished)

    def _commit_group(self, rec: tuple, finished: list[Request]) -> None:
        group, first, cache_k, draft_cache_k = rec
        tc0 = time.perf_counter()
        if self._chunk_in_flight:  # canary: committing mid-flight blocks
            self._m_inflight_syncs.inc()
        first_np = np.asarray(first)  # one sync per admitted group (= TTFT)
        now = time.perf_counter()
        for row, req in enumerate(group):
            p = req.params
            eos = self._eff_eos(req)
            first_i = int(first_np[row])
            req.tokens.append(first_i)
            req.first_token_time = now
            self.stats.tokens_generated += 1
            if (eos >= 0 and first_i == eos) or p.max_new_tokens <= 1:
                # finished at its first token: never touch the slot pool —
                # acquiring a slot just to release it would dispatch a full
                # template reset into a slot that was never written
                if self.flight is not None:
                    self.flight.emit("commit", rid=req.rid, slot=None,
                                     first=first_i, finished=True)
                self._finish(req, finished)
                continue
            slot = self.kv.acquire()
            self.kv.insert(slot, cache_k, self._cache_rows(req), row=row,
                           reserve=self._reserve_rows(req))
            if self.prefix is not None and req.embeds is None:
                # index this prompt's full pages (retention refs): the
                # next identical prefix maps them instead of recomputing
                self.prefix.register(req.prompt, self.kv.slot_pages(slot),
                                     self.kv)
            if self.draft_kv is not None:
                dslot = self.draft_kv.acquire()
                assert dslot == slot, "draft pool diverged from target pool"
                self.draft_kv.insert(slot, draft_cache_k, len(req.prompt),
                                     row=row,
                                     reserve=len(req.prompt) + p.max_new_tokens)
            keff = self._eff_keff(req)
            # full-prompt drafter history (shared-prefix rows included)
            prow, hl = spec_mod.seed_history(req.prompt, first_i,
                                             self.max_seq)
            (self._tok, self._active, self._rem, self._temp, self._topk,
             self._topp, self._eos, self._seeds, self._gens, self._keff,
             self._match, self._hist, self._hlen) = self._set_slot(
                self._tok, self._active, self._rem, self._temp, self._topk,
                self._topp, self._eos, self._seeds, self._gens, self._keff,
                self._match, self._hist, self._hlen, slot, first_i,
                p.max_new_tokens - 1, p.temperature, p.top_k, p.top_p, eos,
                self._eff_seed(req), keff, p.spec_accept == "match",
                jnp.asarray(prow), hl)
            self._active_host[slot] = True
            self._keff_host[slot] = keff
            req.state = RequestState.DECODING
            req.slot = slot
            self._running[slot] = req
            if self.flight is not None:
                self.flight.emit("commit", rid=req.rid, slot=slot,
                                 first=first_i, finished=False)
            if self.telemetry.enabled:
                # open-span decode lifecycle: closed by `_finish`, or
                # auto-closed at export if the request is abandoned
                req.spans.append(self.telemetry.tracer.begin(
                    f"req{req.rid}", "decode", t0=now, rid=req.rid))
        # the whole commit — sync, pool inserts, slot arming — is admission
        # work; leaving the arming loop outside the window misreports it as
        # host gap (it dominated host_overhead_fraction at bench scale)
        self.stats.prefill_seconds += time.perf_counter() - tc0

    def _start_extension(self, req: Request, m) -> None:
        """Begin an extension admission: acquire a slot, map the shared
        prefix pages (refcount++) and the fresh suffix pages into its block
        table — copying only a divergent tail page — and queue the slot
        for per-step suffix prefill (`_advance_prefill`).  No stripe
        scatter happens: shared pages are co-owned and must not be
        overwritten; fresh rows are written in-pool by `zoo.extend_step`."""
        now = time.perf_counter()
        req.state = RequestState.PREFILLING
        req.admit_time = now
        slot = self.kv.acquire()
        shared = m.page_ids if m is not None else []
        self.kv.map_slot(
            slot, shared, len(shared) * self.kv.page,
            self._reserve_rows(req),
            cow_src=m.cow_src if m is not None else None,
            cow_rows=m.cow_rows if m is not None else 0)
        if self.draft_kv is not None:
            # the draft pool acquires in lockstep NOW (so slot ids stay
            # aligned with the target pool); its stripe is prefilled in
            # one shot when the admission completes
            dslot = self.draft_kv.acquire()
            assert dslot == slot, "draft pool diverged from target pool"
        hit = m.total_rows if m is not None else 0
        req.prefix_hit_tokens = hit
        req.prefill_cursor = hit
        self.stats.prefix_hit_tokens += hit
        if hit:
            self._m_hit_tokens.inc(hit)
        req.slot = slot
        self._prefilling[slot] = req
        if self.flight is not None:
            # the prefix decision this admission rode: which pages were
            # mapped by reference, which page was CoW-copied, how many
            # prompt rows never re-prefill
            self.flight.emit(
                "ext_admit", rid=req.rid, slot=slot,
                shared=[int(p) for p in shared],
                cow_src=None if m is None else m.cow_src,
                cow_rows=0 if m is None else m.cow_rows, hit=hit)
        if self.telemetry.enabled:
            self._m_admit_wait.observe(req.admit_time - req.submit_time)
            self.telemetry.tracer.request_span(
                req, "queued", req.submit_time, req.admit_time)

    def _advance_prefill(self, finished: list[Request]) -> None:
        """One extension-prefill chunk for EVERY mid-admission slot, in a
        single batched dispatch: each prefilling lane writes its next
        `min(remaining, prefill_chunk)` suffix rows from its current
        position; every other lane's junk writes are swept in-jit
        (keep=0), so decode state is bitwise untouched.  Lanes whose
        suffix completes sample their first token from the chunk's last
        real row — same logits, same fold keys as monolithic prefill —
        and graduate to decode."""
        if not self._prefilling:
            return
        chunk_w = self.prefill_chunk or self.max_seq
        items = []
        for slot, req in self._prefilling.items():
            remaining = len(req.prompt) - req.prefill_cursor
            width = min(remaining, chunk_w)
            items.append((slot, req, width, width == remaining))
        w_max = max(w for _, _, w, _ in items)
        w_b = self._bucket_len(w_max, 0) if self.bucket else w_max
        sample = any(last for _, _, _, last in items)
        stochastic = any(last and req.params.temperature > 0
                         for _, req, _, last in items)
        s = self.max_slots
        tokens = np.zeros((s, w_b), np.int32)
        keep = np.zeros((s,), np.int32)
        temps = np.zeros((s,), np.float32)
        topks = np.zeros((s,), np.int32)
        topps = np.zeros((s,), np.float32)
        seeds = np.zeros((s,), np.int32)
        for slot, req, width, _ in items:
            cur = req.prefill_cursor
            tokens[slot, :width] = req.prompt[cur:cur + width]
            keep[slot] = width
            temps[slot] = req.params.temperature
            topks[slot] = req.params.top_k
            topps[slot] = req.params.top_p
            seeds[slot] = self._eff_seed(req)
        t0 = time.perf_counter()
        with self.telemetry.annotation("serve_prefill_chunk"):
            self.kv.cache, first = self._extend(w_b, sample, stochastic)(
                self.params, self.kv.cache, jnp.asarray(tokens),
                jnp.asarray(keep), self._key, jnp.asarray(seeds),
                jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(topps))
            if sample and self._chunk_in_flight:  # canary: see _commit_group
                self._m_inflight_syncs.inc()
            first_np = np.asarray(first) if sample else None  # one sync
        now = time.perf_counter()
        self.stats.prefill_seconds += now - t0
        n_lanes = len(items)
        self._m_chunks.inc(n_lanes)
        if self.telemetry.enabled:
            tr = self.telemetry.tracer
            self.telemetry.registry.histogram(
                "serve_prefill_chunk_seconds",
                labels={"bucket": str(w_b)}).observe(now - t0, n=n_lanes)
            tr.span("scheduler", f"prefill_chunk[b{w_b}]", t0, now,
                    lanes=n_lanes, bucket=w_b)
        for slot, req, width, last in items:
            req.prefill_cursor += width
            req.prefill_chunks += 1
            self.stats.prefill_chunks += 1
            self.stats.prefill_rows += width
            self.kv.slot_len[slot] += width
            if self.flight is not None:
                self.flight.emit("chunk", rid=req.rid, slot=slot,
                                 width=width, cursor=req.prefill_cursor,
                                 last=last)
            if self.telemetry.enabled:
                self.telemetry.tracer.request_span(
                    req, f"prefill_chunk[b{w_b}]", t0, now)
            if last:
                self._complete_admission(slot, req, int(first_np[slot]),
                                         now, finished)

    def _complete_admission(self, slot: int, req: Request, first_i: int,
                            now: float, finished: list[Request]) -> None:
        """Graduate a finished extension admission to decode: register its
        full prompt pages in the prefix index (retention refs — this is
        what a LATER identical prefix hits), prefill the draft stripe in
        one shot under spec, and arm the decode lane — or finish outright
        on a first-token EOS / single-token budget."""
        p = req.params
        eos = self._eff_eos(req)
        req.tokens.append(first_i)
        req.first_token_time = now
        self.stats.tokens_generated += 1
        del self._prefilling[slot]
        if self.prefix is not None:
            self.prefix.register(req.prompt, self.kv.slot_pages(slot),
                                 self.kv)
        if self.telemetry.enabled:
            self.telemetry.tracer.request_span(
                req, "prefill", req.admit_time, now,
                hit_tokens=req.prefix_hit_tokens, chunks=req.prefill_chunks)
        if (eos >= 0 and first_i == eos) or p.max_new_tokens <= 1:
            # finished at its first token: unlike the classic path this
            # slot exists (pages were mapped before prefill), so release
            # it — registered pages survive via the index's references
            if self.flight is not None:
                self.flight.emit("graduate", rid=req.rid, slot=slot,
                                 first=first_i, finished=True)
            self.kv.release(slot)
            if self.draft_kv is not None:
                self.draft_kv.release(slot)
            self._finish(req, finished)
            return
        if self.draft_kv is not None:
            n = len(req.prompt)
            s_b = self._bucket_len(n, 0) if self.bucket else n
            dtok = np.zeros((1, s_b), np.int32)
            dtok[0, :n] = req.prompt
            d_rows = jnp.asarray(np.asarray([n], np.int32)) if self.bucket else None
            dcache = self._draft_prefill(self._draft_params,
                                         jnp.asarray(dtok),
                                         self.draft_kv.template(1), d_rows)
            self.draft_kv.insert(slot, dcache, n, row=0,
                                 reserve=n + p.max_new_tokens)
        keff = self._eff_keff(req)
        # full-prompt drafter history: a prefix-shared admission prefilled
        # only its unshared suffix, but the n-gram corpus must still hold
        # the page-mapped prefix rows (spec_mod.seed_history's contract)
        prow, hl = spec_mod.seed_history(req.prompt, first_i, self.max_seq)
        (self._tok, self._active, self._rem, self._temp, self._topk,
         self._topp, self._eos, self._seeds, self._gens, self._keff,
         self._match, self._hist, self._hlen) = self._set_slot(
            self._tok, self._active, self._rem, self._temp, self._topk,
            self._topp, self._eos, self._seeds, self._gens, self._keff,
            self._match, self._hist, self._hlen, slot, first_i,
            p.max_new_tokens - 1, p.temperature, p.top_k, p.top_p, eos,
            self._eff_seed(req), keff, p.spec_accept == "match",
            jnp.asarray(prow), hl)
        self._active_host[slot] = True
        self._keff_host[slot] = keff
        req.state = RequestState.DECODING
        self._running[slot] = req
        if self.flight is not None:
            self.flight.emit("graduate", rid=req.rid, slot=slot,
                             first=first_i, finished=False)
        if self.telemetry.enabled:
            # open-span decode lifecycle, same contract as `_commit_group`
            req.spans.append(self.telemetry.tracer.begin(
                f"req{req.rid}", "decode", t0=now, rid=req.rid))

    def _overlap_admit(self, finished: list[Request]) -> None:
        """Double-buffered admission: called between a decode dispatch and
        its emit sync, while the chunk is still in flight on device.  The
        host prepares the next admission group (array building + prefill
        dispatch — `_admit_group` defers its sync under the in-flight
        flag) and starts extension admissions, all of which queue behind
        the chunk instead of serializing after it."""
        if not self.async_admission:
            return
        self._chunk_in_flight = True
        try:
            self._admit(finished)
        finally:
            self._chunk_in_flight = False

    def _release_slot(self, slot: int) -> None:
        self.kv.release(slot)
        if self.draft_kv is not None:
            self.draft_kv.release(slot)
        self._running.pop(slot)
        self._active_host[slot] = False
        self._keff_host[slot] = 0

    def _decode_and_harvest(self, finished: list[Request]) -> None:
        if not self._active_host.any():
            return
        if self.spec is not None:
            self._spec_decode_and_harvest(finished)
            return
        stochastic = any(r.params.temperature > 0 for r in self._running.values())
        t0 = time.perf_counter()
        if self.telemetry.enabled and self._last_sync is not None:
            self._m_host_gap.observe(t0 - self._last_sync)
        # while a slot is mid-chunked-prefill the chunk guards its rows:
        # inactive lanes' junk writes are swept in-jit (a fully-rejected
        # speculation for the protected lanes)
        guarded = bool(self._prefilling)
        protect = np.zeros((self.max_slots,), bool)
        if guarded:
            protect[list(self._prefilling)] = True
        with self.telemetry.annotation("serve_decode_chunk",
                                       step=self.stats.decode_steps):
            (self.kv.cache, self._tok, self._active, self._rem, self._gens,
             emits) = self._chunk(
                self.params, self.kv.cache, self._tok, self._active, self._rem,
                self._temp, self._topk, self._topp, self._eos, self._seeds,
                self._gens, self._key, jnp.asarray(protect),
                stochastic=stochastic, guarded=guarded)
            self._overlap_admit(finished)  # chunk in flight: prep admission
            emits = np.asarray(emits)             # (chunk, slots) — one sync
            active_np = np.asarray(self._active)
        t1 = time.perf_counter()
        self.stats.decode_seconds += t1 - t0
        self.stats.decode_steps += self.decode_chunk
        self.stats.step_time_hist.observe((t1 - t0) / self.decode_chunk,
                                          n=self.decode_chunk)
        if self.telemetry.enabled:
            self._m_step.observe((t1 - t0) / self.decode_chunk,
                                 n=self.decode_chunk)
            self.telemetry.tracer.span(
                "scheduler", "decode_chunk", t0, t1, steps=self.decode_chunk,
                lanes=int(self._active_host.sum()))
        self._last_sync = t1

        width = np.maximum((emits >= 0).sum(axis=1), 1)  # active lanes/step
        for slot, req in list(self._running.items()):
            col = emits[:, slot]
            mine = col >= 0
            new = col[mine].tolist()
            req.tokens.extend(new)
            req.shared_decode_steps += float((1.0 / width)[mine].sum())
            self.stats.tokens_generated += len(new)
            self.stats.decode_tokens += len(new)
            # slot_len = actual cache rows: prompt rows + one row per
            # decode-emitted token (each emitted token implies the step that
            # wrote the PREVIOUS token's KV; the newest token's row lands on
            # the step that feeds it back)
            self.kv.slot_len[slot] += len(new)
            cap = self.kv.slot_capacity(slot)
            assert self.kv.slot_len[slot] <= cap, (
                f"slot {slot}: {self.kv.slot_len[slot]} cache rows exceed "
                f"the {cap}-row reservation — accounting drift would "
                f"corrupt a neighbor page")
            if self.flight is not None and new:
                self.flight.emit("emit", rid=req.rid, slot=slot, tokens=new)
            if not active_np[slot]:
                self._finish(req, finished)
                self._release_slot(slot)

    def _spec_decode_and_harvest(self, finished: list[Request]) -> None:
        """Draft/verify decode: each cycle proposes k draft tokens per slot,
        verifies all of them with ONE target forward, commits the accepted
        prefix and rolls the rejected rows back — up to k+1 tokens per slot
        per packed-weight read.  Like the chunk loop, the only host sync is
        the stacked emit matrix once per step.  With `SpecConfig.fused`
        (default) all cycles additionally collapse into a single jitted
        `lax.scan` dispatch; `fused=False` keeps the per-cycle dispatch
        chain as the token-identical debugging fallback."""
        s_width = self.spec.k + 1
        cycles = self._spec_cycles
        stochastic = any(r.params.temperature > 0 for r in self._running.values())
        # static specialization: the rejection-sampling pipeline only
        # compiles in when some stochastic lane actually opted into it
        any_reject = any(r.params.temperature > 0
                         and r.params.spec_accept == "reject"
                         for r in self._running.values())
        tele = self.telemetry.enabled
        t0 = time.perf_counter()
        if tele and self._last_sync is not None:
            self._m_host_gap.observe(t0 - self._last_sync)
        dp0, da0 = self.stats.draft_proposed, self.stats.draft_accepted
        if self.spec.fused:
            # ONE dispatch runs all `cycles` draft/verify cycles device-
            # resident (draft cache carried through the scan); the only
            # sync stays the stacked emit matrix below
            with self.telemetry.annotation("serve_spec_fused",
                                           step=self.stats.decode_steps):
                if self.draft_kv is not None:
                    (self.kv.cache, self.draft_kv.cache, self._tok,
                     self._active, self._rem, self._gens, self._hist,
                     self._hlen, emits_dev, cnts_dev,
                     judged_dev) = self._spec_fused(
                        self.params, self._draft_params, self.kv.cache,
                        self.draft_kv.cache, self._tok, self._active,
                        self._rem, self._temp, self._topk, self._topp,
                        self._eos, self._seeds, self._gens, self._keff,
                        self._match, self._hist, self._hlen, self._key,
                        stochastic=stochastic, any_reject=any_reject)
                else:
                    (self.kv.cache, self._tok, self._active, self._rem,
                     self._gens, self._hist, self._hlen, emits_dev, cnts_dev,
                     judged_dev) = self._spec_fused(
                        self.params, self.kv.cache, self._tok, self._active,
                        self._rem, self._temp, self._topk, self._topp,
                        self._eos, self._seeds, self._gens, self._keff,
                        self._match, self._hist, self._hlen, self._key,
                        stochastic=stochastic, any_reject=any_reject)
            self._m_spec_dispatch.inc()
            self.kv.note_scan_rollbacks(cycles)
            if self.draft_kv is not None:
                self.draft_kv.note_scan_rollbacks(cycles)
            self._overlap_admit(finished)  # scan in flight: prep admission
        else:
            emits_acc, cnts_acc, judged_acc = [], [], []
            for _ in range(cycles):
                # the draft/verify split is dispatch-side wall time: the
                # only device sync stays the stacked emit matrix below, so
                # these windows attribute host/dispatch cost, with device
                # compute folded into whichever dispatch first blocks on it
                td0 = time.perf_counter()
                with self.telemetry.annotation("serve_spec_draft"):
                    if self.draft_kv is not None:
                        drafts, dpos0, self.draft_kv.cache = self._draft_propose(
                            self._draft_params, self.draft_kv.cache, self._tok)
                    else:
                        drafts = self._propose(self._hist, self._hlen, self._tok)
                        dpos0 = None
                td1 = time.perf_counter()
                # draft dispatch wall time is accounted on its own so the
                # bench's decode_step_us (target verify cost) and host-gap
                # columns don't each absorb it a second time
                self.stats.spec_draft_seconds += td1 - td0
                with self.telemetry.annotation("serve_spec_verify"):
                    (self.kv.cache, undo, pos0, emits, cnt, judged, self._tok,
                     self._active, self._rem, self._gens, self._hist,
                     self._hlen) = self._verify(
                        self.params, self.kv.cache, drafts, self._tok, self._active,
                        self._rem, self._temp, self._topk, self._topp, self._eos,
                        self._seeds, self._gens, self._keff, self._match, self._hist,
                        self._hlen, self._key, stochastic=stochastic,
                        any_reject=any_reject)
                    self.kv.rollback(pos0, cnt, s_width, undo=undo)
                    if dpos0 is not None:
                        self.draft_kv.rollback(dpos0, cnt, s_width)
                # unfused dispatch chain per cycle: draft + verify + target
                # rollback (+ draft rollback under a model drafter)
                self._m_spec_dispatch.inc(3 if dpos0 is None else 4)
                if tele:
                    td2 = time.perf_counter()
                    self._m_spec_draft.observe(td1 - td0)
                    self._m_spec_verify.observe(td2 - td1)
                    self.telemetry.tracer.span("scheduler", "spec_draft", td0, td1)
                    self.telemetry.tracer.span("scheduler", "spec_verify", td1, td2)
                emits_acc.append(emits)
                cnts_acc.append(cnt)
                judged_acc.append(judged)
            self._overlap_admit(finished)  # dispatches queued: prep admission
            emits_dev = jnp.stack(emits_acc)
            cnts_dev = jnp.stack(cnts_acc)
            judged_dev = jnp.stack(judged_acc)
        emits_np = np.asarray(emits_dev)   # (cycles, slots, k+1) — one sync
        cnts_np = np.asarray(cnts_dev)     # (cycles, slots)
        judged_np = np.asarray(judged_dev)  # (cycles, slots)
        active_np = np.asarray(self._active)
        t1 = time.perf_counter()
        self.stats.decode_seconds += t1 - t0
        self.stats.decode_steps += cycles
        self.stats.verify_steps += cycles
        self.stats.step_time_hist.observe((t1 - t0) / cycles, n=cycles)
        if tele:
            self._m_step.observe((t1 - t0) / cycles, n=cycles)
            self.telemetry.tracer.span(
                "scheduler", "spec_cycles", t0, t1, cycles=cycles,
                lanes=int(self._active_host.sum()))
        self._last_sync = t1

        # lanes that emitted in a cycle share that cycle's weight read
        width = np.maximum((cnts_np > 0).sum(axis=1), 1)
        for slot, req in list(self._running.items()):
            cnts = cnts_np[:, slot]
            rode = cnts > 0
            col = emits_np[:, slot, :].reshape(-1)
            new = col[col >= 0].tolist()
            # acceptance accounting counts only draft verdicts that reached
            # the stream (accepted drafts + an emitted correction's
            # rejection): drafts past an EOS or budget cut were never
            # judgeable, so counting them would misreport truncated cycles
            # as rejections (`judged` from spec.acceptance)
            proposed = int(judged_np[:, slot].sum())
            req.tokens.extend(new)
            req.shared_decode_steps += float((1.0 / width)[rode].sum())
            accepted = int(np.maximum(cnts - 1, 0).sum())
            req.spec_verify_steps += int(rode.sum())
            req.spec_proposed += proposed
            req.spec_accepted += accepted
            self.stats.lane_verify_steps += int(rode.sum())
            self.stats.draft_proposed += proposed
            self.stats.draft_accepted += accepted
            self.stats.tokens_generated += len(new)
            self.stats.decode_tokens += len(new)
            # one committed cache row per emitted token, same invariant as
            # the chunk loop (rollback already rewound the rejected rows)
            self.kv.slot_len[slot] += len(new)
            cap = self.kv.slot_capacity(slot)
            assert self.kv.slot_len[slot] <= cap, (
                f"slot {slot}: {self.kv.slot_len[slot]} cache rows exceed "
                f"the {cap}-row reservation — speculative rollback drifted")
            if self.flight is not None and (new or proposed):
                # per-window draft accounting next to the tokens it earned
                self.flight.emit("emit", rid=req.rid, slot=slot, tokens=new,
                                 proposed=proposed, accepted=accepted)
            if not active_np[slot]:
                self._finish(req, finished)
                self._release_slot(slot)
        if self.flight is not None:
            self.flight.emit("spec_window", cycles=cycles,
                             proposed=self.stats.draft_proposed - dp0,
                             accepted=self.stats.draft_accepted - da0)
        if tele:
            # per-window acceptance: this harvest's accepted/proposed ratio
            # (a drifting distribution here flags drafter quality decaying
            # over the workload, which the aggregate rate averages away)
            dp = self.stats.draft_proposed - dp0
            if dp:
                self._m_spec_accept.observe(
                    (self.stats.draft_accepted - da0) / dp)

    def step(self) -> list[Request]:
        """One scheduler iteration: admit into free slots (extension
        admissions map their shared pages and start chunking), advance
        every mid-prefill slot by one chunk, run one decode chunk,
        harvest. Returns requests that finished this step.

        With async admission (default under the continuous policy) the
        order double-buffers host work against device decode: groups
        whose prefill overlapped the PREVIOUS chunk commit first (their
        one sync — the prefill long finished), then the decode chunk
        dispatches and `_admit` prepares the NEXT group while it runs.

        Any exception escaping a step triggers the flight recorder's
        crash dump (pool state, block tables, refcounts, in-flight
        requests, event tail) and closes every open trace span, so the
        observability artifacts stay loadable exactly when they matter."""
        try:
            return self._step_inner()
        except Exception as exc:
            if self.flight is not None:
                self.flight.crash_dump(self, exc)
            self.telemetry.tracer.finalize()
            raise

    def _step_inner(self) -> list[Request]:
        finished: list[Request] = []
        if self.async_admission:
            self._commit_admissions(finished)
            self._advance_prefill(finished)
            if self._active_host.any():
                self._decode_and_harvest(finished)  # admits mid-flight
            else:
                # idle pool: nothing to overlap with — admit and commit
                # synchronously so fresh slots decode this very step
                self._admit(finished)
                self._commit_admissions(finished)
                self._decode_and_harvest(finished)
        else:
            self._admit(finished)
            self._advance_prefill(finished)
            self._decode_and_harvest(finished)
        return finished

    def run(self, requests: list[Request], max_steps: int = 1_000_000) -> list[Request]:
        """Drive a workload to completion. `Request.arrival` is the
        scheduler step at which a request reaches the queue (staggered
        arrivals for open-loop workloads)."""
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        done: list[Request] = []
        t = 0
        while pending or self.n_pending:
            while pending and pending[0].arrival <= t:
                self.submit(pending.pop(0))
            done.extend(self.step())
            t += 1
            if t > max_steps:
                raise RuntimeError("scheduler did not converge")
        return done
