"""Serving benchmarks: batching policy, paged KV pool, bucketed prefill.

Three comparisons on the same jitted decode machinery (serve.Scheduler):

  1. continuous vs static admission on a skewed staggered-arrival workload
     (one long request per static gang) — the structural utilization gap,
     not wall-clock noise, drives the speedup; the continuous row admits
     asynchronously (double-buffered against the in-flight decode chunk),
     and an `admission` section pins async vs sync throughput and the
     host-overhead fraction the overlap removes;
  2. paged pool vs PR 2 stripe pool on the same workload — KV pool bytes
     at the benchmark's occupancy (pages cover live tokens; stripes pin
     slots x max_seq) and the throughput cost of the page gather;
  3. exact vs bucketed admission prefill on a mixed-length workload
     (8 distinct prompt lengths) — the compile-count column: distinct
     prefill jits traced before vs after power-of-two bucketing.

  4. sharded vs single-device decode: the same continuous paged workload
     on a data mesh over every visible device (the CI multi-device job
     forces 4 fake host devices via XLA_FLAGS; locally this is usually a
     1-device mesh, which still exercises the sharded code path). Fake
     host devices share one CPU, so the column tracks sharding overhead
     and conformance, not real scaling.

  5. paged-attention kernel vs gather: decode step time with the Pallas
     block-table kernel (kernels/paged_attn) vs the pool[bt] gather path.
     Off-TPU the kernel runs under the Pallas interpreter, so that column
     is correctness-grade only; compiled numbers need a TPU.

  6. packed vs dense weights: the same workload served through hinm_spmm
     (PackedHiNM projections) vs the masked-dense fallback
     (``packed="dense"``) — weight bytes per decode token and step time.

  7. telemetry off vs on vs flight-recorder: the observability layer's
     decode-throughput cost (best-of-2 per mode, telemetry and recorder
     each asserted <= 3% when floors are active; both are off by
     default). The on-run dumps `BENCH_serve_metrics.json` (registry
     snapshot) and `BENCH_serve_trace.json` (Perfetto-loadable Chrome
     trace); a recording run dumps `BENCH_serve_flightrec.jsonl` and is
     replayed in-process — event- and token-identical, the determinism
     contract — before the record ships as a CI artifact. Every row also
     publishes p50/p99 TTFT, p50/p99 decode step time, a host-overhead
     fraction, and the raw step-time histogram snapshot that
     `benchmarks/roofline.py` restores for its measured-vs-analytic
     attainment column.

  8. traffic replay (``run_replay`` -> `BENCH_serve_replay.json`): a
     Poisson-arrival multi-tenant workload — many short requests sharing
     a long system-prompt prefix, a few long unshared requests — served
     with prefix sharing off / on / on+chunked prefill / on+sharded.
     Columns: goodput, prefix-hit-rate, live-page occupancy (peak +
     integrated page-steps), prefill rows computed, worst single-step
     prefill burst, p50/p99 TTFT.  Asserted (deterministic admission
     order): hit rate > 0, CoW exercised, sharing's live-page occupancy
     and prefill compute strictly below the no-sharing run, chunking
     bounds the worst per-step prefill burst to `chunk` rows per slot;
     hit-rate / occupancy / goodput floors vs the committed baseline.

Writes `BENCH_serve.json` (CI uploads it as an artifact; the paged pool
must come in at <= 0.5x the stripe pool bytes or the smoke run fails) and
prints the usual ``name,us_per_call,derived`` CSV rows.  When a committed
baseline JSON already exists, regression floors are asserted against it
(generous tok/s floors for noisy runners, firm byte floors); regenerate
baselines with ``REPRO_BENCH_NO_FLOORS=1``.
"""
from __future__ import annotations

import json
import time

import jax
import numpy as np

from benchmarks.common import emit

PAGE, N_PAGES = 16, 12  # pool provisioned for occupancy, not capacity


def _num(x: float):
    """NaN -> None so percentile columns survive strict JSON parsers."""
    return None if x != x else float(x)


def _json_hist(snap: dict) -> dict:
    from repro.serve.telemetry.metrics import _json_safe

    return _json_safe(snap)


def _workload(cfg, rng, n_requests: int, slots: int, prompt_len: int):
    from repro.serve import Request, SamplingParams

    reqs = []
    for i in range(n_requests):
        # one long request per `slots`-wide static gang, rest short
        new = 64 if i % slots == 0 else 8
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, (prompt_len,)).astype(np.int32),
            params=SamplingParams(max_new_tokens=new),
            arrival=i,  # staggered: one request per scheduler step
        ))
    return reqs


def _serve(cfg, params, reqs, policy: str, slots: int, max_seq: int,
           **sched_kw):
    from repro.serve import Request, SamplingParams, Scheduler

    sched = Scheduler(cfg, params, max_slots=slots, max_seq=max_seq,
                      decode_chunk=4, policy=policy, **sched_kw)
    return _drive(sched, reqs)


def _drive(sched, reqs):
    from repro.serve import Request, SamplingParams
    # warm the jitted kernels outside the timed region: the decode chunk,
    # and the admission prefill/insert for every group width 1..slots the
    # admission policy can form (one XLA trace per batch shape). The timed
    # region then measures scheduling, not compilation.
    for k in range(1, sched.max_slots + 1):
        warm = [Request(rid=-1 - i, prompt=reqs[0].prompt.copy(),
                        params=SamplingParams(max_new_tokens=2))
                for i in range(k)]
        sched.run(warm)
        sched.reset()
    t0 = time.perf_counter()
    sched.run(reqs)
    makespan = time.perf_counter() - t0
    st = sched.stats
    # host overhead: makespan not attributed to the timed prefill/decode
    # dispatch windows (admission bookkeeping, harvest, queue management).
    # decode_seconds already contains the unfused chain's draft dispatches
    # (spec_draft_seconds is a SLICE of it, not an addition), so the gap
    # subtracts each second exactly once.
    host_overhead = (max(0.0, makespan - st.prefill_seconds - st.decode_seconds)
                     / max(makespan, 1e-9))
    out = {
        "policy": sched.policy,
        "tokens": st.tokens_generated,
        "requests": st.requests_finished,
        "decode_steps": st.decode_steps,
        "makespan_seconds": makespan,
        "tokens_per_second": st.tokens_generated / max(makespan, 1e-9),
        "decode_tokens_per_second": st.decode_tokens_per_second,
        # per-step device time, net of the unfused spec chain's draft
        # dispatches — those are reported separately below, so a drafter
        # swap moves one column instead of silently skewing this one
        "decode_step_us": (1e6 * (st.decode_seconds - st.spec_draft_seconds)
                           / max(st.decode_steps, 1)),
        "weight_bytes_per_token": st.weight_bytes_per_token,
        "packed_param_bytes": st.packed_param_bytes,
        "dense_param_bytes": st.dense_param_bytes,
        "mean_ttft_seconds": float(np.nanmean([r.ttft for r in reqs])),
        # latency percentiles from the always-on ServeStats histograms
        # (exact at bench scale; NaN -> None so the JSON stays strict)
        "p50_ttft_seconds": _num(st.ttft_percentile(50)),
        "p99_ttft_seconds": _num(st.ttft_percentile(99)),
        "p50_decode_step_us": _num(1e6 * st.step_time_percentile(50)),
        "p99_decode_step_us": _num(1e6 * st.step_time_percentile(99)),
        "host_overhead_fraction": host_overhead,
        # full step-time histogram snapshot: roofline.py restores it to
        # compare measured step percentiles against the analytic model
        "decode_step_hist": _json_hist(st.step_time_hist.snapshot()),
        "kv_pool_bytes": sched.kv.pool_bytes(),
        "kv_paged": sched.kv.paged,
    }
    if sched.spec is not None:
        out.update(
            spec_k=sched.spec.k,
            spec_fused=sched.spec.fused,
            drafter=sched.drafter.kind,
            verify_steps=st.verify_steps,
            acceptance_rate=st.acceptance_rate,
            tokens_per_verify_step=st.tokens_per_verify_step,
            weight_bytes_per_accepted_token=st.weight_bytes_per_accepted_token,
            spec_draft_seconds=st.spec_draft_seconds,
            spec_dispatches=sched.telemetry.registry.counter(
                "serve_spec_dispatches").value,
        )
    return out


def _compile_counts(cfg, packed, rng, slots: int, max_seq: int) -> dict:
    """Distinct prefill jits for >= 8 distinct prompt lengths, exact vs
    bucketed admission. Arrivals are spaced so every request finds a free
    slot (groups of width 1): the count isolates the length axis."""
    from repro.serve import Request, SamplingParams, Scheduler

    lens = [5, 7, 9, 12, 16, 21, 30, 47]
    out = {}
    for mode, bucket in (("exact", False), ("bucketed", True)):
        sched = Scheduler(cfg, packed, max_slots=slots, max_seq=max_seq,
                          decode_chunk=4, page=PAGE, bucket=bucket)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab, (n,)).astype(np.int32),
                        params=SamplingParams(max_new_tokens=5), arrival=2 * i)
                for i, n in enumerate(lens)]
        sched.run(reqs)
        out[mode] = sched.telemetry.registry.counter("serve_prefill_traces").value
    out["distinct_lengths"] = len(lens)
    return out


def _baseline(path: str):
    """The committed benchmark JSON (pre-overwrite) as the floor baseline;
    None when absent or when ``REPRO_BENCH_NO_FLOORS`` is set (baseline
    regeneration mode)."""
    import os

    if os.environ.get("REPRO_BENCH_NO_FLOORS"):
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _assert_serve_floors(report: dict, base: dict) -> None:
    """CI regression floors against the committed BENCH_serve.json.

    Throughput floors are generous (shared CI runners are noisy); byte
    accounting is deterministic for a fixed workload, so those floors are
    firm. A legitimate re-baseline regenerates the committed file with
    ``REPRO_BENCH_NO_FLOORS=1 python -m benchmarks.run serve``."""
    cont, bcont = report["continuous"], base["continuous"]
    assert cont["tokens_per_second"] >= 0.2 * bcont["tokens_per_second"], (
        f"serve throughput collapsed: {cont['tokens_per_second']:.1f} tok/s "
        f"vs committed {bcont['tokens_per_second']:.1f}")
    assert (cont["weight_bytes_per_token"]
            <= 1.01 * bcont["weight_bytes_per_token"]), (
        "weight bytes per decode token regressed vs the committed baseline")
    assert report["kv_pool"]["ratio"] <= base["kv_pool"]["ratio"] + 1e-6, (
        "paged/stripe KV pool byte ratio regressed")
    # host overhead is the async-admission win this bench pins: allow an
    # absolute noise margin over the committed value, never a collapse
    # back to synchronous-admission territory
    assert (cont["host_overhead_fraction"]
            <= bcont["host_overhead_fraction"] + 0.04), (
        f"host overhead fraction regressed: "
        f"{cont['host_overhead_fraction']:.3f} vs committed "
        f"{bcont['host_overhead_fraction']:.3f}")
    if "admission" in base:
        adm, badm = report["admission"], base["admission"]
        assert adm["async_vs_sync"] >= 0.8 * badm["async_vs_sync"], (
            f"async/sync admission throughput ratio collapsed: "
            f"{adm['async_vs_sync']:.2f} vs committed "
            f"{badm['async_vs_sync']:.2f}")
    if "packed_weights" in base:
        pw, bpw = report["packed_weights"], base["packed_weights"]
        assert (pw["packed"]["packed_param_bytes"]
                <= bpw["packed"]["packed_param_bytes"]), (
            "packed parameter footprint grew vs the committed baseline")
        assert (pw["packed"]["weight_bytes_per_token"]
                < pw["dense"]["weight_bytes_per_token"]), (
            "packed serving no longer beats dense on weight bytes/token")
    if "telemetry" in report:
        tele = report["telemetry"]
        assert tele["overhead_fraction"] <= tele["budget_fraction"], (
            f"telemetry-on decode throughput cost "
            f"{100 * tele['overhead_fraction']:.1f}% exceeds the "
            f"{100 * tele['budget_fraction']:.0f}% budget "
            f"(off={tele['off_decode_tokens_per_second']:.1f} tok/s, "
            f"on={tele['on_decode_tokens_per_second']:.1f} tok/s)")
    if "flightrec" in report:
        fr = report["flightrec"]
        assert fr["overhead_fraction"] <= fr["budget_fraction"], (
            f"flight-recorder decode throughput cost "
            f"{100 * fr['overhead_fraction']:.1f}% exceeds the "
            f"{100 * fr['budget_fraction']:.0f}% budget "
            f"(off={fr['off_decode_tokens_per_second']:.1f} tok/s, "
            f"rec={fr['rec_decode_tokens_per_second']:.1f} tok/s)")


def _assert_spec_floors(report: dict, base: dict) -> None:
    for name in ("ngram", "self_draft"):
        row, brow = report[name], base[name]
        assert row["tokens_per_second"] >= 0.2 * brow["tokens_per_second"], (
            f"spec {name} throughput collapsed vs committed baseline")
        assert (report["bytes_per_token_ratio"][name]
                <= base["bytes_per_token_ratio"][name] * 1.05), (
            f"spec {name} bytes/accepted-token ratio regressed")
    # the fused-loop floors: speculation must actually pay wall-clock on
    # the drafter-friendly workload, and fusing must beat the per-cycle
    # dispatch chain (the whole point of the scan)
    assert report["spec_speedup"]["ngram"] >= 1.0, (
        f"ngram speculation no longer beats the non-speculative baseline "
        f"wall-clock: {report['spec_speedup']['ngram']:.2f}x")
    assert report["fused_vs_unfused"]["ngram"] >= 1.0, (
        f"the fused spec loop no longer beats the unfused dispatch chain: "
        f"{report['fused_vs_unfused']['ngram']:.2f}x")


def run(out_path: str = "BENCH_serve.json") -> dict:
    from repro.configs.base import load_arch
    from repro.models import zoo
    from repro.train import pruning

    base = _baseline(out_path)

    cfg = load_arch("qwen2_0_5b").reduced(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab=256, head_dim=32, max_seq=128)
    params = zoo.init(jax.random.PRNGKey(0), cfg)
    _, _, packed, _ = pruning.prune_model(params, cfg, ocp_iters=2, icp_iters=2)

    slots, n_requests, prompt_len, max_seq = 4, 12, 12, 128
    results = {}
    for policy in ("static", "continuous"):
        reqs = _workload(cfg, np.random.default_rng(0), n_requests, slots, prompt_len)
        results[policy] = _serve(cfg, packed, reqs, policy, slots, max_seq,
                                 page=PAGE, n_pages=N_PAGES)

    # paged vs stripe: same continuous workload, pool memory + throughput
    reqs = _workload(cfg, np.random.default_rng(0), n_requests, slots, prompt_len)
    stripe = _serve(cfg, packed, reqs, "continuous", slots, max_seq, page=None)
    paged = results["continuous"]
    kv_ratio = paged["kv_pool_bytes"] / max(stripe["kv_pool_bytes"], 1)
    assert kv_ratio <= 0.5, (
        f"paged pool {paged['kv_pool_bytes']}B exceeds 0.5x the stripe pool "
        f"{stripe['kv_pool_bytes']}B at benchmark occupancy")

    # sharded decode: page-axis pool sharding over every visible device
    from repro.launch.mesh import make_mesh

    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("data",))
    reqs = _workload(cfg, np.random.default_rng(0), n_requests, slots, prompt_len)
    sharded = _serve(cfg, packed, reqs, "continuous", slots, max_seq,
                     page=PAGE, n_pages=N_PAGES, mesh=mesh)
    sharded["n_devices"] = n_dev
    sharded_vs_single = (sharded["tokens_per_second"]
                         / max(paged["tokens_per_second"], 1e-9))

    # async (double-buffered) vs synchronous admission: the continuous row
    # above already admits asynchronously ("auto" resolves on under the
    # continuous policy — prepare + prefill dispatch overlap the in-flight
    # decode chunk, the blocking first-token sync lands at the next step
    # boundary); this row pins what the overlap buys
    reqs = _workload(cfg, np.random.default_rng(0), n_requests, slots,
                     prompt_len)
    sync_row = _serve(cfg, packed, reqs, "continuous", slots, max_seq,
                      page=PAGE, n_pages=N_PAGES, async_admission=False)
    async_vs_sync = (paged["tokens_per_second"]
                     / max(sync_row["tokens_per_second"], 1e-9))

    # paged-attention kernel vs gather: the same continuous paged workload
    # with the decode attention resolved by the Pallas kernel vs the
    # pool[bt] gather path. Off-TPU the kernel runs under the Pallas
    # interpreter, so the step-time column is correctness-grade only
    # (interpreter overhead dominates); compiled numbers need a TPU.
    from repro.kernels.ops import _on_tpu
    from repro.perf_knobs import knobs

    kbackend = "pallas" if _on_tpu() else "interpret"
    n_kreq = 6
    with knobs(paged_attn="off"):
        kern_off = _serve(cfg, packed,
                          _workload(cfg, np.random.default_rng(2), n_kreq,
                                    slots, prompt_len),
                          "continuous", slots, max_seq,
                          page=PAGE, n_pages=N_PAGES)
    with knobs(paged_attn=kbackend):
        kern_on = _serve(cfg, packed,
                         _workload(cfg, np.random.default_rng(2), n_kreq,
                                   slots, prompt_len),
                         "continuous", slots, max_seq,
                         page=PAGE, n_pages=N_PAGES)
    kern_ratio = kern_on["decode_step_us"] / max(kern_off["decode_step_us"],
                                                 1e-9)

    # packed HiNM weights vs dense fallback: identical workload and
    # numerics (the fallback unpacks to masked-dense), so the bytes/token
    # column is the paper's packed-read saving and the latency column is
    # the backend's spmm-vs-dense cost on this host
    reqs = _workload(cfg, np.random.default_rng(0), n_requests, slots,
                     prompt_len)
    dense_row = _serve(cfg, packed, reqs, "continuous", slots, max_seq,
                       page=PAGE, n_pages=N_PAGES, packed="dense")
    packed_row = results["continuous"]  # params served packed as handed in
    assert packed_row["packed_param_bytes"] < dense_row["packed_param_bytes"], (
        "packed serving did not shrink the parameter footprint")
    assert (packed_row["weight_bytes_per_token"]
            < dense_row["weight_bytes_per_token"]), (
        "packed serving did not cut weight bytes per decode token")

    # telemetry overhead: the same continuous workload served with the
    # observability layer off vs fully on (wall-clock histograms + span
    # recording + KV gauges). Best-of-2 per mode damps runner noise; the
    # on-run's metrics snapshot and Chrome trace become the CI artifacts.
    # Sync admission here: with async admission the decode window absorbs
    # the overlapped admission work (prepare runs under the in-flight
    # chunk, which on a shared-core CPU runner is real contention), and
    # how admissions interleave varies run to run — that variance would
    # swamp the 3% budget this compare isolates. The async columns live
    # in report["admission"].
    from repro.serve import Telemetry

    tele_rows = {}
    tele_bundles = []
    for mode in ("off", "on", "rec"):
        # "rec": flight recorder on with telemetry off — the recorder is
        # off by default in production, and this isolates its own decode
        # cost (one event dict per host decision) under the same budget
        best = None
        for _ in range(2):
            tele = Telemetry(enabled=(mode == "on"))
            row = _serve(cfg, packed,
                         _workload(cfg, np.random.default_rng(0), n_requests,
                                   slots, prompt_len),
                         "continuous", slots, max_seq,
                         page=PAGE, n_pages=N_PAGES, telemetry=tele,
                         async_admission=False, flightrec=(mode == "rec"))
            if best is None or (row["decode_tokens_per_second"]
                                > best["decode_tokens_per_second"]):
                best = row
                if mode == "on":
                    tele_bundles = [tele]
        tele_rows[mode] = best
    tele_overhead = max(0.0, 1.0 - (tele_rows["on"]["decode_tokens_per_second"]
                                    / max(tele_rows["off"]["decode_tokens_per_second"],
                                          1e-9)))
    rec_overhead = max(0.0, 1.0 - (tele_rows["rec"]["decode_tokens_per_second"]
                                   / max(tele_rows["off"]["decode_tokens_per_second"],
                                         1e-9)))
    tele_bundles[0].dump_metrics("BENCH_serve_metrics.json")
    tele_bundles[0].dump_trace("BENCH_serve_trace.json")

    # record + replay: the recorder's determinism contract on the bench
    # workload — rebuilding the workload from the record and re-driving a
    # fresh scheduler must reproduce every event and every token; the
    # record ships as a CI artifact next to the metrics/trace dumps
    from repro.serve import Scheduler
    from repro.serve import replay as replay_record

    rec_kw = dict(max_slots=slots, max_seq=max_seq, decode_chunk=4,
                  policy="continuous", page=PAGE, n_pages=N_PAGES,
                  flightrec=True)
    rec_sched = Scheduler(cfg, packed, **rec_kw)
    rec_sched.run(_workload(cfg, np.random.default_rng(0), n_requests,
                            slots, prompt_len))
    rec_sched.flight.dump("BENCH_serve_flightrec.jsonl")
    replay_record("BENCH_serve_flightrec.jsonl",
                  Scheduler(cfg, packed, **rec_kw)).assert_equal()

    compiles = _compile_counts(cfg, packed, np.random.default_rng(1), 8, max_seq)
    assert compiles["bucketed"] <= 4, (
        f"{compiles['distinct_lengths']} prompt lengths compiled "
        f"{compiles['bucketed']} bucketed prefill variants (> 4)")

    speedup = (results["continuous"]["tokens_per_second"]
               / max(results["static"]["tokens_per_second"], 1e-9))
    step_ratio = (results["static"]["decode_steps"]
                  / max(results["continuous"]["decode_steps"], 1))
    report = {
        "shape": {"arch": "qwen2_0_5b.reduced", "d_model": cfg.d_model,
                  "n_layers": cfg.n_layers, "vocab": cfg.vocab,
                  "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
                  "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
                  "max_seq": max_seq, "page": PAGE,
                  "slots": slots, "n_requests": n_requests,
                  "prompt_len": prompt_len},
        "static": results["static"],
        "continuous": results["continuous"],
        "throughput_speedup": speedup,
        "decode_step_ratio": step_ratio,
        "stripe_continuous": stripe,
        "kv_pool": {
            "page": PAGE,
            "n_pages": N_PAGES,
            "paged_bytes": paged["kv_pool_bytes"],
            "stripe_bytes": stripe["kv_pool_bytes"],
            "ratio": kv_ratio,
        },
        "prefill_compiles": compiles,
        "paged_attn_kernel": {
            "backend": kbackend,
            "timing_grade": ("compiled" if kbackend == "pallas"
                             else "interpreter-correctness-only"),
            "gather": {k: kern_off[k] for k in
                       ("decode_step_us", "decode_tokens_per_second",
                        "tokens_per_second")},
            "kernel": {k: kern_on[k] for k in
                       ("decode_step_us", "decode_tokens_per_second",
                        "tokens_per_second")},
            "kernel_vs_gather_step_time": kern_ratio,
        },
        "packed_weights": {
            "packed": {k: packed_row[k] for k in
                       ("packed_param_bytes", "dense_param_bytes",
                        "weight_bytes_per_token", "tokens_per_second",
                        "decode_step_us")},
            "dense": {k: dense_row[k] for k in
                      ("packed_param_bytes", "weight_bytes_per_token",
                       "tokens_per_second", "decode_step_us")},
            "bytes_per_token_ratio": (packed_row["weight_bytes_per_token"]
                                      / dense_row["weight_bytes_per_token"]),
        },
        "sharded": {
            "n_devices": n_dev,
            "tokens_per_second": sharded["tokens_per_second"],
            "single_device_tokens_per_second": paged["tokens_per_second"],
            "vs_single_device": sharded_vs_single,
            "kv_pool_bytes": sharded["kv_pool_bytes"],
        },
        "admission": {
            "async": {k: paged[k] for k in
                      ("tokens_per_second", "host_overhead_fraction",
                       "mean_ttft_seconds")},
            "sync": {k: sync_row[k] for k in
                     ("tokens_per_second", "host_overhead_fraction",
                      "mean_ttft_seconds")},
            "async_vs_sync": async_vs_sync,
        },
        "telemetry": {
            "off_decode_tokens_per_second":
                tele_rows["off"]["decode_tokens_per_second"],
            "on_decode_tokens_per_second":
                tele_rows["on"]["decode_tokens_per_second"],
            "overhead_fraction": tele_overhead,
            "budget_fraction": 0.03,
            "p50_ttft_seconds": tele_rows["on"]["p50_ttft_seconds"],
            "p99_ttft_seconds": tele_rows["on"]["p99_ttft_seconds"],
            "p99_decode_step_us": tele_rows["on"]["p99_decode_step_us"],
            "host_overhead_fraction":
                tele_rows["on"]["host_overhead_fraction"],
            "artifacts": ["BENCH_serve_metrics.json",
                          "BENCH_serve_trace.json"],
        },
        "flightrec": {
            "off_decode_tokens_per_second":
                tele_rows["off"]["decode_tokens_per_second"],
            "rec_decode_tokens_per_second":
                tele_rows["rec"]["decode_tokens_per_second"],
            "overhead_fraction": rec_overhead,
            "budget_fraction": 0.03,
            "events": rec_sched.flight.seq,
            "replay_ok": True,  # assert_equal above would have raised
            "artifacts": ["BENCH_serve_flightrec.jsonl"],
        },
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)

    for policy in ("static", "continuous"):
        r = results[policy]
        emit(f"serve_{policy}", r["makespan_seconds"] * 1e6 / max(r["tokens"], 1),
             f"tok/s={r['tokens_per_second']:.1f} steps={r['decode_steps']}")
    emit("serve_speedup", 0.0,
         f"continuous/static={speedup:.2f}x step_ratio={step_ratio:.2f}x")
    emit("serve_paged_pool", 0.0,
         f"paged/stripe_bytes={kv_ratio:.3f} "
         f"paged_tok/s={paged['tokens_per_second']:.1f} "
         f"stripe_tok/s={stripe['tokens_per_second']:.1f}")
    emit("serve_prefill_compiles", 0.0,
         f"exact={compiles['exact']} bucketed={compiles['bucketed']} "
         f"lengths={compiles['distinct_lengths']}")
    emit("serve_sharded", 0.0,
         f"devices={n_dev} tok/s={sharded['tokens_per_second']:.1f} "
         f"vs_single={sharded_vs_single:.2f}x")
    emit("serve_admission", 0.0,
         f"async_tok/s={paged['tokens_per_second']:.1f} "
         f"sync_tok/s={sync_row['tokens_per_second']:.1f} "
         f"async_vs_sync={async_vs_sync:.2f}x "
         f"host_overhead={paged['host_overhead_fraction']:.3f}"
         f"(sync={sync_row['host_overhead_fraction']:.3f})")
    emit("serve_paged_attn", kern_on["decode_step_us"],
         f"backend={kbackend} gather_step_us={kern_off['decode_step_us']:.0f} "
         f"kernel_step_us={kern_on['decode_step_us']:.0f} "
         f"kernel/gather={kern_ratio:.2f}x")
    emit("serve_packed_weights", packed_row["decode_step_us"],
         f"bytes/tok packed={packed_row['weight_bytes_per_token']:.0f} "
         f"dense={dense_row['weight_bytes_per_token']:.0f} "
         f"packed_tok/s={packed_row['tokens_per_second']:.1f} "
         f"dense_tok/s={dense_row['tokens_per_second']:.1f}")
    r = results["continuous"]
    emit("serve_latency", 0.0,
         f"p50_ttft_ms={1e3 * (r['p50_ttft_seconds'] or 0):.1f} "
         f"p99_ttft_ms={1e3 * (r['p99_ttft_seconds'] or 0):.1f} "
         f"p99_step_us={r['p99_decode_step_us'] or 0:.0f} "
         f"host_overhead={r['host_overhead_fraction']:.3f}")
    emit("serve_telemetry", 0.0,
         f"off_tok/s={tele_rows['off']['decode_tokens_per_second']:.1f} "
         f"on_tok/s={tele_rows['on']['decode_tokens_per_second']:.1f} "
         f"overhead={tele_overhead:.4f} budget=0.03")
    emit("serve_flightrec", 0.0,
         f"off_tok/s={tele_rows['off']['decode_tokens_per_second']:.1f} "
         f"rec_tok/s={tele_rows['rec']['decode_tokens_per_second']:.1f} "
         f"overhead={rec_overhead:.4f} budget=0.03 "
         f"events={rec_sched.flight.seq} replay=ok")
    if base is not None:
        _assert_serve_floors(report, base)
    return report


def run_spec(out_path: str = "BENCH_spec.json") -> dict:
    """Speculative decoding vs the chunked baseline (`BENCH_spec.json`).

    A repetitive-prompt workload (a 4-token pattern tiled, the generation
    itself settles into loops a prompt-lookup drafter can predict) decoded
    four ways on the same paged pool: non-speculative baseline, n-gram
    drafter through the fused draft/verify scan, the same drafter through
    the unfused per-cycle dispatch chain, and a self-drafting ModelDrafter
    (draft == target, the acceptance-1.0 upper bound that pins the stats
    algebra).  CI asserts: tokens identical to the baseline, acceptance-
    weighted tokens-per-verify-step > 1 for the drafters, a proportional
    drop in packed-weight bytes per accepted token, and (vs the committed
    baseline) the wall-clock floors `ngram >= 1.0x baseline` and
    `fused >= unfused`."""
    import jax

    from repro.configs.base import load_arch
    from repro.models import zoo
    from repro.serve import (ModelDrafter, Request, SamplingParams, Scheduler,
                             SpecConfig)
    from repro.train import pruning

    base = _baseline(out_path)
    cfg = load_arch("qwen2_0_5b").reduced(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab=256, head_dim=32, max_seq=128)
    params = zoo.init(jax.random.PRNGKey(0), cfg)
    _, _, packed, _ = pruning.prune_model(params, cfg, ocp_iters=2, icp_iters=2)

    # 64 new tokens per request: long enough that the generation's
    # repetitive steady-state (which the prompt-lookup drafter predicts
    # well) dominates the low-acceptance warmup tokens — the wall-clock
    # floor `ngram >= baseline` is measured where speculation should win
    slots, n_requests, max_new, max_seq, k = 4, 8, 64, 128, 4
    rng = np.random.default_rng(0)
    pat = rng.integers(0, cfg.vocab, (4,)).astype(np.int32)

    def workload():
        return [Request(rid=i,
                        prompt=np.tile(np.roll(pat, i % 4), 6).astype(np.int32),
                        params=SamplingParams(max_new_tokens=max_new),
                        arrival=i)
                for i in range(n_requests)]

    def case(spec, runs=2):
        # best-of-N damps runner noise on the wall-clock columns the
        # fused-vs-unfused and spec-vs-baseline floors compare; tokens
        # must not move between repeats (greedy = deterministic)
        best, toks = None, None
        for _ in range(runs):
            reqs = workload()
            # sharing off: the tiled prompts repeat across requests, and a
            # prefix hit would shrink the prefill this benchmark isolates
            # speculation against (run_replay owns the sharing columns)
            sched = Scheduler(cfg, packed, max_slots=slots, max_seq=max_seq,
                              decode_chunk=4, page=PAGE, n_pages=24,
                              spec=spec, prefix_share=False)
            row = _drive(sched, reqs)
            t = [r.tokens for r in reqs]
            assert toks is None or t == toks
            toks = t
            if best is None or (row["tokens_per_second"]
                                > best["tokens_per_second"]):
                best = row
        return best, toks

    base_row, base_toks = case(None)
    ngram_row, ngram_toks = case(SpecConfig(k=k, drafter="ngram"))
    unfused_row, unfused_toks = case(
        SpecConfig(k=k, drafter="ngram", fused=False))
    self_row, self_toks = case(
        SpecConfig(k=k, drafter=ModelDrafter(cfg, packed)), runs=1)

    # the serving contract survives speculation: tokens are identical
    assert ngram_toks == base_toks, "ngram spec decode changed tokens"
    assert unfused_toks == base_toks, "unfused spec decode changed tokens"
    assert self_toks == base_toks, "self-draft spec decode changed tokens"
    # the fused scan actually fused (one dispatch per step, covering all
    # of that step's cycles) and the unfused chain actually did not
    assert ngram_row["spec_dispatches"] < ngram_row["verify_steps"]
    assert unfused_row["spec_dispatches"] >= 2 * unfused_row["verify_steps"]
    assert ngram_row["spec_draft_seconds"] == 0.0
    assert unfused_row["spec_draft_seconds"] > 0.0
    # acceptance-weighted tokens per verify must beat 1 (else speculation
    # never pays), and the packed-weight read per accepted token must drop
    # proportionally vs the baseline's per-chunk-step read
    for row in (ngram_row, self_row):
        assert row["tokens_per_verify_step"] > 1.0, row
        assert (row["weight_bytes_per_accepted_token"]
                < base_row["weight_bytes_per_token"]), row
    assert self_row["acceptance_rate"] == 1.0  # draft == target upper bound

    report = {
        "shape": {"arch": "qwen2_0_5b.reduced", "d_model": cfg.d_model,
                  "n_layers": cfg.n_layers, "vocab": cfg.vocab,
                  "slots": slots, "n_requests": n_requests,
                  "max_new_tokens": max_new, "spec_k": k},
        "baseline": base_row,
        "ngram": ngram_row,
        "ngram_unfused": unfused_row,
        "self_draft": self_row,
        "bytes_per_token_ratio": {
            "ngram": (ngram_row["weight_bytes_per_accepted_token"]
                      / base_row["weight_bytes_per_token"]),
            "self_draft": (self_row["weight_bytes_per_accepted_token"]
                           / base_row["weight_bytes_per_token"]),
        },
        # wall-clock, not bytes: speculation vs the non-speculative
        # baseline, and the fused scan vs the per-cycle dispatch chain
        "spec_speedup": {
            "ngram": (ngram_row["tokens_per_second"]
                      / max(base_row["tokens_per_second"], 1e-9)),
            "self_draft": (self_row["tokens_per_second"]
                           / max(base_row["tokens_per_second"], 1e-9)),
        },
        "fused_vs_unfused": {
            "ngram": (ngram_row["tokens_per_second"]
                      / max(unfused_row["tokens_per_second"], 1e-9)),
        },
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)

    for name, row in (("baseline", base_row), ("ngram", ngram_row),
                      ("ngram_unfused", unfused_row),
                      ("self_draft", self_row)):
        tps = row.get("tokens_per_verify_step", 1.0)
        acc = row.get("acceptance_rate", 0.0)
        emit(f"serve_spec_{name}",
             row["makespan_seconds"] * 1e6 / max(row["tokens"], 1),
             f"tok/s={row['tokens_per_second']:.1f} "
             f"tok/verify={tps:.2f} accept={acc:.3f} "
             f"bytes/tok={row.get('weight_bytes_per_accepted_token', row['weight_bytes_per_token']):.0f}")
    emit("serve_spec_fusion", 0.0,
         f"ngram_vs_baseline={report['spec_speedup']['ngram']:.2f}x "
         f"fused_vs_unfused={report['fused_vs_unfused']['ngram']:.2f}x "
         f"fused_dispatches={ngram_row['spec_dispatches']} "
         f"unfused_dispatches={unfused_row['spec_dispatches']} "
         f"cycles={ngram_row['verify_steps']}")
    if base is not None:
        _assert_spec_floors(report, base)
    return report


def _replay_workload(cfg, scale: float):
    """Deterministic Poisson-arrival multi-tenant mix: `n_short` short
    completions over one shared system prefix (two full pages + a shared
    tail -> full-page hits and CoW), plus a few long unshared requests
    whose monolithic prefill would block co-resident decode."""
    from repro.serve import Request, SamplingParams

    rng = np.random.default_rng(7)
    n_short = max(4, int(12 * scale))
    n_long = max(1, int(3 * scale))
    # shorts must decode long enough to overlap (arrival gap ~1.7 steps):
    # only CO-RESIDENT sharers shrink live pages — a lone sharer still
    # maps pages_needed(reserve) pages, just prefills fewer rows
    short_new = max(12, int(24 * scale))
    long_new = max(8, int(16 * scale))
    system = rng.integers(0, cfg.vocab, (2 * PAGE + 8,)).astype(np.int32)
    reqs = []
    for i in range(n_short + n_long):
        if i % ((n_short + n_long) // n_long + 1) == 0 and n_long > 0:
            prompt = rng.integers(0, cfg.vocab, (3 * PAGE,)).astype(np.int32)
            new = long_new
        else:
            # tail long enough that page 2 (system tail rows + private
            # suffix) fills -> indexed -> later twins CoW its shared head
            tail = rng.integers(0, cfg.vocab, (10,)).astype(np.int32)
            prompt = np.concatenate([system, tail])
            new = short_new
        reqs.append(Request(rid=i, prompt=prompt,
                            params=SamplingParams(max_new_tokens=new)))
    # Poisson process in scheduler steps: geometric inter-arrival gaps
    gaps = rng.geometric(0.6, size=len(reqs))
    arrivals = np.cumsum(gaps) - gaps[0]
    order = rng.permutation(len(reqs))
    for r, t in zip(reqs, arrivals[np.argsort(order)]):
        r.arrival = int(t)
    return reqs


def _drive_replay(sched, reqs):
    """Step the scheduler manually so pool occupancy can be sampled at
    every step.  Occupancy counts LIVE pages — distinct pages mapped by
    resident slots' block tables; retained prefix pages are reclaimable
    cache (evicted under pressure), not demand, so counting them would
    charge the cache for existing.  `live_page_steps` integrates live
    pages over the whole replay (page-steps): sharing shrinks it even
    when the single peak step happens to be dominated by unshared longs."""
    pending = sorted(reqs, key=lambda r: (r.arrival, r.rid))
    peak_pages, page_steps, t = 0, 0, 0
    max_prefill_rows_step, step_walls = 0, []
    t0 = time.perf_counter()
    while pending or sched.n_pending:
        while pending and pending[0].arrival <= t:
            sched.submit(pending.pop(0))
        rows_before = sched.stats.prefill_rows
        s0 = time.perf_counter()
        sched.step()
        step_walls.append(time.perf_counter() - s0)
        max_prefill_rows_step = max(
            max_prefill_rows_step, sched.stats.prefill_rows - rows_before)
        live = sched.kv.n_live_pages
        peak_pages = max(peak_pages, live)
        page_steps += live
        t += 1
    makespan = time.perf_counter() - t0
    st = sched.stats
    return {
        "tokens": st.tokens_generated,
        "requests": st.requests_finished,
        "makespan_seconds": makespan,
        "goodput_tokens_per_second": st.tokens_generated / max(makespan, 1e-9),
        "prefix_hit_tokens": st.prefix_hit_tokens,
        "prefill_rows": st.prefill_rows,
        "prefill_chunks": st.prefill_chunks,
        "prefix_hit_rate": st.prefix_hit_rate,
        "peak_live_pages": peak_pages,
        "live_page_steps": page_steps,
        "max_prefill_rows_step": max_prefill_rows_step,
        "p99_step_seconds": _num(np.percentile(step_walls, 99)),
        "pool_pages": sched.kv.n_alloc_pages,
        "cow_copies": sched.kv.cow_copies,
        "p50_ttft_seconds": _num(st.ttft_percentile(50)),
        "p99_ttft_seconds": _num(st.ttft_percentile(99)),
        "prefix_share": sched.prefix_share,
        "prefill_chunk": sched.prefill_chunk,
    }


def _assert_replay_floors(report: dict, base: dict) -> None:
    """Floors vs the committed BENCH_serve_replay.json: admission order is
    deterministic, so the sharing/memory columns get firm floors; only
    wall-clock goodput gets the generous noisy-runner margin."""
    row, brow = report["sharing"], base["sharing"]
    assert (row["goodput_tokens_per_second"]
            >= 0.2 * brow["goodput_tokens_per_second"]), (
        "replay goodput collapsed vs the committed baseline")
    assert row["prefix_hit_rate"] >= brow["prefix_hit_rate"] - 1e-6, (
        f"prefix hit rate regressed: {row['prefix_hit_rate']:.3f} vs "
        f"committed {brow['prefix_hit_rate']:.3f}")
    assert (report["live_pages_ratio"]
            <= base["live_pages_ratio"] + 1e-6), (
        "sharing/no-sharing live page occupancy ratio regressed")
    assert (report["prefill_rows_ratio"]
            <= base["prefill_rows_ratio"] + 1e-6), (
        "sharing/no-sharing prefill compute ratio regressed")


def run_replay(out_path: str = "BENCH_serve_replay.json") -> dict:
    import os

    from repro.launch.mesh import make_mesh
    from repro.configs.base import load_arch
    from repro.models import zoo
    from repro.train import pruning

    base = _baseline(out_path)
    scale = float(os.environ.get("REPRO_BENCH_REPLAY_SCALE", "1.0"))

    cfg = load_arch("qwen2_0_5b").reduced(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab=256, head_dim=32, max_seq=128)
    params = zoo.init(jax.random.PRNGKey(0), cfg)
    _, _, packed, _ = pruning.prune_model(params, cfg, ocp_iters=2, icp_iters=2)

    slots, max_seq, n_pages = 4, 128, 20
    chunk = PAGE

    def case(mesh=None, **sched_kw):
        from repro.serve import Scheduler

        sched = Scheduler(cfg, packed, max_slots=slots, max_seq=max_seq,
                          decode_chunk=4, page=PAGE, n_pages=n_pages,
                          mesh=mesh, **sched_kw)
        # warm the decode/prefill/extension jits outside the timed region
        warm = _replay_workload(cfg, scale)
        for r in warm:
            r.arrival = 0
        sched.run(warm)
        sched.reset()
        return _drive_replay(sched, _replay_workload(cfg, scale))

    rows = {
        "no_sharing": case(prefix_share=False),
        "sharing": case(prefix_share=True),
        "sharing_chunked": case(prefix_share=True, prefill_chunk=chunk),
    }
    n_dev = len(jax.devices())
    rows["sharing_sharded"] = case(
        mesh=make_mesh((n_dev,), ("data",)), prefix_share=True)
    rows["sharing_sharded"]["n_devices"] = n_dev

    # the workload shares by construction: the sharing run must hit, copy
    # divergent tails, and strictly cut both live page occupancy and
    # prefill compute vs the identical no-sharing replay (deterministic
    # admission)
    share, nosh = rows["sharing"], rows["no_sharing"]
    assert share["prefix_hit_rate"] > 0, "replay workload never hit"
    assert share["cow_copies"] > 0, "replay workload never exercised CoW"
    pages_ratio = share["live_page_steps"] / max(nosh["live_page_steps"], 1)
    rows_ratio = share["prefill_rows"] / max(nosh["prefill_rows"], 1)
    assert pages_ratio < 1.0, (
        f"sharing did not reduce live page occupancy: "
        f"{share['live_page_steps']} page-steps "
        f"vs {nosh['live_page_steps']} unshared")
    assert rows_ratio < 1.0, (
        f"sharing did not reduce prefill compute: {share['prefill_rows']} "
        f"rows vs {nosh['prefill_rows']} unshared")
    # chunking bounds per-step prefill work (the co-resident latency
    # spike), deterministically: each mid-prefill slot advances at most
    # `chunk` rows per step (the batched advance covers every prefilling
    # slot, so the aggregate bound is chunk * slots), vs the unchunked
    # run's monolithic long prefills.  The chunked request's OWN first
    # token arrives later by construction (its prefill interleaves with
    # decode chunks), so p99 TTFT is reported, not asserted — the
    # latency-shape win is the per-step bound.
    chunked = rows["sharing_chunked"]
    assert chunked["prefill_chunks"] > 0
    assert chunked["max_prefill_rows_step"] <= chunk * slots, (
        f"chunked prefill exceeded the per-step bound: "
        f"{chunked['max_prefill_rows_step']} rows > "
        f"chunk*slots={chunk * slots}")
    assert (chunked["max_prefill_rows_step"]
            < share["max_prefill_rows_step"]), (
        "chunking did not shrink the worst per-step prefill burst: "
        f"{chunked['max_prefill_rows_step']} vs "
        f"{share['max_prefill_rows_step']} unchunked")
    assert rows["sharing_sharded"]["prefix_hit_rate"] > 0

    report = {
        "shape": {"arch": "qwen2_0_5b.reduced", "d_model": cfg.d_model,
                  "n_layers": cfg.n_layers, "vocab": cfg.vocab,
                  "max_seq": max_seq, "page": PAGE, "n_pages": n_pages,
                  "slots": slots, "prefill_chunk": chunk,
                  "replay_scale": scale,
                  "n_requests": len(_replay_workload(cfg, scale))},
        **rows,
        "live_pages_ratio": pages_ratio,
        "prefill_rows_ratio": rows_ratio,
        "chunked_vs_unchunked_p99_ttft": (
            (rows["sharing_chunked"]["p99_ttft_seconds"] or 0)
            / max(share["p99_ttft_seconds"] or 1e-9, 1e-9)),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)

    for name, row in rows.items():
        emit(f"serve_replay_{name}",
             row["makespan_seconds"] * 1e6 / max(row["tokens"], 1),
             f"goodput={row['goodput_tokens_per_second']:.1f}tok/s "
             f"hit_rate={row['prefix_hit_rate']:.3f} "
             f"peak_pages={row['peak_live_pages']} "
             f"prefill_rows={row['prefill_rows']} "
             f"p50_ttft_ms={1e3 * (row['p50_ttft_seconds'] or 0):.1f} "
             f"p99_ttft_ms={1e3 * (row['p99_ttft_seconds'] or 0):.1f}")
    emit("serve_replay_sharing", 0.0,
         f"pages_ratio={pages_ratio:.3f} prefill_rows_ratio={rows_ratio:.3f} "
         f"cow={share['cow_copies']} "
         f"max_step_rows={share['max_prefill_rows_step']}"
         f"->{chunked['max_prefill_rows_step']}chunked "
         f"chunked_p99_ttft_ratio={report['chunked_vs_unchunked_p99_ttft']:.2f}")
    if base is not None:
        _assert_replay_floors(report, base)
    return report


if __name__ == "__main__":
    run()
    run_spec()
    run_replay()
