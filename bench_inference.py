#!/usr/bin/env python
"""Serving throughput: ragged continuous batching vs padded batches.

Reference claim context: FastGen's up-to-2.3x effective throughput vs
padded serving (blogs/deepspeed-fastgen/README.md:28). The workload is a
REQUEST STREAM with long-tail prompt AND generation lengths, served at a
fixed concurrency: the ragged engine (v2.serve) backfills freed slots
from the queue between device-resident fused-decode chunks, while the
padded v1 engine processes arrival-order static batches, each run to its
longest request. Metric = total generated tokens / wall second
(best-of-3 per engine); extra.uniform_gen carries a closed-batch
uniform-length comparison that strips the retirement/backfill advantage.
Prints ONE JSON line.
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def _pick_size(args, on_tpu: bool) -> str:
    """The llama3 preset to serve: ``--size`` when given, else ``1b`` on a
    TPU. Off TPU there is no default — a bare invocation without a chip
    fails instead of benchmarking the tiny preset on the host."""
    if args.size:
        return args.size
    if not on_tpu:
        raise SystemExit(
            "bench_inference.py: no TPU found and no --size given; "
            "refusing to fall back to a host run. A CPU correctness run "
            "is explicit: --size tiny")
    return "1b"


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _roofline_extra(eng) -> dict:
    """Compile-time prefill/decode roofline stamp (telemetry/explain)
    for the result line's extra; {} on any failure — the stamp must
    never break the headline JSON."""
    try:
        recs = eng.cost_records()
        return {lbl: {
            "flops": recs[lbl]["flops"],
            "bytes_accessed": recs[lbl]["bytes_accessed"],
            "predicted_step_ms": round(
                recs[lbl]["predicted_s"] * 1e3, 4),
            "bound": recs[lbl]["bound"],
        } for lbl in ("prefill", "decode") if not recs[lbl].get("error")}
    except Exception:
        return {}


def _slo_extra() -> dict:
    """SLO stamp for the BENCH JSON line. DSTPU_BENCH_SLO=";"-separated
    objective strings (e.g. ``serving/ttft_seconds:p95 <= 0.5``) arms a
    one-shot evaluation: the final registry state is flushed through an
    in-memory metric history and judged by the burn-rate engine. Always
    returns a stamp (zeros when unarmed) so trajectory files stay
    uniform; never breaks the headline JSON."""
    spec = os.environ.get("DSTPU_BENCH_SLO")
    if not spec:
        return {"objectives": 0, "evaluated": 0, "worst_burn": 0.0,
                "breached": []}
    try:
        from deepspeed_tpu.telemetry.registry import registry
        from deepspeed_tpu.telemetry.slo import engine_from_config
        from deepspeed_tpu.telemetry.timeseries import MetricHistory
        hist = MetricHistory()                       # memory-only
        slo = engine_from_config({"objectives": [
            s.strip() for s in spec.split(";") if s.strip()]})
        slo.publish = False
        hist.subscribe(slo.observe)
        registry.flush_to_monitor(None, 0, history=hist)
        return slo.summary()
    except Exception as e:               # noqa: BLE001
        return {"error": str(e)[:200]}


def _trace_exemplars_extra() -> dict:
    """Worst-TTFT / worst-TPOT exemplar trace_ids for the BENCH JSON
    line (request tracing's latency exemplars — telemetry/reqtrace):
    the exact traces to open with ``dstpu-trace --request`` when this
    run's tail regresses. {} when tracing is off or no exemplar was
    recorded; never breaks the headline JSON."""
    try:
        from deepspeed_tpu.telemetry.registry import registry
        out = {}
        for short, name in (("worst_ttft", "serving/ttft_seconds"),
                            ("worst_tpot", "serving/tpot_seconds"),
                            ("worst_router_ttft", "router/ttft_seconds")):
            m = registry.get(name)
            ex = (m.worst_exemplar()
                  if hasattr(m, "worst_exemplar") else None)
            if ex is not None:
                out[short] = {"trace_id": ex[0],
                              "value_s": round(ex[1], 6)}
        return out
    except Exception:                                # noqa: BLE001
        return {}


def _goodput_extra() -> dict:
    """Final goodput-ledger sweep → the BENCH ``extra.goodput`` stamp
    (uptime attribution + dominant badput). {} when the ledger is off or
    on any failure; never breaks the headline JSON."""
    try:
        from deepspeed_tpu.telemetry.goodput import goodput_ledger
        if not goodput_ledger.enabled:
            return {}
        goodput_ledger.update()
        s = goodput_ledger.summary() or {}
        return {k: s.get(k) for k in
                ("uptime_s", "goodput_s", "fraction", "window_fraction",
                 "badput", "dominant_badput", "dominant_badput_s",
                 "captures")} if s else {}
    except Exception:                                # noqa: BLE001
        return {}


def bench_shared_prefix(args) -> None:
    """serving-frontend scenario: a stream of prompts sharing a 50%
    prefix (system prompt / few-shot preamble), served through
    deepspeed_tpu/serving with the radix prefix cache ON vs OFF. Cache
    hits alias the shared pages and skip their prefill entirely, so with
    prefill-dominated requests (short generations) requests/sec should
    approach 2x; the CI floor is 1.5x. Prints ONE JSON line."""
    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    size = _pick_size(args, on_tpu)

    import deepspeed_tpu as ds
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.serving import ServingFrontend

    ds.build_mesh(data=1, devices=jax.devices()[:1])
    seq_cap = 512
    model = llama3_config(size, max_seq_len=seq_cap, tie_embeddings=True)
    dtype = "bfloat16" if on_tpu else "float32"

    rng = np.random.default_rng(0)
    n_req = args.n_requests
    conc = args.n_prompts
    plen, share, new = 384, 192, 4          # 50%-shared, prefill-heavy
    prefix = rng.integers(0, model.vocab_size, size=share)
    prompts = [
        np.concatenate([prefix, rng.integers(0, model.vocab_size,
                                             size=plen - share)])
        for _ in range(n_req)]

    # prefill_chunk 32: a sequence advances ONE chunk per engine step, so
    # the cold run pays plen/32 prefill rounds and the cached run only
    # (plen-share)/32 — on CPU each step costs near-flat wall time
    # (dispatch-bound at tiny sizes), so the request-rate ratio tracks
    # the step-count ratio the cache actually removes
    block = 32
    blocks_per_seq = -(-(plen + new) // block)
    eng = RaggedInferenceEngineTPU(
        model, {"dtype": dtype,
                "num_blocks": conc * blocks_per_seq + blocks_per_seq + 32,
                "block_size": block, "max_seq_len": seq_cap,
                "prefill_chunk": 32, "max_batch_tokens": 2048,
                "max_sequences": conc,
                "use_pallas": (False if args.no_pallas else None)},
        rng=jax.random.PRNGKey(0))

    def run(fe):
        reqs = [fe.submit([int(t) for t in p], max_new_tokens=new)
                for p in prompts]
        fe.run_until_idle()
        assert all(len(r.tokens_out) == new for r in reqs)

    fe_cold = ServingFrontend(eng, max_queue=n_req,
                              enable_prefix_cache=False)
    run(fe_cold)                                     # compile real buckets
    t_cold = min(_timed(lambda: run(fe_cold)) for _ in range(2))
    fe_hot = ServingFrontend(eng, max_queue=n_req)
    run(fe_hot)                        # warm: populates the radix cache
    t_hot = min(_timed(lambda: run(fe_hot)) for _ in range(2))

    result = {
        "metric": f"serving frontend prefix cache llama3-{size}, "
                  f"{n_req} req stream @ conc {conc}, "
                  f"{share}/{plen} shared prefix",
        "value": round(n_req / t_hot, 2),
        "unit": "requests/s (prefix cache on)",
        "vs_baseline": round(t_cold / t_hot, 4),
        "extra": {
            "nocache_req_s": round(n_req / t_cold, 2),
            "cache_req_s": round(n_req / t_hot, 2),
            "speedup": round(t_cold / t_hot, 3),
            "prefix_hit_rate": round(fe_hot.cache.hit_rate, 3),
            "prefix_tokens_reused":
                fe_hot.metrics.counters["prefix_tokens_reused"],
            "engine_steps_cache":
                fe_hot.metrics.counters["engine_steps"],
            "engine_steps_nocache":
                fe_cold.metrics.counters["engine_steps"],
            "ttft_mean_s": round(fe_hot.metrics.ttft.mean, 4),
            "roofline": _roofline_extra(eng),
            "slo": _slo_extra(),
            "trace_exemplars": _trace_exemplars_extra(),
        },
    }
    print(json.dumps(result))


def bench_router(args) -> None:
    """multi-replica scenario: the SAME shared-prefix request stream
    served through the fault-tolerant router over ``--replicas N``
    in-process replicas, optionally under a ``--chaos`` fault plan
    (e.g. ``serving_step:8:replica_kill:router``). Stamps per-replica
    tok/s, failover count and recovery time into the BENCH JSON; when
    the plan degrades a replica (``replica_slow``), runs a hedging A/B
    (same stream, hedge off vs on) and stamps the p99 TTFT improvement
    hedged dispatch buys back. Prints ONE JSON line."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.resilience.faults import fault_injector
    from deepspeed_tpu.serving import LocalReplica, Router, ServingFrontend

    on_tpu = jax.devices()[0].platform == "tpu"
    size = _pick_size(args, on_tpu)
    ds.build_mesh(data=1, devices=jax.devices()[:1])
    seq_cap = 256
    model = llama3_config(size, max_seq_len=seq_cap, tie_embeddings=True)
    dtype = "bfloat16" if on_tpu else "float32"
    params = init_params(model, jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    n_req = args.n_requests
    conc = min(args.n_prompts, 16)
    new = max(2, min(args.new_tokens, 16))
    plen, share = 48, 24                      # 50%-shared → affinity work
    prefix = rng.integers(0, model.vocab_size, size=share)
    prompts = [np.concatenate(
        [prefix, rng.integers(0, model.vocab_size, size=plen - share)])
        for _ in range(n_req)]
    block = 16
    blocks_per_seq = -(-(plen + new) // block)
    eng_cfg = {"dtype": dtype,
               "num_blocks": conc * blocks_per_seq + blocks_per_seq + 16,
               "block_size": block, "max_seq_len": seq_cap,
               "prefill_chunk": 32, "max_batch_tokens": 1024,
               "max_sequences": conc,
               "use_pallas": (False if args.no_pallas else None)}

    c = telemetry.registry.counter

    def run_pool(hedge: bool) -> dict:
        """One fresh pool + router over the stream; per-mode counter
        deltas so A/B modes don't bleed into each other."""
        # replica i on local device i % n: one process, one replica per
        # chip (all on the one device when there is only one)
        devices = jax.local_devices()
        replicas = []
        for i in range(args.replicas):
            dev = devices[i % len(devices)]
            with jax.default_device(dev):
                eng = RaggedInferenceEngineTPU(
                    model, dict(eng_cfg),
                    params=jax.device_put(params, dev))
            replicas.append(LocalReplica(f"r{i}", ServingFrontend(
                eng, max_queue=n_req, enable_prefix_cache=False)))
        router = Router(replicas, hedge=hedge,
                        hedge_delay_s=args.hedge_delay)
        # warm every replica's compile buckets before arming chaos so
        # the drill times recovery, not XLA
        warm = [router.submit([int(t) for t in p], max_new_tokens=new)
                for p in prompts[:args.replicas * 2]]
        router.run_until_idle(wall_timeout_s=600.0)
        assert all(w.finish_reason == "length" for w in warm)
        base = {k: c(k).value for k in (
            "router/failovers", "router/hedges", "router/hedges_won",
            "resilience/faults_injected", "resilience/recoveries")}
        if args.chaos:
            fault_injector.arm(args.chaos, _env=False)
        tok0 = dict(router.replica_tokens)
        t0 = time.perf_counter()
        reqs = [router.submit([int(t) for t in p], max_new_tokens=new)
                for p in prompts]
        router.run_until_idle(wall_timeout_s=600.0)
        wall = time.perf_counter() - t0
        fault_injector.disarm()
        toks = sum(len(r.tokens_out) for r in reqs)
        stats = router.stats()
        out = {
            "tok_s": round(toks / wall, 2), "wall_s": round(wall, 3),
            "completed": sum(r.finish_reason == "length" for r in reqs),
            "requests": n_req,
            "replica_tok_s": {
                name: round((stats["replica_tokens"].get(name, 0) -
                             tok0.get(name, 0)) / wall, 2)
                for name in tok0},
            "replica_states": stats["replicas"],
            "failovers": int(c("router/failovers").value -
                             base["router/failovers"]),
            "hedges": int(c("router/hedges").value -
                          base["router/hedges"]),
            "hedges_won": int(c("router/hedges_won").value -
                              base["router/hedges_won"]),
            "recovery_s": stats["last_recovery_s"],
            "ttft_p99_s": round(router.ttft.percentile(99), 4),
            "ledger": {
                "faults": int(c("resilience/faults_injected").value -
                              base["resilience/faults_injected"]),
                "recoveries": int(c("resilience/recoveries").value -
                                  base["resilience/recoveries"])},
        }
        router.close()
        return out

    hedge_ab = None
    if args.chaos and "replica_slow" in args.chaos:
        off = run_pool(hedge=False)
        on = run_pool(hedge=True)
        hedge_ab = {
            "hedge_off": off, "hedge_on": on,
            "p99_ttft_improvement": round(
                off["ttft_p99_s"] / max(1e-9, on["ttft_p99_s"]), 3)}
        headline = on
    else:
        headline = run_pool(hedge=not args.no_hedge)

    result = {
        "metric": f"multi-replica router llama3-{size}, {n_req} req "
                  f"stream @ {args.replicas} replicas"
                  + (f", chaos [{args.chaos}]" if args.chaos else ""),
        "value": headline["tok_s"],
        "unit": "gen tokens/s (router)",
        "vs_baseline": (hedge_ab["p99_ttft_improvement"]
                        if hedge_ab else 1.0),
        "extra": {
            "replicas": args.replicas,
            "chaos": args.chaos,
            **headline,
            "slo": _slo_extra(),
            "trace_exemplars": _trace_exemplars_extra(),
        },
    }
    if hedge_ab is not None:
        result["extra"]["hedge_ab"] = hedge_ab
    print(json.dumps(result))


def bench_returning_sessions(args) -> None:
    """tiered-KV-cache scenario: N conversation sessions are served,
    go idle (their cached prefixes evicted from HBM), then RETURN with
    a follow-up — with the HBM arena sized for ~N/10 resident sessions.
    With the tier ON the evicted pages land in a bounded host-DRAM
    arena and spill onward to NVMe; the returning request's pages are
    prefetched at submit and re-adopted at admission, so warm resume
    pays only the follow-up prefill. With the tier OFF the pages are
    simply freed and every return re-prefills the full folded prompt.
    Headline = re-prefill TTFT / warm-resume TTFT (mean over all
    returns). Prints ONE JSON line."""
    import shutil
    import tempfile

    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.serving import ServingFrontend

    on_tpu = jax.devices()[0].platform == "tpu"
    size = _pick_size(args, on_tpu)
    ds.build_mesh(data=1, devices=jax.devices()[:1])
    seq_cap = 256
    model = llama3_config(size, max_seq_len=seq_cap, tie_embeddings=True)
    dtype = "bfloat16" if on_tpu else "float32"

    rng = np.random.default_rng(0)
    n_sessions = min(args.n_requests, 40)
    conc = 2                    # low concurrency: sessions are IDLE, not
    block, chunk = 16, 16       # in flight — HBM holds the working set
    plen, new, follow, new2 = 192, 8, 16, 8
    blocks_per_seq = -(-(plen + new) // block)        # phase-1 footprint
    num_blocks = conc * (blocks_per_seq + 2) + 2      # ~2 cached sessions
    hbm_sessions = num_blocks // blocks_per_seq
    prompts = [[int(t) for t in rng.integers(0, model.vocab_size,
                                             size=plen)]
               for _ in range(n_sessions)]
    follows = [[int(t) for t in rng.integers(0, model.vocab_size,
                                             size=follow)]
               for _ in range(n_sessions)]

    eng = RaggedInferenceEngineTPU(
        model, {"dtype": dtype, "num_blocks": num_blocks,
                "block_size": block, "max_seq_len": seq_cap,
                "prefill_chunk": chunk, "max_batch_tokens": 256,
                "max_sequences": conc,
                "use_pallas": (False if args.no_pallas else None)},
        rng=jax.random.PRNGKey(0))
    page_nbytes = eng.kv_page_nbytes()
    nvme_dir = tempfile.mkdtemp(prefix="dstpu-kvtier-bench-")

    def run_mode(tier_on: bool) -> dict:
        cfg = {"kvtier": {"enabled": True, "nvme_dir": nvme_dir,
                          "dram_bytes": 60 * page_nbytes,
                          "high_watermark": 0.75, "low_watermark": 0.5,
                          }} if tier_on else None
        fe = ServingFrontend(eng, max_queue=n_sessions + conc, config=cfg)
        steps0 = fe.metrics.counters["engine_steps"]
        # phase 1: serve every session in small waves, then idle them out
        # of HBM entirely (eviction captures to the tier when it's on)
        gens = [None] * n_sessions
        for lo in range(0, n_sessions, conc):
            reqs = [(i, fe.submit(prompts[i], max_new_tokens=new))
                    for i in range(lo, min(lo + conc, n_sessions))]
            fe.run_until_idle()
            for i, r in reqs:
                gens[i] = list(r.tokens_out)
        fe.cache.evict(1 << 30)
        steps_serve = fe.metrics.counters["engine_steps"] - steps0
        # phase 2: every session returns with a follow-up; TTFT per return
        ttfts = []
        for i in range(n_sessions):
            folded = prompts[i] + gens[i] + follows[i]
            t0 = time.perf_counter()
            r = fe.submit(folded, max_new_tokens=new2)
            while not r.tokens_out:
                fe.step()
            ttfts.append(time.perf_counter() - t0)
            fe.run_until_idle()
            assert len(r.tokens_out) == new2
            # the session idles again: evict at IDLE time (captures to
            # the tier when it's on) so the next return's latency window
            # never pays another conversation's demotion
            fe.cache.evict(1 << 30)
        out = {
            "ttft_mean_s": round(sum(ttfts) / len(ttfts), 5),
            "ttft_p50_s": round(sorted(ttfts)[len(ttfts) // 2], 5),
            "ttft_p95_s": round(sorted(ttfts)[
                int(0.95 * (len(ttfts) - 1))], 5),
            "engine_steps_serve": steps_serve,
            "engine_steps_return":
                fe.metrics.counters["engine_steps"] - steps0 - steps_serve,
        }
        if tier_on:
            st = fe.kvtier.stats()
            out["kvtier"] = {k: st[k] for k in (
                "captures", "spills", "adopts", "hits", "misses",
                "prefetch_issued", "dram_pages", "nvme_pages",
                "bytes_spilled", "bytes_adopted")}
        fe.close()
        fe.cache.evict(1 << 30)            # free pages for the next mode
        return out

    warm_fe = ServingFrontend(eng, max_queue=4)      # compile real buckets
    w = warm_fe.submit(prompts[0] + [0] * (new + follow),
                       max_new_tokens=new2)
    warm_fe.run_until_idle()
    assert len(w.tokens_out) == new2
    warm_fe.close()
    warm_fe.cache.evict(1 << 30)

    off = run_mode(tier_on=False)
    on = run_mode(tier_on=True)
    shutil.rmtree(nvme_dir, ignore_errors=True)
    speedup = round(off["ttft_mean_s"] / max(1e-9, on["ttft_mean_s"]), 3)

    result = {
        "metric": f"tiered KV cache llama3-{size}, {n_sessions} returning "
                  f"sessions vs {hbm_sessions}-session HBM arena",
        "value": round(1.0 / max(1e-9, on["ttft_mean_s"]), 2),
        "unit": "warm resumes/s (mean 1/TTFT, tier on)",
        "vs_baseline": speedup,
        "extra": {
            "resident_sessions": n_sessions,
            "hbm_capacity_sessions": hbm_sessions,
            "residency_ratio": round(n_sessions / max(1, hbm_sessions), 1),
            "warm_resume_ttft_s": on["ttft_mean_s"],
            "reprefill_ttft_s": off["ttft_mean_s"],
            "ttft_speedup": speedup,
            "kv_page_bytes": page_nbytes,
            "tier_on": on, "tier_off": off,
            "slo": _slo_extra(),
            "trace_exemplars": _trace_exemplars_extra(),
        },
    }
    print(json.dumps(result))


def bench_diurnal(args) -> None:
    """elasticity scenario: a DISAGGREGATED prefill/decode fleet under a
    diurnal load swing (10x between trough and peak) with the SLO-driven
    autoscaler sizing each pool — replica counts must follow the curve
    while TTFT p95 holds — plus a chaos ``replica_kill`` landing on a
    replica MID-SCALE-DOWN (the drain window), which must still converge
    with the faults==recoveries ledger balanced. Prints ONE JSON line
    with per-phase pool sizes, TTFT p95, scale events, handoff counts
    and the ledger."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.resilience.faults import fault_injector
    from deepspeed_tpu.serving import (Autoscaler, LocalReplica, Router,
                                       ServingFrontend)

    on_tpu = jax.devices()[0].platform == "tpu"
    size = _pick_size(args, on_tpu)
    ds.build_mesh(data=1, devices=jax.devices()[:1])
    # goodput ledger over the drill: serving/engine_step spans attribute
    # token work vs idle; the stamp lands in extra.goodput below
    telemetry.tracer.configure(enabled=True)
    telemetry.goodput_ledger.configure(enabled=True)
    seq_cap = 256
    model = llama3_config(size, max_seq_len=seq_cap, tie_embeddings=True)
    dtype = "bfloat16" if on_tpu else "float32"
    params = init_params(model, jax.random.PRNGKey(0))
    new = max(2, min(args.new_tokens, 8))
    eng_cfg = {"dtype": dtype, "num_blocks": 96, "block_size": 8,
               "max_seq_len": seq_cap, "prefill_chunk": 16,
               "max_batch_tokens": 256, "max_sequences": 16,
               "use_pallas": (False if args.no_pallas else None)}

    # --from-config: a dstpu-tune plan drives the fleet knobs — engine
    # SplitFuse budget / prefill chunk / resident sequences from the
    # tune stamp's serving_engine keys, hedge policy from router.*,
    # floors/ceilings/queue knee from autoscale.* (ceilings clamped to
    # this host's drill scale; the scenario's fast timing knobs stay so
    # the drill still converges in CI time)
    tuned = getattr(args, "_tuned_cfg", None)
    tuned_stamp = None
    scaler_kw = {"prefill_min": 1, "prefill_max": 3,
                 "decode_min": 1, "decode_max": 4, "queue_high": 2.0}
    hedge_kw = {"hedge": False}
    serving_kw = {}
    if tuned:
        tuned_stamp = dict(tuned.get("tune") or {})
        se = dict(tuned_stamp.get("serving_engine") or {})
        if se.get("prefill_chunk"):
            eng_cfg["prefill_chunk"] = max(8, min(64, int(
                se["prefill_chunk"])))
        if se.get("max_batch_tokens"):
            eng_cfg["max_batch_tokens"] = max(64, min(1024, int(
                se["max_batch_tokens"])))
        if se.get("max_sequences"):
            eng_cfg["max_sequences"] = max(4, min(16, int(
                se["max_sequences"])))
        rb = dict(tuned.get("router") or {})
        if rb:
            hedge_kw = {"hedge": bool(rb.get("hedge", False)),
                        "hedge_delay_s": rb.get("hedge_delay_s")}
        ab = dict(tuned.get("autoscale") or {})
        if ab:
            scaler_kw = {
                "prefill_min": max(1, min(int(ab.get("prefill_min", 1)),
                                          3)),
                "prefill_max": max(1, min(int(ab.get("prefill_max", 3)),
                                          3)),
                "decode_min": max(1, min(int(ab.get("decode_min", 1)), 4)),
                "decode_max": max(1, min(int(ab.get("decode_max", 4)), 4)),
                "queue_high": max(1.0, float(ab.get("queue_high", 2.0))),
            }
            scaler_kw["prefill_min"] = min(scaler_kw["prefill_min"],
                                           scaler_kw["prefill_max"])
            scaler_kw["decode_min"] = min(scaler_kw["decode_min"],
                                          scaler_kw["decode_max"])
        sb = dict(tuned.get("serving") or {})
        if sb.get("megastep_tokens"):
            serving_kw = {"megastep_tokens": int(sb["megastep_tokens"])}

    frontends = []

    def make_replica(pool: str, name: str) -> LocalReplica:
        eng = RaggedInferenceEngineTPU(model, dict(eng_cfg),
                                       params=params)
        fe = ServingFrontend(eng, max_queue=256, **serving_kw)
        frontends.append(fe)
        return LocalReplica(name, fe, pool=pool)

    spawned = {"prefill": 0, "decode": 0}

    def spawn(pool: str) -> LocalReplica:
        spawned[pool] += 1
        return router.add_replica(
            make_replica(pool, f"{pool[0]}{spawned[pool]}"))

    router = Router([make_replica("prefill", "p0"),
                     make_replica("decode", "d0")], **hedge_kw)
    scaler = Autoscaler(router, spawn_fn=spawn,
                        **scaler_kw, idle_s=0.3, cooldown_s=0.2,
                        evaluate_every_s=0.05, drain_deadline_s=15.0)

    rng = np.random.default_rng(0)
    prefix = rng.integers(0, model.vocab_size, size=8)

    def prompt():
        return [int(t) for t in np.concatenate(
            [prefix, rng.integers(0, model.vocab_size, size=4)])]

    # warm every compile bucket at floor size before measuring — the
    # drill times elasticity and recovery, not XLA
    warm = [router.submit(prompt(), max_new_tokens=new) for _ in range(4)]
    router.run_until_idle(wall_timeout_s=600.0)
    assert all(w.finish_reason in ("length", "eos") for w in warm)

    c = telemetry.registry.counter
    base = {k: c(k).value for k in (
        "resilience/faults_injected", "resilience/recoveries",
        "autoscale/scale_ups", "autoscale/scale_downs",
        "handoff/completed", "router/failovers")}

    def pool_sizes():
        return {p: len(router.pool_members(p))
                for p in ("prefill", "decode")}

    def drive(idle_spin_s: float, arm_kill: bool) -> bool:
        """Poll router + autoscaler until streams finish AND the fleet
        has idled ``idle_spin_s`` (the window where idle scale-down
        fires). ``arm_kill`` arms a replica_kill against the FIRST
        replica seen draining — the mid-scale-down chaos drill."""
        armed = False
        t_idle = None
        while True:
            busy = router.poll()
            scaler.maybe_evaluate()
            if arm_kill and not armed and router._draining:
                victim = sorted(router._draining)[0]
                os.environ["DSTPU_CHAOS_REPLICA"] = victim
                fault_injector.arm(
                    f"serving_step:{router._polls + 1}:"
                    f"replica_kill:router", _env=False)
                armed = True
            if busy:
                t_idle = None
            else:
                now = time.monotonic()
                if t_idle is None:
                    t_idle = now
                if now - t_idle >= idle_spin_s:
                    return armed
            time.sleep(0.001)

    # the diurnal curve: trough → ramp → 10x peak → trough again (the
    # final trough spins long enough for idle scale-down + the kill)
    phases = [("night", 2, 0.0), ("morning", 6, 0.0),
              ("peak", 20, 0.0), ("evening", 2, 1.2)]
    steps0 = sum(fe.metrics.counters["engine_steps"] for fe in frontends)
    t0 = time.perf_counter()
    all_reqs = []
    phase_rows = []
    killed = False
    for name, n_req, idle_spin in phases:
        reqs = [router.submit(prompt(), max_new_tokens=new)
                for _ in range(n_req)]
        all_reqs += reqs
        killed |= drive(idle_spin, arm_kill=(name == "evening"
                                             and not killed))
        phase_rows.append({
            "phase": name, "requests": n_req, "pools": pool_sizes(),
            "ttft_p95_s": (round(router.ttft.percentile(95), 4)
                           if router.ttft.count else None)})
    # convergence: the drain set empties (even with the kill landing
    # mid-drain) and the recovery ledger closes
    deadline = time.monotonic() + 60.0
    while (router._draining or router._pending_recovery or
           router._pending_handoff) and time.monotonic() < deadline:
        router.poll()
        time.sleep(0.002)
    wall = time.perf_counter() - t0
    fault_injector.disarm()
    os.environ.pop("DSTPU_CHAOS_REPLICA", None)
    converged = not router._draining and not router._pending_recovery
    toks = sum(len(r.tokens_out) for r in all_reqs)
    faults = int(c("resilience/faults_injected").value -
                 base["resilience/faults_injected"])
    recoveries = int(c("resilience/recoveries").value -
                     base["resilience/recoveries"])
    peak_pools = max(sum(row["pools"].values()) for row in phase_rows)
    tune_extra = None
    if tuned_stamp is not None:
        # predicted-vs-measured per engine step: the cost model's decode
        # prediction against the drill's mean wall time per engine step
        # (mixed prefill/decode; CPU hosts predict 0 → pct stays None)
        eng_steps = sum(fe.metrics.counters["engine_steps"]
                        for fe in frontends) - steps0
        measured_ms = wall / eng_steps * 1e3 if eng_steps else None
        predicted_ms = None
        try:
            recs = frontends[0].engine.cost_records()
            p = recs.get("decode", {}).get("predicted_s")
            predicted_ms = p * 1e3 if p else None
        except Exception:
            pass
        tune_extra = {
            "config": tuned_stamp.get("_path"),
            "search_key": tuned_stamp.get("search_key"),
            "tuned_platform": tuned_stamp.get("platform"),
            "predicted_ms": predicted_ms,
            "measured_ms": (round(measured_ms, 3)
                            if measured_ms else None),
            "pct_of_roofline": (round(100.0 * predicted_ms / measured_ms,
                                      2)
                                if predicted_ms and measured_ms
                                else None),
            "applied": {"engine": {k: eng_cfg[k] for k in
                                   ("prefill_chunk", "max_batch_tokens",
                                    "max_sequences")},
                        "router": hedge_kw,
                        "autoscale": scaler_kw,
                        "serving": serving_kw},
        }
    result = {
        "metric": f"diurnal elasticity llama3-{size}: disagg "
                  f"prefill/decode fleet, "
                  f"{sum(n for _, n, _ in phases)} req over "
                  f"{len(phases)} phases (10x swing), autoscaler + "
                  f"mid-scale-down replica_kill",
        "value": round(toks / wall, 2),
        "unit": "gen tokens/s (autoscaled fleet)",
        "vs_baseline": 1.0,
        "extra": {
            "phases": phase_rows,
            "final_pools": pool_sizes(),
            "peak_fleet": peak_pools,
            "scale_ups": int(c("autoscale/scale_ups").value -
                             base["autoscale/scale_ups"]),
            "scale_downs": int(c("autoscale/scale_downs").value -
                               base["autoscale/scale_downs"]),
            "handoffs": int(c("handoff/completed").value -
                            base["handoff/completed"]),
            "failovers": int(c("router/failovers").value -
                             base["router/failovers"]),
            "completed": sum(r.finish_reason in ("length", "eos")
                             for r in all_reqs),
            "requests": len(all_reqs),
            "kill_armed": killed,
            "converged": converged,
            "ttft_p95_s": (round(router.ttft.percentile(95), 4)
                           if router.ttft.count else None),
            "ledger": {"faults": faults, "recoveries": recoveries,
                       "balanced": faults == recoveries},
            "slo": _slo_extra(),
            "trace_exemplars": _trace_exemplars_extra(),
            "goodput": _goodput_extra(),
        },
    }
    if tune_extra is not None:
        result["extra"]["tune"] = tune_extra
    router.close()
    print(json.dumps(result))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default=None)
    ap.add_argument("--new-tokens", type=int, default=128,
                    help="max generation length (the long tail)")
    ap.add_argument("--n-prompts", type=int, default=16,
                    help="server concurrency (resident sequences)")
    ap.add_argument("--n-requests", type=int, default=64,
                    help="total requests in the stream")
    ap.add_argument("--no-pallas", action="store_true")
    ap.add_argument("--quant", nargs="?", const="int8", default=None,
                    choices=("int8", "fp8", "int4", "fp6"),
                    help="weight-only quantized serving (bare flag = "
                         "int8; int4 quarters the decode weight fetch)")
    ap.add_argument("--scenario", default="stream",
                    choices=("stream", "shared_prefix_stream", "router",
                             "diurnal", "returning_sessions"),
                    help="stream: ragged vs padded request stream; "
                         "shared_prefix_stream: serving frontend with "
                         "the radix prefix cache on vs off over "
                         "50%%-shared prompts; router: the stream over "
                         "--replicas N fault-tolerant replicas, "
                         "optionally under a --chaos plan; diurnal: "
                         "disaggregated prefill/decode fleet under a "
                         "10x load swing with the autoscaler sizing "
                         "each pool and a replica killed mid-scale-down; "
                         "returning_sessions: N idle sessions return "
                         "against an HBM arena sized for N/10 — warm "
                         "resume from the DRAM/NVMe KV tier vs full "
                         "re-prefill TTFT")
    ap.add_argument("--replicas", type=int, default=3,
                    help="router scenario: replica pool size")
    ap.add_argument("--chaos", default=None, metavar="PLAN",
                    help="router scenario: fault plan armed for the "
                         "measured stream (e.g. 'serving_step:8:"
                         "replica_kill:router'); a replica_slow plan "
                         "triggers the hedging A/B")
    ap.add_argument("--hedge-delay", type=float, default=0.05,
                    help="router scenario: fixed hedge delay seconds "
                         "(default 0.05 for deterministic A/Bs)")
    ap.add_argument("--no-hedge", action="store_true",
                    help="router scenario: disable hedged dispatch")
    ap.add_argument("--from-config", default=None, metavar="JSON",
                    help="drive the diurnal fleet scenario from a "
                         "dstpu-tune emitted config: serving/router/"
                         "autoscale blocks size the drill's knobs and "
                         "extra.tune stamps predicted-vs-measured "
                         "(forces --scenario diurnal)")
    ap.add_argument("--megastep", nargs="?", const=32, type=int,
                    default=None, metavar="K",
                    help="A/B the serving frontend stepwise vs decode "
                         "megasteps of up to K tokens (bare flag = 32) "
                         "on the stream workload, stamping per-mode "
                         "tok/s and host-dispatch calls per token "
                         "(dispatch/host_calls deltas) into the JSON")
    args = ap.parse_args()

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.from_config:
        with open(args.from_config) as fh:
            cfg = json.load(fh)
        cfg.setdefault("tune", {})["_path"] = os.path.basename(
            args.from_config)
        args._tuned_cfg = cfg
        args.scenario = "diurnal"

    if args.scenario == "shared_prefix_stream":
        return bench_shared_prefix(args)
    if args.scenario == "router":
        return bench_router(args)
    if args.scenario == "diurnal":
        return bench_diurnal(args)
    if args.scenario == "returning_sessions":
        return bench_returning_sessions(args)

    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    size = _pick_size(args, on_tpu)

    import deepspeed_tpu as ds
    from deepspeed_tpu.inference import (RaggedInferenceEngineTPU,
                                         init_inference)
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.models.transformer import init_params

    ds.build_mesh(data=1, devices=jax.devices()[:1])
    seq_cap = 1024
    model = llama3_config(size, max_seq_len=seq_cap, tie_embeddings=True)
    dtype = "bfloat16" if on_tpu else "float32"
    params = None   # random weights; throughput doesn't depend on values

    if args.quant:
        # ONE host-side init shared by both engines, pre-quantized on the
        # host (numpy init: single-core threefry for 8B params costs ~25
        # min; values don't matter for throughput). Both engines accept
        # pre-quantized trees (the bin/dstpu_quantize serving path), so
        # full-precision weights never touch HBM — int4 llama-8B serves
        # on one 16G chip.
        from deepspeed_tpu.ops.quantized_linear import quantize_param_tree
        shapes = jax.eval_shape(
            lambda r: init_params(model, r), jax.random.PRNGKey(0))
        host_rng = np.random.default_rng(0)

        def np_leaf(s):
            flat = host_rng.standard_normal(int(np.prod(s.shape)),
                                            dtype=np.float32) * 0.02
            return flat.reshape(s.shape).astype(s.dtype)

        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            params = quantize_param_tree(jax.tree.map(np_leaf, shapes),
                                         mode=args.quant)

    rng = np.random.default_rng(0)
    # A REQUEST STREAM, not one closed batch — the workload shape behind
    # the reference FastGen claim (2.3x effective throughput,
    # blogs/deepspeed-fastgen): n_requests arrive up front, the server
    # runs at most `concurrency` sequences resident. Long-tail prompt
    # lengths AND long-tail generation lengths: most requests finish
    # early, a few run long. The ragged engine backfills freed slots
    # from the queue between fused chunks; the padded engine processes
    # arrival-order batches of `concurrency`, each batch running to ITS
    # longest request.
    n_req = args.n_requests
    conc = args.n_prompts
    lens = rng.integers(16, 512, size=n_req)
    lens[rng.permutation(n_req)[: n_req // 8]] = 512
    prompts = [rng.integers(0, model.vocab_size, size=(int(n),),
                            dtype=np.int32) for n in lens]
    new_list = rng.integers(8, max(9, args.new_tokens // 4), size=n_req)
    new_list[rng.permutation(n_req)[: n_req // 8]] = args.new_tokens
    new = int(max(new_list))

    # ---- padded v1: arrival-order batches of `conc`, each padded to the
    # GLOBAL width bucket (one compile) and run to its own longest
    # request — the batch is static, so early-finished rows compute
    # until the batch's longest request completes. (pre-quantized trees
    # carry their own scales — weight_quant stays unset; the engines
    # detect quantized leaves)
    v1 = init_inference(model, {"dtype": dtype},
                        params=params, rng=jax.random.PRNGKey(0))
    width = int(max(lens))

    def padded_batches():
        for lo in range(0, n_req, conc):
            chunk = prompts[lo:lo + conc]
            padded = np.zeros((conc, width), np.int32)
            for i, p in enumerate(chunk):
                padded[i, width - len(p):] = p      # left-pad
            yield padded, int(max(new_list[lo:lo + conc]))

    def run_padded():
        for padded, batch_new in padded_batches():
            v1.generate(padded, max_new_tokens=batch_new)

    run_padded()                                      # compile real shapes
    # best-of-3: the generation loop is host-dispatch-bound, so single
    # runs carry scheduler noise
    t_padded = min(_timed(run_padded) for _ in range(3))

    # ---- ragged v2: continuous batching over the true lengths
    # arena sized to the workload: the flat 512-block default costs
    # nb*block*L*kvh*dh*4 bytes (17 GB at llama-8B dims — more than HBM);
    # the measured workload needs ceil((prompt+new)/block) blocks/seq
    block = 64
    blocks_per_seq = -(-(seq_cap + new) // block)
    num_blocks = max(128, args.n_prompts * blocks_per_seq + 16)
    v2 = RaggedInferenceEngineTPU(
        model, {"dtype": dtype, "num_blocks": num_blocks,
                "block_size": block,
                "max_seq_len": seq_cap, "prefill_chunk": 512,
                "max_batch_tokens": 8192,
                "use_pallas": (False if args.no_pallas else None)},
        params=params if args.quant else v1.params,
        rng=jax.random.PRNGKey(0))
    budgets = [int(x) for x in new_list]
    v2.serve(prompts, max_new_tokens=budgets,
             max_concurrency=conc)                   # compile real buckets
    t_ragged = min(_timed(lambda: v2.serve(prompts,
                                           max_new_tokens=budgets,
                                           max_concurrency=conc))
                   for _ in range(3))

    # secondary: ONE closed batch, UNIFORM generation lengths (no
    # retirement/backfill advantage). NOTE the per-step numbers are
    # whole-call wall time (prefill included) divided by decode steps —
    # a like-for-like loop comparison, not a pure decode-step latency
    uni = min(32, new)
    first = prompts[:conc]
    pad_first = np.zeros((conc, width), np.int32)
    for i, p in enumerate(first):
        pad_first[i, width - len(p):] = p
    v2.generate(first, max_new_tokens=uni)
    t_ragged_uni = min(_timed(lambda: v2.generate(first,
                                                  max_new_tokens=uni))
                       for _ in range(2))
    v1.generate(pad_first, max_new_tokens=uni)
    t_padded_uni = min(_timed(lambda: v1.generate(pad_first,
                                                  max_new_tokens=uni))
                       for _ in range(2))

    # ---- optional --megastep A/B: the SAME long-tail stream through the
    # serving frontend, stepwise (K=1, 2+ host round-trips per token) vs
    # decode megasteps (up to K tokens per device program). The headline
    # is host-dispatch calls per generated token — the dispatch/
    # host_calls counter increments once per device launch, so the
    # megastep column should land near 1/K of stepwise on decode-heavy
    # stretches
    megastep_extra = None
    if args.megastep:
        from deepspeed_tpu.serving import ServingFrontend
        from deepspeed_tpu.telemetry.registry import registry

        def run_frontend(k):
            fe = ServingFrontend(v2, max_queue=n_req,
                                 enable_prefix_cache=False,
                                 megastep_tokens=k,
                                 megastep_adaptive=False)
            for p, m in zip(prompts, budgets):
                fe.submit([int(t) for t in p], max_new_tokens=int(m))
            fe.run_until_idle()
            return fe

        def measure(k):
            run_frontend(k)                       # compile this K's buckets
            hc0 = registry.counter("dispatch/host_calls").value
            t0 = time.perf_counter()
            fe = run_frontend(k)
            wall = time.perf_counter() - t0
            calls = registry.counter("dispatch/host_calls").value - hc0
            toks = fe.metrics.counters["tokens_out"]
            return {"tok_s": round(toks / wall, 2),
                    "host_calls": int(calls),
                    "host_calls_per_token": round(calls / max(1, toks), 4),
                    "tokens": int(toks), "wall_s": round(wall, 3)}

        stepwise = measure(0)
        mega = measure(int(args.megastep))
        megastep_extra = {
            "k": int(args.megastep),
            "stepwise": stepwise,
            "megastep": mega,
            "dispatch_reduction": round(
                stepwise["host_calls_per_token"] /
                max(1e-9, mega["host_calls_per_token"]), 2),
            "speedup": round(stepwise["wall_s"] / mega["wall_s"], 3),
        }

    gen_tokens = int(sum(new_list))
    uni_tokens = conc * uni
    result = {
        "metric": f"ragged-serve vs padded-batches llama3-{size} "
                  f"{n_req} req stream @ conc {conc}, long-tail gen"
                  + (f" {args.quant}" if args.quant else ""),
        "value": round(gen_tokens / t_ragged, 2),
        "unit": "gen tokens/s (ragged)",
        "vs_baseline": round(t_padded / t_ragged, 4),
        "extra": {
            "padded_tok_s": round(gen_tokens / t_padded, 2),
            "ragged_tok_s": round(gen_tokens / t_ragged, 2),
            "speedup": round(t_padded / t_ragged, 3),
            "n_requests": n_req, "concurrency": conc,
            "gen_lens_summary": {
                "total": gen_tokens, "max": new,
                "mean": round(float(np.mean(new_list)), 1)},
            "uniform_gen": {
                "new_tokens": uni,
                "ragged_tok_s": round(uni_tokens / t_ragged_uni, 2),
                "padded_tok_s": round(uni_tokens / t_padded_uni, 2),
                "ragged_wall_ms_per_step": round(
                    t_ragged_uni / uni * 1e3, 2),
                "padded_wall_ms_per_step": round(
                    t_padded_uni / uni * 1e3, 2),
            },
            "roofline": _roofline_extra(v2),
            "slo": _slo_extra(),
            "trace_exemplars": _trace_exemplars_extra(),
        },
    }
    if megastep_extra is not None:
        result["extra"]["megastep"] = megastep_extra
    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
