#!/usr/bin/env python
"""deepspeed_tpu headline benchmark.

Trains the flagship decoder (Llama-3 family) with the deepspeed_tpu engine
and reports tokens/sec/chip and MFU. Baseline context (BASELINE.md): the
reference's north star is ZeRO-3 Llama-3-70B at >=45% MFU on v5p; here we
report single-chip MFU against that 45% bar, so ``vs_baseline`` =
achieved_MFU / 0.45. Without a TPU a bare invocation fails; a CPU
correctness run has to be asked for by name (``--size tiny``).

Default TPU config: the 1.2B-param preset (the VERDICT r1 bar: >=1B), bf16,
Pallas flash attention (512-element blocks), `save_attn_out` remat, 512 MB
chunked-CE logits budget (the biggest single MFU lever found tuning: 51.5%
-> 56.1% on v5e — small CE chunks starve the MXU on the [B*C, D]x[D, 128k]
logits matmul), and — on a single 16G chip, where fp32 Adam moments for
1.2B params cannot fit — bf16 optimizer states (`state_dtype` knob, the
analogue of the reference's fp16_master_weights_and_gradients,
stage_1_and_2.py:159). Multi-chip runs shard fp32 states ZeRO-3 style.

Prints exactly ONE JSON line to stdout.
"""

import argparse
import json
import os
import time

import numpy as np


def _peak_flops(device) -> float:
    """bf16 peak FLOPs/s per chip (the table lives in telemetry.sampler;
    imported lazily so bench argparse stays jax-free)."""
    from deepspeed_tpu.telemetry.sampler import peak_flops
    return peak_flops(device)


def _apply_bench_slo(config) -> None:
    """DSTPU_BENCH_SLO=";"-separated objective strings (e.g.
    ``train/mfu >= 0.3;train/step_time_ms:p95 <= 250``) arms the SLO
    burn-rate engine for the bench run: objectives into the config's
    ``slo`` block, metric history every step so short runs still
    evaluate. No env → config untouched."""
    spec = os.environ.get("DSTPU_BENCH_SLO")
    if not spec:
        return
    config["slo"] = {"objectives":
                     [s.strip() for s in spec.split(";") if s.strip()]}
    config.setdefault("telemetry", {})["history_every"] = 1


def _slo_extra(engine_or_frontend):
    """SLO stamp for the BENCH JSON line — always present so trajectory
    files stay uniform; zeros when no objectives were armed."""
    slo = getattr(engine_or_frontend, "_slo", None)
    if slo is None:
        return {"objectives": 0, "evaluated": 0, "worst_burn": 0.0,
                "breached": []}
    return slo.summary()


def _pick_size(args, dev0, tpu_default: str = "1b") -> str:
    """The preset to run: ``--size`` when given, else the TPU default.
    Off TPU there is no default — a bare invocation without a chip fails
    instead of benchmarking the tiny preset on the host."""
    if args.size:
        return args.size
    if dev0.platform != "tpu":
        raise SystemExit(
            f"bench.py: no TPU found (jax reports platform "
            f"{dev0.platform!r}) and no --size given; refusing to fall "
            f"back to a host run. A CPU correctness run is explicit: "
            f"--size tiny")
    return tpu_default


def dense_train_config(n_dev: int, batch: int, seq: int,
                       on_tpu: bool) -> dict:
    """The dense headline training config (also what ``chip_smoke.py``
    trains with): bf16, ZeRO-3 across chips, and — on fewer than 8 small-
    HBM chips, where fp32 Adam moments for 1.2B params cannot fit — bf16
    optimizer state without a separate master copy."""
    opt_params = {"lr": 1e-4, "weight_decay": 0.1}
    if on_tpu and n_dev < 8:
        opt_params.update(state_dtype="bfloat16", master_weights=False)
    return {
        "train_micro_batch_size_per_gpu": max(1, batch // n_dev),
        "optimizer": {"type": "adamw", "params": opt_params},
        "zero_optimization": {"stage": 3 if (on_tpu and n_dev > 1) else 0},
        "bf16": {"enabled": bool(on_tpu)},
        "gradient_clipping": 1.0,
        # save_attn_kernel keeps the Pallas kernel's (out, lse) residuals so
        # the backward never re-runs the flash FORWARD; at 32K+ the
        # block_in chain no longer fits alongside them, so block inputs
        # park on host
        "activation_checkpointing": {
            "policy": os.environ.get(
                "DSTPU_BENCH_REMAT",
                ("offload_save_attn_kernel_host" if seq >= 65536
                 else "offload_save_attn_kernel" if seq >= 32768
                 else "save_attn_kernel") if on_tpu else "none"),
            # FPDT regime: at 64K+ the [T, ffn] MLP activations alone
            # exceed HBM — run the MLP in sequence tiles
            "ffn_chunk": int(os.environ.get(
                "DSTPU_BENCH_FFN_CHUNK",
                8192 if (on_tpu and seq >= 65536) else 0))},
        # bf16 chunk logits (fp32 accumulation kept) at a 256 MB budget:
        # the optimum is ~128-token chunks — in bf16 that is half the
        # bytes, so the budget halves with the dtype
        "ce_logits_dtype": "bf16" if on_tpu else None,
        "chunked_ce_budget_mb": 256 if on_tpu else None,
        # flash + host-offloaded residuals carries training to 256K;
        # attention_impl=fpdt stays opt-in (forward/serving oriented —
        # its reverse-mode AD stores per-chunk softmax intermediates)
        "attention_impl": os.environ.get("DSTPU_BENCH_ATTN", "auto"),
        "steps_per_print": 1000,
    }


def _roofline(engine, step_s: float):
    """Compile-time roofline of the engine's step (telemetry/explain):
    predicted FLOPs / bytes and % of roofline, so a trajectory can tell
    "kernel got faster" from "model got smaller". Costs one compile of
    the step; a step that cannot be lowered fails the run."""
    from deepspeed_tpu.telemetry import explain as _explain
    rep = _explain.explain_engine(engine, measured_step_ms=step_s * 1e3)
    rl = rep.roofline
    if rl is None:
        raise RuntimeError(
            f"roofline: the train step did not lower: "
            f"{[f.error for f in rep.functions if f.error]}")
    return rl, {
        "flops_per_step": rl.flops, "bytes_per_step": rl.bytes,
        "comm_bytes_per_step": rl.comm_bytes,
        "predicted_step_ms": round(rl.predicted_s * 1e3, 3),
        "bound": rl.bound,
        "pct_of_roofline": round(rl.pct_of(step_s) or 0.0, 2),
    }


def from_config_main(args) -> None:
    """``--from-config best.json``: replay a ``dstpu-tune`` winner and
    stamp predicted-vs-measured into ``extra.tune``. The emitted config
    carries everything needed — the mesh rebuilds from its
    parallel-topology knobs (``mesh_from_config``), the training knobs
    pass straight to ``initialize``, and the ``tune`` stamp supplies the
    model preset / sequence length / roofline prediction. When the tuned
    chip count exceeds the local devices, the run falls back to pure-DP
    over what exists (TP/SP/EP coerced away) — a scaled-down sanity run,
    flagged ``scaled_down`` in the stamp, not the tuned point."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.config.config import DeepSpeedTPUConfig
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.parallel.mesh import mesh_from_config

    with open(args.from_config) as fh:
        cfg = json.load(fh)
    parsed = DeepSpeedTPUConfig.from_any(dict(cfg))
    stamp = parsed.tune
    dev0 = jax.devices()[0]
    n_dev = len(jax.devices())
    on_tpu = dev0.platform == "tpu"

    size = args.size or str(stamp.model or "llama3-tiny").split(
        "llama3-")[-1]
    seq = args.seq or int(stamp.seq_len or (2048 if on_tpu else 128))
    steps = args.steps or (24 if on_tpu else 3)
    warmup = 3 if on_tpu else 1
    model = llama3_config(size, max_seq_len=seq, tie_embeddings=True)

    chips = 1
    for v in (stamp.mesh or {}).values():
        chips *= int(v)
    train_cfg = {k: v for k, v in cfg.items()
                 if k not in ("tune", "serving", "router", "autoscale")}
    _apply_bench_slo(train_cfg)
    scaled_down = chips > n_dev
    if scaled_down:
        for k in ("tensor_parallel", "sequence_parallel", "moe"):
            train_cfg.pop(k, None)
        ds.build_mesh(data=n_dev)
        run_chips = n_dev
    else:
        run_chips = max(1, chips)
        mesh_from_config(parsed, devices=jax.devices()[:run_chips])
    engine, *_ = ds.initialize(model=model, config=train_cfg,
                               rng=jax.random.PRNGKey(0))

    gb = int(engine.config.train_batch_size)
    rng = np.random.default_rng(0)
    batches = [jax.device_put({"input_ids": rng.integers(
        0, model.vocab_size, size=(gb, seq), dtype=np.int32)})
        for _ in range(4)]
    for i in range(warmup):
        float(engine.train_batch(iter([batches[i % 4]])))
    t0 = time.perf_counter()
    loss = None
    for i in range(steps):
        loss = engine.train_batch(iter([batches[i % 4]]))
    loss_val = float(loss)
    dt = time.perf_counter() - t0
    measured_ms = dt / steps * 1e3

    tokens = gb * seq * steps
    tune_extra = {
        "config": os.path.basename(args.from_config),
        "search_key": stamp.search_key,
        "tuned_platform": stamp.platform,
        "tuned_chips": stamp.chips,
        "run_chips": run_chips,
        "scaled_down": scaled_down,
        "predicted_ms": stamp.predicted_step_ms,
        "measured_ms": round(measured_ms, 3),
        "pct_of_roofline": None,
    }
    rl, stamp_rl = _roofline(engine, dt / steps)
    tune_extra["local_predicted_ms"] = stamp_rl["predicted_step_ms"]
    tune_extra["bound"] = rl.bound
    tune_extra["pct_of_roofline"] = stamp_rl["pct_of_roofline"]
    result = {
        "metric": f"tokens/sec/chip tuned llama3-{size} seq{seq} "
                  f"[{stamp.search_key or 'untuned config'}]",
        "value": round(tokens / dt / run_chips, 2),
        "unit": "tokens/s/chip",
        "extra": {
            "loss": loss_val,
            "platform": dev0.platform,
            "n_devices": n_dev,
            "steps": steps,
            "global_batch": gb,
            "tune": tune_extra,
            "slo": _slo_extra(engine),
        },
    }
    print(json.dumps(result))
    if getattr(args, "trace", None):
        from deepspeed_tpu.telemetry import tracer
        tracer.dump(args.trace)


def moe_main(args) -> None:
    """MoE training bench: ~1B total params, 8 experts, top-2, dropless
    (lax.ragged_dot) dispatch — MFU on ACTIVE params (the standard MoE
    accounting; reference context: Mixtral-class EP configs)."""
    import jax
    dev0 = jax.devices()[0]
    on_tpu = dev0.platform == "tpu"
    n_dev = len(jax.devices())
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.mixtral import mixtral_config

    size = _pick_size(args, dev0)
    seq = args.seq or (2048 if on_tpu else 128)
    batch = args.batch or 8
    steps = args.steps or (24 if on_tpu else 3)
    warmup = 3 if on_tpu else 1
    ds.build_mesh(data=n_dev)
    if size != "tiny":
        # head_dim 128 (8 heads), the TPU-native choice every production
        # family here uses (llama3/qwen2/mixtral all ship Dh=128): at
        # the old 16x64 config the flash kernels are VPU-bound (QK^T
        # contracts over 64 = half the MXU depth; traced at ~6.5
        # ms/layer vs ~3.5 at Dh=128) — same params, same active FLOPs,
        # same GQA ratio. Measured 36.4% -> 41.8% MFU on this bench
        # (the r5 kernel work lifted 26.3% -> 36.4% before this).
        model = mixtral_config(
            "tiny", hidden_size=1024, num_layers=12, num_heads=8,
            num_kv_heads=4, intermediate_size=2816, num_experts=8,
            num_experts_per_tok=2, vocab_size=32000, max_seq_len=seq,
            tie_embeddings=True)
    else:
        model = mixtral_config("tiny", max_seq_len=seq)
    config = {
        "train_micro_batch_size_per_gpu": max(1, batch // n_dev),
        "optimizer": {"type": "adamw", "params": {
            "lr": 1e-4, "weight_decay": 0.1,
            **({"state_dtype": "bfloat16", "master_weights": False}
               if on_tpu and n_dev < 8 else {})}},
        "zero_optimization": {"stage": 0},
        "bf16": {"enabled": bool(on_tpu)},
        "gradient_clipping": 1.0,
        "moe": {"impl": os.environ.get("DSTPU_BENCH_MOE_IMPL", "dropless")},
        # the fused MoE backward recomputes gate/up in-kernel, so no
        # policy choice affects the FFN re-run. save_attn_kernel_qkv
        # additionally keeps post-rope q/k/v: measured +0.4pt over
        # save_attn_kernel at THIS geometry (32-step pairs, r5) — the
        # 20pt qkv-residency loss documented for the 1.27B dense bench
        # does not reproduce at this smaller model's memory point.
        # (Saving moe_glu residual stacks instead measured ~1pt slower
        # than the in-kernel recompute.)
        "activation_checkpointing": {
            "policy": os.environ.get(
                "DSTPU_BENCH_MOE_POLICY",
                "save_attn_kernel_qkv") if on_tpu else "none"},
        "ce_logits_dtype": "bf16" if on_tpu else None,
        # DSTPU_BENCH_CE_MB=0 -> None (unchunked CE)
        "chunked_ce_budget_mb": (int(os.environ.get(
            "DSTPU_BENCH_CE_MB", 256)) or None) if on_tpu else None,
        "steps_per_print": 1000,
    }
    _apply_bench_slo(config)
    # DSTPU_BENCH_HEALTH=<every> arms the in-graph model-health taps at
    # that cadence for the benched engine (stamped into extra.health)
    hb_every = int(os.environ.get("DSTPU_BENCH_HEALTH", "0") or 0)
    if hb_every:
        config["telemetry"] = {"health": {"enabled": True,
                                          "every": hb_every}}
    engine, *_ = ds.initialize(model=model, config=config,
                               rng=jax.random.PRNGKey(0))
    gb = int(engine.config.train_batch_size)
    rng = np.random.default_rng(0)
    batches = [jax.device_put({"input_ids": rng.integers(
        0, model.vocab_size, size=(gb, seq), dtype=np.int32)})
        for _ in range(4)]
    for i in range(warmup):
        float(engine.train_batch(iter([batches[i % 4]])))
    t0 = time.perf_counter()
    loss = None
    for i in range(steps):
        loss = engine.train_batch(iter([batches[i % 4]]))
    loss_val = float(loss)
    dt = time.perf_counter() - t0

    tokens = gb * seq * steps
    active = model.num_active_params()
    attn = 12.0 * model.num_layers * model.hidden_size * seq * 0.5
    achieved = (6.0 * active + attn) * tokens / dt / n_dev
    peak = _peak_flops(dev0)
    mfu = achieved / peak if peak else 0.0
    result = {
        "metric": f"tokens/sec/chip moe-8e-top2 ~1B seq{seq} dropless",
        "value": round(tokens / dt / n_dev, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4) if peak else 0.0,
        "extra": {"mfu": round(mfu, 4),
                  "achieved_tflops_per_chip": round(achieved / 1e12, 2),
                  "params_total_b": round(model.num_params() / 1e9, 3),
                  "params_active_b": round(active / 1e9, 3),
                  "loss": loss_val, "platform": dev0.platform,
                  "n_devices": n_dev, "steps": steps,
                  "global_batch": gb,
                  "slo": _slo_extra(engine)}}
    result["extra"]["roofline"] = _roofline(engine, dt / steps)[1]
    if hb_every:
        result["extra"]["health"] = _health_extra()
    print(json.dumps(result))
    if getattr(args, "trace", None):
        from deepspeed_tpu.telemetry import tracer
        tracer.dump(args.trace)


def _health_extra():
    """Final ``health/*`` gauge snapshot → the BENCH ``extra.health``
    stamp ({} on any failure — the stamp must never take the bench
    down)."""
    try:
        from deepspeed_tpu.telemetry.registry import registry
        snap = registry.snapshot(interval=False)
        return {k.split("/", 1)[1].replace("/", "_"): round(float(v), 4)
                for k, v in sorted(snap.items())
                if k.startswith("health/") and "layer/" not in k
                and "expert/" not in k
                and isinstance(v, (int, float))}
    except Exception:                                # noqa: BLE001
        return {}


def health_main(args) -> None:
    """--health-ab: A/B the in-graph model-health taps (health.every=1 —
    stats computed in-graph AND fetched every step) against the same
    engine with telemetry.health disabled: identical model, mesh, rng
    and data. The BENCH value is the step-time overhead in percent; the
    acceptance bar for the static-flag design is <5%, with zero extra
    retraces per engine (asserted against the compile counter)."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models.mixtral import mixtral_config

    dev0 = jax.devices()[0]
    n_dev = len(jax.devices())
    on_tpu = dev0.platform == "tpu"
    seq = args.seq or (2048 if on_tpu else 128)
    batch = args.batch or n_dev
    steps = args.steps or (24 if on_tpu else 6)
    warmup = 3 if on_tpu else 2
    ds.build_mesh(data=n_dev)
    model = mixtral_config("tiny", max_seq_len=seq)

    def run(health):
        config = {
            "train_micro_batch_size_per_gpu": max(1, batch // n_dev),
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 0},
            "moe": {"impl": "dropless"},
            "steps_per_print": 1000,
        }
        if health:
            config["telemetry"] = {"health": {"enabled": True,
                                              "every": 1}}
        traces0 = telemetry.compile_monitor.retrace_count(
            "engine/fused_step")
        engine, *_ = ds.initialize(model=model, config=config,
                                   rng=jax.random.PRNGKey(0))
        gb = int(engine.config.train_batch_size)
        rng = np.random.default_rng(0)
        batches = [{"input_ids": rng.integers(
            0, model.vocab_size, size=(gb, seq), dtype=np.int32)}
            for _ in range(4)]
        for i in range(warmup):
            float(engine.train_batch(iter([batches[i % 4]])))
        t0 = time.perf_counter()
        loss = None
        for i in range(steps):
            loss = engine.train_batch(iter([batches[i % 4]]))
        loss = float(loss)
        dt = time.perf_counter() - t0
        return {"step_ms": round(dt / steps * 1e3, 3),
                "loss": round(loss, 6),
                "retraces": telemetry.compile_monitor.retrace_count(
                    "engine/fused_step") - traces0}

    base = run(False)
    taps = run(True)
    overhead = (taps["step_ms"] / base["step_ms"] - 1.0) \
        if base["step_ms"] else 0.0
    result = {
        "metric": f"model-health taps A/B mixtral-tiny seq{seq} "
                  f"dp{n_dev} {dev0.platform} (every=1 vs off)",
        "value": round(overhead * 100.0, 2),
        "unit": "% step-time overhead",
        "extra": {"baseline": base, "health": taps,
                  "health_stamp": _health_extra(),
                  "platform": dev0.platform, "n_devices": n_dev,
                  "steps": steps, "seq": seq},
    }
    print(json.dumps(result))


def overlap_main(args) -> None:
    """A/B the chunked overlap-scheduled ZeRO-3 collectives against the
    monolithic stage-3 path: identical model, mesh, rng and data; one
    JSON line with per-mode step time, loss, roofline stamp and the
    ``overlap/*`` plan numbers (chunks, prefetch, transient HBM). How much
    collective time a step did NOT hide is a device time, measured from a
    trace (the benchmark's ``collective_exposed_ms_per_step``). On a CPU host the mesh is forced to 8
    virtual devices (the dp=8 smoke geometry the tier-1 tests use);
    wall-clock there validates ordering/numerics — the latency-hiding
    win itself only shows on TPU backends with the scheduler flags."""
    if os.environ.get("JAX_PLATFORMS", "") == "cpu" and \
            "host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=8").strip()
    import jax
    dev0 = jax.devices()[0]
    on_tpu = dev0.platform == "tpu"
    n_dev = len(jax.devices())
    if n_dev < 2:
        print(json.dumps({"metric": "zero3 overlap A/B", "value": 0.0,
                          "error": f"needs a dp>=2 mesh, got {n_dev} "
                                   "device(s) (CPU: JAX_PLATFORMS=cpu)"}))
        return
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.llama import llama3_config

    size = _pick_size(args, dev0)
    seq = args.seq or (2048 if on_tpu else 128)
    batch = args.batch or 8
    steps = args.steps or (24 if on_tpu else 4)
    warmup = 3 if on_tpu else 1
    model = llama3_config(size, max_seq_len=seq, tie_embeddings=True)
    chunk_knobs = {
        "overlap_comm": True,
        "overlap_bucket_bytes": int(os.environ.get(
            "DSTPU_BENCH_OVERLAP_BUCKET", 0)),
        "overlap_prefetch": int(os.environ.get(
            "DSTPU_BENCH_OVERLAP_PREFETCH", 1)),
        "overlap_regather": os.environ.get(
            "DSTPU_BENCH_OVERLAP_REGATHER", "1") != "0",
    }

    def run(zero_extra):
        ds.build_mesh(data=n_dev)
        config = {
            "train_micro_batch_size_per_gpu": max(1, batch // n_dev),
            "optimizer": {"type": "adamw",
                          "params": {"lr": 1e-4, "weight_decay": 0.1}},
            "zero_optimization": {"stage": 3, **zero_extra},
            "bf16": {"enabled": bool(on_tpu)},
            "gradient_clipping": 1.0,
            "steps_per_print": 1000,
        }
        engine, *_ = ds.initialize(model=model, config=config,
                                   rng=jax.random.PRNGKey(0))
        gb = int(engine.config.train_batch_size)
        rng = np.random.default_rng(0)
        batches = [jax.device_put({"input_ids": rng.integers(
            0, model.vocab_size, size=(gb, seq), dtype=np.int32)})
            for _ in range(4)]
        for i in range(warmup):
            float(engine.train_batch(iter([batches[i % 4]])))
        t0 = time.perf_counter()
        loss = None
        for i in range(steps):
            loss = engine.train_batch(iter([batches[i % 4]]))
        loss_val = float(loss)
        dt = time.perf_counter() - t0
        rec = {"step_ms": round(dt / steps * 1e3, 3),
               "loss": loss_val}
        rl, rec["roofline"] = _roofline(engine, dt / steps)
        plan = getattr(engine, "_overlap_plan", None)
        if plan is not None:
            rec["overlap"] = {
                "chunks": plan.n_chunks,
                "prefetch": plan.prefetch,
                "regather": plan.regather,
                "bucket_bytes": plan.bucket_bytes,
                "transient_hbm_bytes": int(plan.transient_bytes()),
            }
        return rec

    mono = run({"overlap_comm": False})
    chunked = run(chunk_knobs)
    speedup = (mono["step_ms"] / chunked["step_ms"]
               if chunked["step_ms"] else 0.0)
    result = {
        "metric": f"zero3 overlap A/B llama3-{size} seq{seq} dp{n_dev} "
                  f"{dev0.platform}",
        "value": round(speedup, 4),
        "unit": "x step-time vs monolithic",
        "extra": {
            "monolithic": mono, "chunked": chunked,
            "loss_abs_diff": abs(mono["loss"] - chunked["loss"]),
            "platform": dev0.platform, "n_devices": n_dev,
            "steps": steps, "seq": seq,
        },
    }
    print(json.dumps(result))
    if getattr(args, "trace", None):
        from deepspeed_tpu.telemetry import tracer
        tracer.dump(args.trace)


def chaos_main(args) -> None:
    """--chaos: short training run under a scripted fault plan (one
    poisoned step, one transient checkpoint IO error, one torn fragment)
    proving the recovery paths end-to-end. The BENCH line's value is the
    recovery ratio — 1.0 means every injected fault was answered by
    exactly one recovery (skipped step / IO retry / CRC fallback)."""
    import glob
    import tempfile

    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models.llama import llama3_config

    n_dev = len(jax.devices())
    seq = args.seq or 64
    batch = args.batch or n_dev
    steps = max(args.steps or 8, 7)
    ds.build_mesh(data=n_dev)
    model = llama3_config("tiny", max_seq_len=seq, tie_embeddings=True)
    config = {
        "train_micro_batch_size_per_gpu": max(1, batch // n_dev),
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 1000,
        "resilience": {"fault_plan":
                       "step:2:nonfinite_grad;step:5:io_error:checkpoint;"
                       "step:6:torn_fragment:checkpoint"},
        # goodput ledger: attribute the drill's wall clock (the
        # fault_recovery/ckpt categories are the drill's cost accounting)
        "telemetry": {"goodput": {"enabled": True}},
    }
    engine, *_ = ds.initialize(model=model, config=config,
                               rng=jax.random.PRNGKey(0))
    gb = int(engine.config.train_batch_size)
    rng = np.random.default_rng(0)
    batches = [{"input_ids": rng.integers(
        0, model.vocab_size, size=(gb, seq), dtype=np.int32)}
        for _ in range(4)]
    recovered_steps = 0
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as ckpt:
        for i in range(steps):
            if engine.global_steps == 4:
                # clean tag committed BEFORE the checkpoint-site faults
                # become due — the fallback target
                engine.save_checkpoint(ckpt, tag="good")
            loss = float(engine.train_batch(iter([batches[i % 4]])))
            if loss != loss:                         # NaN → poisoned step
                recovered_steps += 1
        # final save: the io_error fires (absorbed by the bounded retry)
        # and the torn_fragment advisory truncates one fragment — the
        # load below must CRC-reject "final" and fall back to "good"
        engine.save_checkpoint(ckpt, tag="final")
        tag, _ = engine.load_checkpoint(ckpt)
        quarantined = glob.glob(os.path.join(ckpt, "*.quarantined*"))
    dt = time.perf_counter() - t0
    reg = telemetry.registry
    faults = int(reg.counter("resilience/faults_injected").value)
    recoveries = int(reg.counter("resilience/recoveries").value)
    fallbacks = int(reg.counter("resilience/ckpt_fallbacks").value)
    result = {
        "metric": f"chaos recovery ledger llama3-tiny seq{seq} "
                  f"dp{n_dev} ({steps} steps, 3 faults)",
        "value": round(recoveries / faults, 4) if faults else 0.0,
        "unit": "recoveries/faults",
        "extra": {
            "faults_injected": faults,
            "recoveries": recoveries,
            "recovered_steps": recovered_steps,
            "fallbacks": fallbacks,
            "ckpt_retries": int(
                reg.counter("resilience/ckpt_retries").value),
            "resumed_tag": tag,
            "quarantined": len(quarantined),
            "wall_s": round(dt, 3),
        },
    }
    gp = _goodput_extra()
    if gp:
        result["extra"]["goodput"] = gp
    print(json.dumps(result))


def _goodput_extra():
    """Final ledger sweep → the BENCH ``extra.goodput`` stamp ({} on any
    failure — the stamp must never take the bench down)."""
    try:
        from deepspeed_tpu.telemetry.goodput import goodput_ledger
        goodput_ledger.update()
        s = goodput_ledger.summary() or {}
        return {k: s.get(k) for k in
                ("uptime_s", "goodput_s", "fraction", "window_fraction",
                 "badput", "dominant_badput", "dominant_badput_s",
                 "captures")} if s else {}
    except Exception:                                # noqa: BLE001
        return {}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default=None,
                    help="llama3 preset (tiny/350m/1b/8b); default by platform")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--mode", default="dense", choices=("dense", "moe"))
    ap.add_argument("--overlap", action="store_true",
                    help="A/B the chunked overlap-scheduled ZeRO-3 "
                         "collectives vs the monolithic stage-3 path "
                         "(knobs: DSTPU_BENCH_OVERLAP_BUCKET/_PREFETCH/"
                         "_REGATHER)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record host-side spans and dump Chrome trace-event"
                         " JSON here (inspect with bin/dstpu-trace or "
                         "ui.perfetto.dev)")
    ap.add_argument("--health-ab", action="store_true",
                    help="A/B the in-graph model-health taps "
                         "(telemetry.health every=1 vs disabled) on the "
                         "tiny MoE bench and report % step-time overhead")
    ap.add_argument("--chaos", action="store_true",
                    help="run a short training loop under a scripted "
                         "fault plan (dstpu-chaos) and report the "
                         "recovery ledger instead of MFU")
    ap.add_argument("--from-config", default=None, metavar="JSON",
                    help="replay a dstpu-tune winner: build the mesh and "
                         "engine from the emitted config and stamp "
                         "predicted-vs-measured step time into "
                         "extra.tune")
    args = ap.parse_args()

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.trace:
        from deepspeed_tpu.telemetry import tracer
        tracer.configure(enabled=True)
    if args.from_config:
        from_config_main(args)
        return
    if args.chaos:
        chaos_main(args)
        return
    if args.health_ab:
        health_main(args)
        return
    if args.overlap:
        overlap_main(args)
        return
    if args.mode == "moe":
        moe_main(args)
        return
    import jax
    dev0 = jax.devices()[0]
    platform = dev0.platform
    on_tpu = platform == "tpu"
    n_dev = len(jax.devices())

    size = _pick_size(args, dev0)
    seq = args.seq or (2048 if on_tpu else 128)
    batch = args.batch or 8
    steps = args.steps or (48 if on_tpu else 3)
    warmup = 3 if on_tpu else 1

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.llama import llama3_config

    ds.build_mesh(data=n_dev)

    model = llama3_config(size, max_seq_len=seq, tie_embeddings=True)
    config = dense_train_config(n_dev, batch, seq, on_tpu)
    _apply_bench_slo(config)
    # DSTPU_BENCH_OFFLOAD=cpu|cpu_overlap|zenflow: measure the ZeRO-Offload
    # host-optimizer step (sync / overlapped / ZenFlow selective) against
    # the device step (the VERDICT r1 #6 'measure and report both' criterion)
    off = os.environ.get("DSTPU_BENCH_OFFLOAD")
    if off:
        config["optimizer"]["params"].pop("state_dtype", None)
        config["optimizer"]["params"].pop("master_weights", None)
        config["zero_optimization"]["stage"] = max(
            2, config["zero_optimization"]["stage"])
        config["zero_optimization"]["offload_optimizer"] = {
            "device": "cpu", "overlap": off == "cpu_overlap"}
        if off == "zenflow":
            config["zero_optimization"]["zenflow"] = {
                "topk_ratio": 0.05, "update_interval": 4,
                "select_interval": 32, "full_warm_up_rounds": 2}
    engine, *_ = ds.initialize(model=model, config=config,
                               rng=jax.random.PRNGKey(0))

    gb = int(engine.config.train_batch_size)
    rng = np.random.default_rng(0)
    # distinct batches (cycled) so the reported loss reflects real training,
    # pre-staged on device so the timed loop measures compute, not input PCIe
    n_distinct = 8
    batches = [
        jax.device_put({"input_ids": rng.integers(
            0, model.vocab_size, size=(gb, seq), dtype=np.int32)})
        for _ in range(n_distinct)]

    for i in range(warmup):
        float(engine.train_batch(iter([batches[i % n_distinct]])))

    # async dispatch: no per-step host fetch (a scalar fetch per step
    # drains the device queue); block once at the end.
    # ONE long window beats best-of-short-windows here: the end-of-window
    # loss fetch is a full pipeline drain, so short windows amortize it
    # worse (measured 55.7% MFU best-of-3x8-step windows vs 56.2% as one
    # 24-step window; the shipped default is one 48-step window — 56.3%)
    t0 = time.perf_counter()
    loss = None
    for i in range(steps):
        loss = engine.train_batch(iter([batches[i % n_distinct]]))
    loss_val = float(loss)
    dt = time.perf_counter() - t0

    tokens = gb * seq * steps
    tok_per_sec_chip = tokens / dt / n_dev
    flops_per_token = 6.0 * model.num_params()
    # +attention quadratic term: 12 * L * d * T per token (causal half)
    attn = 12.0 * model.num_layers * model.hidden_size * seq * 0.5
    achieved = (flops_per_token + attn) * tokens / dt / n_dev
    peak = _peak_flops(jax.devices()[0])
    mfu = achieved / peak if peak else 0.0

    stage = config["zero_optimization"]["stage"]
    prec = "bf16" if on_tpu else "fp32"
    result = {
        "metric": f"tokens/sec/chip llama3-{size} seq{seq} zero{stage} {prec}",
        "value": round(tok_per_sec_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4) if peak else 0.0,
        "extra": {
            "mfu": round(mfu, 4),
            "achieved_tflops_per_chip": round(achieved / 1e12, 2),
            "params_b": round(model.num_params() / 1e9, 3),
            "loss": loss_val,
            "platform": platform,
            "n_devices": n_dev,
            "steps": steps,
            "global_batch": gb,
            "slo": _slo_extra(engine),
        },
    }
    result["extra"]["roofline"] = _roofline(engine, dt / steps)[1]
    print(json.dumps(result))
    if args.trace:
        from deepspeed_tpu.telemetry import tracer
        tracer.dump(args.trace)


if __name__ == "__main__":
    main()
