"""Autotuner — micro-batch / ZeRO-config search with an HBM memory model.

Reference: ``autotuning/autotuner.py:42`` (``Autotuner``: builds a space of
micro-batch sizes × ZeRO stages (+offload), launches short experiment runs,
ranks by throughput, reports the best config; ``tune()``, model-info
profiling, FAST mode). The reference orchestrates subprocess experiment
launches through the DeepSpeed launcher; on TPU a candidate is just an
engine construction + a few jitted steps in-process — the measurement is
identical (steps/sec after compile warmup) without the process plumbing.

Memory model (reference FAST mode: ``_get_model_info``/mem estimates prune
the space BEFORE launching): per candidate, predict device HBM from
abstract shapes — params/grads/optimizer state divided by their ZeRO
sharding factors, plus a remat-policy-dependent activation estimate and
the CE-chunk workspace — and skip predicted-infeasible configs without
building them. On a real chip each skipped candidate saves an engine
build + compile + RESOURCE_EXHAUSTED unwind (minutes on a v5e).

Candidates that pass the model but still fail at run time are recorded as
infeasible and the sweep continues — the reference does the same via
experiment exit codes.
"""

import copy
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax

from deepspeed_tpu.utils.logging import log_dist, logger


@dataclass
class TuneResult:
    config: Dict[str, Any]
    throughput: float           #: samples/sec (0 → infeasible)
    step_time: float
    error: Optional[str] = None
    #: True when the memory model rejected the candidate WITHOUT building
    predicted_oom: bool = False
    #: memory-model breakdown in bytes (also set for measured candidates)
    predicted_hbm: Optional[Dict[str, float]] = None
    #: backend-reported peak HBM bytes for candidates that actually ran
    #: (None when the backend exposes no memory stats)
    measured_hbm: Optional[int] = None

    @property
    def feasible(self) -> bool:
        return self.error is None


def device_peak_bytes() -> Optional[int]:
    """Backend-reported peak HBM in use (None when unavailable — e.g.
    the CPU backend). Reset is not exposed by all runtimes, so callers
    compare peaks measured after their own workload ran."""
    try:
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        return int(peak) if peak else None
    except Exception:
        return None


def calibration_report(results, tolerance: float = 0.20) -> Dict[str, Any]:
    """Predicted-vs-measured HBM calibration over the candidates that
    actually ran (VERDICT r4 #7: an uncalibrated model re-introduces the
    OOM-by-building failure mode it exists to prevent). ``ok`` is False
    when any candidate's |predicted - measured| / measured exceeds
    ``tolerance`` — the sweep report carries the failure loudly."""
    rows = []
    for r in results:
        if r.measured_hbm and r.predicted_hbm and r.error is None:
            pred = float(r.predicted_hbm["total"])
            meas = float(r.measured_hbm)
            rows.append({
                "micro_batch": r.config.get(
                    "train_micro_batch_size_per_gpu"),
                "zero_stage": (r.config.get("zero_optimization", {})
                               or {}).get("stage"),
                "predicted_gib": round(pred / 2**30, 3),
                "measured_gib": round(meas / 2**30, 3),
                "pct_error": round((pred - meas) / meas * 100.0, 1),
            })
    worst = max((abs(c["pct_error"]) for c in rows), default=0.0)
    return {"tolerance_pct": tolerance * 100.0, "candidates": rows,
            "max_abs_pct_error": worst,
            # None (not True) when nothing was measurable: an empty
            # calibration must not read as a passing one
            "ok": (worst <= tolerance * 100.0) if rows else None,
            "caveat": ("peak_bytes_in_use is process-cumulative: a "
                       "candidate's measurement can include residual "
                       "live buffers from earlier candidates, and "
                       "candidates that never exceed the prior peak "
                       "record no measurement — run single-candidate "
                       "sweeps for a clean calibration")}


def estimate_candidate_hbm(dec_cfg, config: Dict[str, Any], mesh,
                           seq_len: Optional[int] = None) -> Dict[str, float]:
    """Predict per-device HBM for one candidate from abstract shapes only
    (nothing is allocated). Returns a component breakdown plus 'total'.

    Model (coarse by design, mirrored on the reference's FAST-mode
    activation/model-state estimates):
      params   — compute-dtype leaves; stage 3 shards them over the data
                 axes, MiCS over 'data_inner'.
      grads    — one transient compute-dtype copy; reduce-scattered (so
                 sharded) at stage ≥ 2.
      opt      — Adam family: fp32 master (unless master_weights=False or
                 params already fp32) + two moments in state_dtype; sharded
                 at stage ≥ 1; 0 on device when offloaded to cpu/nvme.
      acts     — scan-carry residuals per layer per token by remat policy
                 + one block's recompute working set + CE chunk workspace.
    """
    zo = config.get("zero_optimization", {}) or {}
    stage = int(zo.get("stage", 0))
    off_dev = (zo.get("offload_optimizer", {}) or {}).get("device", "none")
    bf16 = bool((config.get("bf16", {}) or {}).get("enabled"))
    p_bytes = 2 if bf16 else 4
    opt_p = (config.get("optimizer", {}) or {}).get("params", {}) or {}
    state_bytes = 2 if str(opt_p.get("state_dtype", "")).startswith("bf") \
        else 4
    master = opt_p.get("master_weights", True) and bf16

    d = dec_cfg.hidden_size
    ffn = dec_cfg.ffn_size
    L = dec_cfg.num_layers
    V = dec_cfg.vocab_size
    T = seq_len or dec_cfg.max_seq_len
    B = int(config.get("train_micro_batch_size_per_gpu", 1))
    N = dec_cfg.num_params()

    dp = mesh.shape.get("data", 1) * mesh.shape.get("data_inner", 1)
    mics = int(zo.get("mics_shard_size", 0) or 0)
    param_shard = (mics if mics > 1 else dp) if stage >= 3 else 1
    grad_shard = dp if stage >= 2 else 1
    opt_shard = dp if stage >= 1 else 1

    params = N * p_bytes / param_shard
    grads = N * p_bytes / grad_shard
    if off_dev in ("cpu", "nvme"):
        opt = 0.0
    else:
        opt = N * ((4 if master else 0) + 2 * state_bytes) / opt_shard

    # residuals saved per layer per token (bytes / d), by policy
    policy = (config.get("activation_checkpointing", {}) or {}) \
        .get("policy") or "none"
    act = 2 if dec_cfg.is_glu else 1   # silu_glu keeps 3·ffn recompute live
    per_layer_d = {
        "full": 1.0, "offload_full": 0.0,
        # block_in AND the flash residuals parked on host: no per-layer
        # device residency at all (the 128K+ policy)
        "offload_save_attn_kernel_host": 0.0,
        "offload_attn_out": 1.0, "offload_attn_qkv": 1.0,
        "save_attn_out": 2.0, "save_attn_kernel": 2.0,
        "offload_save_attn_out": 1.0, "offload_save_attn_kernel": 1.0,
        "save_attn_qkv": 2.0 + (dec_cfg.q_dim
                                + 2 * dec_cfg.kv_heads * dec_cfg.head_dim) / d,
        "save_attn_kernel_qkv": 2.0 + (
            dec_cfg.q_dim + 2 * dec_cfg.kv_heads * dec_cfg.head_dim) / d,
        # no remat: everything lives until backward
        "none": 6.0 + act * 3.0 * ffn / d,
        "dots_saveable": 4.0 + act * 1.5 * ffn / d,
        "nothing_saveable": 1.0,
        "dots_with_no_batch_dims_saveable": 1.0,
    }.get(policy, 2.0)
    carry = L * B * T * d * p_bytes * per_layer_d
    # one block recompute; the sequence-chunked MLP (ffn_chunk) caps the
    # live [*, ffn] tiles at chunk tokens instead of the full T
    ffn_chunk = int((config.get("activation_checkpointing", {}) or {})
                    .get("ffn_chunk") or 0)
    t_ffn = min(T, ffn_chunk) if ffn_chunk else T
    working = B * (T * 4 * d + t_ffn * 3 * ffn) * p_bytes
    ce_mb = config.get("chunked_ce_budget_mb")
    ce = (int(ce_mb) * 2 ** 20 * 2 if ce_mb
          else B * T * V * (2 if config.get("ce_logits_dtype") else 4))
    total = (params + grads + opt + carry + working + ce) * 1.15  # fudge
    return {"params": params, "grads": grads, "opt": opt,
            "activations": carry + working, "ce": ce, "total": total}


def device_hbm_bytes(default: Optional[int] = None) -> Optional[int]:
    """Per-chip HBM capacity, from the backend when it reports one."""
    try:
        stats = jax.devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        if limit:
            return int(limit)
    except Exception:
        pass
    return default


class Autotuner:
    """Sweep engine configs, rank by measured throughput (reference
    Autotuner.tune).

    ``batch_fn(micro_batch_size) -> batch dict`` supplies one microbatch
    of the right shape per candidate.
    """

    def __init__(self, model, base_config: Dict[str, Any],
                 batch_fn: Callable[[int], Dict[str, Any]],
                 micro_batch_sizes: Optional[List[int]] = None,
                 zero_stages: Optional[List[int]] = None,
                 remat_policies: Optional[List[str]] = None,
                 ce_budgets_mb: Optional[List[int]] = None,
                 steps: int = 5, warmup: int = 2,
                 rng: Optional[jax.Array] = None,
                 hbm_bytes: Optional[int] = None,
                 memory_model: bool = True):
        self.model = model
        self.base_config = base_config
        self.batch_fn = batch_fn
        self.micro_batch_sizes = micro_batch_sizes or [1, 2, 4, 8]
        self.zero_stages = zero_stages or [2, 3]
        #: optional extra sweep axes (both proved decisive on the v5e
        #: bench: remat policy and the chunked-CE logits budget)
        self.remat_policies = remat_policies or [None]
        self.ce_budgets_mb = ce_budgets_mb or [None]
        self.steps = steps
        self.warmup = warmup
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        #: per-chip HBM budget for the memory model; auto-detected from the
        #: backend when it reports a limit (CPU virtual meshes don't — pass
        #: explicitly to exercise pruning there)
        self.hbm_bytes = hbm_bytes if hbm_bytes is not None \
            else device_hbm_bytes()
        self.memory_model = memory_model and self.hbm_bytes is not None
        self.results: List[TuneResult] = []

    def _decoder_config(self):
        dc = getattr(self.model, "decoder_config", None)
        if dc is not None:
            return dc
        return self.model if hasattr(self.model, "num_params") else None

    def _candidates(self) -> Iterator[Dict[str, Any]]:
        for stage in self.zero_stages:
            for mbs in self.micro_batch_sizes:
                for remat in self.remat_policies:
                    for ce_mb in self.ce_budgets_mb:
                        cfg = copy.deepcopy(self.base_config)
                        cfg["train_micro_batch_size_per_gpu"] = mbs
                        cfg.pop("train_batch_size", None)
                        cfg.setdefault("zero_optimization",
                                       {})["stage"] = stage
                        if remat is not None:
                            cfg.setdefault("activation_checkpointing",
                                           {})["policy"] = remat
                        if ce_mb is not None:
                            cfg["chunked_ce_budget_mb"] = ce_mb
                        yield cfg

    def _measure(self, cfg: Dict[str, Any],
                 pred: Optional[Dict[str, float]] = None) -> TuneResult:
        from deepspeed_tpu.parallel.mesh import get_mesh
        from deepspeed_tpu.runtime.engine import initialize
        mbs = cfg["train_micro_batch_size_per_gpu"]
        # the cumulative peak BEFORE this candidate: peak_bytes_in_use is
        # monotone (no reset API), so a candidate's own peak is only
        # observable when it sets a new high-water mark
        peak_before = device_peak_bytes()
        try:
            # chunked_ce_budget_mb is a REAL config key, so the winning
            # config in autotune_best.json reproduces the measured run
            # when fed straight back to initialize()
            engine, *_ = initialize(model=self.model, config=cfg,
                                    mesh=get_mesh(), rng=self.rng)
            batch = self.batch_fn(mbs)
            gas = int(engine.config.gradient_accumulation_steps)
            it = lambda: iter([batch] * gas)
            for _ in range(self.warmup):
                float(engine.train_batch(it()))
            t0 = time.perf_counter()
            loss = None
            for _ in range(self.steps):
                loss = engine.train_batch(it())
            float(loss)
            dt = (time.perf_counter() - t0) / self.steps
            tput = int(engine.config.train_batch_size) / dt
            peak_after = device_peak_bytes()
            measured = (peak_after if peak_after and
                        (peak_before is None or peak_after > peak_before)
                        else None)       # stale high-water mark: unknown
            return TuneResult(config=cfg, throughput=tput, step_time=dt,
                              predicted_hbm=pred, measured_hbm=measured)
        except Exception as e:          # OOM / invalid combo → infeasible
            logger.warning(f"autotune candidate failed: {e}")
            return TuneResult(config=cfg, throughput=0.0, step_time=0.0,
                              error=str(e)[:500])

    def _predict(self, cfg: Dict[str, Any]):
        """Memory-model gate → (gate_result, estimate): gate_result is a
        predicted-OOM TuneResult (skip the build entirely) or None when
        the candidate fits; the estimate threads into _measure so the
        calibration record reuses it instead of recomputing."""
        dec = self._decoder_config()
        if not self.memory_model or dec is None:
            return None, None
        from deepspeed_tpu.parallel.mesh import get_mesh
        try:
            est = estimate_candidate_hbm(dec, cfg, get_mesh())
        except Exception as e:      # a model the estimator can't shape
            logger.warning(f"autotune memory model failed ({e}); "
                           f"building the candidate unguarded")
            return None, None
        if est["total"] <= self.hbm_bytes:
            return None, est
        return TuneResult(
            config=cfg, throughput=0.0, step_time=0.0,
            error=(f"predicted OOM: {est['total'] / 2**30:.2f} GiB > "
                   f"{self.hbm_bytes / 2**30:.2f} GiB HBM "
                   f"(params {est['params'] / 2**30:.2f}, opt "
                   f"{est['opt'] / 2**30:.2f}, acts "
                   f"{est['activations'] / 2**30:.2f})"),
            predicted_oom=True, predicted_hbm=est), est

    def tune(self, results_dir: Optional[str] = None) -> TuneResult:
        """Run the sweep; returns the best feasible candidate (reference
        autotuner 'tune' + results json output)."""
        for cfg in self._candidates():
            gate, est = self._predict(cfg)
            res = gate or self._measure(cfg, pred=est)
            self.results.append(res)
            extras = ""
            ac = cfg.get("activation_checkpointing", {}).get("policy")
            if ac:
                extras += f" remat={ac}"
            if "chunked_ce_budget_mb" in cfg:
                extras += f" ce={cfg['chunked_ce_budget_mb']}MB"
            log_dist(
                f"autotune: mbs={cfg['train_micro_batch_size_per_gpu']} "
                f"zero={cfg['zero_optimization']['stage']}{extras} → "
                f"{res.throughput:.1f} samples/s"
                + (f" (FAILED: {res.error[:60]})" if res.error else ""))
        feasible = [r for r in self.results if r.feasible]
        if not feasible:
            raise RuntimeError("autotuning found no feasible config")
        best = max(feasible, key=lambda r: r.throughput)
        cal = calibration_report(self.results)
        if cal["candidates"] and not cal["ok"]:
            logger.error(
                f"autotune memory-model calibration FAILED: worst "
                f"|predicted-measured| = {cal['max_abs_pct_error']:.1f}% "
                f"> {cal['tolerance_pct']:.0f}% tolerance — the predicted-"
                f"OOM gate may prune configs that fit (or admit ones "
                f"that don't); details in autotune_results.json")
        if results_dir:
            os.makedirs(results_dir, exist_ok=True)
            with open(os.path.join(results_dir, "autotune_results.json"),
                      "w") as fh:
                json.dump({"candidates": [
                    {"config": r.config,
                     "throughput": r.throughput,
                     "step_time": r.step_time,
                     "error": r.error,
                     "predicted_oom": r.predicted_oom,
                     "predicted_hbm_gib": (
                         round(r.predicted_hbm["total"] / 2**30, 3)
                         if r.predicted_hbm else None),
                     "measured_hbm_gib": (
                         round(r.measured_hbm / 2**30, 3)
                         if r.measured_hbm else None)}
                    for r in self.results],
                    "calibration": cal},
                    fh, indent=1)
            with open(os.path.join(results_dir, "autotune_best.json"),
                      "w") as fh:
                json.dump(best.config, fh, indent=1)
        return best
