"""Serving-side autotuning: size the PR 10/11 fleet knobs from roofline
cost records and a declared traffic mix.

The hand-picked knobs this replaces — router replica counts,
prefill/decode pool splits, autoscale floors/ceilings,
SplitFuse token budgets, hedge delays — all derive from two numbers the
cost model already predicts: the prefill bucket-step time and the decode
step time (``engine_v2.cost_records()`` when an engine exists,
:func:`predict_serving_records` for offline ``--chips N`` sizing). The
emitted ``serving.*`` / ``router.*`` / ``autoscale.*`` blocks are
validated through the real config classes before they leave this module,
so ``dstpu-tune``'s JSON loads cleanly into ``DeepSpeedTPUConfig`` and
straight into ``Router(...)`` / ``Autoscaler(...)`` kwargs.

Zero predictions (CPU host, no ``--platform``) self-disable the sizing —
the plan comes back with the config-class defaults and
``"model": "none"`` — mirroring the frontend's SLO-admission
self-disable on the same records.
"""

import math
from dataclasses import asdict, dataclass
from typing import Any, Dict, Optional

from deepspeed_tpu.telemetry.explain import Peaks, Roofline


@dataclass
class TrafficMix:
    """The declared target traffic the plan sizes against."""
    rps_peak: float = 4.0           #: requests/s at the diurnal peak
    prompt_tokens: int = 512        #: mean prompt length
    gen_tokens: int = 128           #: mean generated tokens
    swing: float = 4.0              #: peak/trough demand ratio
    utilization: float = 0.6        #: target busy fraction per replica
    headroom: float = 1.25          #: ceiling margin over peak demand


def predict_serving_records(dec_cfg, peaks: Peaks, n_bucket: int = 8,
                            prefill_chunk: int = 32,
                            context_tokens: Optional[int] = None,
                            p_bytes: int = 2) -> Dict[str, Any]:
    """Analytic stand-in for ``engine_v2.cost_records()`` when no engine
    exists (offline ``--chips N`` sizing): closed-form FLOPs/bytes for
    one prefill bucket step (``n_bucket × prefill_chunk`` tokens) and one
    decode step (``n_bucket`` tokens, weights + KV-cache reads), scored
    through the same :class:`Roofline`. Record shape matches
    ``explain_serving`` — ``predicted_s``/``bound``/``n_bucket``/
    ``chunk`` — so :func:`plan_serving` consumes either source."""
    N = float(dec_cfg.num_params())
    ctx = int(context_tokens or min(dec_cfg.max_seq_len, 1024))
    kv_per_tok = 2.0 * dec_cfg.num_layers * dec_cfg.kv_heads * \
        dec_cfg.head_dim * p_bytes
    records: Dict[str, Any] = {}
    for label, toks in (("prefill", n_bucket * prefill_chunk),
                        ("decode", n_bucket)):
        flops = 2.0 * N * toks
        hbm = N * p_bytes + toks * kv_per_tok * (ctx if label == "decode"
                                                 else 1)
        rl = Roofline(flops=flops, bytes=hbm,
                      peak_flops=peaks.peak_flops, hbm_bw=peaks.hbm_bw,
                      ici_bw=peaks.ici_bw)
        records[label] = {
            "name": f"serving_{label}", "available": bool(rl.predicted_s),
            "flops": flops, "bytes_accessed": hbm, "collective_bytes": 0.0,
            "n_bucket": n_bucket,
            "chunk": prefill_chunk if label == "prefill" else 1,
            "predicted_s": rl.predicted_s, "bound": rl.bound,
            "error": None, "source": "analytic",
        }
    records["platform"] = peaks.kind
    return records


def _default_plan(note: str) -> Dict[str, Any]:
    """Sizing self-disabled: emit the config-class defaults so the plan
    still loads cleanly, flagged so nobody mistakes it for a model."""
    from deepspeed_tpu.config.config import (AutoscaleConfig, RouterConfig,
                                             ServingConfig)
    return {"model": "none", "notes": [note],
            "serving": ServingConfig().model_dump(),
            "router": RouterConfig().model_dump(),
            "autoscale": AutoscaleConfig().model_dump(),
            "engine": {}, "predictions": {}}


def plan_serving(records: Dict[str, Any], mix: Optional[TrafficMix] = None,
                 validate: bool = True) -> Dict[str, Any]:
    """Size the fleet knobs from cost ``records`` (either
    ``engine_v2.cost_records()`` or :func:`predict_serving_records`)
    against ``mix``. Deterministic closed-form sizing:

    - decode replicas: demand ``rps·gen_tokens`` tokens/s over a
      replica's ``utilization · n_bucket / t_dec``;
    - prefill replicas: ``rps·prompt_tokens`` over
      ``utilization · n_bucket·chunk / t_pre``;
    - floors from the diurnal trough (peak/swing), ceilings at
      ``headroom`` over peak demand;
    - ``queue_high`` at the utilization knee of the decode bucket;
    - SplitFuse budget: prefill tokens per mixed step capped so a mixed
      step costs ≲ 2 decode steps (decode-latency protection);
    - hedge delay: 2× the predicted no-queue TTFT (a hedge below the
      service floor would fire on every request).
    """
    mix = mix or TrafficMix()
    pre, dec = records.get("prefill", {}), records.get("decode", {})
    t_pre = float(pre.get("predicted_s") or 0.0)
    t_dec = float(dec.get("predicted_s") or 0.0)
    if t_pre <= 0.0 or t_dec <= 0.0:
        return _default_plan(
            "no step-time predictions (zero peaks / unavailable cost "
            "analysis) — serving plan self-disabled to defaults, like "
            "the frontend's SLO admission")
    nb = max(1, int(dec.get("n_bucket") or 8))
    chunk = max(1, int(pre.get("chunk") or 32))

    dec_cap = mix.utilization * nb / t_dec            # tokens/s/replica
    pre_cap = mix.utilization * nb * chunk / t_pre
    dec_demand = mix.rps_peak * mix.gen_tokens
    pre_demand = mix.rps_peak * mix.prompt_tokens
    dec_peak = max(1, math.ceil(dec_demand / dec_cap))
    pre_peak = max(1, math.ceil(pre_demand / pre_cap))
    swing = max(1.0, mix.swing)
    dec_min = max(1, math.ceil(dec_demand / swing / dec_cap))
    pre_min = max(1, math.ceil(pre_demand / swing / pre_cap))
    dec_max = max(dec_peak, math.ceil(dec_peak * mix.headroom), dec_min)
    pre_max = max(pre_peak, math.ceil(pre_peak * mix.headroom), pre_min)

    # SplitFuse: prefill-token budget per mixed step — a mixed step may
    # cost at most ~2 decode steps extra
    tau = t_pre / (nb * chunk)                        # s per prefill token
    budget = int(min(nb * chunk, max(chunk, 2.0 * t_dec / tau)))

    ttft_best = math.ceil(mix.prompt_tokens / chunk) * t_pre + t_dec
    hedge_delay = max(0.05, round(2.0 * ttft_best, 3))

    serving_block: Dict[str, Any] = {}      # ServingConfig has no key
    router_block = {
        "replicas": pre_peak + dec_peak,
        "affinity_tokens": max(8, min(64, mix.prompt_tokens // 2)),
        "hedge": True,
        "hedge_delay_s": hedge_delay,
    }
    autoscale_block = {
        "enabled": True,
        "prefill_min": pre_min, "prefill_max": pre_max,
        "decode_min": dec_min, "decode_max": dec_max,
        "queue_high": max(1.0, round(mix.utilization * nb, 1)),
    }
    if validate:
        from deepspeed_tpu.config.config import (AutoscaleConfig,
                                                 RouterConfig,
                                                 ServingConfig)
        ServingConfig(**serving_block)
        RouterConfig(**router_block)
        AutoscaleConfig(**autoscale_block)
    return {
        "model": "roofline",
        "notes": [],
        "serving": serving_block,
        "router": router_block,
        "autoscale": autoscale_block,
        #: engine-level recommendations (engine_v2 construction dict keys)
        "engine": {"max_batch_tokens": budget, "prefill_chunk": chunk,
                   "max_sequences": nb},
        "predictions": {
            "prefill_step_ms": t_pre * 1e3, "decode_step_ms": t_dec * 1e3,
            "prefill_bound": pre.get("bound"), "decode_bound": dec.get("bound"),
            "ttft_best_case_s": ttft_best,
            "decode_tokens_per_s_per_replica": dec_cap,
            "prefill_tokens_per_s_per_replica": pre_cap,
            "platform": records.get("platform"),
        },
        "traffic": asdict(mix),
    }
