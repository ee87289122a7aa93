"""``dstpu-tune`` — roofline-driven offline config search.

Pipeline (all compile-free by default):

1. enumerate — :func:`search.enumerate_candidates` over mesh shape ×
   ZeRO stage × micro-batch × remat × overlap knobs;
2. prune — :func:`search.prune_infeasible` against the target chip's
   HBM capacity (the seed autotuner's memory model, extended with
   TP/SP sharding and overlap transients);
3. score — :func:`search.predict_candidate`'s analytic roofline against
   the platform peak tables; optionally re-score the top N candidates
   by really lowering them through ``explain_engine`` when the mesh
   fits the local devices (``--lower N``);
4. rank — feasible first, known-bound before unknown-bound, ascending
   predicted step time, deterministic tie-break on the candidate key;
5. emit — the winner as a ready-to-run DeepSpeedTPUConfig JSON with a
   ``tune`` stamp, plus ``serving``/``router``/``autoscale`` blocks
   sized by :mod:`.serving_plan` when a traffic mix is declared.

``tune/*`` gauges publish the sweep's shape for dashboards:
candidates enumerated/pruned/unknown-bound and the winner's predicted
step time.
"""

import argparse
import json
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.autotuning.search import (Candidate, SearchSpace,
                                             candidate_hbm,
                                             enumerate_candidates,
                                             predict_candidate,
                                             prune_infeasible, work_proxy)
from deepspeed_tpu.autotuning.serving_plan import (TrafficMix, plan_serving,
                                                   predict_serving_records)
from deepspeed_tpu.telemetry.explain import (Peaks, Roofline, resolve_peaks,
                                             roofline_from_cost)
from deepspeed_tpu.telemetry.registry import registry as _registry
from deepspeed_tpu.utils.logging import logger

#: the pure max(compute, memory, comm) roofline assumes PERFECT overlap
#: of the two non-binding terms — under it every compute-bound candidate
#: at the same per-token FLOPs ties exactly, no matter how much comm it
#: drags along. Scoring charges this fraction of the hidden (non-max)
#: terms as imperfect-overlap residual, so less traffic wins ties.
OVERLAP_RESIDUAL = 0.10


@dataclass
class ScoredCandidate:
    candidate: Candidate
    roofline: Roofline
    penalty_s: float = 0.0
    hbm: Dict[str, float] = field(default_factory=dict)
    source: str = "analytic"          #: "analytic" | "lowered"
    #: global tokens per optimizer step (micro × ga × T × dp) — the
    #: ranking normalizer: the objective is time per token (throughput),
    #: not raw step time, or the sweep would always pick micro_batch=1
    tokens_per_step: float = 1.0

    @property
    def score_s(self) -> float:
        rl = self.roofline
        residual = (rl.compute_s + rl.memory_s + rl.comm_s -
                    rl.predicted_s)
        return rl.predicted_s + self.penalty_s + \
            OVERLAP_RESIDUAL * residual

    @property
    def s_per_token(self) -> float:
        return self.score_s / max(self.tokens_per_step, 1.0)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_per_step / self.score_s if self.score_s else 0.0

    @property
    def bound(self) -> str:
        return self.roofline.bound

    def to_dict(self) -> Dict[str, Any]:
        return {"key": self.candidate.key(),
                "mesh": self.candidate.mesh_dict(),
                "zero_stage": self.candidate.zero_stage,
                "micro_batch": self.candidate.micro_batch,
                "remat": self.candidate.remat,
                "overlap": self.candidate.overlap,
                "predicted_ms": self.roofline.predicted_s * 1e3,
                "penalty_ms": self.penalty_s * 1e3,
                "score_ms": self.score_s * 1e3,
                "tokens_per_step": self.tokens_per_step,
                "tokens_per_s": self.tokens_per_s,
                "bound": self.bound,
                "hbm_gib": round(self.hbm.get("total", 0.0) / 2**30, 3),
                "source": self.source}


@dataclass
class TuneReport:
    platform: str
    chips: int
    seq_len: int
    model_desc: str
    peaks: Peaks
    ranked: List[ScoredCandidate] = field(default_factory=list)
    pruned: List[Tuple[str, str]] = field(default_factory=list)
    serving_plan: Optional[Dict[str, Any]] = None

    def best(self) -> Optional[ScoredCandidate]:
        return self.ranked[0] if self.ranked else None

    def to_dict(self, top: int = 10) -> Dict[str, Any]:
        return {"platform": self.platform, "chips": self.chips,
                "seq_len": self.seq_len, "model": self.model_desc,
                "candidates_ranked": len(self.ranked),
                "candidates_pruned": len(self.pruned),
                "ranked": [s.to_dict() for s in self.ranked[:top]],
                "pruned": [{"key": k, "reason": r}
                           for k, r in self.pruned[:top]],
                "serving_plan": self.serving_plan}

    def render(self, top: int = 10) -> str:
        out = [f"== dstpu-tune ({self.model_desc}, {self.chips} chips, "
               f"platform {self.platform}, seq {self.seq_len}) ==",
               f"candidates: {len(self.ranked)} ranked, "
               f"{len(self.pruned)} pruned (HBM)",
               "",
               f"  {'#':<3}{'candidate':<46}{'bound':<9}"
               f"{'pred ms':>9}{'Mtok/s':>9}{'hbm GiB':>9}  src"]
        for i, s in enumerate(self.ranked[:top]):
            out.append(
                f"  {i + 1:<3}{s.candidate.key()[:45]:<46}{s.bound:<9}"
                f"{s.roofline.predicted_s * 1e3:>9.2f}"
                f"{s.tokens_per_s / 1e6:>9.3f}"
                f"{s.hbm.get('total', 0.0) / 2**30:>9.2f}  {s.source}")
        if not self.ranked:
            out.append("  (no feasible candidates)")
        if self.serving_plan:
            p = self.serving_plan
            if p.get("model") == "none":
                out.append("")
                out.append(f"serving plan: self-disabled — "
                           f"{p['notes'][0] if p.get('notes') else ''}")
            else:
                pred = p["predictions"]
                a = p["autoscale"]
                out.append("")
                out.append(
                    f"serving plan ({p['model']}): prefill "
                    f"{pred['prefill_step_ms']:.2f} ms/step, decode "
                    f"{pred['decode_step_ms']:.2f} ms/step → replicas "
                    f"prefill {a['prefill_min']}..{a['prefill_max']}, "
                    f"decode {a['decode_min']}..{a['decode_max']}, "
                    f"splitfuse {p['engine']['max_batch_tokens']} tok, "
                    f"hedge {p['router']['hedge_delay_s']}s")
        return "\n".join(out)


def _rank_key(s: ScoredCandidate) -> Tuple:
    unknown = s.bound == "unknown"
    norm = max(s.tokens_per_step, 1.0)
    primary = work_proxy(s.roofline) / norm if unknown \
        else s.s_per_token
    return (unknown, primary, s.candidate.key())


def lower_candidate(dec_cfg, cand: Candidate, peaks: Peaks,
                    seq_len: int, platform: Optional[str] = None,
                    base_config: Optional[Dict[str, Any]] = None
                    ) -> Optional[Roofline]:
    """Exact re-score: build the candidate's mesh + engine on the local
    devices and lower the real fused step through ``explain_engine``.
    Only possible when the candidate's chip count fits the host (the
    8-virtual-device CPU mesh covers every ``--chips 8`` smoke). Any
    failure — including a backend whose cost_analysis comes back empty —
    degrades to None / unknown-bound; the sweep continues on the
    analytic score."""
    import jax
    if cand.chips > len(jax.devices()):
        return None
    try:
        from deepspeed_tpu.parallel.mesh import build_mesh
        from deepspeed_tpu.runtime.engine import initialize
        from deepspeed_tpu.telemetry.explain import explain_engine
        mesh = build_mesh(data=cand.data, model=cand.model, seq=cand.seq,
                          expert=cand.expert,
                          devices=jax.devices()[:cand.chips])
        cfg = cand.to_config(base_config)
        import dataclasses as _dc
        model = _dc.replace(dec_cfg, max_seq_len=seq_len) \
            if seq_len != dec_cfg.max_seq_len else dec_cfg
        engine, *_ = initialize(model=model, config=cfg, mesh=mesh,
                                rng=jax.random.PRNGKey(0))
        rep = explain_engine(engine, platform=platform)
        step = next((f for f in rep.functions
                     if f.name == "train_step"), None)
        return roofline_from_cost(step, peaks)
    except Exception as e:                               # noqa: BLE001
        logger.warning("autotune: lowering %s failed (%s: %s) — keeping "
                       "the analytic score", cand.key(),
                       type(e).__name__, e)
        return None


def run_tune(dec_cfg, chips: int, platform: Optional[str] = None,
             seq_len: Optional[int] = None,
             space: Optional[SearchSpace] = None,
             hbm_capacity: Optional[float] = None,
             traffic: Optional[TrafficMix] = None,
             serving_records: Optional[Dict[str, Any]] = None,
             include_serving: bool = True,
             lower: int = 0,
             base_config: Optional[Dict[str, Any]] = None,
             model_desc: str = "model") -> TuneReport:
    """The offline sweep. Deterministic: same inputs → same ranking."""
    seq_len = int(seq_len or dec_cfg.max_seq_len)
    peaks = resolve_peaks(platform=platform)
    cap = hbm_capacity if hbm_capacity is not None else peaks.capacity
    cands = enumerate_candidates(dec_cfg, chips, space)
    keep, pruned = prune_infeasible(dec_cfg, cands, cap, seq_len=seq_len)

    scored: List[ScoredCandidate] = []
    for c in keep:
        rl, penalty = predict_candidate(dec_cfg, c, peaks, seq_len=seq_len)
        scored.append(ScoredCandidate(
            candidate=c, roofline=rl, penalty_s=penalty,
            hbm=candidate_hbm(dec_cfg, c, seq_len=seq_len),
            tokens_per_step=float(c.micro_batch * c.grad_accum *
                                  seq_len * c.data)))
    scored.sort(key=_rank_key)

    if lower > 0:
        for s in scored[:lower]:
            rl = lower_candidate(dec_cfg, s.candidate, peaks, seq_len,
                                 platform=platform,
                                 base_config=base_config)
            if rl is not None and rl.bound != "unknown":
                s.roofline, s.source = rl, "lowered"
        scored.sort(key=_rank_key)

    report = TuneReport(platform=peaks.kind,
                        chips=chips, seq_len=seq_len,
                        model_desc=model_desc, peaks=peaks, ranked=scored,
                        pruned=[(c.key(), r) for c, r in pruned])

    if include_serving:
        records = serving_records or predict_serving_records(
            dec_cfg, peaks)
        report.serving_plan = plan_serving(records, traffic)

    unknown = sum(1 for s in scored if s.bound == "unknown")
    _registry.gauge("tune/candidates_total",
                    help="candidates enumerated by the last sweep").set(
        len(cands))
    _registry.gauge("tune/candidates_pruned",
                    help="candidates rejected by the HBM table").set(
        len(pruned))
    _registry.gauge("tune/candidates_unknown_bound",
                    help="candidates scored with no peak numbers").set(
        unknown)
    best = report.best()
    _registry.gauge("tune/best_predicted_ms",
                    help="winner's roofline-predicted step (0 = no "
                         "model)").set(
        best.roofline.predicted_s * 1e3 if best else 0.0)
    return report


def emit_config(report: TuneReport,
                base: Optional[Dict[str, Any]] = None,
                path: Optional[str] = None) -> Dict[str, Any]:
    """The winner as a ready-to-run config dict (optionally written to
    ``path``): the candidate's real config keys, the serving-plan
    blocks, and the ``tune`` stamp that records where the numbers came
    from (``config.TuneConfig`` — informational; the engine ignores
    it). Round-trips through ``DeepSpeedTPUConfig.from_any``."""
    best = report.best()
    if best is None:
        raise RuntimeError("tune found no feasible candidate to emit")
    cfg = best.candidate.to_config(base)
    plan = report.serving_plan
    if plan and plan.get("model") != "none":
        cfg["serving"] = plan["serving"]
        cfg["router"] = plan["router"]
        cfg["autoscale"] = plan["autoscale"]
    cfg["tune"] = {
        "tuned": True,
        "model": report.model_desc,
        "platform": report.platform,
        "chips": report.chips,
        "seq_len": report.seq_len,
        "mesh": best.candidate.mesh_dict(),
        "predicted_step_ms": best.roofline.predicted_s * 1e3,
        "bound": best.bound,
        "source": best.source,
        "candidates_scored": len(report.ranked),
        "candidates_pruned": len(report.pruned),
        "search_key": best.candidate.key(),
    }
    if plan and plan.get("model") != "none":
        cfg["tune"]["serving_engine"] = dict(plan.get("engine") or {})
    if path:
        with open(path, "w") as fh:
            json.dump(cfg, fh, indent=1)
    return cfg


# ---------------------------------------------------------------------------
# CLI — bin/dstpu-tune
# ---------------------------------------------------------------------------

def _smoke(args) -> int:
    """Tier-1-runnable end-to-end check: tiny model, 8-chip search,
    v5e-modeled peaks — asserts a non-empty ranked table and that the
    emitted JSON round-trips through DeepSpeedTPUConfig (and rebuilds
    its mesh when 8 local devices exist)."""
    import os
    import tempfile
    from deepspeed_tpu.config.config import DeepSpeedTPUConfig
    from deepspeed_tpu.models.llama import llama3_config
    model = llama3_config("tiny", max_seq_len=128)
    space = SearchSpace(zero_stages=(2, 3), micro_batches=(1, 2, 4),
                        remat_policies=("none", "full"),
                        overlap_variants=((False, 1, True),
                                          (True, 1, True)))
    report = run_tune(model, chips=8, platform=args.platform or "v5e",
                      seq_len=128, space=space,
                      traffic=TrafficMix(rps_peak=2.0, prompt_tokens=64,
                                         gen_tokens=32),
                      model_desc="llama3-tiny")
    print(report.render(top=5))
    assert report.ranked, "smoke: empty ranked candidate table"
    assert report.best().bound != "unknown", \
        "smoke: winner has no roofline model (peak tables broken?)"
    path = args.output or os.path.join(tempfile.mkdtemp(), "best.json")
    cfg_dict = emit_config(report, path=path)
    loaded = DeepSpeedTPUConfig.from_any(path)
    assert loaded.tune.tuned, "smoke: tune stamp lost in round-trip"
    assert loaded.zero_optimization.stage == cfg_dict[
        "zero_optimization"]["stage"], "smoke: config round-trip mismatch"
    try:
        import jax
        if len(jax.devices()) >= 8:
            from deepspeed_tpu.parallel.mesh import mesh_from_config
            mesh = mesh_from_config(loaded,
                                    devices=jax.devices()[:8])
            assert sum(1 for _ in mesh.devices.flat) == 8
            print(f"mesh rebuilt from emitted config: "
                  f"{dict(mesh.shape)}")
    except ImportError:
        pass
    print(f"emitted: {path}")
    print("SMOKE OK")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="dstpu-tune",
        description="Roofline-driven offline autotuner: search mesh "
                    "shape / ZeRO stage / overlap / remat / micro-batch "
                    "against the explain.py cost model and emit the "
                    "best config as ready-to-run JSON. Works from any "
                    "host — nothing is allocated unless --lower asks "
                    "for exact re-scoring of local-sized candidates.")
    ap.add_argument("--model", "--size", dest="size", default="tiny",
                    help="llama3 preset (tiny/350m/1b/8b/70b)")
    ap.add_argument("--chips", type=int, default=8,
                    help="target chip count to factorize")
    ap.add_argument("--platform", default=None,
                    help="target chip for the peak tables "
                         "(v2/v3/v4/v5e/v5p/v6e/v7); unknown names warn "
                         "once and score unknown-bound")
    ap.add_argument("--seq", type=int, default=None,
                    help="sequence length (default: model preset)")
    ap.add_argument("--top", type=int, default=10,
                    help="ranked candidates to print")
    ap.add_argument("--lower", type=int, default=0, metavar="N",
                    help="re-score the top N candidates by lowering a "
                         "real engine (needs the candidate's chips <= "
                         "local devices)")
    ap.add_argument("-o", "--output", default=None,
                    help="write the winning config JSON here")
    ap.add_argument("--base-config", default=None,
                    help="JSON config the winner's knobs are merged into")
    ap.add_argument("--json", action="store_true",
                    help="emit the structured report as JSON to stdout")
    ap.add_argument("--no-serving", action="store_true",
                    help="skip the serving-plan sizing")
    ap.add_argument("--rps", type=float, default=4.0,
                    help="serving traffic: peak requests/s")
    ap.add_argument("--prompt-tokens", type=int, default=512)
    ap.add_argument("--gen-tokens", type=int, default=128)
    ap.add_argument("--swing", type=float, default=4.0,
                    help="diurnal peak/trough demand ratio")
    ap.add_argument("--zero-stages", default=None,
                    help="comma list overriding the ZeRO stages swept")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny end-to-end self-check (tier-1 CI)")
    args = ap.parse_args(argv)

    if args.smoke:
        return _smoke(args)

    from deepspeed_tpu.models.llama import llama3_config
    overrides = {"max_seq_len": args.seq} if args.seq else {}
    model = llama3_config(args.size, **overrides)
    space = SearchSpace()
    if args.zero_stages:
        space = SearchSpace(zero_stages=tuple(
            int(s) for s in args.zero_stages.split(",")))
    base = None
    if args.base_config:
        with open(args.base_config) as fh:
            base = json.load(fh)
    traffic = TrafficMix(rps_peak=args.rps,
                         prompt_tokens=args.prompt_tokens,
                         gen_tokens=args.gen_tokens, swing=args.swing)
    report = run_tune(model, chips=args.chips, platform=args.platform,
                      seq_len=args.seq, space=space, traffic=traffic,
                      include_serving=not args.no_serving,
                      lower=args.lower, base_config=base,
                      model_desc=f"llama3-{args.size}")
    if args.json:
        print(json.dumps(report.to_dict(top=args.top), indent=1,
                         default=repr))
    else:
        print(report.render(top=args.top))
    if args.output:
        if report.ranked:
            emit_config(report, base=base, path=args.output)
            print(f"emitted: {args.output}")
        else:
            print("no feasible candidate — nothing emitted",
                  file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
