"""ISSUE 16: roofline-driven offline autotuner (``dstpu-tune``).

Acceptance flows covered here:
- search-space enumeration respects the model's divisibility
  constraints and is deterministic (sorted by candidate key);
- HBM pruning rejects infeasible candidates with a reason, and a
  platform with no capacity number disables pruning instead of
  guessing;
- ranking is deterministic (same inputs → same order) and ranks by
  time-per-token, known-bound before unknown-bound;
- graceful degradation: empty/failed cost analysis scores
  unknown-bound and the sweep continues (explain.roofline_from_cost /
  batch_explain); unknown platforms warn once, never KeyError;
- serving-knob sizing math from synthetic cost records, and the
  zero-prediction self-disable;
- emitted JSON round-trips through DeepSpeedTPUConfig and rebuilds its
  mesh on the 8-virtual-device CPU host;
- ``bin/dstpu-tune --smoke`` end-to-end (subprocess);
- engine_v2.cost_records() cache semantics (lazy, ``refresh=True``
  invalidation) and the serving plan's self-disable on its
  zero-prediction CPU records.
"""

import json
import math
import os
import subprocess
import sys

import pytest
import jax

from deepspeed_tpu.autotuning import (Candidate, SearchSpace,
                                      TrafficMix, candidate_hbm,
                                      emit_config, enumerate_candidates,
                                      mesh_factorizations, plan_serving,
                                      predict_candidate,
                                      predict_serving_records,
                                      prune_infeasible, run_tune)
from deepspeed_tpu.models.llama import llama3_config
from deepspeed_tpu.telemetry import explain
from deepspeed_tpu.telemetry import sampler
from deepspeed_tpu.telemetry.explain import (FunctionCost, Roofline,
                                             batch_explain,
                                             clear_cost_cache,
                                             resolve_peaks,
                                             roofline_from_cost)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": ROOT + os.pathsep + os.environ.get(
               "PYTHONPATH", "")}

SMALL_SPACE = SearchSpace(zero_stages=(2, 3), micro_batches=(1, 2),
                          remat_policies=("none", "full"),
                          overlap_variants=((False, 1, True),
                                            (True, 1, True)))


# -------------------------------------------------------------- enumeration

def test_mesh_factorizations_respect_model_shape():
    model = llama3_config("tiny", max_seq_len=128)
    shapes = mesh_factorizations(8, model)
    assert shapes, "8 chips must admit at least the pure-DP shape"
    assert (8, 1, 1, 1) in shapes
    for d, m, s, e in shapes:
        assert d * m * s * e == 8
        assert model.num_heads % m == 0 and model.kv_heads % m == 0
        assert model.num_heads % s == 0 and model.max_seq_len % s == 0
        assert e == 1, "dense model must never shard an expert axis"
    # deterministic dp-major order
    assert shapes == sorted(shapes, key=lambda t: (-t[0], t[1], t[2], t[3]))


def test_enumerate_candidates_deterministic_and_keyed():
    model = llama3_config("tiny", max_seq_len=128)
    a = enumerate_candidates(model, 8, SMALL_SPACE)
    b = enumerate_candidates(model, 8, SMALL_SPACE)
    assert [c.key() for c in a] == [c.key() for c in b]
    assert len(set(c.key() for c in a)) == len(a), "keys must be unique"
    # stage-2 candidates never carry overlap variants (the knob is
    # stage-3-only), so the overlap axis must not multiply them
    z2 = [c for c in a if c.zero_stage == 2]
    assert all(not c.overlap for c in z2)
    assert "ov-" in z2[0].key()


def test_enumeration_guard_trips():
    model = llama3_config("tiny", max_seq_len=128)
    tiny_cap = SearchSpace(max_candidates=3)
    with pytest.raises(ValueError, match="max_candidates"):
        enumerate_candidates(model, 8, tiny_cap)


def test_candidate_config_encodes_mesh_and_knobs():
    c = Candidate(data=2, model=2, seq=2, zero_stage=3, micro_batch=4,
                  remat="full", overlap=True, overlap_prefetch=2,
                  overlap_regather=False)
    cfg = c.to_config()
    assert cfg["train_micro_batch_size_per_gpu"] == 4
    assert cfg["zero_optimization"]["stage"] == 3
    assert cfg["zero_optimization"]["overlap_comm"] is True
    assert cfg["zero_optimization"]["overlap_prefetch"] == 2
    assert cfg["zero_optimization"]["overlap_regather"] is False
    assert cfg["tensor_parallel"]["tp_size"] == 2
    assert cfg["sequence_parallel"]["size"] == 2
    assert cfg["activation_checkpointing"]["policy"] == "full"
    # stage-2 candidates must not emit stage-3 overlap keys (the config
    # validator coerces overlap_comm off below stage 3 with a warning)
    cfg2 = Candidate(data=8, zero_stage=2, overlap=True).to_config()
    assert "overlap_comm" not in cfg2["zero_optimization"]


# ------------------------------------------------------------------ pruning

def test_prune_rejects_oversized_model_with_reason():
    """llama3-8b on ONE 16 GiB v5e chip: fp32 Adam states alone exceed
    HBM in every configuration — everything prunes, each with a
    human-readable reason."""
    model = llama3_config("8b")
    cands = enumerate_candidates(model, 1, SMALL_SPACE)
    peaks = resolve_peaks(platform="v5e")
    keep, pruned = prune_infeasible(model, cands, peaks.capacity,
                                    seq_len=2048)
    assert not keep
    assert len(pruned) == len(cands)
    for cand, reason in pruned:
        assert "GiB" in reason and ">" in reason


def test_prune_disabled_without_capacity():
    model = llama3_config("8b")
    cands = enumerate_candidates(model, 1, SMALL_SPACE)
    keep, pruned = prune_infeasible(model, cands, 0.0, seq_len=2048)
    assert keep == list(cands) and not pruned


def test_candidate_hbm_shards_over_tp_and_sp():
    model = llama3_config("tiny", max_seq_len=128)
    # hold the data axis fixed — ZeRO already shards over it; the TP/SP
    # division must come on top
    base = candidate_hbm(model, Candidate(data=4), seq_len=128)
    tp = candidate_hbm(model, Candidate(data=4, model=2), seq_len=128)
    assert tp["params"] == pytest.approx(base["params"] / 2)
    sp = candidate_hbm(model, Candidate(data=4, seq=2), seq_len=128)
    assert sp["activations"] == pytest.approx(base["activations"] / 2)
    # keeping forward-gathered chunks for backward (regather=False)
    # costs the whole local stack; regathering holds only the
    # (prefetch+1)-chunk window
    n_local = model.num_params() * 2            # bf16 bytes
    hold = candidate_hbm(model, Candidate(data=8, zero_stage=3,
                                          overlap=True,
                                          overlap_regather=False),
                         seq_len=128)
    assert hold["overlap_transient"] == pytest.approx(n_local)
    win = candidate_hbm(model, Candidate(data=8, zero_stage=3,
                                         overlap=True, overlap_prefetch=0,
                                         overlap_regather=True),
                        seq_len=128)
    assert win["overlap_transient"] == pytest.approx(
        n_local / model.num_layers)
    assert win["overlap_transient"] < hold["overlap_transient"]


# ------------------------------------------------------------------ ranking

def test_ranking_deterministic_and_throughput_ordered():
    model = llama3_config("tiny", max_seq_len=128)
    r1 = run_tune(model, chips=8, platform="v5e", seq_len=128,
                  space=SMALL_SPACE, include_serving=False)
    r2 = run_tune(model, chips=8, platform="v5e", seq_len=128,
                  space=SMALL_SPACE, include_serving=False)
    keys1 = [s.candidate.key() for s in r1.ranked]
    assert keys1 == [s.candidate.key() for s in r2.ranked]
    assert r1.ranked and r1.best().bound != "unknown"
    per_tok = [s.s_per_token for s in r1.ranked
               if s.bound != "unknown"]
    assert per_tok == sorted(per_tok)


def test_unknown_platform_sweep_completes_and_ranks():
    """No peak numbers at all: every candidate scores unknown-bound, the
    sweep still returns a deterministic ranking (work-proxy order), and
    the serving plan self-disables instead of emitting garbage."""
    model = llama3_config("tiny", max_seq_len=128)
    r = run_tune(model, chips=8, platform="made_up_chip_9000",
                 seq_len=128, space=SMALL_SPACE)
    assert r.ranked
    assert all(s.bound == "unknown" for s in r.ranked)
    assert all(s.roofline.predicted_s == 0.0 for s in r.ranked)
    assert r.serving_plan["model"] == "none"
    r2 = run_tune(model, chips=8, platform="made_up_chip_9000",
                  seq_len=128, space=SMALL_SPACE)
    assert [s.candidate.key() for s in r.ranked] == \
        [s.candidate.key() for s in r2.ranked]


def test_run_tune_publishes_gauges():
    from deepspeed_tpu.telemetry.registry import registry
    model = llama3_config("tiny", max_seq_len=128)
    r = run_tune(model, chips=8, platform="v5e", seq_len=128,
                 space=SMALL_SPACE, include_serving=False)
    assert registry.gauge("tune/candidates_total").value == \
        len(r.ranked) + len(r.pruned)
    assert registry.gauge("tune/best_predicted_ms").value == \
        pytest.approx(r.best().roofline.predicted_s * 1e3)


def test_overlap_candidate_beats_monolithic_on_comm():
    """The serial-exposure penalty: at stage 3 the non-overlapped gather
    must never score better than its overlapped twin."""
    model = llama3_config("tiny", max_seq_len=128)
    peaks = resolve_peaks(platform="v5e")
    mono = Candidate(data=8, zero_stage=3, overlap=False)
    chunked = Candidate(data=8, zero_stage=3, overlap=True,
                        overlap_regather=True)
    rl_m, pen_m = predict_candidate(model, mono, peaks, seq_len=128)
    rl_c, pen_c = predict_candidate(model, chunked, peaks, seq_len=128)
    assert pen_m > 0.0 and pen_c == 0.0
    assert rl_m.predicted_s + pen_m > rl_c.predicted_s + pen_c


def test_lowered_rescoring_degrades_gracefully():
    """--lower on a CPU host: whatever the local backend's cost_analysis
    returns (real numbers, empty, or a failed lowering), the sweep
    completes and every candidate keeps a score."""
    model = llama3_config("tiny", max_seq_len=128)
    r = run_tune(model, chips=8, platform="v5e", seq_len=128,
                 space=SMALL_SPACE, include_serving=False, lower=1)
    assert r.ranked
    assert all(s.source in ("analytic", "lowered") for s in r.ranked)


# ------------------------------------- graceful degradation (explain layer)

def test_roofline_from_cost_empty_and_error_records():
    peaks = resolve_peaks(platform="v5e")
    for fc in (None,
               FunctionCost(name="empty", available=False),
               FunctionCost(name="boom", available=True,
                            error="lowering failed")):
        rl = roofline_from_cost(fc, peaks)
        assert rl.bound == "unknown"
        assert rl.predicted_s == 0.0
    good = FunctionCost(name="ok", available=True, flops=1e15,
                        bytes_accessed=1e9)
    assert roofline_from_cost(good, peaks).bound == "compute"


def test_batch_explain_survives_one_bad_candidate():
    clear_cost_cache()
    peaks = resolve_peaks(platform="v5e")

    def good(x):
        return x * 2.0

    def bad(x):
        raise ValueError("mid-search lowering failure")

    arg = jax.ShapeDtypeStruct((8, 8), "float32")
    out = batch_explain([("k-good", "good", good, (arg,)),
                         ("k-bad", "bad", bad, (arg,)),
                         ("k-good2", "good2", good, (arg,))], peaks)
    assert len(out) == 3
    by_key = {k: (fc, rl) for k, fc, rl in out}
    assert by_key["k-bad"][0].error is not None
    assert by_key["k-bad"][1].bound == "unknown"
    assert by_key["k-good"][0].error is None
    # error records are cached too — the same key must not re-lower
    fc_again = explain.analyze_lowerable_cached("k-bad", "bad", bad, arg)
    assert fc_again is by_key["k-bad"][0]
    clear_cost_cache()


# ------------------------------------------------- sampler peak-table sweep

def test_unknown_platform_warns_once_not_keyerror():
    sampler._warned_platforms.discard("tpu_x99")
    assert sampler.warn_unknown_platform("tpu_x99") is True
    assert "tpu_x99" in sampler._warned_platforms
    n = len(sampler._warned_platforms)
    assert sampler.warn_unknown_platform("tpu_x99") is True
    assert len(sampler._warned_platforms) == n, "second call must not " \
        "re-record (one warning per platform)"
    assert sampler.warn_unknown_platform("v5e") is False
    # CPU hosts have no peaks (unknown) but never warn — every local
    # test run would spam otherwise
    assert sampler.warn_unknown_platform("cpu") is True
    assert "cpu" not in sampler._warned_platforms
    sampler._warned_platforms.discard("tpu_x99")


def test_peak_tables_cover_every_known_platform():
    for name in sampler.known_platforms():
        assert sampler.PEAK_HBM_BW.get(name, 0) > 0, name
        assert sampler.HBM_CAPACITY.get(name, 0) > 0, name
        assert name in explain.PEAK_ICI_BW, name
    peaks = resolve_peaks(platform="v7")
    assert peaks.peak_flops > 0 and peaks.capacity > 0
    bogus = resolve_peaks(platform="definitely_not_a_chip")
    assert bogus.peak_flops == 0.0          # zero peaks, not KeyError


# ------------------------------------------------------ serving-plan sizing

def _records(t_pre, t_dec, n_bucket=8, chunk=32):
    return {"prefill": {"predicted_s": t_pre, "chunk": chunk,
                        "n_bucket": n_bucket, "bound": "memory"},
            "decode": {"predicted_s": t_dec, "n_bucket": n_bucket,
                       "bound": "memory"},
            "platform": "v5e"}


def test_plan_serving_sizing_math():
    mix = TrafficMix(rps_peak=4.0, prompt_tokens=512, gen_tokens=128,
                     swing=4.0, utilization=0.6,
                     headroom=1.25)
    plan = plan_serving(_records(t_pre=0.080, t_dec=0.008), mix)
    assert plan["model"] == "roofline"
    a = plan["autoscale"]
    # decode: cap 0.6·8/0.008 = 600 tok/s vs demand 4·128 = 512
    assert a["decode_min"] == 1 and a["decode_max"] == 2
    # prefill: cap 0.6·256/0.080 = 1920 vs demand 4·512 = 2048
    assert a["prefill_min"] == 1 and a["prefill_max"] == 3
    assert a["prefill_min"] <= a["prefill_max"]
    assert a["decode_min"] <= a["decode_max"]
    assert a["queue_high"] == pytest.approx(4.8)
    assert plan["router"]["replicas"] == 3          # pre_peak + dec_peak
    assert plan["serving"] == {}                    # no key left to size
    # SplitFuse: 2 decode steps of prefill tokens = 2·0.008/(0.080/256)
    assert plan["engine"]["max_batch_tokens"] == 51
    ttft_best = math.ceil(512 / 32) * 0.080 + 0.008
    assert plan["router"]["hedge_delay_s"] == pytest.approx(
        round(2 * ttft_best, 3))
    assert plan["predictions"]["prefill_step_ms"] == pytest.approx(80.0)


def test_plan_serving_self_disables_on_zero_predictions():
    plan = plan_serving(_records(t_pre=0.0, t_dec=0.0))
    assert plan["model"] == "none"
    assert plan["notes"]
    assert plan["autoscale"]["enabled"] is False    # config-class default


def test_plan_blocks_validate_through_config_classes():
    from deepspeed_tpu.config.config import (AutoscaleConfig, RouterConfig,
                                             ServingConfig)
    plan = plan_serving(_records(t_pre=0.040, t_dec=0.004),
                        TrafficMix(rps_peak=16.0))
    ServingConfig(**plan["serving"])
    RouterConfig(**plan["router"])
    AutoscaleConfig(**plan["autoscale"])


def test_predict_serving_records_shape():
    model = llama3_config("tiny", max_seq_len=128)
    recs = predict_serving_records(model, resolve_peaks(platform="v5e"))
    for lbl in ("prefill", "decode"):
        assert recs[lbl]["predicted_s"] > 0
        assert recs[lbl]["bound"] in ("compute", "memory", "comm")
    assert recs["platform"] == "v5e"


# ------------------------------------------------------- emitted JSON / CLI

def test_emit_config_round_trips(tmp_path):
    from deepspeed_tpu.config.config import DeepSpeedTPUConfig
    model = llama3_config("tiny", max_seq_len=128)
    report = run_tune(model, chips=8, platform="v5e", seq_len=128,
                      space=SMALL_SPACE, traffic=TrafficMix(),
                      model_desc="llama3-tiny")
    path = str(tmp_path / "best.json")
    cfg = emit_config(report, path=path)
    loaded = DeepSpeedTPUConfig.from_any(path)
    assert loaded.tune.tuned is True
    assert loaded.tune.model == "llama3-tiny"
    assert loaded.tune.platform == "v5e"
    assert loaded.tune.search_key == report.best().candidate.key()
    assert loaded.zero_optimization.stage == \
        cfg["zero_optimization"]["stage"]
    assert loaded.train_micro_batch_size_per_gpu == \
        report.best().candidate.micro_batch
    # the serving plan rode along and validated
    if report.serving_plan and report.serving_plan["model"] != "none":
        assert loaded.autoscale.prefill_min >= 1
        assert loaded.tune.serving_engine.get("max_batch_tokens", 0) > 0
    if len(jax.devices()) >= 8:
        from deepspeed_tpu.parallel.mesh import mesh_from_config
        mesh = mesh_from_config(loaded, devices=jax.devices()[:8])
        assert dict(mesh.shape) == report.best().candidate.mesh_dict()


def test_emit_config_without_candidates_raises():
    from deepspeed_tpu.autotuning.tune import TuneReport
    empty = TuneReport(platform="v5e", chips=8, seq_len=128,
                       model_desc="x",
                       peaks=resolve_peaks(platform="v5e"))
    with pytest.raises(RuntimeError, match="no feasible candidate"):
        emit_config(empty)


def test_dstpu_tune_cli_smoke(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bin", "dstpu-tune"),
         "--smoke", "-o", str(tmp_path / "best.json")],
        env=CPU_ENV, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SMOKE OK" in out.stdout
    assert "ranked" in out.stdout
    cfg = json.loads((tmp_path / "best.json").read_text())
    assert cfg["tune"]["tuned"] is True


@pytest.mark.slow
def test_bench_from_config_stamps_tune(tmp_path):
    """bench.py --from-config: replays the emitted winner and stamps
    predicted-vs-measured into extra.tune."""
    model = llama3_config("tiny", max_seq_len=128)
    report = run_tune(model, chips=8, platform="v5e", seq_len=128,
                      space=SMALL_SPACE, model_desc="llama3-tiny")
    path = str(tmp_path / "best.json")
    emit_config(report, path=path)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"),
         "--from-config", path],
        env={**CPU_ENV,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    stamp = line["extra"]["tune"]
    assert stamp["search_key"] == report.best().candidate.key()
    assert stamp["predicted_ms"] == pytest.approx(
        report.best().roofline.predicted_s * 1e3)
    assert stamp["measured_ms"] > 0


# --------------------------------------------- engine_v2 cost-record cache

@pytest.fixture(scope="module")
def v2_engine():
    import deepspeed_tpu as ds
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    ds.build_mesh(data=1, devices=jax.devices()[:1])
    model = llama3_config("tiny", max_seq_len=128)
    return RaggedInferenceEngineTPU(
        model, {"dtype": "float32", "num_blocks": 32, "block_size": 8,
                "max_seq_len": 128, "prefill_chunk": 16,
                "max_batch_tokens": 128, "max_sequences": 4,
                "use_pallas": False},
        rng=jax.random.PRNGKey(0))


def test_cost_records_cached_until_refresh(v2_engine):
    r1 = v2_engine.cost_records()
    assert r1 is v2_engine.cost_records(), \
        "second call must return the cached object (no recompile)"
    r2 = v2_engine.cost_records(refresh=True)
    assert r2 is not r1, "refresh=True must invalidate the cache"
    assert r2 is v2_engine.cost_records()
    for lbl in ("prefill", "decode"):
        assert lbl in r2


def test_cost_records_zero_predictions_self_disable_plan(v2_engine):
    """CPU records predict 0.0 (no peak numbers) — feeding them to the
    serving planner must self-disable the sizing, exactly like the
    frontend's SLO admission on the same records."""
    recs = v2_engine.cost_records()
    for lbl in ("prefill", "decode"):
        assert not recs[lbl].get("predicted_s"), \
            "CPU platform must predict 0 (no peaks), not a fake number"
    plan = plan_serving(recs, TrafficMix())
    assert plan["model"] == "none"
    assert plan["notes"]
