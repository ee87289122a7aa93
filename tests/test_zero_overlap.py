"""Chunked, overlap-scheduled ZeRO-3 collectives (runtime/zero/overlap.py).

Unit layer: spec surgery, bucketing, scheduler-flag
helpers, chunk-aware HLO attribution and comms-logger coalescing. Engine
layer (dp=8 CPU mesh): numerical parity of the chunked path against the
monolithic stage-3 step across bucket sizes {1 layer, 4 layers, whole
model} plus the reuse (no-regather) mode, and the transient-HBM line the
static budget must carry."""

import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.zero import overlap as ov
from deepspeed_tpu.runtime.zero.overlap import (
    OverlapPlan, build_overlap_plan, chunk_bounds, dense_spec,
    ensure_scheduler_flags, scheduler_flag_status)


# ------------------------------------------------------------- spec surgery

def test_dense_spec_strips_zero_axes():
    assert dense_spec(P(None, ("data", "model"))) == P(None, "model")
    assert dense_spec(P(("data", "data_inner"), None)) == P(None, None)
    assert dense_spec(P(None, "model")) == P(None, "model")
    # 'expert' is a ZeRO axis on dense weights
    assert dense_spec(P("expert", "model")) == P(None, "model")


def test_chunk_bounds():
    # default: one chunk per layer
    assert chunk_bounds(4, 100, 0) == [(0, 1), (1, 2), (2, 3), (3, 4)]
    # bucket holds 2 layers
    assert chunk_bounds(5, 100, 250) == [(0, 2), (2, 4), (4, 5)]
    # bucket smaller than one layer: still one layer per chunk
    assert chunk_bounds(3, 100, 10) == [(0, 1), (1, 2), (2, 3)]
    # bucket covers the whole model: degenerate single chunk
    assert chunk_bounds(3, 100, 10**9) == [(0, 3)]
    assert chunk_bounds(0, 100, 0) == []


# --------------------------------------------------------------------- flags

def test_scheduler_flag_helpers():
    env = {"XLA_FLAGS": "--xla_foo=1"}
    status = scheduler_flag_status(env)
    assert set(status) == set(ov.LATENCY_HIDING_FLAGS)
    assert not any(status.values())
    # probe rejects one flag → it is dropped, the rest appended
    reject = ov.LATENCY_HIDING_FLAGS[1]
    flags = ensure_scheduler_flags(
        probe=lambda cand: reject not in cand, env=env)
    assert env["XLA_FLAGS"] == flags
    status = scheduler_flag_status(env)
    assert not status[reject]
    assert all(okay for f, okay in status.items() if f != reject)
    assert "--xla_foo=1" in flags
    # idempotent: a second call under the same probe appends nothing
    assert ensure_scheduler_flags(
        probe=lambda cand: reject not in cand, env=env) == flags


# ------------------------------------------------- chunk-aware attribution

def test_collective_stats_counts_chunks():
    """Per-op {bytes, count} from HLO: async ``-start`` tuples count the
    LARGEST element once (operand alias must not double-count), ``-done``
    is skipped, and the count exposes the chunk fan-out the overlap path
    introduces (one monolithic gather → n per-chunk gathers)."""
    from deepspeed_tpu.telemetry.explain import collective_stats_from_hlo
    hlo = "\n".join([
        "ENTRY main {",
        "  p0 = f32[8,64]{1,0} parameter(0)",
        "  ag0 = bf16[16,64]{1,0} all-gather(p0), dimensions={0}",
        "  ag1 = bf16[16,64]{1,0} all-gather(p0), dimensions={0}",
        "  rs = (f32[8]{0}, f32[2]{0}) reduce-scatter-start(p0)",
        "  rsd = f32[2]{0} reduce-scatter-done(rs)",
        "}",
    ])
    stats = collective_stats_from_hlo(hlo)
    assert stats["all-gather"]["count"] == 2
    assert stats["all-gather"]["bytes"] == pytest.approx(2 * 16 * 64 * 2)
    assert stats["reduce-scatter"]["count"] == 1
    assert stats["reduce-scatter"]["bytes"] == pytest.approx(8 * 4)
    assert collective_stats_from_hlo("") == {}


def test_collective_stats_tpu_reduce_scatter_fusion():
    """The TPU compiler writes a reduce-scatter as a custom fusion around
    an ``all-reduce-scatter`` computation (seen compiling the ZeRO-3 step
    for a described v5e:2x2); it counts as a reduce-scatter of the
    fusion's result, and an ordinary fusion counts as nothing."""
    from deepspeed_tpu.telemetry.explain import collective_stats_from_hlo
    hlo = "\n".join([
        "  %fusion.409 = bf16[128256,512]{1,0:T(8,128)(2,1)} "
        "fusion(%convolution_bitcast_fusion.10), kind=kCustom, "
        "calls=%all-reduce-scatter.clone.clone",
        "  %fusion.410 = bf16[128,512]{1,0} fusion(%p), kind=kLoop, "
        "calls=%fused_computation.3",
    ])
    stats = collective_stats_from_hlo(hlo)
    assert stats == {"reduce-scatter": {
        "bytes": pytest.approx(128256 * 512 * 2), "count": 1}}


def test_append_chunked_exact_accounting():
    """Coalesced per-chunk records keep the byte/call accounting EXACT
    (flight-recorder deltas are computed from these counters) while the
    tracer sees ONE instant at default verbosity — per-chunk instants
    come back under ``verbose``."""
    from deepspeed_tpu.comm.comms_logger import CommsLogger
    from deepspeed_tpu.telemetry import registry, tracer

    cl = CommsLogger()
    cl.enabled = True
    before_bytes = registry.counter("comm/bytes").value
    before_calls = registry.counter("comm/all_gather/calls").value
    tracer.configure(enabled=True)
    try:
        n0 = len(tracer.events())
        cl.append_chunked("all_gather", 1000, axis=("data",), chunks=8)
        assert cl.comms_dict["all_gather"][1000][0] == 8
        assert registry.counter("comm/bytes").value - before_bytes == 8000
        assert registry.counter(
            "comm/all_gather/calls").value - before_calls == 8
        evs = [e for e in tracer.events()[n0:]
               if e.get("name") == "comm/all_gather"]
        assert len(evs) == 1
        assert evs[0]["args"]["chunks"] == 8
        assert evs[0]["args"]["bytes"] == 8000
        assert evs[0]["args"]["chunk_bytes"] == 1000

        cl.verbose = True
        n1 = len(tracer.events())
        cl.append_chunked("all_gather", 1000, axis=("data",), chunks=3)
        evs = [e for e in tracer.events()[n1:]
               if e.get("name") == "comm/all_gather"]
        assert len(evs) == 3
        assert cl.comms_dict["all_gather"][1000][0] == 11

        # chunks=1 degenerates to the plain append path
        cl.verbose = False
        cl.append_chunked("reduce_scatter", 500, chunks=1)
        assert cl.comms_dict["reduce_scatter"][500][0] == 1
    finally:
        tracer.configure(enabled=False)


# ------------------------------------------------------- plan construction

def _toy_plan(**kw):
    from deepspeed_tpu.parallel.mesh import build_mesh
    mesh = build_mesh(data=8)
    specs = {"w": P(None, "data", "model")}
    abstract = {"w": jax.ShapeDtypeStruct((8, 64, 4), np.float32)}
    return OverlapPlan(mesh, specs, abstract, **kw)


def test_plan_accounting(devices):
    plan = _toy_plan(prefetch=1)
    assert plan.num_layers == 8 and plan.n_chunks == 8
    assert plan.per_layer_bytes == 64 * 4 * 4
    # gathered spec keeps 'model' (size 1 here) — full layer per device
    assert plan.per_layer_gathered_device_bytes == pytest.approx(64 * 4 * 4)
    # regather (default): prefetch+1 window
    assert plan.transient_bytes() == pytest.approx(2 * 64 * 4 * 4)
    # reuse: the whole gathered stack is live at the fwd→bwd turnaround
    reuse = _toy_plan(prefetch=1, regather=False)
    assert reuse.transient_bytes() == pytest.approx(8 * 64 * 4 * 4)
    assert "re-gather" in plan.describe() and "reuse" in reuse.describe()
    # prefetch deeper than the chunk count clamps to the chunk count
    deep = _toy_plan(prefetch=99)
    assert deep.transient_bytes() == pytest.approx(8 * 64 * 4 * 4)


def test_build_plan_fences(devices):
    from deepspeed_tpu.parallel.mesh import build_mesh

    class Z:
        overlap_prefetch = 1
        overlap_bucket_bytes = 0
        overlap_regather = True

    specs = {"w": P(None, "data", "model")}
    abstract = {"w": jax.ShapeDtypeStruct((8, 64, 4), np.float32)}
    mesh = build_mesh(data=2, expert=4)
    assert build_overlap_plan(mesh, specs, abstract, Z(),
                              num_experts=4) is None  # EP fence
    plan = build_overlap_plan(mesh, specs, abstract, Z(), num_experts=0)
    assert plan is not None and plan.n_chunks == 8


# ------------------------------------------------------- engine parity

def _engine(zero_extra, devices):
    from deepspeed_tpu.models.gpt import gpt2_config
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import initialize
    build_mesh(data=8)
    model = gpt2_config("tiny", num_layers=8, max_seq_len=32,
                        vocab_size=128)
    eng, *_ = initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 5e-3}},
                "zero_optimization": {"stage": 3, **zero_extra}},
        rng=jax.random.PRNGKey(7))
    return eng


def _trajectory(eng, steps=3):
    rng = np.random.default_rng(0)
    losses, gnorms = [], []
    for _ in range(steps):
        batch = {"input_ids": rng.integers(0, 128, size=(8, 32),
                                           dtype=np.int32)}
        losses.append(float(eng.train_batch(iter([batch]))))
        gnorms.append(eng.get_global_grad_norm())
    return losses, gnorms


def test_overlap_parity_across_bucket_sizes(devices):
    """Loss AND grad-norm trajectories of the chunked path match the
    monolithic stage-3 step across the bucket-size matrix (per-layer /
    4-layer buckets with reuse mode / whole-model degenerate), dp=8."""
    base = _engine({}, devices)
    assert getattr(base, "_overlap_plan", None) is None
    base_l, base_g = _trajectory(base)

    # per-layer chunks (the default bucket)
    e1 = _engine({"overlap_comm": True}, devices)
    plan = e1._overlap_plan
    assert plan is not None and plan.n_chunks == 8
    l1, g1 = _trajectory(e1)
    np.testing.assert_allclose(l1, base_l, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(g1, base_g, rtol=2e-3, atol=2e-3)

    # 4-layer buckets + reuse (no-regather) mode in one config
    e4 = _engine({"overlap_comm": True, "overlap_regather": False,
                  "overlap_bucket_bytes": 4 * plan.per_layer_bytes},
                 devices)
    assert e4._overlap_plan.n_chunks == 2
    assert not e4._overlap_plan.regather
    l4, g4 = _trajectory(e4)
    np.testing.assert_allclose(l4, base_l, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(g4, base_g, rtol=2e-3, atol=2e-3)

    # whole-model bucket: degenerates to the monolithic gather
    ew = _engine({"overlap_comm": True, "overlap_bucket_bytes": 1 << 40},
                 devices)
    assert ew._overlap_plan.n_chunks == 1
    lw, gw = _trajectory(ew)
    np.testing.assert_allclose(lw, base_l, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(gw, base_g, rtol=2e-3, atol=2e-3)


def test_overlap_smoke_budget_and_gauges(devices):
    """Tier-1/smoke slice: one chunked dp=8 step runs, the static HBM
    budget carries the transient gathered-chunk line, and the static
    ``overlap/*`` gauges are published."""
    from deepspeed_tpu.telemetry import registry
    from deepspeed_tpu.telemetry.explain import static_budget
    eng = _engine({"overlap_comm": True, "overlap_prefetch": 2}, devices)
    plan = eng._overlap_plan
    assert plan is not None and plan.prefetch == 2
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, size=(8, 32),
                                       dtype=np.int32)}
    loss = float(eng.train_batch(iter([batch])))
    assert np.isfinite(loss)
    budget = static_budget(eng)
    assert budget["overlap_gathered_chunks"] == pytest.approx(
        plan.transient_bytes())
    assert budget["overlap_gathered_chunks"] > 0
    # 3 chunks in flight (prefetch 2 + 1 in use) of 8
    assert plan.transient_bytes() == pytest.approx(
        3 * plan.per_layer_gathered_device_bytes)
    assert registry.gauge("overlap/chunks").value == plan.n_chunks
    assert registry.gauge("overlap/prefetch_depth").value == 2
    assert registry.gauge("overlap/transient_hbm_bytes").value == \
        pytest.approx(plan.transient_bytes())
