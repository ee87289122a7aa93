"""Request-scoped distributed tracing (telemetry/reqtrace.py).

Unit tests pin the context algebra (mint/child/tags), the tail-based
sampler (drop-fast vs retain-on-flag/slow/reason, deterministic head
sampling, late-span and overflow accounting) and the critical-path
attribution. Stub-driven router tests prove trace-context SURVIVAL
through every leg the fleet can throw at a stream — hedge races (both
legs tagged, winner/loser), mid-stream failover replays (one trace_id,
replay leg tagged), breaker rejections, the disaggregated
prefill→handoff→decode promotion with a torn-bundle fallback, and
kvtier prefetch/adopt/fallback — asserting exactly one trace per
request with correct parent/child edges. The engine-backed acceptance
test runs a 2-replica disaggregated fleet under `replica_slow` chaos:
slow requests are tail-retained and reassembled by `dstpu-trace
--request` into one merged trace with an unbroken span chain through
the handoff, `/metrics` exposes trace_id exemplars (OpenMetrics), the
doctor names the dominant critical-path segment, and fast requests are
dropped with `trace/dropped_ok` accounting.
"""

import urllib.request

import pytest
import jax

from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.resilience.faults import fault_injector
from deepspeed_tpu.serving.queue import AdmissionError
from deepspeed_tpu.serving.request import Request, RequestState
from deepspeed_tpu.serving.router import LocalReplica, Router
from deepspeed_tpu.telemetry.reqtrace import (TraceContext, critical_path,
                                              reqtrace)


@pytest.fixture(autouse=True)
def _disarm():
    fault_injector.disarm()
    fault_injector.last_step = None
    yield
    fault_injector.disarm()
    fault_injector.last_step = None


@pytest.fixture
def rt():
    """Armed request tracer, reset around each test (the module global
    is process-wide, like the registry)."""
    reqtrace.clear()
    reqtrace.configure(enabled=True, head_sample=0.0,
                       retain_slow_ms=500.0, buffer_traces=256)
    yield reqtrace
    reqtrace.clear()
    reqtrace.configure(enabled=False, head_sample=0.0,
                       retain_slow_ms=500.0, buffer_traces=256)


def _counter(name: str) -> float:
    from deepspeed_tpu import telemetry
    m = telemetry.registry.get(name)
    return float(m.value) if m is not None else 0.0


def _ring():
    from deepspeed_tpu import telemetry
    return list(telemetry.tracer._buf)


class _Clock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# context algebra
# ---------------------------------------------------------------------------

def test_context_mint_child_and_tags():
    root = TraceContext.mint(entry="router", uid=7)
    assert root.root and root.parent_span_id is None
    leg = root.child(replica="r1", role="decode")
    assert not leg.root
    assert leg.trace_id == root.trace_id
    assert leg.span_id != root.span_id
    assert leg.parent_span_id == root.span_id
    # baggage inherits and extends; the parent's is not mutated
    assert leg.baggage == {"entry": "router", "uid": 7,
                           "replica": "r1", "role": "decode"}
    assert root.baggage == {"entry": "router", "uid": 7}
    t = leg.tags()
    assert t["trace_id"] == root.trace_id
    assert t["span_id"] == leg.span_id
    assert t["parent_span_id"] == root.span_id
    assert t["replica"] == "r1"


def test_disabled_mint_returns_none_and_sinks_tolerate_it():
    reqtrace.configure(enabled=False)
    assert reqtrace.mint(entry="router") is None
    # every sink is a no-op on ctx=None — the plain-frontend path
    reqtrace.complete("serving/request", None, 0.0, 1.0)
    reqtrace.instant("router/hedge", None)
    reqtrace.flag(None, "failover")
    assert reqtrace.finish(None) is False


# ---------------------------------------------------------------------------
# tail-based sampling
# ---------------------------------------------------------------------------

def test_fast_healthy_trace_dropped_whole(rt):
    d0 = _counter("trace/dropped_ok")
    n0 = len(_ring())
    ctx = rt.mint(entry="router", uid=1)
    rt.complete("serving/request", ctx, 0.0, 0.01, envelope=True)
    assert rt.finish(ctx, reason="length", ttft_s=0.005,
                     tpot_s=0.002) is False
    assert _counter("trace/dropped_ok") - d0 == 1
    assert len(_ring()) == n0                 # nothing entered the ring
    assert rt.retained() == []
    assert ctx.trace_id not in rt._pending


@pytest.mark.parametrize("cause", ["failover", "hedge", "reprefill",
                                   "kvtier_fallback"])
def test_flagged_trace_retained(rt, cause):
    r0 = _counter("trace/retained")
    n0 = len(_ring())
    ctx = rt.mint(entry="router", uid=2)
    rt.complete("serving/request", ctx, 0.0, 0.01, envelope=True)
    rt.flag(ctx, cause)
    assert rt.finish(ctx, reason="length", ttft_s=0.001) is True
    assert _counter("trace/retained") - r0 == 1
    assert len(_ring()) == n0 + 1             # flushed into the ring
    summary = rt.retained()[-1]
    assert cause in summary["causes"]
    assert summary["trace_id"] == ctx.trace_id


def test_error_reason_and_slow_ttft_retain(rt):
    ctx = rt.mint(uid=3)
    rt.complete("serving/request", ctx, 0.0, 0.01, envelope=True)
    assert rt.finish(ctx, reason="error") is True
    assert "reason:error" in rt.retained()[-1]["causes"]
    # slow TTFT past retain_slow_ms retains without any flag
    ctx2 = rt.mint(uid=4)
    rt.complete("serving/request", ctx2, 0.0, 0.9, envelope=True)
    assert rt.finish(ctx2, reason="length", ttft_s=0.9) is True
    assert "slow_ttft" in rt.retained()[-1]["causes"]
    # just under the threshold drops
    ctx3 = rt.mint(uid=5)
    rt.complete("serving/request", ctx3, 0.0, 0.1, envelope=True)
    assert rt.finish(ctx3, reason="length", ttft_s=0.1) is False


def test_head_sample_deterministic_from_trace_id(rt):
    rt.configure(head_sample=0.5)
    # int("00000000", 16) % 1e6 = 0 → always inside a 0.5 sample
    keep = TraceContext(trace_id="00000000aaaaaaaa", span_id="s1")
    rt.complete("serving/request", keep, 0.0, 0.01, envelope=True)
    assert rt.finish(keep, reason="length") is True
    assert rt.retained()[-1]["causes"] == ["head_sample"]
    # int("ffffffff", 16) % 1e6 = 967295 → outside a 0.5 sample
    drop = TraceContext(trace_id="ffffffffbbbbbbbb", span_id="s2")
    rt.complete("serving/request", drop, 0.0, 0.01, envelope=True)
    assert rt.finish(drop, reason="length") is False


def test_late_spans_dropped_after_tail_decision(rt):
    ctx = rt.mint(uid=6)
    rt.complete("serving/request", ctx, 0.0, 0.01, envelope=True)
    rt.finish(ctx, reason="length")
    l0 = _counter("trace/late_spans")
    # a cancelled hedge loser draining after the decision: dropped, not
    # resurrected as a leaked pending entry
    rt.complete("serving/request/decode", ctx, 0.0, 0.01)
    rt.flag(ctx, "hedge")
    assert _counter("trace/late_spans") - l0 == 1
    assert ctx.trace_id not in rt._pending


def test_buffer_eviction_and_span_overflow_counters(rt):
    rt.configure(buffer_traces=2)
    e0 = _counter("trace/buffer_evicted")
    c1, c2, c3 = (rt.mint(uid=i) for i in range(3))
    assert _counter("trace/buffer_evicted") - e0 == 1
    assert c1.trace_id not in rt._pending     # oldest evicted
    assert c2.trace_id in rt._pending and c3.trace_id in rt._pending
    rt.configure(buffer_traces=256)
    o0 = _counter("trace/span_overflow")
    from deepspeed_tpu.telemetry.reqtrace import MAX_EVENTS_PER_TRACE
    for _ in range(MAX_EVENTS_PER_TRACE + 5):
        rt.instant("router/hedge", c3)
    assert _counter("trace/span_overflow") - o0 == 5
    assert len(rt._pending[c3.trace_id]["events"]) == MAX_EVENTS_PER_TRACE


# ---------------------------------------------------------------------------
# critical-path attribution
# ---------------------------------------------------------------------------

def _span(name, ts_ms, dur_ms, **args):
    return {"name": name, "ph": "X", "ts": ts_ms * 1e3,
            "dur": dur_ms * 1e3, "args": args}


def test_critical_path_segments_replay_and_loser_exclusion():
    events = [
        _span("router/request", 0, 100),             # envelope: no segment
        _span("serving/request/queued", 0, 10),
        _span("serving/request/prefill", 10, 20),
        _span("serving/request/prefill", 10, 15, winner=0),   # hedge loser
        _span("router/handoff", 30, 5),
        _span("serving/request/decode", 35, 40),
        _span("serving/request/decode", 40, 20, replay=1),    # failover leg
        {"name": "router/hedge", "ph": "i", "ts": 1.0},       # instants skip
    ]
    bd = critical_path(events)
    assert bd["queued"] == pytest.approx(10.0)
    assert bd["prefill"] == pytest.approx(20.0)      # loser leg excluded
    assert bd["handoff"] == pytest.approx(5.0)
    assert bd["decode"] == pytest.approx(40.0)
    assert bd["replayed"] == pytest.approx(20.0)
    assert bd["_total_ms"] == pytest.approx(100.0)
    assert bd["stalled"] == pytest.approx(5.0)
    assert critical_path([]) == {"_total_ms": 0.0}


# ---------------------------------------------------------------------------
# latency exemplars: registry → /metrics (OpenMetrics) → fleet parser
# ---------------------------------------------------------------------------

def test_exemplar_prometheus_roundtrip_and_openmetrics_ctype():
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry.endpoint import MetricsServer
    from deepspeed_tpu.telemetry.fleet import (latency_exemplars,
                                               parse_prometheus_text,
                                               worst_exemplar)
    h = telemetry.registry.histogram(
        "serving/ttft_seconds", help="time to first token")
    h.record(0.012, exemplar="cafe0123deadbeef")
    h.record(0.8, exemplar="feed4567deadbeef")
    assert h.worst_exemplar() == ("feed4567deadbeef", 0.8)
    body = telemetry.metrics_text()
    assert '# {trace_id="feed4567deadbeef"}' in body
    srv = MetricsServer(0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers.get("Content-Type", "").startswith(
                "application/openmetrics-text")
            scraped = resp.read().decode()
    finally:
        srv.close()
    # the fleet parser reads the exemplars AND still parses the numbers
    metrics = parse_prometheus_text(scraped)
    hist = metrics["serving_ttft_seconds"]
    assert hist["count"] >= 2
    worst = worst_exemplar(hist)
    assert worst is not None
    assert worst["trace_id"] == "feed4567deadbeef"
    ex = latency_exemplars(metrics)
    assert ex["ttft"]["trace_id"] == "feed4567deadbeef"


# ---------------------------------------------------------------------------
# trace-context survival over router stubs
# ---------------------------------------------------------------------------

class _CtxStubFrontend:
    """test_router's stub plus the ``ctx`` kwarg the router passes when
    tracing is on (plain stubs never see it — the router omits the kwarg
    entirely with tracing off)."""

    def __init__(self):
        self._running = {}
        self.queue = []
        self.submitted = []
        self.cache = None

    def step(self):
        return False

    def submit(self, prompt, max_new_tokens=16, priority=0, deadline=None,
               eos_token_id=None, ctx=None):
        req = Request(prompt=[int(t) for t in prompt],
                      max_new_tokens=int(max_new_tokens),
                      priority=priority, deadline=deadline,
                      eos_token_id=eos_token_id)
        req.trace = ctx
        req.state = RequestState.RUNNING
        self.submitted.append(req)
        return req

    def close(self):
        pass


def _stub_router(n=2, **kw):
    kw.setdefault("hedge", False)
    kw.setdefault("health_every", 0)
    replicas = [LocalReplica(f"r{i}", _CtxStubFrontend())
                for i in range(n)]
    return Router(replicas, **kw), replicas


def _finish_inner(inner, reason="length"):
    inner.state = RequestState.FINISHED
    inner.finish_reason = reason


def _trace_events(trace_id, since=0):
    """Ring events belonging to one trace (retained traces flush there)."""
    return [e for e in _ring()[since:]
            if isinstance(e.get("args"), dict)
            and e["args"].get("trace_id") == trace_id]


def test_router_hedge_race_tags_winner_and_loser_one_trace(rt):
    clk = _Clock()
    router, replicas = _stub_router(2, clock=clk, hedge=True,
                                    hedge_delay_s=1.0)
    n0 = len(_ring())
    try:
        req = router.submit([9, 9, 9], max_new_tokens=4)
        root = req.trace
        assert root is not None and root.root
        primary_ctx = req.primary.ctx
        assert primary_ctx.trace_id == root.trace_id
        assert primary_ctx.parent_span_id == root.span_id
        assert "hedge" not in primary_ctx.baggage
        # the replica's inner request carries the leg context verbatim
        assert req.primary.replica.frontend.submitted[0].trace \
            is primary_ctx
        clk.t += 1.5
        router.poll()                          # hedge fires
        hedge_ctx = req.hedge.ctx
        assert hedge_ctx.trace_id == root.trace_id
        assert hedge_ctx.parent_span_id == root.span_id
        assert hedge_ctx.baggage["hedge"] == 1
        assert hedge_ctx.baggage["replica"] != primary_ctx.baggage["replica"]
        # hedge produces the first token → it wins; BOTH legs back-tagged
        req.hedge.inner.tokens_out.extend([41, 42])
        router.poll()
        assert hedge_ctx.baggage["winner"] == 1
        assert primary_ctx.baggage["winner"] == 0
        winner_inner = req.primary.inner       # hedge got promoted
        winner_inner.tokens_out.extend([43, 44])
        _finish_inner(winner_inner)
        router.poll()
        assert req.done
        # retained (hedge flag), exactly one trace, all legs inside it
        assert "hedge" in rt.retained()[-1]["causes"]
        assert not rt._pending
        evs = _trace_events(root.trace_id, since=n0)
        names = {e["name"] for e in evs}
        assert {"router/request", "router/hedge", "router/hedge_won",
                "router/hedge_lost"} <= names
        assert all(e["args"]["trace_id"] == root.trace_id for e in evs)
        won = next(e for e in evs if e["name"] == "router/hedge_won")
        lost = next(e for e in evs if e["name"] == "router/hedge_lost")
        assert won["args"]["winner"] == 1 and lost["args"]["winner"] == 0
        # parent/child edges: every span parents either another span in
        # the trace or a live leg context (stub frontends don't emit the
        # leg envelope; real ServingFrontends do — see the e2e test)
        ids = {e["args"]["span_id"] for e in evs}
        ids |= {root.span_id, primary_ctx.span_id, hedge_ctx.span_id}
        for e in evs:
            parent = e["args"].get("parent_span_id")
            assert parent is None or parent in ids
    finally:
        router.close()


def test_router_failover_replay_stays_one_trace(rt):
    clk = _Clock()
    router, replicas = _stub_router(2, clock=clk)
    n0 = len(_ring())
    try:
        req = router.submit([5, 6, 7], max_new_tokens=8)
        root = req.trace
        leg0 = req.primary.ctx
        assert "replay" not in leg0.baggage
        req.primary.inner.tokens_out.extend([11, 12])
        router.poll()
        req.primary.replica.kill()
        router.poll()                          # death observed → failover
        assert req.failovers == 1
        leg1 = req.primary.ctx
        assert leg1 is not leg0
        assert leg1.trace_id == root.trace_id      # ONE trace_id
        assert leg1.parent_span_id == root.span_id
        assert leg1.baggage["replay"] == 1         # replay leg tagged
        inner1 = req.primary.inner
        inner1.tokens_out.extend([13, 14, 15, 16, 17, 18])
        _finish_inner(inner1)
        router.poll()
        assert req.done
        summary = rt.retained()[-1]
        assert "failover" in summary["causes"]
        assert summary["trace_id"] == root.trace_id
        evs = _trace_events(root.trace_id, since=n0)
        fo = next(e for e in evs if e["name"] == "router/failover")
        assert fo["args"]["replay"] == 1
        assert fo["args"]["replayed_tokens"] == 2
        env = next(e for e in evs if e["name"] == "router/request")
        assert env["args"]["span_id"] == root.span_id   # envelope IS root
        assert env["args"]["failovers"] == 1
        assert not rt._pending                 # exactly one trace, decided
    finally:
        router.close()


def test_router_rejection_finishes_trace_honestly(rt):
    router, replicas = _stub_router(2, breaker_backoff_s=100.0)
    try:
        for r in replicas:
            router.breakers[r.name].force_open("down")
        r0 = _counter("trace/retained")
        with pytest.raises(AdmissionError):
            router.submit([1, 2, 3], max_new_tokens=4)
        # the trace neither leaks nor vanishes: flagged + finished
        assert _counter("trace/retained") - r0 == 1
        summary = rt.retained()[-1]
        assert "rejected" in summary["causes"]
        assert summary["reason"] == "no_healthy_replica"
        assert not rt._pending
    finally:
        router.close()


def test_disagg_handoff_torn_fallback_flags_reprefill(rt):
    pre = LocalReplica("p0", _CtxStubFrontend(), pool="prefill")
    dec = LocalReplica("d0", _CtxStubFrontend(), pool="decode")
    router = Router([pre, dec], hedge=False, health_every=0)
    n0 = len(_ring())
    try:
        fault_injector.arm("serving_step:1:handoff_torn:handoff",
                           _env=False)
        req = router.submit([4, 3, 2, 1], max_new_tokens=3)
        root = req.trace
        pre_ctx = req.primary.ctx
        assert pre_ctx.baggage["role"] == "prefill"
        inner_p = pre.frontend.submitted[0]
        inner_p.tokens_out.append(5)
        _finish_inner(inner_p)
        router.poll()                          # promote (torn → fallback)
        dec_ctx = req.primary.ctx
        assert dec_ctx.trace_id == root.trace_id
        assert dec_ctx.baggage["role"] == "decode"
        assert dec_ctx.parent_span_id == root.span_id
        inner_d = dec.frontend.submitted[0]
        inner_d.tokens_out.extend([6, 7])
        _finish_inner(inner_d)
        router.poll()
        assert req.done
        assert "reprefill" in rt.retained()[-1]["causes"]
        evs = _trace_events(root.trace_id, since=n0)
        ho = next(e for e in evs if e["name"] == "router/handoff")
        assert ho["args"]["fault"] == "handoff_torn"
        assert ho["args"]["pages"] == 0
        assert ho["args"]["parent_span_id"] == root.span_id
        assert not rt._pending
    finally:
        fault_injector.disarm()
        router.close()


# ---------------------------------------------------------------------------
# kvtier: prefetch/adopt spans + fallback flag ride the request's trace
# ---------------------------------------------------------------------------

def test_kvtier_prefetch_adopt_and_fallback_spans(rt, tmp_path):
    import types

    import numpy as np

    from deepspeed_tpu.inference.ragged import BlockedAllocator
    from deepspeed_tpu.serving import KVTier
    from deepspeed_tpu.serving.prefix_cache import PrefixCache

    BS = 4

    class _Eng:
        def __init__(self):
            self.state = types.SimpleNamespace(
                allocator=BlockedAllocator(16, BS))

        def export_pages(self, blocks):
            m = len(blocks)
            return {k: np.full((2, m, BS, 2), 1.0, np.float32)
                    for k in ("k", "v")}

        def import_pages(self, pages, blocks):
            pass

    eng = _Eng()
    cache = PrefixCache(eng.state.allocator)
    page_bytes = 2 * (2 * 1 * BS * 2) * 4
    tier = KVTier(eng, dram_bytes=2 * page_bytes, high_watermark=0.5,
                  low_watermark=0.25, nvme_dir=str(tmp_path / "nvme"))
    k1 = list(range(BS))
    k2 = k1 + list(range(10, 10 + BS))
    assert tier.capture(k1, 5) and tier.capture(k2, 6)
    tier.capture(list(range(20, 20 + BS)), 7)   # pushes k1+k2 to NVMe
    prompt = k2 + [99]

    ctx = rt.mint(entry="frontend", uid=1)
    assert tier.issue_prefetch(prompt, ctx=ctx) == 2
    assert tier.adopt(prompt, cache, ctx=ctx) == 2
    evs = rt._pending[ctx.trace_id]["events"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["kvtier/prefetch"]["args"]["issued"] == 2
    adopt = by_name["kvtier/adopt"]
    assert adopt["ph"] == "X" and adopt["args"]["pages"] == 2
    assert adopt["args"]["parent_span_id"] == ctx.span_id
    assert all(e["args"]["trace_id"] == ctx.trace_id for e in evs)
    assert rt.finish(ctx, reason="length") is False   # warm hit: healthy

    # a stale adoption flags the trace → tail-retained
    assert tier.capture(list(range(30, 30 + BS)), 8)
    ctx2 = rt.mint(entry="frontend", uid=2)
    fault_injector.arm("serving_step:1:kvtier_stale_adopt:kvtier",
                       _env=False)
    assert tier.adopt(list(range(30, 30 + BS)) + [1], cache, ctx=ctx2) == 0
    assert rt.finish(ctx2, reason="length") is True
    assert "kvtier_fallback" in rt.retained()[-1]["causes"]
    tier.close()


# ---------------------------------------------------------------------------
# engine-backed acceptance: disaggregated fleet under replica_slow chaos
# ---------------------------------------------------------------------------

SRV_CFG = {"dtype": "float32", "num_blocks": 32, "block_size": 8,
           "max_seq_len": 128, "prefill_chunk": 8, "max_batch_tokens": 64,
           "max_sequences": 16}


def _engine(devices, params=None):
    from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.models.transformer import init_params
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=256, vocab_size=256)
    if params is None:
        params = init_params(cfg, jax.random.PRNGKey(0))
    return RaggedInferenceEngineTPU(cfg, dict(SRV_CFG), params=params)


def _disagg_pool(devices):
    from deepspeed_tpu.serving import ServingFrontend
    return [LocalReplica("p0", ServingFrontend(_engine(devices)),
                         pool="prefill"),
            LocalReplica("d0", ServingFrontend(_engine(devices)),
                         pool="decode")]


def test_reqtrace_e2e_disagg_fleet_acceptance(devices, tmp_path,
                                              monkeypatch, capsys):
    """2-replica disaggregated fleet under `replica_slow` chaos: the
    slowed batch is tail-retained and reassembles into ONE merged trace
    spanning router + both replicas with an unbroken parent/child chain
    through the handoff; `/metrics` carries trace_id exemplars; the
    doctor names the dominant critical-path segment; the fast batch is
    dropped whole with `trace/dropped_ok` accounting."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry import fleet as fleetmod
    from deepspeed_tpu.telemetry.doctor import analyze, render
    from deepspeed_tpu.telemetry.summarize import assemble_request
    from deepspeed_tpu.telemetry.summarize import main as trace_main

    def prompts(base):
        return [[base + i, 2, 3, 4, 5, 6, 7, 8, 9] for i in range(2)]

    def worst_ms(reqs):
        """The slower of time-to-first-token and time a token, as
        ``Router._finish`` hands them to the tail sampler."""
        return 1e3 * max(
            max(r.first_token_ts - r.submit_ts,
                (r.finish_ts - r.first_token_ts) / (len(r.tokens_out) - 1))
            for r in reqs)

    new = 8
    reqtrace.clear()
    reqtrace.configure(enabled=False)
    # every step program a batch can ask for, run once on padding rows as
    # a benchmark cell's set-up does: which of them a request meets depends
    # on the pump that picks it up (two requests submitted together prefill
    # as one 2-row batch or as two 1-row batches), and a first-touch compile
    # of half a second inside a "fast" request reads as a slow one
    from benchmark.runners.serve import warm_program_grid
    pool = _disagg_pool(devices)
    for replica in pool:
        warm_program_grid(replica.frontend.engine, replica.frontend.mode)
    router = Router(pool, hedge=False, http_port=0)
    try:
        # ... and what else both legs touch first (the handoff's page
        # export and import), tracing off
        for p in prompts(20):
            router.submit(p, max_new_tokens=new)
        router.run_until_idle(wall_timeout_s=300.0)

        # slow and fast are WALL time, and under the suite's six workers a
        # fast request took over a fixed 400 ms: the threshold follows what
        # a fast batch takes on this machine now (four times its worst),
        # and the chaos delay the threshold. Six of a request's seven
        # decode gaps pay the delay (the seventh may be the handoff's), so
        # a slowed request's time a token is at least 6/7 x 2 thresholds,
        # whatever the load
        probe = [router.submit(p, max_new_tokens=new) for p in prompts(30)]
        router.run_until_idle(wall_timeout_s=300.0)
        retain_ms = max(400.0, 4.0 * worst_ms(probe))
        router.chaos_slow_s = 2.0 * retain_ms / 1e3

        reqtrace.configure(enabled=True, head_sample=0.0,
                           retain_slow_ms=retain_ms, buffer_traces=256)
        d0c = _counter("trace/dropped_ok")
        r0c = _counter("trace/retained")
        fast = [router.submit(p, max_new_tokens=new) for p in prompts(40)]
        router.run_until_idle(wall_timeout_s=300.0)
        assert all(r.finish_reason == "length" for r in fast)
        assert _counter("trace/dropped_ok") - d0c == len(fast)
        assert _counter("trace/retained") == r0c
        assert reqtrace.retained() == []

        # chaos: degrade the decode replica → decode-dominant slow tails
        monkeypatch.setenv("DSTPU_CHAOS_REPLICA", "d0")
        fault_injector.arm("serving_step:1:replica_slow:router",
                           _env=False)
        slow = [router.submit(p, max_new_tokens=new) for p in prompts(60)]
        router.run_until_idle(wall_timeout_s=300.0)
        assert all(r.finish_reason == "length" for r in slow)
        retained = reqtrace.retained()
        assert _counter("trace/retained") - r0c == len(slow)
        assert len(retained) == len(slow)
        assert all(any(c in ("slow_tpot", "slow_ttft")
                       for c in s["causes"]) for s in retained)

        # /metrics exposes trace_id exemplars with the OpenMetrics ctype
        port = router._http.port
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as resp:
            ctype = resp.headers.get("Content-Type", "")
            body = resp.read().decode()
        assert '# {trace_id="' in body
        assert ctype.startswith("application/openmetrics-text")
        ex = fleetmod.latency_exemplars(
            fleetmod.parse_prometheus_text(body))
        assert any(v is not None for v in ex.values())

        # dstpu-trace --request: one merged trace, unbroken chain
        telemetry.tracer.dump(str(tmp_path / "host0.json"))
        tid = slow[0].trace.trace_id
        rep = assemble_request([str(tmp_path)], tid,
                               out=str(tmp_path / "merged.json"))
        names = {e["name"] for e in rep["events"]}
        assert {"router/request", "router/handoff",
                "serving/request"} <= names
        legs = {e["args"].get("replica") for e in rep["events"]}
        assert {"p0", "d0"} <= legs            # spans from BOTH replicas
        assert rep["orphans"] == []            # chain unbroken
        assert rep["flows"]                    # parent/child flow arrows
        root_sid = next(e["args"]["span_id"] for e in rep["events"]
                        if e["name"] == "router/request")
        for e in rep["events"]:
            parent = e["args"].get("parent_span_id")
            assert parent is None or parent == root_sid or \
                parent in {x["args"]["span_id"] for x in rep["events"]}
        assert rep["breakdown"]["decode"] > 0
        assert trace_main(["--request", tid, str(tmp_path)]) == 0
        assert "decode" in capsys.readouterr().out

        # the doctor's slow-requests section names the dominant segment
        report = analyze([telemetry.flight_recorder.snapshot()], [])
        rows = report["reqtrace"]["slow_requests"]
        assert rows
        assert rows[0]["dominant"] in ("decode", "handoff")
        assert report["reqtrace"]["dropped_ok"] >= len(fast)
        text = render(report)
        assert "slow requests" in text
        assert rows[0]["trace_id"] in text
    finally:
        reqtrace.clear()
        reqtrace.configure(enabled=False, head_sample=0.0,
                           retain_slow_ms=500.0)
        fault_injector.disarm()
        router.close()
