"""Disaggregated prefill/decode fleet (serving/handoff.py + router).

Stub-driven tests pin down the routing mechanics (prefill leg runs one
token, the decode leg gets the folded prompt on the decode pool) and the
handoff failure domain (torn / stalled bundles fall back to decode-side
re-prefill and the resilience ledger closes). The page-bundle round-trip
test is the ownership-protocol property: serialize → adopt → invalidate
leaves BOTH arenas with exact refcount/free-block accounting, including
the partial copy-on-write tail page. Engine-backed tests prove the
acceptance property: a disaggregated fleet — with or without an injected
handoff fault — produces the exact argmax token sequences of an
undisturbed single-frontend run.
"""

import numpy as np
import pytest
import jax

from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.resilience.faults import fault_injector
from deepspeed_tpu.serving.handoff import (PageBundle, adopt_bundle,
                                           export_bundle, verify_bundle)
from deepspeed_tpu.serving.request import Request, RequestState
from deepspeed_tpu.serving.router import LocalReplica, Router


@pytest.fixture(autouse=True)
def _disarm():
    fault_injector.disarm()
    fault_injector.last_step = None
    yield
    fault_injector.disarm()
    fault_injector.last_step = None


def _counter(name: str) -> float:
    from deepspeed_tpu import telemetry
    return telemetry.registry.counter(name).value


class _Clock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


class _StubFrontend:
    """Minimal frontend stand-in (same contract as test_router's): the
    router only needs submit()/step() plus the load-accounting attrs;
    tests feed inner-request tokens by hand."""

    def __init__(self):
        self._running = {}
        self.queue = []
        self.submitted = []
        self.cache = None

    def step(self):
        return False

    def submit(self, prompt, max_new_tokens=16, priority=0, deadline=None,
               eos_token_id=None):
        req = Request(prompt=[int(t) for t in prompt],
                      max_new_tokens=int(max_new_tokens),
                      priority=priority, deadline=deadline,
                      eos_token_id=eos_token_id)
        req.state = RequestState.RUNNING
        self.submitted.append(req)
        return req

    def close(self):
        pass


def _finish(inner, reason="length"):
    inner.state = RequestState.FINISHED
    inner.finish_reason = reason


def _stub_disagg(**kw):
    kw.setdefault("hedge", False)
    kw.setdefault("health_every", 0)
    pre = LocalReplica("p0", _StubFrontend(), pool="prefill")
    dec = LocalReplica("d0", _StubFrontend(), pool="decode")
    return Router([pre, dec], **kw), pre, dec


# ---------------------------------------------------------------------------
# page bundle: checksum + serialization contract (no engine)
# ---------------------------------------------------------------------------

def test_bundle_checksum_detects_torn_payload():
    pages = {"k": np.arange(24, dtype=np.float32).reshape(2, 2, 2, 3),
             "v": np.ones((2, 2, 2, 3), np.float32)}
    from deepspeed_tpu.serving.handoff import _checksum
    bundle = PageBundle(tokens=[1, 2, 3, 4], block_size=2, pages=pages,
                        checksum=_checksum(pages))
    assert bundle.num_pages == 2
    assert bundle.nbytes == pages["k"].nbytes + pages["v"].nbytes
    assert verify_bundle(bundle)
    # torn in transit: any flipped byte fails verification
    bundle.pages["v"][1, 1, 0, 2] += 1.0
    assert not verify_bundle(bundle)
    bundle.pages["v"][1, 1, 0, 2] -= 1.0
    assert verify_bundle(bundle)
    bundle.checksum ^= 0x1
    assert not verify_bundle(bundle)


def test_bundle_export_adopt_degrade_gracefully_without_cache():
    fe = _StubFrontend()                     # cache is None
    assert export_bundle(fe, [1, 2, 3]) is None
    bundle = PageBundle(tokens=[1], block_size=8,
                        pages={"k": np.zeros((1, 1, 8, 2), np.float32),
                               "v": np.zeros((1, 1, 8, 2), np.float32)})
    assert adopt_bundle(fe, bundle) == 0


# ---------------------------------------------------------------------------
# routing mechanics over stubs: prefill leg → promotion → decode leg
# ---------------------------------------------------------------------------

def test_disagg_prefill_leg_promotes_to_decode_pool():
    router, pre, dec = _stub_disagg()
    try:
        assert router.disaggregated
        skipped0 = _counter("handoff/skipped")
        req = router.submit([1, 2, 3, 4], max_new_tokens=5)
        assert req.phase == "prefill"
        inner_p = pre.frontend.submitted[0]
        assert inner_p.max_new_tokens == 1       # one token proves the KV
        assert not dec.frontend.submitted
        inner_p.tokens_out.append(7)
        _finish(inner_p)
        router.poll()
        # promoted: decode leg carries the folded prompt and the
        # remaining budget; stub has no cache → handoff skipped
        assert req.phase == "decode"
        assert req.handoff_tokens == 1
        inner_d = dec.frontend.submitted[0]
        assert inner_d.prompt == [1, 2, 3, 4, 7]
        assert inner_d.max_new_tokens == 4
        assert _counter("handoff/skipped") - skipped0 == 1
        inner_d.tokens_out.extend([8, 9, 10, 11])
        _finish(inner_d)
        router.poll()
        assert req.done and req.finish_reason == "length"
        assert req.tokens_out == [7, 8, 9, 10, 11]
        stats = router.stats()
        assert stats["disaggregated"]
        assert stats["pools"] == {"p0": "prefill", "d0": "decode"}
    finally:
        router.close()


def test_disagg_prefill_eos_finishes_without_promotion():
    router, pre, dec = _stub_disagg()
    try:
        req = router.submit([1, 2, 3], max_new_tokens=5, eos_token_id=9)
        inner_p = pre.frontend.submitted[0]
        inner_p.tokens_out.append(9)
        _finish(inner_p, "eos")
        router.poll()
        assert req.done and req.finish_reason == "eos"
        assert req.tokens_out == [9]
        assert not dec.frontend.submitted    # no decode leg for eos@1
    finally:
        router.close()


@pytest.mark.parametrize("kind", ["handoff_torn", "handoff_stall"])
def test_disagg_handoff_fault_falls_back_and_ledger_closes(kind):
    """A torn or stalled bundle ships nothing: the decode replica
    re-prefills the folded prompt (zero token loss) and the fallback is
    ledgered as a recovery once the stream finishes."""
    router, pre, dec = _stub_disagg()
    f0 = _counter("resilience/faults_injected")
    r0 = _counter("resilience/recoveries")
    fb0 = _counter("handoff/fallback_reprefills")
    try:
        fault_injector.arm(f"serving_step:1:{kind}:handoff", _env=False)
        req = router.submit([4, 3, 2, 1], max_new_tokens=3)
        inner_p = pre.frontend.submitted[0]
        inner_p.tokens_out.append(5)
        _finish(inner_p)
        router.poll()
        assert req.phase == "decode"
        assert _counter("handoff/fallback_reprefills") - fb0 == 1
        assert req.uid in router._pending_handoff
        assert _counter("resilience/faults_injected") - f0 == 1
        inner_d = dec.frontend.submitted[0]
        assert inner_d.prompt == [4, 3, 2, 1, 5]     # the fold, not the bundle
        inner_d.tokens_out.extend([6, 7])
        _finish(inner_d)
        router.poll()
        assert req.done and req.tokens_out == [5, 6, 7]
        assert not router._pending_handoff
        assert _counter("resilience/recoveries") - r0 == 1
    finally:
        fault_injector.disarm()
        router.close()


# ---------------------------------------------------------------------------
# drain: streams cut by a scale-down finish honestly as "drained"
# ---------------------------------------------------------------------------

def test_stream_cut_past_retry_budget_finishes_drained():
    """A stream stranded on a draining replica past the retry budget
    finishes with reason "drained" — an operator action, not an error,
    and never the client-side stall RuntimeError."""
    clk = _Clock()
    replicas = [LocalReplica(f"r{i}", _StubFrontend()) for i in range(2)]
    router = Router(replicas, hedge=False, health_every=0,
                    retry_budget=0, clock=clk)
    d0 = _counter("router/drained_streams")
    e0 = _counter("router/errors")
    try:
        req = router.submit([1, 2, 3], max_new_tokens=4)
        victim = req.primary.replica.name
        router.drain(victim, deadline_s=0.0)     # deadline already past
        clk.t = 1.0
        router.poll()
        assert req.done and req.finish_reason == "drained"
        assert _counter("router/drained_streams") - d0 == 1
        assert _counter("router/errors") == e0   # NOT an error
        # the drained replica left the fleet once its streams were cut
        assert victim not in {r.name for r in router.replicas}
    finally:
        router.close()


def test_stream_cut_by_drain_fails_over_within_budget():
    """With retry budget left, a drain-deadline cut is a normal
    failover: the stream replays its fold on a live replica."""
    clk = _Clock()
    replicas = [LocalReplica(f"r{i}", _StubFrontend()) for i in range(2)]
    router = Router(replicas, hedge=False, health_every=0, clock=clk)
    try:
        req = router.submit([1, 2, 3], max_new_tokens=4)
        first = req.primary.replica
        inner1 = first.frontend.submitted[0]
        inner1.tokens_out.append(9)
        router.poll()                            # deliver one token
        router.drain(first.name, deadline_s=0.0)
        clk.t = 1.0
        router.poll()
        other = req.primary.replica
        assert other.name != first.name
        inner2 = other.frontend.submitted[-1]
        assert inner2.prompt == [1, 2, 3, 9]     # token fold replayed
        inner2.tokens_out.extend([10, 11, 12])
        _finish(inner2)
        router.poll()
        assert req.done and req.finish_reason == "length"
        assert req.tokens_out == [9, 10, 11, 12]
    finally:
        router.close()


def test_inner_drained_reason_triggers_failover():
    """A replica that terminates its in-flight requests with reason
    "drained" (frontend.terminate_inflight) pushes each stream back to
    the router, which re-dispatches rather than erroring."""
    clk = _Clock()
    replicas = [LocalReplica(f"r{i}", _StubFrontend()) for i in range(2)]
    router = Router(replicas, hedge=False, health_every=0, clock=clk)
    try:
        req = router.submit([7, 8], max_new_tokens=2)
        first = req.primary.replica
        _finish(first.frontend.submitted[0], "drained")
        router.poll()
        assert not req.done
        assert req.primary.replica.name != first.name
        inner2 = req.primary.replica.frontend.submitted[-1]
        inner2.tokens_out.extend([1, 2])
        _finish(inner2)
        router.poll()
        assert req.done and req.finish_reason == "length"
    finally:
        router.close()


# ---------------------------------------------------------------------------
# engine-backed: page round trip + end-to-end parity
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


def _disagg_pool(devices, prefill=1, decode=1):
    from deepspeed_tpu.serving import ServingFrontend
    out = []
    for i in range(prefill):
        out.append(LocalReplica(f"p{i}", ServingFrontend(_engine(devices)),
                                pool="prefill"))
    for i in range(decode):
        out.append(LocalReplica(f"d{i}", ServingFrontend(_engine(devices)),
                                pool="decode"))
    return out


def _expected(devices, prompts, new):
    """Token sequences from one undisturbed frontend (argmax ground
    truth every replica must reproduce — they share the param seed)."""
    from deepspeed_tpu.serving import ServingFrontend
    fe = ServingFrontend(_engine(devices))
    reqs = [fe.submit(p, max_new_tokens=new) for p in prompts]
    fe.run_until_idle()
    return [r.tokens_out for r in reqs]


def test_handoff_bundle_roundtrip_page_accounting(devices):
    """The ownership protocol: export is read-only on the source, adopt
    leaves the destination cache as the pages' only owner (refcount
    exactly 1, pool shrunk by exactly the shipped pages — including the
    partial CoW tail), re-adopting the same bundle leaks nothing, and
    the source invalidate releases the subtree exactly once."""
    from deepspeed_tpu.serving import ServingFrontend
    src = ServingFrontend(_engine(devices))
    dst = ServingFrontend(_engine(devices))
    # 12 tokens @ block_size 8 → one full page + a 4-token partial tail
    prompt = [5, 4, 3, 2, 1, 6, 7, 8, 9, 10, 11, 12]
    src.submit(prompt, max_new_tokens=1)
    src.run_until_idle()
    src_alloc = src.engine.state.allocator
    dst_alloc = dst.engine.state.allocator
    assert src.cache.pages_cached == 2
    owned_src = sorted(src.cache.owned_blocks())
    assert len(owned_src) == src.cache.pages_cached
    free_src0 = src_alloc.free_blocks

    bundle = export_bundle(src, prompt)
    assert bundle is not None and verify_bundle(bundle)
    assert bundle.num_pages == 2
    assert bundle.tokens == prompt and bundle.block_size == 8
    # read-only on the source: nothing moved
    assert src_alloc.free_blocks == free_src0
    assert sorted(src.cache.owned_blocks()) == owned_src
    assert all(src_alloc.refcount(b) >= 1 for b in owned_src)

    free_dst0 = dst_alloc.free_blocks
    assert adopt_bundle(dst, bundle) == 2
    owned_dst = dst.cache.owned_blocks()
    assert len(owned_dst) == dst.cache.pages_cached == 2
    assert all(dst_alloc.refcount(b) == 1 for b in owned_dst)
    assert dst_alloc.free_blocks == free_dst0 - 2
    m = dst.cache.match(prompt)
    assert len(m.full_blocks) == 1 and m.partial_len == 4
    # payload round trip is byte-exact: re-exporting from the
    # destination reproduces the bundle
    again = export_bundle(dst, prompt)
    assert again is not None and verify_bundle(again)
    for key in ("k", "v"):
        np.testing.assert_array_equal(again.pages[key], bundle.pages[key])
    # idempotent re-adopt: insert declines already-cached pages and
    # adopt_bundle drops its own ref — no leak, no double count
    assert adopt_bundle(dst, bundle) == 0
    assert dst_alloc.free_blocks == free_dst0 - 2
    assert dst.cache.pages_cached == 2
    assert all(dst_alloc.refcount(b) == 1 for b in owned_dst)
    # source invalidate: the shipped subtree releases exactly once
    assert src.cache.invalidate(prompt) == 2
    assert src.cache.pages_cached == 0
    assert src.cache.owned_blocks() == []
    assert src_alloc.free_blocks == free_src0 + 2
    src.close()
    dst.close()


def test_disagg_fleet_parity_with_page_handoff(devices):
    """Happy path acceptance: a prefill+decode fleet with KV-page
    handoff produces the exact argmax sequences of an undisturbed
    single-frontend run, and pages actually ship."""
    prompts = [[20 + i, 2, 3, 4, 5, 6, 7, 8, 9] for i in range(3)]
    new = 6
    expected = _expected(devices, prompts, new)
    h0 = _counter("handoff/completed")
    p0 = _counter("handoff/pages_shipped")
    router = Router(_disagg_pool(devices), hedge=False)
    try:
        reqs = [router.submit(p, max_new_tokens=new) for p in prompts]
        router.run_until_idle(wall_timeout_s=300.0)
        assert [r.tokens_out for r in reqs] == expected
        assert all(r.finish_reason == "length" for r in reqs)
        stats = router.stats()
        assert stats["disaggregated"]
        assert _counter("handoff/completed") - h0 == len(prompts)
        assert _counter("handoff/pages_shipped") - p0 >= len(prompts)
        # every decode token came off the decode pool: the prefill
        # replica delivered exactly one token per stream
        assert stats["replica_tokens"]["p0"] == len(prompts)
        assert stats["replica_tokens"]["d0"] == len(prompts) * (new - 1)
    finally:
        router.close()


@pytest.mark.parametrize("kind", ["handoff_torn", "handoff_stall"])
def test_disagg_handoff_fault_parity_and_doctor(devices, kind):
    """Acceptance for the handoff failure domain: with a torn or
    stalled bundle injected, every stream still matches the undisturbed
    argmax run (decode-side re-prefill, zero token loss), the ledger
    closes, and the doctor renders the handoff fallback + recovery."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry.doctor import analyze, render
    prompts = [[40, 2, 3, 4, 5, 6, 7, 8, 9, 10]]
    new = 5
    expected = _expected(devices, prompts, new)
    f0 = _counter("resilience/faults_injected")
    r0 = _counter("resilience/recoveries")
    n0 = len(telemetry.flight_recorder.snapshot().get("events", []))
    router = Router(_disagg_pool(devices), hedge=False)
    try:
        fault_injector.arm(f"serving_step:1:{kind}:handoff", _env=False)
        reqs = [router.submit(p, max_new_tokens=new) for p in prompts]
        router.run_until_idle(wall_timeout_s=300.0)
        assert [r.tokens_out for r in reqs] == expected
        assert all(r.finish_reason == "length" for r in reqs)
        assert _counter("resilience/faults_injected") - f0 == 1
        assert _counter("resilience/recoveries") - r0 == 1
        events = telemetry.flight_recorder.snapshot().get(
            "events", [])[n0:]
        assert any(e["kind"] == "router_handoff_fallback"
                   and e["fault"] == kind for e in events)
        report = analyze([{"meta": {"hostname": "h0"}, "steps": [],
                           "events": events}], [])
        assert report["resilience"]["unrecovered"] == 0
        text = render(report)
        assert "router_handoff_fallback" in text
        assert "handoff_reprefill" in text
    finally:
        fault_injector.disarm()
        router.close()
