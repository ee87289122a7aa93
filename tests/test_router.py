"""Fault-tolerant multi-replica serving router (serving/router.py).

Unit tests drive the router over stub frontends with an injectable
clock — every race (hedge vs primary, failover vs drain) is decided by
hand-fed tokens, not wall time. The engine-backed tests prove the
acceptance property end to end: a replica killed mid-stream by a chaos
plan loses nothing — every stream completes with the exact token
sequence an undisturbed run produces (the failover fold re-prefills the
client-visible decode state), the resilience ledger balances, and the
doctor names the killed replica.
"""

import time
import urllib.request

import numpy as np
import pytest
import jax

from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.resilience.faults import fault_injector
from deepspeed_tpu.serving.queue import AdmissionError, AdmissionQueue
from deepspeed_tpu.serving.request import Request, RequestState
from deepspeed_tpu.serving.router import (BreakerState, CircuitBreaker,
                                          LocalReplica, Router)


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
    """Minimal frontend stand-in: the router only needs submit()/step()
    plus the load-accounting attrs; tests feed inner-request tokens by
    hand so every race is deterministic."""

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


def _stub_router(n=2, **kw):
    kw.setdefault("hedge", False)
    kw.setdefault("health_every", 0)
    replicas = [LocalReplica(f"r{i}", _StubFrontend()) for i in range(n)]
    return Router(replicas, **kw), replicas


def _finish(inner, reason="length"):
    inner.state = RequestState.FINISHED
    inner.finish_reason = reason


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

def test_breaker_state_machine():
    clk = _Clock()
    transitions = []
    br = CircuitBreaker(failure_threshold=2, backoff_s=1.0,
                        backoff_max_s=4.0, clock=clk,
                        on_transition=lambda o, n, r: transitions.append(
                            (o.value, n.value)))
    assert br.state is BreakerState.CLOSED
    # one failure below threshold does not open; a success resets it
    assert not br.record_failure("x")
    br.record_success()
    assert br.failures == 0 and br.state is BreakerState.CLOSED
    # threshold consecutive failures open
    br.record_failure("a")
    assert br.record_failure("b")
    assert br.state is BreakerState.OPEN
    # no probe before the backoff elapsed
    assert not br.allow_probe()
    clk.t = 1.1
    assert br.allow_probe()
    assert br.state is BreakerState.HALF_OPEN
    assert not br.allow_probe()          # exactly one probe per period
    # failed probe re-opens with doubled backoff
    assert br.record_failure("probe died")
    assert br.state is BreakerState.OPEN
    clk.t += 1.5                         # 1.5 < 2.0 doubled backoff
    assert not br.allow_probe()
    clk.t += 1.0
    assert br.allow_probe()
    # successful probe closes and resets the backoff ladder
    br.record_success()
    assert br.state is BreakerState.CLOSED and br.failures == 0
    assert ("closed", "open") in transitions
    assert ("half_open", "closed") in transitions


def test_breaker_force_open_and_backoff_cap():
    clk = _Clock()
    br = CircuitBreaker(failure_threshold=3, backoff_s=1.0,
                        backoff_max_s=2.0, clock=clk)
    br.force_open("replica died")
    assert br.state is BreakerState.OPEN
    # repeated failed probes saturate at backoff_max_s
    for _ in range(4):
        clk.t += 2.1
        assert br.allow_probe()
        br.record_failure("still dead")
    assert br._backoff == 2.0


# ---------------------------------------------------------------------------
# placement: prefix affinity + load spill
# ---------------------------------------------------------------------------

def test_affinity_stable_spread_and_spill():
    router, replicas = _stub_router(3, affinity_tokens=8)
    try:
        shared = [1, 2, 3, 4, 5, 6, 7, 8]
        # shared-prefix prompts land on ONE replica (warm radix cache)
        homes = {router._choose(shared + [100 + i]).name for i in range(8)}
        assert len(homes) == 1
        home = homes.pop()
        # distinct prefixes spread over the pool
        rng = np.random.default_rng(0)
        spread = {router._choose(rng.integers(1, 250, size=12).tolist()).name
                  for _ in range(30)}
        assert len(spread) >= 2
        # a hot affinity target spills to the least-loaded replica
        fe = next(r.frontend for r in replicas if r.name == home)
        fe.queue.extend(object() for _ in range(10))
        assert router._choose(shared + [999]).name != home
    finally:
        router.close()


def test_no_healthy_replica_rejects_with_reason():
    router, replicas = _stub_router(2, breaker_backoff_s=100.0)
    try:
        for r in replicas:
            router.breakers[r.name].force_open("down")
        with pytest.raises(AdmissionError) as ei:
            router.submit([1, 2, 3], max_new_tokens=4)
        assert ei.value.reason == "no_healthy_replica"
    finally:
        router.close()


# ---------------------------------------------------------------------------
# failover: fold + retry budget
# ---------------------------------------------------------------------------

def test_failover_folds_streamed_tokens_into_prompt():
    clk = _Clock()
    router, replicas = _stub_router(2, clock=clk)
    try:
        f0 = _counter("router/failovers")
        req = router.submit([5, 6, 7], max_new_tokens=8)
        first = req.primary.replica
        other = next(r for r in replicas if r is not first)
        inner0 = req.primary.inner
        inner0.tokens_out.extend([11, 12, 13])
        router.poll()                      # drains 3 tokens to the client
        assert req.tokens_out == [11, 12, 13]
        first.kill()
        router.poll()                      # death observed → failover
        assert _counter("router/failovers") - f0 == 1
        assert req.failovers == 1
        inner1 = req.primary.inner
        assert req.primary.replica is other
        # the fold: already-streamed tokens became prompt, budget shrank
        assert inner1.prompt == [5, 6, 7, 11, 12, 13]
        assert inner1.max_new_tokens == 5
        inner1.tokens_out.extend([14, 15, 16, 17, 18])
        _finish(inner1)
        router.poll()
        assert req.done and req.finish_reason == "length"
        assert req.tokens_out == [11, 12, 13, 14, 15, 16, 17, 18]
        assert router.replica_state(first) == "dead"
    finally:
        router.close()


def test_failover_retry_budget_exhausts_to_error():
    clk = _Clock()
    router, replicas = _stub_router(2, clock=clk, retry_budget=0)
    try:
        e0 = _counter("router/errors")
        req = router.submit([1, 2], max_new_tokens=4)
        req.primary.replica.kill()
        router.poll()
        assert req.done and req.finish_reason == "error"
        assert _counter("router/errors") - e0 == 1
    finally:
        router.close()


# ---------------------------------------------------------------------------
# hedged dispatch
# ---------------------------------------------------------------------------

def test_hedge_races_and_first_token_wins():
    clk = _Clock()
    router, replicas = _stub_router(2, clock=clk, hedge=True,
                                    hedge_delay_s=1.0)
    try:
        h0 = _counter("router/hedges")
        w0 = _counter("router/hedges_won")
        req = router.submit([9, 9, 9], max_new_tokens=4)
        slow = req.primary.inner
        router.poll()
        assert req.hedge is None           # delay not yet elapsed
        clk.t += 1.5
        router.poll()
        assert req.hedge is not None
        assert _counter("router/hedges") - h0 == 1
        assert req.hedge.replica is not req.primary.replica
        # hedge produces the first token → it wins, the primary leg is
        # cancelled, and the client only ever sees the winner's tokens
        hedge_inner = req.hedge.inner
        hedge_inner.tokens_out.extend([41, 42])
        router.poll()
        assert _counter("router/hedges_won") - w0 == 1
        assert slow.cancelled
        assert req.tokens_out == [41, 42]
        hedge_inner.tokens_out.extend([43, 44])
        _finish(hedge_inner)
        router.poll()
        assert req.done and req.tokens_out == [41, 42, 43, 44]
    finally:
        router.close()


def test_hedge_loses_when_primary_answers_first():
    clk = _Clock()
    router, replicas = _stub_router(2, clock=clk, hedge=True,
                                    hedge_delay_s=1.0)
    try:
        l0 = _counter("router/hedges_lost")
        req = router.submit([3, 1, 4], max_new_tokens=2)
        clk.t += 1.5
        router.poll()
        hedge_inner = req.hedge.inner
        req.primary.inner.tokens_out.append(7)
        router.poll()
        assert _counter("router/hedges_lost") - l0 == 1
        assert hedge_inner.cancelled and req.hedge is None
        assert req.tokens_out == [7]
    finally:
        router.close()


# ---------------------------------------------------------------------------
# draining
# ---------------------------------------------------------------------------

def test_drain_finishes_streams_then_removes_replica():
    clk = _Clock()
    router, replicas = _stub_router(2, clock=clk)
    try:
        req = router.submit([2, 2, 2], max_new_tokens=2)
        target = req.primary.replica
        router.drain(target.name)
        assert router.replica_state(target) == "draining"
        # new admissions avoid the draining replica
        req2 = router.submit([8, 8, 8, 8], max_new_tokens=2)
        assert req2.primary.replica is not target
        # the in-flight stream still finishes ON the draining replica
        inner = req.primary.inner
        inner.tokens_out.extend([1, 2])
        _finish(inner)
        router.poll()
        assert req.done and req.tokens_out == [1, 2]
        assert target not in router.replicas
        _finish(req2.primary.inner)
        router.poll()
    finally:
        router.close()


# ---------------------------------------------------------------------------
# chaos drill over stubs: ledger + doctor + degraded healthz
# ---------------------------------------------------------------------------

def test_chaos_kill_ledger_doctor_and_degraded_healthz(monkeypatch):
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry.doctor import analyze, render
    clk = _Clock()
    router, replicas = _stub_router(2, clock=clk, http_port=0)
    try:
        f0 = _counter("resilience/faults_injected")
        r0 = _counter("resilience/recoveries")
        n0 = len(telemetry.flight_recorder.snapshot().get("events", []))
        req = router.submit([4, 4, 4], max_new_tokens=4)
        victim = req.primary.replica.name
        monkeypatch.setenv("DSTPU_CHAOS_REPLICA", victim)
        fault_injector.arm("serving_step:1:replica_kill:router",
                           _env=False)
        router.poll()                  # chaos fires, kill + failover
        assert _counter("resilience/faults_injected") - f0 == 1
        assert req.failovers == 1
        # failover replay still draining → router /healthz degraded
        port = router._http.port
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5)
        assert ei.value.code == 503
        # stream completes gaplessly → recovery recorded, healthz ok
        inner = req.primary.inner
        inner.tokens_out.extend([1, 2, 3, 4])
        _finish(inner)
        router.poll()
        assert req.done and req.tokens_out == [1, 2, 3, 4]
        assert _counter("resilience/recoveries") - r0 == 1
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5) as resp:
            assert resp.status == 200
        # the doctor's recovery timeline names the killed replica
        dump = {"meta": {"hostname": "h0"}, "steps": [],
                "events": telemetry.flight_recorder.snapshot()
                .get("events", [])[n0:]}
        report = analyze([dump], [])
        assert report["resilience"]["unrecovered"] == 0
        timeline = report["recovery_timeline"]
        assert any(e["kind"] == "router_replica_kill"
                   and e.get("replica") == victim for e in timeline)
        text = render(report)
        assert f"replica={victim}" in text
    finally:
        router.close()


def test_chaos_slow_recovery_recorded_when_hedge_engages(monkeypatch):
    clk = _Clock()
    router, replicas = _stub_router(2, clock=clk, hedge=True,
                                    hedge_delay_s=1.0)
    try:
        r0 = _counter("resilience/recoveries")
        req = router.submit([6, 6], max_new_tokens=2)
        victim = req.primary.replica
        monkeypatch.setenv("DSTPU_CHAOS_REPLICA", victim.name)
        fault_injector.arm("serving_step:1:replica_slow:router",
                           _env=False)
        router.poll()
        assert victim.slow_s > 0           # degradation applied
        assert _counter("resilience/recoveries") - r0 == 0
        clk.t += 1.5
        router.poll()                      # hedge engages → recovery
        assert req.hedge is not None
        assert _counter("resilience/recoveries") - r0 == 1
        _finish(req.primary.inner)
        router.poll()
    finally:
        router.close()


# ---------------------------------------------------------------------------
# satellite regressions: queue victim, fault grammar, fleet clock, top
# ---------------------------------------------------------------------------

def test_queue_full_submit_returns_shed_victim():
    q = AdmissionQueue(max_depth=1)
    stale = Request(prompt=[1], max_new_tokens=2, deadline=5.0)
    assert q.submit(stale, now=0.0) is None
    fresh = Request(prompt=[2], max_new_tokens=2)
    victim = q.submit(fresh, now=10.0)     # stale is past-deadline
    assert victim is stale
    assert victim.state is RequestState.SHED
    assert victim.finish_reason == "deadline"
    assert q.peek_all() == [fresh]
    # full of LIVE work still rejects loudly
    with pytest.raises(AdmissionError) as ei:
        q.submit(Request(prompt=[3], max_new_tokens=2), now=10.0)
    assert ei.value.reason == "queue_full"


def test_fault_plan_replica_kinds_pinned_to_router_site(capsys):
    from deepspeed_tpu.resilience.faults import (FaultInjector, main,
                                                 parse_fault_plan)
    entries = parse_fault_plan(
        "serving_step:4:replica_kill:router;"
        "serving_step:9:replica_slow:router")
    assert [e.kind for e in entries] == ["replica_kill", "replica_slow"]
    assert all(e.site == "router" for e in entries)
    # a replica's own pump can never consume a fleet-scoped fault, even
    # with an unsited entry — replica kinds only match the router site
    fi = FaultInjector().arm("serving_step:1:replica_kill", _env=False)
    assert fi.fire("serving_step", serving_step=5) == []
    assert fi.pending()
    assert fi.fire("router", serving_step=5) == ["replica_kill"]
    assert not fi.pending()
    # --explain documents the fleet drills
    assert main(["--plan", "serving_step:4:replica_kill:router",
                 "--explain"]) == 0
    out = capsys.readouterr().out
    assert "replica_kill" in out and "fleet drill" in out


def test_fleet_staleness_robust_to_clock_steps():
    from deepspeed_tpu.telemetry.endpoint import MetricsServer
    from deepspeed_tpu.telemetry.fleet import HostSample, poll_host
    srv = MetricsServer(0)
    try:
        s = HostSample(f"127.0.0.1:{srv.port}")
        poll_host(s, timeout=5.0, clock=lambda: 100.0)
        assert s.ok and s.ts == 100.0
        # wall-clock step backwards between polls (NTP slew): rates must
        # come back None, not negative, and staleness must clamp to 0
        poll_host(s, timeout=5.0, clock=lambda: 50.0)
        row = s.row(now=10.0)
        assert row["stale_s"] == 0.0
        assert row["tok_rate"] is None and row["step_rate"] is None
    finally:
        srv.close()


def test_dstpu_top_renders_per_replica_router_states():
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry.endpoint import MetricsServer
    from deepspeed_tpu.telemetry.fleet import (HostSample, poll_host,
                                               render_table,
                                               router_states)
    # the registry is process-wide: drop the replicas that whichever test
    # file ran before this one in the same xdist worker left behind
    for name in telemetry.registry.names():
        if name.startswith("router/replica/"):
            telemetry.registry.unregister(name)
    telemetry.registry.gauge("router/replica/r0/state").set(0.0)
    telemetry.registry.gauge("router/replica/r1/state").set(2.0)
    telemetry.registry.gauge("router/replica/r2/state").set(3.0)
    srv = MetricsServer(0)
    try:
        s = HostSample(f"127.0.0.1:{srv.port}")
        poll_host(s, timeout=5.0)
        row = s.row(now=time.monotonic())
        assert row["router"] == {"r0": "healthy", "r1": "open",
                                 "r2": "draining"}
        table = render_table([row])
        assert "router: r0=healthy r1=open r2=draining" in table
        assert router_states({"serving_ttft_seconds": 1.0}) is None
    finally:
        srv.close()


def test_replica_pool_agent_spawn_kill_restart_stop():
    from deepspeed_tpu.launcher.agent import ReplicaPoolAgent
    pool = ReplicaPoolAgent(["python", "-c", "import time; time.sleep(60)"],
                            3, base_port=19310).start()
    try:
        assert pool.targets() == [f"127.0.0.1:{19310 + i}"
                                  for i in range(3)]
        assert set(pool.poll().values()) == {"running"}
        pool.kill("r1")                    # deliberate down: stays down
        pool.kill("r2", restart=True)      # chaos kill: budget restarts
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            phases = pool.poll()
            if phases["r1"] == "down" and phases["r2"] != "running":
                break
            time.sleep(0.05)
        assert phases["r0"] == "running"
        assert phases["r1"] == "down"
        assert phases["r2"] == "restarting"
        assert pool.restarts == 1
    finally:
        pool.stop(grace_s=2.0)
    assert all(p == "down" for p in pool.poll().values())


def test_replica_pool_agent_one_chip_per_child(tmp_path, monkeypatch):
    """On a host with several TPU chips every child is shown one chip —
    the lowest no live sibling holds; a restart keeps its own; a replica
    beyond the chip count is refused; a caller who already chose
    (TPU_VISIBLE_DEVICES) or runs off the TPU is left alone."""
    from deepspeed_tpu.launcher import agent
    monkeypatch.setattr(agent, "_local_tpu_chips", lambda: 2)
    monkeypatch.delenv("TPU_VISIBLE_DEVICES", raising=False)
    code = ("import os, time; open(os.path.join(%r, "
            "os.environ['DSTPU_REPLICA_NAME'] + '.' + str(os.getpid())), "
            "'w').write(os.environ.get('TPU_VISIBLE_DEVICES', 'unset') + ' '"
            " + os.environ.get('TPU_PROCESS_BOUNDS', 'unset')); "
            "time.sleep(60)" % str(tmp_path))

    def seen(name, n=1):
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            vals = sorted(p.read_text() for p in tmp_path.glob(name + ".*"))
            if len(vals) == n and all(vals):
                return vals
            time.sleep(0.05)
        raise AssertionError(f"{name}: {n} process(es) never wrote their "
                             f"environment")

    pool = agent.ReplicaPoolAgent(["python", "-c", code], 2,
                                  env={"JAX_PLATFORMS": "tpu,cpu"}).start()
    try:
        assert seen("r0") == ["0 1,1,1"] and seen("r1") == ["1 1,1,1"]
        with pytest.raises(RuntimeError, match="no free chip"):
            pool.add_replica()
        pool.kill("r0", restart=True)
        pool.poll()                              # restarts r0 on ITS chip
        assert seen("r0", 2) == ["0 1,1,1", "0 1,1,1"]
    finally:
        pool.stop(grace_s=2.0)
    # the CPU suite's own environment (JAX_PLATFORMS=cpu): hands off
    off = agent.ReplicaPoolAgent(["python", "-c", code], 1)
    assert off._chips == 0


# ---------------------------------------------------------------------------
# engine-backed: failover stream integrity (the acceptance property)
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


def _pool(devices, n):
    from deepspeed_tpu.serving import ServingFrontend
    engines = [_engine(devices) for _ in range(n)]
    return [LocalReplica(f"r{i}", ServingFrontend(eng))
            for i, eng in enumerate(engines)]


def _expected(devices, prompts, new):
    """Token sequences from one undisturbed frontend (argmax ground
    truth every replica must reproduce — they share the param seed)."""
    from deepspeed_tpu.serving import ServingFrontend
    fe = ServingFrontend(_engine(devices))
    reqs = [fe.submit(p, max_new_tokens=new) for p in prompts]
    fe.run_until_idle()
    return [r.tokens_out for r in reqs]


def test_router_failover_midstream_gapless_parity(devices, monkeypatch):
    """Kill a replica mid-stream via a chaos plan: every stream must
    complete with the exact uninterrupted argmax sequence — no gap, no
    duplicate — and the faults==recoveries ledger must balance."""
    prompts = [[1 + i, 2, 3, 4] for i in range(4)]
    new = 6
    expected = _expected(devices, prompts, new)
    f0 = _counter("resilience/faults_injected")
    r0 = _counter("resilience/recoveries")
    router = Router(_pool(devices, 2), hedge=False)
    try:
        fault_injector.arm("serving_step:3:replica_kill:router",
                           _env=False)
        reqs = [router.submit(p, max_new_tokens=new) for p in prompts]
        router.run_until_idle(wall_timeout_s=300.0)
        assert [r.tokens_out for r in reqs] == expected
        assert all(r.finish_reason == "length" for r in reqs)
        stats = router.stats()
        assert "dead" in stats["replicas"].values()
        assert _counter("resilience/faults_injected") - f0 == 1
        assert _counter("resilience/recoveries") - r0 == 1
    finally:
        fault_injector.disarm()
        router.close()


@pytest.mark.slow
def test_router_fleet_drill_three_replicas_acceptance(devices, monkeypatch):
    """The full fleet drill: 3 replicas, kill one mid-stream, streams
    gapless, router /healthz degraded during the failover replay and
    recovered after, doctor names the killed replica, ledger balanced."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry.doctor import analyze, render
    prompts = [[10 + i, 3, 2, 1] for i in range(6)]
    new = 8
    expected = _expected(devices, prompts, new)
    f0 = _counter("resilience/faults_injected")
    r0 = _counter("resilience/recoveries")
    n0 = len(telemetry.flight_recorder.snapshot().get("events", []))
    router = Router(_pool(devices, 3), hedge=False, http_port=0)
    port = router._http.port
    degraded_seen = False
    try:
        fault_injector.arm("serving_step:4:replica_kill:router",
                           _env=False)
        reqs = [router.submit(p, max_new_tokens=new) for p in prompts]
        t0 = time.monotonic()
        while router.poll():
            if not degraded_seen and _counter("router/failovers") and \
                    router._pending_recovery:
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz", timeout=5)
                assert ei.value.code == 503
                degraded_seen = True
            assert time.monotonic() - t0 < 300.0
            time.sleep(0.001)
        assert degraded_seen, "failover window never observed degraded"
        assert [r.tokens_out for r in reqs] == expected
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5) as resp:
            assert resp.status == 200
        assert _counter("resilience/faults_injected") - f0 == 1
        assert _counter("resilience/recoveries") - r0 == 1
        assert router.stats()["last_recovery_s"] > 0
        events = telemetry.flight_recorder.snapshot().get(
            "events", [])[n0:]
        killed = next(e["replica"] for e in events
                      if e["kind"] == "router_replica_kill")
        report = analyze([{"meta": {"hostname": "h0"}, "steps": [],
                           "events": events}], [])
        assert report["resilience"]["unrecovered"] == 0
        assert f"replica={killed}" in render(report)
    finally:
        fault_injector.disarm()
        router.close()


def test_local_pool_places_one_replica_per_device(devices):
    """``_build_local_pool`` puts replica i's params AND KV arena on local
    device i % n_devices (one process can serve one replica per chip); a
    pool larger than the device count wraps around."""
    from deepspeed_tpu.serving.router import _build_local_pool
    n_dev = len(jax.local_devices())
    pool = _build_local_pool(n_dev + 1, "tiny", http_ports=False)
    try:
        for i, rep in enumerate(pool):
            eng = rep.frontend.engine
            want = {jax.local_devices()[i % n_dev]}
            got = {d for leaf in jax.tree.leaves((eng.params, eng.arena))
                   for d in leaf.devices()}
            assert got == want, (i, got, want)
    finally:
        for rep in pool:
            rep.close()
