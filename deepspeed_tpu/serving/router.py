"""Fault-tolerant multi-replica serving router (``dstpu-router``).

Scales the single-replica :class:`~deepspeed_tpu.serving.frontend.
ServingFrontend` to a fleet: the router spreads streams over N replicas
with prefix-affinity routing (shared-prefix traffic lands where the
radix cache is warm, via rendezvous hashing over the prompt's leading
tokens, spilling to the least-loaded replica under imbalance), tracks
per-replica health with a circuit breaker (closed → open on consecutive
in-band failures or sustained ``/healthz`` 503, half-open probes with
capped exponential backoff before readmission), and defends the client
stream against every replica failure mode:

- **failover**: on replica death or breaker-open mid-stream, the
  request moves to a healthy replica with its already-streamed tokens
  folded into the prompt (the PR 8 requeue fold, one tier up) — the new
  replica re-prefills exactly the decode state the client saw, so the
  delivered token sequence is gapless and duplicate-free;
- **hedged dispatch**: a request queued too long (no first token after
  a p95-derived delay) races a second replica; the first token decides
  the winner and the loser is cancelled;
- **graceful draining**: ``drain(name)`` stops new admissions, lets
  in-flight decodes finish on the replica (optionally bounded by a
  deadline that fails the stragglers over), then removes it without
  dropping a stream — streams it had to cut finish with the honest
  reason ``"drained"``, never a stall error;
- **disaggregated prefill/decode pools**: replicas tagged
  ``pool="prefill"`` / ``pool="decode"`` split the fleet by roofline
  regime (compute-bound ragged prefill vs bandwidth-bound decode). A
  request prefills on the prefill pool for exactly one token, then the
  router ships the prefill replica's radix-cached KV pages to a decode
  replica (:mod:`deepspeed_tpu.serving.handoff`) and the decode leg
  aliases them; a torn or stalled bundle (``handoff_torn`` /
  ``handoff_stall`` faults at the ``handoff`` site) falls back to
  decode-side re-prefill with zero token loss.

T3's principle — host scheduling off the device critical path — holds
at fleet scope: each replica pumps its own frontend on its own thread
(its device never waits on the router), while placement, health, retry
and hedging decisions all happen in :meth:`Router.poll` on the host.

The whole tier is chaos-drillable: ``dstpu-chaos`` plans with
``replica_kill`` / ``replica_slow`` entries at the ``router`` site
kill or degrade a replica mid-drill, and the router publishes
``router/*`` metrics (per-replica state, failovers, hedges won/lost,
breaker transitions) that ``dstpu-top`` and ``dstpu-doctor`` render,
closing the faults==recoveries ledger at fleet scope. See
docs/serving.md "Router, failover & draining".
"""

import enum
import hashlib
import itertools
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from deepspeed_tpu import telemetry
from deepspeed_tpu.resilience.faults import fault_injector, record_recovery
from deepspeed_tpu.serving.queue import AdmissionError
from deepspeed_tpu.serving.request import Request, RequestState
from deepspeed_tpu.telemetry.registry import Histogram
from deepspeed_tpu.telemetry.registry import registry as _registry
from deepspeed_tpu.utils.logging import logger

#: numeric replica-state encoding for the ``router/replica/{name}/state``
#: gauges (dstpu-top maps them back to names)
STATE_CODES = {"healthy": 0.0, "half-open": 1.0, "open": 2.0,
               "draining": 3.0, "dead": 4.0}


class BreakerState(enum.Enum):
    CLOSED = "closed"
    OPEN = "open"
    HALF_OPEN = "half_open"


class CircuitBreaker:
    """Per-replica health automaton fed by in-band observations
    (dispatch errors, stream stalls) and out-of-band ``/healthz`` polls.

    CLOSED → OPEN after ``failure_threshold`` consecutive failures;
    OPEN → HALF_OPEN after a backoff that doubles per consecutive open
    period (capped at ``backoff_max_s``) — HALF_OPEN admits exactly one
    probe; a probe success closes the breaker (backoff resets), a probe
    failure re-opens it. The clock is injectable so tests (and the
    router, which shares one monotonic clock across breakers) never
    depend on the wall clock.
    """

    def __init__(self, failure_threshold: int = 3, backoff_s: float = 1.0,
                 backoff_max_s: float = 30.0, clock=time.monotonic,
                 on_transition=None):
        self.failure_threshold = max(1, int(failure_threshold))
        self.backoff_s = float(backoff_s)
        self.backoff_max_s = float(backoff_max_s)
        self._clock = clock
        self._on_transition = on_transition
        self.state = BreakerState.CLOSED
        self.failures = 0            # consecutive, reset on success
        self.last_reason = ""
        self._opened_at: Optional[float] = None
        self._backoff = self.backoff_s

    def _to(self, new: BreakerState, reason: str = "") -> None:
        if new is self.state:
            return
        old, self.state = self.state, new
        self.last_reason = reason
        if self._on_transition is not None:
            self._on_transition(old, new, reason)

    def record_failure(self, reason: str = "") -> bool:
        """One observed failure; returns True when this observation
        opened (or re-opened) the breaker."""
        self.failures += 1
        if self.state is BreakerState.HALF_OPEN:
            # failed probe: back off harder before the next one
            self._backoff = min(self._backoff * 2.0, self.backoff_max_s)
            self._opened_at = self._clock()
            self._to(BreakerState.OPEN, reason or "probe failed")
            return True
        if self.state is BreakerState.CLOSED and \
                self.failures >= self.failure_threshold:
            self._opened_at = self._clock()
            self._backoff = self.backoff_s
            self._to(BreakerState.OPEN, reason)
            return True
        return False

    def force_open(self, reason: str = "") -> None:
        """Immediate open (replica died — no vote needed)."""
        self.failures = max(self.failures, self.failure_threshold)
        if self.state is not BreakerState.OPEN:
            self._opened_at = self._clock()
            if self.state is BreakerState.HALF_OPEN:
                self._backoff = min(self._backoff * 2.0, self.backoff_max_s)
            else:
                self._backoff = self.backoff_s
            self._to(BreakerState.OPEN, reason)

    def record_success(self) -> None:
        self.failures = 0
        if self.state is BreakerState.HALF_OPEN:
            self._backoff = self.backoff_s
            self._to(BreakerState.CLOSED, "probe succeeded")

    def allow_probe(self) -> bool:
        """OPEN → HALF_OPEN once the backoff elapsed; returns True
        exactly once per backoff period (the single probe admission)."""
        if self.state is not BreakerState.OPEN:
            return False
        if self._opened_at is None or \
                self._clock() - self._opened_at < self._backoff:
            return False
        self._to(BreakerState.HALF_OPEN,
                 f"probing after {self._backoff:.2f}s backoff")
        return True


class LocalReplica:
    """One in-process replica: a :class:`ServingFrontend` pumped on its
    own daemon thread (the per-replica analogue of a replica process —
    its device loop never blocks on the router, and a dead replica is a
    dead thread). All frontend access goes through ``lock``: the pump
    thread holds it across ``step()``, the router across ``submit``.

    ``kill()`` has dead-process semantics: the pump stops and the
    frontend is NOT flushed or drained — whatever tokens it produced but
    had not delivered are lost, exactly like a SIGKILLed replica. The
    router's failover replay is what makes the client stream gapless
    anyway.

    ``pool`` assigns the replica to the disaggregated tier: ``"prefill"``
    replicas run prompt prefills (one token out, pages handed off),
    ``"decode"`` replicas run the decode legs, ``"any"`` (the default)
    serves both — a pool of all-``"any"`` replicas is the classic
    homogeneous fleet and nothing about routing changes.
    """

    def __init__(self, name: str, frontend, idle_sleep_s: float = 0.002,
                 pool: str = "any"):
        if pool not in ("any", "prefill", "decode"):
            raise ValueError(f"bad replica pool {pool!r} "
                             f"(want any/prefill/decode)")
        self.name = name
        self.pool = pool
        self.frontend = frontend
        self.lock = threading.RLock()
        self.idle_sleep_s = idle_sleep_s
        #: injected degradation (``replica_slow``): every pump pays this
        self.slow_s = 0.0
        self.killed = False
        self.error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._pump_loop, daemon=True,
            name=f"dstpu-replica-{name}")
        self._started = False

    def start(self) -> "LocalReplica":
        if not self._started:
            self._started = True
            self._thread.start()
        return self

    def _pump_loop(self) -> None:
        while not self._stop.is_set():
            if self.slow_s > 0.0:
                time.sleep(self.slow_s)
            try:
                with self.lock:
                    progressed = self.frontend.step()
            except BaseException as e:               # noqa: BLE001
                # the frontend's own failure domain absorbs engine
                # faults; anything that escapes is replica-fatal
                self.error = e
                return
            if not progressed:
                time.sleep(self.idle_sleep_s)

    @property
    def alive(self) -> bool:
        return (self._started and not self.killed and self.error is None
                and self._thread.is_alive())

    def submit(self, prompt: List[int], **kw) -> Request:
        if not self.alive:
            raise AdmissionError("replica_dead",
                                 f"replica {self.name} is not alive")
        with self.lock:
            return self.frontend.submit(prompt, **kw)

    def cancel(self, req: Request) -> None:
        req.cancel()                     # flag only — pump honors it

    def load(self) -> int:
        fe = self.frontend
        return len(fe._running) + len(fe.queue)

    def http_target(self) -> Optional[str]:
        http = getattr(self.frontend, "_http", None)
        return None if http is None else f"127.0.0.1:{http.port}"

    def kill(self) -> None:
        self.killed = True
        self._stop.set()

    def close(self) -> None:
        """Graceful teardown (drain-remove or router shutdown): stop the
        pump, terminate any still-attached streams with reason
        ``"drained"`` (their KV released — a client blocked in
        ``frontend.stream()`` sees the request finish instead of a
        stall-timeout RuntimeError), release the cached prefix pages,
        close the endpoint."""
        self._stop.set()
        if self._started and self._thread.is_alive():
            self._thread.join(timeout=5.0)
        fe = self.frontend
        try:
            if hasattr(fe, "terminate_inflight"):
                fe.terminate_inflight("drained")
            else:
                for uid in list(fe._running):
                    try:
                        fe.engine.flush(uid)
                    except Exception:                # noqa: BLE001
                        pass
                fe._running.clear()
            if fe.cache is not None and fe.cache.pages_cached:
                fe.cache.evict(fe.cache.pages_cached)
            fe.close()
        except Exception:                            # noqa: BLE001
            pass


_rr_uid = itertools.count()


@dataclass
class _Assignment:
    replica: LocalReplica
    inner: Request
    dispatch_ts: float
    drained: int = 0                 # inner tokens already delivered
    #: which disaggregated leg this is: "mono" (homogeneous fleet),
    #: "prefill" (one-token leg whose pages hand off) or "decode"
    role: str = "mono"
    #: this leg's TraceContext (child of the request's root) — the
    #: identity the replica's frontend stamps into its spans; None when
    #: request tracing is disabled
    ctx: Optional[object] = None


@dataclass
class RouterRequest:
    """Client-visible request: ``tokens_out`` is exactly what the client
    has been streamed, across any number of failovers/hedges underneath.
    """
    prompt: List[int]
    max_new_tokens: int = 16
    priority: int = 0
    deadline: Optional[float] = None
    eos_token_id: Optional[int] = None

    uid: int = field(default_factory=lambda: next(_rr_uid))
    tokens_out: List[int] = field(default_factory=list)
    state: RequestState = RequestState.QUEUED
    finish_reason: Optional[str] = None
    #: times this request was re-dispatched after a replica failure
    failovers: int = 0
    hedged: bool = False
    #: disaggregated lifecycle: "mono" on a homogeneous fleet, else
    #: "prefill" until the prefill leg finished and its pages handed
    #: off, then "decode"
    phase: str = "mono"
    #: prompt tokens the decode replica served from handed-off pages
    handoff_tokens: int = 0
    #: distributed-trace root context (:class:`~deepspeed_tpu.telemetry.
    #: reqtrace.TraceContext`), minted at :meth:`Router.submit`; every
    #: dispatch leg forks a child from it. The router owns the tail
    #: decision (``reqtrace.finish``) for router-entered requests.
    trace: Optional[object] = field(default=None, repr=False)

    submit_ts: Optional[float] = None
    first_token_ts: Optional[float] = None
    finish_ts: Optional[float] = None
    last_progress_ts: Optional[float] = None

    primary: Optional[_Assignment] = field(default=None, repr=False)
    hedge: Optional[_Assignment] = field(default=None, repr=False)
    #: set once the first token decides the primary-vs-hedge race
    winner: Optional[_Assignment] = field(default=None, repr=False)

    @property
    def done(self) -> bool:
        return self.state in (RequestState.FINISHED, RequestState.CANCELLED,
                              RequestState.SHED, RequestState.REJECTED)


class Router:
    """Health-driven request router over N serving replicas.

    Single coordinator thread by design (the caller drives
    :meth:`poll`, usually via :meth:`stream` / :meth:`run_until_idle`);
    replicas pump themselves. Construction accepts ``LocalReplica``
    objects or ``(name, frontend)`` pairs; kwargs override the
    ``router.*`` config block, which overrides the defaults.
    """

    def __init__(self, replicas: Sequence, *,
                 affinity_tokens: Optional[int] = None,
                 hedge: Optional[bool] = None,
                 hedge_delay_s: Optional[float] = None,
                 retry_budget: Optional[int] = None,
                 breaker_failures: Optional[int] = None,
                 breaker_backoff_s: Optional[float] = None,
                 breaker_backoff_max_s: Optional[float] = None,
                 stall_timeout_s: Optional[float] = None,
                 spill_factor: Optional[float] = None,
                 chaos_slow_s: Optional[float] = None,
                 health_every: Optional[int] = None,
                 http_port: Optional[int] = None,
                 clock=time.monotonic, config=None):
        rcfg = None
        if config is not None:
            rcfg = (config.get("router") if isinstance(config, dict)
                    else getattr(config, "router", None))
        rget = ((rcfg or {}).get if isinstance(rcfg, dict)
                else lambda k, d=None: getattr(rcfg, k, d))

        def knob(val, key, default):
            if val is not None:
                return val
            if rcfg is not None:
                got = rget(key, None)
                if got is not None:
                    return got
            return default

        self.affinity_tokens = int(knob(affinity_tokens,
                                        "affinity_tokens", 64))
        self.hedge = bool(knob(hedge, "hedge", True))
        self.hedge_delay_s = knob(hedge_delay_s, "hedge_delay_s", None)
        self.retry_budget = int(knob(retry_budget, "retry_budget", 2))
        self.stall_timeout_s = float(knob(stall_timeout_s,
                                          "stall_timeout_s", 30.0))
        self.spill_factor = float(knob(spill_factor, "spill_factor", 2.0))
        self.chaos_slow_s = float(knob(chaos_slow_s, "chaos_slow_s", 0.25))
        self.health_every = int(knob(health_every, "health_every", 50))
        self.clock = clock
        self.replicas: List[LocalReplica] = []
        for i, r in enumerate(replicas):
            if not isinstance(r, LocalReplica):
                name, fe = (r if isinstance(r, tuple) else (f"r{i}", r))
                r = LocalReplica(name, fe)
            self.replicas.append(r.start())
        if not self.replicas:
            raise ValueError("router needs at least one replica")
        self.breakers: Dict[str, CircuitBreaker] = {}
        bf = int(knob(breaker_failures, "breaker_failures", 3))
        bb = float(knob(breaker_backoff_s, "breaker_backoff_s", 1.0))
        bm = float(knob(breaker_backoff_max_s, "breaker_backoff_max_s",
                        30.0))
        #: breaker knobs, kept so autoscaler-spawned replicas
        #: (:meth:`add_replica`) get identical health automata
        self._breaker_kw = dict(failure_threshold=bf, backoff_s=bb,
                                backoff_max_s=bm)
        for r in self.replicas:
            self.breakers[r.name] = CircuitBreaker(
                clock=self.clock, **self._breaker_kw,
                on_transition=self._breaker_transition(r.name))
        self._reqs: Dict[int, RouterRequest] = {}
        self._draining: set = set()
        #: forced-drain deadlines: replica → clock time after which its
        #: remaining streams are failed over (terminal reason "drained"
        #: when they cannot be replayed) and the replica is removed
        self._drain_deadline: Dict[str, float] = {}
        self._polls = 0
        #: chaos-kill recovery ledger: replica → {"t0", "uids"} — closed
        #: (record_recovery) when every failed-over stream completed
        self._pending_recovery: Dict[str, Dict[str, Any]] = {}
        #: chaos-slow ledger: replica → recovery not yet recorded
        self._pending_slow: Dict[str, float] = {}
        #: handoff-fault ledger: req uid → fallback re-prefill in flight;
        #: the recovery is recorded when that stream completes
        self._pending_handoff: Dict[int, Dict[str, Any]] = {}
        #: per-replica tokens delivered to clients (bench attribution)
        self.replica_tokens: Dict[str, int] = {
            r.name: 0 for r in self.replicas}
        self.ttft = Histogram()
        _registry.register("router/ttft_seconds", self.ttft,
                           help="router-observed time to first token (s)",
                           replace=True)
        self._http = None
        if http_port is not None:
            from deepspeed_tpu.telemetry.endpoint import MetricsServer
            self._http = MetricsServer(http_port)
        self._publish_states()

    # -- plumbing -----------------------------------------------------------

    def _breaker_transition(self, name: str):
        def cb(old: BreakerState, new: BreakerState, reason: str) -> None:
            _registry.counter(
                "router/breaker_transitions",
                help="circuit-breaker state changes across replicas").inc()
            telemetry.flight_recorder.record_event(
                "router_breaker", replica=name, from_state=old.value,
                to_state=new.value, reason=reason)
            telemetry.tracer.instant("router/breaker", replica=name,
                                     to_state=new.value)
            logger.warning("router: replica %s breaker %s -> %s (%s)",
                           name, old.value, new.value, reason)
        return cb

    def replica_state(self, r: LocalReplica) -> str:
        if not r.alive:
            return "dead"
        if r.name in self._draining:
            return "draining"
        st = self.breakers[r.name].state
        if st is BreakerState.OPEN:
            return "open"
        if st is BreakerState.HALF_OPEN:
            return "half-open"
        return "healthy"

    def _publish_states(self) -> None:
        _registry.gauge("router/replicas",
                        help="replicas currently in the pool").set(
            float(len(self.replicas)))
        for r in self.replicas:
            _registry.gauge(
                f"router/replica/{r.name}/state",
                help="0 healthy, 1 half-open, 2 open, 3 draining, 4 dead"
            ).set(STATE_CODES[self.replica_state(r)])

    def _update_degraded(self) -> None:
        """Router /healthz is degraded (503) while failover replays are
        still draining — the tier is alive and recovering, but an
        upstream balancer should prefer another router cell."""
        draining = bool(self._pending_recovery) or any(
            req.failovers and not req.done for req in self._reqs.values())
        _registry.gauge(
            "router/degraded",
            help="1 while failover replays drain").set(
            1.0 if draining else 0.0)
        if self._http is not None:
            self._http.set_degraded(draining, source="router",
                                    reason="failover replays draining")

    # -- pools --------------------------------------------------------------

    @property
    def disaggregated(self) -> bool:
        """True when the fleet has BOTH a prefill and a decode pool —
        requests then run as a prefill leg + KV-page handoff + decode
        leg. With either pool absent the router behaves exactly as the
        homogeneous PR-10 fleet."""
        pools = {r.pool for r in self.replicas if r.alive}
        return "prefill" in pools and "decode" in pools

    def pool_members(self, pool: str,
                     live_only: bool = True) -> List[LocalReplica]:
        """Replicas serving ``pool`` (``"any"`` replicas serve both)."""
        return [r for r in self.replicas
                if r.pool in ("any", pool)
                and (not live_only or
                     (r.alive and r.name not in self._draining))]

    def add_replica(self, replica) -> LocalReplica:
        """Grow the fleet at runtime (the autoscaler's scale-up
        effector). Accepts a :class:`LocalReplica` or a ``(name,
        frontend)`` pair; the new replica gets a breaker with the same
        knobs as its peers and starts taking traffic on the next
        placement decision."""
        if not isinstance(replica, LocalReplica):
            name, fe = replica
            replica = LocalReplica(name, fe)
        if replica.name in {r.name for r in self.replicas}:
            raise ValueError(f"replica name {replica.name!r} already "
                             f"in the pool")
        self.replicas.append(replica.start())
        self.breakers[replica.name] = CircuitBreaker(
            clock=self.clock, **self._breaker_kw,
            on_transition=self._breaker_transition(replica.name))
        self.replica_tokens.setdefault(replica.name, 0)
        telemetry.flight_recorder.record_event(
            "router_replica_added", replica=replica.name,
            pool=replica.pool)
        self._publish_states()
        return replica

    # -- placement ----------------------------------------------------------

    def _affinity_key(self, prompt: List[int]) -> bytes:
        head = tuple(prompt[:max(1, self.affinity_tokens)])
        return repr(head).encode()

    def _score(self, key: bytes, name: str) -> int:
        return int.from_bytes(
            hashlib.sha1(key + b"|" + name.encode()).digest()[:8], "big")

    def _choose(self, prompt: List[int],
                exclude: Tuple[str, ...] = (),
                pool: Optional[str] = None) -> LocalReplica:
        """Prefix-affinity placement: rendezvous (highest-random-weight)
        hash of the prompt's leading tokens over the healthy replicas —
        shared-prefix traffic keeps landing on the same replica, and a
        replica's death remaps only its own keys. Spills to the
        least-loaded replica when the affinity target is more than
        ``spill_factor``x busier (a warm cache never justifies a hot
        queue). With no CLOSED-breaker replica available, an OPEN
        replica whose backoff elapsed is admitted as the half-open
        probe; otherwise admission fails loudly. ``pool`` restricts
        candidates to one disaggregated pool (``"any"`` replicas always
        qualify)."""
        cands = (self.replicas if pool is None
                 else [r for r in self.replicas
                       if r.pool in ("any", pool)])
        healthy = [r for r in cands
                   if r.alive and r.name not in self._draining
                   and r.name not in exclude
                   and self.breakers[r.name].state is BreakerState.CLOSED]
        if not healthy:
            for r in cands:
                if (r.alive and r.name not in self._draining
                        and r.name not in exclude
                        and self.breakers[r.name].allow_probe()):
                    return r
            raise AdmissionError(
                "no_healthy_replica",
                (f"pool {pool!r}: " if pool is not None else "") +
                f"{len(cands)} replicas, none admitting "
                f"(states: " + ", ".join(
                    f"{r.name}={self.replica_state(r)}"
                    for r in cands) + ")")
        key = self._affinity_key(prompt)
        chosen = max(healthy, key=lambda r: self._score(key, r.name))
        loads = {r.name: r.load() for r in healthy}
        least = min(healthy, key=lambda r: loads[r.name])
        if loads[chosen.name] > self.spill_factor * (loads[least.name] + 1):
            _registry.counter(
                "router/affinity_spills",
                help="affinity choices overridden by load imbalance").inc()
            return least
        return chosen

    # -- admission ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 16, priority: int = 0,
               deadline: Optional[float] = None,
               timeout: Optional[float] = None,
               eos_token_id: Optional[int] = None) -> RouterRequest:
        """Admit one stream; raises :class:`AdmissionError` (reason
        ``no_healthy_replica`` or the chosen replica's own reason) when
        the fleet cannot take it."""
        now = self.clock()
        req = RouterRequest(
            prompt=[int(t) for t in prompt],
            max_new_tokens=int(max_new_tokens), priority=priority,
            deadline=(now + timeout if timeout is not None else deadline),
            eos_token_id=eos_token_id)
        req.submit_ts = now
        req.phase = "prefill" if self.disaggregated else "mono"
        req.trace = telemetry.reqtrace.mint(entry="router", uid=req.uid)
        try:
            self._dispatch(req, exclude=())
        except AdmissionError as e:
            # rejected before any leg ran — the trace still records WHY
            # (breaker states are in the per-attempt router/rejected
            # instants) and finishes honestly instead of leaking
            rt = telemetry.reqtrace
            rt.flag(req.trace, "rejected")
            rt.instant("router/rejected", req.trace, tid=req.uid,
                       reason=e.reason, terminal=1)
            rt.finish(req.trace, reason=e.reason)
            raise
        self._reqs[req.uid] = req
        _registry.counter("router/requests",
                          help="streams admitted by the router").inc()
        return req

    def _dispatch(self, req: RouterRequest,
                  exclude: Tuple[str, ...] = (),
                  hedge: bool = False,
                  prefer: Optional[LocalReplica] = None) -> _Assignment:
        """(Re-)dispatch ``req`` to a replica. The already-streamed
        tokens fold into the prompt so the replica re-prefills exactly
        the client-visible decode state — gapless, duplicate-free.

        On a disaggregated fleet the request's ``phase`` picks the pool
        and the leg: a prefill leg runs for exactly ONE token (the first
        token is the proof the prompt's KV is complete), then
        :meth:`_promote_to_decode` hands the pages off; a decode leg
        runs the remaining budget. ``prefer`` pins the first attempt to
        one replica (the handoff path adopts pages into a replica
        BEFORE dispatching to it, so placement must not move)."""
        remaining = req.max_new_tokens - len(req.tokens_out)
        folded = req.prompt + req.tokens_out
        role, pool, inner_max = "mono", None, remaining
        if req.phase == "prefill":
            role, pool, inner_max = "prefill", "prefill", 1
        elif req.phase == "decode":
            role, pool = "decode", "decode"
        last_err: Optional[Exception] = None
        tried: Tuple[str, ...] = exclude
        for _ in range(len(self.replicas) + 1):
            if prefer is not None:
                replica, prefer = prefer, None
            else:
                replica = self._choose(folded, exclude=tried, pool=pool)
            kw: Dict[str, Any] = dict(
                max_new_tokens=inner_max, priority=req.priority,
                deadline=req.deadline, eos_token_id=req.eos_token_id)
            if req.trace is not None:
                # fork this leg's trace context: the replica's frontend
                # stamps its spans with it, so the fleet-wide trace has
                # one child span-tree per dispatch attempt. Omitted
                # entirely when tracing is off (plain frontends and test
                # stubs need not know the kwarg exists).
                leg: Dict[str, Any] = {"replica": replica.name,
                                       "role": role}
                if hedge:
                    leg["hedge"] = 1
                if req.failovers:
                    leg["replay"] = req.failovers
                kw["ctx"] = req.trace.child(**leg)
            try:
                inner = replica.submit(folded, **kw)
            except AdmissionError as e:
                last_err = e
                tried = tried + (replica.name,)
                telemetry.reqtrace.instant(
                    "router/rejected", req.trace, tid=req.uid,
                    replica=replica.name, reason=e.reason)
                self.breakers[replica.name].record_failure(
                    f"submit rejected: {e.reason}")
                continue
            assign = _Assignment(replica=replica, inner=inner,
                                 dispatch_ts=self.clock(), role=role,
                                 ctx=kw.get("ctx"))
            if hedge:
                req.hedge = assign
            else:
                req.primary = assign
            req.state = RequestState.RUNNING
            return assign
        req.state = RequestState.REJECTED
        req.finish_reason = "no_healthy_replica"
        raise last_err if last_err is not None else AdmissionError(
            "no_healthy_replica", "no replica accepted the request")

    # -- chaos --------------------------------------------------------------

    def _chaos_victim(self) -> Optional[LocalReplica]:
        named = os.environ.get("DSTPU_CHAOS_REPLICA")
        if named:
            # a NAMED victim is killable even mid-drain — the
            # scale-down chaos drill targets exactly that window
            for r in self.replicas:
                if r.name == named and r.alive:
                    return r
        cands = [r for r in self.replicas
                 if r.alive and r.name not in self._draining]
        if not cands:
            return None
        # deterministic: the busiest replica (ties → pool order) — the
        # worst case for stream integrity is the drill the ledger wants
        return max(cands, key=lambda r: (r.load(), ))

    def _apply_chaos(self, kind: str) -> None:
        victim = self._chaos_victim()
        if victim is None:
            logger.warning("router CHAOS: %s with no live replica to "
                           "target — ignored", kind)
            return
        telemetry.flight_recorder.record_event(
            f"router_{kind}", replica=victim.name, poll=self._polls)
        telemetry.tracer.instant(f"router/{kind}", replica=victim.name)
        if kind == "replica_kill":
            logger.warning("router CHAOS: killing replica %s "
                           "(%d streams in flight)", victim.name,
                           self._assigned_count(victim))
            victim.kill()
            self._pending_recovery.setdefault(
                victim.name, {"t0": self.clock(), "uids": set()})
        elif kind == "replica_slow":
            logger.warning("router CHAOS: degrading replica %s "
                           "(+%.0f ms per pump)", victim.name,
                           self.chaos_slow_s * 1e3)
            victim.slow_s = self.chaos_slow_s
            self._pending_slow[victim.name] = self.clock()

    def _assigned_count(self, replica: LocalReplica) -> int:
        n = 0
        for req in self._reqs.values():
            for a in (req.primary, req.hedge):
                if a is not None and a.replica is replica and not req.done:
                    n += 1
        return n

    # -- failure handling ---------------------------------------------------

    def _fail_assignment(self, req: RouterRequest, assign: _Assignment,
                         reason: str) -> None:
        """The replica under ``assign`` failed this request. Hedge legs
        are simply dropped (the primary still runs); a failed primary
        promotes a live hedge, else re-dispatches under the retry
        budget."""
        from_name = assign.replica.name
        if req.hedge is assign:
            req.hedge = None
            if req.winner is assign:
                req.winner = None
            return
        req.primary = None
        if req.winner is assign:
            req.winner = None
        if from_name in self._pending_recovery and not req.done:
            self._pending_recovery[from_name]["uids"].add(req.uid)
        if req.hedge is not None and req.hedge.replica.alive and \
                self.breakers[req.hedge.replica.name].state \
                is BreakerState.CLOSED:
            # the race already has a healthy leg — promote it
            req.primary, req.hedge = req.hedge, None
            _registry.counter(
                "router/hedges_won",
                help="hedge legs that delivered the stream").inc()
            if req.primary.ctx is not None:
                req.primary.ctx.baggage["winner"] = 1
                telemetry.reqtrace.instant(
                    "router/hedge_won", req.primary.ctx, tid=req.uid,
                    replica=req.primary.replica.name, winner=1)
            telemetry.reqtrace.flag(req.trace, "failover")
            return
        req.failovers += 1
        # a stream cut because its replica was intentionally drained is
        # an operator action, not an error: past the retry budget it
        # finishes with the honest reason "drained", never a stall/error
        drained = from_name in self._draining or "drain" in reason
        if req.failovers > self.retry_budget:
            if drained:
                self._finish(req, "drained")
                _registry.counter(
                    "router/drained_streams",
                    help="streams finished because their replica was "
                         "drained past the retry budget").inc()
            else:
                self._finish(req, "error")
                _registry.counter(
                    "router/errors",
                    help="streams failed after the retry budget").inc()
            return
        try:
            self._dispatch(req, exclude=(from_name,))
        except AdmissionError:
            if drained:
                self._finish(req, "drained")
                _registry.counter("router/drained_streams").inc()
            else:
                self._finish(req, "error")
                _registry.counter("router/errors").inc()
            return
        _registry.counter(
            "router/failovers",
            help="mid-stream re-dispatches after replica failure").inc()
        telemetry.reqtrace.flag(req.trace, "failover")
        telemetry.reqtrace.instant(
            "router/failover", req.trace, tid=req.uid,
            replica=from_name, to=req.primary.replica.name,
            reason=reason, replay=req.failovers,
            replayed_tokens=len(req.tokens_out))
        telemetry.flight_recorder.record_event(
            "router_failover", replica=from_name,
            to=req.primary.replica.name, uid=req.uid, reason=reason,
            replayed_tokens=len(req.tokens_out))

    def _on_replica_down(self, replica: LocalReplica, reason: str) -> None:
        self.breakers[replica.name].force_open(reason)
        for req in list(self._reqs.values()):
            if req.done:
                continue
            for a in (req.primary, req.hedge):
                if a is not None and a.replica is replica:
                    self._fail_assignment(req, a, reason)

    # -- health -------------------------------------------------------------

    def check_health(self) -> None:
        """Out-of-band sweep: ``/healthz`` of every replica exposing an
        endpoint feeds its breaker (sustained 503 opens it; an ok
        answer is the half-open probe success that readmits it).
        Replicas without endpoints are probed in-band only: a half-open
        breaker on a live replica closes here (its probe is the next
        request routed to it)."""
        from deepspeed_tpu.telemetry.fleet import HostSample, poll_host
        for r in self.replicas:
            if not r.alive:
                continue
            br = self.breakers[r.name]
            target = r.http_target()
            if target is None:
                if br.state is BreakerState.HALF_OPEN:
                    br.record_success()
                continue
            sample = poll_host(HostSample(target), timeout=1.0,
                               clock=self.clock)
            if sample.ok and sample.status == "ok":
                br.record_success()
            else:
                if br.record_failure(f"healthz {sample.status}"):
                    self._on_replica_down(r, f"healthz {sample.status}")

    # -- the coordinator loop -----------------------------------------------

    def poll(self) -> bool:
        """One coordinator iteration: chaos hook → health sweep → token
        fan-in (winner decision, failover, hedging) → drain/recovery
        bookkeeping → state gauges. Returns True while streams are in
        flight."""
        now = self.clock()
        self._polls += 1
        for kind in fault_injector.fire("router", serving_step=self._polls):
            if kind in ("replica_kill", "replica_slow"):
                self._apply_chaos(kind)
        if self.health_every and self._polls % self.health_every == 0:
            self.check_health()
        for r in self.replicas:
            if not r.alive and (self._assigned_count(r) or
                                self.breakers[r.name].state
                                is not BreakerState.OPEN):
                why = ("killed" if r.killed else
                       f"pump died: {type(r.error).__name__}: {r.error}"
                       if r.error else "pump thread exited")
                self._on_replica_down(r, why)
        for req in list(self._reqs.values()):
            if not req.done:
                self._service(req, now)
            if req.done:
                self._reqs.pop(req.uid, None)
        self._sweep_draining()
        self._sweep_recoveries(now)
        self._publish_states()
        self._update_degraded()
        return bool(self._reqs)

    def _service(self, req: RouterRequest, now: float) -> None:
        # 1. decide the race (first token wins; primary on a tie)
        if req.winner is None:
            for a in (req.primary, req.hedge):
                if a is not None and a.replica.alive and a.inner.tokens_out:
                    req.winner = a
                    break
            if req.winner is not None and req.hedge is not None \
                    and req.primary is not None:
                loser = (req.hedge if req.winner is req.primary
                         else req.primary)
                won = req.winner is req.hedge
                _registry.counter(
                    "router/hedges_won" if won else "router/hedges_lost",
                    help="hedge race outcomes").inc()
                # tag both racing legs: winner/loser markers, plus
                # ``winner`` baggage so spans the legs emit from here on
                # carry it (critical_path drops winner==0 spans — the
                # loser ran off the critical path)
                if req.winner.ctx is not None:
                    req.winner.ctx.baggage["winner"] = 1
                    telemetry.reqtrace.instant(
                        "router/hedge_won", req.winner.ctx, tid=req.uid,
                        replica=req.winner.replica.name, winner=1)
                if loser.ctx is not None:
                    loser.ctx.baggage["winner"] = 0
                    telemetry.reqtrace.instant(
                        "router/hedge_lost", loser.ctx, tid=req.uid,
                        replica=loser.replica.name, winner=0)
                loser.replica.cancel(loser.inner)
                if won:
                    req.primary, req.hedge = req.hedge, None
                else:
                    req.hedge = None
                req.winner = req.primary
        active = req.winner or req.primary
        # 2. drain winner tokens to the client view
        if active is not None and active.replica.alive:
            self._drain_tokens(req, active, now)
        # 3. replica health of the active leg
        if active is not None:
            br = self.breakers[active.replica.name]
            if not active.replica.alive or \
                    br.state is BreakerState.OPEN:
                self._fail_assignment(
                    req, active,
                    "replica dead" if not active.replica.alive
                    else f"breaker open: {br.last_reason}")
                return
        # 4. inner terminal states propagate (or trigger failover)
        if active is not None and active.inner.done:
            inner = active.inner
            if inner.finish_reason == "error":
                # the replica burned ITS retry budget under this stream
                if self.breakers[active.replica.name].record_failure(
                        "stream errored"):
                    self._on_replica_down(active.replica, "stream errored")
                else:
                    self._fail_assignment(req, active, "stream errored")
                return
            if inner.finish_reason == "drained":
                # the replica cut this leg because it is scaling down —
                # failover elsewhere, or finish honestly as "drained"
                self._fail_assignment(req, active, "replica drained")
                return
            if inner.state is RequestState.SHED:
                self._finish(req, inner.finish_reason or "deadline")
                _registry.counter(
                    "router/shed",
                    help="streams shed past their deadline").inc()
                return
            if active.role == "prefill":
                # the prefill leg ran exactly one token — catch any
                # late-arriving token first, then either finish (eos /
                # budget done) or hand the KV pages to the decode pool
                self._drain_tokens(req, active, now)
                if inner.finish_reason != "eos" and \
                        len(req.tokens_out) < req.max_new_tokens:
                    self._promote_to_decode(req, active, now)
                    return
            self._finish(req, inner.finish_reason or "length")
            _registry.counter(
                "router/completed",
                help="streams finished successfully").inc()
            if self.breakers[active.replica.name].state \
                    is BreakerState.HALF_OPEN:
                self.breakers[active.replica.name].record_success()
            return
        # 5. stall detection: an assigned stream making no progress is
        # an in-band failure observation
        if active is not None:
            last = req.last_progress_ts or active.dispatch_ts
            if now - last > self.stall_timeout_s:
                req.last_progress_ts = now   # one observation per window
                if self.breakers[active.replica.name].record_failure(
                        f"no progress for {self.stall_timeout_s:.1f}s"):
                    self._on_replica_down(active.replica, "stalled")
                else:
                    self._fail_assignment(req, active, "stalled")
                return
        # 6. hedged dispatch for queued-too-long requests
        if (self.hedge and req.winner is None and req.hedge is None
                and req.primary is not None
                and not req.tokens_out
                and now - req.primary.dispatch_ts > self._hedge_delay()):
            try:
                self._dispatch(req, exclude=(req.primary.replica.name,),
                               hedge=True)
            except AdmissionError:
                return                       # nobody to race — keep waiting
            req.hedged = True
            _registry.counter(
                "router/hedges",
                help="hedge legs dispatched for slow first tokens").inc()
            telemetry.tracer.instant(
                "router/hedge", uid=req.uid,
                primary=req.primary.replica.name,
                hedge=req.hedge.replica.name)
            telemetry.reqtrace.flag(req.trace, "hedge")
            telemetry.reqtrace.instant(
                "router/hedge", req.trace, tid=req.uid,
                primary=req.primary.replica.name,
                hedge=req.hedge.replica.name)
            # the first hedge raced against a chaos-slowed replica IS
            # that fault's recovery: the mitigation engaged and the
            # tail request no longer waits on the degraded replica
            pname = req.primary.replica.name
            if pname in self._pending_slow:
                t0 = self._pending_slow.pop(pname)
                record_recovery("router_hedge", replica=pname,
                                uid=req.uid,
                                engaged_s=round(now - t0, 3))

    def _hedge_delay(self) -> float:
        if self.hedge_delay_s is not None:
            return float(self.hedge_delay_s)
        if self.ttft.count >= 20:
            return max(0.02, float(self.ttft.percentile(95)))
        return 0.25

    def _drain_tokens(self, req: RouterRequest, assign: _Assignment,
                      now: float) -> None:
        """Fold new tokens from ``assign`` into the client view (TTFT on
        the first, progress stamp, per-replica accounting)."""
        inner_toks = assign.inner.tokens_out
        if len(inner_toks) <= assign.drained:
            return
        new = inner_toks[assign.drained:]
        assign.drained = len(inner_toks)
        if req.first_token_ts is None:
            req.first_token_ts = now
            self.ttft.record(
                max(0.0, now - (req.submit_ts or now)),
                exemplar=(req.trace.trace_id
                          if req.trace is not None else None))
        req.tokens_out.extend(int(t) for t in new)
        req.last_progress_ts = now
        self.replica_tokens[assign.replica.name] = \
            self.replica_tokens.get(assign.replica.name, 0) + len(new)
        _registry.counter(
            "router/tokens_out",
            help="tokens delivered to clients").inc(len(new))

    # -- prefill → decode handoff -------------------------------------------

    def _promote_to_decode(self, req: RouterRequest, active: _Assignment,
                           now: float) -> None:
        """The prefill leg delivered its first token — move the request
        to the decode pool. The happy path ships the prefill replica's
        radix-cached KV pages (export → checksummed bundle → adopt into
        the decode arena BEFORE the decode leg dispatches, so its
        ``adopt_cached`` admission aliases them). The failure domain is
        handled here too: a torn (``handoff_torn``) or timed-out
        (``handoff_stall``) bundle adopts nothing and the decode replica
        re-prefills the folded prompt — recompute, never token loss —
        and the fallback is ledgered so faults == recoveries closes."""
        from deepspeed_tpu.serving.handoff import (adopt_bundle,
                                                   export_bundle,
                                                   verify_bundle)
        src = active.replica
        req.handoff_tokens = len(req.tokens_out)
        h0 = time.monotonic()      # handoff span clock — tracer-aligned
        # fault hook: handoff_torn corrupts the bundle in transit,
        # handoff_stall loses it outright — both land in the fallback
        torn = stalled = False
        for kind in fault_injector.fire("handoff",
                                        serving_step=self._polls):
            if kind == "handoff_torn":
                torn = True
            elif kind == "handoff_stall":
                stalled = True
        bundle = None
        if stalled:
            _registry.counter(
                "handoff/stalls",
                help="page bundles lost in transit (timeout)").inc()
        else:
            try:
                with src.lock:
                    bundle = export_bundle(src.frontend, req.prompt)
            except Exception as e:   # noqa: BLE001 — source may be dying
                logger.warning("handoff: export from %s failed: %s",
                               src.name, e)
                bundle = None
            if torn and bundle is not None:
                bundle.checksum ^= 0x1
                _registry.counter(
                    "handoff/torn",
                    help="page bundles failing checksum on arrival").inc()
        # the shipped subtree leaves the source either way: pages that
        # arrived belong to the decode pool now, pages that didn't are
        # suspect — over-invalidation costs recompute, never correctness
        try:
            with src.lock:
                cache = getattr(src.frontend, "cache", None)
                if cache is not None:
                    cache.invalidate(req.prompt)
        except Exception:   # noqa: BLE001 — dying source already failed over
            pass
        req.phase = "decode"
        req.primary = None
        req.winner = None
        if req.hedge is not None:
            if req.hedge.replica.alive:
                req.hedge.replica.cancel(req.hedge.inner)
            req.hedge = None
        folded = req.prompt + req.tokens_out
        fault_kind = ("handoff_torn" if torn
                      else "handoff_stall" if stalled else None)
        dec: Optional[LocalReplica] = None
        adopted = 0
        if bundle is not None and verify_bundle(bundle):
            # pick the decode replica FIRST, adopt under its lock, THEN
            # dispatch pinned to it — dispatch-before-adopt would let the
            # pump admit the leg before the pages are cached (silent full
            # re-prefill)
            try:
                dec = self._choose(folded, pool="decode")
                with dec.lock:
                    adopted = adopt_bundle(dec.frontend, bundle)
            except AdmissionError:
                dec = None
            except Exception as e:   # noqa: BLE001
                logger.warning("handoff: adopt into %s failed: %s",
                               dec.name if dec is not None else "?", e)
                adopted = 0
        if adopted:
            _registry.counter(
                "handoff/completed",
                help="prefill→decode page handoffs that shipped").inc()
            _registry.counter(
                "handoff/pages_shipped",
                help="KV pages adopted by decode replicas").inc(adopted)
            _registry.counter(
                "handoff/bytes_shipped",
                help="KV bytes adopted by decode replicas").inc(
                    bundle.nbytes)
            telemetry.flight_recorder.record_event(
                "router_handoff", replica=src.name, to=dec.name,
                pages=adopted, uid=req.uid)
        elif fault_kind is not None:
            _registry.counter(
                "handoff/fallback_reprefills",
                help="failed handoffs recovered by decode-side "
                     "re-prefill").inc()
            self._pending_handoff[req.uid] = {
                "req": req, "t0": now, "kind": fault_kind,
                "from": src.name}
            telemetry.flight_recorder.record_event(
                "router_handoff_fallback", replica=src.name,
                fault=fault_kind, uid=req.uid)
        else:
            _registry.counter(
                "handoff/skipped",
                help="promotions with no cached pages to ship").inc()
        if fault_kind is not None:
            telemetry.reqtrace.flag(req.trace, "reprefill")
        telemetry.reqtrace.complete(
            "router/handoff", req.trace, h0, time.monotonic(),
            tid=req.uid, src=src.name,
            dst=(dec.name if dec is not None else None),
            pages=adopted,
            bytes=(bundle.nbytes if adopted and bundle is not None
                   else 0),
            fault=fault_kind)
        try:
            self._dispatch(req, prefer=dec)
        except AdmissionError:
            self._finish(req, "error")
            _registry.counter("router/errors").inc()

    def _finish(self, req: RouterRequest, reason: str) -> None:
        for a in (req.primary, req.hedge):
            if a is not None and a.replica.alive and not a.inner.done:
                a.replica.cancel(a.inner)
        req.state = (RequestState.SHED if reason == "deadline"
                     else RequestState.FINISHED)
        req.finish_reason = reason
        req.finish_ts = self.clock()
        if req.trace is None:
            return
        # the router owns the root context: emit the client-visible
        # envelope span, then hand the trace to the tail sampler —
        # retained (flushed into the ring) or dropped whole
        rt = telemetry.reqtrace
        ttft = (req.first_token_ts - req.submit_ts
                if req.first_token_ts is not None
                and req.submit_ts is not None else None)
        tpot = ((req.finish_ts - req.first_token_ts) /
                (len(req.tokens_out) - 1)
                if req.first_token_ts is not None
                and len(req.tokens_out) >= 2 else None)
        if req.submit_ts is not None:
            rt.complete("router/request", req.trace, req.submit_ts,
                        req.finish_ts, tid=req.uid, envelope=True,
                        reason=reason, tokens_out=len(req.tokens_out),
                        failovers=req.failovers, hedged=int(req.hedged),
                        handoff_tokens=req.handoff_tokens)
        rt.finish(req.trace, reason=reason, ttft_s=ttft, tpot_s=tpot)

    # -- draining & recovery ledger -----------------------------------------

    def drain(self, name: str,
              deadline_s: Optional[float] = None) -> None:
        """Stop new admissions to ``name``; in-flight decodes finish on
        it, then :meth:`poll` removes it without dropping a stream.
        With ``deadline_s`` set, streams still assigned past the
        deadline fail over (token-fold replay) instead of pinning the
        replica open — the scale-down path uses this so a wedged stream
        can't block the fleet from shrinking."""
        if name not in {r.name for r in self.replicas}:
            raise KeyError(f"no replica named {name!r}")
        self._draining.add(name)
        if deadline_s is not None:
            self._drain_deadline[name] = self.clock() + float(deadline_s)
        _registry.counter("router/drains",
                          help="replicas put into draining").inc()
        telemetry.flight_recorder.record_event("router_drain_start",
                                               replica=name)
        self._publish_states()

    def _sweep_draining(self) -> None:
        now = self.clock()
        for r in list(self.replicas):
            if r.name not in self._draining:
                continue
            if self._assigned_count(r) and \
                    now >= self._drain_deadline.get(r.name, float("inf")):
                for req in list(self._reqs.values()):
                    if req.done:
                        continue
                    for a in (req.primary, req.hedge):
                        if a is not None and a.replica is r:
                            self._fail_assignment(req, a, "drain deadline")
            if self._assigned_count(r) == 0:
                self._draining.discard(r.name)
                self._drain_deadline.pop(r.name, None)
                self.replicas.remove(r)
                _registry.gauge(f"router/replica/{r.name}/state").set(
                    STATE_CODES["dead"])
                telemetry.flight_recorder.record_event(
                    "router_drained", replica=r.name, pool=r.pool)
                logger.warning("router: replica %s drained and removed",
                               r.name)
                r.close()

    def _sweep_recoveries(self, now: float) -> None:
        for uid in list(self._pending_handoff):
            entry = self._pending_handoff[uid]
            req = entry["req"]
            if not req.done:
                continue
            del self._pending_handoff[uid]
            if req.finish_reason == "error":
                continue     # the fallback itself failed — stays open
            record_recovery("handoff_reprefill", fault=entry["kind"],
                            replica=entry["from"], uid=uid,
                            recovery_s=round(now - entry["t0"], 3))
            logger.warning("router: %s handoff for uid=%d recovered by "
                           "decode-side re-prefill in %.3fs",
                           entry["kind"], uid, now - entry["t0"])
        for name in list(self._pending_recovery):
            entry = self._pending_recovery[name]
            if any(uid in self._reqs and not self._reqs[uid].done
                   for uid in entry["uids"]):
                continue
            recovery_s = now - entry["t0"]
            del self._pending_recovery[name]
            _registry.gauge(
                "router/last_recovery_s",
                help="wall seconds from replica loss to the last "
                     "failed-over stream completing").set(recovery_s)
            record_recovery("router_failover", replica=name,
                            requests=len(entry["uids"]),
                            recovery_s=round(recovery_s, 3))
            logger.warning("router: replica %s loss recovered — %d "
                           "streams replayed in %.3fs", name,
                           len(entry["uids"]), recovery_s)

    # -- client surface -----------------------------------------------------

    def stream(self, req: RouterRequest, poll_interval: float = 0.001,
               stall_timeout: float = 60.0) -> Iterator[int]:
        """Yield ``req``'s tokens as they arrive, driving :meth:`poll`
        between yields."""
        emitted = 0
        t_last = time.monotonic()
        while True:
            while emitted < len(req.tokens_out):
                yield req.tokens_out[emitted]
                emitted += 1
                t_last = time.monotonic()
            if req.done:
                return
            self.poll()
            if time.monotonic() - t_last > stall_timeout:
                raise RuntimeError(
                    f"router stream stalled {stall_timeout:.1f}s: uid="
                    f"{req.uid} state={req.state.value} tokens="
                    f"{len(req.tokens_out)}/{req.max_new_tokens} "
                    f"replicas=" + ",".join(
                        f"{r.name}:{self.replica_state(r)}"
                        for r in self.replicas))
            time.sleep(poll_interval)

    def run_until_idle(self, wall_timeout_s: float = 120.0,
                       poll_interval: float = 0.001) -> None:
        """Drive :meth:`poll` until every admitted stream is terminal."""
        t0 = time.monotonic()
        while self.poll():
            if time.monotonic() - t0 > wall_timeout_s:
                raise RuntimeError(
                    f"router did not drain in {wall_timeout_s:.0f}s: "
                    f"{len(self._reqs)} streams in flight, replicas=" +
                    ",".join(f"{r.name}:{self.replica_state(r)}"
                             for r in self.replicas))
            time.sleep(poll_interval)

    def stats(self) -> Dict[str, Any]:
        c = _registry.counter
        return {
            "replicas": {r.name: self.replica_state(r)
                         for r in self.replicas},
            "pools": {r.name: r.pool for r in self.replicas},
            "disaggregated": self.disaggregated,
            "requests": int(c("router/requests").value),
            "completed": int(c("router/completed").value),
            "errors": int(c("router/errors").value),
            "failovers": int(c("router/failovers").value),
            "hedges": int(c("router/hedges").value),
            "hedges_won": int(c("router/hedges_won").value),
            "hedges_lost": int(c("router/hedges_lost").value),
            "breaker_transitions":
                int(c("router/breaker_transitions").value),
            "tokens_out": int(c("router/tokens_out").value),
            "drained_streams": int(c("router/drained_streams").value),
            "handoffs": int(c("handoff/completed").value),
            "handoff_pages": int(c("handoff/pages_shipped").value),
            "handoff_fallbacks":
                int(c("handoff/fallback_reprefills").value),
            "handoff_skipped": int(c("handoff/skipped").value),
            "replica_tokens": dict(self.replica_tokens),
            "ttft_p95_s": (round(self.ttft.percentile(95), 4)
                           if self.ttft.count else None),
            "last_recovery_s":
                _registry.gauge("router/last_recovery_s").value,
        }

    def close(self) -> None:
        if self._http is not None:
            self._http.close()
            self._http = None
        for r in self.replicas:
            r.close()


# ---------------------------------------------------------------------------
# dstpu-router CLI: a local replica pool + drill in one command
# ---------------------------------------------------------------------------

def _build_local_pool(n: int, size: str, http_ports: bool,
                      seed: int = 0, pools: Optional[List[str]] = None,
                      ) -> List[LocalReplica]:
    """N in-process replicas over tiny engines with identical params
    (each replica owns its engine + KV arena, exactly the state a real
    replica process would lose on a kill). Replica ``i`` lives on local
    device ``i % n_devices`` — one process can run one replica per chip;
    with a single device they all share it. ``pools`` assigns each
    replica's pool (``prefill``/``decode``/``any``) for a disaggregated
    fleet; default is a monolithic ``any`` pool."""
    import jax
    from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.serving.frontend import ServingFrontend
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config(size, max_seq_len=256, vocab_size=256)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    eng_cfg = {"dtype": "float32", "num_blocks": 64, "block_size": 8,
               "max_seq_len": 256, "prefill_chunk": 16,
               "max_batch_tokens": 128, "max_sequences": 16}
    out = []
    devices = jax.local_devices()
    for i in range(n):
        dev = devices[i % len(devices)]
        with jax.default_device(dev):     # the arena lands here too
            eng = RaggedInferenceEngineTPU(
                cfg, dict(eng_cfg), params=jax.device_put(params, dev))
        fe = ServingFrontend(eng, max_queue=256,
                             http_port=(0 if http_ports else None))
        pool = pools[i] if pools else "any"
        out.append(LocalReplica(f"r{i}", fe, pool=pool))
    return out


def main(argv: Optional[List[str]] = None) -> int:
    """``dstpu-router``: spin up a local pool of N serving replicas,
    route a demo request stream over them (optionally under a chaos
    plan), and print a JSON drill summary::

        dstpu-router --replicas 3 --requests 24 \\
            --chaos "serving_step:8:replica_kill:router"

    For a multi-process pool, spawn the replicas with the launcher's
    pool agent (``python -m deepspeed_tpu.launcher.agent --pool N --
    ...``) and point a Router at their endpoints.
    """
    import argparse
    import json as _json
    ap = argparse.ArgumentParser(
        prog="dstpu-router",
        description="Fault-tolerant multi-replica serving router: local "
                    "pool demo + chaos drill harness.")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--prefill", type=int, default=0,
                    help="run a DISAGGREGATED fleet: this many prefill "
                         "replicas (use with --decode; overrides "
                         "--replicas)")
    ap.add_argument("--decode", type=int, default=0,
                    help="decode-pool replicas for --prefill")
    ap.add_argument("--size", default="tiny")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--chaos", default=None,
                    help="fault plan armed for the drill (e.g. "
                         "'serving_step:8:replica_kill:router')")
    ap.add_argument("--http-port", type=int, default=None,
                    help="router /metrics + /healthz port (0=ephemeral)")
    ap.add_argument("--replica-http", action="store_true",
                    help="give each replica its own ephemeral endpoint "
                         "(breaker then also polls /healthz)")
    ap.add_argument("--no-hedge", action="store_true")
    ap.add_argument("--hedge-delay", type=float, default=None)
    args = ap.parse_args(argv)

    import numpy as np
    rng = np.random.default_rng(0)
    if args.prefill or args.decode:
        if not (args.prefill and args.decode):
            ap.error("--prefill and --decode must both be > 0")
        pools = (["prefill"] * args.prefill + ["decode"] * args.decode)
        replicas = _build_local_pool(len(pools), args.size,
                                     args.replica_http, pools=pools)
    else:
        replicas = _build_local_pool(args.replicas, args.size,
                                     args.replica_http)
    router = Router(replicas, hedge=not args.no_hedge,
                    hedge_delay_s=args.hedge_delay,
                    http_port=args.http_port)
    if args.chaos:
        fault_injector.arm(args.chaos, _env=False)
    shared = rng.integers(1, 250, size=8).tolist()
    t0 = time.perf_counter()
    reqs = [router.submit(shared + rng.integers(1, 250, size=4).tolist(),
                          max_new_tokens=args.max_new)
            for _ in range(args.requests)]
    try:
        router.run_until_idle(wall_timeout_s=300.0)
    finally:
        wall = time.perf_counter() - t0
        summary = {"drill": {"replicas": len(replicas),
                             "requests": args.requests,
                             "chaos": args.chaos,
                             "wall_s": round(wall, 3)},
                   "ok": all(r.finish_reason in ("length", "eos")
                             for r in reqs),
                   "router": router.stats()}
        print(_json.dumps(summary))
        router.close()
        fault_injector.disarm()
    return 0 if all(r.done for r in reqs) else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
