"""ServingFrontend — the single-threaded serving pump.

Owns the admission queue, the prefix cache, the SplitFuse policy and the
metrics, and drives the engine's step in a loop — as a launch and a
collect (:meth:`RaggedInferenceEngineTPU.launch` / ``collect``), step n+1
launched BEFORE step n is collected, so that admission, scheduling,
packing, the fetch and the fan-out all happen while the device runs a
program. Single-threaded by design (a thread pool would only add locks to
a loop whose wall clock is the device's).

Request path: ``submit`` → bounded queue (reject ``queue_full`` /
``kv_exhausted`` / ``too_long``) → admission matches the prompt against
the radix prefix cache, aliases shared full pages (incref), copy-on-writes
a shared partial page, and adopts the sequence with ``seen_tokens``
already covering the cached span → SplitFuse packs prefill + decode under
the token budget → per-token stream callbacks → flush + cache insert.
"""

import time
from typing import Any, Dict, Iterator, List, Optional

from deepspeed_tpu import telemetry
from deepspeed_tpu.resilience.faults import fault_injector, record_recovery
from deepspeed_tpu.serving.metrics import ServingMetrics
from deepspeed_tpu.serving.prefix_cache import PrefixCache
from deepspeed_tpu.serving.queue import AdmissionError, AdmissionQueue
from deepspeed_tpu.serving.request import Request, RequestState
from deepspeed_tpu.serving.scheduler import TokenBudgetPolicy


def adopt_cached(engine, cache, uid: int, prompt: List[int]) -> int:
    """Admit ``prompt`` as sequence ``uid``, reusing cached prefix pages.

    Matches the prompt against the radix cache, aliases shared FULL pages
    (incref — the ref transfers to the sequence), duplicates a shared
    partial page copy-on-write, and adopts the sequence with
    ``seen_tokens`` covering the reused span; the match is capped at
    ``len(prompt) - 1`` so at least one token prefills and produces this
    request's own logits. Evicts cache LRU pages if the arena can't fit
    the uncached tail (never the pages being handed out). Returns the
    number of prompt tokens served from the cache; raises RuntimeError
    when the arena cannot fit even after eviction (nothing is leaked).
    A RECURRENT stack (state-space layers) is handed no pages, whatever
    the cache holds: its pages are not its history.
    """
    if engine.state.recurrent:
        cache = None
    alloc = engine.state.allocator
    bs = alloc.block_size
    aliased: List[int] = []
    cow_src = None
    matched = 0
    if cache is not None:
        m = cache.match(prompt)
        matched = min(m.matched(bs), len(prompt) - 1)
        full_keep = matched // bs
        aliased = m.full_blocks[:full_keep]
        if matched > full_keep * bs:
            # tail of the match lives mid-page → hand that page out
            # copy-on-write (a capped FULL page counts too: its new owner
            # re-prefills into it)
            cow_src = (m.full_blocks[full_keep]
                       if full_keep < len(m.full_blocks)
                       else m.partial_block)
        else:
            matched = full_keep * bs
    need = -(-len(prompt) // bs) - len(aliased)
    if need > alloc.free_blocks and cache is not None:
        with telemetry.tracer.span("serving/cache_evict"):
            cache.evict(need - alloc.free_blocks,
                        exclude_blocks=aliased + [cow_src])
    if need > alloc.free_blocks:
        raise RuntimeError(
            f"KV arena exhausted: want {need} blocks, "
            f"{alloc.free_blocks} free")
    adopted = list(aliased)
    if aliased:
        alloc.incref(aliased)
    if cow_src is not None:
        try:
            adopted.append(engine.cow_block(cow_src))
        except RuntimeError:
            if aliased:
                alloc.free(aliased)
            raise
    engine.state.adopt(uid, prompt, adopted, matched)
    return matched


class ServingFrontend:

    @telemetry.setup_part("frontend")      # always on: setup/frontend_seconds
    def __init__(self, engine, max_queue: int = 128,
                 enable_prefix_cache: bool = True,
                 cache_pages: Optional[int] = None,
                 monitor=None, mode=("argmax",),
                 token_budget: Optional[int] = None,
                 emit_every: int = 0, clock=time.monotonic,
                 watchdog=None, http_port: Optional[int] = None,
                 slo_admission: bool = False,
                 retry_budget: Optional[int] = None,
                 kvtier=None,
                 config=None):
        self.engine = engine
        #: optional telemetry.Watchdog armed around each engine step — a
        #: hung decode (deadlocked collective, runaway compile) dumps
        #: stacks + the flight recorder instead of silently stalling SLOs
        self.watchdog = watchdog
        self.policy = TokenBudgetPolicy()
        engine.scheduler.policy = self.policy
        self.queue = AdmissionQueue(max_queue)
        # a recurrent stack (state-space layers) gets no prefix cache: a
        # sequence carries a state beside its pages, which no page holds
        self.cache = (PrefixCache(engine.state.allocator, cache_pages)
                      if enable_prefix_cache and not engine.state.recurrent
                      else None)
        self.metrics = ServingMetrics()
        self.monitor = monitor
        self.mode = mode
        # vertical page tier under the radix cache (serving/kvtier.py):
        # an explicit KVTier wins; else a config kvtier.* block with
        # enabled=true builds one. Evictions then capture host-side and
        # returning conversations warm-resume instead of re-prefilling.
        self.kvtier = kvtier
        if self.kvtier is None and config is not None and \
                self.cache is not None:
            kcfg = (config.get("kvtier") if isinstance(config, dict)
                    else getattr(config, "kvtier", None))
            kget = ((kcfg or {}).get if isinstance(kcfg, dict)
                    else lambda k, d=None: getattr(kcfg, k, d))
            if kcfg is not None and bool(kget("enabled", False)):
                from deepspeed_tpu.serving.kvtier import KVTier
                self.kvtier = KVTier(
                    engine,
                    dram_bytes=int(kget("dram_bytes", 256 << 20)),
                    nvme_dir=kget("nvme_dir", None),
                    nvme_max_bytes=kget("nvme_max_bytes", None),
                    high_watermark=float(kget("high_watermark", 0.9)),
                    low_watermark=float(kget("low_watermark", 0.7)),
                    compress=str(kget("compress", "none") or "none"))
        if self.cache is not None and self.kvtier is not None:
            self.cache.tier = self.kvtier
        self.token_budget = token_budget     # None → engine max_batch_tokens
        # engine-fault retry budget (resilience.serving_retry_budget):
        # times ONE request may be requeued after an engine step died
        # under it before it finishes with reason "error"
        cfg_rb = 2
        if config is not None:
            rcfg = (config.get("resilience") if isinstance(config, dict)
                    else getattr(config, "resilience", None))
            if isinstance(rcfg, dict):
                cfg_rb = int(rcfg.get("serving_retry_budget", cfg_rb))
            elif rcfg is not None:
                cfg_rb = int(rcfg.serving_retry_budget)
        self.retry_budget = (cfg_rb if retry_budget is None
                             else int(retry_budget))
        #: pump iterations — the ``serving_step`` chaos trigger counts these
        self._pump_steps = 0
        self.emit_every = emit_every
        self.clock = clock                   # injectable for deadline tests
        self._running: Dict[int, Request] = {}
        #: compile-time prefill/decode cost records (telemetry/explain) —
        #: SLO admission reads predicted step times from here; tests
        #: inject synthetic records directly
        self.cost_records: Optional[Dict[str, Any]] = None
        if slo_admission:
            try:
                self.cost_records = engine.cost_records(mode=mode)
            except Exception as e:               # noqa: BLE001
                from deepspeed_tpu.utils.logging import logger
                logger.warning(f"SLO admission disabled — cost records "
                               f"unavailable: {e}")
        self._http = None
        if http_port is not None:
            from deepspeed_tpu.telemetry.endpoint import MetricsServer
            self._http = MetricsServer(http_port)
        # metric history + SLO burn-rate engine, same seam as the
        # training engine's (_init_telemetry): a telemetry.history_file
        # key or any slo.objectives turns continuous evaluation on;
        # breaches flip this frontend's /healthz (source="slo") next to
        # the fault-domain draining flag (source="serving")
        self._history = None
        self._slo = None
        self._history_every = 10
        if config is not None:
            tcfg = (config.get("telemetry") if isinstance(config, dict)
                    else getattr(config, "telemetry", None))
            scfg = (config.get("slo") if isinstance(config, dict)
                    else getattr(config, "slo", None))
            tget = ((tcfg or {}).get if isinstance(tcfg, dict)
                    else lambda k, d=None: getattr(tcfg, k, d))
            hist_file = tget("history_file") if tcfg is not None else None
            objectives = []
            if scfg is not None:
                objectives = (scfg.get("objectives") if isinstance(
                    scfg, dict) else getattr(scfg, "objectives", None)) or []
            if hist_file or objectives:
                from deepspeed_tpu.telemetry.slo import engine_from_config
                from deepspeed_tpu.telemetry.timeseries import MetricHistory
                try:
                    self._history = MetricHistory(
                        path=hist_file,
                        max_bytes=tget("history_max_bytes", 8_388_608),
                        downsample=tget("history_downsample", 2))
                    self._history_every = max(
                        1, int(tget("history_every", 0) or 10))
                    self._slo = engine_from_config(scfg, healthz=self._http)
                    if self._slo is not None:
                        self._history.subscribe(self._slo.observe)
                except Exception as e:               # noqa: BLE001
                    from deepspeed_tpu.utils.logging import logger
                    logger.warning(
                        f"serving metric history/SLO init failed: {e}")
                    self._history = self._slo = None
            # goodput ledger: its own enabled gate; arming it also arms
            # the span tracer (the ledger attributes serving/engine_step
            # spans off the tracer ring)
            gcfg = (tget("goodput") if tcfg is not None else None)
            gget = ((gcfg or {}).get if isinstance(gcfg, dict)
                    else lambda k, d=None: getattr(gcfg, k, d))
            if gcfg is not None and gget("enabled", False):
                from deepspeed_tpu import telemetry as _telemetry
                _telemetry.tracer.configure(enabled=True)
                _telemetry.goodput_ledger.configure(
                    enabled=True,
                    window_s=gget("window_s"),
                    capture_threshold=gget("capture_threshold"),
                    capture_cooldown_s=gget("capture_cooldown_s"),
                    capture_duration_ms=gget("capture_duration_ms"),
                    capture_dir=gget("capture_dir"))

    def close(self) -> None:
        """Release frontend-owned resources (the /metrics server, the
        KV tier's I/O engine and spill files); idempotent, safe to call
        on a frontend that never opened either. What is in flight is
        collected and delivered first."""
        self.drain()
        if self._http is not None:
            self._http.close()
            self._http = None
        if self.kvtier is not None:
            if self.cache is not None:
                self.cache.tier = None    # no capture churn at teardown
            self.kvtier.close()
            self.kvtier = None

    def terminate_inflight(self, reason: str = "drained") -> int:
        """Finish every running AND queued request with ``reason``
        (terminal state, KV released) — the scale-down path. A client
        blocked in :meth:`stream` sees its request reach ``done`` and
        the iterator end, instead of spinning into the stall-timeout
        ``RuntimeError`` because the replica under it was drained.
        Returns requests terminated."""
        now = self.clock()
        n = 0
        for req in list(self._running.values()):
            self._finish(req, reason, RequestState.FINISHED, now)
            n += 1
        for req in list(self.queue._q):
            req.state = RequestState.FINISHED
            req.finish_reason = reason
            req.finish_ts = now
            self._trace_lifecycle(req, reason, now)
            n += 1
        self.queue._q.clear()
        self.drain()             # their rows' tokens in flight: dropped
        if n:
            self.metrics.bump("terminated_inflight", n)
        return n

    def _slo_check(self, req: Request, now: float) -> None:
        """Reject at the door when the roofline says the deadline is
        unattainable even on an idle engine: best-case latency =
        ceil(prompt/prefill_chunk) prefill steps + max_new_tokens decode
        steps at their predicted step times. Zero predictions (CPU, no
        peak table) disable the check — admission behavior is unchanged
        where there is no model."""
        recs = self.cost_records
        if recs is None or req.deadline is None:
            return
        t_pre = float(recs.get("prefill", {}).get("predicted_s", 0.0))
        t_dec = float(recs.get("decode", {}).get("predicted_s", 0.0))
        if t_pre <= 0.0 or t_dec <= 0.0:
            return
        chunk = max(1, int(self.engine.config.prefill_chunk))
        best = -(-len(req.prompt) // chunk) * t_pre + \
            req.max_new_tokens * t_dec
        if now + best > req.deadline:
            req.state = RequestState.REJECTED
            req.finish_reason = "slo_unattainable"
            self.metrics.bump("rejected_slo")
            raise AdmissionError(
                "slo_unattainable",
                f"best-case {best * 1e3:.1f} ms exceeds deadline "
                f"{(req.deadline - now) * 1e3:.1f} ms away "
                f"(roofline: prefill {t_pre * 1e3:.2f} ms/step, "
                f"decode {t_dec * 1e3:.2f} ms/step)")

    # -- admission ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 16, priority: int = 0,
               timeout: Optional[float] = None,
               deadline: Optional[float] = None,
               stream_cb=None,
               eos_token_id: Optional[int] = None,
               ctx=None) -> Request:
        """Admit a request or raise :class:`AdmissionError` with a reason
        (``queue_full`` | ``kv_exhausted`` | ``too_long`` |
        ``slo_unattainable``) — overload is surfaced at the door, not
        buffered into unbounded latency. ``slo_unattainable`` fires only
        with SLO admission on and a deadline the roofline model says
        cannot be met even best-case. ``eos_token_id`` finishes the
        request early (reason ``"eos"``) when that token is sampled.

        ``ctx`` is an upstream :class:`~deepspeed_tpu.telemetry.reqtrace.
        TraceContext` (the router passes its leg context so this
        frontend's spans join the fleet-wide trace); with request tracing
        enabled and no upstream context, the frontend is the entry point
        and mints the trace itself."""
        with telemetry.tracer.span("serving/submit"):
            now = self.clock()
            prompt = [int(t) for t in prompt]
            req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                          priority=priority, stream_cb=stream_cb,
                          deadline=(now + timeout if timeout is not None
                                    else deadline),
                          eos_token_id=eos_token_id)
            total = len(prompt) + req.max_new_tokens
            if not prompt or total > self.engine.config.max_seq_len:
                req.state = RequestState.REJECTED
                req.finish_reason = "too_long"
                self.metrics.bump("rejected_too_long")
                raise AdmissionError(
                    "too_long", f"{total} tokens vs max_seq_len="
                    f"{self.engine.config.max_seq_len}")
            bs = self.engine.state.allocator.block_size
            need = -(-total // bs)
            avail = self.engine.state.allocator.free_blocks + \
                (self.cache.evictable_pages() if self.cache else 0)
            if need > avail:
                req.state = RequestState.REJECTED
                req.finish_reason = "kv_exhausted"
                self.metrics.bump("rejected_kv_exhausted")
                raise AdmissionError(
                    "kv_exhausted", f"need {need} pages, {avail} reclaimable")
            self._slo_check(req, now)
            try:
                victim = self.queue.submit(req, now)
            except AdmissionError:
                self.metrics.bump("rejected_queue_full")
                raise
            if victim is not None:
                # the queue shed a past-deadline entry to make room; give it
                # the same terminal treatment shed_expired victims get — a
                # "deadline" finish the client can observe and a shed count
                victim.finish_ts = now
                self.metrics.bump("shed")
                self._trace_lifecycle(victim, "deadline", now)
            self.metrics.bump("admitted")
            from deepspeed_tpu.telemetry.reqtrace import reqtrace
            req.trace = ctx if ctx is not None else \
                reqtrace.mint(entry="frontend", uid=req.uid)
            if self.kvtier is not None:
                # returning conversation: start the NVMe preads NOW (the PR 6
                # issue/complete split) so the bytes climb to DRAM while the
                # request waits in admission; the complete half runs at admit
                self.kvtier.issue_prefetch(prompt, ctx=req.trace)
            return req

    def cancel(self, req: Request) -> None:
        req.cancel()

    def _try_admit_one(self, now: float) -> bool:
        eng = self.engine
        req = self.queue.pop_next(now)
        if req is None:
            return False
        if len(eng.state.seqs) >= eng.config.max_sequences:
            self.queue._q.insert(0, req)
            return False
        if self.kvtier is not None and self.cache is not None:
            # complete half of the tier prefetch: restore the prompt's
            # spilled chain into arena + radix cache BEFORE the normal
            # cached-prefix adoption aliases it — a warm resume then
            # prefills only the uncovered suffix. The tier degrades to a
            # plain re-prefill on any failure; admission never does.
            try:
                self.kvtier.adopt(req.prompt, self.cache, ctx=req.trace)
            except Exception as e:                   # noqa: BLE001
                from deepspeed_tpu.utils.logging import logger
                logger.warning(f"kvtier adopt failed (re-prefilling): {e}")
        try:
            matched = adopt_cached(eng, self.cache, req.uid, req.prompt)
        except RuntimeError:
            # arena can't fit yet (nothing leaked) — retry when running
            # sequences finish and release pages
            self.queue._q.insert(0, req)
            return False
        self.policy.note_arrival(req.uid)
        req.state = RequestState.RUNNING
        req.schedule_ts = now
        req.cached_tokens = matched
        if matched:
            self.metrics.bump("prefix_tokens_reused", matched)
        self._running[req.uid] = req
        return True

    # -- the pump -----------------------------------------------------------

    def step(self) -> bool:
        """One pump iteration: shed → cancel → admit → LAUNCH the next
        engine step → COLLECT the one before it → fan its tokens out.
        Returns True while there is (or was) work, a launch in flight
        included. The launch made in a call is collected in the NEXT call,
        while the one after it runs: a token reaches its request one
        ``step()`` after the program that sampled it was launched, and the
        device does not wait for the host in between.

        One ``serving/step`` span whose children tile it: ``serving/admit``,
        ``serving/plan``, ``serving/engine_step`` (the engine's
        ``serving/schedule`` / ``pack`` / ``dispatch`` / ``count`` /
        ``retire`` of the launch, then ``serving/fetch`` / ``retire`` of
        the collect, tile that one), ``serving/bookkeeping``,
        ``serving/fanout``, ``serving/bookkeeping`` again: outside them a
        step holds a few attribute reads, so that a reader can put every
        idle moment of the device down to one phase
        (``docs/observability.md``)."""
        with telemetry.tracer.span("serving/step"):
            return self._step()

    def _admit(self, now: float) -> bool:
        progressed = False
        for r in self.queue.shed_expired(now):
            self.metrics.bump("shed")
            progressed = True
        for uid, req in list(self._running.items()):
            if req.cancelled:
                self._finish(req, "cancelled", RequestState.CANCELLED, now)
                progressed = True
            elif req.expired(now):
                self._finish(req, "deadline", RequestState.SHED, now)
                self.metrics.bump("shed")
                progressed = True
        while self._try_admit_one(now):
            progressed = True
        # queue-depth exemplar: the head-of-line request's trace — the
        # one that has been waiting at this depth the longest
        head = self.queue._q[0] if len(self.queue) else None
        self.metrics.queue_depth.record(
            float(len(self.queue)),
            exemplar=head.trace.trace_id
            if head is not None and head.trace else None)
        return progressed

    def _step(self) -> bool:
        with telemetry.tracer.span("serving/admit"):
            now = self.clock()
            progressed = self._admit(now)
        with telemetry.tracer.span("serving/plan"):
            # what a row may still emit, as this pump counts it: a length
            # end is known BEFORE the launch, so the engine continues no
            # row past its budget
            row_limits = {uid: req.max_new_tokens - len(req.tokens_out)
                          for uid, req in self._running.items()}
            if self.watchdog is not None:
                self.watchdog.arm("serving_step")
            t0 = time.monotonic()
            self._pump_steps += 1
        try:
            with telemetry.tracer.span(
                    "serving/engine_step",
                    batch=len(self._running)) as span_args:
                # chaos hook: an engine_error entry raises HERE so the
                # injected fault exercises the same except-path a real
                # engine failure takes
                # advisory=False: this hook acts on no advisory kinds, so
                # fleet-scoped entries (replica_kill/replica_slow) stay
                # pending for the router's hook instead of being consumed
                # and dropped by a replica's own pump
                fault_injector.fire("serving_step",
                                    serving_step=self._pump_steps,
                                    advisory=False)
                launched, got = self._engine_step(row_limits)
                if span_args is not None and launched:
                    # which device program the step launched (decode /
                    # fresh / split / paged): known only now
                    span_args["program"] = getattr(self.engine,
                                                   "last_program", None)
        except Exception as e:                       # noqa: BLE001
            # serving failure domain: one engine fault must cost at most
            # one retry per in-flight request, never a wedged replica
            with telemetry.tracer.span("serving/bookkeeping"):
                self._on_engine_fault(e, self.clock())
                self._update_degraded()
            return True
        finally:
            if self.watchdog is not None:
                self.watchdog.disarm()
        with telemetry.tracer.span("serving/bookkeeping"):
            self._update_degraded()
            # goodput ledger sweep (rate-limited internally; no-op unless
            # telemetry.goodput is on) — BEFORE the nothing-collected early
            # return so idle pumps keep attributing idle seconds
            telemetry.goodput_ledger.maybe_update()
            if launched:
                self.metrics.bump("engine_steps")
                telemetry.flight_recorder.record_step(
                    int(telemetry.registry.counter(
                        "serving/engine_steps").value),
                    kind="serving", dur_s=time.monotonic() - t0,
                    batch=len(self._running),
                    tokens=len(got[0]) if got else 0)
        if got is None:
            return progressed or launched or \
                bool(self._running or len(self.queue))
        with telemetry.tracer.span("serving/fanout"):
            self._fan_out(*got)
        with telemetry.tracer.span("serving/bookkeeping"):
            if self.emit_every and self.metrics.counters["engine_steps"] % \
                    self.emit_every == 0:
                self.emit_metrics()
            # metric history + SLO evaluation on its own cadence: one
            # registry snapshot feeds the history file, the slo/* burn
            # gauges, /healthz, and the flight recorder together
            if self._history is not None and \
                    self.metrics.counters["engine_steps"] % \
                    self._history_every == 0:
                telemetry.registry.flush_to_monitor(
                    None, self.metrics.counters["engine_steps"],
                    history=self._history)
            # re-evaluate AFTER fan-out: the step that finishes the last
            # retried request must flip /healthz back to healthy — no
            # later pump is guaranteed once the replica drains idle
            self._update_degraded()
        return True

    def _engine_step(self, row_limits):
        """``(launched, collected)`` of one pump iteration: launch the next
        engine step, then collect the launch that was in flight BEFORE it
        (``(tokens, continued uids)``, or None without one). A launch with
        nothing in flight before it is collected in the next iteration."""
        eng = self.engine
        waiting = eng.in_flight
        launched = eng.launch(budget=self.token_budget, mode=self.mode,
                              row_limits=row_limits)
        return launched, eng.collect() if waiting else None

    def drain(self) -> None:
        """Collect every launch in flight and deliver its tokens: nothing
        is left on the device that a request waits for. What
        :meth:`run_until_idle`, :meth:`stream`, :meth:`terminate_inflight`,
        :meth:`close` and the page hand-off end with."""
        while self.engine.in_flight:
            try:
                got = self.engine.collect()
            except Exception as e:                   # noqa: BLE001
                self._on_engine_fault(e, self.clock())
                return
            self._fan_out(*got)

    def _fan_out(self, out: Dict[int, Any], continued=()) -> None:
        """Hand the step's tokens to their requests: stamp first tokens,
        stream, finish on eos / length, feed the last token back — but for
        the rows the engine ``continued`` at the launch: their token never
        left the device, and the next program may hold it already. A row
        finished here that was continued is flushed like any other; the
        token its next program samples is dropped at that collect."""
        now = self.clock()
        for uid, tok in out.items():
            req = self._running.get(uid)
            if req is None:
                continue
            if req.first_token_ts is None:
                req.first_token_ts = now
                self.metrics.ttft.record(
                    now - (req.enqueue_ts or now),
                    exemplar=req.trace.trace_id if req.trace else None)
                if self.cache is not None:
                    # prefill done → every prompt page holds valid KV;
                    # publish them (cache increfs what it keeps)
                    with telemetry.tracer.span("serving/cache_insert"):
                        self.cache.insert(
                            req.prompt, self.engine.state.seqs[uid].blocks)
            tok = int(tok)
            req.tokens_out.append(tok)
            self.metrics.bump("tokens_out")
            if req.stream_cb is not None:
                req.stream_cb(tok)
            # eos outranks length: a row that samples eos on its last
            # budgeted token finished because of the eos
            if req.eos_token_id is not None and tok == req.eos_token_id:
                self._finish(req, "eos", RequestState.FINISHED, now)
            elif len(req.tokens_out) >= req.max_new_tokens:
                self._finish(req, "length", RequestState.FINISHED, now)
            elif uid not in continued:
                try:
                    self.engine.state.extend(uid, [tok])
                except RuntimeError:
                    if self.cache is not None and self.cache.evict(1):
                        self.engine.state.extend(uid, [tok])
                    else:
                        self._finish(req, "kv_exhausted",
                                     RequestState.FINISHED, now)

    def _finish(self, req: Request, reason: str, state: RequestState,
                now: float) -> None:
        self.engine.flush(req.uid)
        self.policy.forget(req.uid)
        self._running.pop(req.uid, None)
        req.finish_reason = reason
        req.finish_ts = now
        self._trace_lifecycle(req, reason, now)
        # last: a router's thread that sees the request ``done`` decides
        # its trace's fate at once, and spans that reach the tail sampler
        # after that are dropped (``trace/late_spans``)
        req.state = state
        if req.tpot is not None:
            self.metrics.tpot.record(
                req.tpot,
                exemplar=req.trace.trace_id if req.trace else None)
        if state is RequestState.FINISHED:
            self.metrics.bump("completed")
        elif state is RequestState.CANCELLED:
            self.metrics.bump("cancelled")

    def _on_engine_fault(self, err: BaseException, now: float) -> None:
        """Engine-step failure domain. The engine's device state after a
        mid-step exception is unknowable from here, so every in-flight
        request is flushed (KV pages released — pages never leak on a
        fault), its prefix-cache subtree invalidated (the pages'
        contents are suspect), and the request either requeued at the
        head of the admission queue (tokens already streamed fold into
        the prompt, so re-prefill reproduces the decode state and
        nothing is re-emitted) or — budget exhausted — finished with
        reason ``"error"`` so ``stream()`` terminates instead of
        stalling."""
        from deepspeed_tpu.utils.logging import logger
        telemetry.registry.counter(
            "resilience/serving_engine_faults",
            help="engine-step failures absorbed by the serving "
                 "failure domain").inc()
        telemetry.flight_recorder.record_event(
            "serving_engine_fault", error=f"{type(err).__name__}: {err}",
            batch=len(self._running), pump_step=self._pump_steps)
        requeued = errored = 0
        # a fault at the launch or at the collect: what else is in flight
        # goes with the rows, whose tokens the retry samples again
        self.engine.abandon()
        for uid, req in list(self._running.items()):
            try:
                self.engine.flush(uid)
            except Exception:                        # noqa: BLE001
                pass  # sequence may be half-torn; pages the engine still
                      # tracks are reclaimed with it
            self.policy.forget(uid)
            self._running.pop(uid, None)
            if self.cache is not None:
                self.cache.invalidate(req.prompt)
            if req.retries < self.retry_budget:
                req.retries += 1
                # KV for already-streamed tokens died with the flush;
                # folding them into the prompt re-prefills exactly that
                # state — the client's stream continues where it was
                req.prompt = req.prompt + req.tokens_out
                req.state = RequestState.QUEUED
                req.first_token_ts = None
                telemetry.reqtrace.flag(req.trace, "replay")
                telemetry.reqtrace.instant(
                    "serving/request/replay", req.trace, ts=now,
                    tid=req.uid, replay=req.retries,
                    error=type(err).__name__)
                self.queue._q.insert(0, req)
                self.metrics.bump("requeued_engine_fault")
                telemetry.registry.counter(
                    "resilience/serving_requeued",
                    help="in-flight requests requeued after an engine "
                         "fault").inc()
                requeued += 1
            else:
                self._finish(req, "error", RequestState.FINISHED, now)
                errored += 1
        logger.warning(
            "serving engine fault (%s): requeued %d, errored %d of the "
            "in-flight batch", type(err).__name__, requeued, errored)
        record_recovery("serving_requeue", requeued=requeued,
                        errored=errored,
                        error=f"{type(err).__name__}: {err}")

    def _update_degraded(self) -> None:
        """/healthz shows degraded (503) while fault-requeued requests
        are still draining — the replica is alive and recovering, and a
        balancer should route new traffic elsewhere until it is clean."""
        draining = any(r.retries for r in self._running.values()) or \
            any(r.retries for r in self.queue._q)
        telemetry.registry.gauge(
            "resilience/serving_degraded",
            help="1 while engine-fault retries drain").set(
                1.0 if draining else 0.0)
        if self._http is not None:
            self._http.set_degraded(
                draining, reason="engine-fault retries draining")

    def _trace_lifecycle(self, req: Request, reason: str,
                         now: float) -> None:
        """Emit the request's phase spans retroactively at terminal state
        (queued → prefill → decode, plus the whole-request envelope), one
        trace track per request (tid = uid). The frontend's clock and the
        tracer's are both CLOCK_MONOTONIC-derived, so the retroactive
        timestamps land on the tracer's timeline (see Tracer.complete).

        With a trace context on the request, the spans go through the
        tail-sampling :class:`~deepspeed_tpu.telemetry.reqtrace.ReqTrace`
        buffer instead (trace_id-tagged; retained or dropped whole at the
        root owner's ``finish``); without one, the legacy path records
        untagged spans straight into the tracer ring."""
        rt = telemetry.reqtrace
        ctx = req.trace
        if ctx is not None and rt.enabled:
            if req.enqueue_ts is None:
                return
            tid = req.uid
            rt.complete("serving/request", ctx, req.enqueue_ts, now,
                        tid=tid, envelope=True, reason=reason,
                        tokens_out=len(req.tokens_out),
                        cached_tokens=req.cached_tokens,
                        replay=req.retries)
            if req.schedule_ts is not None:
                rt.complete("serving/request/queued", ctx, req.enqueue_ts,
                            req.schedule_ts, tid=tid)
                if req.first_token_ts is not None:
                    rt.complete("serving/request/prefill", ctx,
                                req.schedule_ts, req.first_token_ts,
                                tid=tid)
                    rt.complete("serving/request/decode", ctx,
                                req.first_token_ts, now, tid=tid)
            if ctx.root:
                # this frontend minted the trace — the stream ends here,
                # so the tail-sampling decision is ours
                rt.finish(ctx, reason=reason, ttft_s=req.ttft,
                          tpot_s=req.tpot)
            return
        tr = telemetry.tracer
        if not tr.enabled or req.enqueue_ts is None:
            return
        tid = req.uid
        tr.complete("serving/request", req.enqueue_ts, now, tid=tid,
                    reason=reason, tokens_out=len(req.tokens_out),
                    cached_tokens=req.cached_tokens)
        if req.schedule_ts is not None:
            tr.complete("serving/request/queued", req.enqueue_ts,
                        req.schedule_ts, tid=tid)
            if req.first_token_ts is not None:
                tr.complete("serving/request/prefill", req.schedule_ts,
                            req.first_token_ts, tid=tid)
                tr.complete("serving/request/decode", req.first_token_ts,
                            now, tid=tid)

    def run_until_idle(self, max_steps: int = 100000) -> None:
        """Pump until every admitted request reached a terminal state."""
        for _ in range(max_steps):
            if not (self._running or len(self.queue)):
                self.drain()     # the launch a row that ended was part of
                return
            self.step()
        raise RuntimeError(f"serving loop did not drain in {max_steps} steps")

    def stream(self, req: Request, poll_interval: float = 0.0005,
               stall_timeout: float = 30.0) -> Iterator[int]:
        """Yield ``req``'s tokens as they are produced, driving the pump
        between yields (single-threaded streaming iterator).

        Empty pumps back off (``poll_interval`` doubling to 50 ms) instead
        of busy-spinning the host, and ``stall_timeout`` seconds of zero
        progress raise with the queue/engine state an operator needs —
        not a bare spin counter."""
        emitted = 0
        idle_since: Optional[float] = None
        delay = poll_interval
        while True:
            while emitted < len(req.tokens_out):
                yield req.tokens_out[emitted]
                emitted += 1
            if req.done:
                self.drain()
                return
            if self.step():
                idle_since = None
                delay = poll_interval
                continue
            # no-op pump: nothing running, nothing admitted — wall-clock
            # (not the injectable SLO clock) bounds the wait for work to
            # appear before declaring the stream wedged
            now = time.monotonic()
            if idle_since is None:
                idle_since = now
            elif now - idle_since > stall_timeout:
                eng = self.engine
                raise RuntimeError(
                    f"stream stalled {stall_timeout:.2f}s with no engine "
                    f"progress: request uid={req.uid} "
                    f"state={req.state.value} "
                    f"tokens_out={len(req.tokens_out)}/"
                    f"{req.max_new_tokens}; queue_depth={len(self.queue)} "
                    f"running={len(self._running)} free_blocks="
                    f"{eng.state.allocator.free_blocks} free_sequences="
                    f"{eng.config.max_sequences - len(eng.state.seqs)} — "
                    f"was the request submitted to THIS frontend?")
            time.sleep(delay)
            delay = min(delay * 2, 0.05)

    def emit_metrics(self, step: Optional[int] = None) -> None:
        self.metrics.emit(self.monitor, self.cache,
                          step if step is not None
                          else self.metrics.counters["engine_steps"])

    def metrics_text(self) -> str:
        """Prometheus text exposition of the process-wide registry (the
        ``serving/*`` series plus anything else recorded in-process) —
        wire this to a ``/metrics`` HTTP handler."""
        return telemetry.metrics_text()

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = dict(self.metrics.counters)
        out["ttft"] = self.metrics.ttft.summary()
        out["tpot"] = self.metrics.tpot.summary()
        out["queue_depth"] = len(self.queue)
        out["running"] = len(self._running)
        if self.cache is not None:
            out["prefix_hit_rate"] = self.cache.hit_rate
            out["prefix_pages_cached"] = self.cache.pages_cached
            out["prefix_evict_calls"] = self.cache.evict_calls
            out["prefix_evict_scans"] = self.cache.evict_scans
            out["prefix_pages_evicted"] = self.cache.pages_evicted
        if self.kvtier is not None:
            out["kvtier"] = self.kvtier.stats()
        if self._slo is not None:
            out["slo"] = self._slo.summary()
        return out
