"""Span tracer: nestable context-manager spans over a thread-safe ring buffer.

The host-side companion to ``jax.profiler``: XLA's profiler sees device
programs, but "where did step time go" on the *host* — admission, batch
placement, host optimizer sweeps, monitor flushes — is invisible to it.
Spans recorded here export as Chrome/Perfetto trace-event JSON
(``chrome://tracing`` / https://ui.perfetto.dev) and, when
``jax_annotations`` is on, additionally enter
``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation`` so the same
names line up inside a real profiler capture.

Design constraints:
- disabled tracing must be near-free (one attribute check per span);
- recording must never allocate unboundedly (fixed-size ring buffer,
  oldest events evicted, eviction counted);
- spans may be emitted retroactively (:meth:`Tracer.complete`) for
  lifecycles that cross call boundaries, e.g. serving requests.
"""

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

DEFAULT_BUFFER_EVENTS = 100_000


class _NoSpan:
    """What :meth:`Tracer.span` hands out while tracing is off: one
    shared, stateless context manager, so a disabled span costs a call
    and an attribute check."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _NoSpan()


class _Span:
    """One span being recorded (:meth:`Tracer.span` with tracing on)."""
    __slots__ = ("tracer", "name", "step", "ctx", "args", "ann", "t0")

    def __enter__(self):
        # the fewest calls between the last sibling's end and the
        # annotation's start: under a capture every call here is idle
        # time that no span accounts for
        ann, tracer = None, self.tracer
        if tracer.jax_annotations:
            profiler = tracer._jprof or tracer._profiler()
            if profiler is not None:
                ann = profiler.TraceAnnotation(self.name) \
                    if self.step is None else \
                    profiler.StepTraceAnnotation(self.name,
                                                 step_num=self.step)
                ann.__enter__()
        self.ann = ann
        self.t0 = time.perf_counter()
        return self.args

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        try:
            tracer, args = self.tracer, self.args
            if self.step is not None:
                args["step"] = self.step
            if self.ctx is not None:
                for key, tag in self.ctx.tags().items():
                    args.setdefault(key, tag)
            ev = tracer._event(self.name, "X",
                               (self.t0 - tracer._t0) * 1e6, None, args)
            ev["dur"] = (t1 - self.t0) * 1e6
            tracer._append(ev)
        finally:
            # last, so that the annotation covers the recording (siblings
            # then tile their parent), and whatever the recording raised
            if self.ann is not None:
                self.ann.__exit__(None, None, None)
        return False


class Tracer:
    """Thread-safe trace-event recorder (Chrome trace-event format).

    Events are stored as plain dicts in the on-disk schema, so
    :meth:`dump` is a serialization, not a conversion. Complete spans use
    ``ph="X"`` (ts/dur in microseconds), instants use ``ph="i"``.
    """

    def __init__(self, buffer_events: int = DEFAULT_BUFFER_EVENTS):
        self.enabled = False
        self.jax_annotations = False
        self._t0 = time.perf_counter()
        self._pid = os.getpid()
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=buffer_events)
        self._dropped = 0
        self._jprof = None

    # -- configuration ------------------------------------------------------

    def configure(self, enabled: Optional[bool] = None,
                  buffer_events: Optional[int] = None,
                  jax_annotations: Optional[bool] = None) -> None:
        with self._lock:
            if buffer_events is not None and \
                    buffer_events != self._buf.maxlen:
                self._buf = deque(self._buf, maxlen=max(1, buffer_events))
            if enabled is not None:
                self.enabled = bool(enabled)
            if jax_annotations is not None:
                self.jax_annotations = bool(jax_annotations)
        if enabled is not None:
            # a traced run sums its device time under the step programs'
            # scopes after the work: the monitor keeps the programs alive
            # for that while tracing is on, and only then
            from deepspeed_tpu.telemetry.compile_monitor import \
                compile_monitor
            compile_monitor.hold_programs(self.enabled)

    def now(self) -> float:
        """Seconds on the tracer's clock (``time.perf_counter``)."""
        return time.perf_counter()

    # -- recording ----------------------------------------------------------

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self._dropped += 1
                dropped = True
            else:
                dropped = False
            self._buf.append(ev)
        if dropped:
            # ring wrap is data loss for the post-mortem — announce it
            # (dstpu-doctor reads trace/ring_dropped from the black box)
            try:
                from deepspeed_tpu.telemetry.registry import registry
                registry.counter(
                    "trace/ring_dropped",
                    help="span events evicted by tracer ring wrap").inc()
            except Exception:                            # noqa: BLE001
                pass

    def ingest(self, events: List[Dict[str, Any]]) -> None:
        """Append pre-formed trace-event dicts (the tail-sampler's flush
        path: a retained request's buffered spans enter the ring here).
        Ring bounds and drop accounting apply as for live spans."""
        for ev in events:
            self._append(ev)

    def _event(self, name: str, ph: str, ts_us: float,
               tid: Optional[int], args: Dict[str, Any]) -> Dict[str, Any]:
        ev: Dict[str, Any] = {
            "name": name, "ph": ph, "cat": "dstpu",
            "ts": ts_us, "pid": self._pid,
            "tid": threading.get_ident() if tid is None else tid,
        }
        if args:
            ev["args"] = args
        return ev

    def _profiler(self):
        """``jax.profiler`` for the annotation passthrough, None where jax
        is unavailable; looked up once, not once a span. Annotations are
        inert outside an active profiler capture, so entering them
        unconditionally is safe."""
        if self._jprof is None:
            try:
                from jax import profiler
            except Exception:
                return None
            self._jprof = profiler
        return self._jprof

    def span(self, name: str, step: Optional[int] = None, ctx=None, **args):
        """Record the enclosed block as a complete span (``with
        tracer.span(...) as args:``). Nestable; nesting
        is reconstructed from ts/dur containment (same pid/tid), which is
        how Chrome/Perfetto render the flame graph. ``ctx`` (a
        :class:`~deepspeed_tpu.telemetry.reqtrace.TraceContext`) stamps
        the span with trace_id/span_id/parent_span_id args so it joins a
        request-scoped distributed trace.

        The block gets the span's argument dict (``None`` while tracing is
        off): what is known only when the block ends -- which program a
        step ran -- is added there, behind an ``is not None`` check so that
        nothing is computed for a disabled tracer. A dict that held an
        argument when the block ended IS the recorded event's ``args``,
        so it may still be completed right after (a launch's work is
        counted after its jitted call).

        Spans that follow one another TILE their parent: the profiler
        annotation opens before the clock is read and closes after the
        event is in the ring, so what lies between two siblings is the
        two ``with`` statements and nothing of the tracer's own."""
        if not self.enabled:
            return _OFF
        span = _Span()      # filled here: an __init__ is one call more
        span.tracer, span.name, span.step = self, name, step
        span.ctx, span.args = ctx, args
        return span

    def instant(self, name: str, tid: Optional[int] = None, ctx=None,
                **args) -> None:
        """Record a zero-duration marker (ph='i', thread-scoped)."""
        if not self.enabled:
            return
        if ctx is not None:
            args = {**ctx.tags(), **args}
        ev = self._event(name, "i",
                         (time.perf_counter() - self._t0) * 1e6, tid, args)
        ev["s"] = "t"
        self._append(ev)

    def complete(self, name: str, start: float, end: float,
                 tid: Optional[int] = None, ctx=None, **args) -> None:
        """Record a span retroactively from ``start``/``end`` timestamps in
        seconds on the tracer's clock (or any CLOCK_MONOTONIC-derived clock
        — ``time.monotonic`` stamps from the serving frontend align on
        Linux). Used for lifecycles that cross call boundaries."""
        if not self.enabled:
            return
        if ctx is not None:
            args = {**ctx.tags(), **args}
        ev = self._event(name, "X", (start - self._t0) * 1e6, tid, args)
        ev["dur"] = max(0.0, (end - start) * 1e6)
        self._append(ev)

    # -- export -------------------------------------------------------------

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._buf)

    @property
    def dropped(self) -> int:
        return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._dropped = 0

    def to_chrome_trace(self) -> Dict[str, Any]:
        evs = sorted(self.events(), key=lambda e: e.get("ts", 0.0))
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "otherData": {"tracer": "deepspeed_tpu.telemetry",
                              "dropped_events": self._dropped}}

    def dump(self, path: str) -> str:
        """Write the Chrome trace-event JSON to ``path`` (parent dirs
        created). Load it in chrome://tracing or ui.perfetto.dev."""
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as fh:
            json.dump(self.to_chrome_trace(), fh)
        return path


#: process-wide tracer (the engine, comm layer, and serving frontend all
#: record here; ``deepspeed_tpu.telemetry.configure`` enables it)
tracer = Tracer()
