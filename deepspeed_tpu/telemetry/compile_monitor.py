"""XLA compilation observability: compile counts/durations and a
recompilation-storm detector.

JAX recompiles silently — a drifting input shape, a weak-typed scalar, or
a serving request outside every bucket each cost seconds-to-minutes of
XLA time that show up only as mysterious step-time spikes. This module
makes each compile loud and attributable:

- ``install()`` subscribes to :mod:`jax.monitoring` duration events
  (``/jax/core/compile/backend_compile_duration`` et al.), mirroring them
  into ``compile/count`` + ``compile/time_ms`` registry metrics and
  tracer complete-spans.
- Per-function attribution: ``jax.monitoring`` events carry no function
  identity, so call sites mark cache misses explicitly via
  :meth:`count_trace` (e.g. ``inference/engine_v2`` on a jit-cache-key
  miss, attributing the compile to the request's bucket shape) or wrap a
  function with :meth:`instrument` — the wrapper body only executes while
  jax is *tracing*, i.e. exactly once per compilation cache miss.
- The scope table: a named step program registers, at its cache miss,
  what is needed to lower it again (:meth:`register_program`: the jitted
  function, held WEAKLY unless the tracer is on, and the ABSTRACT
  arguments of a call). On demand, :meth:`scopes` compiles it once more
  and returns ``{instruction name: {"scope", "backward", "remat",
  "inherited"}}`` from the optimized HLO's ``op_name`` metadata, so a
  device trace can be summed under the program's own ``jax.named_scope``
  names. Nothing is lowered until asked.
- Storm detection: when one function/site retraces more than
  ``storm_threshold`` times, a single loud warning fires and the storm is
  recorded for the flight recorder / ``dstpu-doctor``.
"""

import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

from deepspeed_tpu.utils.logging import logger

DEFAULT_STORM_THRESHOLD = 8

#: jax.monitoring duration events that mean "time spent compiling"
_COMPILE_EVENT_MARKERS = ("compile", "lowering", "jaxpr_to_mlir")


class CompileMonitor:
    """Process-wide compile tracker (counterpart of ``tracer``/``registry``)."""

    def __init__(self, storm_threshold: int = DEFAULT_STORM_THRESHOLD):
        self._lock = threading.Lock()
        self.storm_threshold = storm_threshold
        self._installed = False
        # jax.monitoring offers no per-listener unregister (only a global
        # clear), so the listener stays registered and checks this flag
        self._active = False
        self._events: Dict[str, Dict[str, float]] = {}
        self._functions: Dict[str, int] = {}
        self._details: Dict[str, List[Any]] = {}
        self._storms: List[str] = []
        #: name -> (weakref to the jitted function, abstract args), and the
        #: functions held strongly while the tracer is on: register_program
        self._programs: Dict[str, Any] = {}
        self._held: Dict[str, Callable] = {}
        self._scope_tables: Dict[str, Dict[str, Dict[str, Any]]] = {}

    # -- jax.monitoring bridge ----------------------------------------------

    def install(self, storm_threshold: Optional[int] = None) -> None:
        """Subscribe to jax compile-duration events. Idempotent."""
        if storm_threshold is not None:
            self.storm_threshold = storm_threshold
        self._active = True
        if self._installed:
            return
        self._installed = True
        try:
            from jax import monitoring as jax_monitoring
            jax_monitoring.register_event_duration_secs_listener(
                self._on_event_duration)
        except Exception as e:  # pragma: no cover - very old jax
            logger.warning(f"compile monitor: jax.monitoring unavailable "
                           f"({e}); only explicit count_trace/instrument "
                           f"call sites will be tracked")

    def uninstall(self) -> None:
        self._active = False

    def _on_event_duration(self, event: str, duration_secs: float,
                           **kwargs: Any) -> None:
        if not self._active:
            return
        if not any(m in event for m in _COMPILE_EVENT_MARKERS):
            return
        short = event.rsplit("/", 1)[-1]
        with self._lock:
            agg = self._events.setdefault(short, {"count": 0, "time_ms": 0.0})
            agg["count"] += 1
            agg["time_ms"] += duration_secs * 1e3
        try:
            from deepspeed_tpu.telemetry.registry import registry
            registry.counter("compile/count").inc()
            registry.histogram("compile/time_ms", lo=0.01,
                               hi=600_000.0).record(duration_secs * 1e3)
        except Exception:
            pass
        try:
            from deepspeed_tpu.telemetry.tracer import tracer
            now = tracer.now()
            tracer.complete(f"compile/{short}", now - duration_secs, now)
        except Exception:
            pass

    # -- per-function attribution -------------------------------------------

    def count_trace(self, name: str, detail: Any = None) -> int:
        """Record one (re)compilation of ``name``; returns the new count.
        ``detail`` (e.g. the serving bucket shape that missed the jit
        cache) is kept so ``dstpu-doctor`` can show *what* keeps changing."""
        with self._lock:
            n = self._functions.get(name, 0) + 1
            self._functions[name] = n
            if detail is not None:
                self._details.setdefault(name, []).append(detail)
                del self._details[name][:-16]
            storm = n == self.storm_threshold + 1 and name not in self._storms
            if storm:
                self._storms.append(name)
            details = list(self._details.get(name, ()))
        try:
            from deepspeed_tpu.telemetry.registry import registry
            registry.counter(f"compile/retrace/{name}").inc()
        except Exception:
            pass
        if storm:
            logger.warning(
                f"RECOMPILATION STORM: {name!r} has been traced {n} times "
                f"(threshold {self.storm_threshold}) — every retrace pays "
                f"full XLA compile time. Recent trigger details: "
                f"{details or 'n/a'}. Check for drifting shapes, weak-typed "
                f"scalars, or serving requests that fall outside every "
                f"bucket.")
            try:
                from deepspeed_tpu.telemetry.flight_recorder import \
                    flight_recorder
                flight_recorder.record_event("recompile_storm", name=name,
                                             count=n, details=details)
            except Exception:
                pass
            try:
                from deepspeed_tpu.telemetry.tracer import tracer
                tracer.instant(f"compile/storm/{name}")
            except Exception:
                pass
        return n

    def instrument(self, fn: Callable, name: Optional[str] = None) -> Callable:
        """Wrap ``fn`` so each jax *trace* of it is counted. The wrapper
        body runs only while jax traces (cache miss / retrace); cached
        executions never enter it, so steady state pays nothing."""
        label = name or getattr(fn, "__name__", repr(fn))

        def traced(*args, **kwargs):
            self.count_trace(label)
            return fn(*args, **kwargs)

        traced.__name__ = getattr(fn, "__name__", "traced")
        traced.__wrapped__ = fn
        return traced

    # -- the scope table ------------------------------------------------------

    def register_program(self, name: str, jitted: Callable,
                         args: tuple) -> None:
        """Remember how to lower the named step program again: the jitted
        function and the abstract form (``explain.abstractify``: shape,
        dtype, mesh sharding) of the arguments of one call. Nothing is
        lowered here and no argument buffer is kept.

        The function belongs to its engine, and its closure may hold that
        engine and so its device state (a serving step program's holds the
        model's statics alone), so the function is held WEAKLY: a process
        that drops an engine frees it, registered or not. Only while the
        tracer is on is it held strongly (:meth:`hold_programs`): a traced
        run asks for
        the table after its work, when the caller may hold the engine no
        longer (the benchmark's readers run after the runner returned).
        One name is one compiled module: the name is the one the trace's
        ``XLA Modules`` line shows after ``jit_``."""
        from deepspeed_tpu.telemetry.explain import abstractify
        from deepspeed_tpu.telemetry.tracer import tracer
        with self._lock:
            self._programs[name] = (weakref.ref(jitted), abstractify(args))
            self._scope_tables.pop(name, None)
            self._held.pop(name, None)
            if tracer.enabled:
                self._held[name] = jitted

    def hold_programs(self, hold: bool) -> None:
        """The tracer was switched on (``hold``) or off: keep every
        registered step program that is still alive until its table was
        asked for or the tracer goes off, or let them all go.
        ``tracer.configure`` calls this; nothing else needs to."""
        with self._lock:
            if not hold:
                self._held.clear()
                return
            for name, (ref, _args) in self._programs.items():
                jitted = ref()
                if jitted is not None and name not in self._scope_tables:
                    self._held[name] = jitted

    def programs(self) -> List[str]:
        """Names :meth:`scopes` can answer for: a table was made, or the
        program's function is still alive."""
        with self._lock:
            return sorted(n for n, (ref, _args) in self._programs.items()
                          if n in self._scope_tables or ref() is not None)

    def scopes(self, program: str) -> Dict[str, Dict[str, Any]]:
        """``{instruction name: {"scope", "backward", "remat",
        "inherited"}}`` of a registered program
        (``telemetry/explain.scope_table_from_hlo``); raises ``KeyError``
        for a name never registered, or whose function was freed before a
        table was asked for (the tracer was off).

        The first ask compiles the program again, under a persistent-cache
        key that holds the metadata (:func:`_compile_with_current_metadata`):
        jax leaves metadata out of the cache's key, so the executable that
        ran may have been compiled before a scope existed, with the old
        ``op_name``s. Same input, same compiler: the instruction names are
        those of the executable that ran. On a v5e 4 to 23 s for the first
        traced run of a source in its directory, under half a second for
        the next (it reads the entry back; PERF.md, PR 28); callers ask
        after their measurement, never on the step path. The table stays;
        the function is let go."""
        with self._lock:
            if program in self._scope_tables:
                return self._scope_tables[program]
            ref, args = self._programs[program]
            jitted = ref()
        if jitted is None:
            raise KeyError(f"{program}: its function was freed before a "
                           f"scope table was asked for")
        from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
        table = scope_table_from_hlo(
            _compile_with_current_metadata(jitted, args))
        with self._lock:
            self._scope_tables[program] = table
            self._held.pop(program, None)
        return table

    # -- export --------------------------------------------------------------

    def retrace_count(self, name: str) -> int:
        with self._lock:
            return self._functions.get(name, 0)

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "events": {k: dict(v) for k, v in self._events.items()},
                "functions": dict(self._functions),
                "details": {k: list(v) for k, v in self._details.items()},
                "storms": list(self._storms),
                "storm_threshold": self.storm_threshold,
            }

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._functions.clear()
            self._details.clear()
            del self._storms[:]
            self._programs.clear()
            self._held.clear()
            self._scope_tables.clear()


def _compile_with_current_metadata(jitted: Callable, args: tuple) -> str:
    """Optimized HLO text of ``jitted`` for ``args`` whose ``op_name``s are
    those of the source as it is now. Two caches stand in the way, and
    neither is switched off:

    - the persistent compilation cache, whose key leaves metadata out by
      default, so a hit may carry another source's ``op_name``s. For this
      one compile, in this thread only (a jax config context, so a server
      thread compiling at the same moment keeps its keys), the key holds
      the metadata: a hit under it was compiled from these very scopes, a
      miss compiles and leaves the entry for the next traced run.
    - the executable the jitted function already holds in memory for these
      arguments, the very one that may have come from the persistent cache
      under the default key (0.0 s and a stale table on the chip, PR 28).
      A compiler option set to its own default changes nothing in the
      program and makes jax build the executable anew.

    Verified against jax 0.9.0 (``jax._src.config`` is where the context
    manager of ``jax_compilation_cache_include_metadata_in_key`` lives;
    tests/test_scopes.py stages the stale entry and counts the compile)."""
    from jax._src import config as jax_config
    with jax_config.compilation_cache_include_metadata_in_key(True):
        return jitted.lower(*args).compile(
            compiler_options={"xla_dump_hlo_as_text": False}).as_text()


#: process-wide compile monitor
compile_monitor = CompileMonitor()
