"""XLA compilation observability: compile counts/durations and a
recompilation-storm detector.

JAX recompiles silently — a drifting input shape, a weak-typed scalar, or
a serving request outside every bucket each cost seconds-to-minutes of
XLA time that show up only as mysterious step-time spikes. This module
makes each compile loud and attributable:

- ``install()`` subscribes to :mod:`jax.monitoring`'s three compile
  duration events (trace, lowering, backend) and the persistent cache's
  four, mirroring the three into ``compile/count`` + ``compile/time_ms``
  registry metrics and tracer complete-spans.
- The build record: the three events carry the function's name
  (``fun_name``; jax 0.9.0), so every BUILD of a program that
  :meth:`register_program` knows — one trace, one lowering, one backend
  compile or load from the persistent cache — is one row of
  ``summary()["programs"]`` and one step of seven always-on counters
  (:meth:`_record_phase`). Call sites still mark jit-cache misses via
  :meth:`count_trace` (e.g. ``inference/engine_v2``, attributing the
  compile to the request's bucket shape: the ``detail`` a storm prints).
- Set-up by part: :func:`setup_part` times a block of an engine's
  construction into ``setup/<part>_seconds`` and a ``setup/<part>`` span.
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

import contextlib
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

from deepspeed_tpu.telemetry.registry import registry
from deepspeed_tpu.telemetry.tracer import tracer
from deepspeed_tpu.utils.logging import logger

DEFAULT_STORM_THRESHOLD = 8

#: the jax.monitoring events the monitor takes, BY NAME (jax 0.9.0,
#: ``jax._src.dispatch`` / ``compiler``): the three phases of a build and
#: the key of a build row each fills ...
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s"}
#: ... and the persistent cache's, which carry no name: they fire in the
#: compiling thread INSIDE the backend interval, so they belong to the
#: backend event that follows them in that thread. A request that is no
#: hit is a miss (``cache_misses`` itself fires only when the entry is
#: large and slow enough to be written)
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/cache_hits": "hit"}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_PARTS = tuple(_PHASES.values())
#: build rows kept a program, newest last
RECENT_BUILDS = 4
#: the row of every build under a name :meth:`register_program` never saw
OTHER = "other"


def _program_name(fun_name: Any) -> str:
    """``jit(serve_split_r64_c128)`` (lowering, backend) or
    ``serve_split_r64_c128`` (trace) -> ``serve_split_r64_c128``."""
    name = str(fun_name or "")
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name[4:] if name.startswith("jit_") else name


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
        #: the build record (:meth:`_record_phase`): a row a name, and a
        #: compiling thread's open build and cache answer
        self._builds: Dict[str, Dict[str, Any]] = {}
        self._thread = threading.local()

    # -- jax.monitoring bridge ----------------------------------------------

    def install(self, storm_threshold: Optional[int] = None) -> None:
        """Subscribe to jax's compile and persistent-cache events.
        Idempotent; the training and the serving engine both call it. The
        listeners run at compile events only, never on a step's path."""
        if storm_threshold is not None:
            self.storm_threshold = storm_threshold
        self._active = True
        _publish_import()
        if self._installed:
            return
        self._installed = True
        try:
            from jax import monitoring as jax_monitoring
            jax_monitoring.register_event_duration_secs_listener(
                self._on_event_duration)
            jax_monitoring.register_event_listener(self._on_event)
        except Exception as e:  # pragma: no cover - very old jax
            logger.warning(f"compile monitor: jax.monitoring unavailable "
                           f"({e}); only explicit count_trace call sites "
                           f"will be tracked")

    def uninstall(self) -> None:
        self._active = False

    def _on_event(self, event: str, **kwargs: Any) -> None:
        answer = _CACHE_EVENTS.get(event)
        if answer is not None and self._active:
            # the request comes first: a hit after it overwrites its miss
            self._thread.__dict__.setdefault("cache", {})["cache"] = answer

    def _on_event_duration(self, event: str, duration_secs: float,
                           **kwargs: Any) -> None:
        if not self._active:
            return
        if event == _CACHE_RETRIEVAL:
            self._thread.__dict__.setdefault("cache", {})["retrieval_s"] = \
                duration_secs
            return
        part = _PHASES.get(event)
        if part is None:
            return
        short = event.rsplit("/", 1)[-1]
        with self._lock:
            agg = self._events.setdefault(short, {"count": 0, "time_ms": 0.0})
            agg["count"] += 1
            agg["time_ms"] += duration_secs * 1e3
        # an unrolled typed stack traces tens of thousands of inner
        # functions a grid (cell 10: PERF.md, PR 54): what an event costs
        # here is paid that often, so nothing is looked up or built twice
        try:
            registry.counter("compile/count").inc()
            registry.histogram("compile/time_ms", lo=0.01,
                               hi=600_000.0).record(duration_secs * 1e3)
            now = tracer.now()
            if tracer.enabled:
                tracer.complete(f"compile/{short}", now - duration_secs, now)
            self._record_phase(part, _program_name(kwargs.get("fun_name")),
                               duration_secs, now)
        except Exception:
            pass

    def _record_phase(self, part: str, name: str, secs: float,
                      now: float) -> None:
        """One phase of one build into the build record. A build of a
        KNOWN program (:meth:`register_program`) opens at its trace event
        (or at its lowering: ``.lower()`` of a traced function traces
        nothing), closes at its backend event and is then one row of
        ``summary()["programs"][name]["recent"]``, one step of the
        ``compile/*`` build counters and, tracer on, one ``compile/build``
        span around its ``compile/<phase>`` spans. The counters hold known
        programs alone, since the process began: ``backend_s`` goes to
        ``compile/load_seconds`` where the persistent cache answered and
        to ``compile/compile_seconds`` where it did not or is off, so a
        warm start reads 0 there.

        Functions traced INSIDE a program (a jitted kernel wrapper,
        ``multiply``) fire trace events of their own before the program's
        and lie inside its duration. They, every other unknown name (a
        parameter tree's ``<lambda>``, a reference's programs) and the
        scope table's compile (:meth:`scopes`) make up the one row
        ``other`` — or, built inside a part of an engine's construction
        (:func:`setup_part`: the parameters' jitted init), the row
        ``setup/<part>``, which says how much of that part was a build:
        counted, never summed into a program or a counter (a ``trace_s``
        there holds a function nested in another once a level)."""
        local = self._thread.__dict__
        cache = local.pop("cache", {}) if part == "backend_s" else {}
        with self._lock:
            known = name in self._programs and not local.get("tabling")
            key = name if known else _open_part() or OTHER
            row = self._builds.get(key)
            if row is None:
                row = self._builds[key] = {"builds": 0,
                                           **dict.fromkeys(_PARTS, 0.0)}
            if not known:
                row[part] += secs
                row["builds"] += part == "backend_s"
                return
        # this thread's open build: (program, start, seconds by phase)
        opened = local.get("build")
        if part == "trace_s" or opened is None or opened[0] != name:
            opened = local["build"] = (name, now - secs,
                                       dict.fromkeys(_PARTS, 0.0))
        _, t0, build = opened
        build[part] += secs
        if part != "backend_s":
            return
        del local["build"]
        build.update(cache=cache.get("cache", "off"),
                     retrieval_s=cache.get("retrieval_s", 0.0), at=now)
        with self._lock:
            row["builds"] += 1
            for k in _PARTS:
                row[k] += build[k]
            row["recent"] = (row.get("recent", []) + [build])[-RECENT_BUILDS:]
        hit = build["cache"] == "hit"
        # all seven at every build: a reader finds 0, not nothing
        for counter, by in (
                ("programs_built", 1), ("trace_seconds", build["trace_s"]),
                ("lower_seconds", build["lower_s"]),
                ("load_seconds", build["backend_s"] if hit else 0.0),
                ("compile_seconds", 0.0 if hit else build["backend_s"]),
                ("cache_hits", int(hit)),
                ("cache_misses", int(build["cache"] == "miss"))):
            registry.counter(f"compile/{counter}").inc(by)
        tracer.complete(
            "compile/build", t0, now, program=name, cache=build["cache"],
            **{k[:-2] + "_ms": 1e3 * build[k] for k in _PARTS})

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
        self._thread.tabling = True     # no build of the program: `other`
        try:
            table = scope_table_from_hlo(
                _compile_with_current_metadata(jitted, args))
        finally:
            self._thread.tabling = False
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
                "programs": {k: dict(v) for k, v in self._builds.items()},
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
            self._builds.clear()


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


_parts = threading.local()


@contextlib.contextmanager
def setup_part(part: str):
    """Time a part of an engine's construction: the block's seconds on
    ``time.perf_counter`` (the tracer's clock) go to the always-on counter
    ``setup/<part>_seconds`` and, tracer on, one ``setup/<part>`` span. A
    part inside a part (``with``, or as a decorator of ``__init__``) is
    taken off the outer one's seconds, so the counters never overlap; the
    spans nest. It times the HOST: a block that only enqueues device work
    (an allocation, a placement) returns before the device has done it."""
    stack = _parts.__dict__.setdefault("open", [])
    stack.append([part, 0.0])   # ... and the seconds of the parts inside it
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        inner = stack.pop()[1]
        if stack:
            stack[-1][1] += t1 - t0
        _publish(part, t0, t1, t1 - t0 - inner)


def _open_part() -> Optional[str]:
    """``setup/<part>`` of the innermost part open in this thread."""
    stack = _parts.__dict__.get("open")
    return f"setup/{stack[-1][0]}" if stack else None


def _publish(part: str, t0: float, t1: float, seconds: float) -> None:
    name = f"setup/{part}"
    registry.counter(name + "_seconds").inc(max(0.0, seconds))
    tracer.complete(name, t0, t1)


def _publish_import() -> None:
    """``setup/import``: the first to the last line of
    ``deepspeed_tpu/__init__.py``, which may not load telemetry itself and
    so leaves its two clock readings for the first ``install()``."""
    import deepspeed_tpu
    t0, t1 = getattr(deepspeed_tpu, "_IMPORT_SPAN", (0.0, 0.0))
    if t1 > t0 and registry.get("setup/import_seconds") is None:
        _publish("import", t0, t1, t1 - t0)


#: process-wide compile monitor
compile_monitor = CompileMonitor()
