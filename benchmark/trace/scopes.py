"""The device's time under the program's own names, and the host's under
the program's own spans (PR 28). Beside ``reduce.py``, which it only calls.

The program names its step programs (``jit_serve_split_r64_c128``,
``jit_fused_step``), carries ``jax.named_scope``s from one fixed vocabulary
at its layer-part boundaries, and gives, on demand, a table
``{instruction name: {"scope", "backward", "remat"}}`` per program
(``deepspeed_tpu.telemetry.compile_monitor.scopes``; it compiles the
program once more, after the window). Here a device operation is put down
to the program whose ``XLA Modules`` event encloses it in time, then to its
scope by that table; self times (``reduce.self_times``) make the rows a
partition of the device's busy time, ``(no scope)`` included.

A program that lacks all this (the parent of the PR that added it) gives no
table, no span and no counter: every reader here then returns None and the
line leaves the metric out. ``docs``: ``benchmark/SCOPES.md``.

The first reader that asks prints two lines for a human, once per run:
``device_by_scope`` and ``host_spans``."""

import bisect
import json
import re
import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from benchmark.trace import reduce

MODULES_LINE = "XLA Modules"
NO_SCOPE = "(no scope)"
_MODULE = re.compile(r"^jit_(.*?)(?:\(\d+\))?$")
_CONTROL_FLOW = ("while", "conditional", "call")
#: the program's host spans, parents before children (SCOPES.md)
PROGRAM_SPANS = (
    "train/step", "train/batch", "train/dispatch", "train/bookkeeping",
    "serving/step", "serving/admit", "serving/engine_step", "serving/pack",
    "serving/dispatch", "serving/fetch", "serving/fanout")


# -- the program's side, asked for and never assumed -------------------------

def program_tables(programs: Iterable[str]) -> Dict[str, dict]:
    """Scope tables of those of ``programs`` the program registered; empty
    where the program has no scope table at all."""
    try:
        from deepspeed_tpu.telemetry import compile_monitor
        known = set(compile_monitor.programs())
    except (ImportError, AttributeError):
        return {}
    return {p: compile_monitor.scopes(p) for p in programs if p in known}


def program_events(run) -> List[dict]:
    """The program's tracer events of the run: the runner's own copy
    (``facts["spans"]``) or, for a runner that keeps none, what the
    process-wide tracer still holds."""
    spans = run.facts.get("spans")
    if spans:
        return spans
    try:
        from deepspeed_tpu.telemetry import tracer
    except ImportError:
        return []
    return tracer.events()


def counter_value(name: str) -> Optional[float]:
    """A program counter's value, None where the program has none."""
    try:
        from deepspeed_tpu.telemetry.registry import registry
    except ImportError:
        return None
    metric = registry.get(name)
    return None if metric is None else float(metric.value)


# -- device time by program and scope ----------------------------------------

def module_name(event_name: str) -> str:
    """``jit_serve_split_r64_c128(1234)`` -> ``serve_split_r64_c128``."""
    m = _MODULE.match(event_name)
    return m.group(1) if m else event_name


def attribute(trace: dict, window: Optional[Tuple[float, float]],
              tables: Callable[[Iterable[str]], Dict[str, dict]],
              device: int = 0) -> Optional[dict]:
    """Self time of the device's operations that start inside ``window``,
    by (program, scope, forward / backward / remat).

    Returns ``{"rows": {(program, scope, kind): ns}, "inherited_rows":
    the same keys, "ops": {(program, instruction): [ns, scope, kind,
    inherited]}, "sum_ns", "scoped_ns", "inherited_ns", "remat_ns",
    "programs": [...], "tables_s"}`` or None without a device plane.
    "Inherited" is the time of operations whose scope the table's
    heuristic assigned (from the computation a fusion calls, or from its
    users) and not the compiler's own metadata: ``rows`` and ``scoped_ns``
    hold it, ``inherited_rows`` and ``inherited_ns`` say how much it is.
    ``tables`` is asked once, for the programs the ``XLA Modules`` line
    names; ``tables_s`` is how long it took to answer (the program compiles
    each once more: what a traced run pays, after its window)."""
    for i, plane in reduce.device_planes(trace):
        if i != device:
            continue
        mods = sorted((e[1], e[1] + e[2], module_name(e[0]))
                      for e in reduce.line_events(plane, MODULES_LINE))
        starts = [m[0] for m in mods]
        asked = time.monotonic()
        table = tables(sorted({m[2] for m in mods}))
        tables_s = time.monotonic() - asked
        rows: Dict[tuple, float] = {}
        handed_rows: Dict[tuple, float] = {}
        ops: Dict[tuple, list] = {}
        total = scoped = inherited = remat = 0.0
        for ev, self_ns in reduce.self_times(
                reduce.line_events(plane, reduce.OPS_LINE)):
            if window and not (window[0] <= ev[1] < window[1]):
                continue
            k = bisect.bisect_right(starts, ev[1]) - 1
            program = mods[k][2] if k >= 0 and ev[1] < mods[k][1] \
                else "(no module)"
            instr = reduce.op_name(ev)
            entry = table.get(program, {}).get(instr) or {}
            scope = entry.get("scope") or NO_SCOPE
            kind = "remat" if entry.get("remat") else \
                "backward" if entry.get("backward") else "forward"
            handed = bool(entry.get("inherited")) and scope != NO_SCOPE
            key = (program, scope, kind)
            rows[key] = rows.get(key, 0.0) + self_ns
            rec = ops.setdefault((program, instr),
                                 [0.0, scope, kind, handed])
            rec[0] += self_ns
            total += self_ns
            if scope != NO_SCOPE:
                scoped += self_ns
            if handed:
                handed_rows[key] = handed_rows.get(key, 0.0) + self_ns
                inherited += self_ns
            if kind == "remat":
                remat += self_ns
        if not total:
            return None
        return {"rows": rows, "inherited_rows": handed_rows, "ops": ops,
                "sum_ns": total,
                "scoped_ns": scoped, "inherited_ns": inherited,
                "remat_ns": remat,
                "programs": sorted(table), "tables_s": tables_s}
    return None


def exposed_collective_ns(trace: dict,
                          window: Optional[Tuple[float, float]] = None,
                          device: int = 0) -> Tuple[float, int]:
    """(nanoseconds in which a collective was in flight on the device and
    NO other operation ran, number of collective events). Collectives as
    ``reduce.collective_seconds`` finds them (synchronous ones on the ops
    line, start-to-done spans on the async line); the other operations are
    the ops line's events that are neither collectives nor control flow
    (a ``while`` spans its body). In flight and not hidden: the union of
    both less the others' own union."""
    for i, plane in reduce.device_planes(trace):
        if i != device:
            continue

        def collective(e):
            return bool(reduce.COLLECTIVE.search(reduce.op_name(e)) or
                        reduce.COLLECTIVE.search(reduce.opcode(e)))
        ops = reduce.line_events(plane, reduce.OPS_LINE)
        coll = [e for e in ops if collective(e)] + \
            [e for e in reduce.line_events(plane, reduce.ASYNC_LINE)
             if collective(e)]
        other = [e for e in ops if not collective(e) and
                 reduce.opcode(e) not in _CONTROL_FLOW]
        lo, hi = window if window else (float("-inf"), float("inf"))
        c = reduce.clipped(coll, lo, hi)
        o = reduce.clipped(other, lo, hi)
        return reduce.union_ns(c + o) - reduce.union_ns(o), len(coll)
    return 0.0, 0


# -- host time by the program's spans ----------------------------------------

def spans_named(events: List[dict], name: str) -> List[dict]:
    return sorted((e for e in events
                   if e.get("name") == name and e.get("ph") == "X"),
                  key=lambda e: e["ts"])


def children(events: List[dict], parent: dict, name: str) -> List[dict]:
    """Spans of that name inside ``parent`` (same thread, by time)."""
    t0, t1 = parent["ts"], parent["ts"] + parent["dur"]
    return [e for e in spans_named(events, name)
            if e.get("tid") == parent.get("tid") and
            t0 <= e["ts"] and e["ts"] + e["dur"] <= t1 + 1e-3]


def span_self_ms(events: List[dict]) -> Dict[str, List[float]]:
    """Self time in ms of every program span, by name: its duration less
    what the program spans nested inside it (same thread) cover."""
    known = [e for e in events if e.get("ph") == "X" and
             e.get("name") in PROGRAM_SPANS]
    out: Dict[str, List[float]] = {}
    by_tid: Dict[object, List[dict]] = {}
    for e in known:
        by_tid.setdefault(e.get("tid"), []).append(e)
    for evs in by_tid.values():
        as_events = [[e["name"], e["ts"], e["dur"], {}] for e in evs]
        for ev, self_us in reduce.self_times(as_events):
            out.setdefault(ev[0], []).append(self_us / 1e3)
    return out


# -- one analysis a run, shared by the readers -------------------------------

def analysis(run) -> dict:
    """Everything the PR-28 readers take from one run, computed once and
    kept on the view: ``device`` (:func:`attribute` over the traced
    window, or None), ``steps`` (host spans of the runner's name in the
    profile), ``events`` (the program's tracer events). Prints the
    ``device_by_scope`` and ``host_spans`` lines."""
    cached = getattr(run, "_scopes_analysis", None)
    if cached is not None:
        return cached
    events = program_events(run)
    device, steps = None, 0
    if run.trace is not None:
        steps = len(reduce.host_events(run.trace, run.span_name))
        device = attribute(
            run.trace, reduce.traced_window(run.trace, run.span_name),
            program_tables)
    out = {"device": device, "steps": steps, "events": events}
    run._scopes_analysis = out
    for line in report_lines(run, out):
        print(json.dumps(line), flush=True)
    return out


def report_lines(run, a: dict) -> List[dict]:
    """``PERF.md`` section 5, printed: where the device's time went under
    the program's names, and the host's under the program's spans."""
    lines = []
    dev = a["device"]
    if dev is not None:
        bw = reduce.busy_and_window(run.trace, run.span_name)
        heavy = sorted(dev["ops"].items(), key=lambda kv: -kv[1][0])[:10]
        lines.append({
            "phase": "device_by_scope", "steps": a["steps"],
            "busy_s": bw[0] if bw else None, "sum_s": dev["sum_ns"] / 1e9,
            "tables": dev["programs"], "tables_s": dev["tables_s"],
            # of sum_s: under a scope at all, and under one the table's
            # heuristic handed down (no vocabulary word of its own)
            "scoped_s": dev["scoped_ns"] / 1e9,
            "inherited_s": dev["inherited_ns"] / 1e9,
            # [program, scope, kind, seconds, of which inherited]
            "rows": [[p, s, k, ns / 1e9,
                      dev["inherited_rows"].get((p, s, k), 0.0) / 1e9]
                     for (p, s, k), ns in
                     sorted(dev["rows"].items(), key=lambda kv: -kv[1])],
            "heaviest": [[p, i[:64], rec[1], rec[2], rec[0] / 1e9,
                          "inherited" if rec[3] else "own"]
                         for (p, i), rec in heavy]})
    selfs = span_self_ms(a["events"])
    if selfs:
        present = [n for n in PROGRAM_SPANS if n in selfs]
        gaps = reduce.idle_gaps(run.trace, present, 10) \
            if run.trace is not None else []
        launched: Dict[str, int] = {}
        for e in spans_named(a["events"], "serving/dispatch"):
            kind = e.get("args", {}).get("program")
            launched[kind] = launched.get(kind, 0) + 1
        line = {"phase": "host_spans",
                "self_ms_median": {n: run.stats.median(selfs[n])
                                   for n in present},
                "count": {n: len(selfs[n]) for n in present},
                "idle_gaps": gaps}
        if launched:        # the step programs the spans launched, by kind
            line["programs"] = launched
        lines.append(line)
    return lines


# -- what the readers share ---------------------------------------------------

def scope_ms_per_step(run, scopes: Iterable[str]) -> Optional[float]:
    """Device self time under those scopes (forward, backward and remat
    together) per traced step, in ms; None without a device trace, traced
    steps or a scope table."""
    a = analysis(run)
    dev = a["device"]
    if dev is None or not a["steps"] or not dev["scoped_ns"]:
        return None
    want = set(scopes)
    ns = sum(v for (_p, s, _k), v in dev["rows"].items() if s in want)
    return ns / 1e6 / a["steps"]


def scope_coverage(run) -> Optional[float]:
    """Share of the device's busy time, in %, whose operation maps to a
    vocabulary scope, the table's inherited assignments included (the
    ``device_by_scope`` line says how much of it they are). Reads low
    where a table is stale or a scope is missing; None without a device
    trace or any table."""
    dev = analysis(run)["device"]
    if dev is None or not dev["programs"]:
        return None
    return 100.0 * dev["scoped_ns"] / dev["sum_ns"]


def counters_with_prefix(prefix: str) -> Dict[str, float]:
    """``{name after the prefix: value}`` of the program's counters whose
    name starts with ``prefix``; empty where the program has none."""
    try:
        from deepspeed_tpu.telemetry.registry import registry
    except ImportError:
        return {}
    return {n[len(prefix):]: float(registry.get(n).value)
            for n in registry.names() if n.startswith(prefix)}


def counter_ratio(numerator: str, denominator: str) -> Optional[float]:
    """100 x one program counter over another, both since the process
    began; None where the program has not both or the second is 0."""
    num, den = counter_value(numerator), counter_value(denominator)
    if num is None or not den:
        return None
    return 100.0 * num / den
