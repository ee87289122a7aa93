"""The device's idle time under the host's own names (PR 39).

A serving replica's pump is synchronous: ``device_get`` returns, the tokens
are retired and fanned out, the caller submits, the next batch is admitted,
planned, scheduled, packed and launched, and the pump waits again. While
the host does all that the device has nothing to run, so what each phase
costs shows as idle time of the device BETWEEN two step programs; idle time
INSIDE a program is the device's own (no host change can shorten it).

``partition(run)`` splits the idle time of device 0 inside the traced
window (``reduce.traced_window``: first to last of the runner's own spans)
over the program's LEAF spans, which tile ``serving/step`` from one
program's end to the next launch (``docs/observability.md``, "Tracing
spans"). It works on the loaded profile alone: the spans arrive there as
``TraceAnnotation``s on the host thread's line, on one clock with the
device's operations. Every idle interval (the complement of the union of
the ``XLA Ops`` events) is CUT at the boundaries of the ``XLA Modules``
events and of the host spans of the thread that holds ``serving/step``,
and each piece goes to exactly one part, by OVERLAP (``reduce.idle_gaps``
gives a whole gap to the span over its midpoint, which in a synchronous
pump is the fan-out whatever each phase costs):

- inside a module event → ``in_program``;
- between modules → the innermost leaf span that covers the piece; under
  ``serving/fetch`` the piece is launch latency (``serving/fetch:head``)
  while a step program has yet to begin before that span ends, and the
  wait for ``device_get`` to return after the program's last operation
  (``serving/fetch:tail``) once none has;
- outside every ``serving/step`` and ``serving/submit`` → ``caller`` (the
  code that drives the frontend: in the benchmark, the runner's submit /
  retire);
- under ``serving/step`` or ``serving/engine_step`` but under no leaf →
  ``unattributed``: the guard. It is 0 where the leaves tile the pump, and
  all of the step's idle time for a program without the leaves.

ONE CLOCK, TO WITHIN CAUSALITY. The profile stamps host spans with the
host's clock and device events with the chip's, brought together once a
capture; on a v5e they disagree by up to a millisecond or two from capture
to capture (PR 39's first traced run: a decode program "began" 0.56 ms
BEFORE the jitted call that launched it), which is as long as the phases
measured here. What a synchronous pump guarantees bounds the error: no
program begins before its ``serving/dispatch`` span does, and none ends
after its ``serving/fetch`` span has. ``clock_lag`` takes both bounds over
the traced launches and the idle intervals are moved to their MIDDLE
before they are laid over the host's spans. Where exactly a program
begins and ends between its call and its fetch's return is therefore an
ASSUMPTION (the middle takes the shortest launch latency and the shortest
way back to be equal), sure to half the distance between the bounds and
no closer. So the METRIC is the whole of it: ``launch_and_fetch`` sums
every part from the scheduler's pick to ``device_get``'s return, which
does not depend on where in that stretch the program lies, and neither
do the parts under the spans from one fetch's return to the next
``serving/dispatch`` (a program ends before its fetch returns on any
clock the bounds allow). The ``host_path`` line prints the stretch's
three pieces beside it with their +/-.

The parts sum to ``window_s - busy_s`` of the run's ``device`` line by
construction: the idle intervals are the complement of
``reduce.busy_and_window``'s own union, taken on the device's clock AS
STAMPED inside the same window, and only then moved for the overlay (a
piece at a window edge is laid that much aside; it is counted once all
the same). ``partition`` checks the sum against ``busy_and_window`` and
reads nothing where they differ by more than 0.1% (another window or
device than the ``device`` line's). ``GROUPS`` folds the parts into the
four ``idle_ms_per_step.*`` metrics; each is divided by the traced server
steps, as the other ``*_ms_per_step`` readers divide. ``in_program`` and
``unattributed`` are in no metric: the first is a field of the line (with
``busy_s`` the union of ALL operation events and a layer loop's ``while``
over its body it reads a microsecond a step in every cell, and nothing
can move it), the second is ``idle_attributed_share.serve``'s numerator.

The first reader that asks prints one ``host_path`` line: every part
ungrouped, the groups a step, the same a launch for the gaps that
precede a launch of each kind (``decode``, ``split``, ...: a few traced
seconds catch a mix of kinds, and a decode launch's gap is not a split
launch's), the three pieces of ``launch_and_fetch`` with their +/-, the
median and the longest idle time before one launch (the parts are means:
one stalled ``device_get`` shows there), the median self time of the two
parent spans (what they hold outside their children: the tiling's own
check), and the busy time that only a control-flow event covers (the
bubbles between the operations of a layer loop, which ``busy_s`` counts
as busy).

A profile without device operations gives None, and every reader then
reads nothing (``metric_not_read``)."""

import bisect
import json
from typing import Dict, List, Optional, Tuple

from benchmark.lib import stats
from benchmark.trace import reduce, scopes

STEP, SUBMIT, FETCH = "serving/step", "serving/submit", "serving/fetch"
PARENTS = (STEP, "serving/engine_step")
LEAVES = (SUBMIT, "serving/admit", "serving/plan", "serving/schedule",
          "serving/pack", "serving/dispatch", "serving/count", FETCH,
          "serving/retire", "serving/bookkeeping", "serving/fanout")
IN_PROGRAM, CALLER, UNATTRIBUTED = "in_program", "caller", "unattributed"
FETCH_HEAD, FETCH_TAIL = FETCH + ":head", FETCH + ":tail"
#: the three pieces of ``launch_and_fetch``: the first does not depend on
#: where the device's clock is put between its bounds, the other two trade
#: what the first and the last operation of a program move by
TO_THE_CALL = ("serving/schedule", "serving/pack")
CALL_TO_FIRST_OP = ("serving/dispatch", "serving/count", FETCH_HEAD)
LAST_OP_TO_RETURN = (FETCH_TAIL,)
#: metric suffix -> the parts it sums (``in_program`` and ``unattributed``
#: are in none: fields of the line, and the guard's numerator)
GROUPS = {
    "fanout": ("serving/fanout",),
    "frontend": ("serving/retire", "serving/bookkeeping", "serving/admit",
                 "serving/plan"),
    "caller": (CALLER, SUBMIT),
    "launch_and_fetch": TO_THE_CALL + CALL_TO_FIRST_OP + LAST_OP_TO_RETURN,
}
#: opcodes whose events span the events of their bodies
CONTROL_FLOW = ("while", "conditional", "call")
#: step programs are named ``serve_<kind>_r<rows>...`` (``engine_v2``)
_STEP_PROGRAM = "serve_"
NO_LAUNCH = "(no launch follows)"


def idle_intervals(busy: List[Tuple[float, float]], t0: float, t1: float
                   ) -> List[Tuple[float, float]]:
    """The complement of the union of ``busy`` inside ``[t0, t1]``."""
    out, end = [], t0
    for s, e in sorted(busy):
        if s > end:
            out.append((end, s))
        end = max(end, e)
    if t1 > end:
        out.append((end, t1))
    return out


def pump_spans(trace: dict) -> List[list]:
    """The program's serving spans on the host thread that holds
    ``serving/step`` (the pump's): ``[name, start, duration, stats]``."""
    lines = [line["events"] for plane in trace["planes"]
             if plane["name"] == reduce.HOST_PLANE for line in plane["lines"]]
    pump = max(lines, key=lambda evs: sum(e[0] == STEP for e in evs),
               default=[])
    return [e for e in pump if e[0] in LEAVES or e[0] in PARENTS]


def launch_kind(module: str) -> Optional[str]:
    """``serve_split_r64_c128`` -> ``split``; None for another program."""
    if not module.startswith(_STEP_PROGRAM):
        return None
    return module[len(_STEP_PROGRAM):].split("_", 1)[0]


class _Cover:
    """Which of a set of non-overlapping intervals holds a point."""

    def __init__(self, intervals):
        self.ivs = sorted(intervals)
        self.starts = [iv[0] for iv in self.ivs]

    def at(self, t: float):
        k = bisect.bisect_right(self.starts, t) - 1
        if k >= 0 and t < self.ivs[k][1]:
            return self.ivs[k]
        return None

    def first_from(self, t: float):
        """The first interval that starts at or after ``t``."""
        k = bisect.bisect_left(self.starts, t)
        return self.ivs[k] if k < len(self.ivs) else None


def clock_lag(programs: _Cover, spans: List[list]
              ) -> Optional[Tuple[float, float]]:
    """``(at_least, at_most)`` in ns: what must be ADDED to the device's
    timestamps for every traced launch to be causal. A launch is a
    ``serving/dispatch`` span, the step program whose start is nearest its
    own (each the other's nearest) and the first ``serving/fetch`` that
    begins after it: the program begins no earlier than the call
    (``at_least``) and ends no later than the fetch returns
    (``at_most``). None without such a launch."""
    calls = sorted((e[1], e[1] + e[2]) for e in spans
                   if e[0] == "serving/dispatch")
    fetches = _Cover((e[1], e[1] + e[2]) for e in spans if e[0] == FETCH)
    starts = [c[0] for c in calls]

    def nearest(sorted_starts, t):
        k = bisect.bisect_left(sorted_starts, t)
        near = [j for j in (k - 1, k) if 0 <= j < len(sorted_starts)]
        return min(near, key=lambda j: abs(sorted_starts[j] - t),
                   default=None)
    lo = hi = None
    for i, (begin, end) in enumerate(calls):
        k = nearest(programs.starts, begin)
        fetch = fetches.first_from(end)
        if k is None or fetch is None or \
                nearest(starts, programs.starts[k]) != i:
            continue
        program = programs.ivs[k]
        lo = begin - program[0] if lo is None \
            else max(lo, begin - program[0])
        hi = fetch[1] - program[1] if hi is None \
            else min(hi, fetch[1] - program[1])
    return None if lo is None else (lo, hi)


def split_idle(trace: dict, window: Tuple[float, float], device: int = 0
               ) -> Optional[dict]:
    """``{"idle_ns", "parts": {part: ns}, "by_kind": {kind: {part: ns}},
    "launches": {kind: n}, "clock_lag_ns": (at least, at most) or None,
    "shift_ns", "bubbles_ns", "gaps_ns": the idle time before each launch,
    sorted}`` of that device inside ``window``: the idle intervals of the
    device's clock as stamped, laid over the host's spans ``shift_ns``
    later (``clock_lag``); None without a device operation there."""
    t0, t1 = window
    plane = dict(reduce.device_planes(trace)).get(device)
    if plane is None:
        return None
    spans = pump_spans(trace)
    stamped = [(e[1], e[1] + e[2], scopes.module_name(e[0]))
               for e in reduce.line_events(plane, scopes.MODULES_LINE)]
    lag = clock_lag(_Cover(m for m in stamped if launch_kind(m[2])), spans)
    # the middle of the bounds; no shift without a traced launch
    shift = 0.0 if lag is None else (lag[0] + lag[1]) / 2.0
    ops = reduce.line_events(plane, reduce.OPS_LINE)
    busy = reduce.clipped(ops, t0, t1)      # as ``busy_and_window`` clips
    if not busy:
        return None
    # beside the split, for the line: busy time that only a control-flow
    # event covers (a ``while`` spans its body: the bubbles between the
    # operations of a layer loop count as BUSY in ``busy_s``)
    bubbles = reduce.union_ns(busy) - reduce.union_ns(reduce.clipped(
        [e for e in ops if reduce.opcode(e) not in CONTROL_FLOW], t0, t1))
    modules = _Cover((a + shift, b + shift, name) for a, b, name in stamped)
    programs = _Cover(m for m in modules.ivs if launch_kind(m[2]))
    leaves = _Cover((e[1], e[1] + e[2], e[0]) for e in spans
                    if e[0] in LEAVES)
    steps = _Cover((e[1], e[1] + e[2], e[0]) for e in spans if e[0] == STEP)
    cuts = sorted({t for iv in modules.ivs + leaves.ivs + steps.ivs
                   for t in iv[:2]})
    parts: Dict[str, float] = {}
    by_kind: Dict[str, Dict[str, float]] = {}
    before: Dict[float, float] = {}     # a program's start -> idle before it
    idle = 0.0
    for s, e in idle_intervals(busy, t0, t1):
        idle += e - s
        s, e = s + shift, e + shift         # onto the host's clock
        lo = bisect.bisect_right(cuts, s)
        hi = bisect.bisect_left(cuts, e)
        edges = [s] + cuts[lo:hi] + [e]
        for a, b in zip(edges, edges[1:]):
            if b <= a:
                continue
            mid = (a + b) / 2.0         # no boundary lies inside a piece
            module = modules.at(mid)
            if module is not None:
                part = IN_PROGRAM
                kind = launch_kind(module[2]) or module[2]
            else:
                leaf = leaves.at(mid)
                nxt = programs.first_from(b)
                kind = launch_kind(nxt[2]) if nxt else None
                if nxt:
                    before[nxt[0]] = before.get(nxt[0], 0.0) + (b - a)
                if leaf is None:
                    part = UNATTRIBUTED if steps.at(mid) else CALLER
                elif leaf[2] != FETCH:
                    part = leaf[2]
                else:
                    # its program has yet to begin: the launch's latency
                    part = FETCH_HEAD if nxt and nxt[0] < leaf[1] \
                        else FETCH_TAIL
            parts[part] = parts.get(part, 0.0) + (b - a)
            row = by_kind.setdefault(kind or NO_LAUNCH, {})
            row[part] = row.get(part, 0.0) + (b - a)
    launches: Dict[str, int] = {}
    for m in programs.ivs:
        if t0 <= m[0] - shift < t1:
            kind = launch_kind(m[2])
            launches[kind] = launches.get(kind, 0) + 1
    return {"idle_ns": idle, "parts": parts, "by_kind": by_kind,
            "launches": launches, "clock_lag_ns": lag, "shift_ns": shift,
            "bubbles_ns": bubbles, "gaps_ns": sorted(before.values())}


def grouped(parts: Dict[str, float]) -> Dict[str, float]:
    """The four metrics' sums, then the two parts no metric holds: all of
    ``parts`` between them."""
    out = {g: sum(parts.get(p, 0.0) for p in names)
           for g, names in GROUPS.items()}
    out[IN_PROGRAM] = parts.get(IN_PROGRAM, 0.0)
    out[UNATTRIBUTED] = parts.get(UNATTRIBUTED, 0.0)
    return out


def launch_and_fetch_pieces(p: dict) -> dict:
    def ms(names):
        return sum(p["parts"].get(n, 0.0) for n in names) / 1e6 / p["steps"]
    lag, n = p["clock_lag_ns"], sum(p["launches"].values())
    return {"to_the_call": ms(TO_THE_CALL),
            "call_to_first_op": ms(CALL_TO_FIRST_OP),
            "last_op_to_return": ms(LAST_OP_TO_RETURN),
            "plus_minus": None if lag is None else
            (lag[1] - lag[0]) / 2.0 * n / 1e6 / p["steps"]}


def gap_stats(gaps_ns: List[float]) -> Optional[dict]:
    """Median and longest idle time before a launch, in ms, and how many
    gaps are over three medians with the seconds they hold."""
    if not gaps_ns:
        return None
    median = stats.median(gaps_ns)
    long = [g for g in gaps_ns if g > 3 * median]
    return {"median": median / 1e6, "max": gaps_ns[-1] / 1e6,
            "over_3_medians": len(long), "over_3_medians_s": sum(long) / 1e9}


def parent_self_ms(trace: dict) -> Dict[str, List[float]]:
    """Self time in ms of each ``serving/step`` and ``serving/engine_step``
    that launched a program (a ``serving/dispatch`` starts inside it): its
    duration less what the spans nested in it cover."""
    spans = pump_spans(trace)
    launches = sorted(e[1] for e in spans if e[0] == "serving/dispatch")
    out: Dict[str, List[float]] = {}
    for ev, self_ns in reduce.self_times(spans):
        k = bisect.bisect_left(launches, ev[1])
        if ev[0] in PARENTS and k < len(launches) and \
                launches[k] < ev[1] + ev[2]:
            out.setdefault(ev[0], []).append(self_ns / 1e6)
    return out


def partition(run) -> Optional[dict]:
    """The run's split, computed once and kept on the view; prints the
    ``host_path`` line. None without a profile, its window, traced steps
    or device operations, and where the parts do not sum to the ``device``
    line's ``window_s - busy_s`` within 0.1% (the line then says so)."""
    cached = getattr(run, "_host_path", False)
    if cached is not False:
        return cached
    out = None
    if run.trace is not None:
        window = reduce.traced_window(run.trace, run.span_name)
        steps = len(reduce.host_events(run.trace, run.span_name))
        if window is not None and steps:
            out = split_idle(run.trace, window)
        if out is not None:
            out["steps"] = steps
            busy_s, window_s = reduce.busy_and_window(run.trace,
                                                      run.span_name)
            device_idle_ns = (window_s - busy_s) * 1e9
            if abs(sum(out["parts"].values()) - device_idle_ns) > \
                    1e-3 * device_idle_ns:
                print(json.dumps({
                    "phase": "host_path", "error": "the parts do not sum "
                    "to the device line's idle time",
                    "parts_s": sum(out["parts"].values()) / 1e9,
                    "device_idle_s": device_idle_ns / 1e9}), flush=True)
                out = None
    run._host_path = out
    if out is not None:
        print(json.dumps(report_line(run, out)), flush=True)
    return out


def report_line(run, p: dict) -> dict:
    selfs = parent_self_ms(run.trace)
    return {
        "phase": "host_path", "steps": p["steps"],
        "idle_s": p["idle_ns"] / 1e9,
        # what causality lets the device's clock lag the host's by, and
        # the shift applied (their middle): launch / fetch are sure to
        # half their distance
        "device_clock_lag_ms": None if p["clock_lag_ns"] is None else {
            "at_least": p["clock_lag_ns"][0] / 1e6,
            "at_most": p["clock_lag_ns"][1] / 1e6,
            "applied": p["shift_ns"] / 1e6},
        # every part on its own, seconds of the traced window
        "parts_s": {k: v / 1e9 for k, v in
                    sorted(p["parts"].items(), key=lambda kv: -kv[1])},
        "ms_per_step": {k: v / 1e6 / p["steps"]
                        for k, v in grouped(p["parts"]).items()},
        # launch_and_fetch's three pieces: the first is sure, the other
        # two trade up to ``plus_minus`` between them (half the distance
        # of the clock's bounds a traced launch)
        "launch_and_fetch_ms_per_step": launch_and_fetch_pieces(p),
        # NOT idle time by ``busy_s``: inside a control-flow event, between
        # the operations of its body (the rest of "the device's own")
        "busy_under_control_flow_only_ms_per_step":
            p["bubbles_ns"] / 1e6 / p["steps"],
        # the gaps that precede (or lie inside) a launch of each kind
        "ms_per_launch": {
            kind: dict({k: v / 1e6 / max(1, p["launches"].get(kind, 0))
                        for k, v in grouped(row).items()},
                       launches=p["launches"].get(kind, 0))
            for kind, row in sorted(p["by_kind"].items())},
        "parent_self_ms_median": {n: run.stats.median(v)
                                  for n, v in selfs.items()},
        # the idle time before ONE launch: the parts above are means, and
        # one stalled device_get of 100 ms is 0.6 ms a step of them
        "gap_ms": gap_stats(p["gaps_ns"]),
    }


# -- what the readers share ---------------------------------------------------

def idle_ms_per_step(run, group: str) -> Optional[float]:
    """Idle time of the device under that group of ``GROUPS`` per traced
    server step, in ms."""
    p = partition(run)
    if p is None:
        return None
    return grouped(p["parts"])[group] / 1e6 / p["steps"]


def attributed_share(run) -> Optional[float]:
    """100 x (1 - ``unattributed`` / idle time): the guard of the four."""
    p = partition(run)
    if p is None or not p["idle_ns"]:
        return None
    return 100.0 * (1.0 - p["parts"].get(UNATTRIBUTED, 0.0) / p["idle_ns"])
