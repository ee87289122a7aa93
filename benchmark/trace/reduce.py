"""From a profiler trace (``.xplane.pb``) to numbers. Kept with the
benchmark: every PR computes device busy time, kernel time and collective
time the same way, and no PR that claims a gain can change how.

``load`` turns the protobuf into plain data (``{"planes": [{"name",
"lines": [{"name", "events": [[name, start_ns, duration_ns, stats]]}]}]}``)
with nothing but jax; everything else works on that plain form, which is
also what ``benchmark/testdata/`` keeps a trimmed recording of.

On a TPU the device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per executed HLO instruction (control flow such as
``while`` spans its body, so events nest), ``XLA Modules`` one per
program run. Host threads live on ``/host:CPU``, where the program's
tracer spans appear as annotations when ``jax_annotations`` is on."""

import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
_STAT_CHARS = 300

_OPCODE = re.compile(r"(?:^|[\s)}])([a-z][a-z0-9\-]*)\(")

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute",
    re.IGNORECASE)


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                stats = {}
                for key, val in ev.stats:
                    if isinstance(val, (int, float)):
                        stats[str(key)] = val
                    elif val is not None:
                        stats[str(key)] = str(val)[:_STAT_CHARS]
                events.append([ev.name, float(ev.start_ns),
                               float(ev.duration_ns), stats])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def trim(data: dict, t0_ns: float, t1_ns: float,
         max_events: int = 4000) -> dict:
    """A recording small enough to keep: events that start inside
    [t0, t1), at most ``max_events`` a line."""
    out = []
    for plane in data["planes"]:
        lines = []
        for line in plane["lines"]:
            evs = [e for e in line["events"] if t0_ns <= e[1] < t1_ns]
            if evs:
                lines.append({"name": line["name"],
                              "events": evs[:max_events]})
        if lines:
            out.append({"name": plane["name"], "lines": lines})
    return {"planes": out}


def device_planes(data: dict) -> List[Tuple[int, dict]]:
    found = []
    for plane in data["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            found.append((int(m.group(1)), plane))
    return sorted(found, key=lambda p: p[0])


def line_events(plane: dict, line_name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def host_events(data: dict, name: str) -> List[list]:
    """Every host-thread event of that name (tracer spans arrive here)."""
    out = []
    for plane in data["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            out.extend(e for e in line["events"] if e[0] == name)
    return sorted(out, key=lambda e: e[1])


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by the (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clipped(events: List[list], t0: float, t1: float
            ) -> List[Tuple[float, float]]:
    out = []
    for _n, s, d, _st in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b))
    return out


def traced_window(data: dict, span_name: Optional[str]
                  ) -> Optional[Tuple[float, float]]:
    """The window the idle share is taken over, in the trace's clock: from
    the start of the first to the end of the last host span of that name
    (whole steps, so profiler start-up and shut-down are outside); without
    such spans, the extent of the device's own events."""
    if span_name:
        spans = host_events(data, span_name)
        if len(spans) >= 2:
            return spans[0][1], max(e[1] + e[2] for e in spans)
    lo, hi = None, None
    for _i, plane in device_planes(data):
        for e in line_events(plane, OPS_LINE):
            lo = e[1] if lo is None else min(lo, e[1])
            hi = e[1] + e[2] if hi is None else max(hi, e[1] + e[2])
    return None if lo is None else (lo, hi)


def busy_and_window(data: dict, span_name: Optional[str]
                    ) -> Optional[Tuple[float, float]]:
    """(seconds in which an operation ran on the device, averaged over the
    device planes; seconds of the traced window)."""
    win = traced_window(data, span_name)
    planes = device_planes(data)
    if win is None or not planes:
        return None
    t0, t1 = win
    busy = [union_ns(clipped(line_events(p, OPS_LINE), t0, t1))
            for _i, p in planes]
    return sum(busy) / len(busy) / 1e9, (t1 - t0) / 1e9


def idle_share(data: Optional[dict], span_name: Optional[str]
               ) -> Optional[float]:
    """Share of the traced window, in %, in which no operation ran on the
    device (averaged over the device planes)."""
    bw = busy_and_window(data, span_name) if data is not None else None
    return None if bw is None else 100.0 * (1.0 - bw[0] / bw[1])


def op_name(ev: list) -> str:
    """The HLO instruction's own name. On a TPU an ``XLA Ops`` event is
    named by the instruction's whole text (``%flash_fwd.6 = (bf16[...])
    custom-call(... %bitcast.444 ...)``); what follows `` = `` names shapes
    and OPERANDS, so a kernel is recognised by what precedes it — a Pallas
    kernel's ``name=`` becomes the instruction's name."""
    return ev[0].split(" = ", 1)[0].lstrip("%")


def opcode(ev: list) -> str:
    """The HLO opcode (``fusion``, ``custom-call``, ``all-gather-start``,
    ...): the word before the operand list. Empty where the event's name
    is not an instruction's text."""
    parts = ev[0].split(" = ", 1)
    if len(parts) < 2:
        return ""
    m = _OPCODE.search(parts[1])
    return m.group(1) if m else ""


def self_times(events: List[list]) -> List[Tuple[list, float]]:
    """(event, nanoseconds not covered by an event nested inside it)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List] = []
    stack: List[int] = []
    for ev in order:
        s, e = ev[1], ev[1] + ev[2]
        while stack and out[stack[-1]][0][1] + out[stack[-1]][0][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(e, out[stack[-1]][0][1] +
                                     out[stack[-1]][0][2]) - s
        out.append([ev, ev[2]])
        stack.append(len(out) - 1)
    return [(ev, max(0.0, t)) for ev, t in out]


def matching_seconds(data: dict, match: Callable[[list], bool],
                     device: int = 0,
                     window: Optional[Tuple[float, float]] = None
                     ) -> Tuple[float, int]:
    """(summed self time in seconds, count) of the device's op events that
    ``match``, inside ``window`` when one is given."""
    for i, plane in device_planes(data):
        if i != device:
            continue
        total, n = 0.0, 0
        for ev, self_ns in self_times(line_events(plane, OPS_LINE)):
            if window and not (window[0] <= ev[1] < window[1]):
                continue
            if match(ev):
                total += self_ns
                n += 1
        return total / 1e9, n
    return 0.0, 0


def collective_seconds(data: dict,
                       window: Optional[Tuple[float, float]] = None,
                       device: int = 0) -> Tuple[float, int]:
    """(seconds in which SOME collective was in flight on the device,
    number of collective events): the union of the synchronous collectives
    on the ops line and of the start-to-done spans on the async line (the
    TPU compiler turns ZeRO's all-gathers and reduce-scatters into rings of
    asynchronous collective-permutes). Hidden behind compute or not."""
    for i, plane in device_planes(data):
        if i != device:
            continue
        evs = [e for line in (OPS_LINE, ASYNC_LINE)
               for e in line_events(plane, line)
               if COLLECTIVE.search(op_name(e)) or
               COLLECTIVE.search(opcode(e))]
        lo, hi = window if window else (float("-inf"), float("inf"))
        return union_ns(clipped(evs, lo, hi)) / 1e9, len(evs)
    return 0.0, 0


def top_ops(data: dict, n: int = 10, device: int = 0) -> List[list]:
    """The ``n`` device operations with most summed self time, under the
    instruction names the trace gives them: [[name, seconds], ...]."""
    sums: Dict[str, float] = {}
    for i, plane in device_planes(data):
        if i != device:
            continue
        for ev, self_ns in self_times(line_events(plane, OPS_LINE)):
            name = op_name(ev)[:64]
            sums[name] = sums.get(name, 0.0) + self_ns
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(data: dict, span_names: Iterable[str], n: int = 10,
              device: int = 0) -> List[list]:
    """Idle time of the device inside the traced extent, by what the host
    was doing: each gap between device ops goes to the innermost of the
    named host spans that covers its midpoint (``(no span)`` otherwise).
    [[span name, seconds], ...], longest first."""
    for i, plane in device_planes(data):
        if i != device:
            continue
        ivs = sorted((e[1], e[1] + e[2])
                     for e in line_events(plane, OPS_LINE))
        if not ivs:
            return []
        spans = []
        for name in span_names:
            spans.extend((e[1], e[1] + e[2], name)
                         for e in host_events(data, name))
        sums: Dict[str, float] = {}
        end = ivs[0][1]
        for s, e in ivs[1:]:
            if s > end:
                mid = (s + end) / 2.0
                owner = [(b - a, nm) for a, b, nm in spans if a <= mid < b]
                name = min(owner)[1] if owner else "(no span)"
                sums[name] = sums.get(name, 0.0) + (s - end)
            end = max(end, e)
        ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in ranked]
    return []


def summarize(data: dict, top: int = 40) -> dict:
    """For a human: planes, lines, event counts, and the heaviest names of
    each line with one sample of their stats."""
    out = []
    for plane in data["planes"]:
        lines = []
        for line in plane["lines"]:
            sums: Dict[str, List] = {}
            for ev in line["events"]:
                rec = sums.setdefault(
                    op_name(ev)[:80] + " " + opcode(ev), [0.0, 0, ev[3]])
                rec[0] += ev[2]
                rec[1] += 1
            ranked = sorted(sums.items(), key=lambda kv: -kv[1][0])[:top]
            lines.append({"line": line["name"],
                          "events": len(line["events"]),
                          "top": [[k, v[0] / 1e9, v[1], v[2]]
                                  for k, v in ranked]})
        out.append({"plane": plane["name"], "lines": lines})
    return {"planes": out}
