#!/usr/bin/env python3
"""One run of a serving cell THROUGH THE HARNESS, then the engine's two
always-on host-path counters over the measured window alone: every argument
is ``benchmark/run.py``'s, and the last line printed is one more,

    {"phase": "host_counters", "launches", "host_s", "fetch_wait_s",
     "host_ms_per_launch", "fetch_wait_ms_per_launch", "host_share",
     "launches_ahead", "ahead_share", "ahead_rows_dropped",
     "window": {<the WORK counters' growth over the window>},
     "split_steps": {<the window's split launches by what they held>}}

and, after a ``--trace 1`` run, a ``split_by_rung`` line before it: the
traced split launches of device 0 by the token capacity their program ran
at — launches, the program's mean device time and its scopes, ms a launch
(PR 46: ``device_by_scope`` sums a program over all its launches, and a
split program holds an instance of its layer loop a capacity).

``dispatch/host_seconds`` and ``dispatch/fetch_wait_seconds``
(``engine_v2._fetch``) less what they read when the window opened, over the
window's ``dispatch/host_calls``; read when the runner ends the window
(its ``terminate_inflight``, which drops the rows still running and is no
part of it). Since the pump launches step n+1 before it fetches step n
(ISSUE 44) ``host_s`` is what the host did between two fetches WHILE the
device ran the next program, not time the device idled: ``ahead_share``
(``dispatch/launches_ahead`` over the launches, %) says for how many
launches that held — about 100 in a saturated closed loop, 0 on a tree
before the change — and ``ahead_rows_dropped`` counts the continued rows
whose token was thrown away (0 where every request ends by length). The harness has no reader of the two: it
prints per-layer metrics in traced runs only, and there the capture's own
start and stop stall the pump between two launches for seconds that
``dispatch/host_seconds`` takes for host time (PERF.md section 7). Run
with ``--trace 0`` this gives a replica's host share undisturbed; the
cost of the instrumentation is read from runs with the tracer on against
off at one seed (PERF.md section 6, PR 39). ``window`` holds what the
launches of the window alone counted (``dispatch/steps.split``,
``split_grouped_steps``, ``chunk_rows``, ``attn_row_slots``, ``tokens``,
``token_slots``): the share of split launches that took a grouped instance
and what attention worked on (PR 40; a tree without a counter reads 0);
``kv_pages_walked`` / ``kv_page_fetches`` (PR 47): the live pages the paged
kernel's readers had to read and the page DMAs issued for them — their
ratio is 2 where every call fetches a page once for all of a row's KV heads,
16 at 8 KV heads a head at a time. ``delta_chunk_live_share`` (PR 63; None
for a stack with no delta-rule layer): the window's
``delta_chunk_positions_live`` over its ``delta_chunk_positions`` — how much
of the chunk groups' width the delta rule's chunk form still computed.
``split_steps`` (PR 46) is the joint histogram of the window's split
launches by what ``launch_work.launch_work`` returned: ``by_slots``
(launches at each token capacity taken), ``hist`` (``"<tokens, in bins of
64>x<chunk rows>"`` -> launches), ``tokens_mean``, and ``fits``: the share (%) of them
that an instance of ``<slots>x<chunk rows>`` would hold, for the rungs a
ladder of token capacities might have — the same on any tree, since it reads
the batches and not the programs.

``prefix_cache`` (PR 53) is what the prefix cache's room-making cost the
window: the growth of the cache's ``evict_calls`` / ``evict_scans`` /
``pages_evicted`` (None on a tree without them) and, timed here around the
calls the frontend's spans ``serving/cache_insert`` (inside
``serving/fanout``) and ``serving/cache_evict`` (``adopt_cached``'s, inside
``serving/admit``) enclose — so that an untraced run and a tree without
the spans give them too —, each one's calls, total and longest in ms, and
the longest ``serving/step`` beside them (``step_ms_max``).

``build_record`` (PR 54), a line of its own before ``host_counters`` and in
a training cell too, is what set-up built, read when the window OPENS:
the ``setup/<part>_seconds`` and ``compile/*`` build counters, and
``compile_monitor.summary()["programs"]`` a row a step program in the order
they were built — builds, ``trace_s`` / ``lower_s`` / ``backend_s``, the
newest build's ``cache`` and ``retrieval_s``, and ``gap_s``: from the
previous build's end to this one's, less this one's three phases, i.e.
what its first call cost that jax's three events do not cover (the rest of
lowering and loading, the first run itself). ``covered_s`` is the three
phases' sum over all of them, ``unnamed`` the rows of the builds under no
step program's name (``other``; ``setup/<part>``: built inside that part
of construction). Empty on a tree without the record.

Arguments of its own (PR 55), taken off before the harness reads the rest:
``--clients N`` runs the cell's traffic mix with ``N`` closed-loop clients
for the file's count (a replica under its ``max_sequences``: the mix's
lengths, cycle and ramp as committed, no file under ``benchmark/``
touched), ``--traffic NAME`` hands the cell's configuration another
committed mix, ``--trace-seconds S`` sets how long the traced part of the
window is, and ``--own-rows`` packs every split batch for its OWN row
bucket's program, as before ``engine_v2._pick_form`` — the control a lifted
launch's device time is read against, at the same seed and clients.
``split_by_rung`` keys a launch by program AND slots
(``serve_split_r8_c128@1024``, ``serve_split_r64_c128@512``; a program
with no packed shape, the row form, by its rows x chunk), so a lifted
launch stands beside the one it replaced in ONE trace where a window holds
both; ``split_steps.by_program`` counts the window's launches the same way.

Since PR 57 ``window`` also holds ``moe_assignments`` and
``moe_buffer_rows`` (the rows the held experts' many-token dispatch computed
in its first rounds; 0 on a tree without the counter), and ``--scope-ops
moe[,moe_experts]`` adds to each rung of ``split_by_rung`` an ``ops_ms``
field: the named scopes' device operations of that rung's launches, ms a
launch, under ``<instruction name without its number> <opcode> <result
shape>`` — a layer's copies of one operation summed —, the heaviest 30 a
scope (which of a scope's operations carries its time).

    chiprun --chips 1 -- python3 tools/host_path_probe.py \
        --workload <cell> --seed <n> --trace <0|1> [--clients N] \
        [--traffic NAME] [--trace-seconds S] [--own-rows] [--scope-ops a,b]
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import run as bench_run                # noqa: E402

NAMES = ("dispatch/host_seconds", "dispatch/fetch_wait_seconds",
         "dispatch/host_calls", "dispatch/launches_ahead",
         "dispatch/ahead_rows_dropped")
WORK = ("steps.split", "split_grouped_steps", "chunk_rows",
        "attn_row_slots", "tokens", "token_slots", "kv_pages_walked",
        "kv_page_fetches", "split_lifted_steps", "moe_assignments",
        "moe_buffer_rows", "delta_chunk_positions",
        "delta_chunk_positions_live")
#: (slots, chunk rows) of the instances ``split_steps.fits`` asks about
RUNGS = ((256, 2), (256, 8), (512, 4), (512, 8), (1024, 8))
CACHE_COUNTERS = ("evict_calls", "evict_scans", "pages_evicted")


def live_share(window):
    """``delta_chunk_positions_live`` ÷ ``delta_chunk_positions``."""
    every = window["delta_chunk_positions"]
    return window["delta_chunk_positions_live"] / every if every else None


class Calls:
    """Times the OUTERMOST calls of the methods it wraps (those of one
    ``Calls`` may call each other: an eviction ``insert`` makes itself is
    inside the insert's time), kept while ``on()`` holds; ``last`` is the
    object the newest call was made on."""

    def __init__(self, on):
        self.on, self.depth, self.last = on, 0, None

    def wrap(self, owner, method):
        """Replace ``owner.method``; the list its calls' seconds go to."""
        into, inner = [], getattr(owner, method)

        def call(*args, **kwargs):
            self.last = args[0]
            self.depth += 1
            t0 = time.perf_counter()
            try:
                return inner(*args, **kwargs)
            finally:
                self.depth -= 1
                if self.depth == 0 and self.on():
                    into.append(time.perf_counter() - t0)
        setattr(owner, method, call)
        return into


def calls_ms(seconds):
    return {"calls": len(seconds), "total_ms": 1e3 * sum(seconds),
            "longest_ms": 1e3 * max(seconds, default=0.0)}


def counters():
    from deepspeed_tpu.telemetry.registry import registry
    return [registry.counter(n).value
            for n in NAMES + tuple("dispatch/" + w for w in WORK)]


def build_record():
    """The ``build_record`` line's fields (module docstring)."""
    from deepspeed_tpu.telemetry import compile_monitor
    from deepspeed_tpu.telemetry.registry import registry
    programs = dict(compile_monitor.summary().get("programs", {}))
    # `other` and `setup/<part>`: builds under no step program's name
    unnamed = {n: programs.pop(n) for n in list(programs)
               if "recent" not in programs[n]}
    rows, last = {}, None
    for name, row in sorted(programs.items(),
                            key=lambda kv: kv[1]["recent"][-1]["at"]):
        new = row["recent"][-1]
        phases = sum(new[k] for k in ("trace_s", "lower_s", "backend_s"))
        rows[name] = {
            "builds": row["builds"],
            **{k: round(row[k], 3) for k in ("trace_s", "lower_s",
                                             "backend_s")},
            "cache": new["cache"], "retrieval_s": round(new["retrieval_s"], 3),
            "gap_s": None if last is None
            else round(new["at"] - last - phases, 3)}
        last = new["at"]
    return {"phase": "build_record",
            "counters": {n: registry.get(n).value for n in registry.names()
                         if n.startswith("setup/") or (
                             n.startswith("compile/") and n.endswith(
                                 ("_seconds", "_built", "_hits", "_misses")))},
            "covered_s": sum(r[k] for r in rows.values()
                             for k in ("trace_s", "lower_s", "backend_s")),
            "unnamed": unnamed, "programs": rows}


def split_steps(launches):
    """The ``split_steps`` field from ``(tokens, chunk rows, slots, the
    program's rows)`` of each split launch of the window."""
    from collections import Counter
    if not launches:
        return {"launches": 0}
    n = len(launches)
    hist = Counter((tokens // 64 * 64, rows) for tokens, rows, *_ in launches)
    return {"launches": n,
            "by_slots": dict(Counter(str(s) for _, _, s, _ in launches)),
            "by_program": dict(Counter(f"r{nb}@{s}"
                                       for _, _, s, nb in launches)),
            "tokens_mean": sum(t for t, *_ in launches) / n,
            "chunk_rows_mean": sum(r for _, r, *_ in launches) / n,
            "fits": {f"{cap}x{group}": 100.0 * sum(
                t <= cap and r <= group for t, r, *_ in launches) / n
                for cap, group in RUNGS},
            "hist": {f"{tokens}x{rows}": hist[tokens, rows]
                     for tokens, rows in sorted(hist)}}


def op_label(ev):
    """A device operation's label in the per-operation lists:
    ``<instruction name without its number> <opcode> <result shape>``, so
    that a layer's copies of one operation sum."""
    import re
    from benchmark.trace import reduce
    made = re.match(r"^\(?(\w+\[[\d,]*\])", ev[0].split(" = ", 1)[-1])
    return " ".join((re.sub(r"\.\d+", "", reduce.op_name(ev)),
                     reduce.opcode(ev), made.group(1) if made else ""))


def split_by_rung(trace, ladder_of=None,
                  capacities=(256, 512, 1024, 2048), ops_of=()):
    """The ``split_by_rung`` line from a loaded trace (``reduce.load``'s
    form): every ``serve_split_*`` launch of device 0, put down to the
    capacity whose packed shapes (``[1, capacity, ...]`` or ``[capacity,
    ...]`` results) take most of its time, with its operations' self times
    by the program's scope table — under ``<program>@<slots>``.
    ``ladder_of(rows, chunk)``: the token capacities of that split program
    (the engine's ``_token_capacities``), so that a shape counts only where
    it is a rung of the program that ran (an 8 x 128 row form holds
    ``[256, ...]`` results: 8 rows x 32 heads); a program without a ladder,
    or a launch with no such shape, ran the row form, its program's rows x
    chunk. ``ops_of``: scopes whose operations a rung also lists
    (``ops_ms``, module docstring). None without such a launch."""
    import bisect
    import re
    from benchmark.trace import reduce, scopes
    shape = re.compile(r"^\(?\w+\[(?:1,)?(\d+)[,\]]")
    row_form = re.compile(r"_r(\d+)_c(\d+)")
    for i, plane in reduce.device_planes(trace):
        if i != 0:
            continue
        mods = sorted((e[1], e[1] + e[2], scopes.module_name(e[0]))
                      for e in reduce.line_events(plane, scopes.MODULES_LINE))
        mods = [m for m in mods if m[2].startswith("serve_split")]
        if not mods:
            return None
        tables = scopes.program_tables(sorted({m[2] for m in mods}))
        ladders = {}

        def rungs_of(program):
            if program not in ladders:
                ladders[program] = capacities if ladder_of is None else \
                    ladder_of(*map(int, row_form.search(program).groups()))
            return ladders[program]
        starts = [m[0] for m in mods]
        # (by scope, by capacity, by (scope, operation)) ns
        per = [({}, {}, {}) for _ in mods]
        for ev, self_ns in reduce.self_times(
                reduce.line_events(plane, reduce.OPS_LINE)):
            k = bisect.bisect_right(starts, ev[1]) - 1
            if k < 0 or ev[1] >= mods[k][1]:
                continue
            entry = tables.get(mods[k][2], {}).get(reduce.op_name(ev)) or {}
            scope = entry.get("scope") or scopes.NO_SCOPE
            per[k][0][scope] = per[k][0].get(scope, 0.0) + self_ns
            if scope in ops_of:
                op = (scope, op_label(ev))
                per[k][2][op] = per[k][2].get(op, 0.0) + self_ns
            m = shape.match(ev[0].split(" = ", 1)[-1])
            if m and int(m.group(1)) in rungs_of(mods[k][2]):
                cap = int(m.group(1))
                per[k][1][cap] = per[k][1].get(cap, 0.0) + self_ns
        rungs = {}
        for (_t0, _t1, program), (by_scope, by_cap, by_op) in zip(mods, per):
            if by_cap:
                cap = max(by_cap, key=by_cap.get)
            else:
                rows, chunk = map(int, row_form.search(program).groups())
                cap = rows * chunk
            rung = rungs.setdefault(f"{program}@{cap}", [0, 0.0, {}, {}])
            rung[0] += 1
            rung[1] += sum(by_scope.values())
            for into, by in ((rung[2], by_scope), (rung[3], by_op)):
                for name, ns in by.items():
                    into[name] = into.get(name, 0.0) + ns

        def heaviest(by, n, top=None):
            return {name: round(ns / n / 1e6, 3) for name, ns in sorted(
                by.items(), key=lambda kv: -kv[1])[:top]}
        return {"phase": "split_by_rung", "rungs": {
            name: {"launches": n, "device_ms": busy / n / 1e6,
                   "scopes_ms": heaviest(by_scope, n),
                   **({"ops_ms": {scope: heaviest(
                       {op: ns for (sc, op), ns in by_op.items()
                        if sc == scope}, n, 30) for scope in ops_of}}
                      if ops_of else {})}
            for name, (n, busy, by_scope, by_op) in sorted(rungs.items())}}
    return None


def own_arguments():
    """The probe's own arguments (module docstring), taken off
    ``sys.argv`` and applied: the mix loader and the engine's choice of
    program are replaced in this process alone."""
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--trace-seconds", type=float, default=None)
    ap.add_argument("--own-rows", action="store_true")
    ap.add_argument("--scope-ops", default="")
    own, sys.argv[1:] = ap.parse_known_args()
    if (own.clients, own.traffic, own.trace_seconds) != (None,) * 3:
        from benchmark.lib import traffic
        load_mix = traffic.load_mix

        def with_overrides(name):
            mix = load_mix(own.traffic or name)
            if own.clients is not None:
                mix["arrival"] = dict(mix["arrival"], clients=own.clients)
            if own.trace_seconds is not None:
                mix["trace_seconds"] = own.trace_seconds
            return mix
        traffic.load_mix = with_overrides
    if own.own_rows:
        from deepspeed_tpu.inference.engine_v2 import \
            RaggedInferenceEngineTPU as engine
        if hasattr(engine, "_pick_form"):    # a tree before it: as is
            engine._pick_form = engine._launch_form
    return own


def main() -> int:
    own = own_arguments()
    at_open = []
    open_window = bench_run.Context.open_window
    launches, engines = [], []

    def in_window():
        return bool(at_open) and not at_close

    try:
        from deepspeed_tpu.inference import launch_work
    except ImportError:             # a tree before the module: no histogram
        launch_work = None
    if launch_work is not None:
        work_of = launch_work.launch_work

        def counted(*args, **kwargs):
            work = work_of(*args, **kwargs)
            if work["program"] == "split" and in_window():
                launches.append((work["tokens"], work["chunk_rows"],
                                 work["slots"], work["rows_bucket"]))
            return work
        launch_work.launch_work = counted

    from deepspeed_tpu.serving import ServingFrontend
    from deepspeed_tpu.serving.prefix_cache import PrefixCache
    steps = Calls(in_window).wrap(ServingFrontend, "step")
    # `cache_insert` is `_fan_out`'s call, `cache_evict` what is left:
    # `adopt_cached`'s and `extend`'s last resort, as the spans have it
    cache_calls = Calls(in_window)
    inserts = cache_calls.wrap(PrefixCache, "insert")
    evicts = cache_calls.wrap(PrefixCache, "evict")
    cache_open = []
    built = []

    def cache_counters():
        return [getattr(cache_calls.last, n, None) for n in CACHE_COUNTERS]

    def opened(self):
        at_open[:] = counters()
        cache_open[:] = cache_counters()
        built[:] = [build_record()]
        return open_window(self)
    bench_run.Context.open_window = opened
    traces = []
    load_trace = bench_run.Context.load_trace

    def loaded(self):
        traces.append(load_trace(self))
        return traces[-1]
    bench_run.Context.load_trace = loaded
    at_close = []
    terminate = ServingFrontend.terminate_inflight

    def closed(self, *args, **kwargs):
        engines[:] = [self.engine]
        at_close[:] = at_close or counters()
        return terminate(self, *args, **kwargs)
    ServingFrontend.terminate_inflight = closed
    rc = bench_run.main()
    if traces and traces[-1] is not None:
        engine = engines[-1] if engines else None
        line = split_by_rung(
            traces[-1], engine and (lambda rows, chunk:
                                    engine._token_capacities(rows, chunk,
                                                             "split")),
            ops_of=tuple(filter(None, own.scope_ops.split(","))))
        if line is not None:
            print(json.dumps(line), flush=True)
    if built:
        print(json.dumps(built[0]), flush=True)
    if at_open:
        host, wait, calls, ahead, dropped, *work = (
            b - a for a, b in zip(at_open, at_close or counters()))
        window = dict(zip(WORK, work))
        print(json.dumps({
            "phase": "host_counters", "clients": own.clients,
            "traffic": own.traffic, "own_rows": own.own_rows,
            "launches": int(calls),
            "host_s": host, "fetch_wait_s": wait,
            "host_ms_per_launch": 1e3 * host / max(1, calls),
            "fetch_wait_ms_per_launch": 1e3 * wait / max(1, calls),
            "host_share": 100.0 * host / (host + wait)
            if host + wait else None,
            "launches_ahead": int(ahead),
            "ahead_share": 100.0 * ahead / max(1, calls),
            "ahead_rows_dropped": int(dropped),
            "window": window,
            "delta_chunk_live_share": live_share(window),
            "split_steps": split_steps(launches),
            "prefix_cache": {
                **{n: None if a is None else b - a for n, a, b in zip(
                    CACHE_COUNTERS, cache_open, cache_counters())},
                "cache_insert": calls_ms(inserts),
                "cache_evict": calls_ms(evicts),
                "step_ms_max": 1e3 * max(steps, default=0.0)}}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
