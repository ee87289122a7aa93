"""``lib/host_path.py`` (PR 39) on a made-up profile: the device's idle time
split over the program's leaf spans by overlap. Two server steps on one
clock (ns): a decode program whose fetch holds a head and a tail, then a
split program with a bubble inside it; the long gap between them lies
across ten spans, its MIDDLE under ``serving/fanout``."""

import json
import os

import pytest

from benchmark.lib import host_path
from benchmark.tests.test_scopes import SPAN, ev, plane, reader, view
from benchmark.trace import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = ("fanout", "frontend", "caller", "launch_and_fetch")


def step(t0, spans):
    """``serving/step`` at ``t0`` with that ``[(leaf, duration)]`` tiling:
    the engine's leaves under one ``serving/engine_step``."""
    out, t, engine = [], t0, []
    for name, dur in spans:
        out.append(ev(name, t, dur))
        if name in ("serving/schedule", "serving/pack", "serving/dispatch",
                    "serving/count", "serving/fetch", "serving/retire"):
            engine.append((t, t + dur))
        t += dur
    return [ev("serving/step", t0, t - t0),
            ev("serving/engine_step", engine[0][0],
               engine[-1][1] - engine[0][0])] + out


def made_up():
    host = [ev(SPAN, 0, 1_000), ev(SPAN, 1_100, 1_100)]
    host += step(0, [("serving/admit", 20), ("serving/plan", 10),
                     ("serving/schedule", 30), ("serving/pack", 100),
                     ("serving/dispatch", 40), ("serving/count", 20),
                     ("serving/fetch", 580),        # 220 .. 800
                     ("serving/retire", 20), ("serving/bookkeeping", 30),
                     ("serving/fanout", 120), ("serving/bookkeeping", 30)])
    host += [ev("serving/submit", 1_020, 40)]
    host += step(1_100, [("serving/admit", 20), ("serving/plan", 10),
                         ("serving/schedule", 30), ("serving/pack", 100),
                         ("serving/dispatch", 40), ("serving/count", 20),
                         ("serving/fetch", 780),    # 1320 .. 2100
                         ("serving/retire", 20), ("serving/bookkeeping", 30),
                         ("serving/fanout", 20), ("serving/bookkeeping", 30)])
    mods = [ev("jit_serve_decode_r64(1)", 250, 480),         # .. 730
            ev("jit_serve_split_r64_c128(2)", 1_330, 700)]   # .. 2030
    ops = [ev("%fusion.1 = bf16[8] fusion(bf16[8] %p)", 250, 480),
           ev("%fusion.2 = bf16[8] fusion(bf16[8] %p)", 1_330, 300),
           # a bubble of 60 inside the split program
           ev("%fusion.3 = bf16[8] fusion(bf16[8] %p)", 1_690, 340)]
    other = [ev("serving/step", 0, 5), ev("something/else", 900, 50)]
    return {"planes": [plane("/device:TPU:0", XLA_Modules=mods, XLA_Ops=ops),
                       plane("/host:CPU", worker=other, python3=host)]}


def test_the_parts_sum_to_the_idle_time_to_the_nanosecond():
    trace = made_up()
    run = view(trace, SPAN)
    p = host_path.partition(run)
    busy_s, window_s = reduce.busy_and_window(trace, SPAN)
    assert p["steps"] == 2 and window_s == pytest.approx(2_200e-9)
    assert abs(sum(p["parts"].values()) - (window_s - busy_s) * 1e9) < 1.0
    assert abs(p["idle_ns"] - sum(p["parts"].values())) < 1.0
    assert abs(sum(v for row in p["by_kind"].values()
                   for v in row.values()) - p["idle_ns"]) < 1.0
    # the four metrics, the program's own bubbles and the guard's part
    # are all of it, a step
    total = sum(reader("idle_ms_per_step." + m)(run) for m in METRICS)
    assert total * 1e6 * 2 + p["parts"][host_path.IN_PROGRAM] == \
        pytest.approx(p["idle_ns"])
    assert sum(host_path.grouped(p["parts"]).values()) == \
        pytest.approx(p["idle_ns"])
    assert reader("idle_attributed_share.serve")(run) == 100.0
    assert p["launches"] == {"decode": 1, "split": 1}


def test_a_gap_across_spans_is_split_by_overlap_not_given_to_its_midpoint():
    trace = made_up()
    p = host_path.split_idle(trace, reduce.traced_window(trace, SPAN))
    parts = p["parts"]
    # the gap 730 .. 1330: its midpoint 1030 lies under serving/submit, and
    # reduce.idle_gaps gives all 600 of it to the span it is told of there
    gaps = dict(reduce.idle_gaps(trace, ["serving/fanout", "serving/fetch",
                                         "serving/submit"]))
    assert gaps["serving/submit"] == pytest.approx(600e-9)
    assert "serving/fanout" not in gaps
    # by overlap each phase gets what it held of it
    assert parts["serving/fanout"] == 120 + 20      # both steps' fan-outs
    assert parts["serving/retire"] == 20 + 20
    assert parts["serving/bookkeeping"] == 60 + 60
    assert parts["serving/submit"] == 40
    assert parts["serving/admit"] == 20 + 20 and parts["serving/plan"] == 20
    assert parts["serving/schedule"] == 30 + 30
    assert parts["serving/pack"] == 100 + 100
    assert parts["serving/dispatch"] == 40 + 40
    assert parts["serving/count"] == 20 + 20
    # outside every step and submit: 1000 .. 1020 and 1060 .. 1100
    assert parts[host_path.CALLER] == 20 + 40
    assert host_path.UNATTRIBUTED not in parts
    # what precedes the split launch is put down to it, by kind
    split = host_path.grouped(p["by_kind"]["split"])
    assert split["fanout"] == 120 and split["caller"] == 60 + 40
    # ... its own way to the call, the latency, and the way back of the
    # DECODE program's result, which is idle time before the split launch
    assert split["launch_and_fetch"] == 30 + 100 + 40 + 20 + 10 + 70
    assert split["in_program"] == 60
    decode = host_path.grouped(p["by_kind"]["decode"])
    assert decode["launch_and_fetch"] == 30 + 100 + 40 + 20 + 30


def test_fetch_head_is_launch_latency_and_its_tail_the_way_back(capsys):
    trace = made_up()
    parts = host_path.split_idle(
        trace, reduce.traced_window(trace, SPAN))["parts"]
    # fetch 220 .. 800 around the program 250 .. 730; 1320 .. 2100 around
    # 1330 .. 2030 (the window ends at 2200: the last 100 are the step's)
    assert parts[host_path.FETCH_HEAD] == 30 + 10
    assert parts[host_path.FETCH_TAIL] == 70 + 70
    # ONE metric holds both and the way to the call; the line has the
    # pieces, and what the last two may trade: half of 70 - (-70) a launch
    run = view(trace, SPAN)
    assert reader("idle_ms_per_step.launch_and_fetch")(run) == \
        pytest.approx((2 * 190 + 40 + 140) / 1e6 / 2)
    line = json.loads(capsys.readouterr().out)
    assert line["launch_and_fetch_ms_per_step"] == pytest.approx({
        "to_the_call": 2 * 130 / 1e6 / 2,
        "call_to_first_op": (2 * 60 + 40) / 1e6 / 2,
        "last_op_to_return": 140 / 1e6 / 2, "plus_minus": 70 / 1e6})


@pytest.mark.parametrize("early", [-60.0, -30.0, 0.0, 45.0, 100.0, 160.0])
def test_no_metric_depends_on_where_the_device_clock_is_put(early):
    """The device's events stamped ``early`` ns too early, and NO shift
    applied (``clock_lag`` made to say 0: the worst a wrong middle does,
    with every program still between its call and its fetch's return):
    ``fetch:head`` and ``fetch:tail`` trade ``early`` a launch, and every
    metric reads what it read on one clock."""
    trace = made_up()
    for line in trace["planes"][0]["lines"]:
        for e in line["events"]:
            e[1] -= early
    window = reduce.traced_window(trace, SPAN)
    true = host_path.split_idle(made_up(), window)
    real_lag = host_path.clock_lag
    host_path.clock_lag = lambda programs, spans: (0.0, 0.0)
    try:
        p = host_path.split_idle(trace, window)
    finally:
        host_path.clock_lag = real_lag
    if early:
        assert p["parts"][host_path.FETCH_TAIL] != \
            true["parts"][host_path.FETCH_TAIL]
    # (the window's own edges aside: the last program's tail ends 70
    # before the last fetch returns, so up to there nothing crosses)
    moved, was = host_path.grouped(p["parts"]), \
        host_path.grouped(true["parts"])
    for name in METRICS + (host_path.IN_PROGRAM, host_path.UNATTRIBUTED):
        assert moved[name] == pytest.approx(was[name]), name


def test_a_bubble_inside_a_module_is_the_programs_own(capsys):
    trace = made_up()
    parts = host_path.split_idle(
        trace, reduce.traced_window(trace, SPAN))["parts"]
    assert parts[host_path.IN_PROGRAM] == 60
    # no metric of its own (nothing can move it): a field of the line
    host_path.partition(view(trace, SPAN))
    line = json.loads(capsys.readouterr().out)
    assert line["ms_per_step"]["in_program"] == pytest.approx(60 / 1e6 / 2)


def test_a_loop_event_over_the_bubble_makes_it_busy_time_and_says_so():
    """``busy_s`` is the union of ALL operation events and a ``while``
    spans its body: a program that is one layer loop has no idle time
    inside it by that rule. The line says how much only the loop covers."""
    trace = made_up()
    trace["planes"][0]["lines"][1]["events"].append(
        ev("%while.1 = (s32[]) while((s32[]) %t)", 1_330, 700))
    p = host_path.split_idle(trace, reduce.traced_window(trace, SPAN))
    assert host_path.IN_PROGRAM not in p["parts"]
    assert p["bubbles_ns"] == 60 and p["idle_ns"] == 1_080 - 60


def test_idle_under_a_parent_but_no_leaf_lowers_the_attributed_share():
    """A program without the leaves (the parent of PR 39): what it has is
    read, the rest of a step is ``unattributed``, nothing raises."""
    trace = made_up()
    old = ("serving/step", "serving/engine_step", "serving/admit",
           "serving/pack", "serving/dispatch", "serving/fetch",
           "serving/fanout", SPAN)
    line = trace["planes"][1]["lines"][1]
    line["events"] = [e for e in line["events"] if e[0] in old]
    run = view(trace, SPAN)
    p = host_path.partition(run)
    lost = 2 * (10 + 30 + 20 + 20 + 30 + 30)
    assert p["parts"][host_path.UNATTRIBUTED] == lost
    assert p["parts"][host_path.CALLER] == 60 + 40      # submit's span too
    assert reader("idle_attributed_share.serve")(run) == \
        pytest.approx(100.0 * (1 - lost / p["idle_ns"]))
    assert abs(sum(p["parts"].values()) - p["idle_ns"]) < 1.0


def test_no_device_events_reads_nothing(capsys):
    trace = made_up()
    trace["planes"][0]["lines"][1]["events"] = []
    for run in (view(trace, SPAN), view(None, SPAN),
                view({"planes": trace["planes"][1:]}, SPAN)):
        assert host_path.partition(run) is None
        for m in METRICS:
            assert reader("idle_ms_per_step." + m)(run) is None
        assert reader("idle_attributed_share.serve")(run) is None
    assert capsys.readouterr().out == ""


def test_the_host_path_line_and_the_parents_self_time(capsys):
    run = view(made_up(), SPAN)
    host_path.partition(run)
    host_path.partition(run)                # once a run
    (line,) = [json.loads(s) for s in
               capsys.readouterr().out.splitlines()]
    assert line["phase"] == "host_path" and line["steps"] == 2
    assert line["idle_s"] == pytest.approx(1_080e-9)
    assert line["ms_per_launch"]["split"]["launches"] == 1
    assert line["ms_per_step"]["fanout"] == pytest.approx(140 / 1e6 / 2)
    # the idle time before each of the two launches: 250 and 600
    assert line["gap_ms"] == {"median": 425 / 1e6, "max": 600 / 1e6,
                              "over_3_medians": 0, "over_3_medians_s": 0.0}
    # the leaves tile both parents: nothing of a step lies outside them
    assert line["parent_self_ms_median"] == {"serving/step": 0.0,
                                             "serving/engine_step": 0.0}


def test_parts_that_miss_the_device_lines_idle_time_read_nothing(
        capsys, monkeypatch):
    """The readers' own check: the parts against ``busy_and_window``, the
    ``device`` line's source. Another window than its gives nothing, and
    the line says why."""
    trace = made_up()
    run = view(trace, SPAN)
    window = reduce.traced_window(trace, SPAN)
    monkeypatch.setattr(reduce, "busy_and_window",
                        lambda data, name: (1_000e-9, 2_200e-9))
    assert window == (0, 2_200)
    assert host_path.partition(run) is None
    for m in METRICS:
        assert reader("idle_ms_per_step." + m)(run) is None
    (line,) = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    assert line["phase"] == "host_path" and "error" in line
    assert line["parts_s"] == pytest.approx(1_080e-9)
    assert line["device_idle_s"] == pytest.approx(1_200e-9)


def test_recorded_v5e_serve_trace_is_acausal_as_stamped_and_is_shifted():
    """The recording of PR 28's day: one split step of a program that had
    five of the leaves. Its program begins 0.9 ms BEFORE the call that
    launched it and ends 2.8 ms before the fetch returns: the device's
    clock lags by something between the two, and the idle time is laid
    over the spans at their middle. (The recording's window is the
    device's own extent; it is opened here where the runner's span is.)"""
    with open(os.path.join(os.path.dirname(HERE), "testdata",
                           "v5e_serve_step_trace.json")) as fh:
        trace = json.load(fh)
    (runner_span,) = reduce.host_events(trace, SPAN)
    window = (runner_span[1], reduce.traced_window(trace, SPAN)[1])
    p = host_path.split_idle(trace, window)
    at_least, at_most = p["clock_lag_ns"]
    assert 0.89e6 < at_least < 0.90e6 and 2.84e6 < at_most < 2.85e6
    assert p["shift_ns"] == (at_least + at_most) / 2
    # the sum is the device's clock's own, whatever the shift
    plane = dict(reduce.device_planes(trace))[0]
    busy = reduce.union_ns(reduce.clipped(
        reduce.line_events(plane, reduce.OPS_LINE), *window))
    assert abs(sum(p["parts"].values()) - p["idle_ns"]) < 1.0
    assert abs(p["idle_ns"] - (window[1] - window[0] - busy)) < 1.0
    # what the device waited before its first operation lies under the
    # call and the head of the fetch (as stamped, the program "ran"
    # through the pack and the call)
    # (up to the program's module event, 302 ns before its first operation)
    before = window[0] + p["shift_ns"], 44_220_043 + p["shift_ns"]
    assert before[0] > 45_118_199       # serving/dispatch's start
    assert p["parts"]["serving/dispatch"] == pytest.approx(
        45_118_199 + 579_380 - before[0], abs=2)
    assert p["parts"][host_path.FETCH_HEAD] == pytest.approx(
        before[1] - 45_762_389, abs=2)
    assert p["by_kind"]["split"][host_path.IN_PROGRAM] >= 302
    assert set(p["launches"]) == {"split"}


def test_a_lagging_device_clock_is_moved_to_the_middle_of_its_bounds(capsys):
    """Device events stamped 100 too early: the split launch's program
    would begin 30 before its call, and both programs would end 170 before
    their fetch returns. The middle of the two bounds (100) drops the
    capture's offset out: every part reads what it reads on one clock in
    the window the device line's idle time is measured in, which is the
    runner's on the DEVICE's clock: 100 later on the host's."""
    trace = made_up()
    for line in trace["planes"][0]["lines"]:
        for e in line["events"]:
            e[1] -= 100.0
    window = reduce.traced_window(trace, SPAN)
    p = host_path.split_idle(trace, window)
    assert p["clock_lag_ns"] == (30.0, 170.0) and p["shift_ns"] == 100.0
    aligned = host_path.split_idle(made_up(),
                                   (window[0] + 100, window[1] + 100))
    assert aligned["clock_lag_ns"] == (-70.0, 70.0)
    assert aligned["shift_ns"] == 0.0
    assert p["parts"] == aligned["parts"]
    assert p["by_kind"] == aligned["by_kind"]
    assert p["idle_ns"] == aligned["idle_ns"]
    # and the sum is the device line's, to the nanosecond, shift or none
    busy_s, window_s = reduce.busy_and_window(trace, SPAN)
    assert abs(p["idle_ns"] - (window_s - busy_s) * 1e9) < 1.0
    # as stamped, the midpoint rule's victim would be another span again
    assert dict(reduce.idle_gaps(trace, ["serving/fanout",
                                         "serving/submit"]))[
        "serving/fanout"] == pytest.approx(600e-9)
    run = view(trace, SPAN)
    host_path.partition(run)
    line = json.loads(capsys.readouterr().out)
    assert line["device_clock_lag_ms"] == {
        "at_least": 30 / 1e6, "at_most": 170 / 1e6, "applied": 100 / 1e6}
