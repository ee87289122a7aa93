"""``trace/scopes.py`` and the readers built on it (PR 28): device time by
program and scope on made-up events and on the two recordings of real v5e
traces (with a synthetic scope table: the recordings were taken with the
tables of their day, which are not kept), exposed collective time, the
program's span tree, its counters -- and that a program with none of it
(the parent of PR 28) makes every reader return None, not raise."""

import importlib.util
import json
import os
import types

import pytest

from benchmark.lib import stats
from benchmark.trace import reduce, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def reader(metric):
    spec = importlib.util.spec_from_file_location(
        "reader_" + metric.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def view(trace=None, span_name=None, **facts):
    return types.SimpleNamespace(facts=facts, trace=trace, peaks=None,
                                 span_name=span_name, reduce=reduce,
                                 stats=stats)


def ev(name, start, dur):
    return [name, float(start), float(dur), {}]


def plane(name, **lines):
    return {"name": name, "lines": [
        {"name": ln.replace("_", " "), "events": evs}
        for ln, evs in lines.items()]}


SPAN = "benchmark/serve_step"


def made_up():
    """Two launches of a split program and one of a decode program, whose
    ``fusion.1`` is ANOTHER instruction than the split program's."""
    mods = [ev("jit_serve_split_r64_c128(11)", 100, 500),
            ev("jit_serve_decode_r64(22)", 700, 100),
            ev("jit_serve_split_r64_c128(11)", 900, 500)]
    ops = []
    for t0 in (100, 900):
        ops += [ev("%while.1 = (s32[]) while((s32[]) %t)", t0, 500),
                ev("%fusion.1 = bf16[8] fusion(bf16[8] %p)", t0, 300),
                ev("%fusion.2 = bf16[8] fusion(bf16[8] %fusion.1)",
                   t0 + 300, 150)]
    ops += [ev("%fusion.1 = bf16[8] fusion(bf16[8] %q)", 700, 100)]
    host = [ev(SPAN, 50, 600), ev(SPAN, 660, 180), ev(SPAN, 850, 600)]
    return {"planes": [plane("/device:TPU:0", XLA_Modules=mods, XLA_Ops=ops),
                       plane("/host:CPU", python=host)]}


TABLES = {
    "serve_split_r64_c128": {
        "fusion.1": {"scope": "attn_history", "backward": False,
                     "remat": False},
        # a relayout the table's heuristic put down to its user
        "fusion.2": {"scope": "mlp", "backward": False, "remat": False,
                     "inherited": True},
        "while.1": {"scope": None, "backward": False, "remat": False}},
    "serve_decode_r64": {
        "fusion.1": {"scope": "mlp", "backward": False, "remat": False}},
}


def tables(programs):
    return {p: TABLES[p] for p in programs if p in TABLES}


def test_module_names_lose_their_prefix_and_their_id():
    assert scopes.module_name("jit_serve_split_r64_c128(1234567)") == \
        "serve_split_r64_c128"
    assert scopes.module_name("jit_fused_step") == "fused_step"
    assert scopes.module_name("SyncTensorsGraph.7") == "SyncTensorsGraph.7"


def test_rows_are_a_partition_of_busy_time_by_program_and_scope():
    data = made_up()
    win = reduce.traced_window(data, SPAN)
    dev = scopes.attribute(data, win, tables)
    rows = dev["rows"]
    # the same instruction name is another scope in another program
    assert rows[("serve_split_r64_c128", "attn_history", "forward")] == 600
    assert rows[("serve_split_r64_c128", "mlp", "forward")] == 300
    assert rows[("serve_decode_r64", "mlp", "forward")] == 100
    assert rows[("serve_split_r64_c128", scopes.NO_SCOPE, "forward")] == 100
    assert sum(rows.values()) == dev["sum_ns"] == 1100
    busy, _window = reduce.busy_and_window(data, SPAN)
    assert dev["sum_ns"] / 1e9 == pytest.approx(busy)
    assert dev["scoped_ns"] == 1000 and dev["remat_ns"] == 0
    assert dev["ops"][("serve_decode_r64", "fusion.1")] == \
        [100.0, "mlp", "forward", False]
    # what the heuristic assigned is inside the rows, and told apart
    assert dev["inherited_ns"] == 300
    assert dev["inherited_rows"] == \
        {("serve_split_r64_c128", "mlp", "forward"): 300}
    assert dev["ops"][("serve_split_r64_c128", "fusion.2")][3] is True


def test_without_a_table_everything_is_unscoped_and_readers_read_nothing():
    data = made_up()
    dev = scopes.attribute(data, None, lambda programs: {})
    assert dev["scoped_ns"] == 0 and dev["programs"] == []
    run = view(data, SPAN)
    run._scopes_analysis = {"device": dev, "steps": 3, "events": []}
    assert scopes.scope_ms_per_step(run, ["mlp"]) is None
    assert scopes.scope_coverage(run) is None
    assert reader("remat_device_share")(run) is None


def test_scope_readers_divide_by_the_traced_steps():
    data = made_up()
    run = view(data, SPAN)
    run._scopes_analysis = {
        "device": scopes.attribute(
            data, reduce.traced_window(data, SPAN), tables),
        "steps": 3, "events": []}
    assert reader("attn_history_ms_per_step")(run) == \
        pytest.approx(600 / 1e6 / 3)
    assert reader("serve_mlp_ms_per_step")(run) == \
        pytest.approx(400 / 1e6 / 3)
    assert reader("kv_write_ms_per_step")(run) == 0.0
    assert reader("scope_coverage.serve")(run) == \
        pytest.approx(100 * 1000 / 1100)


def test_backward_and_remat_are_kinds_of_their_own():
    mods = [ev("jit_fused_step(5)", 0, 1000)]
    ops = [ev("%fusion.1 = f32[] fusion()", 0, 400),
           ev("%fusion.2 = f32[] fusion()", 400, 300),
           ev("%fusion.3 = f32[] fusion()", 700, 200),
           ev("%fusion.4 = f32[] fusion()", 900, 100)]
    table = {"fused_step": {
        "fusion.1": {"scope": "mlp", "backward": False, "remat": False},
        "fusion.2": {"scope": "mlp", "backward": True, "remat": True},
        "fusion.3": {"scope": "loss", "backward": True, "remat": False},
        "fusion.4": {"scope": "optimizer", "backward": False,
                     "remat": False}}}
    data = {"planes": [plane("/device:TPU:0", XLA_Modules=mods,
                             XLA_Ops=ops)]}
    dev = scopes.attribute(data, None, lambda ps: table)
    assert dev["rows"] == {("fused_step", "mlp", "forward"): 400,
                           ("fused_step", "mlp", "remat"): 300,
                           ("fused_step", "loss", "backward"): 200,
                           ("fused_step", "optimizer", "forward"): 100}
    run = view(data, None)
    run._scopes_analysis = {"device": dev, "steps": 2, "events": []}
    assert reader("remat_device_share")(run) == pytest.approx(30.0)
    assert reader("loss_ms_per_step")(run) == pytest.approx(200 / 1e6 / 2)
    assert reader("optimizer_ms_per_step")(run) == \
        pytest.approx(100 / 1e6 / 2)
    assert reader("scope_coverage.train")(run) == pytest.approx(100.0)


def test_exposed_collective_time_is_what_no_other_operation_covers():
    ops = [ev("%while.1 = (s32[]) while((s32[]) %t)", 0, 1000),
           ev("%fusion.1 = bf16[8] fusion(bf16[8] %p)", 0, 300),
           # a synchronous collective: nothing else runs, all exposed
           ev("%all-gather.3 = bf16[8] all-gather(bf16[2] %p)", 300, 100),
           ev("%fusion.2 = bf16[8] fusion(bf16[8] %p)", 400, 200),
           # the wait at the end of an asynchronous one: exposed
           ev("%collective-permute-done.1 = bf16[8] "
              "collective-permute-done(%s)", 700, 50)]
    # in flight from 350 to 750: hidden under fusion.2 from 400 to 600,
    # alone with the idle device from 600 to 700
    async_ops = [ev("%collective-permute-start.1 = bf16[8] "
                    "collective-permute-start(bf16[8] %p)", 350, 400)]
    data = {"planes": [plane("/device:TPU:0", XLA_Ops=ops,
                             Async_XLA_Ops=async_ops)]}
    ns, count = scopes.exposed_collective_ns(data)
    assert count == 3
    assert ns == 100 + (750 - 600)          # [300, 400) and [600, 750)
    in_flight, _n = reduce.collective_seconds(data)
    assert in_flight * 1e9 == pytest.approx(450) and ns <= 450
    run = view(data, None, traced_steps=2)
    assert reader("collective_exposed_ms_per_step")(run) == \
        pytest.approx(250 / 1e6 / 2)
    assert reader("collective_exposed_ms_per_step")(
        view(data, None)) is None


def tracer_events():
    """Two server steps as the program's tracer records them (us)."""
    def x(name, ts, dur, **args):
        e = {"name": name, "ph": "X", "ts": ts, "dur": dur, "tid": 1}
        if args:
            e["args"] = args
        return e
    out = []
    for t0, program, fetch in ((0, "split", 600_000), (700_000, "decode",
                                                       140_000)):
        out += [x("serving/step", t0, fetch + 5_000),
                x("serving/admit", t0 + 100, 400),
                x("serving/engine_step", t0 + 600, fetch + 3_000,
                  batch=64, program=program),
                x("serving/pack", t0 + 700, 900),
                x("serving/dispatch", t0 + 1_700, 1_200, program=program,
                  tokens=70, slots=8192),
                x("serving/fetch", t0 + 3_000, fetch),
                x("serving/fanout", t0 + fetch + 3_800, 1_000)]
    return out


def test_span_readers_on_the_programs_span_tree():
    run = view(None, SPAN, spans=tracer_events())
    assert reader("serve_host_ms_per_step")(run) == pytest.approx(5.0)
    # the traced spans' own program mix, on the line for a human
    analysis = {"device": None, "steps": 0, "events": tracer_events()}
    (line,) = scopes.report_lines(run, analysis)
    assert line["phase"] == "host_spans"
    assert line["programs"] == {"split": 1, "decode": 1}
    selfs = scopes.span_self_ms(tracer_events())
    assert selfs["serving/fetch"] == [600.0, 140.0]
    # a step's self time: it less admit, engine_step and fanout
    assert selfs["serving/step"][0] == pytest.approx(
        (605_000 - 400 - 603_000 - 1_000) / 1e3)
    train = view(None, None, spans=[
        {"name": "train/step", "ph": "X", "ts": i * 1e6, "dur": d, "tid": 1}
        for i, d in enumerate((4_000, 6_000, 5_000))])
    assert reader("train_host_ms_per_step")(train) == pytest.approx(5.0)


def test_a_program_without_spans_or_counters_reads_nothing(monkeypatch):
    """The parent of PR 28: ``serving/dispatch`` does not exist, nor do the
    counters; nothing raises, every reader returns None."""
    run = view(None, SPAN, spans=[
        {"name": "serving/engine_step", "ph": "X", "ts": 0, "dur": 5,
         "tid": 1, "args": {"batch": 64}}])
    assert reader("serve_host_ms_per_step")(run) is None
    monkeypatch.setattr(scopes, "counter_value", lambda name: None)
    monkeypatch.setattr(scopes, "counters_with_prefix", lambda prefix: {})
    assert reader("decode_program_step_share")(run) is None
    assert reader("token_slot_utilization")(run) is None
    assert reader("kv_extent_utilization")(run) is None
    # a program whose compile monitor has no scope table
    import deepspeed_tpu.telemetry as telemetry
    monkeypatch.setattr(telemetry, "compile_monitor", object())
    assert scopes.program_tables(["fused_step"]) == {}


def test_counter_ratios_read_the_programs_registry():
    from deepspeed_tpu.telemetry.registry import registry
    steps = ("dispatch/steps.split", "dispatch/steps.decode",
             "dispatch/steps.megastep")
    for name in ("dispatch/tokens", "dispatch/token_slots",
                 "dispatch/context_tokens", "dispatch/context_slots") + \
            tuple(n for n in registry.names()
                  if n.startswith("dispatch/steps.")):
        registry.unregister(name)
    run = view(None, SPAN)
    assert reader("token_slot_utilization")(run) is None
    assert reader("decode_program_step_share")(run) is None
    for name, by in zip(steps, (60, 3, 1)):
        registry.counter(name).inc(by)
    registry.counter("dispatch/tokens").inc(410)
    registry.counter("dispatch/token_slots").inc(8192)
    registry.counter("dispatch/context_tokens").inc(30_000)
    registry.counter("dispatch/context_slots").inc(262_144)
    try:
        assert reader("token_slot_utilization")(run) == \
            pytest.approx(100 * 410 / 8192)
        assert reader("kv_extent_utilization")(run) == \
            pytest.approx(100 * 30_000 / 262_144)
        # decode and megastep launches over all launches, by the counters
        assert reader("decode_program_step_share")(run) == \
            pytest.approx(100 * 4 / 64)
    finally:
        for name in ("dispatch/tokens", "dispatch/token_slots",
                     "dispatch/context_tokens",
                     "dispatch/context_slots") + steps:
            registry.unregister(name)


# -- the recordings of real v5e traces ------------------------------------------

def recording(name):
    with open(os.path.join(BENCH, "testdata", name)) as fh:
        return json.load(fh)


def synthetic_tables(data, rule):
    """A scope table for every program of the recording's ``XLA Modules``
    line: ``rule(instruction name)`` -> scope or None."""
    plane0 = reduce.device_planes(data)[0][1]
    names = {reduce.op_name(e)
             for e in reduce.line_events(plane0, reduce.OPS_LINE)}
    table = {n: {"scope": rule(n), "backward": False,
                 "remat": n.endswith(".remat")} for n in names}
    programs = {scopes.module_name(e[0])
                for e in reduce.line_events(plane0, scopes.MODULES_LINE)}
    return lambda asked: {p: table for p in asked if p in programs}


def test_recorded_v5e_train_trace_partitions_under_a_synthetic_table():
    data = recording("v5e_train_step_trace.json")
    span = "benchmark/train_step"
    rule = (lambda n: "attn_core" if n.startswith("flash_") else
            "mlp" if "convolution" in n else
            None if n.startswith("copy") else "norm")
    dev = scopes.attribute(data, None, synthetic_tables(data, rule))
    assert "fused_step" in dev["programs"]
    plane0 = reduce.device_planes(data)[0][1]
    selfs = sum(ns for _e, ns in reduce.self_times(
        reduce.line_events(plane0, reduce.OPS_LINE)))
    assert sum(dev["rows"].values()) == pytest.approx(selfs)
    flash = sum(ns for (_p, s, _k), ns in dev["rows"].items()
                if s == "attn_core")
    secs, _n = reduce.matching_seconds(
        data, lambda e: reduce.op_name(e).startswith("flash_"))
    assert flash / 1e9 == pytest.approx(secs)
    assert 0 < dev["scoped_ns"] < dev["sum_ns"]
    run = view(data, span, traced_steps=2)
    run._scopes_analysis = {"device": dev, "steps": 2, "events": []}
    assert 0 < reader("scope_coverage.train")(run) < 100
    lines = scopes.report_lines(run, run._scopes_analysis)
    assert lines[0]["phase"] == "device_by_scope"
    assert lines[0]["sum_s"] == pytest.approx(selfs / 1e9)
    assert len(lines[0]["heaviest"]) == 10


def test_recorded_v5e_serve_trace_names_its_program_and_partitions():
    """1.2 s of the serving cell on a v5e (PR 28, ``--dump-trace``): the
    ``XLA Modules`` line names the split program, not ``jit_fn``."""
    data = recording("v5e_serve_step_trace.json")
    plane0 = reduce.device_planes(data)[0][1]
    programs = {scopes.module_name(e[0])
                for e in reduce.line_events(plane0, scopes.MODULES_LINE)}
    assert "serve_split_r64_c128" in programs and "fn" not in programs
    rule = (lambda n: "attn_core" if n.startswith("flash_") else
            "mlp" if "convolution" in n else "attn_history")
    dev = scopes.attribute(data, None, synthetic_tables(data, rule))
    selfs = sum(ns for _e, ns in reduce.self_times(
        reduce.line_events(plane0, reduce.OPS_LINE)))
    assert sum(dev["rows"].values()) == pytest.approx(selfs)
    split = sum(ns for (p, _s, _k), ns in dev["rows"].items()
                if p == "serve_split_r64_c128")
    assert split > 0.9 * selfs
    # the program's spans reached the profiler as annotations
    for name in ("serving/step", "serving/dispatch", "serving/fetch"):
        assert reduce.host_events(data, name), name
