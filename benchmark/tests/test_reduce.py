"""The trace reduction: interval arithmetic on made-up events, and the
numbers it reads from the recording of a real v5e trace."""

import json
import os

import pytest

from benchmark.trace import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
SPAN = "benchmark/train_step"


def plane(name, **lines):
    return {"name": name, "lines": [
        {"name": ln.replace("_", " "), "events": evs}
        for ln, evs in lines.items()]}


def ev(name, start, dur):
    return [name, float(start), float(dur), {}]


def made_up():
    ops = [ev("%while.1 = (s32[]) while((s32[]) %tuple.1)", 100, 800),
           ev("%fusion.7 = bf16[8] fusion(bf16[8] %flash_fwd.2)", 100, 300),
           ev("%flash_fwd.2 = bf16[8] custom-call(bf16[8] %p.1)", 400, 200),
           ev("%all-gather.3 = bf16[8] all-gather(bf16[2] %p.2)", 700, 100),
           ev("%fusion.9 = bf16[8] fusion(bf16[8] %p.3)", 1000, 100)]
    host = [ev(SPAN, 50, 900), ev(SPAN, 960, 200), ev("other", 0, 2000)]
    return {"planes": [plane("/device:TPU:0", XLA_Ops=ops),
                       plane("/device:TPU:1", XLA_Ops=[ev("%f = f()", 50, 555)]),
                       plane("/host:CPU", python=host)]}


def test_union_merges_overlaps_and_nesting():
    assert reduce.union_ns([(0, 10), (5, 12), (20, 30), (22, 25)]) == 22


def test_window_is_first_to_last_span_and_busy_is_averaged_over_chips():
    data = made_up()
    assert reduce.traced_window(data, SPAN) == (50.0, 1160.0)
    busy, window = reduce.busy_and_window(data, SPAN)
    # device 0: [100, 900) and [1000, 1100) = 900 ns; device 1: 555 ns
    assert busy == pytest.approx((900 + 555) / 2 / 1e9)
    assert window == pytest.approx(1110 / 1e9)
    # without host spans: the devices' own extent
    assert reduce.traced_window(data, None) == (50.0, 1100.0)


def test_self_time_takes_children_out_and_kernels_match_by_their_own_name():
    data = made_up()
    tops = dict(reduce.top_ops(data, 10))
    assert tops["while.1"] == pytest.approx(200e-9)     # 800 - 300-200-100
    assert tops["fusion.7"] == pytest.approx(300e-9)
    # fusion.7 names flash_fwd.2 only as an OPERAND: not the kernel
    secs, n = reduce.matching_seconds(
        data, lambda e: reduce.op_name(e).startswith("flash_"))
    assert (secs, n) == (pytest.approx(200e-9), 1)
    secs, n = reduce.matching_seconds(
        data, lambda e: bool(reduce.COLLECTIVE.search(reduce.opcode(e))))
    assert (secs, n) == (pytest.approx(100e-9), 1)
    assert reduce.opcode(data["planes"][0]["lines"][0]["events"][2]) == \
        "custom-call"


def test_idle_gaps_go_to_the_innermost_named_span():
    gaps = dict(reduce.idle_gaps(made_up(), [SPAN, "other"]))
    # the one gap, [900, 1000): its midpoint 950 lies between the two
    # benchmark spans, inside "other"
    assert gaps == {"other": pytest.approx(100e-9)}


def test_trim_keeps_events_that_start_inside():
    small = reduce.trim(made_up(), 0, 500)
    names = [e[0].split(" ")[0] for p in small["planes"]
             for ln in p["lines"] for e in ln["events"]]
    assert names == ["%while.1", "%fusion.7", "%flash_fwd.2", "%f", SPAN,
                     "other"]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(os.path.dirname(HERE), "testdata",
                           "v5e_train_step_trace.json")) as fh:
        return json.load(fh)


def test_recorded_v5e_trace(recorded):
    """The first 0.3 s of a fused train step of Mistral-7B widths on a
    v5e (PR 27): numbers read once from the recording and pinned."""
    assert [i for i, _p in reduce.device_planes(recorded)] == [0]
    assert len(reduce.host_events(recorded, SPAN)) == 2
    ops = reduce.line_events(reduce.device_planes(recorded)[0][1],
                             reduce.OPS_LINE)
    lo = min(e[1] for e in ops)
    hi = max(e[1] + e[2] for e in ops)
    busy = reduce.union_ns(reduce.clipped(ops, lo, hi))
    assert busy / (hi - lo) == pytest.approx(0.99255, abs=1e-4)
    secs, n = reduce.matching_seconds(
        recorded, lambda e: reduce.op_name(e).startswith("flash_"))
    assert n == 4 and secs == pytest.approx(0.026438196)
    top = reduce.top_ops(recorded, 3)
    assert [t[0] for t in top] == ["convolution_multiply_fusion.2",
                                   "convolution_add_fusion.6", "fusion.422"]
    assert top[0][1] == pytest.approx(0.049602388)
    assert reduce.idle_gaps(recorded, [SPAN]) == [
        [SPAN, pytest.approx(0.002383137)]]
    kinds = {reduce.opcode(e) for e in ops}
    # (a long instruction, such as a while, lost its opcode to the cut)
    assert {"fusion", "custom-call"} <= kinds
