"""Telemetry tests: span tracer, metrics registry, samplers, summarize CLI,
and the traced-train-step smoke (the ISSUE 3 acceptance flow: one tiny CPU
step with tracing on → dumped Chrome JSON loads → summarize prints a
self-time table with the train/forward|backward|optimizer spans →
metrics_text() exposes train_step_time_ms / train_mfu / serving_ttft_seconds
in Prometheus format).
"""

import json
import math
import os
import re
import time

import numpy as np
import pytest
import jax

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import summarize
from deepspeed_tpu.telemetry.registry import (Counter, Gauge, Histogram,
                                              MetricsRegistry, prom_name)
from deepspeed_tpu.telemetry.sampler import (MemorySampler,
                                             device_memory_stats,
                                             host_rss_bytes, mfu, peak_flops)
from deepspeed_tpu.telemetry.tracer import Tracer


# ---------------------------------------------------------------- tracer

def test_span_nesting_and_ordering(tmp_path):
    t = Tracer()
    t.configure(enabled=True)
    with t.span("outer", step=3):
        with t.span("inner"):
            time.sleep(0.002)
    t.instant("mark", bytes=7)
    evs = t.events()
    # inner closes (and records) before outer
    assert [e["name"] for e in evs] == ["inner", "outer", "mark"]
    inner, outer = evs[0], evs[1]
    # containment: outer's window covers inner's
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert outer["args"]["step"] == 3
    assert evs[2]["ph"] == "i" and evs[2]["args"]["bytes"] == 7


def test_disabled_tracer_records_nothing():
    t = Tracer()
    with t.span("x"):
        pass
    t.instant("y")
    t.complete("z", 0.0, 1.0)
    assert t.events() == []


def test_ring_buffer_evicts_and_counts():
    t = Tracer(buffer_events=4)
    t.configure(enabled=True)
    for i in range(10):
        t.instant(f"e{i}")
    assert len(t.events()) == 4
    assert t.dropped == 6
    assert [e["name"] for e in t.events()] == ["e6", "e7", "e8", "e9"]


def test_chrome_trace_schema(tmp_path):
    t = Tracer()
    t.configure(enabled=True)
    with t.span("a"):
        pass
    t.complete("b", t.now() - 0.01, t.now(), tid=42, reason="done")
    path = t.dump(str(tmp_path / "sub" / "trace.json"))   # parent dir made
    with open(path) as fh:
        doc = json.load(fh)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for e in evs:
        assert e["ph"] == "X" and e["cat"] == "dstpu"
        for field in ("name", "ts", "dur", "pid", "tid"):
            assert field in e, f"missing {field}"
        assert e["pid"] == os.getpid()
        assert e["dur"] >= 0.0
    assert {e["name"] for e in evs} == {"a", "b"}
    b = next(e for e in evs if e["name"] == "b")
    assert b["tid"] == 42 and b["args"]["reason"] == "done"


def test_threaded_recording_is_safe():
    import threading
    t = Tracer()
    t.configure(enabled=True)

    def worker(i):
        for _ in range(50):
            with t.span(f"w{i}"):
                pass
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    evs = t.events()
    assert len(evs) == 200              # no lost updates under contention
    from collections import Counter as C
    assert C(e["name"] for e in evs) == {f"w{i}": 50 for i in range(4)}


# -------------------------------------------------------------- registry

def test_counter_gauge_semantics():
    r = MetricsRegistry()
    c = r.counter("n")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("v")
    g.set(2.5)
    g.inc(0.5)
    assert g.value == 3.0
    assert r.counter("n") is c          # get-or-create returns same object
    with pytest.raises(TypeError):
        r.gauge("n")                    # type mismatch


def test_histogram_overflow_bucket():
    h = Histogram(lo=0.001, hi=10.0, n_buckets=20)
    h.record(10.0)       # exactly hi → top regular bucket, NOT overflow
    h.record(11.0)       # > hi → overflow
    h.record(1e9)
    assert h.counts[-1] == 2
    assert h.bounds[-1] == 10.0
    assert h.vmax == 1e9 and h.vmin == 10.0
    assert h.percentile(99) == 1e9      # overflow percentile = exact vmax
    assert h.percentile(1) <= h.percentile(50) <= h.percentile(99)
    h.record(float("nan"))              # ignored
    assert h.count == 3


def test_prometheus_exposition_parses():
    r = MetricsRegistry()
    r.counter("comm/bytes", help="total bytes").inc(128)
    r.gauge("train/mfu").set(0.41)
    h = r.histogram("train/step_time_ms", lo=0.1, hi=1000.0, n_buckets=8)
    h.record(5.0)
    h.record(5000.0)    # overflow
    text = r.prometheus_text()
    lines = text.strip().splitlines()
    # every line is a comment or `name{labels} value` / `name value`
    sample_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{le=\"[^\"]+\"\})? [^ ]+$")
    types = {}
    for ln in lines:
        if ln.startswith("# TYPE"):
            _, _, name, kind = ln.split()
            types[name] = kind
        elif not ln.startswith("#"):
            assert sample_re.match(ln), ln
    assert types == {"comm_bytes": "counter", "train_mfu": "gauge",
                     "train_step_time_ms": "histogram"}
    assert "# HELP comm_bytes total bytes" in lines
    assert "comm_bytes 128" in lines
    assert "train_mfu 0.41" in lines
    # histogram: cumulative buckets, +Inf == _count, _sum exact
    buckets = [ln for ln in lines if "_bucket" in ln]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts)                 # cumulative
    assert buckets[-1].startswith('train_step_time_ms_bucket{le="+Inf"}')
    assert counts[-1] == 2
    assert "train_step_time_ms_count 2" in lines
    assert "train_step_time_ms_sum 5005" in lines


def test_prom_name_sanitization():
    assert prom_name("train/step_time_ms") == "train_step_time_ms"
    assert prom_name("serving/ttft.p99") == "serving_ttft_p99"
    assert prom_name("9lives") == "_9lives"


def test_registry_events_and_monitor_bridge():
    r = MetricsRegistry()
    r.counter("a").inc(2)
    r.gauge("b").set(7.0)
    h = r.histogram("c", lo=0.1, hi=10.0, n_buckets=4)
    h.record(1.0)

    class FakeMonitor:
        enabled = True
        events = []

        def write_events(self, ev):
            self.events = list(ev)

    mon = FakeMonitor()
    r.flush_to_monitor(mon, step=5)
    names = {n for n, _, _ in mon.events}
    assert names == {"a", "b", "c_mean", "c_p99", "c_count"}
    assert all(s == 5 for _, _, s in mon.events)
    mon.enabled = False
    mon.events = None
    r.flush_to_monitor(mon, step=6)     # disabled → untouched
    assert mon.events is None


def test_register_replace_semantics():
    r = MetricsRegistry()
    h1 = Histogram()
    r.register("serving/ttft_seconds", h1)
    with pytest.raises(ValueError):
        r.register("serving/ttft_seconds", Histogram())
    h2 = Histogram()
    r.register("serving/ttft_seconds", h2, replace=True)
    assert r.get("serving/ttft_seconds") is h2


# --------------------------------------------------------------- sampler

def test_mfu_hand_computed():
    # 1e12 FLOPs over 2 s on 2 chips of 250 GFLOPs/s peak → exactly 1.0
    assert mfu(1e12, 2.0, n_devices=2, peak=250e9) == pytest.approx(1.0)
    # half the work → 0.5
    assert mfu(5e11, 2.0, n_devices=2, peak=250e9) == pytest.approx(0.5)
    # undefined cases → 0.0, never a crash
    assert mfu(0.0, 1.0, peak=1e12) == 0.0
    assert mfu(1e12, 0.0, peak=1e12) == 0.0
    assert mfu(1e12, 1.0, peak=0.0) == 0.0


def test_peak_flops_table():
    class Dev:
        def __init__(self, kind):
            self.device_kind = kind
    assert peak_flops(Dev("TPU v5p")) == 459e12
    assert peak_flops(Dev("TPU v5 lite")) == 197e12
    assert peak_flops(Dev("cpu")) == 0.0           # CPU: MFU undefined
    assert peak_flops(jax.devices()[0]) == 0.0     # test mesh is CPU


def test_peak_flops_unknown_tpu_kind_raises():
    """A device whose platform is ``tpu`` and whose ``device_kind`` is in
    no table is an error (a peak of 0 would make MFU read 0 instead of
    failing); the attached chip's kind still resolves."""
    class Dev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind
    assert peak_flops(Dev("TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="matches no entry"):
        peak_flops(Dev("TPU v99 imaginary"))


def test_sampler_cpu_noop():
    """On the CPU backend memory_stats is unavailable — every probe must
    degrade cleanly, and sample() must still publish what it CAN get."""
    assert device_memory_stats() is None
    rss = host_rss_bytes()
    assert rss is None or rss > 0
    r = MetricsRegistry()
    out = MemorySampler(registry=r).sample()        # must not raise
    for name, val in out.items():
        assert r.gauge(name).value == val
        assert val >= 0


# ------------------------------------------------------------- summarize

def _ev(name, ts, dur, tid=1):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur,
            "pid": 1, "tid": tid}


def test_self_times_attribution():
    # parent [0, 100] with children [10, 30] and [50, 20] → self = 50
    evs = [_ev("parent", 0, 100), _ev("child", 10, 30), _ev("child", 50, 20)]
    st = summarize.self_times(evs)
    assert st["parent"]["total_us"] == 100
    assert st["parent"]["self_us"] == 50
    assert st["child"]["count"] == 2 and st["child"]["self_us"] == 50
    # separate tracks never parent each other
    st2 = summarize.self_times([_ev("a", 0, 100, tid=1),
                                _ev("b", 10, 30, tid=2)])
    assert st2["a"]["self_us"] == 100
    assert st2["b"]["self_us"] == 30


def test_summarize_cli(tmp_path, capsys):
    doc = {"traceEvents": [_ev("outer", 0, 1000), _ev("inner", 100, 400)],
           "displayTimeUnit": "ms"}
    path = tmp_path / "t.json"
    path.write_text(json.dumps(doc))
    assert summarize.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "outer" in out and "inner" in out
    assert "self ms" in out
    # bare-list form also accepted
    path2 = tmp_path / "bare.json"
    path2.write_text(json.dumps(doc["traceEvents"]))
    assert summarize.main([str(path2), "--sort", "total", "--top", "1"]) == 0


# ------------------------------------------------------------------ timer

def test_timer_satellite_fixes():
    from deepspeed_tpu.utils.timer import _Timer
    t = _Timer("t")
    assert t.mean() == 0.0 and t.elapsed() == 0.0   # empty: no raise
    t.start()
    t.stop(record=False)
    t.start()                                        # started was reset
    t.stop()
    assert len(t.records) == 1 and t.mean() > 0.0
    t.start()
    t.reset()                                        # clears in-flight start
    assert not t.started and t.records == [] and t.elapsed() == 0.0
    t.start()                                        # usable after reset
    t.stop()
    assert len(t.records) == 1


# ------------------------------------------- config + end-to-end smoke

def test_telemetry_config_section():
    from deepspeed_tpu.config.config import DeepSpeedTPUConfig
    cfg = DeepSpeedTPUConfig.from_any({
        "train_micro_batch_size_per_gpu": 1,
        "telemetry": {"enabled": True, "trace_buffer_events": 500,
                      "jax_annotations": False}})
    assert cfg.telemetry.enabled
    assert cfg.telemetry.trace_buffer_events == 500
    assert DeepSpeedTPUConfig.from_any(None).telemetry.enabled is False


@pytest.fixture()
def clean_global_telemetry():
    """The smoke test drives the process-wide tracer/registry; leave them
    as found so other test files see a quiet baseline."""
    telemetry.tracer.clear()
    telemetry.tracer.configure(enabled=True)
    yield
    telemetry.tracer.configure(enabled=False)
    telemetry.tracer.clear()


def test_traced_train_step_smoke(devices, tmp_path, capsys,
                                 clean_global_telemetry):
    """ISSUE 3 acceptance: one tiny traced CPU step → dumped JSON loads →
    `python -m deepspeed_tpu.telemetry.summarize` prints a per-span
    self-time table including train/forward, train/backward,
    train/optimizer → metrics_text() has train_step_time_ms, train_mfu and
    serving_ttft_seconds in Prometheus exposition format."""
    from deepspeed_tpu.models.gpt import gpt2_config
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import initialize
    from deepspeed_tpu.serving.metrics import ServingMetrics

    build_mesh(data=8)
    # the registry is process-wide: other test files' engines also bump
    # train/steps, so assert on the delta, not the absolute value
    steps_before = telemetry.registry.counter("train/steps").value
    model = gpt2_config("tiny", max_seq_len=32, vocab_size=128)
    engine, *_ = initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "telemetry": {"enabled": True}},
        rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, size=(8, 32),
                                       dtype=np.int32)}
    # the 3-call parity API exercises the forward/backward/optimizer spans
    for _ in range(2):
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
    # fused path exercises the train/step envelope + step metrics
    engine.train_batch(iter([batch]))
    assert np.isfinite(float(loss))

    trace_path = str(tmp_path / "trace.json")
    telemetry.tracer.dump(trace_path)
    with open(trace_path) as fh:
        doc = json.load(fh)                         # valid JSON
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"train/forward", "train/backward", "train/optimizer",
            "train/step"} <= names

    # the CLI entry point (same function `python -m ...summarize` runs)
    assert summarize.main([trace_path]) == 0
    table = capsys.readouterr().out
    for span in ("train/forward", "train/backward", "train/optimizer"):
        assert span in table, f"{span} missing from summary:\n{table}"
    assert "self ms" in table

    ServingMetrics()       # registers the serving histograms process-wide
    text = telemetry.metrics_text()
    assert "# TYPE train_step_time_ms histogram" in text
    assert re.search(r"^train_mfu [0-9.eE+-]+$", text, re.M)
    assert "# TYPE serving_ttft_seconds histogram" in text
    assert 'serving_ttft_seconds_bucket{le="+Inf"} 0' in text
    # step histogram saw all 3 optimizer steps
    m = re.search(r"^train_step_time_ms_count (\d+)$", text, re.M)
    assert m and int(m.group(1)) >= 3
    assert telemetry.registry.counter("train/steps").value - \
        steps_before == 3


def test_bench_trace_flag(tmp_path):
    """`bench.py --trace <path>` on CPU: one tiny traced step, dumped
    JSON loads, and the headline JSON line still prints."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    trace = str(tmp_path / "bench_trace.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py"), "--size", "tiny",
         "--seq", "64", "--batch", "2", "--steps", "1", "--trace", trace],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["unit"] == "tokens/s/chip"
    with open(trace) as fh:
        doc = json.load(fh)
    names = {e["name"] for e in doc["traceEvents"]}
    assert "train/step" in names        # fused path emits the envelope
    assert all({"name", "ph", "ts", "pid", "tid"} <= set(e)
               for e in doc["traceEvents"])
