"""ISSUE 19: goodput/badput wall-clock attribution ledger.

Acceptance flows covered here:
- the ledger conserves wall clock: categories sum to uptime within
  epsilon, whatever the span soup looks like (property test);
- a chaos drill's injection→recovery interval shows up as
  fault_recovery seconds matching the resilience ledger;
- profile-on-regression starts exactly one capture per dip and honors
  the cooldown (stubbed profiler);
- dstpu-top --once exits 3 when fleet goodput sits below --min-goodput;
- dstpu-doctor renders the LOW GOODPUT verdict naming the dominant
  badput;
- the dstpu-goodput CLI selftest (the tier-1 smoke) passes.
"""

import time

import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.telemetry import doctor, fleet, goodput
from deepspeed_tpu.telemetry.goodput import (CATEGORIES, CaptureController,
                                             GoodputLedger, attribute)
from deepspeed_tpu.telemetry.timeseries import MetricHistory
from deepspeed_tpu.telemetry.tracer import Tracer


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def _tracer():
    tr = Tracer(buffer_events=4096)
    tr.configure(enabled=True)
    return tr


@pytest.fixture()
def clean_recovery_ledger():
    faults.clear_recovery_intervals()
    faults.fault_injector.disarm()
    yield
    faults.clear_recovery_intervals()
    faults.fault_injector.disarm()


# ------------------------------------------------------------ conservation


def test_attribution_conserves_wall_clock_property():
    """Whatever overlapping span soup the ring holds — nested compiles,
    checkpoint saves inside steps, serving pumps, recovery intervals
    crossing window edges — the categories sum to the window width."""
    tr = _tracer()
    t0 = tr._t0
    # deterministic pseudo-random soup (no random module: reproducible)
    spans = []
    for i in range(40):
        s = t0 + (i * 7 % 23) * 0.37
        d = 0.1 + (i * 13 % 11) * 0.21
        name = ("train/step", "compile/fn", "checkpoint/save",
                "serving/engine_step")[i % 4]
        kw = {"batch": i % 3} if name == "serving/engine_step" else {}
        spans.append((name, s, s + d, kw))
    for name, s, e, kw in spans:
        tr.complete(name, s, e, **kw)
    rec = [(t0 + 1.0, t0 + 1.5, "preempt"), (t0 + 8.0, t0 + 12.0, "hang")]
    for w0, w1 in ((t0, t0 + 30.0), (t0 + 3.3, t0 + 7.7),
                   (t0 + 11.0, t0 + 11.0001), (t0 - 5.0, t0 + 50.0)):
        res = attribute(tr.events(), w0, w1, base=tr._t0,
                        recovery_intervals=rec)
        assert sum(res["seconds"].values()) == pytest.approx(
            w1 - w0, abs=1e-6)
        assert set(res["seconds"]) == set(CATEGORIES)
        assert all(v >= 0 for v in res["seconds"].values())


def test_attribution_priority_and_gap_classes():
    """A compile spanning a train step is badput (named cause beats
    generic productivity); pre-first-work time is init; inter-step gaps
    are input_stall on a training host, idle on a serving host."""
    tr = _tracer()
    t0 = tr._t0
    tr.complete("compile/train_step", t0 + 1.0, t0 + 3.0)
    tr.complete("train/step", t0 + 2.0, t0 + 4.0, step=0)   # 1s overlap
    tr.complete("train/step", t0 + 5.0, t0 + 6.0, step=1)
    res = attribute(tr.events(), t0, t0 + 7.0, base=tr._t0)
    sec = res["seconds"]
    assert sec["compile"] == pytest.approx(2.0)
    assert sec["goodput"] == pytest.approx(2.0)     # steps minus overlap
    assert sec["init"] == pytest.approx(1.0)
    assert sec["input_stall"] == pytest.approx(2.0)  # 4→5 gap + 6→7 tail
    assert res["train_steps"] == 2

    # serving host: empty pumps and gaps both land in idle
    tr2 = _tracer()
    s0 = tr2._t0
    tr2.complete("serving/engine_step", s0 + 1.0, s0 + 2.0, batch=4)
    tr2.complete("serving/engine_step", s0 + 2.0, s0 + 3.0, batch=0)
    res2 = attribute(tr2.events(), s0, s0 + 5.0, base=tr2._t0)
    assert res2["seconds"]["goodput"] == pytest.approx(1.0)
    assert res2["seconds"]["idle"] == pytest.approx(3.0)    # pump + gap
    assert res2["seconds"]["init"] == pytest.approx(1.0)


def test_ledger_batch_fetch_is_input_stall_inside_the_step():
    """``train/step`` opens before the batch is fetched: its
    ``train/batch`` child is an input stall, not goodput; the 3-call
    API's forward / backward / optimizer spans are goodput without an
    envelope -- and the ledger still conserves."""
    tr = _tracer()
    t0 = tr._t0
    for i in range(4):
        tr.complete("train/step", t0 + i, t0 + i + 1.0, step=i)
        tr.complete("train/batch", t0 + i, t0 + i + 0.1)
    tr.complete("train/forward", t0 + 4.0, t0 + 4.5, step=4)
    tr.complete("train/optimizer", t0 + 4.6, t0 + 5.0, step=4)
    led = GoodputLedger(tracer=tr)
    led.configure(enabled=True)
    s = led.update(t0 + 5.0)
    assert "comm_exposed" not in s["badput"]
    assert s["badput"]["input_stall"] == pytest.approx(0.5, abs=1e-6)
    assert s["goodput_s"] == pytest.approx(4.5, abs=1e-6)
    total = s["goodput_s"] + sum(s["badput"].values())
    assert total == pytest.approx(s["uptime_s"], abs=1e-6)


# ---------------------------------------------------------- chaos drill


def test_chaos_drill_attributes_fault_recovery(clean_recovery_ledger):
    """An injected fault closed by record_recovery becomes
    fault_recovery wall time matching the resilience ledger's interval,
    tagged with the fault kind."""
    tr = _tracer()
    faults.fault_injector.arm("step:0:io_error", _env=False)
    with pytest.raises(faults.InjectedIOError):
        faults.fault_injector.fire("checkpoint", step=0)
    time.sleep(0.05)
    faults.record_recovery("io_error")
    intervals = faults.recovery_intervals()
    assert len(intervals) == 1
    start, end, kind = intervals[0]
    assert kind == "io_error" and end > start

    led = GoodputLedger(tracer=tr)
    led.configure(enabled=True)
    s = led.update(time.perf_counter())
    assert s["badput"]["fault_recovery"] == pytest.approx(
        end - start, abs=1e-3)
    assert s["recovery_kinds"] == {"io_error": 1}
    total = s["goodput_s"] + sum(s["badput"].values())
    # every term of the summary is rounded to a microsecond on its own, so
    # on real clock readings the sum may sit a few of them off the total
    assert total == pytest.approx(s["uptime_s"], abs=5e-6)
    # dominant badput names the drill (init is the only competitor and
    # the tracer was born right before the injection)
    assert s["dominant_badput"] in ("fault_recovery", "init")


# -------------------------------------------------- profile-on-regression


def test_capture_one_shot_and_cooldown(tmp_path):
    """A goodput dip starts exactly ONE stubbed capture; while active no
    second trigger fires; after stop, the cooldown gates re-arming until
    it elapses."""
    calls = []
    cc = CaptureController(start_fn=lambda p: calls.append(("start", p)),
                           stop_fn=lambda: calls.append(("stop",)))
    cc.configure(threshold=0.5, cooldown_s=100.0, duration_ms=2000.0,
                 dir=str(tmp_path))
    assert cc.poll(0.0, 0.9) is None                # healthy: no capture
    p1 = cc.poll(10.0, 0.2)                         # dip: capture starts
    assert p1 is not None and calls == [("start", p1)]
    assert cc.poll(11.0, 0.1) is None               # active: one-shot
    assert cc.poll(13.0, 0.1) is None               # stops (2s elapsed)...
    assert ("stop",) in calls
    assert cc.poll(50.0, 0.1) is None               # ...cooldown holds
    p2 = cc.poll(111.0, 0.1)                        # cooldown elapsed
    assert p2 is not None and p2 != p1
    assert cc.captures == 2 and cc.paths == [p1, p2]


def test_capture_disabled_threshold_zero_ignores_breach(tmp_path):
    """threshold=0 disarms capture entirely — even a latched SLO breach
    must not start the profiler."""
    calls = []
    cc = CaptureController(start_fn=lambda p: calls.append(p),
                           stop_fn=lambda: None)
    cc.configure(threshold=0.0, dir=str(tmp_path))
    assert cc.poll(1.0, 0.0, breach=True) is None
    assert not calls
    # armed, the breach latch alone fires it even with healthy goodput
    cc.configure(threshold=0.5)
    assert cc.poll(2.0, 0.9, breach=True) is not None


def test_ledger_dip_triggers_exactly_one_capture(tmp_path):
    """End-to-end acceptance: a forced goodput dip through the ledger's
    own update path starts exactly one capture within the cooldown."""
    tr = _tracer()
    t0 = tr._t0
    led = GoodputLedger(tracer=tr)
    led.configure(enabled=True, window_s=10.0, capture_threshold=0.5,
                  capture_cooldown_s=3600.0, capture_duration_ms=100.0,
                  capture_dir=str(tmp_path))
    calls = []
    led.capture._start_fn = lambda p: calls.append(p)
    led.capture._stop_fn = lambda: None
    tr.complete("train/step", t0, t0 + 1.0, step=0)
    led.update(t0 + 1.0)                     # 100% goodput: no capture
    assert not calls
    for i in range(20):                      # pure stall: windowed dip
        led.update(t0 + 2.0 + i)
    assert len(calls) == 1                   # one-shot within cooldown
    assert led.summary()["captures"] == 1


# --------------------------------------------------------------- dstpu-top


def test_dstpu_top_once_min_goodput_exit3(tmp_path, capsys):
    """--once --min-goodput exits 3 below the floor (with the badput
    sub-line rendered), 0 at/above it; degraded still exits 2."""
    clock = FakeClock()
    p = str(tmp_path / "tpu-vm-0.jsonl")
    hist = MetricHistory(path=p, host="tpu-vm-0", clock=clock)
    for i in range(2):
        clock.advance(2.0)
        hist.append(i, {"train/steps": float(i),
                        "goodput/fraction": 0.3,
                        "goodput/uptime_s": 100.0,
                        "goodput/goodput_s": 30.0,
                        "goodput/input_stall_s": 55.0,
                        "goodput/compile_s": 15.0})
    assert fleet.main(["--once", "--history", p,
                       "--min-goodput", "0.5"]) == 3
    out = capsys.readouterr().out
    assert "GOOD%" in out and "30" in out
    assert "badput: dominant input_stall (55.0s)" in out
    # floor below the measured fraction: healthy exit
    assert fleet.main(["--once", "--history", p,
                       "--min-goodput", "0.25"]) == 0
    capsys.readouterr()
    # degraded outranks the goodput floor
    clock.advance(2.0)
    hist.append(2, {"train/steps": 2.0, "goodput/fraction": 0.3,
                    "slo/breached": 1.0})
    assert fleet.main(["--once", "--history", p,
                       "--min-goodput", "0.5"]) == 2


# ------------------------------------------------------------ dstpu-doctor


def test_doctor_low_goodput_verdict():
    """A black box carrying a low-goodput ledger summary earns the LOW
    GOODPUT verdict naming the dominant badput with its seconds."""
    dump = {"meta": {"hostname": "tpu-vm-7"}, "reason": "periodic",
            "steps": [{"step": i, "dur_ms": 100.0} for i in range(3)],
            "events": [],
            "goodput": {"uptime_s": 600.0, "goodput_s": 120.0,
                        "fraction": 0.2,
                        "badput": {"input_stall": 400.0, "compile": 80.0},
                        "dominant_badput": "input_stall",
                        "dominant_badput_s": 400.0,
                        "recovery_kinds": {}, "captures": 1,
                        "capture_paths": ["/tmp/cap_0"]}}
    report = doctor.analyze([dump])
    assert report["verdict"].startswith("LOW GOODPUT on tpu-vm-7")
    assert "20%" in report["verdict"]
    assert "input_stall" in report["verdict"]
    assert "400.0s" in report["verdict"]
    assert report["goodput"]["low"][0]["host"] == "tpu-vm-7"
    text = doctor.render(report)
    assert "goodput ledger" in text
    assert "input_stall" in text

    # a healthy ledger stays off the verdict ladder
    dump["goodput"] = {"uptime_s": 600.0, "goodput_s": 540.0,
                       "fraction": 0.9, "badput": {"compile": 60.0},
                       "dominant_badput": "compile",
                       "dominant_badput_s": 60.0, "recovery_kinds": {},
                       "captures": 0, "capture_paths": []}
    report2 = doctor.analyze([dump])
    assert not report2["verdict"].startswith("LOW GOODPUT")


def test_doctor_goodput_from_metrics_text():
    """Without a ledger summary section, the doctor reconstructs
    goodput state from the black box's Prometheus exposition."""
    mt = ("goodput_fraction 0.25\n"
          "goodput_ckpt_s 42.0\n"
          "goodput_idle_s 12.0\n")
    dump = {"meta": {"hostname": "tpu-vm-2"}, "reason": "periodic",
            "steps": [], "events": [], "metrics_text": mt}
    report = doctor.analyze([dump])
    h = report["hosts"][0]
    assert h["goodput"]["fraction"] == pytest.approx(0.25)
    assert h["goodput"]["dominant_badput"] == "ckpt"
    assert "LOW GOODPUT" in report["verdict"]


# ------------------------------------------------------- CLI + comm timing


def test_dstpu_goodput_cli_selftest(capsys):
    """The tier-1 smoke: the synthetic-trace conservation selftest."""
    assert goodput.main(["--selftest"]) == 0
    out = capsys.readouterr().out
    assert "conservation OK" in out


def test_comm_verbose_synchronous_path_records_measured_time():
    """In verbose mode the eager (non-traced) collective path records a
    MEASURED wall time into the CommsLogger and a comm/* span."""
    import jax.numpy as jnp
    from deepspeed_tpu.comm.comm import _timed
    from deepspeed_tpu.comm.comms_logger import comms_logger
    x = jnp.ones((8,), jnp.float32)
    size = x.size * x.dtype.itemsize
    old = (comms_logger.enabled, comms_logger.verbose,
           comms_logger.prof_all)
    comms_logger.enabled = comms_logger.verbose = True
    comms_logger.prof_all = True
    comms_logger.comms_dict.pop("all_reduce", None)
    try:
        out = _timed("all_reduce", x, "data",
                     lambda: (time.sleep(0.01), x)[1])
        assert out is x
        count, total = comms_logger.comms_dict["all_reduce"][size]
        assert count == 1 and total > 0.0
    finally:
        (comms_logger.enabled, comms_logger.verbose,
         comms_logger.prof_all) = old
        comms_logger.comms_dict.pop("all_reduce", None)


def test_goodput_config_parses_and_arms_ledger():
    from deepspeed_tpu.config.config import DeepSpeedTPUConfig
    cfg = DeepSpeedTPUConfig.from_any({
        "train_batch_size": 8,
        "telemetry": {"goodput": {"enabled": True, "window_s": 30,
                                  "capture_threshold": 0.4,
                                  "capture_cooldown_s": 120,
                                  "capture_duration_ms": 500}}})
    assert cfg.telemetry.goodput.enabled
    assert cfg.telemetry.goodput.window_s == 30.0
    assert cfg.telemetry.goodput.capture_threshold == 0.4
    with pytest.raises(Exception):
        DeepSpeedTPUConfig.from_any(
            {"telemetry": {"goodput": {"capture_threshold": 1.5}}})
