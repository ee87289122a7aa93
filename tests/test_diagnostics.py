"""ISSUE 4: flight recorder, watchdog, compile monitor, anomaly
detection, dstpu-doctor, and the metric-name lint.

Acceptance flows covered here:
- a CPU train run killed by an injected exception leaves a black box
  that dstpu-doctor turns into a report naming the last completed step,
  the anomaly, and per-step timing (subprocess, no TPU);
- a hung step produces thread stacks + a parsable black box within the
  watchdog deadline (subprocess, action="kill" → exit 124);
- a shape-change recompile is counted and the storm warning fires at
  the threshold.
"""

import json
import math
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry.anomaly import (AnomalyDetector,
                                             first_flagged_path)
from deepspeed_tpu.telemetry.compile_monitor import CompileMonitor
from deepspeed_tpu.telemetry.flight_recorder import (FlightRecorder,
                                                     load_dump)
from deepspeed_tpu.telemetry.watchdog import (WATCHDOG_EXIT_CODE,
                                              Watchdog)
from deepspeed_tpu.telemetry import doctor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": ROOT + os.pathsep + os.environ.get(
               "PYTHONPATH", "")}


@pytest.fixture()
def clean_diagnostics():
    """The flight recorder / anomaly detector are process-wide; leave
    them as found so other test files see a quiet baseline."""
    telemetry.flight_recorder.clear()
    telemetry.anomaly_detector.clear()
    yield
    telemetry.flight_recorder.clear()
    telemetry.anomaly_detector.clear()


# ---------------------------------------------------------------- recorder

def test_flight_recorder_ring_and_dump(tmp_path, clean_diagnostics):
    fr = FlightRecorder(max_steps=4)
    for i in range(10):
        fr.record_step(i, dur_s=0.01 * (i + 1), loss=float(i))
    assert fr.last_step() == 9
    fr.record_event("marker", note="hello")
    path = fr.dump(str(tmp_path / "bb.json"), reason="on_demand")
    doc = load_dump(path)
    assert doc["reason"] == "on_demand"
    # bounded ring: only the last 4 steps survive
    assert [s["step"] for s in doc["steps"]] == [6, 7, 8, 9]
    assert doc["steps"][-1]["dur_ms"] == pytest.approx(100.0)
    assert doc["events"][0]["kind"] == "marker"
    assert doc["meta"]["pid"] == os.getpid()


def test_flight_recorder_lazy_device_scalars(tmp_path, clean_diagnostics):
    """Device scalars recorded as-is resolve to floats only at dump."""
    fr = FlightRecorder()
    fr.record_step(1, loss=jnp.float32(2.5), grad_norm=jnp.float32(0.1))
    doc = fr.snapshot()
    assert doc["steps"][0]["loss"] == pytest.approx(2.5)
    # non-finite scalars become a repr string, not invalid JSON
    fr.record_step(2, loss=jnp.float32(float("nan")))
    dumped = json.loads(json.dumps(fr.snapshot()))
    assert "nan" in str(dumped["steps"][1]["loss"])


def test_load_dump_rejects_non_dump(tmp_path):
    p = tmp_path / "not_a_dump.json"
    p.write_text('{"phase": "armed"}')
    with pytest.raises(ValueError, match="not a flight-recorder dump"):
        load_dump(str(p))


# ---------------------------------------------------------------- watchdog

def test_watchdog_warn_fires_and_dumps(tmp_path, clean_diagnostics):
    fired = []
    wd = Watchdog(timeout_s=0.2, action="warn",
                  dump_dir=str(tmp_path),
                  heartbeat_file=str(tmp_path / "hb.json"),
                  on_fire=lambda label, step, paths: fired.append(
                      (label, step, paths)))
    try:
        wd.arm("fake_step", step=7)
        deadline = time.time() + 10
        while not fired and time.time() < deadline:
            time.sleep(0.05)
        assert fired, "watchdog did not fire within 10s"
        label, step, paths = fired[0]
        assert (label, step) == ("fake_step", 7)
        stacks = open(paths["stacks"]).read()
        assert "exceeded 0.2s" in stacks
        assert "Current thread" in stacks        # faulthandler dump
        doc = load_dump(paths["blackbox"])
        assert any(e["kind"] == "watchdog" and e["label"] == "fake_step"
                   for e in doc["events"])
        assert os.path.exists(paths["metrics"])
        hb = json.load(open(tmp_path / "hb.json"))
        assert hb["phase"] == "stalled" and hb["step"] == 7
        # warn action: process alive; disarm+rearm works, one fire/miss
        assert wd.fired == 1
        wd.disarm()
        hb = json.load(open(tmp_path / "hb.json"))
        assert hb["phase"] == "idle"
    finally:
        wd.stop()


def test_watchdog_guard_no_false_positive(tmp_path):
    wd = Watchdog(timeout_s=5.0, action="warn", dump_dir=str(tmp_path))
    try:
        with wd.guard("quick_step", step=1):
            time.sleep(0.01)
        time.sleep(0.1)
        assert wd.fired == 0
    finally:
        wd.stop()


def test_watchdog_rejects_bad_action():
    with pytest.raises(ValueError, match="warn.*kill"):
        Watchdog(action="explode")


def test_watchdog_hang_subprocess_kills_within_deadline(tmp_path):
    """Acceptance: a hung step (sleep inside a fake step) produces
    thread stacks + a parsable black box and exits 124 within the
    configured deadline."""
    script = tmp_path / "hang.py"
    script.write_text(textwrap.dedent(f"""
        import time
        from deepspeed_tpu.telemetry.flight_recorder import flight_recorder
        from deepspeed_tpu.telemetry.watchdog import Watchdog
        flight_recorder.record_step(41, dur_s=0.1, loss=1.0)
        flight_recorder.record_step(42, dur_s=0.1, loss=2.0)
        wd = Watchdog(timeout_s=1.0, action="kill",
                      dump_dir={str(tmp_path)!r})
        wd.arm("train_batch", step=43)
        time.sleep(300)          # the hung step
    """))
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, timeout=120,
                          env=CPU_ENV)
    elapsed = time.monotonic() - t0
    assert proc.returncode == WATCHDOG_EXIT_CODE, \
        f"rc={proc.returncode} stderr={proc.stderr[-2000:]}"
    assert elapsed < 60, f"dump took {elapsed:.0f}s for a 1s deadline"
    stacks = [p for p in os.listdir(tmp_path)
              if p.startswith("watchdog_stacks")]
    assert stacks, os.listdir(tmp_path)
    text = open(tmp_path / stacks[0]).read()
    # header names the wedged step, the stack names the hung frame
    assert "step 43" in text and "hang.py" in text
    boxes = [p for p in os.listdir(tmp_path)
             if p.startswith("blackbox_watchdog")]
    assert boxes, os.listdir(tmp_path)
    doc = load_dump(str(tmp_path / boxes[0]))
    assert doc["steps"][-1]["step"] == 42        # last COMPLETED step
    assert any(e["kind"] == "watchdog" and e["step"] == 43
               for e in doc["events"])


# ---------------------------------------------------------- compile monitor

def test_compile_monitor_counts_shape_change_recompile():
    """A retrace of a NAMED, registered function is a build in its record:
    jax's own trace / lowering / backend events carry its name."""
    cm = CompileMonitor(storm_threshold=100)
    cm.install()

    def unit_f(x):
        return x * 2 + 1
    jf = jax.jit(unit_f)
    cm.register_program("unit_f", jf, (jnp.zeros((4,)),))
    try:
        jf(jnp.zeros((4,)))
        assert cm.summary()["programs"]["unit_f"]["builds"] == 1
        jf(jnp.ones((4,)))                 # cache hit: no event at all
        assert cm.summary()["programs"]["unit_f"]["builds"] == 1
        jf(jnp.zeros((8,)))                # shape change → retrace
        record = cm.summary()["programs"]["unit_f"]
        assert record["builds"] == 2 == len(record["recent"])
    finally:
        cm.uninstall()


def test_compile_monitor_jax_monitoring_events():
    """install() mirrors real XLA compiles into compile/count and
    compile/time_ms via jax.monitoring duration events."""
    before = telemetry.registry.counter("compile/count").value
    telemetry.compile_monitor.install()
    try:
        # a fresh jit of a never-seen shape forces a real compile
        jax.jit(lambda x: jnp.tanh(x) * 3)(jnp.zeros((3, 5, 7)))
        after = telemetry.registry.counter("compile/count").value
        assert after > before
        ev = telemetry.compile_monitor.summary()["events"]
        assert any("compile" in k or "jaxpr" in k for k in ev)
    finally:
        telemetry.compile_monitor.uninstall()


def test_compile_monitor_storm_warning_at_threshold(clean_diagnostics):
    import logging
    from deepspeed_tpu.utils.logging import logger

    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    logger.addHandler(handler)
    try:
        cm = CompileMonitor(storm_threshold=3)
        for i in range(3):
            cm.count_trace("serving/step_fn", detail={"nb": i})
        assert not any("RECOMPILATION STORM" in m for m in records)
        cm.count_trace("serving/step_fn", detail={"nb": 3})   # 4th > 3
        storm_logs = [m for m in records if "RECOMPILATION STORM" in m]
        assert len(storm_logs) == 1
        assert "serving/step_fn" in storm_logs[0]
        assert "'nb': 3" in storm_logs[0]      # trigger details shown
        assert cm.summary()["storms"] == ["serving/step_fn"]
        # warned once: further retraces don't re-log
        records.clear()
        cm.count_trace("serving/step_fn")
        assert not any("RECOMPILATION STORM" in m for m in records)
    finally:
        logger.removeHandler(handler)
    # the storm landed in the flight recorder for the doctor
    assert any(e["kind"] == "recompile_storm"
               for e in telemetry.flight_recorder.snapshot()["events"])


# ------------------------------------------------------------------ anomaly

def test_anomaly_nonfinite_and_spike(clean_diagnostics):
    det = AnomalyDetector()
    out = det.observe(1, loss=float("nan"))
    assert [a["kind"] for a in out] == ["nonfinite_loss"]
    det.clear()
    for i in range(10):
        det.observe(i, loss=1.0 + 0.01 * i)
    out = det.observe(11, loss=50.0)
    assert [a["kind"] for a in out] == ["loss_spike"]
    # baseline updates after the check: next normal loss is clean
    assert det.observe(12, loss=1.1) == []


def test_anomaly_grad_zscore_and_step_regression(clean_diagnostics):
    det = AnomalyDetector()
    for i in range(20):
        det.observe(i, grad_norm=1.0 + 0.05 * math.sin(i),
                    step_time_ms=100.0 + (i % 3))
    out = det.observe(21, grad_norm=500.0)
    assert "grad_norm_outlier" in [a["kind"] for a in out]
    out = det.observe(22, step_time_ms=1000.0)
    assert "step_time_regression" in [a["kind"] for a in out]
    s = det.summary()
    assert s["total"] == 2 and s["by_kind"]["grad_norm_outlier"] == 1


def test_first_flagged_path_names_leaf():
    flags = {"a": {"w": np.bool_(False), "b": np.bool_(False)},
             "z": {"wi": np.bool_(True)}}
    path = first_flagged_path(flags)
    assert "z" in path and "wi" in path
    assert first_flagged_path({"a": np.bool_(False)}) is None


def test_scoped_nan_check_names_param_leaf(devices, clean_diagnostics):
    """check_nan_inf="scoped": a poisoned param leaf is reported with
    its pytree path after the next step, with jax_debug_nans LEFT OFF."""
    from deepspeed_tpu.models.gpt import gpt2_config
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import initialize

    build_mesh(data=8)
    model = gpt2_config("tiny", max_seq_len=32, vocab_size=128)
    engine, *_ = initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "check_nan_inf": "scoped"},
        rng=jax.random.PRNGKey(0))
    assert not jax.config.jax_debug_nans
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, size=(8, 32),
                                       dtype=np.int32)}
    engine.train_batch(iter([batch]))
    assert telemetry.anomaly_detector.anomalies == []
    engine.params["embed"]["pos"] = \
        engine.params["embed"]["pos"].at[0, 0].set(jnp.nan)
    engine.train_batch(iter([batch]))
    kinds = [a["kind"] for a in telemetry.anomaly_detector.anomalies]
    assert "nonfinite_params" in kinds
    detail = [a for a in telemetry.anomaly_detector.anomalies
              if a["kind"] == "nonfinite_params"][0]["detail"]
    assert "embed" in detail and "pos" in detail


# ------------------------------------------------------------- comms fixes

def test_convert_size_negative_and_zero():
    from deepspeed_tpu.comm.comms_logger import convert_size
    assert convert_size(0) == "0B"
    assert convert_size(-2048) == "-2.0 KB"
    assert convert_size(1536) == "1.5 KB"


def test_get_msg_size_unknown_op_warns_once():
    import importlib
    # the package re-exports ``comms_logger`` as a CommsLogger instance;
    # go through importlib to reach the module itself
    cl = importlib.import_module("deepspeed_tpu.comm.comms_logger")
    import logging
    from deepspeed_tpu.utils.logging import logger

    cl._unknown_msg_ops.clear()
    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    logger.addHandler(handler)
    try:
        assert cl.get_msg_size("frobnicate", 1000, 4) == 1000
        assert cl.get_msg_size("frobnicate", 2000, 4) == 2000
        warns = [m for m in records if "frobnicate" in m]
        assert len(warns) == 1
        # known ops keep their algorithmic factors, silently
        records.clear()
        assert cl.get_msg_size("all_reduce", 1000, 4) == 1500  # 2(w-1)/w
        assert cl.get_msg_size("all_gather", 1000, 4) == 750   # (w-1)/w
        assert records == []
    finally:
        logger.removeHandler(handler)
    with pytest.raises(ValueError, match="negative size_bytes"):
        cl.get_msg_size("all_reduce", -1, 4)


# ------------------------------------------------------------------- doctor

def _synthetic_dump(host, steps, dur_ms, exception=None, events=(),
                    comm=None, compile_summary=None, process_index=0):
    return {
        "schema": 1, "reason": "on_demand", "written_at": 2e9,
        "started_at": 2e9 - 100,
        "meta": {"hostname": host, "pid": 1000 + process_index,
                 "process_index": process_index, "process_count": 2},
        "steps": [{"step": s, "kind": "train", "ts": 2e9 - 100 + i,
                   "dur_ms": dur_ms(s)} for i, s in enumerate(steps)],
        "events": list(events),
        "exception": exception,
        "comm": comm or {},
        "compile": compile_summary or {"storms": [], "functions": {}},
    }


def test_doctor_straggler_golden(tmp_path):
    """Golden-output test: two synthetic host dumps with an injected
    straggler → the report names the slow host, shows per-step timing
    and algorithmic bandwidth, and the verdict says STRAGGLER."""
    fast = _synthetic_dump(
        "hostA", range(1, 21), lambda s: 100.0, process_index=0,
        comm={"all_reduce": {"1048576": [20, 2.0]}})
    slow = _synthetic_dump(
        "hostB", range(1, 21), lambda s: 250.0, process_index=1,
        comm={"all_reduce": {"1048576": [20, 0.0]}})
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(fast))
    pb.write_text(json.dumps(slow))

    report = doctor.analyze([json.load(open(pa)), json.load(open(pb))])
    assert report["straggler"]["host"] == "hostB[p1]"
    assert report["straggler"]["skew"] == pytest.approx(2.5)
    assert report["straggler"]["significant"]
    # hostB was the slowest on every shared step
    assert report["straggler"]["slowest_step_counts"] == {"hostB[p1]": 20}
    assert report["verdict"].startswith("STRAGGLER")

    text = doctor.render(report)
    assert "VERDICT: STRAGGLER: hostB[p1]" in text
    assert "2.50x" in text
    # per-host table: last step + per-step timing
    assert "hostA[p0]" in text and "100.0" in text and "250.0" in text
    # bandwidth: 20 calls of 1 MiB all_reduce at world=2 → factor
    # 2*(2-1)/2 = 1 → 20 MiB algorithmic over 2.0s = ~0.0105 GB/s
    row = [ln for ln in text.splitlines()
           if "all_reduce" in ln and "hostA" in ln][0]
    assert "20.0 MB" in row and "0.01" in row
    # zero recorded comm time on hostB → stepped-wall-time upper bound
    row_b = [ln for ln in text.splitlines()
             if "all_reduce" in ln and "hostB" in ln][0]
    assert "<=" in row_b

    # the CLI wrapper over the same dumps
    rc = doctor.main([str(pa), str(pb)])
    assert rc == 0


def test_doctor_crash_verdict_wins_over_straggler(tmp_path):
    crashed = _synthetic_dump(
        "hostA", [1, 2, 3], lambda s: 100.0,
        exception={"type": "RuntimeError", "message": "injected boom",
                   "traceback": "...", "ts": 2e9})
    slow = _synthetic_dump("hostB", [1, 2, 3], lambda s: 900.0,
                           process_index=1)
    report = doctor.analyze([crashed, slow])
    assert report["verdict"].startswith("CRASH on hostA")
    assert "after step 3" in report["verdict"]
    assert "injected boom" in report["verdict"]


def test_doctor_hang_heartbeat_and_storm_verdicts():
    clean = _synthetic_dump("hostA", [1, 2], lambda s: 100.0)
    hb = {"hostname": "hostB", "pid": 7, "step": 3, "label": "train_batch",
          "phase": "stalled", "ts": 2e9}
    report = doctor.analyze([clean], heartbeats=[hb])
    assert report["verdict"].startswith("HANG: host hostB stalled at "
                                        "step 3")
    stormy = _synthetic_dump(
        "hostA", [1, 2], lambda s: 100.0,
        compile_summary={"storms": ["serving/step_fn"],
                         "functions": {"serving/step_fn": 12}})
    assert doctor.analyze([stormy])["verdict"].startswith(
        "RECOMPILATION STORM")
    assert doctor.analyze([clean])["verdict"].startswith("HEALTHY")


def test_doctor_anomaly_timeline():
    dump = _synthetic_dump(
        "hostA", [1, 2, 3], lambda s: 100.0,
        events=[{"kind": "anomaly", "anomaly": "nonfinite_params",
                 "step": 3, "ts": 2e9,
                 "detail": "first non-finite leaf in params: "
                           "['embed']['pos']"}])
    report = doctor.analyze([dump])
    assert report["verdict"].startswith("NON-FINITE values from step 3")
    text = doctor.render(report)
    assert "anomaly timeline:" in text
    assert "['embed']['pos']" in text


def test_doctor_cli_bad_input(tmp_path, capsys):
    p = tmp_path / "garbage.json"
    p.write_text("{not json")
    assert doctor.main([str(p)]) == 2
    assert "cannot read" in capsys.readouterr().err


# --------------------------------------------- crash black-box acceptance

def test_crash_leaves_black_box_doctor_reads_it(tmp_path):
    """ISSUE 4 acceptance: CPU train run killed by an injected exception
    → flight-recorder JSON → dstpu-doctor report naming the last
    completed step, the anomaly, and per-step timing."""
    bb = str(tmp_path / "crash_blackbox.json")
    script = tmp_path / "crash_train.py"
    script.write_text(textwrap.dedent(f"""
        import numpy as np, jax
        from deepspeed_tpu.models.gpt import gpt2_config
        from deepspeed_tpu.parallel.mesh import build_mesh
        from deepspeed_tpu.runtime.engine import initialize

        build_mesh(data=8)
        model = gpt2_config("tiny", max_seq_len=32, vocab_size=128)
        engine, *_ = initialize(
            model=model,
            config={{"train_micro_batch_size_per_gpu": 1,
                     "optimizer": {{"type": "adam",
                                    "params": {{"lr": 1e-3}}}},
                     "telemetry": {{"blackbox_path": {bb!r}}}}},
            rng=jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {{"input_ids": rng.integers(0, 128, size=(8, 32),
                                            dtype=np.int32)}}
        for _ in range(2):
            engine.train_batch(iter([batch]))
        raise RuntimeError("injected failure after step 2")
    """))
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=420,
        env={**CPU_ENV,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert proc.returncode != 0
    assert "injected failure" in proc.stderr           # traceback intact
    assert "flight recorder black box written" in proc.stderr
    assert os.path.exists(bb), proc.stderr[-2000:]

    doc = load_dump(bb)
    assert doc["reason"] == "crash"
    assert doc["steps"][-1]["step"] == 2
    assert doc["exception"]["type"] == "RuntimeError"
    assert all(s["dur_ms"] > 0 for s in doc["steps"])
    assert isinstance(doc["steps"][0]["loss"], float)  # resolved at dump

    report = doctor.analyze([doc])
    assert report["verdict"].startswith("CRASH")
    assert "after step 2" in report["verdict"]
    assert "injected failure" in report["verdict"]
    text = doctor.render(report)
    assert "crashed (RuntimeError)" in text
    # per-step timing made it into the per-host table
    host_row = [ln for ln in text.splitlines() if "crashed" in ln][0]
    assert any(c.isdigit() for c in host_row)

    # the installed CLI ingests the same file
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bin", "dstpu-doctor"), bb],
        capture_output=True, text=True, timeout=120, env=CPU_ENV)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "VERDICT: CRASH" in proc.stdout


# ----------------------------------------------------- launcher heartbeat

def test_launch_agent_heartbeat_and_env(tmp_path):
    from deepspeed_tpu.launcher.agent import LaunchAgent
    hb = str(tmp_path / "hb.json")
    out = str(tmp_path / "env.json")
    agent = LaunchAgent(
        [sys.executable, "-c",
         "import json,os;json.dump("
         "os.environ.get('DSTPU_HEARTBEAT_FILE'),open(%r,'w'))" % out],
        heartbeat_file=hb)
    assert agent.run() == 0
    # the worker saw the exported heartbeat path...
    assert json.load(open(out)) == hb
    # ...and the agent stamped worker_exited after the child left
    doc = json.load(open(hb))
    assert doc["agent"] is True and doc["phase"] == "worker_exited"
    assert doc["rc"] == 0


# ------------------------------------------------------------- metric lint

def test_metric_name_lint_passes_on_tree():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "check_metric_names.py")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "call sites OK" in proc.stdout


def test_metric_name_lint_catches_violations(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_metric_names as lint
    finally:
        sys.path.pop(0)
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        from deepspeed_tpu.telemetry import registry
        registry.counter("noslash").inc()
        registry.gauge("Train/MFU").set(1.0)
        registry.counter("train/steps").inc()
        registry.gauge("train/steps").set(2)
        registry.counter(f"comm/{op}/calls").inc()
        registry.gauge(name_variable)
    """))
    sites = lint.collect_sites(str(tmp_path))
    errors = lint.check(sites)
    assert any("noslash" in e and "convention" in e for e in errors)
    assert any("Train/MFU" in e and "invalid segment" in e
               for e in errors)
    assert any("train/steps" in e and "TypeError" in e for e in errors)
    # the f-string site is valid ({} placeholder) and the variable-name
    # site is skipped, not flagged
    assert not any("comm/" in e for e in errors)
    assert len([s for s in sites if s[3] == "comm/{}/calls"]) == 1


# -------------------------------------------------------------------- config

def test_watchdog_config_parses():
    from deepspeed_tpu.config.config import DeepSpeedTPUConfig
    cfg = DeepSpeedTPUConfig.from_any({
        "train_batch_size": 8,
        "check_nan_inf": "scoped",
        "telemetry": {"flight_recorder_steps": 64,
                      "compile_storm_threshold": 4,
                      "watchdog": {"enabled": True, "step_timeout_s": 5,
                                   "action": "kill"}}})
    assert cfg.check_nan_inf == "scoped"
    assert cfg.telemetry.flight_recorder_steps == 64
    assert cfg.telemetry.watchdog.enabled
    assert cfg.telemetry.watchdog.action == "kill"
    with pytest.raises(Exception):
        DeepSpeedTPUConfig.from_any(
            {"telemetry": {"watchdog": {"action": "explode"}}})
    with pytest.raises(Exception):
        DeepSpeedTPUConfig.from_any({"check_nan_inf": "sometimes"})
