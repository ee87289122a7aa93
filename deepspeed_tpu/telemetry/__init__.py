"""deepspeed_tpu.telemetry — unified tracing, metrics, and MFU/memory
accounting across the engine, comm layer, and serving frontend.

The reference threads observability through five disconnected pieces
(MonitorMaster events, SynchronizedWallClockTimer, comms logging, the
flops profiler, serving histograms); this package gives them one spine:

- :mod:`~deepspeed_tpu.telemetry.tracer` — nestable spans → Chrome/
  Perfetto trace-event JSON (+ optional jax.profiler annotations);
- :mod:`~deepspeed_tpu.telemetry.registry` — process-wide Counters/
  Gauges/Histograms with Prometheus text exposition and a MonitorMaster
  bridge;
- :mod:`~deepspeed_tpu.telemetry.sampler` — device-memory watermarks and
  MFU against the per-platform peak-FLOPs table;
- :mod:`~deepspeed_tpu.telemetry.summarize` — the trace self-time CLI
  (``python -m deepspeed_tpu.telemetry.summarize`` / ``bin/dstpu-trace``).

The diagnostics layer on top of that spine (PR 4) answers "why did the
run die, hang, or slow down":

- :mod:`~deepspeed_tpu.telemetry.flight_recorder` — always-on bounded
  ring of per-step records, serialized to a JSON black box on crash /
  preemption / hang / demand;
- :mod:`~deepspeed_tpu.telemetry.watchdog` — per-step deadline monitor
  that dumps all-thread stacks + the black box on a hung step;
- :mod:`~deepspeed_tpu.telemetry.compile_monitor` — XLA compile
  counts/durations and the recompilation-storm detector;
- :mod:`~deepspeed_tpu.telemetry.anomaly` — non-finite / loss-spike /
  grad-outlier / step-time-regression flags on the step stream;
- :mod:`~deepspeed_tpu.telemetry.doctor` — the ``dstpu-doctor`` CLI
  that turns per-host black boxes into a health report;
- :mod:`~deepspeed_tpu.telemetry.health` — in-graph model-health taps
  (per-layer training dynamics, MoE expert load) published as
  ``health/*`` gauges, with the per-layer anomaly localizer and the
  ``dstpu-health`` renderer.

The compile-time side (PR 5) answers "where was this step ALWAYS going
to spend its FLOPs, bytes, and HBM" before it runs:

- :mod:`~deepspeed_tpu.telemetry.explain` — lowers the jitted step /
  serving programs, reads back XLA cost+memory analysis, and builds the
  roofline + HBM-budget report (``bin/dstpu-explain``, ``roofline/*``
  gauges);
- :mod:`~deepspeed_tpu.telemetry.endpoint` — the live scrape server
  (``GET /metrics`` + ``GET /healthz``), ``telemetry.http_port`` config.

The time axis over all of it (PR 9):

- :mod:`~deepspeed_tpu.telemetry.timeseries` — durable per-host metric
  history (JSONL ring, size-bounded rotation + downsampling) recording
  every registry flush, with a range/rate/windowed query API;
- :mod:`~deepspeed_tpu.telemetry.slo` — config-declared objectives
  (``slo.objectives``) evaluated continuously with fast/slow
  multi-window burn-rate alerting (``slo/*`` gauges, /healthz 503,
  flight-recorder events, doctor verdicts);
- :mod:`~deepspeed_tpu.telemetry.fleet` — the ``dstpu-top`` live
  terminal fleet view over N /metrics + /healthz endpoints (or history
  files offline);
- :mod:`~deepspeed_tpu.telemetry.compare` — the ``dstpu_report
  --compare`` run-regression gate over BENCH JSONL / history files.

See docs/observability.md for the config reference, the trace-capture
workflow, the metric-name catalog, and post-mortem debugging.
"""

from deepspeed_tpu.telemetry.anomaly import (AnomalyDetector,  # noqa: F401
                                             anomaly_detector,
                                             first_flagged_path)
from deepspeed_tpu.telemetry.compile_monitor import (  # noqa: F401
    CompileMonitor, compile_monitor, setup_part)
from deepspeed_tpu.telemetry.endpoint import MetricsServer  # noqa: F401
from deepspeed_tpu.telemetry.explain import (ExplainReport,  # noqa: F401
                                             FunctionCost, Roofline,
                                             analyze_fn, explain_engine,
                                             explain_serving,
                                             normalize_cost_analysis,
                                             publish_gauges, render,
                                             resolve_peaks)
from deepspeed_tpu.telemetry.flight_recorder import (  # noqa: F401
    FlightRecorder, flight_recorder, load_dump)
from deepspeed_tpu.telemetry.goodput import (GoodputLedger,  # noqa: F401
                                             goodput_ledger)
from deepspeed_tpu.telemetry.health import HealthMonitor  # noqa: F401
from deepspeed_tpu.telemetry.registry import (Counter, Gauge,  # noqa: F401
                                              Histogram, MetricsRegistry,
                                              registry)
from deepspeed_tpu.telemetry.reqtrace import (ReqTrace,  # noqa: F401
                                              TraceContext, critical_path,
                                              reqtrace)
from deepspeed_tpu.telemetry.slo import (Objective, SLOEngine,  # noqa: F401
                                         engine_from_config,
                                         evaluate_history)
from deepspeed_tpu.telemetry.timeseries import (MetricHistory,  # noqa: F401
                                                load_records, merge_records,
                                                resolve_metric, windowed)
from deepspeed_tpu.telemetry.sampler import (MemorySampler,  # noqa: F401
                                             device_memory_stats,
                                             host_rss_bytes, mfu,
                                             peak_flops)
from deepspeed_tpu.telemetry.tracer import Tracer, tracer  # noqa: F401
from deepspeed_tpu.telemetry.watchdog import Watchdog  # noqa: F401

__all__ = ["tracer", "Tracer", "registry", "MetricsRegistry", "Counter",
           "Gauge", "Histogram", "MemorySampler", "peak_flops", "mfu",
           "device_memory_stats", "host_rss_bytes", "configure",
           "metrics_text", "flight_recorder", "FlightRecorder",
           "load_dump", "Watchdog", "compile_monitor", "CompileMonitor",
           "setup_part",
           "anomaly_detector", "AnomalyDetector", "first_flagged_path",
           "ExplainReport", "FunctionCost", "Roofline", "analyze_fn",
           "explain_engine", "explain_serving", "normalize_cost_analysis",
           "publish_gauges", "render", "resolve_peaks", "MetricsServer",
           "MetricHistory", "load_records", "merge_records",
           "resolve_metric", "windowed", "Objective", "SLOEngine",
           "engine_from_config", "evaluate_history", "reqtrace",
           "ReqTrace", "TraceContext", "critical_path",
           "goodput_ledger", "GoodputLedger", "HealthMonitor"]


def configure(telemetry_config) -> None:
    """Apply a :class:`~deepspeed_tpu.config.config.TelemetryConfig` to
    the process-wide tracer. Enable-only: an engine whose config leaves
    telemetry off must not silence a tracer something else (bench
    ``--trace``, a test) already turned on. The ``reqtrace`` and
    ``goodput`` sub-blocks additionally arm their own layers (each has
    its own ``enabled`` gate); enabling goodput also enables the span
    tracer — the ledger attributes off the tracer ring."""
    if telemetry_config is None:
        return
    rt = getattr(telemetry_config, "reqtrace", None)
    if rt is not None and getattr(rt, "enabled", False):
        reqtrace.configure(
            enabled=True,
            head_sample=getattr(rt, "head_sample", None),
            retain_slow_ms=getattr(rt, "retain_slow_ms", None),
            buffer_traces=getattr(rt, "buffer_traces", None))
    gp = getattr(telemetry_config, "goodput", None)
    if gp is not None and getattr(gp, "enabled", False):
        tracer.configure(enabled=True)
        goodput_ledger.configure(
            enabled=True,
            window_s=getattr(gp, "window_s", None),
            capture_threshold=getattr(gp, "capture_threshold", None),
            capture_cooldown_s=getattr(gp, "capture_cooldown_s", None),
            capture_duration_ms=getattr(gp, "capture_duration_ms", None),
            capture_dir=getattr(gp, "capture_dir", None))
    if not getattr(telemetry_config, "enabled", False):
        return
    tracer.configure(
        enabled=True,
        buffer_events=getattr(telemetry_config, "trace_buffer_events", None),
        jax_annotations=getattr(telemetry_config, "jax_annotations", None))


def metrics_text() -> str:
    """Prometheus text exposition of the process-wide registry — the
    payload for a ``/metrics`` endpoint."""
    return registry.prometheus_text()
