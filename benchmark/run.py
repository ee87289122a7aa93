#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips. Everything it prints goes to
stdout as one JSON object per line; the LAST line is the result the driver
reads (``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
traced, ``breakdown``). With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Driven by data: the cell names a configuration (``configs/<name>.json``)
and a traffic mix (``traffic/<name>.json``); the mix's ``kind`` picks the
runner (``runners/<kind>.py``); the configuration names the plain
reference of its architecture (``reference/<name>.py``); each per-layer
metric the cell reports has a reader of its own
(``layer_metrics/<metric>.py``). No cell's name occurs in code, and no
architecture's beyond the default of that one lookup.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. ``--rehearse`` is the only way to a CPU run: tiny widths,
control flow only, ``"platform": "cpu"``; no command of BENCHMARK.json
uses it."""

import time

T_START = time.monotonic()      # set-up is counted from here

import argparse                 # noqa: E402
import contextlib               # noqa: E402
import glob                     # noqa: E402
import importlib                # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402
import tempfile                 # noqa: E402

#: --dump-trace keeps this much of the traced window as a recording
SAMPLE_NS = 1.2e9

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny-width CPU rehearsal of the control flow")
    ap.add_argument("--dump-trace", default=None, metavar="PATH",
                    help="with --trace 1: also write the loaded trace's "
                         "summary (PATH) and a trimmed recording "
                         "(PATH.sample.json)")
    return ap.parse_args(argv)


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(metric: str):
    path = os.path.join(HERE, "layer_metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Context:
    """What a runner gets from the harness."""

    def __init__(self, args, cell, conf, mix, devices, ledger, reference):
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        self.cell, self.conf, self.mix = cell, conf, mix
        self.reference = reference
        self.devices = devices
        self.ledger = ledger
        self.t_start = T_START
        self.setup_s = None
        self.trace_dir = None
        self._tmp = None

    log = staticmethod(emit)

    def open_window(self) -> float:
        now = time.monotonic()
        self.setup_s = now - T_START
        emit({"phase": "window_open", "setup_s": self.setup_s})
        return now

    def annotate(self, name: str):
        """A host span in the profiler's own trace (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start_device_trace(self) -> None:
        import jax
        self._tmp = tempfile.TemporaryDirectory(prefix="benchmark-trace-")
        self.trace_dir = self._tmp.name
        jax.profiler.start_trace(self.trace_dir)

    def stop_device_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def load_trace(self):
        if self.trace_dir is None:
            return None
        from benchmark.trace import reduce
        found = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        data = reduce.load(found[-1]) if found else None
        self._tmp.cleanup()
        return data


class RunView:
    """What a per-layer metric's reader gets: the runner's facts (counts,
    step records, the program's spans and counters), the loaded device
    trace, the chip's peaks, and the yardstick's own arithmetic."""

    def __init__(self, facts, trace, peaks, span_name):
        from benchmark.lib import flops, stats
        from benchmark.trace import reduce
        self.facts, self.trace, self.peaks = facts, trace, peaks
        self.span_name = span_name
        self.reduce, self.flops, self.stats = reduce, flops, stats

    def program_spans(self, name: str) -> list:
        return sorted((e for e in self.facts.get("spans", [])
                       if e.get("name") == name and e.get("ph") == "X"),
                      key=lambda e: e["ts"])

    def server_step_ms(self, decode_only: bool) -> list:
        """Durations of the program's ``serving/engine_step`` spans of the
        steps the runner recorded as decode-only (or not). Spans and step
        records must pair one to one, else nothing is read."""
        spans = self.program_spans("serving/engine_step")
        steps = self.facts.get("steps", [])
        if not spans or len(spans) != len(steps):
            return []
        return [e["dur"] / 1e3 for e, s in zip(spans, steps)
                if s.decode_only == decode_only and s.rows > 0]


def main(argv=None) -> int:
    args = parse_args(argv)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no cell {args.workload!r} in BENCHMARK.json "
              f"(have {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    chips = int(cell["chips"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips} "
                f"--xla_cpu_enable_concurrency_optimized_scheduler=false"
            ).strip()
    import jax

    found = jax.devices()
    if found[0].platform != "tpu" and not args.rehearse:
        print(f"run.py: jax found no TPU (platform {found[0].platform!r}); "
              f"refusing to run. The CPU rehearsal is explicit: --rehearse",
              file=sys.stderr)
        return 2
    if len(found) < chips:
        print(f"run.py: the cell needs {chips} chips, jax reports "
              f"{len(found)}", file=sys.stderr)
        return 2
    devices = found[:chips]

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from benchmark.lib import flops as flops_lib
    from benchmark.lib import model as model_lib
    from benchmark.lib import traffic
    from benchmark.lib.ledger import CompileLedger

    # every program is cached, the sub-second ones too: a run's set-up is
    # then cache loads, not some fifty small compiles (5 s, and unsteady)
    cache_dir = enable_compile_cache(min_compile_secs=0.0)
    ledger = CompileLedger()
    conf = model_lib.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    reference = model_lib.load_reference(conf)
    emit({"phase": "start", "cell": cell["name"], "config": cell["config"],
          "traffic": cell["traffic"], "reference": reference.__name__,
          "seed": args.seed,
          "seconds": args.seconds, "trace": args.trace,
          "platform": found[0].platform, "device_kind": found[0].device_kind,
          "devices": len(found), "chips_used": chips,
          "compile_cache_dir": cache_dir, "jax": jax.__version__,
          # imports and the backend's start, before any of the program
          "t": round(time.monotonic() - T_START, 2)})
    ctx = Context(args, cell, conf, mix, devices, ledger, reference)
    runner = importlib.import_module(f"benchmark.runners.{mix['kind']}")
    result = runner.run(ctx)

    total = ledger.snapshot()
    emit({"phase": "compile_totals", "compiles": total["compiles"],
          "compile_s": round(total["compile_s"], 2),
          "cache_hits": total["cache_hits"],
          "cache_misses": total["cache_requests"] - total["cache_hits"]})
    peak_bytes = 0
    for d in devices:
        s = d.memory_stats() or {}
        peak_bytes = max(peak_bytes, int(s.get("peak_bytes_in_use", 0)))
    device = {"platform": found[0].platform, "kind": found[0].device_kind,
              "count": len(found), "memory_peak_bytes": peak_bytes}
    correct = bool(result["correct"])
    out = {"correct": correct, "attempted": int(result["attempted"]),
           "failed": int(result["failed"])}
    metrics = {}
    if not args.trace:
        values = dict(result["end_to_end"], setup_s=ctx.setup_s)
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if values.get(m["name"]) is None:
                emit({"phase": "missing_metric", "metric": m["name"]})
                correct = False
                continue
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from benchmark.trace import reduce
        data = ctx.load_trace()
        if args.dump_trace and data is not None:
            os.makedirs(os.path.dirname(os.path.abspath(args.dump_trace)),
                        exist_ok=True)
            with open(args.dump_trace, "w") as fh:
                json.dump(reduce.summarize(data), fh)
            win = reduce.traced_window(data, result["span_name"])
            if win is not None:
                with open(args.dump_trace + ".sample.json", "w") as fh:
                    json.dump(reduce.trim(data, win[0], win[0] + SAMPLE_NS,
                                          3000), fh)
        peaks = None if args.rehearse else flops_lib.peaks(
            found[0].device_kind)
        view = RunView(result["facts"], data, peaks, result["span_name"])
        for m in metrics_of(bench, "per_layer", cell["name"]):
            value = load_reader(m["name"]).read(view)
            if value is None:
                # nothing to read in this run: left out of the line, and
                # said so (a metric no run of a cell reads is not the cell's)
                emit({"phase": "metric_not_read", "metric": m["name"]})
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        bw = reduce.busy_and_window(data, result["span_name"]) \
            if data is not None else None
        if bw is not None:
            device["busy_s"], device["window_s"] = bw
            out["breakdown"] = {
                "device_ops": reduce.top_ops(data, 10),
                "idle_gaps": reduce.idle_gaps(
                    data, result.get("gap_spans", [result["span_name"]]),
                    10)}
        elif not args.rehearse:
            emit({"phase": "no_device_events_in_trace"})
            correct = False
    out.update(correct=correct, metrics=metrics, device=device)
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
