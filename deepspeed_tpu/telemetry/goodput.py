"""Goodput/badput wall-clock attribution ledger (``dstpu-goodput``).

The one question a fleet owner asks that no other telemetry layer
answers: *of every wall-clock second we pay for, how many produced
tokens or gradient steps?* The raw signals already exist — spans in the
tracer ring, the resilience ledger's injection→recovery pairs — but none
of them closes the accounting. This module does. (Communication a step
did not hide behind compute is a DEVICE time: it is measured from a
device trace -- the benchmark's ``collective_exposed_ms_per_step`` --
and no longer carved out of goodput from a roofline's prediction.)

The :class:`GoodputLedger` classifies every second of process lifetime
into exactly one category (``CATEGORIES``):

- ``goodput`` — productive compute: ``train/step`` spans (and the
  3-call API's ``train/forward`` / ``backward`` / ``optimizer``), and
  ``serving/engine_step`` spans with a non-empty running batch;
- ``init`` — process start until the first productive/compile/ckpt work;
- ``compile`` — XLA compilation (``compile/*`` spans emitted by the
  compile monitor);
- ``ckpt`` — checkpoint save/restore (``checkpoint/*`` spans);
- ``fault_recovery`` — injection→recovery intervals from the resilience
  ledger (:func:`deepspeed_tpu.resilience.faults.recovery_intervals`);
- ``input_stall`` — ``train/batch`` spans (fetching, stacking and
  placing the step's batch) and gaps between train steps on a training
  host (dataloader / host-input wait);
- ``idle`` — serving pumps with an empty running set, and gaps on a
  serving host (no admitted work);
- ``other`` — the residual that forces the ledger to sum to 100%.

Attribution is an interval sweep over the tracer ring: each instant of
the update window is assigned to the highest-priority overlapping
interval, so the categories sum to elapsed wall clock *by construction*
— the conservation property the tier-1 suite asserts. The ledger runs
off the existing ring + registry flush cadence; it adds nothing to any
hot path.

On top rides **profile-on-regression**: when the windowed goodput
fraction drops below ``telemetry.goodput.capture_threshold`` (or an SLO
breach latches while captures are armed), the
:class:`CaptureController` starts ONE bounded ``jax.profiler`` capture,
guarded by a cooldown, and records the dump path in the flight-recorder
black box — the expensive profile exists exactly for the windows worth
explaining.

CLI (``bin/dstpu-goodput``)::

    dstpu-goodput trace.json          # offline attribution of a dump
    dstpu-goodput --selftest          # synthetic-trace conservation check
"""

import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from deepspeed_tpu.telemetry.tracer import Tracer, tracer as _global_tracer

#: the complete attribution taxonomy, highest-priority badput first is
#: NOT implied by order — see ``_PRIORITY``. Every literal here must be
#: documented in docs/observability.md (tools/check_metric_names.py
#: lints this, mirroring the resilience fault catalog).
CATEGORIES = ("goodput", "init", "compile", "ckpt", "fault_recovery",
              "input_stall", "idle", "other")

#: sweep priority when intervals overlap: a named cause beats generic
#: productivity (a recovery or compile spanning a train step is badput)
_PRIORITY = {"fault_recovery": 0, "compile": 1, "ckpt": 2,
             "input_stall": 3, "goodput": 4, "idle": 5}

#: spans that are a train step's productive time: the fused path's
#: envelope and the 3-call API's three parts
_TRAIN_SPANS = ("train/step", "train/forward", "train/backward",
                "train/optimizer")

#: fleet/doctor alarm line: a fraction below this names its dominant
#: badput in the dstpu-doctor verdict ladder
LOW_GOODPUT_FRACTION = 0.5


def _classify_span(ev: Dict[str, Any]) -> Optional[str]:
    """Span event → ledger category (None: not an attribution source)."""
    name = ev.get("name", "")
    if name in _TRAIN_SPANS:
        return "goodput"
    if name == "train/batch":
        return "input_stall"
    if name == "serving/engine_step":
        args = ev.get("args") or {}
        batch = args.get("batch")
        return "goodput" if (batch or 0) > 0 else "idle"
    if name.startswith("compile/"):
        return "compile"
    if name.startswith("checkpoint/"):
        return "ckpt"
    return None


def attribute(events: Sequence[Dict[str, Any]], t0: float, t1: float,
              base: float = 0.0,
              recovery_intervals: Sequence[Tuple[float, float, str]] = (),
              ) -> Dict[str, Any]:
    """Sweep attribution of the window ``[t0, t1]`` (seconds).

    ``events`` are Chrome trace-event dicts whose ``ts``/``dur`` are in
    microseconds relative to ``base`` (a :class:`Tracer`'s ``_t0``;
    pass 0 for an offline dump whose timestamps are already absolute).
    ``recovery_intervals`` are absolute ``(start, end, kind)`` seconds
    on the same clock.

    Returns ``{"seconds": {category: s}, "train_steps": n,
    "kinds": {...}, "first_work": t|None}`` with the guarantee
    ``sum(seconds.values()) == t1 - t0`` (within float epsilon) —
    conservation by construction.
    """
    sec = {c: 0.0 for c in CATEGORIES}
    if t1 <= t0:
        return {"seconds": sec, "train_steps": 0, "kinds": {},
                "first_work": None}
    ivals: List[Tuple[float, float, int]] = []  # (start, end, rank)
    kinds: Dict[str, int] = {}
    train_steps = 0
    first_work: Optional[float] = None
    serving_seen = False
    train_seen = False
    for ev in events:
        if ev.get("ph") != "X":
            continue
        cat = _classify_span(ev)
        if cat is None:
            continue
        s = base + float(ev.get("ts", 0.0)) / 1e6
        e = s + float(ev.get("dur", 0.0)) / 1e6
        if ev.get("name") == "serving/engine_step":
            serving_seen = True
        elif ev.get("name") in _TRAIN_SPANS:
            train_seen = True
        if cat in ("goodput", "compile", "ckpt"):
            first_work = s if first_work is None else min(first_work, s)
        if ev.get("name") == "train/step" and t0 < e <= t1:
            train_steps += 1
        if e <= t0 or s >= t1:
            continue
        ivals.append((max(s, t0), min(e, t1), _PRIORITY[cat]))
    for (s, e, kind) in recovery_intervals:
        if e <= t0 or s >= t1:
            continue
        ivals.append((max(s, t0), min(e, t1),
                      _PRIORITY["fault_recovery"]))
        kinds[kind] = kinds.get(kind, 0) + 1
    rank_to_cat = {v: k for k, v in _PRIORITY.items()}
    gap_cat = ("input_stall" if train_seen and not serving_seen
               else "idle" if serving_seen
               else "other")
    bounds = sorted({t0, t1, *(s for s, _, _ in ivals),
                     *(e for _, e, _ in ivals)})
    for a, b in zip(bounds, bounds[1:]):
        if b <= a:
            continue
        mid = (a + b) / 2.0
        active = [r for s, e, r in ivals if s <= mid < e]
        if active:
            cat = rank_to_cat[min(active)]
        elif first_work is None or mid < first_work:
            cat = "init"
        else:
            cat = gap_cat
        sec[cat] += b - a
    return {"seconds": sec, "train_steps": train_steps, "kinds": kinds,
            "first_work": first_work}


class CaptureController:
    """One-shot, cooldown-guarded, bounded ``jax.profiler`` capture.

    Armed only when ``capture_threshold`` > 0. A windowed goodput
    fraction below the threshold (or a latched SLO breach) starts ONE
    capture of ``capture_duration_ms``; the next capture cannot start
    until ``capture_cooldown_s`` after the previous one began. Start and
    stop callables are injectable so tests stub the profiler out.
    """

    def __init__(self,
                 start_fn: Optional[Callable[[str], None]] = None,
                 stop_fn: Optional[Callable[[], None]] = None):
        self.threshold = 0.0
        self.cooldown_s = 600.0
        self.duration_ms = 2000.0
        self.dir: Optional[str] = None
        self._start_fn = start_fn
        self._stop_fn = stop_fn
        self._active_path: Optional[str] = None
        self._stop_at: Optional[float] = None
        self._last_start: Optional[float] = None
        self.captures = 0
        self.paths: List[str] = []

    def configure(self, threshold: Optional[float] = None,
                  cooldown_s: Optional[float] = None,
                  duration_ms: Optional[float] = None,
                  dir: Optional[str] = None) -> None:
        if threshold is not None:
            self.threshold = float(threshold)
        if cooldown_s is not None:
            self.cooldown_s = float(cooldown_s)
        if duration_ms is not None:
            self.duration_ms = float(duration_ms)
        if dir is not None:
            self.dir = dir

    def _start(self, path: str) -> None:
        if self._start_fn is not None:
            self._start_fn(path)
            return
        from jax import profiler as jprof
        jprof.start_trace(path)

    def _stop(self) -> None:
        if self._stop_fn is not None:
            self._stop_fn()
            return
        from jax import profiler as jprof
        jprof.stop_trace()

    def poll(self, now: float, window_fraction: Optional[float],
             breach: bool = False) -> Optional[str]:
        """Advance the capture state machine. Returns the dump path when
        a capture STARTS this poll, else None. Never raises — a broken
        profiler must not take the ledger down."""
        if self._active_path is not None and self._stop_at is not None \
                and now >= self._stop_at:
            try:
                self._stop()
            except Exception:                        # noqa: BLE001
                pass
            try:
                from deepspeed_tpu.telemetry.flight_recorder import \
                    flight_recorder
                flight_recorder.record_event("goodput_capture_done",
                                             path=self._active_path)
            except Exception:                        # noqa: BLE001
                pass
            self._active_path = self._stop_at = None
        if self.threshold <= 0 or self._active_path is not None:
            return None
        dip = (window_fraction is not None
               and window_fraction < self.threshold)
        if not dip and not breach:
            return None
        if self._last_start is not None and \
                now - self._last_start < self.cooldown_s:
            return None
        root = self.dir or os.path.join(os.getcwd(),
                                        "dstpu_goodput_captures")
        path = os.path.join(
            root, time.strftime("capture_%Y%m%d_%H%M%S")
            + f"_{self.captures}")
        reason = ("slo_breach" if breach and not dip else
                  f"goodput_window={window_fraction:.3f}"
                  f"<{self.threshold:.3f}")
        try:
            os.makedirs(path, exist_ok=True)
            self._start(path)
        except Exception:                            # noqa: BLE001
            return None
        self._active_path = path
        self._stop_at = now + self.duration_ms / 1e3
        self._last_start = now
        self.captures += 1
        self.paths.append(path)
        try:
            from deepspeed_tpu.telemetry.flight_recorder import \
                flight_recorder
            flight_recorder.record_event("goodput_capture", path=path,
                                         reason=reason)
        except Exception:                            # noqa: BLE001
            pass
        return path


class GoodputLedger:
    """Per-host wall-clock attribution over the tracer ring.

    ``update()`` attributes the window since the previous update (the
    first update anchors at the tracer's ``_t0`` — process lifetime on
    the tracer clock), folds the per-category seconds into the running
    totals, publishes ``goodput/*`` gauges, and polls the capture
    controller. Callers invoke it on the existing registry-flush
    cadence; ``maybe_update()`` additionally rate-limits for callers on
    tighter loops.
    """

    def __init__(self, tracer: Optional[Tracer] = None):
        self.enabled = False
        self.window_s = 60.0
        self._tracer = tracer
        self._lock = threading.Lock()
        self._t0: Optional[float] = None
        self._last: Optional[float] = None
        self.seconds: Dict[str, float] = {c: 0.0 for c in CATEGORIES}
        self.recovery_kinds: Dict[str, int] = {}
        self._first_work: Optional[float] = None
        #: (ts, cumulative goodput_s) samples for the windowed fraction
        self._samples: deque = deque(maxlen=4096)
        self._min_interval_s = 1.0
        self.capture = CaptureController()

    # -- configuration ------------------------------------------------------

    def configure(self, enabled: Optional[bool] = None,
                  window_s: Optional[float] = None,
                  capture_threshold: Optional[float] = None,
                  capture_cooldown_s: Optional[float] = None,
                  capture_duration_ms: Optional[float] = None,
                  capture_dir: Optional[str] = None) -> None:
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if window_s is not None:
                self.window_s = float(window_s)
            self.capture.configure(threshold=capture_threshold,
                                   cooldown_s=capture_cooldown_s,
                                   duration_ms=capture_duration_ms,
                                   dir=capture_dir)

    def reset(self) -> None:
        with self._lock:
            self._t0 = self._last = self._first_work = None
            self.seconds = {c: 0.0 for c in CATEGORIES}
            self.recovery_kinds = {}
            self._samples.clear()

    # -- attribution --------------------------------------------------------

    @property
    def _tr(self) -> Tracer:
        return self._tracer if self._tracer is not None else _global_tracer

    def maybe_update(self, now: Optional[float] = None
                     ) -> Optional[Dict[str, Any]]:
        """``update()`` rate-limited to one sweep per second — the hook
        for callers on per-pump loops."""
        if not self.enabled:
            return None
        now = self._tr.now() if now is None else now
        if self._last is not None and \
                now - self._last < self._min_interval_s:
            return None
        return self.update(now)

    def update(self, now: Optional[float] = None) -> Optional[Dict[str, Any]]:
        """Attribute the window since the last update; publish gauges;
        poll the capture controller. Returns :meth:`summary`."""
        if not self.enabled:
            return None
        tr = self._tr
        now = tr.now() if now is None else now
        with self._lock:
            if self._t0 is None:
                self._t0 = self._last = tr._t0
            if now <= self._last:
                return self._summary_locked()
            try:
                from deepspeed_tpu.resilience.faults import \
                    recovery_intervals
                rec = recovery_intervals()
            except Exception:                        # noqa: BLE001
                rec = []
            res = attribute(tr.events(), self._last, now, base=tr._t0,
                            recovery_intervals=rec)
            delta = res["seconds"]
            if res["first_work"] is not None:
                self._first_work = (res["first_work"]
                                    if self._first_work is None
                                    else min(self._first_work,
                                             res["first_work"]))
            for c in CATEGORIES:
                self.seconds[c] += delta[c]
            for k, n in res["kinds"].items():
                self.recovery_kinds[k] = self.recovery_kinds.get(k, 0) + n
            self._last = now
            self._samples.append((now, self.seconds["goodput"]))
            wf = self._window_fraction_locked(now)
            summary = self._summary_locked()
        self._publish(summary, wf)
        breach = False
        try:
            from deepspeed_tpu.telemetry.registry import registry
            g = registry.get("slo/breached")
            breach = g is not None and float(g.value) > 0
        except Exception:                            # noqa: BLE001
            pass
        self.capture.poll(now, wf, breach=breach)
        summary["window_fraction"] = wf
        return summary

    def _window_fraction_locked(self, now: float) -> Optional[float]:
        """Goodput share of the trailing ``window_s`` seconds."""
        if not self._samples:
            return None
        anchor = None
        for ts, g in self._samples:
            if ts <= now - self.window_s:
                anchor = (ts, g)
            else:
                break
        if anchor is None:
            anchor = self._samples[0]
            # the whole history is shorter than the window: fall back to
            # the lifetime fraction so early dips still read correctly
            if now - (self._t0 or now) > 0:
                return self.seconds["goodput"] / (now - self._t0)
            return None
        dt = now - anchor[0]
        if dt <= 0:
            return None
        return max(0.0, min(1.0, (self.seconds["goodput"] - anchor[1])
                            / dt))

    # -- export -------------------------------------------------------------

    def _summary_locked(self) -> Dict[str, Any]:
        uptime = max(0.0, (self._last or 0.0) - (self._t0 or 0.0))
        badput = {c: round(self.seconds[c], 6) for c in CATEGORIES
                  if c != "goodput"}
        dominant = max(badput, key=badput.get) if uptime > 0 else None
        if dominant is not None and badput[dominant] <= 0:
            dominant = None
        return {
            "uptime_s": round(uptime, 6),
            "goodput_s": round(self.seconds["goodput"], 6),
            "fraction": (round(self.seconds["goodput"] / uptime, 6)
                         if uptime > 0 else None),
            "badput": badput,
            "dominant_badput": dominant,
            "dominant_badput_s": (badput[dominant]
                                  if dominant is not None else 0.0),
            "recovery_kinds": dict(self.recovery_kinds),
            "captures": self.capture.captures,
            "capture_paths": list(self.capture.paths),
        }

    def summary(self) -> Dict[str, Any]:
        """Ledger state as a JSON-safe dict (bench ``extra.goodput``,
        flight-recorder ``goodput`` section, doctor ingestion)."""
        with self._lock:
            s = self._summary_locked()
        s["window_fraction"] = None
        with self._lock:
            if self._last is not None:
                s["window_fraction"] = self._window_fraction_locked(
                    self._last)
        return s

    def _publish(self, summary: Dict[str, Any],
                 window_fraction: Optional[float]) -> None:
        try:
            from deepspeed_tpu.telemetry.registry import registry
            registry.gauge(
                "goodput/uptime_s",
                help="wall-clock seconds attributed by the ledger"
            ).set(summary["uptime_s"])
            if summary["fraction"] is not None:
                registry.gauge(
                    "goodput/fraction",
                    help="lifetime goodput share of wall clock, 0-1"
                ).set(summary["fraction"])
            if window_fraction is not None:
                registry.gauge(
                    "goodput/window_fraction",
                    help="goodput share over the trailing window, 0-1"
                ).set(window_fraction)
            for cat in CATEGORIES:
                # variable name on purpose: '{cat}_s' is not a whole
                # placeholder segment, so the literal-name lint would
                # reject the f-string spelling (docs carry the catalog
                # row goodput/<category>_s instead)
                name = "goodput/%s_s" % cat
                registry.gauge(
                    name,
                    help="seconds attributed to this ledger category"
                ).set(round(self.seconds[cat], 6))
            registry.gauge(
                "goodput/captures",
                help="profile-on-regression captures started"
            ).set(float(self.capture.captures))
        except Exception:                            # noqa: BLE001
            pass


#: process-wide ledger (armed by ``telemetry.configure`` /
#: ``telemetry.goodput.enabled``; the engine and serving frontend call
#: ``update()`` on their registry-flush cadence)
goodput_ledger = GoodputLedger()


# ---------------------------------------------------------------------------
# CLI (bin/dstpu-goodput)
# ---------------------------------------------------------------------------

def format_ledger(summary: Dict[str, Any]) -> str:
    """Render a ledger summary as an aligned category table."""
    uptime = summary.get("uptime_s") or 0.0
    rows = [("goodput", summary.get("goodput_s") or 0.0)]
    rows += sorted((summary.get("badput") or {}).items(),
                   key=lambda kv: -kv[1])
    lines = [f"{'category':<16}{'seconds':>12}{'% of wall':>11}"]
    for cat, s in rows:
        pct = 100.0 * s / uptime if uptime > 0 else 0.0
        lines.append(f"{cat:<16}{s:>12.3f}{pct:>10.1f}%")
    lines.append(f"{'total':<16}{uptime:>12.3f}{100.0:>10.1f}%")
    dom = summary.get("dominant_badput")
    if dom:
        lines.append(f"dominant badput: {dom} "
                     f"({summary.get('dominant_badput_s', 0.0):.3f}s)")
    if summary.get("captures"):
        lines.append(f"profiler captures: {summary['captures']} "
                     f"({', '.join(summary.get('capture_paths') or [])})")
    return "\n".join(lines)


def selftest() -> int:
    """Synthetic-trace conservation check (the tier-1 smoke): build a
    known timeline, attribute it, and verify the categories sum to the
    wall clock and land where they should."""
    tr = Tracer(buffer_events=1024)
    tr.configure(enabled=True)
    t0 = tr._t0
    tr.complete("compile/train_step", t0 + 1.0, t0 + 3.0)
    for i in range(5):
        tr.complete("train/step", t0 + 3.0 + i, t0 + 3.8 + i, step=i)
    tr.complete("checkpoint/save", t0 + 8.0, t0 + 9.0)
    led = GoodputLedger(tracer=tr)
    led.configure(enabled=True)
    s = led.update(t0 + 10.0)
    total = s["goodput_s"] + sum(s["badput"].values())
    ok = (abs(total - s["uptime_s"]) < 1e-6
          and abs(s["goodput_s"] - 4.0) < 1e-6
          and abs(s["badput"]["compile"] - 2.0) < 1e-6
          and abs(s["badput"]["ckpt"] - 1.0) < 1e-6
          and abs(s["badput"]["init"] - 1.0) < 1e-6)
    print(format_ledger(s))
    print(f"selftest: conservation "
          f"{'OK' if ok else 'FAILED'} (sum={total:.6f}s, "
          f"uptime={s['uptime_s']:.6f}s)")
    return 0 if ok else 1


def main(argv: Optional[List[str]] = None) -> int:
    """``dstpu-goodput``: offline goodput attribution of a Chrome
    trace-event dump, or ``--selftest`` for the synthetic conservation
    check."""
    import argparse
    ap = argparse.ArgumentParser(
        prog="dstpu-goodput",
        description="Goodput/badput wall-clock attribution: classify "
                    "every second of a trace into the ledger taxonomy "
                    "(see docs/observability.md 'Goodput ledger').")
    ap.add_argument("trace", nargs="?", default=None,
                    help="Chrome trace-event JSON (tracer.dump output)")
    ap.add_argument("--selftest", action="store_true",
                    help="run the synthetic-trace conservation check")
    ap.add_argument("--json", action="store_true",
                    help="emit the attribution as JSON")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.trace:
        ap.error("give a trace file or --selftest")
    from deepspeed_tpu.telemetry.summarize import load_trace
    events = load_trace(args.trace)
    spans = [e for e in events if e.get("ph") == "X"
             and _classify_span(e) is not None]
    if not spans:
        print(f"{args.trace}: no attributable spans (train/step, "
              f"serving/engine_step, compile/*, checkpoint/*)",
              file=sys.stderr)
        return 1
    t0 = min(float(e["ts"]) for e in spans) / 1e6
    t1 = max(float(e["ts"]) + float(e.get("dur", 0.0))
             for e in spans) / 1e6
    res = attribute(events, t0, t1, base=0.0)
    sec = res["seconds"]
    summary = {
        "uptime_s": round(t1 - t0, 6),
        "goodput_s": round(sec["goodput"], 6),
        "fraction": (round(sec["goodput"] / (t1 - t0), 6)
                     if t1 > t0 else None),
        "badput": {c: round(sec[c], 6) for c in CATEGORIES
                   if c != "goodput"},
        "train_steps": res["train_steps"],
    }
    bp = summary["badput"]
    dom = max(bp, key=bp.get)
    summary["dominant_badput"] = dom if bp[dom] > 0 else None
    summary["dominant_badput_s"] = bp[dom]
    if args.json:
        print(json.dumps(summary))
    else:
        print(format_ledger(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
