"""``dstpu_report --compare a b`` — history-aware run regression gate.

Compares two runs' artifacts and flags metric regressions beyond a
noise band, exit-code-first so it drops straight into CI::

    dstpu_report --compare baseline.jsonl candidate.jsonl
    dstpu_report --compare runs/a/history.jsonl runs/b/history.jsonl \
                 --noise 0.08 --json

Each side may be:

- **BENCH JSONL** — lines of ``{"metric": ..., "value": ..., "unit":
  ...}`` as printed by ``bench.py`` (a driver
  wrapper object with the stdout under ``"tail"`` also works);
- **metric history** — a :mod:`~deepspeed_tpu.telemetry.timeseries`
  JSONL file (detected by the ``"m"`` record key). History compare
  summarizes each run over its whole span for a whitelist of
  regression-meaningful families (MFU, step time p95, TTFT p95,
  TPOT p99, token/step throughput, SLO worst burn) — per-flush noise is
  averaged out, tails are judged on interval percentiles.

Direction (higher- vs lower-is-better) is inferred from the metric name
and unit — latency/time/burn metrics regress upward, throughput/MFU
regress downward. A metric present on only one side is reported but
never fails the gate (benches grow metrics release to release).
"""

import json
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.telemetry.timeseries import (Record, load_records,
                                                resolve_metric, windowed)

DEFAULT_NOISE = 0.05

#: name/unit fragments ⇒ lower is better (everything else: higher wins)
_LOWER_BETTER = re.compile(
    r"(time|latency|ttft|tpot|wall|ms\b|seconds|stall|burn|overhead|"
    r"bytes|hbm|breach|p9[059]|p50|retries|evictions|drops)", re.I)

#: history families worth gating on: (label, metric, agg, lower_better)
HISTORY_FAMILIES: List[Tuple[str, str, str, bool]] = [
    ("train/mfu (mean)", "train/mfu", "mean", False),
    ("train/step_time_ms p95 (mean)", "train/step_time_ms:p95",
     "mean", True),
    ("serving/ttft_seconds p95 (mean)", "serving/ttft_seconds:p95",
     "mean", True),
    ("serving/tpot_seconds p99 (mean)", "serving/tpot_seconds:p99",
     "mean", True),
    ("serving/tokens_out (rate/s)", "serving/tokens_out", "rate", False),
    ("train/steps (rate/s)", "train/steps", "rate", False),
    ("slo/worst_burn (max)", "slo/worst_burn", "max", True),
    ("slo/breached (max)", "slo/breached", "max", True),
]


def lower_is_better(metric: str, unit: str = "") -> bool:
    return bool(_LOWER_BETTER.search(f"{metric} {unit}"))


def load_bench_lines(path: str) -> List[Dict[str, Any]]:
    """BENCH result dicts from a bench-stdout JSONL file; also unwraps
    the driver's ``{"tail": "<stdout>"}`` capture format."""
    out: List[Dict[str, Any]] = []

    def eat(text: str) -> None:
        for line in text.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if isinstance(doc, dict) and "metric" in doc \
                    and "value" in doc:
                out.append(doc)

    with open(path) as fh:
        text = fh.read()
    try:
        whole = json.loads(text)
    except ValueError:
        whole = None
    if isinstance(whole, dict) and "metric" in whole and "value" in whole:
        out.append(whole)
    elif isinstance(whole, dict) and isinstance(whole.get("tail"), str):
        eat(whole["tail"])
    else:
        eat(text)
    return out


def is_history(path: str) -> bool:
    """A metric-history file's first parseable line carries ``"m"``."""
    try:
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    doc = json.loads(line)
                except ValueError:
                    return False
                return isinstance(doc, dict) and "m" in doc
    except OSError:
        pass
    return False


def _span_rate(recs: List[Record], name: str) -> Optional[float]:
    """Counter increase over the whole span / elapsed seconds."""
    pts = [(r.get("ts", 0.0), resolve_metric(r, name)) for r in recs]
    pts = [(t, v) for t, v in pts if v is not None]
    if len(pts) < 2 or pts[-1][0] <= pts[0][0] or pts[-1][1] < pts[0][1]:
        return None
    return (pts[-1][1] - pts[0][1]) / (pts[-1][0] - pts[0][0])


def summarize_history(path: str) -> Dict[str, Tuple[float, bool]]:
    """``{label: (value, lower_is_better)}`` over one history file."""
    recs = load_records(path)
    out: Dict[str, Tuple[float, bool]] = {}
    if not recs:
        return out
    span = max(1.0, recs[-1].get("ts", 0.0) - recs[0].get("ts", 0.0))
    for label, metric, agg, lower in HISTORY_FAMILIES:
        if agg == "rate":
            v = _span_rate(recs, metric)
        else:
            pts = windowed(recs, metric, window_s=span * 2, agg=agg,
                           prefer_interval=":" in metric)
            v = pts[0][1] if pts else None
        if v is not None:
            out[label] = (float(v), lower)
    return out


def summarize_bench(path: str) -> Dict[str, Tuple[float, bool]]:
    out: Dict[str, Tuple[float, bool]] = {}
    for doc in load_bench_lines(path):
        try:
            v = float(doc["value"])
        except (TypeError, ValueError):
            continue
        name = str(doc["metric"])
        out[name] = (v, lower_is_better(name, str(doc.get("unit", ""))))
    return out


def compare(a_path: str, b_path: str,
            noise: float = DEFAULT_NOISE) -> Dict[str, Any]:
    """Compare run ``a`` (baseline) against ``b`` (candidate).

    Returns ``{"rows": [...], "regressions": [...], "only_a": [...],
    "only_b": [...]}`` — a row regresses when the candidate moves in the
    bad direction by more than ``noise`` (relative; absolute when the
    baseline is 0, e.g. ``slo/breached`` going 0 → 1)."""
    kind = "history" if (is_history(a_path) and is_history(b_path)) \
        else "bench"
    summar = summarize_history if kind == "history" else summarize_bench
    a, b = summar(a_path), summar(b_path)
    rows, regressions = [], []
    for name in sorted(set(a) | set(b)):
        if name not in a or name not in b:
            continue
        (va, lower), (vb, _) = a[name], b[name]
        if va != 0:
            delta = (vb - va) / abs(va)
        else:
            delta = vb            # absolute movement off a zero baseline
        bad = delta > noise if lower else delta < -noise
        row = {"metric": name, "a": va, "b": vb,
               "delta_pct": round(delta * 100, 2),
               "direction": "lower_better" if lower else "higher_better",
               "regression": bad}
        rows.append(row)
        if bad:
            regressions.append(row)
    return {"kind": kind, "noise": noise, "rows": rows,
            "regressions": regressions,
            "only_a": sorted(set(a) - set(b)),
            "only_b": sorted(set(b) - set(a))}


def render(report: Dict[str, Any], a_path: str, b_path: str) -> str:
    lines = [f"compare ({report['kind']}): A={a_path}  B={b_path}  "
             f"noise band ±{report['noise'] * 100:.0f}%"]
    w = max((len(r["metric"]) for r in report["rows"]), default=10)
    for r in report["rows"]:
        mark = "REGRESSION" if r["regression"] else (
            "improved" if (r["delta_pct"] < 0) ==
            (r["direction"] == "lower_better") and
            abs(r["delta_pct"]) > report["noise"] * 100 else "~")
        lines.append(f"  {r['metric'].ljust(w)}  "
                     f"{r['a']:>12.4g} -> {r['b']:>12.4g}  "
                     f"{r['delta_pct']:>+8.2f}%  {mark}")
    for side, names in (("A", report["only_a"]), ("B", report["only_b"])):
        for n in names:
            lines.append(f"  {n.ljust(w)}  (only in {side}, not gated)")
    n_reg = len(report["regressions"])
    lines.append(f"{n_reg} regression(s) beyond the noise band"
                 if n_reg else "no regressions beyond the noise band")
    return "\n".join(lines)


def main_compare(a_path: str, b_path: str, noise: float = DEFAULT_NOISE,
                 as_json: bool = False, file=None) -> int:
    """CLI body for ``dstpu_report --compare`` → exit 1 on regression."""
    report = compare(a_path, b_path, noise=noise)
    out = file if file is not None else sys.stdout
    if as_json:
        print(json.dumps(report, indent=2), file=out)
    else:
        print(render(report, a_path, b_path), file=out)
    return 1 if report["regressions"] else 0
