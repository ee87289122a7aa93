"""Compile-time explain layer: roofline & HBM-budget attribution.

PR 3/4 instrumented the *measured* side (spans, metrics, flight
recorder); this module adds the *static* side: lower the engine's jitted
step (and the serving prefill/decode programs) ahead of time and read
back what XLA already knows about the compiled program —

- ``cost_analysis()``: FLOPs and bytes accessed, fusion-accurate;
- ``memory_analysis()``: the HBM split (argument / output / temp /
  generated-code bytes) of the exact executable;
- the optimized HLO text: bytes moved by collectives (all-reduce,
  all-gather, reduce-scatter, all-to-all, collective-permute).

Combined with the per-platform peak tables (``PEAK_FLOPS_BF16`` /
``PEAK_HBM_BW`` in :mod:`~deepspeed_tpu.telemetry.sampler`, the ICI
table here) that yields a roofline: predicted step time =
max(compute, memory, comm) bound, published as ``roofline/*`` gauges and
compared against the measured ``train/step_time_ms`` so "% of roofline"
is a first-class health number (T3 / Big-Send-off framing: static cost
attribution paired with achieved-vs-peak measurement).

Everything degrades gracefully: backends whose ``cost_analysis`` returns
nothing (some CPU builds) still produce a report with the static byte
budget, and unknown platforms (CPU CI) report an "unknown" roofline
bound unless peaks are overridden (``--platform v5e`` models a target
chip from any host — nothing is allocated, lowering is abstract).

CLI: ``bin/dstpu-explain`` / ``python -m deepspeed_tpu.telemetry.explain``.
"""

import argparse
import json
import math
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from deepspeed_tpu.telemetry.registry import registry as _registry
from deepspeed_tpu.telemetry.sampler import (HBM_CAPACITY, PEAK_FLOPS_BF16,
                                             PEAK_HBM_BW, hbm_capacity,
                                             peak_flops, peak_hbm_bw,
                                             warn_unknown_platform)

#: peak interconnect bandwidth, bytes/s per chip (public ICI specs,
#: aggregate over the chip's links; the comm side of the roofline)
PEAK_ICI_BW: Dict[str, float] = {
    "v7": 1200e9, "ironwood": 1200e9,
    "v6e": 448e9, "trillium": 448e9,
    "v5p": 600e9,
    "v5e": 200e9, "v5 lite": 200e9, "v5litepod": 200e9,
    "v4": 300e9,
    "v3": 82e9,
    "v2": 62e9,
}

#: most recent explain snapshots ({"train": ..., "serving": ...}) — the
#: flight recorder folds this into black boxes so dstpu-doctor can show
#: predicted vs achieved post mortem
last_report: Dict[str, Any] = {}

_DTYPE_BYTES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
                "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1}

_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

#: one HLO instruction: ``name = <shape> <opcode>(...)`` where <shape>
#: is a single ``f32[8,64]{1,0}`` or a tuple ``(f32[...], f32[...])``
_INSTR_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>[a-z][a-z-]*)\(")


# ---------------------------------------------------------------------------
# cost extraction — THE cost-analysis helper (flops_profiler re-exports)
# ---------------------------------------------------------------------------

def abstractify(tree):
    """Pytree of arrays → ShapeDtypeStructs, keeping shardings when the
    leaves carry them (so lowering sees the real GSPMD layout). Nothing
    is allocated — 70B-scale programs explain for free."""
    import jax

    def leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        sharding = getattr(x, "sharding", None)
        try:
            # only NamedShardings: uncommitted host arrays carry a
            # SingleDeviceSharding whose device set clashes with the
            # mesh-sharded params under one jitted computation
            if isinstance(sharding, jax.sharding.NamedSharding):
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=sharding)
        except Exception:
            pass
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    return jax.tree.map(leaf, tree)


def normalize_cost_analysis(cost: Any) -> Dict[str, float]:
    """``compiled.cost_analysis()`` → plain dict. Handles the dict /
    per-device-list return shapes across jax versions, and None/empty
    from backends without an implementation."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return {}
    return {str(k): float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and math.isfinite(float(v))}


def collective_stats_from_hlo(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-op collective traffic in optimized HLO text:
    ``{op: {"bytes": float, "count": int}}``.

    Counts each collective INSTRUCTION once. Async pairs are attributed
    to the ``-start`` op only (the ``-done`` merely unpacks the result),
    and a ``-start`` whose shape is a tuple — ``(operand_aliases,
    result)`` or the tupled variadic form — contributes the single
    LARGEST element of the tuple, not the sum: summing would double-count
    every async/fused collective (all-gather-start's tuple repeats the
    operand next to the gathered result; all-reduce-start's repeats the
    buffer on both sides; collective-permute-start adds tiny u32 context
    slots). The chunked ZeRO-3 overlap path fragments the whole-model
    gather into dozens of small async all-gathers, which made that
    double-count structural rather than occasional — ``count`` exposes
    the fragmentation (chunk count) instead.
    """
    stats: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        if op.endswith("-done"):
            continue                      # async pair: count the start only
        if op.endswith("-start"):
            op = op[:-len("-start")]
        if op == "fusion" and "calls=%all-reduce-scatter" in line:
            # the TPU compiler emits a reduce-scatter as a custom fusion
            # around an ``all-reduce-scatter`` computation
            op = "reduce-scatter"
        if op not in _COLLECTIVE_OPS:
            continue
        best = 0.0
        for dt, dims in _SHAPE_RE.findall(m.group("shape")):
            nbytes = _DTYPE_BYTES.get(dt)
            if nbytes is None:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            best = max(best, float(n * nbytes))
        s = stats.setdefault(op, {"bytes": 0.0, "count": 0})
        s["bytes"] += best
        s["count"] += 1
    return stats


#: the ONE vocabulary of ``jax.named_scope`` names the step programs carry
#: at their layer-part boundaries (models/transformer.py, inference/
#: engine_v2.py, runtime/engine.py); docs/observability.md says what each
#: covers. Trainer and servers share the model functions, so they share
#: the names.
SCOPE_VOCABULARY = (
    "embed", "norm", "attn_qkv", "attn_core", "attn_history", "attn_merge",
    "kv_write", "attn_out", "mlp", "moe", "moe_router", "moe_experts",
    "lm_head", "loss", "grad_clip", "optimizer", "sample", "step_misc",
    "attn_latent", "moe_shared",
    "ssm_in", "ssm_conv", "ssm_scan", "ssm_state", "ssm_norm", "ssm_out",
    "ssm_select", "attn_index", "attn_select", "conv_mixer", "conv_state",
    "hc_maps", "hc_mix", "delta_rule", "attn_gate")

_HLO_NAME_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_HLO_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_HLO_COMPUTATION_RE = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_HLO_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_REF_RE = re.compile(r"%([\w.\-]+)")
_IDENT_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def scope_of_op_name(op_name: str) -> Dict[str, Any]:
    """``{"scope", "backward", "remat"}`` of one HLO ``op_name`` path.

    ``scope`` is the INNERMOST vocabulary word on the path, or None. jax
    writes a scope either as a component of its own (``.../while/body/
    mlp/dot_general``) or inside the transforms that wrap it
    (``transpose(jvp(loss))/mul``), so every identifier of every component
    is looked at, last first. ``backward``: the path holds ``transpose(``;
    ``remat``: it holds ``rematted_computation`` (a forward operation
    computed again for the backward pass)."""
    scope = None
    for part in reversed(op_name.split("/")):
        for word in reversed(_IDENT_RE.findall(part)):
            if word in SCOPE_VOCABULARY:
                scope = word
                break
        if scope is not None:
            break
    return {"scope": scope, "backward": "transpose(" in op_name,
            "remat": "rematted_computation" in op_name}


def scope_table_from_hlo(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """``{instruction name: {"scope", "backward", "remat", "inherited"}}``
    for every instruction of an optimized HLO module's text (fusions, convolutions,
    custom-calls, the instructions inside fused computations too: names
    are unique in a module, and the profiler prints the same ones).

    The compiler drops or never writes the metadata of some instructions
    it builds, so one without a vocabulary word of its own inherits, in
    this order: (1) a fusion takes the scope of the computation it
    ``calls=`` -- its root's, else the commonest among its instructions (a
    scatter into the KV arena becomes a ``kCustom`` fusion whose root has
    no metadata); (2) what is still unnamed takes the commonest scope of
    the instructions that USE it, three rounds for chains: a relayout
    copy, or the layer loop's slice of a stacked weight, is made for its
    consumer and is that layer part's cost. The rest maps to scope None.
    ``inherited`` says which it was: False where the scope is a word of
    the instruction's OWN ``op_name`` (or there is no scope), True where
    rule (1) or (2) assigned it -- a reader can so tell the time the
    compiler's metadata names from the time this heuristic names."""
    table: Dict[str, Dict[str, Any]] = {}
    members: Dict[str, List[Dict[str, Any]]] = {}
    roots: Dict[str, Dict[str, Any]] = {}
    calls: Dict[str, str] = {}
    own_path: Dict[str, str] = {}
    refs: Dict[str, List[str]] = {}
    current = None
    for line in hlo_text.splitlines():
        cm = _HLO_COMPUTATION_RE.match(line)
        if cm is not None:
            current = cm.group(1)
            continue
        m = _HLO_NAME_RE.match(line)
        if m is None or "(" not in line:
            continue
        name = m.group(2)
        om = _HLO_OP_NAME_RE.search(line)
        own_path[name] = om.group(1) if om else ""
        entry = table[name] = dict(scope_of_op_name(own_path[name]),
                                   inherited=False)
        members.setdefault(current, []).append(entry)
        if m.group(1):
            roots[current] = entry
        called = _HLO_CALLS_RE.search(line)
        if called is not None:
            calls[name] = called.group(1)
        refs[name] = _HLO_REF_RE.findall(line[m.end():])

    def commonest(entries):
        counts: Dict[str, int] = {}
        for e in entries:
            counts[e["scope"]] = counts.get(e["scope"], 0) + 1
        best = max(counts, key=counts.get)
        return next(e for e in entries if e["scope"] == best)

    def inherit(name, donor):
        # with a path of its own, the instruction's own direction stands
        table[name] = dict(donor if not own_path[name] else table[name],
                           scope=donor["scope"], inherited=True)

    for name, comp in calls.items():
        if table[name]["scope"] is not None:
            continue
        named = [e for e in members.get(comp, ()) if e["scope"] is not None]
        root = roots.get(comp)
        if named:
            inherit(name, root if root is not None and
                    root["scope"] is not None else commonest(named))
    for _round in range(3):
        users: Dict[str, List[Dict[str, Any]]] = {}
        for user, operands in refs.items():
            if table[user]["scope"] is None:
                continue
            for op in operands:
                if op in table and table[op]["scope"] is None:
                    users.setdefault(op, []).append(table[user])
        if not users:
            break
        for name, entries in users.items():
            inherit(name, commonest(entries))
    return table


def collective_bytes_from_hlo(hlo_text: str) -> float:
    """Total bytes moved by collectives in optimized HLO text (the
    largest buffer of each collective instruction, summed). An
    approximation of wire traffic — good enough to rank the comm
    roofline bound. See :func:`collective_stats_from_hlo` for the
    per-op/per-chunk breakdown."""
    return sum(s["bytes"] for s in collective_stats_from_hlo(
        hlo_text).values())


@dataclass
class FunctionCost:
    """Per-compiled-function static costs (all bytes are per device —
    the compiled program is the SPMD per-device program)."""
    name: str
    available: bool = False           #: cost_analysis had real numbers
    flops: float = 0.0
    bytes_accessed: float = 0.0
    argument_bytes: float = 0.0
    output_bytes: float = 0.0
    temp_bytes: float = 0.0
    generated_code_bytes: float = 0.0
    collective_bytes: float = 0.0
    #: {op: {"bytes", "count"}} — per-op totals + instruction counts
    #: (chunked-overlap runs show count ≈ 2×chunks here)
    collective_stats: Dict[str, Dict[str, float]] = None
    error: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        return dict(self.__dict__)


def analyze_compiled(name: str, compiled) -> FunctionCost:
    """Extract a :class:`FunctionCost` from a ``jax`` AOT-compiled
    object. Every source is best-effort; missing pieces stay 0."""
    fc = FunctionCost(name=name)
    try:
        cost = normalize_cost_analysis(compiled.cost_analysis())
    except Exception:
        cost = {}
    fc.flops = cost.get("flops", 0.0)
    fc.bytes_accessed = cost.get("bytes accessed", 0.0)
    fc.available = bool(cost) and (fc.flops > 0 or fc.bytes_accessed > 0)
    try:
        mem = compiled.memory_analysis()
        fc.argument_bytes = float(getattr(mem, "argument_size_in_bytes", 0))
        fc.output_bytes = float(getattr(mem, "output_size_in_bytes", 0))
        fc.temp_bytes = float(getattr(mem, "temp_size_in_bytes", 0))
        fc.generated_code_bytes = float(
            getattr(mem, "generated_code_size_in_bytes", 0))
    except Exception:
        pass
    try:
        fc.collective_stats = collective_stats_from_hlo(compiled.as_text())
        fc.collective_bytes = sum(s["bytes"]
                                  for s in fc.collective_stats.values())
    except Exception:
        pass
    return fc


def analyze_lowerable(name: str, fn: Callable, *abstract_args,
                      static_argnums=()) -> FunctionCost:
    """Lower + compile ``fn`` over abstract args (already-jitted
    functions lower directly; plain callables are jitted first) and
    extract its costs. Failures come back as an unavailable record with
    the error string, never an exception — explain must not take an
    engine down."""
    import jax
    try:
        target = fn if hasattr(fn, "lower") else \
            jax.jit(fn, static_argnums=static_argnums)
        compiled = target.lower(*abstract_args).compile()
        return analyze_compiled(name, compiled)
    except Exception as e:                          # noqa: BLE001
        return FunctionCost(name=name, error=f"{type(e).__name__}: {e}")


#: per-candidate cost reuse for batch explain (dstpu-tune): the same
#: (candidate key, function) pair is lowered once per process — the tuner
#: re-ranks, the bench A/B re-scores, and the CLI re-renders without
#: paying the XLA compile again
_COST_CACHE: Dict[str, FunctionCost] = {}


def clear_cost_cache() -> None:
    _COST_CACHE.clear()


def analyze_lowerable_cached(key: str, name: str, fn: Callable,
                             *abstract_args,
                             static_argnums=()) -> FunctionCost:
    """:func:`analyze_lowerable` behind the per-candidate cost cache.
    ``key`` must uniquely identify (function identity × abstract arg
    shapes) — the tuner uses the candidate's config key. Error records
    are cached too: a candidate that failed to lower once will fail the
    same way again, and re-lowering it per rank pass is the cost this
    cache exists to avoid."""
    hit = _COST_CACHE.get(key)
    if hit is not None:
        return hit
    fc = analyze_lowerable(name, fn, *abstract_args,
                           static_argnums=static_argnums)
    _COST_CACHE[key] = fc
    return fc


def roofline_from_cost(fc: FunctionCost, peaks: "Peaks") -> "Roofline":
    """FunctionCost → Roofline against ``peaks``, degrading gracefully:
    a record whose ``cost_analysis`` came back empty (some CPU builds)
    or that failed to lower scores as an all-zero roofline —
    ``bound='unknown'``, ``predicted_s == 0.0`` — instead of raising, so
    a mid-search candidate with no numbers is kept (ranked behind every
    known-bound candidate) and the sweep continues."""
    if fc is None or fc.error is not None or not fc.available:
        return Roofline(peak_flops=peaks.peak_flops, hbm_bw=peaks.hbm_bw,
                        ici_bw=peaks.ici_bw)
    return Roofline(flops=fc.flops, bytes=fc.bytes_accessed,
                    comm_bytes=fc.collective_bytes,
                    peak_flops=peaks.peak_flops, hbm_bw=peaks.hbm_bw,
                    ici_bw=peaks.ici_bw)


def batch_explain(items, peaks: "Peaks") -> List[Tuple[str, FunctionCost,
                                                       "Roofline"]]:
    """Batch-explain API for the autotuner: ``items`` is an iterable of
    ``(key, name, fn, abstract_args)``; each entry is lowered through the
    cost cache and scored with :func:`roofline_from_cost`. One bad
    candidate never aborts the batch — its record carries the error and
    an unknown-bound roofline."""
    out = []
    for key, name, fn, abstract_args in items:
        fc = analyze_lowerable_cached(key, name, fn, *abstract_args)
        out.append((key, fc, roofline_from_cost(fc, peaks)))
    return out


def analyze_fn(fn: Callable, *args, static_argnums=()) -> Dict[str, float]:
    """Compile ``fn`` for the current devices and return XLA cost
    analysis (the historical ``flops_profiler.analyze_fn`` API —
    re-exported from there)."""
    fc = analyze_lowerable("fn", fn, *args, static_argnums=static_argnums)
    out = {"flops": fc.flops, "bytes_accessed": fc.bytes_accessed}
    peak = fc.argument_bytes + fc.output_bytes + fc.temp_bytes
    if peak:
        out["peak_bytes"] = peak
    return out


def _cost(fn: Callable, *abstract_args) -> Dict[str, float]:
    """Historical ``flops_profiler._cost`` API: {'flops', 'bytes'}."""
    fc = analyze_lowerable("fn", fn, *abstract_args)
    return {"flops": fc.flops, "bytes": fc.bytes_accessed}


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

BOUND_CODES = {"unknown": 0, "compute": 1, "memory": 2, "comm": 3}


@dataclass
class Roofline:
    """max(compute, memory, comm) step-time model for one program.

    All inputs are per device: ``flops``/``bytes``/``comm_bytes`` from
    the compiled per-device program, peaks from the platform tables.
    Zero peaks (CPU, unknown chips) yield ``bound='unknown'`` and a zero
    prediction — callers must treat 0 as "no model", not "instant"."""
    flops: float = 0.0
    bytes: float = 0.0
    comm_bytes: float = 0.0
    peak_flops: float = 0.0
    hbm_bw: float = 0.0
    ici_bw: float = 0.0

    @property
    def compute_s(self) -> float:
        return self.flops / self.peak_flops if self.peak_flops else 0.0

    @property
    def memory_s(self) -> float:
        return self.bytes / self.hbm_bw if self.hbm_bw else 0.0

    @property
    def comm_s(self) -> float:
        return self.comm_bytes / self.ici_bw if self.ici_bw else 0.0

    @property
    def predicted_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.comm_s)

    @property
    def bound(self) -> str:
        p = self.predicted_s
        if p <= 0.0:
            return "unknown"
        if p == self.comm_s and self.comm_bytes > 0:
            return "comm"
        if p == self.memory_s and self.memory_s >= self.compute_s:
            return "memory"
        return "compute"

    def pct_of(self, measured_s: Optional[float]) -> Optional[float]:
        """Predicted/measured as a percentage — 100% means the step runs
        at the roofline; None when either side is missing."""
        if not measured_s or measured_s <= 0 or self.predicted_s <= 0:
            return None
        return 100.0 * self.predicted_s / measured_s

    def to_dict(self, measured_s: Optional[float] = None) -> Dict[str, Any]:
        return {"flops": self.flops, "bytes": self.bytes,
                "comm_bytes": self.comm_bytes,
                "peak_flops": self.peak_flops, "hbm_bw": self.hbm_bw,
                "ici_bw": self.ici_bw,
                "compute_ms": self.compute_s * 1e3,
                "memory_ms": self.memory_s * 1e3,
                "comm_ms": self.comm_s * 1e3,
                "predicted_ms": self.predicted_s * 1e3,
                "bound": self.bound,
                "pct_of_roofline": self.pct_of(measured_s)}


@dataclass
class Peaks:
    """Resolved peak numbers + identity of the (possibly hypothetical)
    target platform."""
    kind: str = "cpu"
    peak_flops: float = 0.0
    hbm_bw: float = 0.0
    ici_bw: float = 0.0
    capacity: float = 0.0


def _platform_lookup(table: Dict[str, float], name: str) -> float:
    name = name.lower()
    for key, val in table.items():
        if key in name:
            return val
    return 0.0


def resolve_peaks(device: Any = None, platform: Optional[str] = None,
                  peak_flops_override: Optional[float] = None,
                  hbm_bw_override: Optional[float] = None,
                  ici_bw_override: Optional[float] = None) -> Peaks:
    """Peak numbers for the roofline: from the live device by default,
    from the spec tables when ``platform`` names a chip ("v5e", "v5p",
    …) — so a CPU host can model a TPU target — with per-number
    overrides on top."""
    if platform:
        warn_unknown_platform(platform, context="resolve_peaks")
        p = Peaks(kind=platform,
                  peak_flops=_platform_lookup(PEAK_FLOPS_BF16, platform),
                  hbm_bw=_platform_lookup(PEAK_HBM_BW, platform),
                  ici_bw=_platform_lookup(PEAK_ICI_BW, platform),
                  capacity=_platform_lookup(HBM_CAPACITY, platform))
    else:
        kind = "cpu"
        try:
            import jax
            dev = device if device is not None else jax.devices()[0]
            kind = str(getattr(dev, "device_kind", dev.platform))
        except Exception:
            dev = None
        p = Peaks(kind=kind, peak_flops=peak_flops(device),
                  hbm_bw=peak_hbm_bw(device),
                  ici_bw=_platform_lookup(PEAK_ICI_BW, kind.lower()),
                  capacity=hbm_capacity(device))
    if peak_flops_override:
        p.peak_flops = float(peak_flops_override)
    if hbm_bw_override:
        p.hbm_bw = float(hbm_bw_override)
    if ici_bw_override:
        p.ici_bw = float(ici_bw_override)
    return p


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

@dataclass
class ExplainReport:
    """Structured explain output (JSON-able via :meth:`to_dict`)."""
    kind: str = "train"                       #: "train" | "serving"
    platform: str = "cpu"
    n_devices: int = 1
    peaks: Peaks = field(default_factory=Peaks)
    functions: List[FunctionCost] = field(default_factory=list)
    #: (name, shape, dtype, global bytes, sharding spec) per param leaf
    params: List[Tuple[str, str, str, float, str]] = field(
        default_factory=list)
    #: HBM budget components, bytes per device
    budget: Dict[str, float] = field(default_factory=dict)
    roofline: Roofline = field(default_factory=Roofline)
    measured_step_ms: Optional[float] = None
    warnings: List[str] = field(default_factory=list)

    @property
    def budget_total(self) -> float:
        return sum(self.budget.values())

    def to_dict(self) -> Dict[str, Any]:
        return {
            "kind": self.kind, "platform": self.platform,
            "n_devices": self.n_devices,
            "peaks": dict(self.peaks.__dict__),
            "functions": [f.to_dict() for f in self.functions],
            "params": [list(p) for p in self.params],
            "budget": dict(self.budget),
            "budget_total": self.budget_total,
            "roofline": self.roofline.to_dict(
                (self.measured_step_ms or 0) / 1e3 or None),
            "measured_step_ms": self.measured_step_ms,
            "warnings": list(self.warnings),
        }


def _fmt_bytes(b: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(b) < 1024.0 or unit == "TiB":
            return f"{b:.2f} {unit}" if unit != "B" else f"{b:.0f} B"
        b /= 1024.0
    return f"{b:.2f} TiB"


def _fmt_num(v: float) -> str:
    for thresh, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(v) >= thresh:
            return f"{v / thresh:.2f}{suffix}"
    return f"{v:.0f}"


def verdict_line(report: "ExplainReport") -> str:
    """The one-line roofline verdict (rendered last, grep-able)."""
    rl = report.roofline
    measured_s = (report.measured_step_ms or 0) / 1e3 or None
    if rl.bound == "unknown":
        line = (f"ROOFLINE: unknown bound — no peak numbers for "
                f"'{report.peaks.kind}' (pass --platform/--peak-flops to "
                f"model a target chip); static costs only")
        if report.measured_step_ms:
            line += f"; measured {report.measured_step_ms:.2f} ms/step"
        return line
    line = (f"ROOFLINE: {rl.bound}-bound — predicted step "
            f"{rl.predicted_s * 1e3:.2f} ms "
            f"(compute {rl.compute_s * 1e3:.2f}, "
            f"memory {rl.memory_s * 1e3:.2f}, "
            f"comm {rl.comm_s * 1e3:.2f})")
    pct = rl.pct_of(measured_s)
    if pct is not None:
        line += (f"; measured {report.measured_step_ms:.2f} ms → "
                 f"{pct:.1f}% of roofline")
    return line


def render(report: ExplainReport) -> str:
    """Plain-text explain report: HBM-budget table, per-function
    FLOPs/bytes table, sharding layout, roofline verdict."""
    out: List[str] = []
    p = report.peaks
    out.append(f"== dstpu-explain report ({report.kind}) ==")
    out.append(
        f"target: {p.kind} x{report.n_devices} "
        f"(peak {_fmt_num(p.peak_flops)}FLOP/s, "
        f"HBM {_fmt_num(p.hbm_bw)}B/s, ICI {_fmt_num(p.ici_bw)}B/s, "
        f"capacity {_fmt_bytes(p.capacity) if p.capacity else 'unknown'})")
    out.append("")
    out.append("HBM budget (bytes per device):")
    out.append(f"  {'component':<28}{'bytes':>14}")
    for name, b in report.budget.items():
        out.append(f"  {name:<28}{_fmt_bytes(b):>14}")
    total = report.budget_total
    cap_note = ""
    if p.capacity:
        cap_note = (f"  ({100.0 * total / p.capacity:.1f}% of "
                    f"{_fmt_bytes(p.capacity)})")
    out.append(f"  {'total':<28}{_fmt_bytes(total):>14}{cap_note}")
    out.append("")
    out.append("per-function costs (per device, from XLA cost analysis):")
    out.append(f"  {'function':<22}{'flops':>10}{'bytes':>12}"
               f"{'args':>12}{'temps':>12}{'collective':>12}")
    for f in report.functions:
        if f.error:
            out.append(f"  {f.name:<22}unavailable ({f.error[:60]})")
            continue
        note = "" if f.available else "  (cost_analysis empty)"
        out.append(
            f"  {f.name:<22}{_fmt_num(f.flops):>10}"
            f"{_fmt_bytes(f.bytes_accessed):>12}"
            f"{_fmt_bytes(f.argument_bytes):>12}"
            f"{_fmt_bytes(f.temp_bytes):>12}"
            f"{_fmt_bytes(f.collective_bytes):>12}{note}")
        if f.collective_stats:
            # per-op breakdown with instruction counts — under the
            # chunked-overlap path the count is the chunk fan-out
            parts = ", ".join(
                f"{op} {_fmt_bytes(s['bytes'])} in {int(s['count'])} op(s)"
                for op, s in sorted(f.collective_stats.items()))
            out.append(f"  {'':<22}collectives: {parts}")
    if report.params:
        out.append("")
        top = sorted(report.params, key=lambda r: -r[3])[:12]
        out.append(f"param layout (top {len(top)} of {len(report.params)} "
                   f"leaves by bytes; global bytes):")
        out.append(f"  {'param':<34}{'shape':<20}{'dtype':<10}"
                   f"{'bytes':>12}  sharding")
        for name, shape, dtype, nbytes, spec in top:
            out.append(f"  {name[:33]:<34}{shape:<20}{dtype:<10}"
                       f"{_fmt_bytes(nbytes):>12}  {spec}")
    for w in report.warnings:
        out.append("")
        out.append(f"WARNING: {w}")
    out.append("")
    out.append(verdict_line(report))
    return "\n".join(out)


def publish_gauges(report: ExplainReport, registry=None) -> None:
    """Publish the report's roofline as ``roofline/*`` gauges (the
    static counterparts of the measured ``train/*`` series)."""
    reg = registry if registry is not None else _registry
    rl = report.roofline
    reg.gauge("roofline/flops_per_step",
              help="predicted FLOPs per step per device").set(rl.flops)
    reg.gauge("roofline/bytes_per_step",
              help="predicted HBM bytes per step per device").set(rl.bytes)
    reg.gauge("roofline/comm_bytes_per_step",
              help="predicted collective bytes per step per device").set(
        rl.comm_bytes)
    reg.gauge("roofline/predicted_step_ms",
              help="roofline-predicted step time (0 = no model)").set(
        rl.predicted_s * 1e3)
    reg.gauge("roofline/bound_code",
              help="0 unknown, 1 compute, 2 memory, 3 comm").set(
        BOUND_CODES[rl.bound])
    reg.gauge("roofline/hbm_budget_bytes",
              help="predicted HBM footprint per device").set(
        report.budget_total)
    reg.gauge("roofline/hbm_capacity_bytes",
              help="device HBM capacity (0 = unknown)").set(
        report.peaks.capacity)
    pct = rl.pct_of((report.measured_step_ms or 0) / 1e3 or None)
    if pct is not None:
        reg.gauge("roofline/pct",
                  help="predicted/measured step time, percent").set(pct)


# ---------------------------------------------------------------------------
# engine / serving explain
# ---------------------------------------------------------------------------

def _leaf_name(path) -> str:
    import jax
    parts = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            parts.append(str(k.name))
        else:
            parts.append(str(k).strip(".[]'\""))
    return ".".join(parts) or "<root>"


def param_table(params) -> List[Tuple[str, str, str, float, str]]:
    """(name, shape, dtype, global bytes, sharding spec) per leaf."""
    import jax
    import numpy as np
    rows = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        nbytes = float(np.prod(shape, dtype=np.float64) *
                       np.dtype(dtype).itemsize) if dtype is not None else 0.0
        spec = ""
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            spec = str(getattr(sharding, "spec", sharding.__class__.__name__))
        rows.append((_leaf_name(path), str(list(shape)),
                     str(dtype), nbytes, spec))
    return rows


def _shard_bytes(tree) -> float:
    """Per-device bytes of a pytree: each leaf's shard size under its
    sharding (global size when unsharded/abstract)."""
    import jax
    import numpy as np
    total = 0.0
    for leaf in jax.tree.leaves(tree):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:
            continue
        sharding = getattr(leaf, "sharding", None)
        try:
            if sharding is not None:
                shape = sharding.shard_shape(shape)
        except Exception:
            pass
        total += float(np.prod(shape, dtype=np.float64) *
                       np.dtype(dtype).itemsize)
    return total


def static_budget(engine) -> Dict[str, float]:
    """The compile-free part of the HBM budget (bytes per device):
    params / optimizer state / loss-scale shard sizes, plus — when the
    chunked ZeRO-3 overlap path is armed — the transient footprint of
    in-flight gathered chunks (prefetch+1 chunks live at once; they are
    freed after use but the budget must cover the peak). Pure metadata —
    never syncs the device."""
    budget: Dict[str, float] = {}
    params = getattr(engine, "params", None)
    if params is not None:
        budget["params"] = _shard_bytes(params)
    opt_state = getattr(engine, "opt_state", None)
    if opt_state:
        budget["optimizer_state"] = _shard_bytes(opt_state)
    scaler = getattr(engine, "loss_scale_state", None)
    if scaler is not None:
        budget["loss_scale_state"] = _shard_bytes(scaler)
    plan = getattr(engine, "_overlap_plan", None)
    if plan is not None:
        try:
            budget["overlap_gathered_chunks"] = float(plan.transient_bytes())
        except Exception:
            pass
    return budget


def _abstract_train_args(engine, sample_batch=None):
    """Abstract argument tuple for the engine's fused step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    gas = int(engine.config.gradient_accumulation_steps)
    if sample_batch is None:
        micro = max(1, int(engine.config.train_batch_size) // gas)
        tps = int(getattr(engine.model, "tokens_per_sample", None) or 128)
        sample_batch = {"input_ids": jax.ShapeDtypeStruct(
            (micro, tps), np.int32)}
    else:
        sample_batch = abstractify(sample_batch)
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((gas,) + tuple(s.shape), s.dtype),
        sample_batch)
    try:
        # shard the abstract batch the way _place_stacked_batch would —
        # an unsharded (replicated) batch lowers to a program with no
        # grad all-reduce and gas*dp times the per-device flops, which
        # would poison both sides of the roofline
        from deepspeed_tpu.parallel.mesh import ZERO_AXES
        sp = engine.mesh.shape.get("seq", 1) > 1

        def shard(s):
            entries = [None, ZERO_AXES] + [None] * (len(s.shape) - 2)
            if sp and len(s.shape) >= 3:
                entries[2] = "seq"
            return jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=jax.sharding.NamedSharding(
                    engine.mesh, jax.sharding.PartitionSpec(*entries)))
        stacked = jax.tree.map(shard, stacked)
    except Exception:
        pass
    return (abstractify(engine.params),
            abstractify(engine.opt_state),
            abstractify(engine.loss_scale_state),
            stacked,
            jax.ShapeDtypeStruct((), jnp.int32),
            abstractify(jax.random.PRNGKey(0)))


def explain_engine(engine, measured_step_ms: Optional[float] = None,
                   sample_batch=None, platform: Optional[str] = None,
                   peak_flops_override: Optional[float] = None,
                   hbm_bw_override: Optional[float] = None,
                   ici_bw_override: Optional[float] = None
                   ) -> ExplainReport:
    """Lower the engine's jitted train step abstractly and build the
    full explain report. Costs one XLA compile of the step program (the
    executable is dropped afterwards); nothing runs on the device.

    Engine modes without a lowerable fused step (host-offload optimizer,
    1-bit, ZeRO++ flat storage) degrade to the static budget with the
    step function marked unavailable."""
    import jax
    tcfg = getattr(engine.config, "telemetry", None)
    peaks = resolve_peaks(
        platform=platform,
        peak_flops_override=peak_flops_override or
        (getattr(tcfg, "peak_flops_override", None) if not platform else None),
        hbm_bw_override=hbm_bw_override or
        (getattr(tcfg, "peak_hbm_bw_override", None) if not platform
         else None),
        ici_bw_override=ici_bw_override)
    report = ExplainReport(kind="train", platform=peaks.kind,
                           n_devices=jax.device_count(), peaks=peaks,
                           measured_step_ms=measured_step_ms)
    report.budget.update(static_budget(engine))
    try:
        report.params = param_table(engine.params)
    except Exception:
        pass

    fused = getattr(engine, "_fused_step", None)
    if fused is None:
        report.functions.append(FunctionCost(
            name="train_step",
            error="no fused step in this engine mode (host-offload/1-bit "
                  "paths run partly on the host)"))
    else:
        try:
            args = _abstract_train_args(engine, sample_batch)
        except Exception as e:                       # noqa: BLE001
            args = None
            report.functions.append(FunctionCost(
                name="train_step", error=f"{type(e).__name__}: {e}"))
        if args is not None:
            fc = analyze_lowerable("train_step", fused, *args)
            report.functions.append(fc)
            if fc.error is None:
                report.budget["step_temporaries"] = fc.temp_bytes
                if fc.generated_code_bytes:
                    report.budget["generated_code"] = \
                        fc.generated_code_bytes
    step = next((f for f in report.functions if f.name == "train_step"),
                None)
    if step is not None and step.error is None:
        report.roofline = Roofline(
            flops=step.flops, bytes=step.bytes_accessed,
            comm_bytes=step.collective_bytes,
            peak_flops=peaks.peak_flops, hbm_bw=peaks.hbm_bw,
            ici_bw=peaks.ici_bw)
        if not step.available:
            report.warnings.append(
                "cost_analysis returned no numbers on this backend — "
                "FLOPs/bytes read 0; the byte budget above is still exact")
    if peaks.capacity and report.budget_total > peaks.capacity:
        report.warnings.append(
            f"predicted HBM footprint {_fmt_bytes(report.budget_total)} "
            f"EXCEEDS device capacity {_fmt_bytes(peaks.capacity)} — "
            f"expect OOM; shard further (zero stage / tensor parallel), "
            f"shrink the batch, or offload")
    last_report["train"] = report.to_dict()
    return report


def explain_serving(engine, mode=("argmax",),
                    platform: Optional[str] = None) -> Dict[str, Any]:
    """Cost records for the serving engine's prefill and decode bucket
    programs (lowered abstractly over the engine's real packed-input
    layout). Returns ``{"prefill": {...}, "decode": {...}}`` where each
    record carries the :class:`FunctionCost` fields plus
    ``predicted_s`` — the roofline step-time prediction the frontend's
    SLO admission consumes (0.0 when no peak numbers exist)."""
    import jax
    import numpy as np
    from deepspeed_tpu.inference.engine_v2 import _bucket
    cfg = engine.config
    peaks = resolve_peaks(platform=platform)
    nb = _bucket(int(cfg.max_sequences))
    mb = engine.mb
    records: Dict[str, Any] = {}
    aparams = abstractify(engine.params)
    aarena = abstractify(engine.arena)
    arng = abstractify(jax.random.PRNGKey(0))
    for label, cb, fresh in (("prefill", int(cfg.prefill_chunk), "fresh"),
                             ("decode", 1, False)):
        packed = jax.ShapeDtypeStruct((engine._packed_len(nb, cb),),
                                      np.int32)
        try:
            jitted = engine._step_fn(nb, cb, mode, fresh=fresh)
            fc = analyze_lowerable(f"serving_{label}", jitted,
                                   aparams, aarena, packed, arng)
        except Exception as e:                       # noqa: BLE001
            fc = FunctionCost(name=f"serving_{label}",
                              error=f"{type(e).__name__}: {e}")
        rl = Roofline(flops=fc.flops, bytes=fc.bytes_accessed,
                      comm_bytes=fc.collective_bytes,
                      peak_flops=peaks.peak_flops, hbm_bw=peaks.hbm_bw,
                      ici_bw=peaks.ici_bw)
        rec = fc.to_dict()
        rec.update(n_bucket=nb, chunk=cb,
                   predicted_s=rl.predicted_s, bound=rl.bound)
        records[label] = rec
    records["platform"] = peaks.kind
    last_report["serving"] = records
    _registry.gauge(
        "roofline/prefill_predicted_ms",
        help="roofline-predicted serving prefill step (0 = no model)").set(
        records["prefill"]["predicted_s"] * 1e3)
    _registry.gauge(
        "roofline/decode_predicted_ms",
        help="roofline-predicted serving decode step (0 = no model)").set(
        records["decode"]["predicted_s"] * 1e3)
    return records


def startup_budget(engine, log=None) -> Dict[str, float]:
    """The always-on, compile-free engine-init budget check: log the
    static HBM budget, publish the gauges, and warn LOUDLY when the
    static footprint alone exceeds device capacity."""
    from deepspeed_tpu.utils.logging import log_dist, logger
    budget = static_budget(engine)
    total = sum(budget.values())
    cap = hbm_capacity()
    reg = _registry
    reg.gauge("roofline/hbm_budget_bytes",
              help="predicted HBM footprint per device").set(total)
    reg.gauge("roofline/hbm_capacity_bytes",
              help="device HBM capacity (0 = unknown)").set(cap)
    parts = ", ".join(f"{k}={_fmt_bytes(v)}" for k, v in budget.items())
    (log or log_dist)(
        f"HBM budget: {parts}; total {_fmt_bytes(total)}"
        + (f" of {_fmt_bytes(cap)} capacity "
           f"({100.0 * total / cap:.1f}%)" if cap else ""))
    if cap and total > cap:
        logger.error(
            f"HBM BUDGET EXCEEDED: static footprint {_fmt_bytes(total)} "
            f"> device capacity {_fmt_bytes(cap)} — params + optimizer "
            f"state alone do not fit; expect OOM before the first step "
            f"(shard further, shrink the model, or offload)")
    return budget


# ---------------------------------------------------------------------------
# CLI — bin/dstpu-explain
# ---------------------------------------------------------------------------

def _build_engine(args):
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.llama import llama3_config
    if args.config:
        with open(args.config) as fh:
            config = json.load(fh)
    else:
        config = {}
    config.setdefault("train_micro_batch_size_per_gpu",
                      max(1, args.batch // len(jax.devices())))
    config.setdefault("steps_per_print", 1000)
    from deepspeed_tpu.parallel.mesh import has_mesh
    if not has_mesh():
        ds.build_mesh(data=len(jax.devices()))
    model = llama3_config(args.size, max_seq_len=args.seq,
                          tie_embeddings=True)
    engine, *_ = ds.initialize(model=model, config=config,
                               rng=jax.random.PRNGKey(0))
    return engine, model


def _measure_steps(engine, model, n: int) -> float:
    """Run ``n`` real steps and return the best step time in ms (min —
    the compile lands on step 1, warmed by an extra throwaway step)."""
    import time

    import jax
    import numpy as np
    gb = int(engine.config.train_batch_size)
    seq = int(model.max_seq_len)
    rng = np.random.default_rng(0)
    batch = jax.device_put({"input_ids": rng.integers(
        0, model.vocab_size, size=(gb, seq), dtype=np.int32)})
    float(engine.train_batch(iter([batch])))          # compile + warm
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        float(engine.train_batch(iter([batch])))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="dstpu-explain",
        description="Compile-time explain: lower the engine's jitted "
                    "step, read back XLA cost/memory analysis, and print "
                    "the HBM budget + roofline report. Works on a "
                    "CPU-only host (lowering is abstract); --platform "
                    "models a target chip's peaks.")
    ap.add_argument("--config", default=None,
                    help="DeepSpeedTPUConfig JSON (default: minimal "
                         "config like examples/pretrain.py)")
    ap.add_argument("--size", default="tiny",
                    help="llama3 preset (tiny/350m/1b/8b)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--serving", action="store_true",
                    help="also lower the serving prefill/decode bucket "
                         "programs (ragged engine over the same model "
                         "size)")
    ap.add_argument("--platform", default=None,
                    help="model a target chip's peaks from any host "
                         "(v2/v3/v4/v5e/v5p/v6e)")
    ap.add_argument("--peak-flops", type=float, default=None,
                    help="override peak FLOPs/s per chip")
    ap.add_argument("--hbm-bw", type=float, default=None,
                    help="override peak HBM bytes/s per chip")
    ap.add_argument("--ici-bw", type=float, default=None,
                    help="override peak interconnect bytes/s per chip")
    ap.add_argument("--measured-ms", type=float, default=None,
                    help="a measured step time (ms) to compare against "
                         "the prediction (%% of roofline)")
    ap.add_argument("--measure", type=int, default=0, metavar="N",
                    help="run N real steps and use the best as the "
                         "measured step time")
    ap.add_argument("--json", action="store_true",
                    help="emit the structured report as JSON")
    args = ap.parse_args(argv)

    engine, model = _build_engine(args)
    measured = args.measured_ms
    if args.measure:
        measured = _measure_steps(engine, model, args.measure)
    report = explain_engine(engine, measured_step_ms=measured,
                            platform=args.platform,
                            peak_flops_override=args.peak_flops,
                            hbm_bw_override=args.hbm_bw,
                            ici_bw_override=args.ici_bw)
    publish_gauges(report)
    serving_records = None
    if args.serving:
        from deepspeed_tpu.inference.engine_v2 import \
            RaggedInferenceEngineTPU
        seq_cap = max(64, args.seq)
        eng = RaggedInferenceEngineTPU(
            model, {"dtype": "float32", "num_blocks": 64,
                    "block_size": 16, "max_seq_len": seq_cap,
                    "prefill_chunk": 32, "max_sequences": 4})
        serving_records = explain_serving(eng, platform=args.platform)
    if args.json:
        doc = report.to_dict()
        if serving_records is not None:
            doc["serving"] = serving_records
        print(json.dumps(doc, indent=1, default=repr))
    else:
        print(render(report))
        if serving_records is not None:
            print()
            print("serving cost records:")
            for label in ("prefill", "decode"):
                r = serving_records[label]
                if r.get("error"):
                    print(f"  {label:<10}unavailable ({r['error'][:60]})")
                else:
                    print(f"  {label:<10}nb={r['n_bucket']} "
                          f"chunk={r['chunk']} "
                          f"flops={_fmt_num(r['flops'])} "
                          f"bytes={_fmt_bytes(r['bytes_accessed'])} "
                          f"predicted={r['predicted_s'] * 1e3:.3f} ms "
                          f"({r['bound']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
