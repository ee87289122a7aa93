#!/usr/bin/env python
"""Metric-name lint: every ``registry.counter/gauge/histogram(...)`` call
site with a literal name must follow the ``area/name`` convention, and no
name may be requested as two different metric types (the registry raises
``TypeError`` at runtime on such a collision — this catches it in CI,
before the colliding code paths happen to run in one process).

Rules (docs/observability.md "metric catalog"):
- names are ``area/name`` — at least two ``/``-separated segments;
- segments are lowercase ``[a-z0-9_]`` (f-string ``{placeholder}``
  segments are allowed and normalized to ``{}``);
- one name ↔ one metric type across the whole tree;
- the leading area segment must come from ``KNOWN_AREAS`` (the catalog's
  table of contents) — a typo'd area (``rooflne/``) otherwise publishes
  silently into a namespace no dashboard watches.

Only literal string / f-string first arguments are checked; call sites
passing a variable (e.g. ``gauge(name)`` in a generic flusher) are
skipped — their names are produced by checked call sites upstream.

The tool also lints the FAULT CATALOG: every injectable fault kind
declared in ``resilience/faults.py`` (the module-level ``*_KINDS``
tuples the FaultInjector validates plans against) must be documented in
``docs/resilience.md`` — an undocumented kind is a chaos drill nobody
can discover or interpret from the runbook.

And the SPAN CATALOG: every literal span name the serving tier
(``deepspeed_tpu/serving``: frontend, router, handoff, kvtier) emits via
``span``/``instant``/``complete`` must appear in
``docs/observability.md`` — request-scoped traces are only as readable
as their span names are documented.

Usage: ``python tools/check_metric_names.py [root]`` → exit 0 clean,
exit 1 with one line per violation. Invoked from the tier-1 suite
(tests/test_diagnostics.py) so a bad name fails CI.
"""

import ast
import os
import re
import sys
from typing import Dict, List, Optional, Set, Tuple

METRIC_METHODS = ("counter", "gauge", "histogram")
_SEGMENT = re.compile(r"^(?:[a-z0-9_]+|\{\})$")

#: the metric catalog's areas (docs/observability.md) — extend here AND
#: in the docs when a new subsystem starts publishing
KNOWN_AREAS = ("anomaly", "autoscale", "comm", "compile", "dispatch",
               "fleet", "goodput", "handoff", "health", "kvtier", "mem",
               "overlap", "resilience", "roofline", "router", "serving",
               "setup", "slo", "trace", "train", "tune")

#: span-emitting methods (Tracer / ReqTrace) linted by the span-catalog
#: check below
SPAN_METHODS = ("span", "instant", "complete")


def _literal_name(node: ast.AST) -> Optional[str]:
    """First-arg metric name, with f-string placeholders normalized to
    ``{}``; None when the arg isn't a (partially) literal string."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            elif isinstance(v, ast.FormattedValue):
                parts.append("{}")
        return "".join(parts)
    return None


def collect_sites(root: str) -> List[Tuple[str, int, str, str]]:
    """(file, line, metric_type, normalized_name) for every literal-name
    registry call site under ``root``."""
    sites = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", ".git")]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as fh:
                try:
                    tree = ast.parse(fh.read(), filename=path)
                except SyntaxError as e:
                    print(f"{path}: unparseable: {e}", file=sys.stderr)
                    continue
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call) and
                        isinstance(node.func, ast.Attribute) and
                        node.func.attr in METRIC_METHODS and node.args):
                    continue
                name = _literal_name(node.args[0])
                if name is None:
                    continue
                sites.append((os.path.relpath(path, root), node.lineno,
                              node.func.attr, name))
    return sites


def check(sites) -> List[str]:
    errors = []
    types_by_name: Dict[str, Set[str]] = {}
    first_site: Dict[str, Tuple[str, int, str]] = {}
    for path, line, mtype, name in sites:
        segments = name.split("/")
        if len(segments) < 2:
            errors.append(f"{path}:{line}: metric {name!r} violates the "
                          f"area/name convention (no '/' namespace)")
        bad = [s for s in segments if not _SEGMENT.match(s)]
        if bad:
            errors.append(f"{path}:{line}: metric {name!r} has invalid "
                          f"segment(s) {bad} (want lowercase "
                          f"[a-z0-9_] or a placeholder)")
        elif len(segments) >= 2 and segments[0] not in KNOWN_AREAS \
                and segments[0] != "{}":
            errors.append(f"{path}:{line}: metric {name!r} uses unknown "
                          f"area {segments[0]!r} (known: "
                          f"{', '.join(KNOWN_AREAS)}; extend KNOWN_AREAS "
                          f"+ the docs catalog for a new subsystem)")
        types_by_name.setdefault(name, set()).add(mtype)
        first_site.setdefault(name, (path, line, mtype))
        if len(types_by_name[name]) > 1:
            fp, fl, ft = first_site[name]
            errors.append(f"{path}:{line}: metric {name!r} requested as "
                          f"{mtype} but first seen as {ft} at {fp}:{fl} "
                          f"(the registry raises TypeError at runtime)")
    return errors


def collect_fault_kinds(pkg_root: str) -> List[str]:
    """Every fault kind declared in resilience/faults.py: the string
    elements of module-level ``*_KINDS`` tuple assignments (the same
    tuples the FaultInjector validates plan entries against)."""
    path = os.path.join(pkg_root, "resilience", "faults.py")
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=path)
    kinds: List[str] = []
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id.endswith("_KINDS")):
            continue
        for sub in ast.walk(node.value):
            if isinstance(sub, ast.Constant) and \
                    isinstance(sub.value, str):
                kinds.append(sub.value)
    # ADVISORY_KINDS concatenates the other tuples — dedup, keep order
    return list(dict.fromkeys(kinds))


def check_fault_kinds(pkg_root: str) -> List[str]:
    """Every declared fault kind must appear in docs/resilience.md."""
    kinds = collect_fault_kinds(pkg_root)
    if not kinds:
        return []
    doc_path = os.path.join(os.path.dirname(pkg_root), "docs",
                            "resilience.md")
    if not os.path.exists(doc_path):
        return [f"docs/resilience.md missing but resilience/faults.py "
                f"declares {len(kinds)} fault kinds"]
    with open(doc_path, encoding="utf-8") as fh:
        doc = fh.read()
    return [f"resilience/faults.py declares fault kind {k!r} but "
            f"docs/resilience.md never mentions it (document the drill "
            f"in the fault catalog)"
            for k in kinds if k not in doc]


def collect_goodput_categories(pkg_root: str) -> List[str]:
    """Every ledger category declared in telemetry/goodput.py: the
    string elements of module-level ``*CATEGORIES`` tuple assignments
    (the taxonomy the attribution sweep classifies into)."""
    path = os.path.join(pkg_root, "telemetry", "goodput.py")
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=path)
    cats: List[str] = []
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id.endswith("CATEGORIES")):
            continue
        for sub in ast.walk(node.value):
            if isinstance(sub, ast.Constant) and \
                    isinstance(sub.value, str):
                cats.append(sub.value)
    return list(dict.fromkeys(cats))


def check_goodput_categories(pkg_root: str) -> List[str]:
    """Every ledger category must appear in docs/observability.md —
    mirrors the fault-catalog check: an undocumented badput category is
    an attribution nobody can act on from the runbook."""
    cats = collect_goodput_categories(pkg_root)
    if not cats:
        return []
    doc_path = os.path.join(os.path.dirname(pkg_root), "docs",
                            "observability.md")
    if not os.path.exists(doc_path):
        return [f"docs/observability.md missing but telemetry/goodput.py "
                f"declares {len(cats)} ledger categories"]
    with open(doc_path, encoding="utf-8") as fh:
        doc = fh.read()
    return [f"telemetry/goodput.py declares ledger category {c!r} but "
            f"docs/observability.md never mentions it (document it in "
            f"the goodput-ledger taxonomy)"
            for c in cats if c not in doc]


def collect_health_stats(pkg_root: str) -> List[str]:
    """Every model-health gauge name declared in telemetry/health.py:
    the string elements of module-level ``*_STATS`` tuple assignments
    (the catalog ``HealthMonitor.publish`` emits from)."""
    path = os.path.join(pkg_root, "telemetry", "health.py")
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=path)
    stats: List[str] = []
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id.endswith("_STATS")):
            continue
        for sub in ast.walk(node.value):
            if isinstance(sub, ast.Constant) and \
                    isinstance(sub.value, str):
                stats.append(sub.value)
    return list(dict.fromkeys(stats))


def check_health_stats(pkg_root: str) -> List[str]:
    """Every declared health stat must appear in docs/observability.md —
    mirrors the goodput-category check: an undocumented health gauge is
    a training-dynamics signal nobody can interpret from the runbook."""
    stats = collect_health_stats(pkg_root)
    if not stats:
        return []
    doc_path = os.path.join(os.path.dirname(pkg_root), "docs",
                            "observability.md")
    if not os.path.exists(doc_path):
        return [f"docs/observability.md missing but telemetry/health.py "
                f"declares {len(stats)} health stats"]
    with open(doc_path, encoding="utf-8") as fh:
        doc = fh.read()
    return [f"telemetry/health.py declares health stat {s!r} but "
            f"docs/observability.md never mentions it (document it in "
            f"the model-health catalog)"
            for s in stats if s not in doc]


def collect_span_names(pkg_root: str) -> List[Tuple[str, int, str]]:
    """(file, line, span_name) for every literal-name ``span`` /
    ``instant`` / ``complete`` call site under the serving tier
    (``deepspeed_tpu/serving``: frontend, router, handoff, kvtier) —
    the spans that appear in request-scoped distributed traces."""
    sites: List[Tuple[str, int, str]] = []
    root = os.path.join(pkg_root, "serving")
    if not os.path.isdir(root):
        return sites
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", ".git")]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as fh:
                try:
                    tree = ast.parse(fh.read(), filename=path)
                except SyntaxError:
                    continue                  # reported by collect_sites
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call) and
                        isinstance(node.func, ast.Attribute) and
                        node.func.attr in SPAN_METHODS and node.args):
                    continue
                name = _literal_name(node.args[0])
                if name is None or "{}" in name or "/" not in name:
                    continue
                sites.append((os.path.relpath(path, pkg_root),
                              node.lineno, name))
    return sites


def check_span_names(pkg_root: str) -> List[str]:
    """Every span name the serving tier emits must appear in
    docs/observability.md (the span catalog) — mirrors the fault-kind
    check: an undocumented span is a trace nobody can interpret."""
    sites = collect_span_names(pkg_root)
    if not sites:
        return []
    doc_path = os.path.join(os.path.dirname(pkg_root), "docs",
                            "observability.md")
    if not os.path.exists(doc_path):
        return [f"docs/observability.md missing but the serving tier "
                f"emits {len(sites)} literal-name spans"]
    with open(doc_path, encoding="utf-8") as fh:
        doc = fh.read()
    errors = []
    seen: Set[str] = set()
    for path, line, name in sites:
        if name in doc or name in seen:
            continue
        seen.add(name)
        errors.append(f"{path}:{line}: span {name!r} emitted by the "
                      f"serving tier but docs/observability.md never "
                      f"mentions it (add it to the span catalog)")
    return errors


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "deepspeed_tpu")
    sites = collect_sites(root)
    errors = check(sites)
    errors += check_fault_kinds(root)
    errors += check_span_names(root)
    errors += check_goodput_categories(root)
    errors += check_health_stats(root)
    for e in errors:
        print(e)
    if not errors:
        spans = {name for _, _, name in collect_span_names(root)}
        print(f"check_metric_names: {len(sites)} literal call sites OK; "
              f"{len(collect_fault_kinds(root))} fault kinds documented; "
              f"{len(spans)} span names documented; "
              f"{len(collect_goodput_categories(root))} goodput "
              f"categories documented; "
              f"{len(collect_health_stats(root))} health stats "
              f"documented")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
