"""The gated delta rule's share of its roofline in the traced launches: the
larger of the state bytes the launches must read and write over the HBM
peak and the rule's least FLOPs over the bf16 peak
(``lib/delta_rule_work``, from the ``state_rows`` and ``tokens`` arguments
of the program's ``serving/dispatch`` spans inside the traced
``serving/engine_step`` spans: every fed token is one step of the rule,
whichever form took it) through ``flops.roofline_share``, over the device
self time under the ``delta_rule`` and ``ssm_state`` scopes on device 0 — by
SCOPE, not by a kernel's name, so the same work whatever implements it
(``ssm_scan_roofline``'s rule; that reader's numerator counts the Mamba-2
kind). A program without delta-rule layers, the scopes or the counters
gives nothing."""

from benchmark.lib import delta_rule_work
from benchmark.trace import scopes

LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SCOPES = ("delta_rule", "ssm_state")


def read(run):
    rng = run.facts.get("traced_step_range")
    model = run.facts.get("model")
    if run.peaks is None or not rng or \
            not getattr(model, "delta_rule", False):
        return None
    steps = run.program_spans("serving/engine_step")
    if len(steps) != len(run.facts.get("steps", [])):
        return None
    events = scopes.program_events(run)
    rows = tokens = 0
    for step in steps[rng[0]:rng[1]]:
        for e in scopes.children(events, step, "serving/dispatch"):
            args = e.get("args", {})
            if "state_rows" not in args:
                continue
            rows += args["state_rows"]
            tokens += args["tokens"]
    dev = scopes.analysis(run)["device"]
    if dev is None or not rows:
        return None
    ns = sum(v for (_p, s, _k), v in dev["rows"].items() if s in SCOPES)
    if not ns:
        return None
    return run.flops.roofline_share(
        delta_rule_work.rule_flops(model, tokens),
        delta_rule_work.state_bytes(model, rows), ns / 1e9, run.peaks)
