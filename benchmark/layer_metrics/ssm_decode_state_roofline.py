"""The DECODE programs' state pass against the HBM peak: the state bytes
the traced ``serve_decode*`` launches must read and write
(``lib/ssm_work.state_bytes`` of the ``state_rows`` arguments of their
``serving/dispatch`` spans: every row's state in every state-space layer,
once in and once out) over the device self time under the ``ssm_scan`` and
``ssm_state`` scopes IN THOSE PROGRAMS on device 0 — by SCOPE, not by a
kernel's name, so the same work whatever implements it (the XLA slot-order
recurrence today). ``ssm_scan_roofline`` mixes this pass with the split
step's chunk group, whose time has almost no bytes to its name; this one
bounds the one-token pass alone. A program without state-space layers, the
scopes or the span arguments gives nothing."""

from benchmark.lib import ssm_work
from benchmark.trace import scopes

LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SCOPES = ("ssm_scan", "ssm_state")


def read(run):
    rng = run.facts.get("traced_step_range")
    model = run.facts.get("model")
    if run.peaks is None or not rng or \
            not getattr(model, "recurrent", False):
        return None
    steps = run.program_spans("serving/engine_step")
    if len(steps) != len(run.facts.get("steps", [])):
        return None
    events = scopes.program_events(run)
    rows = sum(e["args"]["state_rows"] for step in steps[rng[0]:rng[1]]
               for e in scopes.children(events, step, "serving/dispatch")
               if e.get("args", {}).get("program") == "decode"
               and "state_rows" in e["args"])
    dev = scopes.analysis(run)["device"]
    if dev is None or not rows:
        return None
    ns = sum(rec[0] for (program, _instr), rec in dev["ops"].items()
             if program.startswith("serve_decode") and rec[1] in SCOPES)
    if not ns:
        return None
    return run.flops.roofline_share(
        0.0, ssm_work.state_bytes(model, rows), ns / 1e9, run.peaks)
