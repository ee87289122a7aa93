"""The expert matmuls' share of their roofline in the traced DECODE steps:
the bytes those steps' expert matmuls must read
(``lib/expert_matmul_work.decode_step_bytes``: the experts the step's rows
are expected to hit x three matrices, plus the routed rows in and out;
memory-bound at decode shapes) over the HBM peak, over the device self
time of the operations under the ``moe_experts`` scope in the
``serve_decode*`` programs on device 0. The traced decode steps are the
program's ``serving/engine_step`` spans (``program`` = decode, ``batch`` =
rows) of the runner's traced step range.

The numerator is an EXPECTATION, not a count (13.9 of the 16 held experts
at 64 rows under uniform routing): the routing stays on the device and the
program reads every held expert whatever it is. A program that skips the
experts no row picked would be judged against that assumption and could
read over 100% on a step that hit fewer: before any PR claims on this
metric it needs a device-side count of the experts hit (a counter the
program fetches with the step's tokens) in place of the expectation."""

from benchmark.lib import expert_matmul_work
from benchmark.trace import scopes

LAYER = "kernels"
MOVES = "serve_tokens_per_s"


def read(run):
    rng = run.facts.get("traced_step_range")
    model = run.facts.get("model")
    if run.peaks is None or not rng or \
            not getattr(model, "layer_sparse", None):
        return None
    spans = run.program_spans("serving/engine_step")
    if len(spans) != len(run.facts.get("steps", [])):
        return None
    rows = [e["args"]["batch"] for e in spans[rng[0]:rng[1]]
            if e.get("args", {}).get("program") == "decode"]
    dev = scopes.analysis(run)["device"]
    if dev is None or not rows:
        return None
    ns = sum(rec[0] for (program, _instr), rec in dev["ops"].items()
             if program.startswith("serve_decode") and
             rec[1] == "moe_experts")
    if not ns:
        return None
    nbytes = sum(expert_matmul_work.decode_step_bytes(model, r)
                 for r in rows)
    return run.flops.roofline_share(0.0, nbytes, ns / 1e9, run.peaks)
