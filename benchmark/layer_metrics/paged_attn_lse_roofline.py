"""The paged history kernel's share of its roofline in the traced SPLIT
steps: the K and V bytes their history attention must read
(``lib/paged_hist_work.history_bytes``, from the ``kv_tokens_full`` /
``kv_tokens_window_live`` / ``tokens`` arguments of the program's
``serving/dispatch`` spans inside the traced ``serving/engine_step``
spans; memory-bound) over the HBM peak, over the summed device time of the
events named ``paged_attn_lse*`` on device 0."""

import re

from benchmark.lib import paged_hist_work
from benchmark.trace import scopes

LAYER = "kernels"
MOVES = "itl_p95_ms"
KERNEL = re.compile(r"^paged_attn_lse")


def read(run):
    rng = run.facts.get("traced_step_range")
    model = run.facts.get("model")
    if run.trace is None or run.peaks is None or not rng or \
            not getattr(model, "layer_kinds", None):
        return None
    steps = run.program_spans("serving/engine_step")
    if len(steps) != len(run.facts.get("steps", [])):
        return None
    events = scopes.program_events(run)
    full = window = 0
    for step in steps[rng[0]:rng[1]]:
        for e in scopes.children(events, step, "serving/dispatch"):
            args = e.get("args", {})
            if args.get("program") != "split" or \
                    "kv_tokens_window_live" not in args:
                continue
            full += args["kv_tokens_full"] - args["tokens"]
            window += args["kv_tokens_window_live"] - args["tokens"]
    seconds, count = run.reduce.matching_seconds(
        run.trace, lambda ev: bool(KERNEL.search(run.reduce.op_name(ev))))
    if count == 0 or not full + window:
        return None
    return run.flops.roofline_share(
        0.0, paged_hist_work.history_bytes(model, full, window), seconds,
        run.peaks)
