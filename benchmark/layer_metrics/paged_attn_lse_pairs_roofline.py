"""The paged history kernel's share of its roofline in the traced SPLIT
steps where the rows carry chunks of LIVE queries: the larger of its FLOPs
over the bf16 peak (``lib/paged_pairs_work.history_flops``: 4 x head
width x query heads a live (query, key) pair of the history, from the
``attn_pairs_*`` arguments of the program's ``serving/dispatch`` spans
inside the traced ``serving/engine_step`` spans) and its K and V bytes
over the HBM peak (``lib/paged_hist_work.history_bytes``, as
``paged_attn_lse_roofline``), over the summed device time of the events
named ``paged_attn_lse*`` on device 0. ``paged_attn_lse_roofline`` counts
the bytes alone: right where a history row carries one live query, low by
the arithmetic intensity where it carries a chunk. A program whose spans
carry no ``attn_pairs_*`` (before PR 37) gives nothing to read."""

import re

from benchmark.lib import paged_hist_work, paged_pairs_work
from benchmark.trace import scopes

LAYER = "kernels"
MOVES = "serve_tokens_per_s"
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
    full = window = pairs_full = pairs_window = 0
    for step in steps[rng[0]:rng[1]]:
        for e in scopes.children(events, step, "serving/dispatch"):
            args = e.get("args", {})
            pairs = paged_pairs_work.history_pairs(args)
            if args.get("program") != "split" or pairs is None or \
                    "kv_tokens_window_live" not in args:
                continue
            full += args["kv_tokens_full"] - args["tokens"]
            window += args["kv_tokens_window_live"] - args["tokens"]
            pairs_full += pairs[0]
            pairs_window += pairs[1]
    seconds, count = run.reduce.matching_seconds(
        run.trace, lambda ev: bool(KERNEL.search(run.reduce.op_name(ev))))
    if count == 0 or not pairs_full + pairs_window:
        return None
    return run.flops.roofline_share(
        paged_pairs_work.history_flops(model, pairs_full, pairs_window),
        paged_hist_work.history_bytes(model, full, window), seconds,
        run.peaks)
