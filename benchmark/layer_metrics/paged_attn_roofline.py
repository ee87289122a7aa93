"""The paged decode-attention kernel's share of its roofline: the K and V
bytes the decode rows of the traced decode-only steps must read
(``lib/flops.paged_kv_bytes``: every cached token of every row once per
layer, memory-bound) over the HBM peak, over the summed device time of the
events named ``paged_attn*`` on device 0."""

import re

LAYER = "kernels"
MOVES = "itl_p95_ms"
KERNEL = re.compile(r"^paged_attn")


def read(run):
    rng = run.facts.get("traced_step_range")
    if run.trace is None or run.peaks is None or not rng:
        return None
    steps = run.facts["steps"][rng[0]:rng[1]]
    context = sum(s.context_tokens for s in steps if s.decode_only)
    if not context:
        return None
    seconds, count = run.reduce.matching_seconds(
        run.trace, lambda ev: bool(KERNEL.search(run.reduce.op_name(ev))))
    if count == 0:
        return None
    nbytes = run.flops.paged_kv_bytes(run.facts["model"], context)
    return run.flops.roofline_share(0.0, nbytes, seconds, run.peaks)
