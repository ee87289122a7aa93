"""The flash forward and the two backward kernels' share of their
roofline in training: the FLOPs they must compute for the traced steps
(``lib/flops.flash_train_flops_per_step``, compute-bound: forward 2 and
backward 4 matmuls over the visible pairs) over the bf16 peak, over the
summed device time of the events named ``flash_*`` on device 0."""

import re

LAYER = "kernels"
MOVES = "train_tokens_per_s_per_chip"
KERNEL = re.compile(r"^flash_(fwd|bwd)")


def read(run):
    steps = run.facts.get("traced_steps")
    if run.trace is None or run.peaks is None or not steps:
        return None
    win = run.reduce.traced_window(run.trace, run.span_name)
    seconds, count = run.reduce.matching_seconds(
        run.trace, lambda ev: bool(KERNEL.search(run.reduce.op_name(ev))),
        window=win)
    if count == 0:
        return None
    flops = run.facts["flash_flops_per_step_per_chip"] * steps
    return run.flops.roofline_share(flops, 0.0, seconds, run.peaks)
