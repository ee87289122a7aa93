"""Model FLOP/s utilisation: tokens/s/chip times the FLOPs one token's
forward and backward need (``lib/flops.train_flops_per_token``: matmuls and
causal attention, no recompute) over the chip's bf16 peak. The rate is the
traced run's own, over its whole window."""

LAYER = "step programs"
MOVES = "train_tokens_per_s_per_chip"


def read(run):
    if run.peaks is None:
        return None
    f = run.facts
    return 100.0 * f["tokens_per_s_per_chip"] * f["flops_per_token"] / \
        run.peaks["bf16_flops_per_s"]
