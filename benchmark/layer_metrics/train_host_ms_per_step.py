"""Host time of a training step: the median ``train/step`` span (the whole
of ``train_batch``: batch fetch, stack and placement, the dispatch of the
fused step, bookkeeping). The trainer returns before the device ends, so
the span holds no device wait."""

from benchmark.trace import scopes

LAYER = "entry"
MOVES = "train_tokens_per_s_per_chip"


def read(run):
    return run.stats.median([
        e["dur"] / 1e3 for e in
        scopes.spans_named(scopes.program_events(run), "train/step")])
