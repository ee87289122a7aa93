"""Device self time under the ``loss`` scope (``chunked_cross_entropy``: the
head matmul a chunk at a time, forward, recomputed and backward) per traced
training step (``trace/scopes.py``)."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "train_tokens_per_s_per_chip"


def read(run):
    return scopes.scope_ms_per_step(run, ('loss',))
