"""Device self time under the ``conv_state`` scope (``inference/
engine_v2.py``: what a gated short-convolution layer does to its pool of
carried tails — the gather of each row's tail, a fresh row's reset, the
write-back; where PR 45 found a state pool copied whole) per traced server
step (``trace/scopes.py``). A program without the scope gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ("conv_state",)) or None
