"""Device self time under the ``moe_shared`` scope (``models/
typed_layers.typed_ffn``: the shared expert every token takes beside the
routed ones, a SiLU-GLU in the compute dtype) per traced server step
(``trace/scopes.py``). A program without the scope gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ('moe_shared',)) or None
