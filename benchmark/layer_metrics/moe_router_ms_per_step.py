"""Device self time under the ``moe_router`` scope (``parallel/moe.
route_tokens``: the router's input upcast, its float32 matmul at highest
precision over ALL the router's experts, the sigmoid, the selection bias,
top-k and the renormalisation) per traced server step
(``trace/scopes.py``)."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ('moe_router',))
